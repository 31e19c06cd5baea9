"""Each traffic file names its plain reference, ``bench/references/<name>.py``,
which ``bench/spec.load_cell`` loads by that name: the references give bit
for bit the answers that the dispatch by scheme and metric gave, a cell
with a new scheme is new files and entries only, and no number that a
reference gives goes without a limit."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import check, reference, spec
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
GOLDEN = json.loads((Path(__file__).parent / "data" / "reference_answers.json").read_text())
Q = {"f32": reference.q_f32, "bf16": reference.q_bf16}


def _bits(a: np.ndarray) -> str:
    return "".join("1" if b else "0" for b in a.ravel())


@pytest.mark.parametrize("name", sorted(GOLDEN["cells"]))
def test_lookup_gives_the_recorded_answers_bit_for_bit(name):
    cell = spec.load_cell(ROOT, name)
    gen = Traffic(cell.traffic, cell.config, tuple(GOLDEN["trials"]), cell.reference)
    rows = GOLDEN["cells"][name]
    assert len({r["seed"] for r in rows}) >= 3 and {r["q"] for r in rows} == set(Q)
    for row in rows:
        req = gen.request(row["seed"], row["request"])
        got = check.reference_point(gen, req, row["point"], Q[row["q"]])
        want = row["answer"]
        assert list(got) == list(want), row
        for key, w in want.items():
            g = got[key]
            if isinstance(w, str):   # per-trial outcomes
                assert isinstance(g, np.ndarray) and g.dtype == bool, (row, key)
                assert _bits(g) == w, (row, key)
            else:
                assert type(g) is float and g == w, (row, key, g)


@pytest.mark.parametrize("name, target, metric", [
    ("oblivious", {"scheme": "seq_retry"}, "eval"),
    ("oblivious", {"scheme": "vtrs_ssm"}, "min_tr"),
    ("oblivious", {"policy": "lta"}, "eval"),
    ("lta_min_tr", {"policy": "ltc"}, "min_tr"),
    ("lta_min_tr", {"policy": "lta"}, "eval"),
    ("lta_min_tr", {"scheme": "vtrs_ssm"}, "min_tr"),
])
def test_a_reference_refuses_a_target_it_does_not_know(name, target, metric):
    cell = spec.load_cell(ROOT, "wdm8-vtrs-shmoo")
    gen = Traffic(cell.traffic, cell.config, (2, 2))
    req = gen.request(1, 0)
    with pytest.raises(ValueError):
        spec.reference_answer(name)(cell.config, target, metric, req.units, req.point(0))


def _tree(tmp_path):
    """A copy of the benchmark's files to add to; returns (root, bench_dir)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path, bench_dir


def test_a_traffic_without_a_known_reference_is_refused(tmp_path):
    root, bench_dir = _tree(tmp_path)
    path = bench_dir / "traffic" / "vtrs-shmoo.json"
    traffic = json.loads(path.read_text())
    path.write_text(json.dumps({**traffic, "reference": "no_such"}))
    with pytest.raises(FileNotFoundError):
        spec.load_cell(root, "wdm8-vtrs-shmoo", bench_dir=bench_dir)
    del traffic["reference"]
    path.write_text(json.dumps(traffic))
    with pytest.raises(KeyError, match="names no reference"):
        spec.load_cell(root, "wdm8-vtrs-shmoo", bench_dir=bench_dir)


def test_a_compared_number_without_a_limit_is_refused():
    cell = spec.load_cell(ROOT, "wdm16-lta-mintr")

    def answer(*args):
        return {**cell.reference(*args), "min_tr_at_half": 1.0}

    gen = Traffic(cell.traffic, cell.config, (2, 2), answer)
    requests = [gen.request(3, 0)]
    answers = [{"min_tr": np.ones(1, np.float32), "min_tr_at_half": np.ones(1, np.float32)}]
    with pytest.raises(ValueError, match="min_tr_at_half_rel_gap"):
        check.check(gen, 3, requests, answers, cell.workload["check"]["limits"], 1)
    trial = {"alg_success": np.ones(4, bool), "ideal_ok": np.ones(4, bool),
             "afp": 0.0, "cafp": 0.0, "lock_err": 0.0, "order_err": 0.0}
    with pytest.raises(ValueError, match="rounds"):
        check.compare([(trial, {**trial, "rounds": 3.0})])


TOY_REFERENCE = '''"""Toy scheme: ring i always locks line i, so every trial succeeds."""
import numpy as np


def answer(cfg, target, metric, units, point, q=None):
    if target != {"scheme": "diagonal"} or metric != "eval":
        raise ValueError(f"no diagonal reference for {target} with {metric!r}")
    ok = np.ones(units["u_go"].shape[0] * units["u_rlv"].shape[0], bool)
    return {"afp": 0.0, "cafp": 0.0, "lock_err": 0.0, "order_err": 0.0,
            "alg_success": ok, "ideal_ok": ok}
'''


def test_a_cell_with_a_new_scheme_is_new_files_only(tmp_path):
    tmp_path, bench_dir = _tree(tmp_path)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    old = json.loads((tmp_path / "BENCHMARK.json").read_text())

    cfg = json.loads((bench_dir / "configs" / "wdm8-g200.json").read_text())
    cfg.update(name="wdm4-g200", n_ch=4, fsr_nm=4.48, tr_mean_nm=4.48,
               ring_order=[0, 1, 2, 3], target_order=[0, 1, 2, 3])
    (bench_dir / "configs" / "wdm4-g200.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "diagonal-pair.json").write_text(json.dumps({
        "target": {"scheme": "diagonal"}, "metric": "eval", "reference": "diagonal",
        "axes": {"tr_mean": {"unit": "fsr", "values": [0.4, 0.6]}},
        "trials": [2, 3]}))
    (bench_dir / "workloads" / "wdm4-diagonal.json").write_text(
        json.dumps({"check": {"sample": 4, "limits": {"outcome_mismatch": 0.0}}}))
    (bench_dir / "references" / "diagonal.py").write_text(TOY_REFERENCE)
    new = json.loads(json.dumps(old))
    new["configs"].append({"name": "wdm4-g200", "source": "a public source",
                           "file": "bench/configs/wdm4-g200.json", "reduced": [],
                           "why": "a new deployment"})
    new["workloads"].append({"name": "wdm4-diagonal", "config": "wdm4-g200",
                             "traffic": "diagonal-pair", "chips": 1,
                             "why": "a new scheme"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    cell = spec.load_cell(tmp_path, "wdm4-diagonal", bench_dir=bench_dir)
    gen = Traffic(cell.traffic, cell.config, answer=cell.reference)
    requests = [gen.request(5, i) for i in range(3)]
    grid = (2,)

    def answer(flip: bool = False):
        ok = np.ones(grid + (6,), bool)
        ok[0, 0] ^= flip
        return {"afp": np.zeros(grid), "cafp": np.zeros(grid),
                "lock_err": np.zeros(grid), "order_err": np.zeros(grid),
                "alg_success": ok, "ideal_ok": np.ones(grid + (6,), bool)}

    limits = cell.workload["check"]["limits"]
    correct, numbers = check.check(gen, 5, requests, [answer() for _ in requests],
                                   limits, 4)
    assert correct and numbers["outcome_mismatch"]["value"] == 0.0, numbers
    bad = [answer(flip=True) for _ in requests]
    correct, numbers = check.check(gen, 5, requests, bad, limits, 6)
    assert not correct and numbers["outcome_mismatch"]["value"] > 0, numbers
    assert {p: p.read_bytes() for p in before} == before
    for key, value in old.items():   # BENCHMARK.json: entries appended, none changed
        kept = new[key][:len(value)] if isinstance(value, list) else new[key]
        assert kept == value, key
