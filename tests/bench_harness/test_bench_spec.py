"""BENCHMARK.json keeps to the benchmark file's format, and every name in it
resolves to a file of its own, so a new cell is new files plus entries."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import spec
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\n\t]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(TEXT.match(w) for w in cmd)
    for word in cmd:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"]), word
            assert (ROOT / word).is_file()


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and TEXT.match(w["why"])
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        kind_names = [e["name"] for e in BENCH[kind]]
        assert len(kind_names) == len(set(kind_names))


def test_metric_entries_are_well_formed():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert TEXT.match(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = spec.load_cell(ROOT, name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    check = cell.workload["check"]
    assert int(check["sample"]) >= 1 and check["limits"]
    traffic = Traffic(cell.traffic, cell.config)
    assert traffic.n_laser * traffic.n_ring == 10_000   # the paper's 100 x 100


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_configuration_as_run(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(entry["file"]) == 1
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    assert cfg["fsr_nm"] == pytest.approx(cfg["n_ch"] * cfg["grid_spacing_nm"])
    assert cfg["ring_bias_nm"] == pytest.approx(4 * cfg["grid_spacing_nm"])


def test_adding_a_cell_needs_only_new_files_and_entries(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    new = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((bench_dir / "configs" / "wdm8-g200.json").read_text())
    cfg.update(name="wdm8-g400", grid_ghz=400, grid_spacing_nm=2.24,
               ring_bias_nm=8.96, fsr_nm=17.92, tr_mean_nm=17.92)
    (bench_dir / "configs" / "wdm8-g400.json").write_text(json.dumps(cfg))
    traffic = {"target": {"scheme": "rs_ssm"}, "metric": "eval",
               "axes": {"tr_mean": {"unit": "fsr", "values": [0.4, 0.6]}},
               "deck": {"sigma_rlv": {"unit": "grid_spacing", "values": [1, 2]}},
               "trials": [3, 4]}
    (bench_dir / "traffic" / "rs-pair.json").write_text(json.dumps(traffic))
    (bench_dir / "workloads" / "wdm8g400-rs-pair.json").write_text(
        json.dumps({"check": {"sample": 2, "limits": {"outcome_mismatch": 0.0}}}))
    (bench_dir / "metrics" / "requests_per_s.py").write_text(
        "def read(run):\n    return len(run.requests) / run.window_s\n")
    new["configs"].append({"name": "wdm8-g400", "source": "a public source",
                           "file": "bench/configs/wdm8-g400.json", "reduced": [],
                           "why": "a new deployment"})
    new["workloads"].append({"name": "wdm8g400-rs-pair", "config": "wdm8-g400",
                             "traffic": "rs-pair", "chips": 1, "why": "a new cell"})
    new["end_to_end"].append({"name": "requests_per_s", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["wdm8g400-rs-pair"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))

    cell = spec.load_cell(tmp_path, "wdm8g400-rs-pair", bench_dir=bench_dir)
    assert cell.config["grid_spacing_nm"] == 2.24
    assert [m["name"] for m in cell.end_to_end] == ["trials_per_s", "setup_s",
                                                    "requests_per_s"]
    read = spec.metric_reader("requests_per_s", bench_dir=bench_dir)
    assert read(type("Run", (), {"requests": [1, 2], "window_s": 4.0})) == 0.5
    gen = Traffic(cell.traffic, cell.config)
    req = gen.request(7, 1)
    assert req.n_points == 2 and req.n_trials == 12
    assert req.units["u_rlv"].shape == (4, 8)
    unchanged = {p: p.read_bytes() for p in before}
    assert unchanged == before
