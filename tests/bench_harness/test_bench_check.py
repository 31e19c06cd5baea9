"""The comparison that decides ``correct``: it passes for the program on the
CPU, fails for a perturbed answer, fails for the bfloat16 control, and a
run with the timed path broken underneath comes out not correct."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, program, reference, run, spec
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("wdm8-vtrs-shmoo", "wdm16-lta-mintr")
TRIALS = (12, 12)


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    return spec.load_cell(ROOT, request.param)


def _answers(cell, seed, n):
    gen = Traffic(cell.traffic, cell.config, TRIALS)
    cfg = program.build_config(cell.config)
    reqs = [gen.request(seed, i) for i in range(n)]
    answers = [program.readback(program.submit(
        program.make_request(cfg, gen.target, gen.metric, r)), gen.metric)
        for r in reqs]
    return gen, reqs, answers


def _limits(cell):
    return cell.workload["check"]["limits"]


def test_program_passes_the_comparison(cell):
    gen, reqs, answers = _answers(cell, 21, 3)
    ok, numbers = check.check(gen, 21, reqs, answers, _limits(cell), 8)
    assert ok, numbers
    assert set(numbers) == set(_limits(cell))


def test_perturbed_answer_fails_the_comparison(cell):
    gen, reqs, answers = _answers(cell, 22, 2)
    bad = []
    for a in answers:
        a = {k: np.array(v) for k, v in a.items()}
        if "alg_success" in a:
            a["alg_success"][..., :3] = ~a["alg_success"][..., :3]
        else:
            a["min_tr"] = a["min_tr"] * np.float32(1.001)
        bad.append(a)
    ok, numbers = check.check(gen, 22, reqs, bad, _limits(cell), 8)
    assert not ok, numbers
    missing = [None] + answers[1:]
    assert not check.check(gen, 22, reqs, missing, _limits(cell), 8)[0]


def test_bfloat16_control_fails_the_comparison(cell):
    """The control: the reference one precision down, in the program's place."""
    gen = Traffic(cell.traffic, cell.config, TRIALS)
    reqs = [gen.request(seed, 0) for seed in (31, 32)]
    pairs = [(check.reference_point(gen, r, p, reference.q_bf16),
              check.reference_point(gen, r, p))
             for r in reqs for p in range(r.n_points)]
    values = check.compare(pairs)
    assert any(values[k] > lim for k, lim in _limits(cell).items()), values


def test_control_readings_on_cpu(cell):
    out = control.readings(cell, 41, 2, trials=TRIALS)
    assert out["points"] >= 1
    assert all(out["program"][k] <= lim for k, lim in _limits(cell).items())


def _leave_out_half(submit):
    """Fault: half of the trials left out, the statistics taken over the rest."""
    def broken(sreq):
        u = sreq.units
        half = u.u_rlv.shape[0] // 2
        return submit(sreq.replace(units=u._replace(
            u_rlv=u.u_rlv[:half], u_fsr=u.u_fsr[:half], u_tr=u.u_tr[:half])))
    return broken


def _alter_answer(submit):
    """Fault: the answer altered where it is produced."""
    def broken(sreq):
        res = submit(sreq)
        data = res.data
        if hasattr(data, "_fields"):
            data = data._replace(alg_success=data.alg_success.at[..., 0].set(
                ~data.alg_success[..., 0]))
        else:
            data = data + jnp.float32(0.01)
        return res._replace(data=data)
    return broken


@pytest.mark.parametrize("fault", [_leave_out_half, _alter_answer])
def test_run_with_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    devices = jax.devices()   # the look for a chip is skipped: CPU devices
    good = run.run_cell(cell, 51, 0.5, False, devices, trials=TRIALS,
                        log=lambda *_: None)
    assert good["correct"], good["checks"]
    monkeypatch.setattr(program, "submit", fault(program.submit))
    bad = run.run_cell(cell, 51, 0.5, False, devices, trials=TRIALS,
                       log=lambda *_: None)
    assert not bad["correct"], bad["checks"]
    assert list(bad)[-1] == "checks"
