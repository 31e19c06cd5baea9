"""The request generator: the same seed gives the same requests, every seed
gets the same work, and both cells' requests run through the program on
the CPU at a tiny trial count."""
from pathlib import Path

import numpy as np
import pytest

from bench import program, spec
from bench.traffic import Traffic, draw_units, sample_points

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("wdm8-vtrs-shmoo", "wdm16-lta-mintr")


def _traffic(name, trials=(3, 4)):
    cell = spec.load_cell(ROOT, name)
    return cell, Traffic(cell.traffic, cell.config, trials)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3, -5])
def test_unit_draw_is_seeded_uniform_and_shaped(seed):
    a = draw_units(8, 5, 6, seed, 3)
    b = draw_units(8, 5, 6, seed, 3)
    c = draw_units(8, 5, 6, seed, 4)
    assert {k: v.shape for k, v in a.items()} == {
        "u_go": (5, 1), "u_llv": (5, 8), "u_rlv": (6, 8), "u_fsr": (6, 8),
        "u_tr": (6, 8)}
    for k in a:
        assert a[k].dtype == np.float32
        assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
        assert np.all((a[k] >= -1.0) & (a[k] <= 1.0))


def test_unit_draw_is_uniform_on_the_unit_interval():
    u = draw_units(16, 100, 100, 123, 0)["u_rlv"].ravel()
    assert abs(float(u.mean())) < 0.03
    assert float(u.var()) == pytest.approx(1 / 3, rel=0.05)


def test_shmoo_grid_is_the_papers_fig14():
    cell, gen = _traffic("wdm8-vtrs-shmoo")
    req = gen.request(1, 0)
    gs = cell.config["grid_spacing_nm"]
    np.testing.assert_allclose(req.axes["sigma_rlv"] / gs, [0.25, 0.5, 1, 2, 3, 4],
                               rtol=1e-6)
    tr = req.axes["tr_mean"]
    assert len(tr) == 12 and tr[0] == pytest.approx(0.25 * gs)
    assert tr[-1] == pytest.approx(cell.config["fsr_nm"])
    assert req.n_points == 72 and req.trial_evaluations == 72 * 12


def test_deck_gives_every_seed_the_same_work_in_another_order():
    cell, gen = _traffic("wdm16-lta-mintr")
    gs = cell.config["grid_spacing_nm"]
    for seed in (3, 4):
        values = [float(gen.request(seed, i).axes["sigma_rlv"][0]) / gs
                  for i in range(16)]
        for block in (values[:8], values[8:]):
            assert sorted(np.round(block, 4)) == [0.25, 0.5, 1, 2, 3, 4, 6, 8]
    first = [float(gen.request(3, i).axes["sigma_rlv"][0]) for i in range(8)]
    other = [float(gen.request(4, i).axes["sigma_rlv"][0]) for i in range(8)]
    assert first != other


def test_sample_is_drawn_from_the_seed():
    _, gen = _traffic("wdm8-vtrs-shmoo")
    reqs = [gen.request(5, i) for i in range(3)]
    a = sample_points(5, reqs, 8)
    assert a == sample_points(5, reqs, 8) and a != sample_points(6, reqs, 8)
    assert len(set(a)) == 8 and all(0 <= p < 72 for _, p in a)
    assert sample_points(5, reqs[:1], 100) == [(0, p) for p in range(72)]


@pytest.mark.parametrize("name", CELLS)
def test_requests_run_through_the_program_on_cpu(name):
    cell, gen = _traffic(name, trials=(3, 4))
    cfg = program.build_config(cell.config)
    assert cfg.grid.n_ch == cell.config["n_ch"]
    assert cfg.grid.fsr == pytest.approx(cell.config["fsr_nm"])
    req = gen.request(9, 0)
    result = program.submit(program.make_request(cfg, gen.target, gen.metric, req))
    program.wait(result)
    answer = program.readback(result, gen.metric)
    shape = tuple(len(v) for v in req.axes.values())
    if "scheme" in gen.target:
        assert answer["cafp"].shape == shape
        assert answer["alg_success"].shape == shape + (12,)
    else:
        assert answer["min_tr"].shape == shape
        assert np.all(answer["min_tr"] > 0)
