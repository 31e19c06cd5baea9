"""Stage names on the device ops and sweep spans on the host timeline.

The engine's stages (``repro.obs.phase.stage``) must reach the compiled
program's ``op_name`` metadata on every path a benchmark runs, and the
frontend's steps (``sweep.*`` spans) must reach a profiler trace, with and
without an installed recorder.
"""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs.wdm import WDM8_G200, WDM16_G200
from repro.core import SweepRequest, make_units, sweep
from repro.core.sweep import lower_sweep
from repro.obs import STAGES, PhaseRecorder, span, stage, use_recorder
from repro.obs.phase import STAGE_PREFIX, compile_count

SPANS = ("sweep.validate", "sweep.plan", "sweep.dispatch", "sweep.shape")
PLAN_ARGS = {"points", "chunk", "n_chunks", "per_point_bytes", "metric",
             "table_route", "match_route"}


def _lta_min_tr(backend=None):
    units = make_units(WDM16_G200, seed=2, n_laser=3, n_ring=4)
    return SweepRequest(cfg=WDM16_G200, units=units, policy="lta",
                        metric="min_tr", backend=backend,
                        axes={"sigma_rlv": np.array([1.0, 2.0], np.float32)})


def _vtrs_shmoo():
    units = make_units(WDM8_G200, seed=3, n_laser=3, n_ring=4)
    return SweepRequest(cfg=WDM8_G200, units=units, scheme="vtrs_ssm",
                        axes={"sigma_rlv": np.array([1.0, 2.0], np.float32),
                              "tr_mean": np.array([3.0, 6.0], np.float32)})


def _stages_in(hlo_text: str) -> set:
    """Innermost stage of each op's ``op_name``."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        names = re.findall(re.escape(STAGE_PREFIX) + r"([a-z_]+)", op_name)
        if names:
            found.add(names[-1])
    return found


@pytest.mark.parametrize("make, expected", [
    (_vtrs_shmoo, {"chunk_loop", "instantiate", "ideal", "table_build",
                   "arbitrate", "classify"}),
    (_lta_min_tr, {"chunk_loop", "instantiate", "ideal", "matching",
                   "classify"}),
    (lambda: _lta_min_tr(backend="jnp"),
     {"chunk_loop", "instantiate", "ideal", "matching", "classify"}),
], ids=["vtrs_ssm", "lta_min_tr", "lta_min_tr_kernel_ops"])
def test_compiled_sweep_names_each_stage_it_runs(make, expected):
    text = lower_sweep(make()).compile().as_text()
    assert _stages_in(text) >= expected
    assert _stages_in(text) <= set(STAGES)


def test_stage_vocabulary_is_fixed():
    assert all(re.fullmatch(r"[a-z_]+", s) for s in STAGES)
    with pytest.raises(ValueError, match="unknown stage"):
        stage("not_a_stage")


def test_compile_cache_key_keeps_op_metadata():
    # Without it a cache entry of another tree serves its own op names.
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, dict(e.stats), e.start_ns, e.end_ns)
                           for e in line.events if e.name.startswith("sweep.")]
    return sorted(events, key=lambda e: e[2])


def test_sweep_spans_reach_the_profiler_trace(tmp_path):
    jax.block_until_ready(sweep(_lta_min_tr()).data)   # compiled outside
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(sweep(_lta_min_tr()).data)
    events = _host_events(tmp_path)
    assert [e[0] for e in events] == list(SPANS)
    by = {name: stats for name, stats, _, _ in events}
    plan = by["sweep.plan"]
    assert PLAN_ARGS <= set(plan)
    assert (plan["points"], plan["chunk"], plan["n_chunks"]) == (2, 2, 1)
    assert plan["per_point_bytes"] > 0
    assert plan["table_route"] == "none"        # a policy builds no table
    assert plan["match_route"] == "core"        # LtA off a TPU
    assert by["sweep.dispatch"]["compiles"] == 0
    assert by["sweep.shape"]["compiles"] == 0
    # one after another on the host: plan, then dispatch, then shape
    for (_, _, _, end), (_, _, start, _) in zip(events[1:], events[2:]):
        assert end <= start


def test_recorder_records_the_same_spans_and_plan_note():
    rec = PhaseRecorder()
    with use_recorder(rec):
        req = _lta_min_tr()
        plain = sweep(req)
    names = [s.name for s in rec.spans]
    for name in SPANS + ("sweep",):
        assert name in names
    plan_span = next(s for s in rec.spans if s.name == "sweep.plan")
    plan_note = next(n for n in rec.notes if n["name"] == "sweep.plan")
    assert PLAN_ARGS <= set(plan_span.extra)
    assert {k: plan_note[k] for k in plan_span.extra} == plan_span.extra
    assert {"link_chunk", "budget", "target"} <= set(plan_note)
    dispatch = next(s for s in rec.spans if s.name == "sweep.dispatch")
    assert dispatch.extra["compiles"] >= 0
    assert np.array_equal(np.asarray(plain.data), np.asarray(sweep(req).data))


@pytest.mark.parametrize("backend, route", [(None, "core"), ("jnp", "jnp")])
def test_plan_names_the_table_builder(backend, route):
    """A scheme sweep's ``sweep.plan`` carries the builder that its traced
    program notes (``kernels.table_route``); on the CPU ``None`` is core."""
    jax.clear_caches()  # the note is made while tracing
    rec = PhaseRecorder()
    with use_recorder(rec):
        sweep(_vtrs_shmoo().replace(backend=backend))
    plan = next(s for s in rec.spans if s.name == "sweep.plan")
    assert plan.extra["table_route"] == route
    routes = {(n["route"], n["n"]) for n in rec.notes
              if n["name"] == "kernels.table_route"}
    assert routes == {(route, WDM8_G200.grid.n_ch)}
    # vtrs_ssm is scored against LtC: no LtA matching runs
    assert plan.extra["match_route"] == "none"
    assert not any(n["name"] == "kernels.match_route" for n in rec.notes)


@pytest.mark.parametrize("backend, route", [(None, "core"), ("jnp", "jnp")])
def test_plan_names_the_lta_matching(backend, route):
    """An LtA sweep's ``sweep.plan`` carries the matching that its traced
    program notes (``kernels.match_route``); on the CPU ``None`` is core."""
    jax.clear_caches()  # the note is made while tracing
    rec = PhaseRecorder()
    with use_recorder(rec):
        sweep(_lta_min_tr(backend=backend))
    plan = next(s for s in rec.spans if s.name == "sweep.plan")
    assert plan.extra["match_route"] == route
    routes = {(n["route"], n["n"]) for n in rec.notes
              if n["name"] == "kernels.match_route"}
    assert routes == {(route, WDM16_G200.grid.n_ch)}


def _plan_case(case):
    from repro.configs.fabric import FABRIC_TINY
    from repro.core.temporal import make_timeline
    from repro.fabric import make_fabric_units

    cfg = WDM16_G200
    sigma = {"sigma_rlv": np.array([1.0], np.float32)}
    if case == "fabric":
        return SweepRequest(cfg=cfg, scheme="protocol_lta", fabric=FABRIC_TINY,
                            units=make_fabric_units(cfg, FABRIC_TINY, seed=3),
                            axes=sigma)
    units = make_units(cfg, seed=2, n_laser=16, n_ring=8 if case != "few" else 4)
    if case == "timeline":
        return SweepRequest(cfg=cfg, units=units, scheme="protocol_lta",
                            axes=sigma, fixed={"tr_mean": 5.0},
                            timeline=make_timeline(2, cfg.grid.n_ch))
    return SweepRequest(cfg=cfg, units=units, policy="lta", metric="min_tr",
                        axes=sigma)


@pytest.mark.parametrize("case, route", [("block", "pallas"), ("few", "core"),
                                         ("fabric", "core"),
                                         ("timeline", "none")])
def test_plan_match_route_on_a_tpu(case, route, monkeypatch):
    """On a TPU the plan names the Pallas matching only where the program's
    ideal scoring batches a 128-trial block or more at 10 < N <= 32: not
    for 64 trials, nor for a fabric link's two endpoints; a temporal sweep
    of one link does no ideal scoring."""
    from repro.core.sweep import _plan_match_route
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert _plan_match_route(_plan_case(case)) == route


def test_span_counts_the_compiles_inside_it():
    f = jax.jit(lambda x: x * 3 + 1)
    x = np.arange(5, dtype=np.float32)
    before = compile_count()
    rec = PhaseRecorder()
    with use_recorder(rec):
        with span("first") as first:
            f(x)
        with span("second", tag="kept") as second:
            f(x)
    assert first["compiles"] == 1 and compile_count() == before + 1
    assert second == {"tag": "kept", "compiles": 0}
    assert [s.extra for s in rec.spans] == [first, second]
    # with nobody looking (no recorder, no profiler) a span only yields
    with span("quiet", tag=1) as quiet:
        f(x)
    assert quiet == {"tag": 1}
