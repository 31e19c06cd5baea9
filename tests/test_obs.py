"""Observability-layer invariants (repro.obs + the instrumented call sites).

The obs layer is only admissible if it is *free* when off and *inert* when
on:

  * trace-off bit-parity: ``run_protocol(trace=None)`` is bit-identical to
    the uninstrumented engine on every CPU-reachable backend (the disabled
    branch is Python-static, so the jaxpr itself is unchanged);
  * trace-on outcome invariance: enabling the flight recorder never changes
    assignments, lock state, or probe accounting — it only *adds* a
    ``TraceBuffer`` return;
  * ring-buffer honesty: per-kind ``counts`` are wraparound-immune
    (``counts.sum(axis=1) == n`` even when ``n > cap``), decoded events are
    chronological and drawn from the closed event vocabulary;
  * taxonomy closure: every classified trial gets a code from ``TAXONOMY``
    and the ``unknown`` bucket stays empty on the fig19 residual setup;
  * recorder transparency: a ``PhaseRecorder`` around ``sweep`` changes no
    grid value while capturing spans, chunk plans, and (under
    ``measure_memory``) compiled-memory watermarks vs the 256 MB budget;
  * health-matrix consistency: ``run_fabric_timeline(health=True)`` changes
    no chaos stat and its codes agree with the stats they summarize;
  * manifest round-trip: whatever the instruments record renders back
    through ``repro.obs.report``.
"""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs.fabric import FABRIC_TINY
from repro.configs.wdm import WDM16_G200, drift_timeline
from repro.core import (
    ArbitrationConfig,
    DWDMGrid,
    SweepRequest,
    make_units,
    run_timeline,
    slice_timeline,
    sweep,
)
from repro.core.protocol import default_rounds, run_protocol
from repro.core.relation import chain_spec
from repro.core.sampling import instantiate
from repro.core.search_table import build_search_tables
from repro.fabric import make_fabric_timeline, run_fabric_timeline
from repro.fabric.sampling import make_fabric_units
from repro.obs import (
    EVENT_KINDS,
    HEALTH_CODES,
    PhaseRecorder,
    current_recorder,
    format_events,
    health_matrix_summary,
    measured_call,
    note,
    span,
    trace_buffer,
    trace_append,
    trace_events,
    trace_summary,
    use_recorder,
)
from repro.obs.manifest import RunManifest, latest_manifest, read_manifest
from repro.obs.report import render_report
from repro.obs.taxonomy import TAXONOMY, classify_trials, explain_residuals

CFG = ArbitrationConfig(grid=DWDMGrid(n_ch=8))
BACKENDS = (None, "jnp", "interpret")


@pytest.fixture(scope="module")
def tables():
    units = make_units(CFG, seed=3, n_laser=3, n_ring=4)
    sys_b = instantiate(CFG, units)
    return build_search_tables(sys_b, 3.0, max_alias=CFG.max_fsr_alias)


def _arrays(pytree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(pytree)]


# ---------------------------------------------------------------------------
# flight recorder: parity + ring semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_trace_on_changes_no_outcome(tables, backend):
    """trace= only *adds* a buffer: every other output is bit-identical,
    on every CPU-reachable backend column."""
    spec = chain_spec(CFG.s)
    kw = dict(with_stats=True, with_state=True, backend=backend)
    assign0, stats0, state0 = run_protocol(tables, spec, **kw)
    assign1, stats1, state1, buf = run_protocol(tables, spec, trace=32, **kw)
    for a, b in zip(_arrays((assign0, stats0, state0)),
                    _arrays((assign1, stats1, state1))):
        assert np.array_equal(a, b)
    # and the backend column itself changes nothing vs the default path
    if backend is not None:
        base = run_protocol(tables, spec, with_stats=True, with_state=True)
        for a, b in zip(_arrays(base), _arrays((assign0, stats0, state0))):
            assert np.array_equal(a, b)
    assert np.asarray(buf.n).sum() > 0  # the engine did record something


def test_trace_counts_are_wrap_immune(tables):
    """Per-kind counts survive ring overflow; decode is chronological and
    stays inside the closed event vocabulary."""
    spec = chain_spec(CFG.s)
    out_small = run_protocol(tables, spec, trace=4)   # tiny cap: overflows
    out_large = run_protocol(tables, spec, trace=256)  # effectively unbounded
    buf_s, buf_l = out_small[-1], out_large[-1]
    # counts/n are fired-event totals, independent of capacity
    assert np.array_equal(np.asarray(buf_s.n), np.asarray(buf_l.n))
    assert np.array_equal(np.asarray(buf_s.counts), np.asarray(buf_l.counts))
    assert np.array_equal(np.asarray(buf_l.counts).sum(axis=1),
                          np.asarray(buf_l.n))
    summ = trace_summary(buf_s)
    assert summ["events_total"] == int(np.asarray(buf_l.n).sum())
    assert summ["overflowed_trials"] == int((np.asarray(buf_s.n) > 4).sum())
    for ev in trace_events(buf_l):
        if not len(ev):
            continue
        assert ev.shape[1] == 4
        assert np.all((ev[:, 2] >= 0) & (ev[:, 2] < len(EVENT_KINDS)))
        assert np.all(np.diff(ev[:, 0]) >= 0)  # rounds never go backwards
        assert isinstance(format_events(ev, limit=5), str)
    # the overflowed ring keeps the *newest* cap events
    n = np.asarray(buf_l.n)
    trial = int(np.argmax(n))
    if n[trial] > 4:
        tail = trace_events(buf_l, trial)[-4:]
        assert np.array_equal(trace_events(buf_s, trial), tail)


def test_taxonomy_closed_on_fig19_residuals():
    """The acceptance gate at test scale: every WDM16 trial where seq_retry
    fails against a feasible ideal gets a non-unknown code."""
    cfg = WDM16_G200
    units = make_units(cfg, seed=21, n_laser=5, n_ring=5)
    trs = np.linspace(0.25 * cfg.grid.grid_spacing,
                      cfg.grid.n_ch * cfg.grid.grid_spacing, 12,
                      dtype=np.float32)[::4]
    tax = explain_residuals(cfg, units, trs, scheme="seq_retry", depth=1,
                            trace_cap=64)
    assert tax["unknown"] == 0
    assert "unknown" not in tax["histogram"]
    assert tax["residual_total"] > 0  # mid-TR seq_retry does fail here
    assert tax["residual_total"] == sum(tax["histogram"].values())
    for p in tax["points"]:
        assert all(0 <= c < len(TAXONOMY) for c in p["codes"])
        assert len(p["codes"]) == p["residual_trials"]


def test_classify_trials_locked_and_hopeless(tables):
    """Degenerate corners of the classifier: a fully locked trial is
    ST_LOCKED; an infeasible one is hopeless regardless of activity."""
    spec = chain_spec(CFG.s)
    _, stats, state, buf = run_protocol(
        tables, spec, with_stats=True, with_state=True, trace=64
    )
    t = state.lock.shape[0]
    rounds = default_rounds(CFG.grid.n_ch)
    complete = np.asarray((state.lock >= 0).all(axis=1))
    codes = np.asarray(classify_trials(
        state.lock, tables.n_valid, buf.counts, stats.worked, rounds=rounds
    ))
    assert codes.shape == (t,)
    assert np.all((codes >= 0) & (codes < len(TAXONOMY)))
    assert np.all((codes == TAXONOMY.index("locked")) == complete)
    # feasible=False forces every incomplete trial to "hopeless"
    codes_h = np.asarray(classify_trials(
        state.lock, tables.n_valid, buf.counts, stats.worked, rounds=rounds,
        feasible=jnp.zeros((t,), bool),
    ))
    assert np.all(codes_h[~complete] == TAXONOMY.index("hopeless"))


# ---------------------------------------------------------------------------
# phase telemetry: recorder transparency
# ---------------------------------------------------------------------------

def test_recorder_leaves_sweep_grid_bit_identical():
    units = make_units(CFG, seed=5, n_laser=3, n_ring=4)
    req = dict(cfg=CFG, units=units, scheme="seq_retry",
               axes={"tr_mean": np.linspace(1.5, 5.5, 3, dtype=np.float32)})
    bare = sweep(SweepRequest(**req))
    rec = PhaseRecorder(measure_memory=True)
    with use_recorder(rec):
        recd = sweep(SweepRequest(**req))
    assert current_recorder() is None  # context restored
    for a, b in zip(_arrays(bare.data), _arrays(recd.data)):
        assert np.array_equal(a, b)
    fields = rec.phase_fields()
    assert any(k.startswith("sweep") for k in fields)
    assert all(f["ms"] >= 0 for f in fields.values())
    # chunk plan + compiled-memory watermark landed as notes
    names = [n["name"] for n in rec.notes]
    assert "sweep.plan" in names
    mem = rec.memory_fields()
    assert any(n["name"].startswith("memory.sweep") for n in mem)
    wm = next(n for n in mem if "temp" in n["name"])
    assert 0 < wm["bytes"] and 0 < wm["frac"] < 1


def test_phase_helpers_are_noops_without_recorder(tables):
    """Module-level span()/note()/measured_call() cost nothing and change
    nothing when no recorder is installed — the default state everywhere."""
    assert current_recorder() is None
    with span("never-recorded", kind="host"):
        note("never.recorded", x=1)
    spec = chain_spec(CFG.s)
    plain = run_protocol(tables, spec)
    via = measured_call("p", run_protocol, (tables, spec), {},
                        dynamic_args=(tables,))
    assert np.array_equal(np.asarray(plain), np.asarray(via))


def test_measured_call_propagates_compile_errors():
    """A program the backend refuses is a failure, not a plain re-run: a
    compiled (non-interpret) Pallas call cannot lower on CPU."""
    import jax
    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    fn = jax.jit(lambda x: pl.pallas_call(
        copy, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
    x = jnp.zeros((8, 128), jnp.float32)
    rec = PhaseRecorder(measure_memory=True)
    with use_recorder(rec), pytest.raises(Exception):
        measured_call("p", fn, (x,), {}, dynamic_args=(x,))
    assert not any("aot_fallback" in n["name"] for n in rec.notes)


def test_recorder_span_nesting_and_current_path():
    rec = PhaseRecorder()
    with use_recorder(rec):
        with rec.span("outer"):
            with rec.span("inner", kind="compile"):
                assert rec.current_path() == "outer/inner"
        assert rec.current_path() is None
    by = rec.phase_fields()
    assert by["outer"]["count"] == 1 and by["inner"]["kind"] == "compile"


# ---------------------------------------------------------------------------
# chaos health matrix
# ---------------------------------------------------------------------------

def test_fabric_health_matrix_parity_and_consistency():
    spec = FABRIC_TINY
    n = CFG.grid.n_ch
    units = make_fabric_units(CFG, spec, 0)
    tl = make_fabric_timeline(spec, 3, n, thermal=0.15,
                              events=[(1, "link_kill", 0)])
    _, plain = run_fabric_timeline(CFG, units, spec, tl)
    _, obs = run_fabric_timeline(CFG, units, spec, tl, health=True)
    assert plain.health is None
    for a, b in zip(_arrays(plain._replace(health=None)),
                    _arrays(obs._replace(health=None))):
        assert np.array_equal(a, b)
    health = np.asarray(obs.health)
    assert health.shape == (3, spec.n_links) and health.dtype == np.int8
    assert np.all((health >= 0) & (health < len(HEALTH_CODES)))
    # the killed link reads "down" exactly while link_alive says so
    alive = np.asarray(tl.link_alive, bool)
    assert np.array_equal(health == 0, ~alive)
    summ = health_matrix_summary(obs.health)
    assert summ["steps"] == 3 and summ["links"] == spec.n_links
    assert summ["by_code"].get("down", 0) == int((~alive).sum())
    assert 0.0 <= summ["healthy_frac"] <= 1.0


# ---------------------------------------------------------------------------
# temporal: traced re-lock scans
# ---------------------------------------------------------------------------

def test_run_timeline_trace_parity_and_stacking():
    tcfg, tl = drift_timeline("wdm16-hotswap")
    tl = slice_timeline(tl, 0, 3)
    units = make_units(tcfg, seed=1, n_laser=4, n_ring=4)
    var = {"tr_mean": 4.0 * tcfg.grid.grid_spacing}
    final0, stats0 = run_timeline(tcfg, units, tl, var)
    final1, stats1, bufs = run_timeline(tcfg, units, tl, var, trace=16)
    for a, b in zip(_arrays((final0, stats0)), _arrays((final1, stats1))):
        assert np.array_equal(a, b)
    # lax.scan stacks one TraceBuffer per step
    assert bufs.ev.shape[0] == 3 and bufs.ev.shape[2] == 16
    assert np.array_equal(np.asarray(bufs.counts).sum(axis=-1),
                          np.asarray(bufs.n))


def test_run_timeline_trace_rejects_one_shot_schemes():
    tcfg, tl = drift_timeline("wdm16-hotswap")
    tl = slice_timeline(tl, 0, 2)
    units = make_units(tcfg, seed=1, n_laser=3, n_ring=3)
    with pytest.raises(ValueError, match="one-shot"):
        run_timeline(tcfg, units, tl, {"tr_mean": 5.0}, scheme="vtrs_ssm",
                     trace=8)


# ---------------------------------------------------------------------------
# manifest + report round-trip
# ---------------------------------------------------------------------------

def test_manifest_report_roundtrip(tmp_path):
    buf = trace_buffer(2, 4)
    fire = jnp.array([True, False])
    buf = trace_append(buf, fire, 0, 1, 0, 3)       # probe on trial 0
    buf = trace_append(buf, ~fire, 1, 2, 1, 5)      # lock on trial 1
    rec = PhaseRecorder()
    with rec.span("demo", kind="execute"):
        pass
    rec.memory("demo.temp", 64 << 20, 256 << 20)
    health = jnp.array([[4, 0], [2, 3]], jnp.int8)

    man = RunManifest.create(str(tmp_path), label="t", answer=42)
    with man:
        man.record_phases(rec, scope="ph")
        man.record_trace(buf, scope="tr",
                         taxonomy={"histogram": {"starvation": 1},
                                   "unknown": 0})
        man.record_health(health, scope="he")
        man.record_bench({"figure": "f", "name": "f/x", "module_wall_ms": 1.0,
                          "derived": {"v": 1}})

    assert latest_manifest(str(tmp_path)) == man.path
    lines = list(read_manifest(man.path))
    kinds = [l["kind"] for l in lines]
    for k in ("meta", "phases", "trace", "health", "bench_record"):
        assert k in kinds
    assert lines[0]["answer"] == 42
    # every line is plain JSON (numpy scrubbed)
    for line in lines:
        json.dumps(line)

    report = render_report(man.path)
    for section in ("phases [ph]", "trace [tr]", "health [he]",
                    "bench trajectory"):
        assert section in report
    assert "starvation" in report and "25.0%" in report  # 64/256 MiB note
    # corrupt trailing line is skipped, not fatal
    with open(man.path, "a") as fh:
        fh.write("{not json\n")
    assert len(list(read_manifest(man.path))) == len(lines)
