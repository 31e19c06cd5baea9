"""The stage and frontend-span reduction (``bench/stages.py``) and its metric
readers: exact on hand-made traces, innermost attribution against brute
force, and on the trimmed chip traces of ``data/``
(``chip_trace_stages.pbtxt``: two ``wdm16-lta-mintr`` requests recorded on
a TPU v5e with stage names and ``sweep.*`` spans, ops to depth 1).  The
numbers ``bench/trace.py`` gives stay as they were."""
from pathlib import Path

import numpy as np
import pytest

from bench import stages, trace, xspace
from bench.run import RunRecord, Timing
from bench.spec import metric_reader

DATA = Path(__file__).resolve().parent / "data"
STAGE_METRICS = ("table_build_ms_per_ktrial", "arbitrate_ms_per_ktrial",
                 "matching_ms_per_ktrial")


def _ops(*rows):
    """Ops from (name, start, end, stage) rows."""
    kinds = sorted({(r[0], r[3]) for r in rows})
    index = {k: i for i, k in enumerate(kinds)}
    return stages.Ops(np.asarray([r[1] for r in rows], float),
                      np.asarray([r[2] for r in rows], float),
                      np.asarray([index[(r[0], r[3])] for r in rows]),
                      [k[0] for k in kinds], [k[1] for k in kinds])


PLAN = {"points": 1, "chunk": 1, "n_chunks": 1, "per_point_bytes": 64, "metric": "min_tr"}
HAND = stages.Trace(
    devices={"/device:TPU:0": _ops(
        ("while.1", 100, 400, "chunk_loop"),     # a loop with two nested ops
        ("fusion.2", 120, 200, "table_build"),
        ("fusion.3", 250, 390, "arbitrate"),
        ("copy.5", 210, 240, "unscoped"),        # compiler-made, inside the loop
        ("copy.4", 600, 700, "unscoped"),
        ("fusion.2", 680, 900, "table_build"),   # overlaps copy.4
        ("fusion.9", 50, 80, "classify"),        # before the window: clipped away
    )},
    spans=[("bench.draw", 60, 90, {}), ("sweep.validate", 70, 80, {}),
           ("bench.submit", 90, 110, {}), ("sweep.plan", 91, 99, PLAN),
           ("sweep.dispatch", 99, 108, {"compiles": 0}),
           ("bench.wait", 110, 500, {}), ("bench.readback", 500, 560, {}),
           ("bench.draw", 560, 590, {}), ("sweep.validate", 565, 575, {}),
           ("bench.submit", 590, 600, {}), ("sweep.plan", 590, 596, PLAN),
           ("sweep.dispatch", 596, 600, {"compiles": 0}),
           ("bench.wait", 600, 950, {}), ("bench.readback", 950, 1000, {})],
)


def _base(tr: stages.Trace) -> trace.Trace:
    """The same trace as ``bench/trace.py`` reads it."""
    return trace.Trace(
        {k: trace.DeviceOps(o.start, o.end, [o.name[i] for i in o.kind])
         for k, o in tr.devices.items()},
        [s[:3] for s in tr.spans if s[0].startswith("bench.")])


def test_hand_made_trace_stage_times():
    s = stages.reduce(HAND)
    assert s.window_s == pytest.approx(910e-9)
    assert s.busy_s == trace.reduce(_base(HAND)).busy_s == pytest.approx(600e-9)
    expected = {"chunk_loop": 80e-9, "table_build": 300e-9, "arbitrate": 140e-9,
                "unscoped": 80e-9}
    assert s.stage_s.keys() == expected.keys()
    for k, v in expected.items():
        assert s.stage_s[k] == pytest.approx(v)
    assert sum(s.stage_s.values()) == pytest.approx(s.busy_s, rel=1e-12)
    assert s.op_s["table_build/fusion.2"] == pytest.approx(300e-9)
    # an op without a stage counts in the stage of the op it runs inside
    assert s.op_s["chunk_loop/copy.5"] == pytest.approx(30e-9)
    assert s.op_s["chunk_loop/while.1"] == pytest.approx(50e-9)
    assert s.op_s["unscoped/copy.4"] == pytest.approx(80e-9)
    assert s.staged and s.frontend and s.n_devices == 1
    b = stages.breakdown(s, top=2)
    assert [k for k, _ in b["device_ops"]] == ["table_build/fusion.2", "arbitrate/fusion.3"]


def test_gaps_are_named_after_the_innermost_span():
    s = stages.reduce(HAND)
    # 90-100: submit 1, plan 8, dispatch 1; 400-600: wait 100, readback 60,
    # draw 20, validate 10, plan 6, dispatch 4; 900-1000: wait and readback
    # 50 each (the earlier wins)
    assert s.gaps == [("bench.wait", pytest.approx(200e-9)),
                      ("bench.wait", pytest.approx(100e-9)),
                      ("sweep.plan", pytest.approx(10e-9))]
    assert s.frontend_idle_s == pytest.approx((8 + 1 + 10 + 6 + 4) * 1e-9)
    assert trace.reduce(_base(HAND)).gaps[-1][0] == "bench.submit"


def _nested(rng, lo, hi, depth, out):
    t = lo
    while depth and t < hi - 4:
        a = rng.uniform(t, hi - 2)
        b = rng.uniform(a + 0.5, hi)
        out.append((a, b))
        _nested(rng, a, b, depth - 1, out)
        t = b + rng.uniform(0, 3)
    return out


def _brute(start, end, lo, hi, step=0.01):
    """Innermost interval (latest begun) at each grid point of [lo, hi]."""
    t = np.arange(lo, hi, step) + step / 2
    own = np.zeros(len(start))
    for x in t:
        open_ = np.flatnonzero((start <= x) & (x < end))
        if len(open_):
            own[open_[np.argmax(start[open_])]] += step
    return own


@pytest.mark.parametrize("kind", ["nested", "overlapping"])
def test_innermost_time_matches_brute_force(kind):
    rng = np.random.default_rng(7)
    if kind == "nested":
        iv = np.asarray(_nested(rng, 0.0, 60.0, 4, []))
    else:
        a = rng.uniform(0, 55, 40)
        iv = np.stack([a, a + rng.uniform(0.5, 8, 40)], axis=1)
    start, end = iv[:, 0], iv[:, 1]
    lo, hi = 5.0, 50.0
    fast = stages.own_time(start, end, lo, hi, stages.nest(start, end))
    np.testing.assert_allclose(fast, _brute(start, end, lo, hi), atol=0.05)
    union = trace.merge(start, end, lo, hi)
    assert fast.sum() == pytest.approx(np.sum(union[:, 1] - union[:, 0]))
    a, b, i = stages.innermost(start, end, lo, hi)
    np.testing.assert_allclose(np.bincount(i, weights=b - a, minlength=len(start)), fast,
                               atol=1e-9)


@pytest.mark.parametrize("op_name, stage", [
    ("jit(_sweep_flat)/arb.chunk_loop/while/body/closed_call/vmap(arb.instantiate)/"
     "broadcast_in_dim:", "instantiate"),
    ("jit(_sweep_flat)/arb.chunk_loop/while/body/vmap(arb.ideal)/jit(max_matching)/"
     "arb.matching/while", "matching"),
    ("jit(_sweep_flat)/arb.chunk_loop/while", "chunk_loop"),
    ("jit(convert_element_type)/convert_element_type", None),
])
def test_stage_of_takes_the_innermost_stage(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_op_names_from_the_traces_hlo_protos(tmp_path):
    """A ``while`` has no ``tf_op`` stat on the TPU: its name stack comes
    from the HLO proto of its program, which the trace keeps."""
    classes = xspace._classes()
    hlo = classes["HloProto"]()
    comp = hlo.hlo_module.computations.add()
    for name, op_name in (("while.7", "jit(f)/arb.chunk_loop/while/body/arb.matching/while"),
                          ("copy.8", "")):
        ins = comp.instructions.add(name=name)
        ins.metadata.op_name = op_name
    space = classes["XSpace"]()
    meta_plane = space.planes.add(name="/host:metadata")
    meta_plane.stat_metadata[1].name = "Hlo Proto"
    pid = 2**63 + 5                     # a program id above int64, as the TPU gives
    entry = meta_plane.event_metadata[pid - 2**64]
    entry.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "program_id"
    line = dev.lines.add(name=trace.OPS_LINE)
    for mid, name, (a, b) in ((1, "while.7", (0, 100)), (2, "copy.8", (10, 20)),
                              (3, "%copy.9 = f32[] copy(f32[] %p)", (200, 210))):
        dev.event_metadata[mid].name = name
        dev.event_metadata[mid].stats.add(metadata_id=1, uint64_value=pid)
        line.events.add(metadata_id=mid, offset_ps=a * 1000, duration_ps=(b - a) * 1000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    ops = stages.load(str(path)).devices["/device:TPU:0"]
    assert dict(zip(ops.name, ops.stage)) == {"while.7": "matching", "copy.8": "unscoped",
                                              "copy.9": "unscoped"}


def test_text_proto_round_trip_keeps_stages_and_args(tmp_path):
    path = tmp_path / "hand.pbtxt"
    path.write_text(stages.to_text_proto(HAND))
    again = stages.load(str(path))
    assert [s[0] for s in again.spans] == [s[0] for s in HAND.spans]
    assert [s[3] for s in again.spans] == [s[3] for s in HAND.spans]
    ops, hand = again.devices["/device:TPU:0"], HAND.devices["/device:TPU:0"]
    assert [ops.name[k] for k in ops.kind] == [hand.name[k] for k in hand.kind]
    assert [ops.stage[k] for k in ops.kind] == [hand.stage[k] for k in hand.kind]
    np.testing.assert_allclose(ops.start, hand.start)
    np.testing.assert_allclose(ops.end, hand.end)
    # bench/trace.py reads the same file to the same numbers
    old = trace.reduce(trace.load(str(path)))
    assert old.busy_s == pytest.approx(stages.reduce(again).busy_s)
    assert old.window_s == pytest.approx(stages.reduce(again).window_s)


def _record(tr: stages.Trace, tmp_path, monkeypatch, trials=1_000) -> RunRecord:
    """A run record whose trace is ``tr``, written where a run writes it."""
    text = tmp_path / "t.pbtxt"
    text.write_text(stages.to_text_proto(tr))
    out = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t.xplane.pb"
    out.parent.mkdir(parents=True)
    out.write_bytes(xspace.parse(str(text)).SerializeToString())
    monkeypatch.setattr(stages, "TRACE_DIR", tmp_path / "trace")
    summary = trace.reduce(trace.load(str(out)))
    n = sum(1 for s in tr.spans if s[0] == "bench.submit")
    return RunRecord(9.0, summary.window_s,
                     [Timing(trials, 0.0, 0.001, 0.002)] * n, summary)


def test_stage_readers_on_the_hand_made_trace(tmp_path, monkeypatch):
    record = _record(HAND, tmp_path, monkeypatch)
    per_k = lambda s: s * 1e3 / 2.0          # two requests of 1,000 trials
    assert metric_reader("table_build_ms_per_ktrial")(record) == pytest.approx(per_k(300e-9))
    assert metric_reader("arbitrate_ms_per_ktrial")(record) == pytest.approx(per_k(140e-9))
    assert metric_reader("matching_ms_per_ktrial")(record) is None   # no such op
    assert metric_reader("frontend_idle_ms")(record) == pytest.approx(29e-9 * 1e3 / 2)


def test_readers_read_nothing_without_stages_or_frontend_spans(tmp_path, monkeypatch):
    ops = HAND.devices["/device:TPU:0"]
    bare = stages.Trace(
        {"/device:TPU:0": ops._replace(stage=[stages.UNSCOPED] * len(ops.stage))},
        [s for s in HAND.spans if s[0].startswith("bench.")])
    record = _record(bare, tmp_path, monkeypatch)
    for name in STAGE_METRICS + ("frontend_idle_ms",):
        assert metric_reader(name)(record) is None
    # the reduction still accounts for the whole busy time
    s = stages.from_run(record)
    assert s.stage_s == {stages.UNSCOPED: pytest.approx(s.busy_s)}


def test_from_run_refuses_another_runs_trace(tmp_path, monkeypatch):
    record = _record(HAND, tmp_path, monkeypatch)
    other = record._replace(trace=record.trace._replace(window_s=2 * record.window_s))
    assert stages.from_run(other) is None
    assert stages.from_run(record._replace(trace=None)) is None


def test_pr12_chip_trace_reads_as_before():
    """``data/chip_trace.pbtxt`` (two ``wdm16-lta-mintr`` requests, PR 12)
    gives the numbers it gave when it was recorded."""
    s = trace.reduce(trace.load(str(DATA / "chip_trace.pbtxt")))
    record = RunRecord(setup_s=9.0, window_s=s.window_s,
                       requests=[Timing(10_000, 0.0, 0.002, 0.19),
                                 Timing(10_000, 0.19, 0.193, 0.38)], trace=s)
    assert metric_reader("device_idle_share")(record) == pytest.approx(1.8849527301461277,
                                                                       rel=1e-12)
    assert metric_reader("device_ms_per_ktrial")(record) == pytest.approx(18.832989350000002,
                                                                          rel=1e-12)
    assert metric_reader("dispatch_ms")(record) == pytest.approx(2.5, rel=1e-12)


#: What the recorded stage trace reduces to (computed once from the file,
#: kept so that a change to the reduction shows).
CHIP_STAGES = {"matching_s": 0.376237277578, "frontend_idle_s": 0.0014058734219999762,
               "n_gaps": 85, "top_gap": ("bench.wait", 0.0028337775)}


def test_recorded_chip_trace_with_stages_and_frontend_spans(tmp_path, monkeypatch):
    path = str(DATA / "chip_trace_stages.pbtxt")
    tr = stages.load(path)
    s = stages.reduce(tr)
    old = trace.reduce(trace.load(path))
    assert s.window_s == pytest.approx(old.window_s, rel=1e-12)
    assert s.busy_s == pytest.approx(old.busy_s, rel=1e-6)
    assert sum(s.stage_s.values()) == pytest.approx(s.busy_s, rel=1e-9)
    assert max(s.stage_s, key=s.stage_s.get) == "matching"
    assert s.stage_s["matching"] == pytest.approx(CHIP_STAGES["matching_s"], rel=1e-9)
    assert s.stage_s.get(stages.UNSCOPED, 0.0) < 1e-3 * s.busy_s
    assert s.frontend_idle_s == pytest.approx(CHIP_STAGES["frontend_idle_s"], rel=1e-9)
    assert len(s.gaps) == CHIP_STAGES["n_gaps"]
    assert s.gaps[0][0] == CHIP_STAGES["top_gap"][0]
    assert s.gaps[0][1] == pytest.approx(CHIP_STAGES["top_gap"][1], rel=1e-9)
    assert all(g[0].startswith(("bench.", "sweep.")) for g in s.gaps)
    plans = [a for n, _, _, a in tr.spans if n == "sweep.plan"]
    assert len(plans) == 2
    assert plans[0] == {"points": 1, "chunk": 1, "n_chunks": 1,
                        "per_point_bytes": 30720000, "metric": "min_tr", "compiles": 0}
    record = _record(tr, tmp_path, monkeypatch, trials=10_000)
    assert metric_reader("matching_ms_per_ktrial")(record) == pytest.approx(
        CHIP_STAGES["matching_s"] * 1e3 / 20, rel=1e-6)
    assert metric_reader("frontend_idle_ms")(record) == pytest.approx(
        CHIP_STAGES["frontend_idle_s"] * 1e3 / 2, rel=1e-6)
    assert metric_reader("table_build_ms_per_ktrial")(record) is None
