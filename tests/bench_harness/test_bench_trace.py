"""The reduction from a profiler trace to busy time, idle gaps and op self
time: exact on a hand-made trace, and on a trace recorded on a TPU v5e
(``data/chip_trace.pbtxt``: two requests of ``wdm16-lta-mintr``, top-level
device ops only, kept as an XSpace text proto)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace
from bench.run import RunRecord, Timing
from bench.spec import metric_reader

DATA = Path(__file__).resolve().parent / "data"


def _ops(*rows):
    return trace.DeviceOps(np.asarray([r[1] for r in rows], float),
                           np.asarray([r[2] for r in rows], float),
                           [r[0] for r in rows])


HAND = trace.Trace(
    devices={"/device:TPU:0": _ops(
        ("while.1", 100, 400),      # a loop with two nested ops
        ("fusion.2", 120, 200),
        ("fusion.3", 250, 390),
        ("copy.4", 600, 700),
        ("fusion.2", 680, 900),     # overlaps copy.4
        ("fusion.9", 50, 80),       # before the window: clipped away
    )},
    spans=[("bench.draw", 60, 90), ("bench.submit", 90, 110),
           ("bench.wait", 110, 500), ("bench.readback", 500, 560),
           ("bench.draw", 560, 590), ("bench.submit", 590, 600),
           ("bench.wait", 600, 950), ("bench.readback", 950, 1000)],
)


def test_hand_made_trace_reduces_exactly():
    s = trace.reduce(HAND)
    assert s.window_s == pytest.approx(910e-9)          # 90 .. 1000
    assert s.busy_s == pytest.approx(600e-9)            # 100..400, 600..900
    assert s.n_devices == 1
    # gaps: 90-100 under submit, 400-600 mostly under the first wait,
    # 900-1000 split evenly between wait and readback (the earlier wins)
    assert [g[0] for g in s.gaps] == ["bench.wait", "bench.wait", "bench.submit"]
    assert [g[1] for g in s.gaps] == pytest.approx([200e-9, 100e-9, 10e-9])
    assert s.op_s["while.1"] == pytest.approx((300 - 80 - 140) * 1e-9)
    assert s.op_s["fusion.2"] == pytest.approx((80 + 220) * 1e-9)
    assert s.op_s["copy.4"] == pytest.approx((100 - 20) * 1e-9)   # overlap only
    b = trace.breakdown(s, top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.2", "fusion.3"]
    assert len(b["idle_gaps"]) == 2


def test_text_proto_round_trip(tmp_path):
    path = tmp_path / "hand.pbtxt"
    path.write_text(trace.to_text_proto(HAND))
    again = trace.load(str(path))
    assert again.spans == HAND.spans
    ops = again.devices["/device:TPU:0"]
    assert ops.name == HAND.devices["/device:TPU:0"].name
    np.testing.assert_allclose(ops.start, HAND.devices["/device:TPU:0"].start)
    np.testing.assert_allclose(ops.end, HAND.devices["/device:TPU:0"].end)


def _naive(tr):
    """Busy and idle by brute force over 1 ns steps of the window."""
    lo, hi = trace.window_of(tr.spans)
    ops = tr.devices["/device:TPU:0"]
    t = np.arange(int(lo), int(hi)) + 0.5
    busy = np.zeros(len(t), bool)
    for s, e in zip(ops.start, ops.end):
        busy |= (t >= s) & (t < e)
    return (hi - lo) * 1e-9, busy.sum() * 1e-9


def test_hand_made_trace_matches_brute_force():
    window, busy = _naive(HAND)
    s = trace.reduce(HAND)
    assert s.window_s == pytest.approx(window) and s.busy_s == pytest.approx(busy)


#: What the recorded trace reduces to (computed once from the file, kept so
#: that a change to the reduction shows).
CHIP = {"window_s": 0.38389604600000005, "busy_s": 0.376659787, "n_gaps": 68,
        "top_gap": ("bench.wait", 0.003810818)}


def test_recorded_chip_trace():
    tr = trace.load(str(DATA / "chip_trace.pbtxt"))
    s = trace.reduce(tr)
    assert list(tr.devices) == ["/device:TPU:0"]
    assert s.window_s == pytest.approx(CHIP["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(CHIP["busy_s"], rel=1e-9)
    assert len(s.gaps) == CHIP["n_gaps"]
    assert s.gaps[0][0] == CHIP["top_gap"][0]
    assert s.gaps[0][1] == pytest.approx(CHIP["top_gap"][1], rel=1e-9)
    idle = sum(g for _, g in s.gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_metric_readers_on_the_recorded_trace():
    s = trace.reduce(trace.load(str(DATA / "chip_trace.pbtxt")))
    record = RunRecord(setup_s=9.0, window_s=s.window_s,
                       requests=[Timing(10_000, 0.0, 0.002, 0.19),
                                 Timing(10_000, 0.19, 0.193, 0.38)], trace=s)
    idle = metric_reader("device_idle_share")(record)
    assert idle == pytest.approx(100 * (1 - s.busy_s / s.window_s))
    assert 0 < idle < 100
    per_k = metric_reader("device_ms_per_ktrial")(record)
    assert per_k == pytest.approx(s.busy_s * 1e3 / 20)
    assert metric_reader("dispatch_ms")(record) == pytest.approx(2.5)
    assert metric_reader("trials_per_s")(record) == pytest.approx(20_000 / s.window_s)
