"""From a profiler trace to device busy time, idle gaps and op times.

The traced run records the window with ``jax.profiler``.  ``load`` reads the
``.xplane.pb`` it wrote (or a text proto of the same schema, as the tests'
trimmed trace is) into plain arrays: every ``XLA Ops`` event of each TPU
plane, and the benchmark's own host spans (``bench.*`` trace annotations).
``reduce`` turns those into the numbers the per-layer metrics and the
``breakdown`` read:

- the window: from the first ``bench.submit`` to the end of the last
  ``bench.readback``;
- busy: the union of the device's op intervals inside the window (nested
  ops, such as the body of a ``while``, count once), averaged over chips;
- idle gaps: the rest of the window, each gap named after the host span
  that overlaps it most (``none`` where no span does);
- op self time: each op's time less that of the ops nested in it, summed
  by op name.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class DeviceOps(NamedTuple):
    start: np.ndarray  # (n,) ns, float64
    end: np.ndarray    # (n,) ns
    name: list         # (n,) short op names ("fusion.12", "while.3")


class Trace(NamedTuple):
    devices: dict      # plane name -> DeviceOps
    spans: list        # [(name, start_ns, end_ns)] host spans of the benchmark


class Summary(NamedTuple):
    window_s: float
    busy_s: float                 # mean over chips
    op_s: dict                    # op name -> self seconds (all chips)
    gaps: list                    # [(host span, seconds)], longest first
    n_devices: int


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def find_xspace(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def from_profile(pd) -> Trace:
    """Plain arrays from a ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ev = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                devices[name] = DeviceOps(
                    np.asarray([e[0] for e in ev], np.float64),
                    np.asarray([e[1] for e in ev], np.float64),
                    [short_name(e[2]) for e in ev],
                )
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as f:
            return from_profile(ProfileData.from_text_proto(f.read()))
    if os.path.isdir(path):
        path = find_xspace(path)
    return from_profile(ProfileData.from_file(path))


def merge(start: np.ndarray, end: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Union of intervals clipped to [lo, hi], as a sorted (m, 2) array."""
    s, e = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return np.stack([s[first], reach[last]], axis=1)


def self_times(ops: DeviceOps) -> dict:
    """Op name -> seconds of its own (nested ops subtracted)."""
    order = np.lexsort((-ops.end, ops.start))   # parents before their children
    total: dict = {}
    stack: list = []   # open ops: [end, name, duration, time of children]

    def close(item):
        total[item[1]] = total.get(item[1], 0.0) + max(item[2] - item[3], 0.0)

    for i in order:
        s, e = ops.start[i], ops.end[i]
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][0]) - s
        stack.append([e, ops.name[i], e - s, 0.0])
    while stack:
        close(stack.pop())
    return {k: v * 1e-9 for k, v in total.items()}


def window_of(spans: list) -> tuple[float, float]:
    starts = [s for n, s, _ in spans if n == SPAN_PREFIX + "submit"]
    ends = [e for n, _, e in spans if n == SPAN_PREFIX + "readback"]
    if not starts or not ends:
        raise ValueError("trace holds no bench.submit / bench.readback spans")
    return min(starts), max(ends)


def label_gap(spans: list, lo: float, hi: float) -> str:
    best, label = 0.0, "none"
    for name, s, e in spans:
        if s >= hi:
            break
        overlap = min(e, hi) - max(s, lo)
        if overlap > best:
            best, label = overlap, name
    return label


def reduce(trace: Trace) -> Summary:
    lo, hi = window_of(trace.spans)
    busy, op_s = [], {}
    first_dev = None
    for name in sorted(trace.devices):
        ops = trace.devices[name]
        merged = merge(ops.start, ops.end, lo, hi)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        for k, v in self_times(ops).items():
            op_s[k] = op_s.get(k, 0.0) + v
        if first_dev is None:
            first_dev = merged
    gaps = []
    if first_dev is not None:
        edges = np.r_[lo, first_dev.ravel(), hi].reshape(-1, 2)
        for a, b in edges:
            if b > a:
                gaps.append((label_gap(trace.spans, a, b), float(b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) if busy else 0.0,
        op_s=op_s, gaps=gaps, n_devices=len(busy),
    )


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, float(v)] for k, v in ops],
            "idle_gaps": [[k, float(v)] for k, v in summary.gaps[:top]]}


def to_text_proto(trace: Trace) -> str:
    """The trace as an XSpace text proto (how the tests' trimmed trace is kept)."""
    out = []

    def plane(pid, name, lines):
        meta, body = {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for ename, s, e in events:
                mid = meta.setdefault(ename, len(meta) + 1)
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: {int(round(s * 1000))}"
                           f" duration_ps: {int(round((e - s) * 1000))} }}")
            body.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                        + "\n".join(evs) + "\n  }")
        md = [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
              for n, i in meta.items()]
        out.append(f'planes {{\n  id: {pid}\n  name: "{name}"\n' + "\n".join(body + md) + "\n}")

    for pid, (name, ops) in enumerate(sorted(trace.devices.items()), 1):
        plane(pid, name, [(OPS_LINE, list(zip(ops.name, ops.start, ops.end)))])
    plane(len(trace.devices) + 1, "/host:CPU", [("python3", trace.spans)])
    return "\n".join(out) + "\n"
