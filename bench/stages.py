#!/usr/bin/env python3
"""Engine stages on the device and frontend steps on the host, from the
traced window.

The program names its engine stages with ``jax.named_scope`` (``arb.<stage>``
in each HLO op's ``op_name``) and its frontend steps with profiler
annotations (``sweep.validate``, ``sweep.plan``, ``sweep.dispatch``,
``sweep.shape``, with counters as arguments).  ``load`` reads a trace with
both: every ``XLA Ops`` event of each TPU plane with the innermost stage of
its op (``unscoped`` where none), and the ``bench.*`` and ``sweep.*`` host
spans with their arguments.  ``reduce`` adds to ``bench/trace.py``'s
numbers, over the same window (first ``bench.submit`` to last
``bench.readback``):

- ``stage_s``: the device's busy time by stage.  Each instant goes to the
  innermost op running then; clipped to the window, averaged over chips,
  so the stages sum to ``busy_s``.  An op's stage is the innermost
  ``arb.<stage>`` of its name stack: from its event metadata's ``tf_op``
  stat, else from the HLO proto of its program that the trace keeps (a
  ``while`` has no ``tf_op``); an op with none, such as a copy the compiler
  made, takes the stage of the op it runs inside;
- ``frontend_idle_s``: device-idle time in the window during which the
  innermost host span is a ``sweep.*`` span (averaged over chips);
- ``op_s``: each op's innermost time in the window as ``<stage>/<op>``;
- ``gaps``: the idle gaps of the first chip, each named after the host span
  that is innermost over most of it.

``from_run`` gives the metric readers this summary for the run's own trace
(the newest under ``.bench_trace/``, checked against the run's window), or
None.  Run as a script on a trace file or directory, it prints the
breakdown as JSON.
"""
from __future__ import annotations

import functools
import json
import os
import re
import sys
import traceback
from pathlib import Path
from typing import NamedTuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as base
from bench import xspace

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"   # where bench/run.py writes a traced run
STAGE_PREFIX = "arb."          # the program's stage scopes
FRONTEND_PREFIX = "sweep."     # the program's frontend spans
UNSCOPED = "unscoped"
#: Stats of an op's event or metadata that may hold its name stack.
OP_NAME_STATS = ("tf_op", "long_name")
_STAGE_RE = re.compile(re.escape(STAGE_PREFIX) + r"([A-Za-z0-9_]+)")


class Ops(NamedTuple):
    start: np.ndarray   # (n,) ns, float64
    end: np.ndarray     # (n,) ns
    kind: np.ndarray    # (n,) int: index into ``name`` and ``stage``
    name: list          # short op name of each kind ("fusion.12")
    stage: list         # innermost stage of each kind, or ``unscoped``


class Trace(NamedTuple):
    devices: dict       # plane name -> Ops
    spans: list         # [(name, start_ns, end_ns, args)] bench.* and sweep.* spans


class Summary(NamedTuple):
    window_s: float
    busy_s: float             # union of the device ops in the window (mean over chips)
    stage_s: dict             # stage -> busy seconds (mean over chips)
    frontend_idle_s: float    # idle under a sweep.* span (mean over chips)
    op_s: dict                # "<stage>/<op>" -> seconds (all chips)
    gaps: list                # [(innermost span, seconds)], longest first
    n_devices: int
    staged: bool              # some op in the window carries a stage
    frontend: bool            # the trace holds sweep.* spans


def stage_of(text: str) -> str | None:
    """Innermost ``arb.<stage>`` of a name stack, or None."""
    found = _STAGE_RE.findall(text or "")
    return found[-1] if found else None


def _stage(stats: dict) -> str | None:
    for key in OP_NAME_STATS:
        value = stats.get(key)
        if isinstance(value, str):
            found = stage_of(value)
            if found:
                return found
    return None


def _is_chip(plane_name: str) -> bool:
    prefix = "/device:TPU:"
    return plane_name.startswith(prefix) and plane_name[len(prefix):].isdigit()


def _program_op_names(space) -> dict:
    """``{program id: {instruction: op_name}}`` from the HLO protos that a
    TPU trace keeps in its ``/host:metadata`` plane."""
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for pid, meta in plane.event_metadata.items():
            for stat in meta.stats:
                if names.get(stat.metadata_id) == "Hlo Proto":
                    out[pid % 2**64] = xspace.hlo_op_names(stat.bytes_value)
    return out


def _device_ops(plane, names: dict, programs: dict) -> Ops | None:
    meta = plane.event_metadata
    line = next((ln for ln in plane.lines if ln.name == base.OPS_LINE), None)
    if line is None:
        return None
    rows = np.asarray([(e.metadata_id, e.offset_ps, e.duration_ps) for e in line.events],
                      np.int64).reshape(-1, 3)
    ids, first, kind = np.unique(rows[:, 0], return_index=True, return_inverse=True)
    op, stage = [], []
    for mid, i in zip(ids.tolist(), first.tolist()):
        op.append(base.short_name(meta[mid].name))
        # the op's name stack: its metadata's stats, its first event's, or
        # the HLO proto of its program
        st = xspace.stats(meta[mid].stats, names)
        found = _stage(st) or _stage(xspace.stats(line.events[i].stats, names))
        if not found and "program_id" in st:
            hlo = programs.get(int(st["program_id"]) % 2**64, {})
            found = stage_of(hlo.get(op[-1], ""))
        stage.append(found or UNSCOPED)
    start = rows[:, 1] / 1e3 + line.timestamp_ns
    return Ops(start, start + rows[:, 2] / 1e3, kind.reshape(-1), op, stage)


def load(path: str) -> Trace:
    """Device ops with stages and host spans with arguments, from a
    ``.xplane.pb`` file, the directory it is under, or a text proto."""
    if os.path.isdir(path):
        path = base.find_xspace(path)
    space = xspace.parse(path)
    programs = _program_op_names(space)
    devices, spans = {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if _is_chip(plane.name):
            ops = _device_ops(plane, names, programs)
            if ops is not None:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            meta = plane.event_metadata
            keep = {k for k, m in meta.items()
                    if m.name.startswith((base.SPAN_PREFIX, FRONTEND_PREFIX))}
            for line in plane.lines:
                for e in line.events:
                    if e.metadata_id in keep:
                        t = line.timestamp_ns + e.offset_ps / 1e3
                        spans.append((meta[e.metadata_id].name, t, t + e.duration_ps / 1e3,
                                      xspace.stats(e.stats, names)))
    return Trace(devices, sorted(spans, key=lambda sp: sp[1]))


def innermost(start, end, lo: float, hi: float):
    """The parts of [lo, hi] that the intervals cover, each part given to
    the innermost interval over it (the latest begun of those still open).
    Returns (a, b, index) arrays of disjoint, sorted segments."""
    start, end = np.asarray(start, np.float64), np.asarray(end, np.float64)
    order = np.lexsort((-end, start))          # parents before children
    seg_a, seg_b, seg_i = [], [], []
    stack: list = []
    cursor = -np.inf

    def upto(t):
        nonlocal cursor
        if t > cursor:
            if stack:
                seg_a.append(cursor)
                seg_b.append(t)
                seg_i.append(stack[-1])
            cursor = t

    for i in order:
        s = start[i]
        while stack and end[stack[-1]] <= s:
            upto(end[stack[-1]])
            stack.pop()
        upto(s)
        stack.append(i)
    while stack:
        upto(end[stack[-1]])
        stack.pop()
    a = np.clip(np.asarray(seg_a, np.float64), lo, hi)
    b = np.clip(np.asarray(seg_b, np.float64), lo, hi)
    keep = b > a
    return a[keep], b[keep], np.asarray(seg_i, np.int64)[keep]


def nest(start: np.ndarray, end: np.ndarray):
    """How intervals of positive length nest: ``(order, parent, depth,
    nested)``, with ``order`` listing them parents first, ``parent`` (-1 at
    the top: the latest begun of those open at an interval's start) and
    ``depth`` given by position in that order, and ``nested`` false where
    two intervals overlap without one holding the other.  The ops of one
    device line nest, and are reduced with arrays."""
    live = np.flatnonzero(end > start)
    order = live[np.lexsort((-end[live], start[live]))]
    ss, ee = start[order], end[order]
    pos = np.arange(len(order))
    # intervals open at each start: begun before it, not yet ended
    depth = pos - np.searchsorted(np.sort(ee), ss, side="right")
    parent = np.full(len(order), -1)
    for d in range(1, int(depth.max(initial=0)) + 1):
        up, here = pos[depth == d - 1], pos[depth == d]
        parent[here] = up[np.searchsorted(up, here) - 1]
    child = parent >= 0
    sib = np.lexsort((pos, parent))
    same = parent[sib[1:]] == parent[sib[:-1]]
    if not (np.any(ee[child] > ee[parent[child]])             # outside its parent
            or np.any(ee[sib[:-1]][same] > ss[sib[1:]][same])):  # siblings overlap
        return order, parent, depth, True
    parent, depth, stack = np.full(len(order), -1), np.zeros(len(order), int), []
    for k in range(len(order)):
        while stack and ee[stack[-1]] <= ss[k]:
            stack.pop()
        if stack:
            parent[k], depth[k] = stack[-1], len(stack)
        stack.append(k)
    return order, parent, depth, False


def own_time(start, end, lo: float, hi: float, nesting) -> np.ndarray:
    """Each interval's time in [lo, hi] during which it is the innermost one
    open (``innermost``'s segments summed per interval), so the sum is the
    union's length; ``nesting`` is ``nest(start, end)``."""
    order, parent, _, nested = nesting
    if not nested:                        # the exact sweep
        a, b, i = innermost(start, end, lo, hi)
        return np.bincount(i, weights=b - a, minlength=len(start))
    own = np.clip(end[order], lo, hi) - np.clip(start[order], lo, hi)
    child = parent >= 0
    own -= np.bincount(parent[child], weights=own[child], minlength=len(order))
    out = np.zeros(len(start))
    out[order] = own
    return out


def _inherit(code: np.ndarray, nesting, none: int) -> np.ndarray:
    """Each event's stage code, where an event with none (``none``) takes
    that of the event it runs inside."""
    order, parent, depth, _ = nesting
    c = code[order]
    for d in range(1, int(depth.max(initial=0)) + 1):
        here = np.flatnonzero((depth == d) & (c == none))
        c[here] = c[parent[here]]
    out = code.copy()
    out[order] = c
    return out


def _idle(ops: Ops, lo: float, hi: float) -> np.ndarray:
    """The window less the union of the ops, as an (m, 2) array."""
    busy = base.merge(ops.start, ops.end, lo, hi)
    edges = np.r_[lo, busy.ravel(), hi].reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _overlaps(first: np.ndarray, second: np.ndarray):
    """(i, j, length) for each overlap of two sorted lists of disjoint
    intervals ((n, 2) and (m, 2))."""
    out, j = [], 0
    for i, (a, b) in enumerate(first):
        while j < len(second) and second[j, 1] <= a:
            j += 1
        k = j
        while k < len(second) and second[k, 0] < b:
            length = min(b, second[k, 1]) - max(a, second[k, 0])
            if length > 0:
                out.append((i, k, length))
            k += 1
    return out


def reduce(trace: Trace) -> Summary:
    lo, hi = base.window_of([s[:3] for s in trace.spans])
    busy, stage_s, op_s, frontend_idle = [], {}, {}, []
    gaps = []
    host_a, host_b, host_i = innermost([s[1] for s in trace.spans],
                                       [s[2] for s in trace.spans], lo, hi)
    host = np.stack([host_a, host_b], axis=1)
    host_name = [trace.spans[i][0] for i in host_i]
    for n, name in enumerate(sorted(trace.devices)):
        ops = trace.devices[name]
        merged = base.merge(ops.start, ops.end, lo, hi)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        nesting = nest(ops.start, ops.end)
        own = own_time(ops.start, ops.end, lo, hi, nesting)
        # an op without a stage of its own (a copy or fusion the compiler
        # made) is counted in the stage of the op it runs inside
        names = sorted(set(ops.stage) | {UNSCOPED})
        code = np.asarray([names.index(st) for st in ops.stage])[ops.kind]
        code = _inherit(code, nesting, names.index(UNSCOPED))
        n_kind = len(ops.name)
        total = np.bincount(code * n_kind + ops.kind, weights=own,
                            minlength=len(names) * n_kind) * 1e-9
        for key in np.flatnonzero(total > 0).tolist():
            st, op, d = names[key // n_kind], ops.name[key % n_kind], float(total[key])
            stage_s[st] = stage_s.get(st, 0.0) + d
            op_s[f"{st}/{op}"] = op_s.get(f"{st}/{op}", 0.0) + d
        idle = _overlaps(_idle(ops, lo, hi), host)
        frontend_idle.append(sum(length for _, j, length in idle
                                 if host_name[j].startswith(FRONTEND_PREFIX)) * 1e-9)
        if n == 0:
            gap_iv = _idle(ops, lo, hi)
            best = {}
            for i, j, length in idle:
                if length > best.get(i, (0.0, ""))[0]:
                    best[i] = (length, host_name[j])
            gaps = [(best.get(i, (0.0, "none"))[1], float(b - a) * 1e-9)
                    for i, (a, b) in enumerate(gap_iv)]
    n_dev = len(busy)
    stage_s = {k: v / n_dev for k, v in stage_s.items()} if n_dev else {}
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) if busy else 0.0,
        stage_s=stage_s,
        frontend_idle_s=float(np.mean(frontend_idle)) if frontend_idle else 0.0,
        op_s=op_s, gaps=gaps, n_devices=n_dev,
        staged=any(v > 0 for k, v in stage_s.items() if k != UNSCOPED),
        frontend=any(s[0].startswith(FRONTEND_PREFIX) for s in trace.spans),
    )


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"stage_s": dict(sorted(summary.stage_s.items(), key=lambda kv: -kv[1])),
            "device_ops": [[k, float(v)] for k, v in ops],
            "idle_gaps": [[k, float(v)] for k, v in summary.gaps[:top]]}


@functools.lru_cache(maxsize=2)
def _summary_of(path: str, mtime_ns: int, size: int) -> Summary:
    del mtime_ns, size   # the cache key: a rewritten file is read again
    return reduce(load(path))


def from_run(run) -> Summary | None:
    """The staged summary of the run's own trace: the newest under
    ``.bench_trace/``, if its window is the run's.  None without a trace,
    and where it cannot be read (the reason goes to stderr)."""
    if run.trace is None:
        return None
    try:
        path = base.find_xspace(str(TRACE_DIR))
        st = os.stat(path)
        summary = _summary_of(path, st.st_mtime_ns, st.st_size)
    except Exception:  # noqa: BLE001 - a reader reports nothing, never raises
        traceback.print_exc(file=sys.stderr)
        return None
    if abs(summary.window_s - run.trace.window_s) > 1e-9 * run.trace.window_s:
        print(f"bench.stages: {path} is not this run's trace", file=sys.stderr)
        return None
    return summary


def per_ktrial_ms(run, stage: str) -> float | None:
    """Device time of ``stage`` in the traced window per 1,000 trial
    evaluations [ms]; None where no op in the window carries a stage, or
    none this one."""
    summary = from_run(run)
    trials = sum(r.trials for r in run.requests)
    if summary is None or not summary.staged or not trials:
        return None
    if summary.stage_s.get(stage, 0.0) <= 0:
        return None
    return summary.stage_s[stage] * 1e3 / (trials / 1e3)


def to_text_proto(trace: Trace) -> str:
    """The trace as an XSpace text proto, with each op's stage as its
    metadata's ``tf_op`` stat and each span's arguments as event stats."""
    out = []

    def stat(sid, value):
        if isinstance(value, str):
            return f"stats {{ metadata_id: {sid} str_value: {json.dumps(value)} }}"
        if isinstance(value, float):
            return f"stats {{ metadata_id: {sid} double_value: {value!r} }}"
        return f"stats {{ metadata_id: {sid} int64_value: {int(value)} }}"

    def plane(pid, name, lines, op_stage=None):
        meta, stat_ids, body = {}, {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for ename, s, e, args in events:
                mid = meta.setdefault(ename, len(meta) + 1)
                st = " ".join(stat(stat_ids.setdefault(k, len(stat_ids) + 1), v)
                              for k, v in args.items())
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: {int(round(s * 1000))}"
                           f" duration_ps: {int(round((e - s) * 1000))} {st}}}")
            body.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                        + "\n".join(evs) + "\n  }")
        md = []
        for n, i in meta.items():
            st = ""
            if op_stage and op_stage.get(n, UNSCOPED) != UNSCOPED:
                sid = stat_ids.setdefault("tf_op", len(stat_ids) + 1)
                st = " " + stat(sid, STAGE_PREFIX + op_stage[n])
            md.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}"{st} }} }}')
        md += [f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
               for k, i in stat_ids.items()]
        out.append(f'planes {{\n  id: {pid}\n  name: "{name}"\n' + "\n".join(body + md) + "\n}")

    for pid, (name, ops) in enumerate(sorted(trace.devices.items()), 1):
        events = [(ops.name[k], s, e, {}) for k, s, e in zip(ops.kind, ops.start, ops.end)]
        op_stage = dict(zip(ops.name, ops.stage))
        plane(pid, name, [(base.OPS_LINE, events)], op_stage)
    plane(len(trace.devices) + 1, "/host:CPU", [("python3", trace.spans)])
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 bench/stages.py <trace file or directory>", file=sys.stderr)
        return 2
    tr = load(argv[0])
    s = reduce(tr)
    spans: dict = {}
    for name, a, b, args in tr.spans:
        slot = spans.setdefault(name, {"count": 0, "s": 0.0, "compiles": 0})
        slot["count"] += 1
        slot["s"] += (b - a) * 1e-9
        slot["compiles"] += int(args.get("compiles", 0))
    print(json.dumps({
        "window_s": s.window_s, "busy_s": s.busy_s, "n_devices": s.n_devices,
        "unscoped_share": s.stage_s.get(UNSCOPED, 0.0) / s.busy_s if s.busy_s else None,
        "frontend_idle_s": s.frontend_idle_s, "spans": spans,
        "plan": next((args for name, _, _, args in tr.spans if name == "sweep.plan"), None),
        **breakdown(s, top=20),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
