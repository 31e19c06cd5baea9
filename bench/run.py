#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m bench.run`` from the repository root works the same.)

The cell, its configuration, traffic and metrics are looked up by name
from ``BENCHMARK.json`` (``bench/spec.py``).  Set-up loads the cell's
program (from JAX's persistent compile cache, or by compiling it) and runs
one warm-up request of the cell's own shapes.  Then one client runs a
closed loop through ``repro.core.sweep`` for ``--seconds``: draw a
request's units, submit it, wait for the device, read the grids back,
repeat.  The window runs from the first timed submit to the completion of
the last request begun before ``--seconds``.  With ``--trace 1`` the
window is recorded by the profiler and only the per-layer metrics are
reported.  Once the window has closed, a sample of its answers drawn from
the seed is compared with the plain reference (``bench/check.py``).

Earlier stdout lines carry the request count, the compilations seen inside
the window (expected 0) and the window's length; the last stdout line is
the result (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and the compared numbers
beside their limits under ``checks``, last).  The compared numbers are
also the last lines on stderr.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import NamedTuple

ROOT = Path(__file__).resolve().parents[1]
_T_IMPORT = time.perf_counter()


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Timing(NamedTuple):
    trials: int          # trial evaluations of the request
    submit: float        # host clock (perf_counter) at submit
    dispatched: float    # when ``sweep`` returned (asynchronously)
    done: float          # when the grids were on the host


class RunRecord(NamedTuple):
    """What the metric readers (``bench/metrics/*.py``) read."""

    setup_s: float
    window_s: float
    requests: list       # [Timing], window requests that completed
    trace: object        # bench.trace.Summary with --trace 1, else None


def process_age_s() -> float:
    """Seconds since this process started (from /proc where it exists)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else the fixed ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(n: int) -> list:
    """The first ``n`` TPU devices; never falls back to the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Counts traces and compilations (cache loads included) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


def one_request(traffic, program_cfg, seed: int, index: int):
    """Draw, submit, wait and read back one request, each in a host span.
    Returns (request, answer, Timing)."""
    import jax

    from bench import program

    with jax.profiler.TraceAnnotation("bench.draw"):
        req = traffic.request(seed, index)
        sreq = program.make_request(program_cfg, traffic.target, traffic.metric, req)
    t_submit = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.submit"):
        result = program.submit(sreq)
    t_dispatched = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.wait"):
        program.wait(result)
    with jax.profiler.TraceAnnotation("bench.readback"):
        answer = program.readback(result, traffic.metric)
    t_done = time.perf_counter()
    return req, answer, Timing(req.trial_evaluations, t_submit, t_dispatched, t_done)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list,
             trials: tuple | None = None, log=print) -> dict:
    """Set up, run the window, check the answers; returns the result dict.

    ``trials`` overrides the traffic's ``[n_laser, n_ring]`` (tests only).
    """
    import jax

    from bench import check, program
    from bench.traffic import WARMUP_INDEX, Traffic

    traffic = Traffic(cell.traffic, cell.config, trials)
    program_cfg = program.build_config(cell.config)
    counter = CompileCounter()

    # Set-up: the cell's own program, compiled or loaded, and run once.
    one_request(traffic, program_cfg, seed, WARMUP_INDEX)
    trace_dir = ROOT / ".bench_trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    age, t_age = process_age_s(), time.perf_counter()

    requests, answers, timings, failed = [], [], [], 0
    counter.armed = True
    t_start = time.perf_counter()
    deadline = t_start + seconds
    index = 0
    while index == 0 or time.perf_counter() < deadline:
        try:
            req, answer, timing = one_request(traffic, program_cfg, seed, index)
            timings.append(timing)
        except Exception:  # noqa: BLE001 - a failed request is counted, not fatal
            traceback.print_exc(file=sys.stderr)
            req, answer = traffic.request(seed, index), None
            failed += 1
        requests.append(req)
        answers.append(answer)
        index += 1
    counter.armed = False
    window_s = (timings[-1].done - timings[0].submit) if timings else 0.0
    setup_s = age + ((timings[0].submit if timings else t_start) - t_age)
    if trace:
        jax.profiler.stop_trace()

    used = devices[:cell.chips]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    gc.collect()   # the program's device buffers of the window are gone now

    summary = None
    if trace:
        from bench import trace as trace_mod

        t0 = time.perf_counter()
        summary = trace_mod.reduce(trace_mod.load(str(trace_dir)))
        log(json.dumps({"trace_read_s": time.perf_counter() - t0}))

    t0 = time.perf_counter()
    check_spec = cell.workload["check"]
    correct, numbers = check.check(traffic, seed, requests, answers,
                                   check_spec["limits"], int(check_spec["sample"]))
    correct = correct and failed == 0
    log(json.dumps({"run": {
        "workload": cell.name, "seed": seed, "requests": len(requests),
        "failed": failed, "compiles_in_window": counter.count,
        "window_s": window_s, "seconds": seconds, "setup_s": setup_s,
        "reference_s": time.perf_counter() - t0,
    }}))

    from bench.spec import metric_reader

    record = RunRecord(setup_s, window_s, timings, summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": len(requests),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        from bench.trace import breakdown

        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = breakdown(summary)
    result["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in numbers.items()}
    return result


def _finite(x: float):
    return x if math.isfinite(x) else None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    try:
        import repro.core  # noqa: F401 - the system under test must be present
    except ImportError as e:
        print(f"bench: the program is missing: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; this benchmark does not fall back to the CPU",
              file=sys.stderr)
        return 1
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, n in result["checks"].items():
        print(f"check {name}: {n['value']} (limit {n['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
