"""Benchmark driver: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (derived = paper-comparable values);
``--json out.json`` additionally writes the per-figure wall-times and derived
metrics machine-readably (the seed for BENCH_*.json trajectory tracking).

``--runs N`` repeats every module N times and records the *median* wall-time
and per-record ``*_ms`` timings (``engine_ms`` plus any per-phase breakdown
such as ``table_ms``/``arbitrate_ms``/``score_ms``) — the derived grids are
deterministic, so only the timings vary.  On noisy shared machines (PR 3 measured 23/51 records of
identical code drifting >20% between single runs on a 2-core container)
median-of-3 is what makes the ``check_regression`` wall-time gate usable.

``--timeout S`` arms a per-module alarm (SIGALRM; POSIX main thread only).
A module that hangs past it is recorded as a single marker record
(``derived: {"timeout": true, "phase": ...}``) attributing the hang to the
phase span that was executing when the alarm fired (``repro.obs.phase``
recorder — e.g. ``sweep:warm`` vs ``sweep:steady``, or fig18's
table/arbitrate/score breakdown), every module that already finished keeps
its records, and the JSON is still written — one wedged figure no longer
loses the whole run.  The run then exits non-zero, naming the modules that
timed out.  ``check_regression`` treats marker records as missing (note,
never a failure).

Every run also writes a ``repro.obs`` JSONL manifest (``.obs/``): each
record mirrors there as it lands, with per-module phase dumps; each JSON
record carries the manifest path and its module's aggregated ``phases``
fields so BENCH files and manifests cross-reference both ways.  Render
with ``python -m repro.obs.report``.
"""
from __future__ import annotations

import argparse
import json
import signal
import statistics
import time

from .common import write_json


class ModuleTimeout(Exception):
    """A benchmark module exceeded the per-module wall budget.

    ``phase`` carries the open span stack of the module's phase recorder at
    the instant the alarm fired (None when nothing was instrumented) — the
    difference between "the sweep compile wedged" and "the steady-state
    timing wedged" without re-running anything.
    """

    def __init__(self, phase: str | None = None):
        super().__init__(phase or "")
        self.phase = phase


def _run_with_timeout(fn, seconds: int | None, recorder=None):
    """Run ``fn()`` under a SIGALRM budget; raises ModuleTimeout on expiry.

    No-op passthrough when ``seconds`` is None/0 or SIGALRM is unavailable
    (non-POSIX or non-main-thread): the run degrades to untimed, never
    breaks.  ``recorder`` (a ``repro.obs.phase.PhaseRecorder``) attributes
    the timeout to the span executing when the alarm fired.
    """
    if not seconds or not hasattr(signal, "SIGALRM"):
        return fn()

    def on_alarm(signum, frame):
        raise ModuleTimeout(
            recorder.current_path() if recorder is not None else None
        )

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        return fn()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-size Monte Carlo (100x100 trials)")
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument("--json", dest="json_out", default=None, metavar="OUT",
                    help="also write machine-readable results to OUT")
    ap.add_argument("--runs", type=int, default=1, metavar="N",
                    help="repeat each module N times; record median wall "
                         "and *_ms timings (noise-robust BENCH files)")
    ap.add_argument("--timeout", type=int, default=0, metavar="S",
                    help="per-module wall budget in seconds (0 = off); a "
                         "module over budget becomes a timeout marker "
                         "record and the run continues")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.runs < 1:
        ap.error("--runs must be >= 1")
    if args.timeout < 0:
        ap.error("--timeout must be >= 0")

    from . import (
        beyond_lta,
        fig4_afp_shmoo,
        fig5_min_tuning_range,
        fig6_ltd_grid_offset,
        fig7_sensitivity,
        fig8_fsr_design,
        fig14_cafp_schemes,
        fig15_seq_breakdown,
        fig16_high_variation,
        fig17_retry_budget,
        fig18_wdm32_cafp,
        fig19_lta_protocol,
        fig20_temporal_relock,
        fig21_fabric_yield,
        fig22_fabric_chaos,
        kernel_bench,
    )

    modules = [
        fig4_afp_shmoo,
        fig5_min_tuning_range,
        fig6_ltd_grid_offset,
        fig7_sensitivity,
        fig8_fsr_design,
        fig14_cafp_schemes,
        fig15_seq_breakdown,
        fig16_high_variation,
        fig17_retry_budget,
        fig18_wdm32_cafp,
        fig19_lta_protocol,
        fig20_temporal_relock,
        fig21_fabric_yield,
        fig22_fabric_chaos,
        kernel_bench,
        beyond_lta,
    ]
    from repro.obs.manifest import RunManifest
    from repro.obs.phase import PhaseRecorder, use_recorder

    manifest = RunManifest.create(
        label="bench", full=args.full, runs=args.runs, timeout=args.timeout
    )
    print("name,us_per_call,derived")
    records = []
    timed_out = []
    for mod in modules:
        mod_name = mod.__name__.rsplit(".", 1)[-1]
        if args.only and args.only not in mod_name:
            continue
        walls, timing_runs = [], []
        # One recorder per module: its spans time each repeat's sweeps
        # (warm = compile, steady = execute) and — under --timeout — name
        # the phase a wedged module was stuck in.
        recorder = PhaseRecorder()
        try:
            with use_recorder(recorder):
                for _ in range(args.runs):
                    t0 = time.time()
                    rows = _run_with_timeout(
                        lambda: mod.run(full=args.full), args.timeout,
                        recorder,
                    )
                    walls.append((time.time() - t0) * 1e3)
                    timing_runs.append(
                        {name: {k: v for k, v in d.items()
                                if k.endswith("_ms")}
                         for name, d in rows}
                    )
        except ModuleTimeout as to:
            # One wedged module must not lose the run: emit a marker record
            # (check_regression treats it as missing) and move on.  Partial
            # repeats are discarded — a half-measured median is not a median.
            print(f"{mod_name}/TIMEOUT,0,{{}}")
            timed_out.append(mod_name)
            records.append(
                {
                    "figure": mod_name,
                    "name": f"{mod_name}/TIMEOUT",
                    "module_wall_ms": 0.0,
                    "manifest": manifest.path,
                    "derived": {"timeout": True,
                                "budget_s": args.timeout,
                                "phase": to.phase},
                }
            )
            manifest.record_bench(records[-1])
            manifest.record_phases(recorder, scope=mod_name)
            if args.json_out:
                write_json(args.json_out, records, full=args.full)
            continue
        wall_ms = statistics.median(walls)
        if args.runs > 1:
            # Grids are deterministic across runs; only timings vary.  Keep
            # the last run's rows and replace every *_ms derived field
            # (engine_ms and the per-phase breakdown) with its median.
            for name, derived in rows:
                for field in [k for k in derived if k.endswith("_ms")]:
                    derived[field] = round(statistics.median(
                        run[name][field] for run in timing_runs
                    ), 1)
        phases = recorder.phase_fields()
        manifest.record_phases(recorder, scope=mod_name)
        us = wall_ms * 1e3 / max(len(rows), 1)
        for name, derived in rows:
            print(f"{name},{us:.0f},{json.dumps(derived, default=float)}")
            records.append(
                {
                    "figure": mod_name,
                    "name": name,
                    "module_wall_ms": round(wall_ms, 1),
                    "manifest": manifest.path,
                    "phases": phases,
                    "derived": derived,
                }
            )
            manifest.record_bench(records[-1])
        if args.json_out:
            # incremental flush: a crash mid-suite keeps everything finished
            write_json(args.json_out, records, full=args.full)
    if args.json_out:
        write_json(args.json_out, records, full=args.full)
    manifest.close()
    if timed_out:
        raise SystemExit(f"modules timed out: {', '.join(timed_out)}")


if __name__ == "__main__":
    main()
