#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 --requests 3

For each seed it draws the cell's requests as a run would (``--requests``
of them, about as many as a window completes) and the same sample of grid
points that ``bench/check.py`` compares, then prints one JSON line with

- ``program``: the compared numbers of the program (``repro.core.sweep``,
  the timed path at the cell's size) against the float32 reference: the
  lower readings;
- ``control``: the same numbers of the reference computed in bfloat16, put
  in the program's place: the upper readings.  The control has to fail.

Benchmark runs never run this; it needs the chip for the program's side
and exits non-zero without one.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, n_requests: int, trials: tuple | None = None) -> dict:
    from bench import check, program, reference
    from bench.traffic import Traffic, sample_points

    traffic = Traffic(cell.traffic, cell.config, trials)
    requests = [traffic.request(seed, i) for i in range(n_requests)]
    pairs = sample_points(seed, requests, int(cell.workload["check"]["sample"]))
    program_cfg = program.build_config(cell.config)
    answers = {}
    for r in sorted({r for r, _ in pairs}):
        sreq = program.make_request(program_cfg, traffic.target, traffic.metric, requests[r])
        answers[r] = program.readback(program.submit(sreq), traffic.metric)
    got, ctl = [], []
    for r, flat in pairs:
        req = requests[r]
        shape = tuple(len(v) for v in req.axes.values())
        want = check.reference_point(traffic, req, flat)
        got.append((check.program_point(answers[r], shape, flat), want))
        ctl.append((check.reference_point(traffic, req, flat, reference.q_bf16), want))
    return {"seed": seed, "points": len(pairs),
            "program": check.compare(got), "control": check.compare(ctl)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    from bench.spec import load_cell

    cell = load_cell(ROOT, args.workload)
    run.enable_compile_cache()
    try:
        run.find_chips(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(cell, seed, args.requests)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps({"workload": cell.name, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
