"""Shared benchmark harness.

Each fig*.py exposes ``run(full=False) -> list[(name, derived_dict)]``;
``benchmarks.run`` times each and prints ``name,us_per_call,derived`` CSV
(the derived column carries the paper-comparable quantities).

Default sizes are CPU-friendly (24x24 = 576 Monte-Carlo trials/point);
``--full`` restores the paper's 100x100 = 10,000.
"""
from __future__ import annotations

import json
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

TRIALS_FAST = 24
TRIALS_FULL = 100


def n_samples(full: bool) -> int:
    return TRIALS_FULL if full else TRIALS_FAST


def timed(fn: Callable, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, (time.time() - t0) * 1e6


def timed_steady(fn: Callable, *args, **kw):
    """(result, steady_ms): first call warms the jit cache, second is timed.

    Keeps ``engine_ms`` comparable across figures and commits in the
    BENCH_*.json trajectory — compile time is excluded everywhere.  With a
    ``repro.obs.phase`` recorder installed, the warm call is recorded as a
    ``compile`` span and the steady call as ``execute`` — the compile vs
    execute split the run manifests report — with no recorder the two
    spans are only profiler annotations around the identical calls.
    """
    import jax

    from repro.obs.phase import span

    label = getattr(fn, "__name__", fn.__class__.__name__)
    with span(f"{label}:warm", kind="compile"):
        out = jax.block_until_ready(fn(*args, **kw))
    t0 = time.time()
    with span(f"{label}:steady", kind="execute"):
        jax.block_until_ready(fn(*args, **kw))
    return out, (time.time() - t0) * 1e3


def emit(rows: List[Tuple[str, float, Dict]]):
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{json.dumps(derived, default=float)}")


def write_json(path: str, records: List[Dict], *, full: bool) -> None:
    """Machine-readable benchmark output (seed for BENCH_*.json tracking).

    records: [{"figure": module, "name": row, "module_wall_ms": wall-time of
    the row's whole module, "derived": {...}}].  Schema version bumps on
    layout changes.
    """
    payload = {
        "schema": "bench.v1",
        "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "full": full,
        "trials_per_point": n_samples(full) ** 2,
        "records": records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
        f.write("\n")


def tr_sweep(n_ch: int = 8, spacing: float = 1.12) -> np.ndarray:
    """Paper default TR sweep: 0.25*gS .. FSR (Table I note 1)."""
    return np.linspace(0.25 * spacing, n_ch * spacing, 12).astype(np.float32)


def rlv_sweep(spacing: float = 1.12) -> np.ndarray:
    """sigma_rLV sweep: 0.25x .. 8x grid spacing (paper §II-C)."""
    return np.array(
        [0.25, 0.5, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0], dtype=np.float32
    ) * spacing
