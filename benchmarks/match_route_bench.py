"""Ideal Lock-to-Any scoring on a TPU: the core path against the Pallas
matching kernels, at the sizes ``repro.core.api.match_route`` decides.

    PYTHONPATH=src python -m benchmarks.match_route_bench [--json out.jsonl]

For each channel count it times, on 100 x 100 = 10,000 trials at the
paper's default variations, what each route runs for ``_ideal_min_tr``
(``ideal.min_tr`` against ``ops.bottleneck_threshold``) and for
``_ideal_success`` at the median threshold (``ideal.success`` against
``ops.perfect_matching``), once on one batch of 10,000 trials and once as
5,000 batches of two mapped one by one (a fabric link's two endpoints;
up to 32 channels).
Each pair of routes must agree bit for bit.  One JSON line per case: the
first call's wall time (compile included) and the median of the steady
calls, in seconds.  TPU only: exits non-zero without one.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ideal, make_units, wdm_config
from repro.core.matching import adjacency_bitmask
from repro.core.reach import reach_matrix, scaled_residual
from repro.core.sampling import instantiate
from repro.kernels import ops

TRIALS = 100   # per side: 100 x 100 = 10,000 trials
REPS = 5


def _timed(fn, arg):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(arg))
    cold = time.perf_counter() - t0
    steady = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        steady.append(time.perf_counter() - t0)
    return np.asarray(out), cold, statistics.median(steady)


def _routes(s, tr):
    """Per quantity: the core path and the kernel path, on a (T, N) batch."""
    return {
        "min_tr": (lambda b: ideal.min_tr(b, "lta", s),
                   lambda b: ops.bottleneck_threshold(
                       scaled_residual(b), backend="pallas")),
        "success": (lambda b: ideal.success(b, "lta", s, tr),
                    lambda b: ops.perfect_matching(
                        adjacency_bitmask(reach_matrix(b, tr)),
                        backend="pallas")[1]),
    }


def run(n_chs, emit) -> None:
    for n_ch in n_chs:
        cfg = wdm_config(n_ch=n_ch, ghz=200)
        batch = instantiate(cfg, make_units(cfg, seed=n_ch, n_laser=TRIALS,
                                            n_ring=TRIALS))
        s = jnp.asarray(cfg.s)
        tr = float(np.median(np.asarray(ideal.min_tr(batch, "lta", s))))
        pairs = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, 2, a.shape[-1]), batch)
        for quantity, (core, kernel) in _routes(s, tr).items():
            if quantity == "success" and n_ch > 32:
                continue   # multiword adjacency: both routes are core
            for shape, arg in (("block", batch), ("pairs", pairs))[
                    :2 if n_ch <= 32 else 1]:
                times = {}
                outs = []
                for route, fn in (("core", core), ("pallas", kernel)):
                    f = jax.jit(fn if shape == "block"
                                else lambda b, fn=fn: jax.vmap(fn)(b))
                    out, cold, steady = _timed(f, arg)
                    outs.append(out.reshape(-1))
                    times[route] = {"cold_s": cold, "steady_s": steady}
                if not np.array_equal(*outs):
                    raise AssertionError(f"n={n_ch} {quantity} {shape}: "
                                         "core and pallas differ")
                emit({"n_ch": n_ch, "quantity": quantity, "batch": shape,
                      "trials": TRIALS * TRIALS, **times,
                      "core_over_pallas": times["core"]["steady_s"]
                      / times["pallas"]["steady_s"],
                      "bit_identical": True})


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--n-ch", type=int, nargs="+", default=[8, 16, 32, 64])
    p.add_argument("--json", help="append the JSON lines to this file too")
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("match_route_bench times the TPU kernels: no TPU here")
    dev = jax.devices()[0]
    sink = open(args.json, "a") if args.json else None

    def emit(rec):
        rec = {"device": dev.device_kind, **rec}
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")

    try:
        run(args.n_ch, emit)
    finally:
        if sink:
            sink.close()


if __name__ == "__main__":
    main()
