"""Pallas-kernel micro-benchmark: jnp path timings (the CPU-executable
production path) + interpret-mode parity check.  On-TPU wall-times are not
measurable in this container; the roofline for the kernels comes from the
BlockSpec VMEM analysis in kernels/*.py docstrings."""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import ArbitrationConfig, make_units, wdm_config
from repro.core.matching import (
    adjacency_bitmask,
    _bottleneck_threshold_kuhn,
    bottleneck_matching_threshold,
)
from repro.core.reach import reach_matrix, scaled_residual
from repro.core.sampling import instantiate
from repro.kernels import ops

from .common import n_samples


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, (time.time() - t0) / reps * 1e6


def run(full: bool = False):
    n = n_samples(full)
    cfg = ArbitrationConfig()
    units = make_units(cfg, seed=12, n_laser=n, n_ring=n)
    sys = instantiate(cfg, units)
    s = tuple(int(v) for v in cfg.s)
    rows = []

    (ltd, ltc), us = _time(
        ops.feasibility, sys.laser, sys.ring, sys.fsr, sys.tr_unit,
        s=s, backend="jnp",
    )
    rows.append(
        ("kernel/feasibility_jnp",
         {"trials": sys.n_trials, "us_per_call": round(us),
          "ns_per_trial": round(us * 1e3 / sys.n_trials, 1)})
    )

    adj = adjacency_bitmask(reach_matrix(sys, 4.0))
    (_, ok), us = _time(ops.perfect_matching, adj, backend="jnp")
    rows.append(
        ("kernel/bitmask_match_jnp",
         {"trials": sys.n_trials, "us_per_call": round(us),
          "match_rate": round(float(np.mean(np.asarray(ok))), 3)})
    )

    tr = 5.0 * sys.tr_unit
    _, us = _time(ops.build_tables, sys.laser, sys.ring, sys.fsr, tr,
                  max_alias=4, backend="jnp")
    rows.append(
        ("kernel/table_build_jnp",
         {"trials": sys.n_trials, "us_per_call": round(us)})
    )

    # Streaming top-E table build across channel counts: jnp (the engine's
    # scheme-path hot spot) at bench trials and the Pallas kernel in
    # interpret mode on one 128-trial lane block (correctness-path cost;
    # max_alias=2 there because interpret wall time is trace-dominated —
    # note that at that alias count the kernel's alias-group merge
    # degenerates to a single sort, so the multi-group path is guarded by
    # tests/test_kernels.py::test_table_kernel_multi_group_merge, not this
    # timing row).  The jnp legs DO run the multi-step streaming merge at
    # N=32, so a regression back to the dense build shows up in us_jnp and
    # in the memory pins before it OOMs a WDM32 sweep.
    for n_ch in (8, 16, 32):
        cfg_n = wdm_config(n_ch=n_ch)
        units_n = make_units(cfg_n, seed=7, n_laser=n, n_ring=n)
        sys_n = instantiate(cfg_n, units_n)
        tr_n = 5.0 * sys_n.tr_unit
        _, us_jnp = _time(ops.build_tables, sys_n.laser, sys_n.ring,
                          sys_n.fsr, tr_n, max_alias=4, backend="jnp")
        blk = type(sys_n)(*[a[:128] for a in sys_n])
        (d_i, w_i, nv_i), us_int = _time(
            ops.build_tables, blk.laser, blk.ring, blk.fsr, tr_n[:128],
            max_alias=2, backend="interpret", reps=1,
        )
        d_j, w_j, nv_j = ops.build_tables(
            blk.laser, blk.ring, blk.fsr, tr_n[:128],
            max_alias=2, backend="jnp",
        )
        fin = np.isfinite(np.asarray(d_j))
        parity = bool(
            np.array_equal(np.asarray(w_i), np.asarray(w_j))
            and np.array_equal(np.asarray(nv_i), np.asarray(nv_j))
            and np.allclose(np.asarray(d_i)[fin], np.asarray(d_j)[fin], atol=1e-5)
        )
        if not parity:
            raise AssertionError(f"table build n={n_ch}: interpret != jnp")
        rows.append(
            (f"kernel/table_build_n{n_ch}",
             {"trials": sys_n.n_trials, "us_jnp": round(us_jnp),
              "interpret_trials": 128, "us_interpret": round(us_int),
              "identical_wl": parity})
        )

    # Rank-merge streaming builder (the core jnp path sweeps run off the TPU)
    # across channel counts including the first 64-channel config: timing
    # plus the merge plan actually chosen, with a dense-oracle parity check
    # on a small trial slice at N<=32 (N=64 parity is covered by
    # tests/test_rank_merge.py; the dense tensor there is too large for a
    # timing row).
    from repro.core.search_table import (
        build_search_tables, build_search_tables_dense, merge_plan,
    )

    build_jit = jax.jit(build_search_tables)
    for n_ch in (16, 32, 64):
        cfg_n = wdm_config(n_ch=n_ch)
        units_n = make_units(cfg_n, seed=7, n_laser=n, n_ring=n)
        sys_n = instantiate(cfg_n, units_n)
        _, us_rm = _time(build_jit, sys_n, 5.0)
        plan = merge_plan(sys_n.n_trials, n_ch)
        derived = {
            "trials": sys_n.n_trials, "us_per_call": round(us_rm),
            "line_block": plan.line_block, "ring_block": plan.ring_block,
            "plan_mb": round(plan.total_bytes / 2**20, 1),
        }
        if n_ch <= 32:
            sub = type(sys_n)(*[a[:64] for a in sys_n])
            t_s = build_jit(sub, 5.0)
            t_d = build_search_tables_dense(sub, 5.0)
            parity = bool(
                np.array_equal(np.asarray(t_s.wl), np.asarray(t_d.wl))
                and np.array_equal(np.asarray(t_s.delta), np.asarray(t_d.delta),
                                   equal_nan=True)
            )
            if not parity:
                raise AssertionError(f"rank-merge n={n_ch}: stream != dense")
            derived["identical_to_dense"] = parity
        rows.append((f"kernel/table_rankmerge_n{n_ch}", derived))

    # WDM64 smoke: the first 64-channel config end to end — streaming
    # tables through the sweep engine plus one vtrs_ssm scheme point, all
    # inside the 256 MB chunk budget (LtC conditioning: the int32 adjacency
    # bitmask of the ideal LtA path tops out at N=32).  Trials are capped so
    # --full keeps the point inside the budget too.
    from repro.configs.wdm import WDM64_G200
    from repro.core import SweepRequest, sweep
    from repro.core.sweep import _CHUNK_BUDGET, scheme_point_bytes

    cfg64 = WDM64_G200
    m64 = min(n, 48)
    units64 = make_units(cfg64, seed=9, n_laser=m64, n_ring=m64)
    pt_bytes = scheme_point_bytes(cfg64, m64 * m64)
    if pt_bytes > _CHUNK_BUDGET:
        raise AssertionError(
            f"WDM64 scheme point {pt_bytes} B exceeds the chunk budget"
        )
    req64 = SweepRequest(
        cfg=cfg64, units=units64, scheme="vtrs_ssm",
        axes={"tr_mean": np.array([0.28 * cfg64.grid.fsr], np.float32)},
    )
    res64, us64 = _time(sweep, req64, reps=1)
    rows.append(
        ("kernel/wdm64_sweep_smoke",
         {"trials": m64 * m64, "point_mb": round(pt_bytes / 2**20, 1),
          "budget_mb": round(_CHUNK_BUDGET / 2**20, 1),
          "cafp": round(float(np.asarray(res64.data.cafp)[0]), 4),
          "afp": round(float(np.asarray(res64.data.afp)[0]), 4),
          "us_per_call": round(us64)})
    )

    # Bottleneck matching across channel counts: the retired Kuhn binary
    # search vs the current dispatch (Hall subsets at N=8, the single-pass
    # bottleneck sweep at N=16/32).  Thresholds must stay bit-identical —
    # the oracle pin is part of the benchmark, not just the test suite.
    new_fn = jax.jit(bottleneck_matching_threshold)
    kuhn_fn = jax.jit(_bottleneck_threshold_kuhn)
    for n_ch in (8, 16, 32):
        cfg_n = wdm_config(n_ch=n_ch)
        m = min(n, 16) if n_ch == 32 else n   # bound the Kuhn oracle's cost
        units_n = make_units(cfg_n, seed=5, n_laser=m, n_ring=m)
        w = scaled_residual(instantiate(cfg_n, units_n))
        new_thr, us_new = _time(new_fn, w, reps=3 if n_ch < 32 else 1)
        kuhn_thr, us_kuhn = _time(kuhn_fn, w, reps=3 if n_ch < 32 else 1)
        identical = bool(np.array_equal(np.asarray(new_thr), np.asarray(kuhn_thr)))
        if not identical:
            raise AssertionError(f"bottleneck n={n_ch}: sweep != Kuhn oracle")
        rows.append(
            (f"kernel/bottleneck_match_n{n_ch}",
             {"trials": int(w.shape[0]),
              "us_new": round(us_new), "us_kuhn": round(us_kuhn),
              "speedup_vs_kuhn": round(us_kuhn / us_new, 2),
              "identical_to_kuhn": identical})
        )

    # interpret-mode parity on a 128-trial lane block (correctness proof)
    sub = type(sys)(*[a[:128] for a in sys])
    l1, c1 = ops.feasibility(sub.laser, sub.ring, sub.fsr, sub.tr_unit, s=s,
                             backend="interpret")
    l2, c2 = ops.feasibility(sub.laser, sub.ring, sub.fsr, sub.tr_unit, s=s,
                             backend="jnp")
    parity = bool(
        np.allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
        and np.allclose(np.asarray(c1), np.asarray(c2), atol=1e-5)
    )
    rows.append(("kernel/interpret_parity", {"pass": parity}))
    return rows


def main() -> None:
    """Standalone entry: ``python -m benchmarks.kernel_bench --json kb.json``."""
    import argparse

    from .common import write_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", dest="json_out", default=None, metavar="OUT")
    args = ap.parse_args()
    t0 = time.time()
    rows = run(full=args.full)
    wall_ms = (time.time() - t0) * 1e3
    for name, derived in rows:
        print(name, derived)
    if args.json_out:
        write_json(
            args.json_out,
            [
                {"figure": "kernel_bench", "name": name,
                 "module_wall_ms": round(wall_ms, 1), "derived": derived}
                for name, derived in rows
            ],
            full=args.full,
        )


if __name__ == "__main__":
    main()
