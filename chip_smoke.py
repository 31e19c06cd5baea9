#!/usr/bin/env python3
"""Run the sweep engine on the TPU through its public entry points.

    python chip_smoke.py              # one chip: phases 1-5 below
    python chip_smoke.py --chips 4    # four chips: the sharded fabric sweep only

One chip:
  1. WDM32 ``vtrs_ssm`` scheme sweep at 10,000 trials per point (fig18 grid);
  2. WDM16 ``protocol_lta`` at 10,000 trials on a 2-point TR grid;
  3. the 1,008-link WDM16 fabric (``FABRIC_1K``) on a 1-device sweep mesh,
     with fig21's constraints-off parity check on every link;
  4. ``backend="pallas"`` against ``backend="jnp"`` (the kernel wrappers'
     jnp path, whose table build is the core builder), bit for bit, with
     every kernel stage present as a ``tpu_custom_call`` in the program,
     and the kernels the default ``backend=None`` runs on the chip;
  5. engine against ``sweep_reference`` on the chip (bit for bit), and the
     chip against the reference on the host CPU (differences counted).

Four chips: the fig21 fabric sweep (``FABRIC_1K``, 2 couplings x 2 TRs)
sharded over a 4-device mesh against the same request unsharded.

Each phase prints one JSON line with its cold seconds (compile included),
steady seconds, compile seconds (cold less steady) and the device's peak
bytes in use so far.  Any failed check raises, and the script exits
non-zero.  The last line is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero and prints no result: it never falls back to
the CPU.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

TRIALS = 100  # paper-scale Monte Carlo: 100 x 100 = 10,000 trials per point


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=_jsonable), flush=True)


def _jsonable(x):
    import numpy as np

    return np.asarray(x).tolist()


def _peak_bytes(dev) -> int | None:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _timed(fn):
    """(result, cold_s, steady_s): the first call compiles, the second is
    timed warm; both end in ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, cold, time.perf_counter() - t0


def _times(cold: float, steady: float) -> dict:
    return {"cold_s": cold, "steady_s": steady, "compile_s": cold - steady}


def _fields(data) -> dict:
    """Result pytree -> {field: numpy array} (a bare grid is ``data``)."""
    import numpy as np

    if hasattr(data, "_fields"):
        return {k: np.asarray(getattr(data, k)) for k in data._fields}
    return {"data": np.asarray(data)}


def _assert_equal(a, b, what: str) -> None:
    import numpy as np

    fa, fb = _fields(a), _fields(b)
    bad = [k for k in fa if not np.array_equal(fa[k], fb[k])]
    if bad:
        raise AssertionError(f"{what}: fields differ bit-wise: {bad}")


def _assert_rates(grid, what: str) -> None:
    """Finite rates in [0, 1], up to the f32 roundoff of ``1 - mean``."""
    import numpy as np

    g = np.asarray(grid)
    if not (np.all(np.isfinite(g)) and np.all((g >= -1e-6) & (g <= 1 + 1e-6))):
        raise AssertionError(f"{what}: rates outside [0, 1]: {g}")


def phase_wdm32(dev) -> None:
    import numpy as np

    from repro.configs.wdm import WDM32_G200
    from repro.core import SweepRequest, make_units, sweep

    cfg = WDM32_G200
    units = make_units(cfg, seed=21, n_laser=TRIALS, n_ring=TRIALS)
    axes = {
        "sigma_rlv": np.array([2.0], np.float32) * cfg.grid.grid_spacing,
        "tr_mean": np.array([0.25, 0.28], np.float32) * cfg.grid.fsr,
    }
    req = SweepRequest(cfg=cfg, units=units, scheme="vtrs_ssm", axes=axes)
    res, cold, steady = _timed(lambda: sweep(req))
    _assert_rates(res.data.cafp, "wdm32 cafp")
    _assert_rates(res.data.afp, "wdm32 afp")
    if res.data.cafp.shape != (1, 2):
        raise AssertionError(f"wdm32 grid shape {res.data.cafp.shape}")
    _emit("1_wdm32_vtrs_ssm", config="wdm32-g200", trials=TRIALS * TRIALS,
          grid=axes, **_times(cold, steady), peak_bytes=_peak_bytes(dev),
          cafp=res.data.cafp, afp=res.data.afp)


def phase_protocol(dev) -> None:
    import numpy as np

    from repro.configs.wdm import WDM16_G200
    from repro.core import SweepRequest, make_units, sweep

    cfg = WDM16_G200
    units = make_units(cfg, seed=16, n_laser=TRIALS, n_ring=TRIALS)
    axes = {"tr_mean": np.array([0.40, 0.46], np.float32) * cfg.grid.fsr}
    req = SweepRequest(cfg=cfg, units=units, scheme="protocol_lta", axes=axes)
    res, cold, steady = _timed(lambda: sweep(req))
    _assert_rates(res.data.cafp, "protocol cafp")
    _emit("2_wdm16_protocol_lta", config="wdm16-g200",
          trials=TRIALS * TRIALS, grid=axes, **_times(cold, steady),
          peak_bytes=_peak_bytes(dev), cafp=res.data.cafp, afp=res.data.afp)


def phase_fabric(dev) -> None:
    import numpy as np

    from benchmarks.fig21_fabric_yield import _assert_parity
    from repro.configs.fabric import FABRIC_1K
    from repro.configs.wdm import WDM16_G200
    from repro.core import SweepRequest, sweep
    from repro.fabric import make_fabric_units
    from repro.launch.mesh import make_sweep_mesh

    cfg, spec = WDM16_G200, FABRIC_1K
    units = make_fabric_units(cfg, spec, seed=33)
    tr = float(np.float32(0.40) * np.float32(cfg.grid.fsr))
    axes = {"comb_coupling": [0.0], "tr_mean": [tr]}
    req = SweepRequest(cfg=cfg, units=units, scheme="vtrs_ssm", fabric=spec,
                       axes=axes, mesh=make_sweep_mesh())
    res, cold, steady = _timed(lambda: sweep(req))
    _assert_rates(res.data.link_up, "fabric link_up")
    t0 = time.perf_counter()
    parity_links = _assert_parity(cfg, spec, tr, "vtrs_ssm", 33)
    _emit("3_fabric_1k", config="fabric1k-wdm16", links=spec.n_links,
          trials=2 * spec.n_links, grid=axes, **_times(cold, steady),
          parity_links=parity_links,
          parity_s=time.perf_counter() - t0, peak_bytes=_peak_bytes(dev),
          link_up=res.data.link_up, cafp=res.data.cafp,
          route_cont=res.data.route_cont)


#: Kernels each phase-4 request must run on the chip: with
#: ``backend="pallas"``, and with the default ``backend=None``
#: (``table_route``, and ``match_route`` for LtA at 10 < N <= 32 on
#: batches of 128 trials or more).
_PALLAS_CASES = {
    "vtrs_ssm": ("table_pallas", "feasibility_pallas"),
    "protocol_lta": ("table_pallas", "match_pallas", "research_pallas"),
    "policy_lta": ("bottleneck_pallas",),
}
_DEFAULT_KERNELS = {
    "vtrs_ssm": ("table_pallas",),
    "protocol_lta": ("match_pallas", "table_pallas"),
    "policy_lta": ("bottleneck_pallas",),
}


def _custom_call_kernels(hlo: str) -> list:
    """Names of the Pallas kernels that appear as ``tpu_custom_call``."""
    import re

    found = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            found.update(re.findall(r"jit\((\w+_pallas)\)", line))
    return sorted(found)


def phase_pallas(dev) -> None:
    import numpy as np

    from repro.configs.wdm import WDM16_G200
    from repro.core import SweepRequest, lower_sweep, make_units, sweep
    from repro.obs.phase import PhaseRecorder, use_recorder

    cfg = WDM16_G200
    units = make_units(cfg, seed=16, n_laser=TRIALS, n_ring=TRIALS)
    tr = np.array([0.40], np.float32) * cfg.grid.fsr
    for case, kernels in _PALLAS_CASES.items():
        target = ({"policy": "lta"} if case == "policy_lta"
                  else {"scheme": case})
        req = SweepRequest(cfg=cfg, units=units, axes={"tr_mean": tr},
                           backend="jnp", **target)
        ref, ref_cold, ref_steady = _timed(lambda: sweep(req))
        pal_req = req.replace(backend="pallas")
        rec = PhaseRecorder()
        with use_recorder(rec):  # collects kernel-route notes while tracing
            got, cold, steady = _timed(lambda: sweep(pal_req))
        _assert_equal(ref.data, got.data, f"pallas vs jnp ({case})")
        ran = _custom_call_kernels(lower_sweep(pal_req).compile().as_text())
        missing = sorted(set(kernels) - set(ran))
        if missing:
            raise AssertionError(f"{case}: no tpu_custom_call for {missing}")
        routes = [n for n in rec.notes if n["name"].startswith("kernels.")]
        default = _custom_call_kernels(
            lower_sweep(req.replace(backend=None)).compile().as_text())
        if default != sorted(_DEFAULT_KERNELS[case]):
            raise AssertionError(f"{case}: backend=None ran {default}, "
                                 f"not {sorted(_DEFAULT_KERNELS[case])}")
        _emit(f"4_pallas_{case}", config="wdm16-g200",
              trials=TRIALS * TRIALS, grid={"tr_mean": tr},
              jnp=_times(ref_cold, ref_steady), pallas=_times(cold, steady),
              tpu_custom_calls=ran, default_custom_calls=default,
              core_routes=routes, bit_identical=True,
              peak_bytes=_peak_bytes(dev))


def _count_diffs(a, b) -> dict:
    """Per field: [how many grid cells (or trials, for per-trial fields)
    differ, the largest absolute difference] between two results."""
    import numpy as np

    fa, fb = _fields(a), _fields(b)
    return {k: [int(np.sum(fa[k] != fb[k])),
                float(np.max(np.abs(fa[k].astype(np.float64)
                                    - fb[k].astype(np.float64))))]
            for k in fa}


def phase_reference(dev) -> None:
    import jax
    import numpy as np

    from repro.configs.wdm import WDM8_G200
    from repro.core import SweepRequest, make_units, sweep, sweep_reference

    cfg = WDM8_G200
    cpu = jax.devices("cpu")[0]
    units = make_units(cfg, seed=4, n_laser=24, n_ring=24)
    axes = {
        "sigma_rlv": np.array([1.0, 2.0], np.float32) * cfg.grid.grid_spacing,
        "tr_mean": np.array([0.4, 0.6], np.float32) * cfg.grid.fsr,
    }
    for target in ({"scheme": "vtrs_ssm"}, {"scheme": "protocol_lta"},
                   {"policy": "lta"}):
        req = SweepRequest(cfg=cfg, units=units, axes=axes, **target)
        got, cold, steady = _timed(lambda: sweep(req))
        ref = sweep_reference(req)
        _assert_equal(ref.data, got.data, f"engine vs reference ({target})")
        with jax.default_device(cpu):
            host = sweep_reference(req.replace(units=jax.device_put(units, cpu)))
        _emit("5_reference", config="wdm8-g200", trials=24 * 24, grid=axes,
              **target, **_times(cold, steady), engine_equals_reference=True,
              chip_vs_cpu_diffs=_count_diffs(got.data, host.data),
              peak_bytes=_peak_bytes(dev))


def phase_mesh4(devs) -> None:
    import numpy as np

    from repro.configs.fabric import FABRIC_1K
    from repro.configs.wdm import WDM16_G200
    from repro.core import SweepRequest, sweep
    from repro.fabric import make_fabric_units
    from repro.launch.mesh import make_sweep_mesh

    cfg, spec = WDM16_G200, FABRIC_1K
    units = make_fabric_units(cfg, spec, seed=33)
    axes = {"comb_coupling": np.array([0.0, 1.0], np.float32),
            "tr_mean": np.array([0.40, 0.46], np.float32) * cfg.grid.fsr}
    req = SweepRequest(cfg=cfg, units=units, scheme="vtrs_ssm", fabric=spec,
                       axes=axes)
    base, base_cold, base_steady = _timed(lambda: sweep(req))
    mesh_req = req.replace(mesh=make_sweep_mesh(4))
    got, cold, steady = _timed(lambda: sweep(mesh_req))
    _assert_equal(base.data, got.data, "4-device mesh vs unsharded")
    _assert_rates(got.data.link_up, "mesh link_up")
    _emit("mesh4_fabric_1k", config="fabric1k-wdm16", links=spec.n_links,
          grid=axes, unsharded=_times(base_cold, base_steady),
          mesh4=_times(cold, steady), bit_identical=True,
          peak_bytes_per_device=[_peak_bytes(d) for d in devs],
          link_up=got.data.link_up, cafp=got.data.cafp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded fabric sweep on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              "this script does not fall back to the CPU", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 1
    _emit("setup", compile_cache=cache_dir, jax=jax.__version__)
    if args.chips == 4:
        phase_mesh4(devs[:4])
    else:
        for phase in (phase_wdm32, phase_protocol, phase_fabric,
                      phase_pallas, phase_reference):
            phase(devs[0])
    # The LM-era launchers rewrite XLA_FLAGS on import: never on this path.
    stray = [m for m in ("repro.launch.dryrun", "repro.launch.perf")
             if m in sys.modules]
    if stray:
        raise AssertionError(f"chip path imported {stray}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
