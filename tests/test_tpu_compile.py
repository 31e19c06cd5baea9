"""Compile the Pallas kernels and one sweep program for a described TPU v5e.

Nothing runs: the TPU compiler checks, at real widths, what interpret mode
cannot (Mosaic lowering, tiling, VMEM limits).  The topology is described
inside a fixture, never at import, so that every pytest worker collects the
same tests and only the worker that runs this file loads the TPU library.
All compiles stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.wdm import WDM8_G200, WDM16_G200
from repro.core import SweepRequest, make_units
from repro.core.sweep import _plan, _sweep_flat
from repro.kernels.bitmask_match import bottleneck_pallas, match_pallas
from repro.kernels.feasibility import feasibility_pallas
from repro.kernels.probe import research_pallas
from repro.kernels.table_build import table_pallas

T = 1024  # trials: 8 lane blocks of 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _kernel_args(name, n, sds):
    f32 = lambda shape: sds(shape, jnp.float32)  # noqa: E731
    i32 = lambda shape: sds(shape, jnp.int32)  # noqa: E731
    if name == "feasibility":
        return feasibility_pallas, [f32((n, T))] * 4, {"s": tuple(range(n))}
    if name == "match":
        return match_pallas, [i32((n, T))], {}
    if name == "bottleneck":
        return bottleneck_pallas, [f32((n, n, T))], {}
    if name == "research":
        return research_pallas, [i32((n, 3 * n, T)), i32((n, T)), i32((n, T))], {}
    return table_pallas, [f32((n, T))] * 4, {"max_alias": 8}


@pytest.mark.parametrize("n", [8, 16, 32])
@pytest.mark.parametrize(
    "kernel", ["feasibility", "match", "bottleneck", "research", "table"]
)
def test_kernel_compiles_for_v5e(kernel, n, one_chip):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    fn, args, kw = _kernel_args(kernel, n, sds)
    hlo = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in hlo


def _compiled(req, one_chip):
    """The program ``sweep(req)`` would run, compiled for one chip."""
    plan = _plan(req)
    cfg_arg, *dyn = plan.args
    dyn = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        dyn,
    )
    return _sweep_flat.lower(cfg_arg, *dyn, **plan.kwargs).compile()


def _compiled_sweep(backend, one_chip):
    cfg = WDM8_G200
    units = make_units(cfg, seed=4, n_laser=24, n_ring=24)
    spacing = cfg.grid.grid_spacing
    req = SweepRequest(
        cfg=cfg, units=units, scheme="vtrs_ssm", backend=backend,
        axes={"sigma_rlv": np.array([1.0, 2.0], np.float32) * spacing,
              "tr_mean": np.array([0.4, 0.6], np.float32) * cfg.grid.fsr},
    )
    return _compiled(req, one_chip)


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_sweep_program_compiles_for_v5e(backend, one_chip):
    """The WDM8 ``vtrs_ssm`` grid at 24x24 trials, as ``sweep`` would run
    it, compiled for one chip from this CPU process: the default path here
    is the core one and has no kernel, the Pallas path has one per stage
    (table build and LtC feasibility)."""
    compiled = _compiled_sweep(backend, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    hlo = compiled.as_text()
    for kernel in ("table_pallas", "feasibility_pallas"):
        assert (f"jit({kernel})" in hlo) == (backend == "pallas"), kernel


def test_default_sweep_on_a_tpu_builds_tables_with_the_kernel(one_chip,
                                                             monkeypatch):
    """Where the default platform is a TPU, ``backend=None`` compiles the
    table build as the Pallas kernel and keeps the core path for ideal
    scoring (no feasibility kernel)."""
    from repro.kernels import ops

    # The route is taken while tracing: trace afresh, and leave no program
    # traced for a TPU behind for the CPU tests that follow.
    jax.clear_caches()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    try:
        hlo = _compiled_sweep(None, one_chip).as_text()
    finally:
        jax.clear_caches()
    assert "jit(table_pallas)" in hlo
    assert "jit(feasibility_pallas)" not in hlo


def test_default_lta_sweep_on_a_tpu_runs_the_bottleneck_kernel(one_chip,
                                                               monkeypatch):
    """Where the default platform is a TPU, ``backend=None`` compiles the
    WDM16 LtA min-TR sweep (``wdm16-lta-mintr``'s request, at 24x24 trials)
    with the Pallas bottleneck kernel, and builds no table."""
    from repro.kernels import ops

    cfg = WDM16_G200
    req = SweepRequest(
        cfg=cfg, units=make_units(cfg, seed=4, n_laser=24, n_ring=24),
        policy="lta", metric="min_tr",
        axes={"sigma_rlv": np.array([2.0], np.float32) * cfg.grid.grid_spacing},
    )
    jax.clear_caches()  # as above: the route is taken while tracing
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    try:
        hlo = _compiled(req, one_chip).as_text()
    finally:
        jax.clear_caches()
    assert "jit(bottleneck_pallas)" in hlo
    assert "jit(table_pallas)" not in hlo
