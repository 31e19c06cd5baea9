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

from repro.configs.wdm import WDM8_G200
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


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_sweep_program_compiles_for_v5e(backend, one_chip):
    """The WDM8 ``vtrs_ssm`` grid at 24x24 trials, as ``sweep`` would run
    it, compiled for one chip: the default path has no kernel, the Pallas
    path has one per stage (table build and LtC feasibility)."""
    cfg = WDM8_G200
    units = make_units(cfg, seed=4, n_laser=24, n_ring=24)
    spacing = cfg.grid.grid_spacing
    req = SweepRequest(
        cfg=cfg, units=units, scheme="vtrs_ssm", backend=backend,
        axes={"sigma_rlv": np.array([1.0, 2.0], np.float32) * spacing,
              "tr_mean": np.array([0.4, 0.6], np.float32) * cfg.grid.fsr},
    )
    plan = _plan(req)
    cfg_arg, *dyn = plan.args
    dyn = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        dyn,
    )
    compiled = _sweep_flat.lower(cfg_arg, *dyn, **plan.kwargs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    hlo = compiled.as_text()
    for kernel in ("table_pallas", "feasibility_pallas"):
        assert (f"jit({kernel})" in hlo) == (backend == "pallas"), kernel
