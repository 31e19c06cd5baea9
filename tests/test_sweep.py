"""Sweep-engine golden tests: the batched engine must match the per-point
reference loop bit-for-bit, the vectorized relation search must match the
per-position loop, the Hall matching fast path must match Kuhn, and the
kernel wrappers must stay vmap-safe (the engine maps them over grid points)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.wdm import WDM8_G200
from repro.core import (
    ArbitrationConfig,
    evaluate_scheme,
    make_units,
    register_scheme,
    registered_schemes,
    sweep_grid,
    sweep_grid_reference,
    sweep_min_tr,
    sweep_policy,
    sweep_scheme,
)
from repro.core import matching
from repro.core.relation import chain_spec, relation_search, relation_search_loop
from repro.core.reach import reach_matrix, scaled_residual
from repro.core.sampling import instantiate
from repro.core.search_table import build_search_tables
from repro.core.sequential import sequential_tuning

RLVS = np.array([0.28, 1.12, 2.24], np.float32)
TRS = np.array([2.0, 5.0, 9.5], np.float32)
AXES = {"sigma_rlv": RLVS, "tr_mean": TRS}


def _units(cfg, seed=4, n=6):
    return make_units(cfg, seed=seed, n_laser=n, n_ring=n)


# ---------------------------------------------------------------- engine ---

def test_policy_sweep_bit_exact_both_paths():
    """Engine == reference loop, with and without the TR fast path."""
    cfg = WDM8_G200
    units = _units(cfg)
    for policy in ("lta", "ltc", "ltd"):
        ref = np.asarray(sweep_grid_reference(cfg, units, AXES, policy=policy))
        fast = np.asarray(sweep_policy(cfg, units, policy, AXES))
        direct = np.asarray(sweep_policy(cfg, units, policy, AXES, tr_fast=False))
        assert np.array_equal(fast, ref), policy
        assert np.array_equal(direct, ref), policy


@pytest.mark.parametrize("scheme", ["seq", "vtrs_ssm"])
def test_scheme_sweep_bit_exact(scheme):
    cfg = WDM8_G200.with_orders("permuted")
    units = _units(cfg)
    res = sweep_scheme(cfg, units, scheme, AXES)
    ref = sweep_grid_reference(cfg, units, AXES, scheme=scheme)
    for field in res._fields:
        a = np.asarray(getattr(res, field))
        b = np.asarray(getattr(ref, field))
        assert np.array_equal(a, b), (scheme, field)


def test_scheme_sweep_fixed_overrides_bit_exact():
    cfg = WDM8_G200
    units = _units(cfg)
    fixed = {"sigma_fsr_frac": 0.05, "sigma_tr_frac": 0.20}
    res = sweep_scheme(cfg, units, "rs_ssm", {"tr_mean": TRS}, fixed=fixed)
    ref = sweep_grid_reference(cfg, units, {"tr_mean": TRS}, scheme="rs_ssm", fixed=fixed)
    assert np.array_equal(np.asarray(res.cafp), np.asarray(ref.cafp))


def test_min_tr_sweep_bit_exact():
    cfg = WDM8_G200
    units = _units(cfg)
    axes = {"fsr_mean": np.array([6.72, 8.96, 15.68], np.float32)}
    for policy in ("lta", "ltc"):
        got = np.asarray(sweep_min_tr(cfg, units, policy, axes))
        ref = np.asarray(
            sweep_grid_reference(cfg, units, axes, policy=policy, metric="min_tr")
        )
        assert np.array_equal(got, ref), policy


def test_sweep_chunking_invariant():
    """Chunk size is a pure performance knob: results are identical."""
    cfg = WDM8_G200
    units = _units(cfg)
    base = np.asarray(sweep_policy(cfg, units, "ltd", AXES))
    for chunk in (1, 9):
        got = np.asarray(sweep_policy(cfg, units, "ltd", AXES, chunk_size=chunk))
        assert np.array_equal(got, base), chunk


def test_sweep_single_axis_and_tr_only():
    """A tr_mean-only axis exercises the fast path's empty-sigma branch."""
    cfg = WDM8_G200
    units = _units(cfg)
    got = np.asarray(sweep_policy(cfg, units, "ltc", {"tr_mean": TRS}))
    ref = np.asarray(sweep_grid_reference(cfg, units, {"tr_mean": TRS}, policy="ltc"))
    assert np.array_equal(got, ref)


def test_sweep_axis_order_follows_axes_mapping():
    cfg = WDM8_G200
    units = _units(cfg)
    a = np.asarray(sweep_policy(cfg, units, "ltd", {"sigma_rlv": RLVS, "tr_mean": TRS}))
    b = np.asarray(sweep_policy(cfg, units, "ltd", {"tr_mean": TRS, "sigma_rlv": RLVS}))
    assert a.shape == (len(RLVS), len(TRS))
    assert b.shape == (len(TRS), len(RLVS))
    assert np.array_equal(a, b.T)


def test_sweep_backend_jnp_bit_exact():
    cfg = WDM8_G200
    units = _units(cfg)
    ref = np.asarray(sweep_grid_reference(cfg, units, AXES, policy="ltc"))
    got = np.asarray(sweep_policy(cfg, units, "ltc", AXES, backend="jnp"))
    assert np.array_equal(got, ref)
    res = sweep_scheme(cfg, units, "vtrs_ssm", {"tr_mean": TRS[:2]}, backend="jnp")
    sref = sweep_grid_reference(cfg, units, {"tr_mean": TRS[:2]}, scheme="vtrs_ssm")
    assert np.array_equal(np.asarray(res.cafp), np.asarray(sref.cafp))


def test_sweep_validation_errors():
    cfg = WDM8_G200
    units = _units(cfg, n=2)
    with pytest.raises(ValueError, match="exactly one"):
        sweep_grid(cfg, units, AXES)
    with pytest.raises(ValueError, match="unknown sweep axis"):
        sweep_policy(cfg, units, "ltc", {"bogus": RLVS})
    with pytest.raises(ValueError, match="cannot be an axis"):
        sweep_min_tr(cfg, units, "ltc", AXES)
    with pytest.raises(ValueError, match="overlap"):
        sweep_policy(cfg, units, "ltc", AXES, fixed={"sigma_rlv": 1.0})


def test_sweep_reference_validates_like_engine():
    """The oracle must reject exactly what the engine rejects (same shared
    validation): bad fixed names, axes/fixed overlap, metric misuse."""
    cfg = WDM8_G200
    units = _units(cfg, n=2)
    for call in (sweep_grid, sweep_grid_reference):
        with pytest.raises(ValueError, match="exactly one"):
            call(cfg, units, AXES)
        with pytest.raises(ValueError, match="unknown sweep axis"):
            call(cfg, units, {"tr_mean": TRS}, policy="ltc", fixed={"bogus": 1.0})
        with pytest.raises(ValueError, match="overlap"):
            call(cfg, units, AXES, policy="ltc", fixed={"sigma_rlv": 1.0})
        with pytest.raises(ValueError, match="unknown metric"):
            call(cfg, units, AXES, policy="ltc", metric="nope")
        with pytest.raises(ValueError, match="cannot be an axis"):
            call(cfg, units, AXES, policy="ltc", metric="min_tr")
        with pytest.raises(ValueError, match="policy sweeps"):
            call(cfg, units, {"sigma_rlv": RLVS}, scheme="seq", metric="min_tr")


# ---------------------------------------------------------- sharded mesh ---

def test_sweep_mesh_sharded_bit_exact_in_process():
    """shard_map over a 1-device host mesh == unsharded engine, for both a
    policy grid and a scheme EvalResult pytree."""
    from repro.launch.mesh import make_sweep_mesh

    cfg = WDM8_G200
    units = _units(cfg)
    mesh = make_sweep_mesh()
    base = np.asarray(sweep_policy(cfg, units, "ltc", AXES))
    got = np.asarray(sweep_policy(cfg, units, "ltc", AXES, mesh=mesh, chunk_size=2))
    assert np.array_equal(got, base)
    r0 = sweep_scheme(cfg, units, "seq", {"tr_mean": TRS})
    r1 = sweep_scheme(cfg, units, "seq", {"tr_mean": TRS}, mesh=mesh, chunk_size=2)
    for field in r0._fields:
        assert np.array_equal(
            np.asarray(getattr(r0, field)), np.asarray(getattr(r1, field))
        ), field


def test_sweep_mesh_must_be_1d():
    cfg = WDM8_G200
    units = _units(cfg, n=2)
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh2d = jax.sharding.Mesh(devs, ("a", "b"))
    with pytest.raises(ValueError, match="1-D"):
        sweep_policy(cfg, units, "ltc", AXES, mesh=mesh2d)


def test_sweep_mesh_size_invariance_subprocess():
    """Mesh size is a pure performance knob: 1-device and 8-placeholder-
    device grids are bit-identical to the unsharded engine (wdm16, so the
    N > 10 bottleneck sweep runs under shard_map too).  Subprocess because
    jax locks the host device count at first init; the child is held to the
    CPU so that it never tries to open an accelerator its parent holds."""
    import os
    import subprocess
    import sys as _sys
    from pathlib import Path

    script = """
import numpy as np
from repro.configs.wdm import WDM16_G200
from repro.core import make_units, sweep_policy
from repro.launch.mesh import make_sweep_mesh

cfg = WDM16_G200
units = make_units(cfg, seed=4, n_laser=5, n_ring=5)
axes = {"sigma_rlv": np.array([0.28, 2.24], np.float32),
        "tr_mean": np.array([4.0, 9.5], np.float32)}
base = np.asarray(sweep_policy(cfg, units, "lta", axes))
for nd in (1, 8):
    got = np.asarray(
        sweep_policy(cfg, units, "lta", axes, mesh=make_sweep_mesh(nd), chunk_size=1)
    )
    assert np.array_equal(got, base), nd
print("MESH_INVARIANT_OK")
"""
    root = Path(__file__).resolve().parent.parent
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=str(root / "src") + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    out = subprocess.run(
        [_sys.executable, "-c", script],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_INVARIANT_OK" in out.stdout


# ---------------------------------------------------- streaming tables ---

def test_streaming_tables_multi_block_merge_matches_dense():
    """Non-degenerate streaming: at this shape ``merge_plan`` splits the
    build into 32 fori_loop steps (line_block=8, ring_block=1), so the
    cross-block stable-merge/tie-order logic itself runs — not the
    single-sort degenerate case that small test shapes collapse to.
    (Lives here rather than test_property.py so it runs even where
    hypothesis is unavailable.)"""
    from functools import partial

    from repro.core import DWDMGrid
    from repro.core.search_table import (
        build_search_tables,
        build_search_tables_dense,
        merge_plan,
    )

    cfg = ArbitrationConfig(grid=DWDMGrid(n_ch=16))
    sys = instantiate(cfg, make_units(cfg, 13, 32, 32))  # T = 1024
    T, N = sys.laser.shape
    plan = merge_plan(T, N, max_alias=8)
    steps = (N // plan.line_block) * (N // plan.ring_block)
    assert steps > 1, plan  # the point of this test: a real multi-step merge

    @partial(jax.jit, static_argnames=("has_vis",))
    def both(s, vis, has_vis):
        v = vis if has_vis else None
        return (build_search_tables(s, 9.5, visible=v, max_alias=8),
                build_search_tables_dense(s, 9.5, visible=v, max_alias=8))

    for vis in (None, jax.random.bernoulli(jax.random.key(3), 0.6, (T, N, N))):
        stream, dense = both(
            sys, vis if vis is not None else jnp.zeros(()), vis is not None
        )
        assert np.array_equal(np.asarray(stream.wl), np.asarray(dense.wl))
        assert np.array_equal(np.asarray(stream.n_valid), np.asarray(dense.n_valid))
        assert np.array_equal(
            np.asarray(stream.delta), np.asarray(dense.delta), equal_nan=True
        )


# ------------------------------------------------------- relation search ---

@pytest.mark.parametrize("kind", ["natural", "permuted"])
@pytest.mark.parametrize("vt", [False, True])
def test_relation_search_vectorized_matches_loop(kind, vt):
    cfg = ArbitrationConfig().with_orders(kind)
    for seed, tr_mean in ((0, 3.0), (1, 9.5)):
        sys = instantiate(cfg, make_units(cfg, seed, 5, 5))
        tables = build_search_tables(sys, tr_mean, max_alias=cfg.max_fsr_alias)
        spec = chain_spec(cfg.s)
        vec = np.asarray(relation_search(tables, spec, variation_tolerant=vt))
        loop = np.asarray(relation_search_loop(tables, spec, variation_tolerant=vt))
        assert np.array_equal(vec, loop), (seed, tr_mean)


# ------------------------------------------------------------- hall path ---

def _kuhn_bottleneck(w):
    """Value oracle: binary search over sorted edge weights with Kuhn
    matching existence checks (the pre-Hall reference implementation)."""
    import math

    T, N, _ = w.shape
    cand = np.sort(np.asarray(w).reshape(T, N * N), axis=1)
    lo = np.zeros(T, np.int32)
    hi = np.full(T, N * N - 1, np.int32)
    for _ in range(int(math.ceil(math.log2(N * N))) + 1):
        mid = (lo + hi) // 2
        thr = cand[np.arange(T), mid]
        adj = matching.adjacency_bitmask(jnp.asarray(w) <= thr[:, None, None])
        mw, _ = matching.max_matching(adj)
        ok = np.asarray(jnp.all(mw >= 0, axis=1))
        lo = np.where(ok, lo, mid + 1)
        hi = np.where(ok, mid, hi)
    return cand[np.arange(T), hi]


def test_hall_matching_matches_kuhn():
    cfg = WDM8_G200
    sys = instantiate(cfg, make_units(cfg, 3, 6, 6))
    w = scaled_residual(sys)
    hall_thr = np.asarray(matching._bottleneck_threshold_hall(w))
    # value-level oracle: the Hall threshold is bit-for-bit the binary-search
    # result (an actual edge weight), not merely consistent at spot TRs
    assert np.array_equal(hall_thr, _kuhn_bottleneck(w))
    for tr in (2.0, 4.0, 8.96):
        reach = reach_matrix(sys, tr)
        hall = np.asarray(matching._has_perfect_matching_hall(reach))
        adj = matching.adjacency_bitmask(reach)
        mw, _ = matching.max_matching(adj)
        kuhn = np.asarray(jnp.all(mw >= 0, axis=1))
        assert np.array_equal(hall, kuhn), tr
        # threshold form consistent with existence form at every TR
        assert np.array_equal(hall_thr <= tr, kuhn), tr


# ----------------------------------------------------------- ops vmap -----

def test_ops_wrappers_vmap_safe_jnp():
    from repro.kernels import ops
    from repro.core import DWDMGrid

    cfg = ArbitrationConfig(grid=DWDMGrid(n_ch=4))
    sys = instantiate(cfg, make_units(cfg, 0, 3, 3))
    s = tuple(int(v) for v in cfg.s)
    scales = jnp.asarray([0.9, 1.0, 1.1])

    ltd, ltc = jax.vmap(
        lambda t: ops.feasibility(sys.laser, sys.ring, sys.fsr, sys.tr_unit * t,
                                  s=s, backend="jnp")
    )(scales)
    assert ltd.shape == (3, sys.n_trials)
    # batch slice 1.0 must equal the unbatched call
    l0, c0 = ops.feasibility(sys.laser, sys.ring, sys.fsr, sys.tr_unit, s=s,
                             backend="jnp")
    assert np.array_equal(np.asarray(ltc[1]), np.asarray(c0))

    d, w, nv = jax.vmap(
        lambda t: ops.build_tables(sys.laser, sys.ring, sys.fsr, t * sys.tr_unit,
                                   max_alias=4, backend="jnp")
    )(jnp.asarray([4.0, 5.0]))
    d0, w0, nv0 = ops.build_tables(sys.laser, sys.ring, sys.fsr, 5.0 * sys.tr_unit,
                                   max_alias=4, backend="jnp")
    assert np.array_equal(np.asarray(nv[1]), np.asarray(nv0))
    assert np.array_equal(np.asarray(w[1]), np.asarray(w0))

    adj = matching.adjacency_bitmask(reach_matrix(sys, 4.0))
    mw, ok = jax.vmap(lambda _: ops.perfect_matching(adj, backend="jnp"))(
        jnp.arange(2)
    )
    mw0, ok0 = ops.perfect_matching(adj, backend="jnp")
    assert np.array_equal(np.asarray(ok[0]), np.asarray(ok0))


@pytest.mark.slow
def test_ops_wrappers_vmap_safe_interpret():
    from repro.kernels import ops

    cfg = ArbitrationConfig()
    sys = instantiate(cfg, make_units(cfg, 0, 3, 3))
    s = tuple(int(v) for v in cfg.s)
    ltd, ltc = jax.vmap(
        lambda t: ops.feasibility(sys.laser, sys.ring, sys.fsr, sys.tr_unit * t,
                                  s=s, backend="interpret")
    )(jnp.asarray([1.0, 1.1]))
    l0, c0 = ops.feasibility(sys.laser, sys.ring, sys.fsr, sys.tr_unit, s=s,
                             backend="interpret")
    np.testing.assert_allclose(np.asarray(ltd[0]), np.asarray(l0), atol=1e-5)


# ------------------------------------------------------------- registry ---

def test_scheme_registry_round_trip():
    name = "test_seq_clone"
    if name not in registered_schemes():
        register_scheme(name, lambda cfg, tables, spec: sequential_tuning(tables, spec))
    cfg = WDM8_G200
    units = _units(cfg, n=4)
    # registered schemes work through the sweep engine exactly like built-ins
    ra = sweep_scheme(cfg, units, name, {"tr_mean": TRS[:2]})
    rb = sweep_scheme(cfg, units, "seq", {"tr_mean": TRS[:2]})
    assert np.array_equal(np.asarray(ra.cafp), np.asarray(rb.cafp))


def test_scheme_registry_errors():
    with pytest.raises(ValueError, match="already registered"):
        register_scheme("seq", lambda cfg, tables, spec: None)
    with pytest.raises(ValueError, match="unknown scheme"):
        cfg = WDM8_G200
        evaluate_scheme(cfg, _units(cfg, n=2), "no_such_scheme", 5.0)
    with pytest.raises(ValueError, match="policy"):
        register_scheme("bad_policy_scheme", lambda c, t, s: None, policy="nope")


# ------------------------------------------------- backend -> arbiters ---

_SPY_BACKENDS = []


def test_backend_reaches_registered_arbiter():
    """SweepRequest.backend is forwarded into the scheme's arbiter.

    The spy arbiter records the backend value it receives at trace time;
    the legacy 3-arg lambda above (``test_seq_clone``) proves old-style
    arbiters still register (``_normalize_arbiter`` swallows the kwarg)."""
    name = "test_backend_spy"
    if name not in registered_schemes():
        def spy(cfg, tables, spec, *, backend=None):
            _SPY_BACKENDS.append(backend)
            return sequential_tuning(tables, spec)

        register_scheme(name, spy)
    cfg = WDM8_G200
    units = _units(cfg, n=4)
    _SPY_BACKENDS.clear()
    sweep_scheme(cfg, units, name, {"tr_mean": TRS[:1]}, backend="jnp")
    assert "jnp" in _SPY_BACKENDS
    _SPY_BACKENDS.clear()
    sweep_scheme(cfg, units, name, {"tr_mean": TRS[:2]})
    assert _SPY_BACKENDS and set(_SPY_BACKENDS) == {None}


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_protocol_scheme_backend_parity(backend):
    """A *registered* protocol scheme honors SweepRequest.backend — the
    kernel-backed masked re-search loop must match core jnp bit-for-bit."""
    cfg = WDM8_G200
    units = _units(cfg, n=4)
    axes = {"tr_mean": TRS[:2]}
    base = sweep_scheme(cfg, units, "protocol_lta_h1", axes)
    got = sweep_scheme(cfg, units, "protocol_lta_h1", axes, backend=backend)
    for field in ("cafp", "afp", "lock_err", "order_err"):
        a = np.asarray(getattr(got, field))
        b = np.asarray(getattr(base, field))
        assert np.array_equal(a, b), (backend, field)
