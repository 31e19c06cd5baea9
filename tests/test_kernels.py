"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept across channel counts, trial counts (incl. non-multiples of the lane
block), orderings and tuning ranges."""
import numpy as np
import pytest

from repro.core import ArbitrationConfig, DWDMGrid, make_units, permuted_order
from repro.core.matching import adjacency_bitmask
from repro.core.reach import reach_matrix, scaled_residual
from repro.core.sampling import instantiate
from repro.kernels import ops


# n=10 -> 100 trials: fits one 128-lane interpret block (half the cost
# of the previous 144-trial default) with identical coverage.
def _sys(n_ch=8, seed=0, n=10, kind="natural"):
    cfg = ArbitrationConfig(grid=DWDMGrid(n_ch=n_ch)).with_orders(kind)
    units = make_units(cfg, seed=seed, n_laser=n, n_ring=n)
    return cfg, instantiate(cfg, units)


@pytest.mark.parametrize("n_ch", [4, 8, pytest.param(16, marks=pytest.mark.slow)])
@pytest.mark.parametrize("kind", ["natural", "permuted"])
def test_feasibility_kernel(n_ch, kind):
    cfg, sys = _sys(n_ch=n_ch, kind=kind)
    s = tuple(int(v) for v in cfg.s)
    args = (sys.laser, sys.ring, sys.fsr, sys.tr_unit)
    ltd_k, ltc_k = ops.feasibility(*args, s=s, backend="interpret")
    ltd_r, ltc_r = ops.feasibility(*args, s=s, backend="jnp")
    np.testing.assert_allclose(np.asarray(ltd_k), np.asarray(ltd_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ltc_k), np.asarray(ltc_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_trials", [7, 128, 150])
def test_feasibility_kernel_padding(n_trials):
    """Trial counts that are not lane-block multiples survive padding."""
    import math

    n = max(2, int(math.isqrt(n_trials)))
    cfg, sys = _sys(n=n)
    t = min(n_trials, sys.n_trials)
    sub = type(sys)(*[a[:t] for a in sys])
    s = tuple(int(v) for v in cfg.s)
    args = (sub.laser, sub.ring, sub.fsr, sub.tr_unit)
    ltd_k, ltc_k = ops.feasibility(*args, s=s, backend="interpret")
    ltd_r, ltc_r = ops.feasibility(*args, s=s, backend="jnp")
    assert ltd_k.shape == (t,)
    np.testing.assert_allclose(np.asarray(ltd_k), np.asarray(ltd_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ltc_k), np.asarray(ltc_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_ch", [4, 8, pytest.param(16, marks=pytest.mark.slow)])
@pytest.mark.parametrize("tr_mean", [2.0, 4.5, 9.0])
def test_match_kernel(n_ch, tr_mean):
    _, sys = _sys(n_ch=n_ch, seed=1)
    adj = adjacency_bitmask(reach_matrix(sys, tr_mean))
    mw_k, ok_k = ops.perfect_matching(adj, backend="interpret")
    mw_r, ok_r = ops.perfect_matching(adj, backend="jnp")
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
    # Where a perfect matching exists both must produce a *valid* one.
    okk = np.asarray(ok_k)
    mwk = np.asarray(mw_k)
    adj_np = np.asarray(adj)
    for t in np.where(okk)[0][:32]:
        wl = mwk[t]
        assert len(set(wl.tolist())) == n_ch          # all distinct lines
        for i in range(n_ch):
            assert (adj_np[t, i] >> wl[i]) & 1 == 1   # edges exist


@pytest.mark.parametrize("n_ch", [8, 16])
def test_bottleneck_kernel(n_ch):
    """Bottleneck sweep kernel (interpret) vs the jnp dispatch — N=8 crosses
    the Hall path, N=16 the core single-pass sweep; all bit-identical."""
    _, sys = _sys(n_ch=n_ch, seed=3, n=6)        # 36 trials, one padded block
    w = scaled_residual(sys)
    thr_k = ops.bottleneck_threshold(w, backend="interpret")
    thr_r = ops.bottleneck_threshold(w, backend="jnp")
    np.testing.assert_array_equal(np.asarray(thr_k), np.asarray(thr_r))


@pytest.mark.parametrize("n_ch", [4, 8, pytest.param(16, marks=pytest.mark.slow)])
@pytest.mark.parametrize("tr_mean", [2.0, 5.0, 9.5])
@pytest.mark.parametrize("max_alias", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_table_kernel(n_ch, tr_mean, max_alias):
    _, sys = _sys(n_ch=n_ch, seed=2)
    tr = tr_mean * sys.tr_unit
    args = (sys.laser, sys.ring, sys.fsr, tr)
    d_k, w_k, nv_k = ops.build_tables(*args, max_alias=max_alias, backend="interpret")
    d_r, w_r, nv_r = ops.build_tables(*args, max_alias=max_alias, backend="jnp")
    np.testing.assert_array_equal(np.asarray(nv_k), np.asarray(nv_r))
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
    fin = np.isfinite(np.asarray(d_r))
    np.testing.assert_allclose(np.asarray(d_k)[fin], np.asarray(d_r)[fin], atol=1e-5)


def test_table_kernel_multi_group_merge(monkeypatch):
    """Force a multi-group alias merge at CI-affordable size: with the VMEM
    row bound shrunk to 64, n_ch=8 / max_alias=8 splits into 4 merge steps
    per ring (alias_group=5), exercising the cross-group top-E buffer logic
    that the default test shapes collapse to a single sort."""
    from repro.kernels import table_build

    monkeypatch.setattr(table_build, "_VMEM_ROWS", 64)
    table_build.table_pallas.clear_cache()  # drop single-sort compilations
    try:
        _, sys = _sys(n_ch=8, seed=6, n=8)  # 64 trials, padded to one block
        tr = 9.5 * sys.tr_unit              # TR ~ FSR: multi-alias entries
        args = (sys.laser, sys.ring, sys.fsr, tr)
        d_k, w_k, nv_k = ops.build_tables(*args, max_alias=8, backend="interpret")
        d_r, w_r, nv_r = ops.build_tables(*args, max_alias=8, backend="jnp")
        np.testing.assert_array_equal(np.asarray(nv_k), np.asarray(nv_r))
        np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
        fin = np.isfinite(np.asarray(d_r))
        np.testing.assert_allclose(
            np.asarray(d_k)[fin], np.asarray(d_r)[fin], atol=1e-5
        )
    finally:
        table_build.table_pallas.clear_cache()


@pytest.mark.parametrize("vis_ndim", [2, 3])
def test_table_kernel_visible_masks(vis_ndim):
    """Visible-masked re-search through the kernel wrappers: interpret-mode
    streaming merge vs the jnp streaming builder, with bus-wide (2-D) and
    per-ring (3-D) masks including fully-masked rings (n_valid == 0)."""
    import jax

    _, sys = _sys(n_ch=8, seed=5)
    T, N = sys.laser.shape
    shape = (T, N) if vis_ndim == 2 else (T, N, N)
    vis = jax.random.bernoulli(jax.random.key(0), 0.5, shape)
    if vis_ndim == 3:
        vis = vis.at[: T // 2].set(False)
    tr = 5.0 * sys.tr_unit
    args = (sys.laser, sys.ring, sys.fsr, tr)
    d_k, w_k, nv_k = ops.build_tables(
        *args, visible=vis, max_alias=2, backend="interpret"
    )
    d_r, w_r, nv_r = ops.build_tables(*args, visible=vis, max_alias=2, backend="jnp")
    np.testing.assert_array_equal(np.asarray(nv_k), np.asarray(nv_r))
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
    fin = np.isfinite(np.asarray(d_r))
    np.testing.assert_allclose(np.asarray(d_k)[fin], np.asarray(d_r)[fin], atol=1e-5)
    if vis_ndim == 3:
        assert int(np.asarray(nv_r)[: T // 2].max()) == 0


@pytest.mark.parametrize("vis_ndim", [None, 2, 3])
@pytest.mark.parametrize("n_ch", [8, 16])
@pytest.mark.parametrize("platform, route", [("tpu", "pallas"), ("cpu", "core")])
def test_default_table_route_follows_the_platform(platform, route, n_ch,
                                                  vis_ndim, monkeypatch):
    """``backend=None`` builds tables with the Pallas kernel on a TPU and
    with the core rank-merge elsewhere, notes the route it took, and gives
    ``build_search_tables``'s tables bit for bit.  The Pallas route runs
    here in interpret mode.

    XLA's CPU backend contracts ``(laser - ring) - j * fsr`` into a fused
    multiply-add in some compiled programs and not in others, so two
    builders compiled for the CPU can differ in a delta's last bit.  The
    inputs are therefore rounded to multiples of 2**-12 nm, on which every
    term is exact in f32 with or without the contraction: what is left to
    differ is which candidates a builder keeps and in what order."""
    import jax

    from repro.core.api import _build_tables
    from repro.core.search_table import build_search_tables
    from repro.obs import PhaseRecorder, use_recorder

    called = []
    build_tables = ops.build_tables

    def interpreted(*args, backend, **kw):
        called.append(backend)
        return build_tables(*args, backend="interpret", **kw)

    monkeypatch.setattr(ops, "on_tpu", lambda: platform == "tpu")
    monkeypatch.setattr(ops, "build_tables", interpreted)
    cfg, sys = _sys(n_ch=n_ch, seed=7, n=8)
    exact = lambda a: np.round(np.asarray(a) * 4096) / 4096  # noqa: E731
    sys = sys._replace(laser=exact(sys.laser), ring=exact(sys.ring),
                       fsr=exact(sys.fsr))
    T, N = sys.laser.shape
    vis = None
    if vis_ndim is not None:
        shape = (T, N) if vis_ndim == 2 else (T, N, N)
        vis = jax.random.bernoulli(jax.random.key(n_ch), 0.5, shape)
    tr_mean = 0.5 * cfg.grid.fsr
    rec = PhaseRecorder()
    with use_recorder(rec):
        got = _build_tables(cfg, sys, tr_mean, None, visible=vis)
    notes = [n for n in rec.notes if n["name"] == "kernels.table_route"]
    assert notes == [{"name": "kernels.table_route", "backend": None,
                      "route": route, "n": n_ch}]
    assert called == (["pallas"] if route == "pallas" else [])
    want = build_search_tables(sys, tr_mean, visible=vis,
                               max_alias=cfg.max_fsr_alias)
    for field in ("delta", "wl", "n_valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)))


@pytest.mark.parametrize("batch", ["block", "pairs"])
@pytest.mark.parametrize("fn", ["min_tr", "success"])
@pytest.mark.parametrize("policy, n_ch", [("lta", 8), ("lta", 16), ("lta", 40),
                                          ("ltc", 16)])
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_default_match_route_follows_the_platform(platform, policy, n_ch, fn,
                                                  batch, monkeypatch):
    """``backend=None`` scores ideal LtA with the Pallas kernels on a TPU
    where the core path is the bottleneck sweep (10 < N <= 32) and the batch
    fills a 128-trial block, and with the core path at Hall sizes, past 32
    channels, on batches of two trials mapped one by one (a fabric link's
    endpoints) and off a TPU; it notes the route it took and gives the core
    path's thresholds and booleans bit for bit.  LtC notes nothing and runs
    no kernel.  The Pallas route runs here in interpret mode."""
    import jax
    import jax.numpy as jnp

    from repro.core import ideal
    from repro.core.api import _ideal_min_tr, _ideal_success
    from repro.obs import PhaseRecorder, use_recorder

    called = []

    def interpreted(name):
        kernel = getattr(ops, name)

        def run(*args, backend, **kw):
            called.append((name, backend))
            return kernel(*args, backend="interpret", **kw)

        return run

    monkeypatch.setattr(ops, "on_tpu", lambda: platform == "tpu")
    for name in ("bottleneck_threshold", "perfect_matching", "feasibility"):
        monkeypatch.setattr(ops, name, interpreted(name))
    cfg = ArbitrationConfig(grid=DWDMGrid(n_ch=n_ch))
    sys = instantiate(cfg, make_units(cfg, seed=5, n_laser=16, n_ring=8))
    s = jnp.asarray(cfg.s)
    tr_mean = float(np.median(np.asarray(ideal.min_tr(sys, policy, s))))
    if fn == "min_tr":
        want = ideal.min_tr(sys, policy, s)
        run = lambda b: _ideal_min_tr(cfg, b, policy, None)
    else:
        want = ideal.success(sys, policy, s, tr_mean)
        run = lambda b: _ideal_success(cfg, b, policy, tr_mean, None)
        assert 0 < int(np.sum(np.asarray(want))) < sys.n_trials
    if batch == "pairs":
        pairs = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, 2, a.shape[-1]), sys)
        run = (lambda r: lambda b: jax.vmap(r)(b).reshape(-1))(run)
        sys = pairs
    rec = PhaseRecorder()
    with use_recorder(rec):
        got = run(sys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    notes = [n for n in rec.notes if n["name"] == "kernels.match_route"]
    if policy != "lta":
        assert notes == [] and called == []
        return
    trials = 128 if batch == "block" else 2
    route = ("pallas" if platform == "tpu" and n_ch == 16 and trials == 128
             else "core")
    assert notes == [{"name": "kernels.match_route", "backend": None,
                      "route": route, "n": n_ch, "trials": trials}]
    kernel = "bottleneck_threshold" if fn == "min_tr" else "perfect_matching"
    assert called == ([(kernel, "pallas")] if route == "pallas" else [])


@pytest.mark.parametrize("scope, expected", [(None, True), ("cpu", False),
                                             ("cpu_device", False)])
def test_on_tpu_honours_a_default_device_scope(scope, expected, monkeypatch):
    """On a TPU host, work placed on the CPU by a ``jax.default_device``
    scope (a platform name or a device) is not TPU work."""
    import contextlib

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    scopes = {None: contextlib.nullcontext(), "cpu": jax.default_device("cpu"),
              "cpu_device": jax.default_device(jax.devices("cpu")[0])}
    with scopes[scope]:
        assert ops.on_tpu() is expected
