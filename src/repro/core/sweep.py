"""Declarative batched sweep engine: whole variation grids in one jitted call.

The paper's headline results (Figs. 4-8, 14-16) are shmoo grids: every point
is one policy/scheme evaluation at a different combination of variation-axis
values.  The frontend is a single declarative request object::

    from repro.core import SweepRequest, make_units, sweep
    from repro.configs.wdm import WDM8_G200

    cfg = WDM8_G200
    units = make_units(cfg, seed=4, n_laser=100, n_ring=100)

    # Fig. 4: AFP over a sigma_rLV x TR shmoo, one dispatch.
    res = sweep(SweepRequest(cfg=cfg, units=units, policy="ltc",
                             axes={"sigma_rlv": rlvs, "tr_mean": trs}))
    res.data                 # (len(rlvs), len(trs)) AFP grid
    res.axis_names           # ("sigma_rlv", "tr_mean")
    res.axis("tr_mean")      # the coordinate values, carried with the result

    # Fig. 16: CAFP grid with fixed harsh variations (traced: changing them
    # never recompiles).
    res = sweep(SweepRequest(
        cfg=cfg, units=units, scheme="vtrs_ssm",
        axes={"sigma_rlv": rlvs, "tr_mean": trs},
        fixed={"sigma_fsr_frac": 0.05, "sigma_tr_frac": 0.20}))
    res.data.cafp            # EvalResult of grid-shaped fields

    # Fig. 5/7/8: minimum tuning range along any registered axis.
    res = sweep(SweepRequest(cfg=cfg, units=units, policy="lta",
                             metric="min_tr", axes={"fsr_mean": fsrs}))

Valid axis/fixed names are whatever the ``Variations`` axis registry knows
(``repro.core.variations.axis_names()``) — an axis registered with
``register_axis`` is immediately sweepable here, with no engine edits.
``sweep_policy`` / ``sweep_scheme`` / ``sweep_min_tr`` / ``sweep_grid`` are
thin wrappers that build a request and return the bare grid(s).

Engine mechanics (unchanged by the declarative frontend):

  * named axes are crossed into a flat (P, K) point list on the host;
  * the un-jitted evaluation body is ``vmap``-ped over points within a
    chunk, and ``lax.map`` iterates the chunks — so peak memory is bounded
    by ``chunk_size`` times the per-point footprint (for scheme sweeps the
    streaming T x N x E table build; see ``scheme_point_bytes``) while the
    whole grid remains ONE jit compilation and ONE dispatch;
  * results come back as grid-shaped arrays (leading dims = axis lengths,
    in the order the ``axes`` mapping lists them);
  * with ``mesh`` (1-D, e.g. from ``repro.launch.mesh.make_sweep_mesh``)
    the chunk axis is split over devices with ``shard_map`` — bit-identical
    to the unsharded engine and invariant to the mesh size;
  * ``backend`` threads through to the kernel wrappers in
    ``repro.kernels.ops`` (``"jnp"``, ``"interpret"``, ``"pallas"``); the
    default ``None`` is the engine's best path on the platform: on a TPU the
    Pallas table build (``table_route``) and the Pallas Lock-to-Any matching
    at 10 < N <= 32 channels on batches of 128 trials or more
    (``match_route``), the pure-jnp core path for everything else.

``sweep_reference`` keeps the pre-engine per-point loop as the golden
oracle — the engine is bit-for-bit equal to it (asserted in
tests/test_sweep.py), and both consume the same validated ``SweepRequest``
so they reject exactly the same inputs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.phase import measured_call, note, span, stage

from .api import (
    EvalResult,
    evaluate_policy,
    evaluate_policy_impl,
    evaluate_scheme,
    evaluate_scheme_impl,
    match_route,
    policy_min_tr,
    policy_min_tr_impl,
    policy_trial_min_tr_impl,
    scheme_spec,
    table_route,
)
from .grid import ArbitrationConfig
from .matching import _HALL_MAX_N
from .sampling import UnitSamples
from .search_table import max_entries_for, merge_plan
from .variations import Variations, axis_names, axis_spec, _maybe_validate

#: Per-chunk device memory budget for auto chunk sizing [bytes].
_CHUNK_BUDGET = 256 * 1024 * 1024


def __getattr__(name: str):
    # Back-compat: the pre-registry engine exposed its axis names as a
    # module-level tuple frozen at import time.  Serve it live instead so
    # axes registered later are visible through the old spelling too.
    if name == "AXIS_NAMES":
        return axis_names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def chunked_map(item_fn, xs, *, chunk, mesh=None, broadcast=(), tag=None):
    """Map ``item_fn`` over ``xs``'s leading axis in vmapped chunks.

    The engine's memory-bounding primitive, extracted so other batch axes
    (the fabric layer's link axis) reuse the exact same machinery:
    ``lax.map`` iterates chunks of size ``chunk`` and ``vmap`` runs the
    items within a chunk, so peak memory is ``chunk`` times the per-item
    footprint while the whole map stays one traced program.  ``broadcast``
    pytrees are passed unchunked as leading arguments:
    ``item_fn(*broadcast, item)``.

    The tail chunk is padded by repeating the last item (numerically
    benign; padded results are dropped).  With ``mesh`` (1-D) the chunk
    axis is split over devices with ``shard_map`` — the chunk count is
    rounded up to a device multiple so every device runs whole chunks,
    which keeps results bit-identical to the unsharded path and invariant
    to the mesh size.  Composable: with ``mesh=None`` this is vmap-safe,
    so an outer ``chunked_map`` (grid points) may contain an inner one
    (links per point).  The whole map is the ``chunk_loop`` stage; the
    stages of ``item_fn`` nest inside it.
    """
    with stage("chunk_loop"):
        tree = jax.tree_util
        p = tree.tree_leaves(xs)[0].shape[0]
        n_chunks = -(-p // chunk)
        if mesh is not None:
            n_dev = mesh.devices.size
            n_chunks = -(-n_chunks // n_dev) * n_dev   # whole chunks per device
        pad = n_chunks * chunk - p
        if tag is not None:
            # Best-effort plan telemetry: this body runs at *trace* time, so
            # the note fires once per compilation, not per executed chunk.
            note(f"chunked_map.{tag}", items=int(p), chunk=int(chunk),
                 n_chunks=int(n_chunks), pad=int(pad))
        if pad:
            xs = tree.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.tile(a[-1:], (pad,) + (1,) * (a.ndim - 1))]
                ),
                xs,
            )
        chunks = tree.tree_map(
            lambda a: a.reshape((n_chunks, chunk) + a.shape[1:]), xs
        )

        def run(*args):
            *br, ch = args
            return jax.lax.map(jax.vmap(partial(item_fn, *br)), ch)

        if mesh is None:
            out = run(*broadcast, chunks)
        else:
            P = jax.sharding.PartitionSpec
            axis = mesh.axis_names[0]
            # check_vma=False: the table merge's loop carry mixes replicated
            # and per-device values, which the varying-manual-axes check rejects.
            out = jax.shard_map(
                run, mesh=mesh,
                in_specs=(P(),) * len(broadcast) + (P(axis),),
                out_specs=P(axis), check_vma=False,
            )(*broadcast, chunks)
        return tree.tree_map(
            lambda a: a.reshape((n_chunks * chunk,) + a.shape[2:])[:p], out
        )


def _check_names(names, *, metric: str) -> None:
    valid = axis_names()
    for name in names:
        if name not in valid:
            raise ValueError(f"unknown sweep axis {name!r}; valid: {valid}")
    if metric == "min_tr" and "tr_mean" in names:
        raise ValueError("min_tr sweeps solve for TR; 'tr_mean' cannot be an axis")


def _validate_request(names, fixed, *, metric: str, policy, scheme) -> None:
    """Shared request validation: the engine and the reference loop consume
    the same validated ``SweepRequest``, so they accept/reject identically
    (the oracle is only an oracle on the domain the engine serves)."""
    if (policy is None) == (scheme is None):
        raise ValueError("exactly one of policy/scheme required")
    if metric not in ("eval", "min_tr"):
        raise ValueError(f"unknown metric {metric!r}")
    if metric == "min_tr" and policy is None:
        raise ValueError("min_tr sweeps are policy sweeps")
    _check_names(names, metric=metric)
    _check_names(fixed, metric=metric)
    overlap = set(names) & set(fixed)
    if overlap:
        raise ValueError(f"axes and fixed overlap: {sorted(overlap)}")


@dataclasses.dataclass(frozen=True, eq=False)
class SweepRequest:
    """A complete, validated description of one grid evaluation.

    axes:   ordered mapping axis name -> 1-D coordinate values; the result's
            leading dims follow this order.  Names come from the
            ``Variations`` axis registry.
    policy/scheme: exactly one; the evaluation target.
    metric: "eval" (AFP for a policy / EvalResult for a scheme) or
            "min_tr" (policy only; minimum mean TR for complete success).
    fixed:  scalar overrides applied at every point (a mapping or a
            ``Variations``; traced, so changing values never recompiles).
    chunk_size: points per vmap chunk (None = auto from the memory budget;
            since the streaming top-E table build the per-point scheme
            footprint is ~6x smaller, so scheme sweeps auto-size
            correspondingly larger chunks — fewer ``lax.map`` iterations).
    backend: kernel backend threaded to ``repro.kernels.ops``.  None is
            the engine's best path on the platform: on a TPU the Pallas
            table build (``repro.core.api.table_route``) and the Pallas
            Lock-to-Any matching of ideal scoring at 10 < N <= 32 channels
            on batches of 128 trials or more
            (``repro.core.api.match_route``), the jnp core path for
            everything else (LtD/LtC scoring, Hall-size and multiword
            matching, a fabric link's two-endpoint batches, all of it off
            a TPU).
    tr_fast: policy-eval sweeps with a ``tr_mean`` axis collapse that axis
            to a free threshold comparison against one per-trial min-TR
            evaluation per remaining point (bit-exact; see
            ``_afp_from_trial_min_tr``).  Disable to force the direct path.
    mesh:   optional 1-D ``jax.sharding.Mesh``; the chunk axis is split
            over its devices with ``shard_map``.  A pure performance knob.
    timeline: optional ``repro.core.temporal.Timeline``.  Each grid point
            then runs the full temporal scan (incremental re-arbitration
            with ``run_timeline`` defaults) instead of a one-shot
            evaluation, and the result grids are trial-mean
            ``TemporalStats`` fields with a trailing step axis.  Requires a
            ``protocol_*`` scheme and ``metric="eval"``; warm/hysteresis
            knobs live on ``run_timeline`` itself.
    fabric: optional ``repro.fabric.FabricSpec``.  Each grid point then
            brings up the whole fabric (per-link scheme arbitration + the
            network-level wavelength-assignment constraints) and the result
            grids are ``FabricStats`` fields.  Requires a scheme,
            ``metric="eval"`` and ``units`` from
            ``repro.fabric.make_fabric_units`` matching the spec.  The link
            axis is chunked *inside* each grid point against the same
            memory budget.

    Composition precedence: with BOTH ``fabric`` and ``timeline`` set, the
    fabric wins the dispatch and the timeline must be a fabric-scoped
    ``repro.fabric.FabricTimeline`` matching the spec's link count and the
    config's channel count — each grid point then runs the full chaos scan
    (``run_fabric_timeline`` defaults: warm, transactional) and the result
    grids are link-mean ``FabricChaosStats`` fields with a trailing step
    axis.  A per-transceiver ``Timeline`` has no link addressing, and a
    ``FabricTimeline`` without ``fabric=`` has no topology — both
    combinations are rejected at construction.  Any scheme is accepted
    (bring-up uses the scheme's arbiter; re-lock always runs the protocol
    engine), unlike transceiver timelines which need ``protocol_*``.

    Validation happens at construction, so an invalid request never reaches
    the engine (or the reference loop).
    """

    cfg: ArbitrationConfig
    units: UnitSamples
    axes: Mapping[str, np.ndarray]
    policy: str | None = None
    scheme: str | None = None
    metric: str = "eval"
    fixed: Mapping[str, float] | Variations | None = None
    chunk_size: int | None = None
    backend: str | None = None
    tr_fast: bool = True
    mesh: Any = None
    timeline: Any = None
    fabric: Any = None

    def __post_init__(self):
        with span("sweep.validate"):
            self._validate()

    def _validate(self):
        axes = {
            str(k): np.asarray(v, np.float32).reshape(-1)
            for k, v in dict(self.axes).items()
        }
        fixed = self.fixed
        if isinstance(fixed, Variations):
            fixed = dict(fixed.items())
        fixed = {str(k): v for k, v in dict(fixed or {}).items()}
        object.__setattr__(self, "axes", axes)
        object.__setattr__(self, "fixed", fixed)
        if self.fabric is not None:
            # Fabric-specific diagnostics win over the generic metric/policy
            # checks: a fabric request that also trips e.g. the min_tr rule
            # should say what is wrong with the *fabric* usage.
            if self.scheme is None:
                raise ValueError(
                    "fabric sweeps arbitrate every link with an oblivious "
                    "scheme; pass scheme=..., not policy=..."
                )
            if self.metric != "eval":
                raise ValueError("fabric sweeps require metric='eval'")
            if self.timeline is not None:
                from repro.fabric.chaos import FabricTimeline

                if not isinstance(self.timeline, FabricTimeline):
                    raise ValueError(
                        "fabric sweeps compose with a fabric-scoped "
                        "FabricTimeline (repro.fabric.make_fabric_timeline); "
                        "a per-transceiver Timeline has no link addressing "
                        f"at fabric scale (got {type(self.timeline).__name__})"
                    )
                if self.timeline.n_links != self.fabric.n_links:
                    raise ValueError(
                        f"timeline spans {self.timeline.n_links} links but "
                        f"the fabric spec describes {self.fabric.n_links}"
                    )
                if self.timeline.n_ch != len(self.cfg.s):
                    raise ValueError(
                        f"timeline has {self.timeline.n_ch} channels but "
                        f"cfg has {len(self.cfg.s)}"
                    )
            from repro.fabric.sampling import FabricUnits

            if not isinstance(self.units, FabricUnits):
                raise ValueError(
                    "fabric sweeps take FabricUnits from "
                    "repro.fabric.make_fabric_units, not UnitSamples"
                )
            if self.units.n_links != self.fabric.n_links:
                raise ValueError(
                    f"units carry {self.units.n_links} links but the spec "
                    f"describes {self.fabric.n_links}"
                )
        _validate_request(
            tuple(axes), tuple(fixed),
            metric=self.metric, policy=self.policy, scheme=self.scheme,
        )
        if not axes:
            raise ValueError("at least one sweep axis required")
        for name, values in axes.items():
            spec = axis_spec(name)
            for v in values:
                _maybe_validate(spec, v)
        for name, v in fixed.items():
            _maybe_validate(axis_spec(name), v)
        if self.mesh is not None and len(self.mesh.axis_names) != 1:
            raise ValueError(
                f"sweep meshes are 1-D (the chunk axis); got axes "
                f"{self.mesh.axis_names}"
            )
        if self.timeline is not None and self.fabric is None:
            from repro.fabric.chaos import FabricTimeline

            if isinstance(self.timeline, FabricTimeline):
                raise ValueError(
                    "a FabricTimeline carries per-link faults but no "
                    "topology; pass the matching fabric=FabricSpec(...) "
                    "alongside it"
                )
            if self.scheme is None or not self.scheme.startswith("protocol_"):
                raise ValueError(
                    "timeline sweeps run incremental re-arbitration and "
                    f"need a protocol_* scheme; got scheme={self.scheme!r}"
                )
            if self.metric != "eval":
                raise ValueError("timeline sweeps require metric='eval'")
            n_ch = int(self.timeline.n_ch)
            if n_ch != len(self.cfg.s):
                raise ValueError(
                    f"timeline has {n_ch} channels but cfg has {len(self.cfg.s)}"
                )

    def replace(self, **kw) -> "SweepRequest":
        return dataclasses.replace(self, **kw)


class SweepResult(NamedTuple):
    """Grid(s) plus the axis metadata they were evaluated over.

    ``data`` is the grid array (policy/min_tr requests) or an ``EvalResult``
    whose fields are grids (scheme requests); leading dims follow
    ``axis_names``, with ``coords[i]`` holding axis i's coordinate values.
    A NamedTuple, hence a pytree: ``jax.block_until_ready`` etc. work.
    """

    data: Any
    axis_names: tuple
    coords: tuple

    def axis(self, name: str) -> np.ndarray:
        """Coordinate values of the named axis."""
        try:
            return self.coords[self.axis_names.index(name)]
        except ValueError:
            raise ValueError(
                f"result has no axis {name!r}; axes: {self.axis_names}"
            ) from None


def _grid_points(axes: Mapping[str, np.ndarray]):
    """Cross the named axes into a flat (P, K) float32 point array."""
    names = tuple(axes)
    values = [np.asarray(v, np.float32).reshape(-1) for v in axes.values()]
    shape = tuple(len(v) for v in values)
    mesh = np.meshgrid(*values, indexing="ij")
    points = np.stack([m.reshape(-1) for m in mesh], axis=-1)  # (P, K)
    return names, points, shape


def scheme_point_bytes(cfg: ArbitrationConfig, n_trials: int) -> int:
    """Per-grid-point working-set estimate [bytes] for a *scheme* sweep —
    the quantity ``_auto_chunk`` budgets against.  Exposed for capacity
    audits (e.g. the WDM32 table-footprint test).

    Dominant: the persistent (T, N, E) search tables (f32 delta + i32 wl)
    plus the bounded transient of the streaming top-E merge — the tiling
    and its scratch come from the same ``merge_plan`` the builder uses, so
    the accounting cannot drift from the implementation.  The dense
    (T, N, N*J) candidate tensor of the retired full-sort build no longer
    exists: at N=32, J=17 this is ~6x smaller, which is what lets
    ``chunk_size=None`` auto-size scheme chunks ~6x larger (fewer
    ``lax.map`` iterations per grid) and a paper-scale (100x100-trial)
    WDM32 scheme point fit the 256 MB chunk budget.
    """
    return merge_plan(
        n_trials, cfg.grid.n_ch, max_alias=cfg.max_fsr_alias
    ).total_bytes


def policy_point_bytes(cfg: ArbitrationConfig, n_trials: int) -> int:
    """Per-grid-point working-set estimate [bytes] for a *policy* sweep.

    Policy sweeps never build search tables (the streaming-merge budget is
    scheme-path only); the dominant term is the (T, 2^N, N) Hall subset
    table (small N) or the (T, N, N) residual tensor of the bottleneck
    sweep — a few live f32 copies either way.
    """
    n = cfg.grid.n_ch
    width = max(n, (1 << n) if n <= _HALL_MAX_N else 0)
    return n_trials * n * width * 4 * 3


def _auto_chunk(cfg: ArbitrationConfig, units: UnitSamples, n_points: int,
                scheme: str | None) -> int:
    """Largest chunk whose per-point working set fits the memory budget."""
    trials = units.u_rlv.shape[0] * units.u_go.shape[0]
    per_point = (scheme_point_bytes(cfg, trials) if scheme is not None
                 else policy_point_bytes(cfg, trials))
    return int(np.clip(_CHUNK_BUDGET // max(per_point, 1), 1, n_points))


@partial(
    jax.jit,
    static_argnames=("cfg", "policy", "scheme", "metric", "names",
                     "fixed_names", "chunk", "backend", "mesh", "fabric",
                     "link_chunk"),
)
def _sweep_flat(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    points,            # (P, K) traced
    fixed_values,      # (F,) traced
    *,
    policy: str | None,
    scheme: str | None,
    metric: str,
    names: tuple,
    fixed_names: tuple,
    chunk: int,
    backend: str | None,
    mesh=None,
    timeline=None,     # Timeline pytree (traced) for temporal sweeps
    fabric=None,       # FabricSpec (static) for fabric sweeps
    link_chunk: int = 0,
):
    """Chunked vmap over flat grid points; one compilation for the grid.

    All chunking/sharding mechanics live in ``chunked_map``: the grid
    points are its mapped axis, and ``units``/``fixed_values``/``timeline``
    broadcast to every point.  With ``mesh`` the chunk axis is split over
    devices — bit-identical to the unsharded engine and invariant to the
    mesh size (the chunking contract extended to devices).
    """

    def eval_point(units, fixed_values, tl, vals):
        over = {fn: fixed_values[i] for i, fn in enumerate(fixed_names)}
        over.update({name: vals[i] for i, name in enumerate(names)})
        var = Variations(**over)
        if fabric is not None:
            if tl is not None:
                from repro.fabric.chaos import (
                    run_fabric_timeline_impl,
                    summarize_chaos,
                )

                _, cs = run_fabric_timeline_impl(
                    cfg, units, fabric, tl, var,
                    scheme=scheme, backend=backend, link_chunk=link_chunk,
                )
                # link-mean per step: grids stay axis-shaped + (S,) trailing
                return summarize_chaos(cs)
            from repro.fabric.bringup import fabric_stats_impl

            return fabric_stats_impl(
                cfg, units, fabric, var,
                scheme=scheme, backend=backend, link_chunk=link_chunk,
            )
        if tl is not None:
            from .temporal import run_timeline_impl

            _, tstats = run_timeline_impl(
                cfg, units, tl, var, scheme=scheme, backend=backend
            )
            # trial-mean per step: grids stay axis-shaped + (S,) trailing
            return jax.tree_util.tree_map(
                lambda a: jnp.mean(a.astype(jnp.float32), axis=-1), tstats
            )
        if metric == "min_tr":
            return policy_min_tr_impl(cfg, units, policy, var, backend=backend)
        if metric == "trial_min_tr":
            return policy_trial_min_tr_impl(cfg, units, policy, var, backend=backend)
        if policy is not None:
            return evaluate_policy_impl(
                cfg, units, policy, variations=var, backend=backend
            )
        return evaluate_scheme_impl(
            cfg, units, scheme, variations=var, backend=backend
        )

    return chunked_map(
        eval_point, points, chunk=chunk, mesh=mesh,
        broadcast=(units, fixed_values, timeline), tag="sweep_points",
    )


@jax.jit
def _afp_from_trial_min_tr(trial_min_tr, tr_values):
    """(..., T) per-trial min TR x (L,) TR axis -> (..., L) AFP grid.

    Bit-exact vs evaluating each TR point: success bools are identical
    (ideal success at t == trial_min_tr <= t for every policy) and a mean
    of 0/1 float32 values is order-independent (integer sums < 2^24).
    """
    with stage("classify"):
        ok = trial_min_tr[..., None, :] <= tr_values[:, None]
        return 1.0 - jnp.mean(ok.astype(jnp.float32), axis=-1)


class _Plan(NamedTuple):
    """Host-side plan of one request: the call of ``_sweep_flat`` and how to
    shape its output into grids."""

    args: tuple          # positional arguments of ``_sweep_flat``
    kwargs: dict         # its static keywords (plus ``timeline`` when set)
    shape: tuple         # grid shape of the evaluated points
    tr_idx: int | None   # axis the TR fast path re-inserts, or None
    per_point: int       # budgeted working set of one grid point [bytes]


def _plan(request: SweepRequest) -> _Plan:
    cfg, units = request.cfg, request.units
    policy, scheme, metric = request.policy, request.scheme, request.metric
    names, points, shape = _grid_points(request.axes)

    if (policy is not None and metric == "eval" and request.tr_fast
            and "tr_mean" in names):
        # TR fast path: one per-trial min-TR evaluation per non-TR point,
        # then the whole TR axis is a broadcast threshold comparison.
        metric = "trial_min_tr"
        tr_idx = names.index("tr_mean")
        sub_names = tuple(n for n in names if n != "tr_mean")
        shape = shape[:tr_idx] + shape[tr_idx + 1:]
        if sub_names:
            points = _grid_points({n: request.axes[n] for n in sub_names})[1]
        else:
            points = np.zeros((1, 0), np.float32)  # single all-defaults point
        run_names = sub_names
    else:
        tr_idx = None
        run_names = names

    if request.fabric is not None:
        # Budget the *link* axis first (one fabric point is a 2*link_chunk-
        # trial scheme evaluation), then fit grid points over it.
        from repro.fabric.bringup import auto_link_chunk

        link_chunk = auto_link_chunk(cfg, request.fabric.n_links)
        per_point = scheme_point_bytes(cfg, 2 * link_chunk)
    else:
        link_chunk = 0
        trials = units.u_rlv.shape[0] * units.u_go.shape[0]
        per_point = (scheme_point_bytes(cfg, trials) if scheme is not None
                     else policy_point_bytes(cfg, trials))
    chunk = request.chunk_size or int(
        np.clip(_CHUNK_BUDGET // max(per_point, 1), 1, points.shape[0])
    )
    fixed_names = tuple(request.fixed)
    fixed_values = jnp.asarray(
        [float(request.fixed[k]) for k in fixed_names], jnp.float32
    )
    kwargs = dict(
        policy=policy, scheme=scheme, metric=metric, names=run_names,
        fixed_names=fixed_names, chunk=chunk, backend=request.backend,
        mesh=request.mesh, fabric=request.fabric, link_chunk=link_chunk,
    )
    if request.timeline is not None:  # else the default: None confuses the
        kwargs["timeline"] = request.timeline  # AOT pytree signature
    return _Plan(
        args=(cfg, units, jnp.asarray(points), fixed_values), kwargs=kwargs,
        shape=shape, tr_idx=tr_idx, per_point=per_point,
    )


def _plan_match_route(request: SweepRequest) -> str:
    """The ``match_route`` of the request's ideal scoring, as its program
    notes it (``kernels.match_route``): a fabric link scores its two
    endpoints as one batch, and a temporal sweep of one link does no ideal
    scoring."""
    if request.timeline is not None and request.fabric is None:
        return "none"
    policy = (request.policy if request.scheme is None
              else scheme_spec(request.scheme).policy)
    units = request.units
    trials = (2 if request.fabric is not None
              else units.u_rlv.shape[0] * units.u_go.shape[0])
    return match_route(policy, request.backend, request.cfg.grid.n_ch, trials)


def lower_sweep(request: SweepRequest) -> jax.stages.Lowered:
    """Lower the one program ``sweep(request)`` runs, without running it.

    For compile audits: ``.compile().memory_analysis()`` gives the device
    bytes of the program, ``.compile().as_text()`` shows which stages are
    Pallas kernels (``tpu_custom_call``).
    """
    plan = _plan(request)
    return _sweep_flat.lower(*plan.args, **plan.kwargs)


def sweep(request: SweepRequest) -> SweepResult:
    """Evaluate a ``SweepRequest`` in one jitted call.

    The single entry point of the engine; ``sweep_policy`` /
    ``sweep_scheme`` / ``sweep_min_tr`` / ``sweep_grid`` are wrappers over
    it.  Returns a ``SweepResult`` carrying the grid(s) and the axis
    metadata (names + coordinate values).

    Each step is a ``repro.obs.phase`` span, so it shows in a profiler
    trace (and in an installed recorder): ``sweep.plan`` carries the chunk
    plan, the table builder (``table_route``; "none" for a policy, which
    builds no table) and the Lock-to-Any matching (``match_route``; "none"
    where the policy, or the scheme's, is not LtA), and each span the
    executables compiled or loaded inside it (``sweep.dispatch`` ends at the
    asynchronous return).
    """
    with span("sweep.plan") as counters:
        plan = _plan(request)
        points, chunk = int(plan.args[2].shape[0]), int(plan.kwargs["chunk"])
        counters.update(points=points, chunk=chunk, n_chunks=-(-points // chunk),
                        per_point_bytes=int(plan.per_point),
                        metric=plan.kwargs["metric"],
                        table_route=("none" if request.scheme is None
                                     else table_route(request.backend)),
                        match_route=_plan_match_route(request))
    note("sweep.plan", **counters,
         link_chunk=int(plan.kwargs["link_chunk"]), budget=_CHUNK_BUDGET,
         target=request.scheme if request.scheme is not None
         else request.policy)
    # ``measured_call`` is a plain call unless an installed recorder opted
    # into the AOT compile/execute split (memory watermarks vs the budget).
    dyn_kw = {k: v for k, v in plan.kwargs.items() if k == "timeline"}
    with span("sweep.dispatch"):
        out = measured_call(
            "sweep", _sweep_flat, plan.args, plan.kwargs,
            dynamic_args=plan.args[1:], dynamic_kwargs=dyn_kw,
            budget=_CHUNK_BUDGET,
        )
    names = tuple(request.axes)
    with span("sweep.shape"):
        if plan.tr_idx is not None:
            tr_values = jnp.asarray(request.axes["tr_mean"])
            afp = _afp_from_trial_min_tr(
                out.reshape(plan.shape + out.shape[1:]), tr_values
            )
            data = jnp.moveaxis(afp, -1, plan.tr_idx)
        else:
            data = jax.tree_util.tree_map(
                lambda a: a.reshape(plan.shape + a.shape[1:]), out
            )
    return SweepResult(
        data=data, axis_names=names,
        coords=tuple(request.axes[n] for n in names),
    )


def sweep_grid(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    axes: Mapping[str, np.ndarray],
    *,
    policy: str | None = None,
    scheme: str | None = None,
    metric: str = "eval",
    fixed: Mapping[str, float] | None = None,
    chunk_size: int | None = None,
    backend: str | None = None,
    tr_fast: bool = True,
    mesh=None,
):
    """Bare-grid wrapper over ``sweep``: builds the ``SweepRequest`` and
    returns ``SweepResult.data`` only (EvalResult of grids for a scheme, a
    single grid otherwise)."""
    return sweep(SweepRequest(
        cfg=cfg, units=units, axes=axes, policy=policy, scheme=scheme,
        metric=metric, fixed=fixed, chunk_size=chunk_size, backend=backend,
        tr_fast=tr_fast, mesh=mesh,
    )).data


def sweep_policy(cfg, units, policy, axes, **kw):
    """Grid of AFP values for an ideal policy.  See ``SweepRequest``."""
    return sweep_grid(cfg, units, axes, policy=policy, **kw)


def sweep_scheme(cfg, units, scheme, axes, **kw) -> EvalResult:
    """EvalResult whose fields are grids, for an oblivious scheme."""
    return sweep_grid(cfg, units, axes, scheme=scheme, **kw)


def sweep_min_tr(cfg, units, policy, axes, **kw):
    """Grid of minimum mean tuning ranges for an ideal policy."""
    return sweep_grid(cfg, units, axes, policy=policy, metric="min_tr", **kw)


def sweep_reference(request: SweepRequest) -> SweepResult:
    """Pre-engine per-point Python loop: one jitted call per grid point.

    The golden oracle for ``sweep`` (bit-for-bit equal on CPU); also a
    readable spec of what the engine computes.  Consumes the same validated
    ``SweepRequest`` as the engine, so it rejects exactly what the engine
    rejects.  Never use on a hot path.
    """
    cfg, units = request.cfg, request.units
    policy, scheme = request.policy, request.scheme
    if request.timeline is not None:
        raise NotImplementedError(
            "sweep_reference has no temporal path; run_timeline is itself "
            "the per-point primitive a timeline sweep maps — compare "
            "against direct run_timeline calls instead"
        )
    if request.fabric is not None:
        raise NotImplementedError(
            "sweep_reference has no fabric path; the per-link oracle is a "
            "vmapped core instantiate + one flat oblivious_arbitrate "
            "(asserted bit-identical in tests/test_fabric.py)"
        )
    names, points, shape = _grid_points(request.axes)
    outs = []
    for vals in points:
        over = dict(request.fixed)
        over.update({name: float(v) for name, v in zip(names, vals)})
        var = Variations(**over)
        if request.metric == "min_tr":
            outs.append(policy_min_tr(cfg, units, policy, var,
                                      backend=request.backend))
        elif policy is not None:
            outs.append(evaluate_policy(cfg, units, policy, variations=var,
                                        backend=request.backend))
        else:
            outs.append(evaluate_scheme(cfg, units, scheme, variations=var,
                                        backend=request.backend))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
    data = jax.tree_util.tree_map(
        lambda a: a.reshape(shape + a.shape[1:]), stacked
    )
    return SweepResult(
        data=data, axis_names=names,
        coords=tuple(request.axes[n] for n in names),
    )


def sweep_grid_reference(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    axes: Mapping[str, np.ndarray],
    *,
    policy: str | None = None,
    scheme: str | None = None,
    metric: str = "eval",
    fixed: Mapping[str, float] | None = None,
    backend: str | None = None,
):
    """Bare-grid wrapper over ``sweep_reference`` (see there)."""
    return sweep_reference(SweepRequest(
        cfg=cfg, units=units, axes=axes, policy=policy, scheme=scheme,
        metric=metric, fixed=fixed, backend=backend,
    )).data
