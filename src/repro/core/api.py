"""High-level arbitration API: the entry points used by benchmarks, the
optics runtime and the examples.

Evaluation is declarative: all variation/TR overrides travel in a single
``Variations`` pytree (``repro.core.variations``) instead of per-sigma
keyword arguments —

    from repro.core import Variations, evaluate_scheme
    r = evaluate_scheme(cfg, units, "vtrs_ssm",
                        variations=Variations(tr_mean=5.0, sigma_rlv=2.24))

``tr_mean`` may still be passed positionally as the operating point
(``evaluate_scheme(cfg, units, "seq", 5.0)``); the old ``sigma_* =``
keywords survive as deprecated shims with bit-identical numerics.  New
variation axes registered with ``register_axis`` are picked up here and by
the sweep engine with no signature changes.

All heavy functions are jitted with the (hashable, frozen) ArbitrationConfig
static; the ``Variations`` key set is part of the treedef (also static)
while its values are traced, so parameter sweeps reuse one compilation.
The un-jitted ``*_impl`` bodies are exported for the sweep engine
(``repro.core.sweep``), which vmaps them over whole grids inside one jit.

Schemes are pluggable and parametrizable: ``register_scheme`` adds a
wavelength-oblivious arbiter to the dispatch registry, and
``register_scheme_family`` stamps out parametrized variants (e.g. the
retry-budgeted ``seq_retry_r{1,2,4}``) whose static parameters are baked
into the registered name — names stay jit-static cache keys, so every
variant compiles once and shmoo grids / CAFP scoring come for free.
``SCHEMES`` and ``SCHEME_POLICY`` are live views of the registry: schemes
registered after import are immediately visible.

Every evaluator takes ``backend=`` (None | "jnp" | "pallas" | "interpret").
``None`` picks each stage's implementation from the platform: the search
table build from ``table_route`` (the Pallas kernel on a TPU), Lock-to-Any
matching in ideal scoring from ``match_route`` (the Pallas kernels on a TPU
at 10 < N <= 32 channels and batches of 128 trials or more), and LtD/LtC
ideal scoring on the core path.
"""
from __future__ import annotations

import inspect
from collections.abc import Mapping as _MappingABC
from collections.abc import Sequence as _SequenceABC
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.phase import note, stage

from . import ideal, metrics
from .grid import ArbitrationConfig
from .matching import _HALL_MAX_N, adjacency_bitmask
from .outcomes import Outcome, classify
from .reach import reach_matrix, scaled_residual
from .relation import ChainSpec, chain_spec, relation_search
from .sampling import SystemBatch, UnitSamples, draw_unit_samples, instantiate
from .lta_retry import sequential_retry
from .protocol import run_protocol
from .search_table import SearchTables, build_search_tables
from .sequential import sequential_tuning
from .ssm import Assignment, single_step_matching
from .variations import Variations, merge_legacy_overrides

# An arbiter maps (cfg, tables, spec) -> Assignment using only oblivious
# primitives (entry indices and masking events; never wavelength values).
# Registered arbiters additionally receive the engine's ``backend=`` keyword
# (None | "jnp" | "pallas" | "interpret"); ``register_scheme`` wraps legacy
# 3-argument arbiters so pure-jnp schemes may simply ignore it.
Arbiter = Callable[..., Assignment]


def _normalize_arbiter(arbiter: Callable[..., Assignment]) -> Arbiter:
    """Ensure a registered arbiter accepts the engine's ``backend`` keyword.

    Arbiters that already take ``backend`` (or ``**kwargs``) pass through
    untouched; legacy 3-argument arbiters are wrapped to swallow it, so
    existing registrations (and user schemes) keep working unchanged.
    """
    try:
        params = inspect.signature(arbiter).parameters
    except (TypeError, ValueError):
        params = None
    if params is not None and (
        "backend" in params
        or any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())
    ):
        return arbiter

    def legacy(cfg, tables, spec, *, backend=None):
        del backend  # pure-jnp arbiter: backend selection has nothing to reach
        return arbiter(cfg, tables, spec)

    return legacy


class SchemeSpec(NamedTuple):
    """Registry record for a wavelength-oblivious arbitration scheme.

    ``params`` carries the static parameters a parametrized variant was
    built with (introspection only — the values are already baked into the
    arbiter closure, which is what keeps them jit-static).
    """

    name: str
    arbiter: Arbiter
    policy: str  # conditioning ideal policy for CAFP: "ltc" | "lta" | "ltd"
    params: tuple = ()


_SCHEME_REGISTRY: dict[str, SchemeSpec] = {}


def register_scheme(
    name: str,
    arbiter: Arbiter,
    *,
    policy: str = "ltc",
    params: Mapping[str, Any] | None = None,
) -> SchemeSpec:
    """Register an oblivious arbitration scheme under ``name``.

    ``policy`` selects the ideal arbiter the scheme is scored against (CAFP
    conditioning event).  ``params`` records the static parameters of a
    parametrized variant (see ``register_scheme_family``).  Registered names
    are accepted everywhere a scheme string is: ``oblivious_arbitrate``,
    ``evaluate_scheme`` and the sweep engine.  Names are jit-static cache
    keys, so re-binding a name after it has been evaluated would silently
    serve stale compiled code — duplicate registration is therefore an
    error; pick a fresh name to iterate.
    """
    if name in _SCHEME_REGISTRY:
        raise ValueError(f"scheme {name!r} already registered")
    if policy not in ("ltd", "ltc", "lta"):
        raise ValueError(f"unknown conditioning policy {policy!r}")
    frozen = tuple(sorted(dict(params or {}).items()))
    spec = SchemeSpec(name=name, arbiter=_normalize_arbiter(arbiter),
                      policy=policy, params=frozen)
    _SCHEME_REGISTRY[name] = spec
    return spec


def register_scheme_family(
    base: str,
    factory: Callable[..., Arbiter],
    variants: Mapping[str, Mapping[str, Any]],
    *,
    policy: str = "ltc",
) -> tuple[SchemeSpec, ...]:
    """Register a family of parametrized schemes in one call.

    ``factory(**params) -> Arbiter`` builds one concrete arbiter per
    variant; ``variants`` maps a name suffix to its static params, and each
    variant is registered as ``f"{base}_{suffix}"``.  Because the params are
    closed over before registration, every variant is an ordinary scheme —
    a distinct jit-static name with its own compilation cache entry — and
    gets shmoo grids and CAFP scoring through the sweep engine for free::

        register_scheme_family(
            "seq_retry", make_seq_retry,
            {"r1": {"n_rounds": 1}, "r2": {"n_rounds": 2}}, policy="lta")

    Any duplicate variant name fails the whole call (schemes registered
    before the clash stay registered; re-running with a fresh base fixes it).
    """
    return tuple(
        register_scheme(f"{base}_{suffix}", factory(**dict(params)),
                        policy=policy, params=params)
        for suffix, params in variants.items()
    )


def scheme_spec(name: str) -> SchemeSpec:
    try:
        return _SCHEME_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scheme {name!r}; registered: {registered_schemes()}"
        ) from None


def registered_schemes() -> tuple[str, ...]:
    return tuple(_SCHEME_REGISTRY)


register_scheme("seq", lambda cfg, tables, spec: sequential_tuning(tables, spec))
register_scheme(
    "rs_ssm",
    lambda cfg, tables, spec: single_step_matching(
        tables, relation_search(tables, spec, variation_tolerant=False), spec
    ),
)
register_scheme(
    "vtrs_ssm",
    lambda cfg, tables, spec: single_step_matching(
        tables, relation_search(tables, spec, variation_tolerant=True), spec
    ),
)


def make_seq_retry(n_rounds: int | None = None,
                   constrained_first: bool = True) -> Arbiter:
    """Factory for retry-budgeted oblivious LtA arbiters (§V-E future work).

    ``n_rounds`` caps the conflict-retry sweeps (None = N_ch, enough for
    convergence); ``constrained_first`` picks the lock order.  Both are
    static — bake them here and register the result under its own name.
    """
    def arbiter(cfg, tables, spec):
        return sequential_retry(
            tables, n_rounds=n_rounds, constrained_first=constrained_first
        )
    return arbiter


# beyond-paper oblivious LtA (§V-E future work): the full-budget arbiter
# plus a retry-budget family for the budget/CAFP trade-off study
# (benchmarks/fig17_retry_budget.py).
register_scheme("seq_retry", make_seq_retry(), policy="lta")
register_scheme_family(
    "seq_retry",
    make_seq_retry,
    {
        "r1": {"n_rounds": 1},
        "r2": {"n_rounds": 2},
        "r4": {"n_rounds": 4},
        "phys": {"n_rounds": None, "constrained_first": False},
    },
    policy="lta",
)


def make_protocol(
    depth: int | None = None,
    n_rounds: int | None = None,
    order: str = "constrained",
    backend: str | None = None,
) -> Arbiter:
    """Factory for protocol-engine arbiters (``repro.core.protocol``).

    ``depth`` bounds the displacement chains of the augment phase (None = N,
    full multi-hop; 0 = probe/release only), ``n_rounds`` the static round
    budget, ``order`` the probe-phase controller order.  All static — bake
    them here and register the result under its own jit-static name.

    ``backend`` is only a *default*: at call time the engine's backend
    (``SweepRequest.backend`` / ``oblivious_arbitrate(backend=)``) takes
    precedence when set, so registered protocol schemes honor
    ``backend="pallas"``/``"interpret"`` sweeps without re-registration.
    """
    baked = backend

    def arbiter(cfg, tables, spec, *, backend=None):
        return run_protocol(
            tables, spec, order=order, depth=depth, n_rounds=n_rounds,
            backend=baked if backend is None else backend,
        )

    return arbiter


# Protocol-engine schemes (the multi-hop augmenting LtA that closes
# seq_retry's residual mid-TR CAFP, plus its chain-depth family for the
# probe-budget trade-off and the LtD-conditioned chain-order variant) —
# benchmarked in benchmarks/fig19_lta_protocol.py.
register_scheme("protocol_lta", make_protocol(), policy="lta")
register_scheme_family(
    "protocol_lta",
    make_protocol,
    {
        "h1": {"depth": 1},
        "h2": {"depth": 2},
        "h4": {"depth": 4},
    },
    policy="lta",
)
register_scheme(
    "protocol_ltd",
    make_protocol(depth=0, n_rounds=1, order="chain"),
    policy="ltd",
)


class _SchemeNamesView(_SequenceABC):
    """Live, read-only, tuple-like view of the registered scheme names.

    Replaces the old module-level snapshot that was frozen at import time
    (schemes registered later were invisible through it)."""

    def __getitem__(self, i):
        return tuple(_SCHEME_REGISTRY)[i]

    def __len__(self) -> int:
        return len(_SCHEME_REGISTRY)

    def __contains__(self, name) -> bool:
        return name in _SCHEME_REGISTRY

    def __iter__(self):
        return iter(tuple(_SCHEME_REGISTRY))

    def __eq__(self, other):
        try:
            return tuple(self) == tuple(other)
        except TypeError:
            return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"SCHEMES{tuple(_SCHEME_REGISTRY)}"


class _SchemePolicyView(_MappingABC):
    """Live, read-only mapping view: scheme name -> conditioning policy."""

    def __getitem__(self, name: str) -> str:
        return _SCHEME_REGISTRY[name].policy

    def __len__(self) -> int:
        return len(_SCHEME_REGISTRY)

    def __iter__(self):
        return iter(tuple(_SCHEME_REGISTRY))

    def __repr__(self) -> str:
        return f"SCHEME_POLICY({dict(self)})"


SCHEMES = _SchemeNamesView()
SCHEME_POLICY = _SchemePolicyView()


def _kernel_route(backend: str | None, kernel_fits: bool = True) -> str:
    """``backend`` if set; else the Pallas kernel where the default platform
    is a TPU and the kernel takes the input, and the core path otherwise."""
    if backend is not None:
        return backend
    from repro.kernels import ops  # local import: kernels layer is optional

    return "pallas" if kernel_fits and ops.on_tpu() else "core"


def table_route(backend: str | None) -> str:
    """The search-table builder ``_build_tables`` runs for ``backend``:
    ``"core"`` (``build_search_tables``, the jnp rank-merge) or a
    ``repro.kernels.ops`` backend.

    An explicit backend keeps its meaning.  ``None`` is the faster builder
    on this platform: the Pallas kernel on a TPU, where its bitonic network
    beats the rank-merge's gathers at every channel count measured (8-32,
    PERF.md), and the core builder elsewhere.  Both give the same tables
    bit for bit.
    """
    return _kernel_route(backend)


def match_route(policy: str, backend: str | None, n: int, trials: int) -> str:
    """The matching that ideal scoring (``_ideal_min_tr``,
    ``_ideal_success``) runs for ``policy`` on a batch of ``trials`` systems
    of ``n`` channels: ``"none"`` where the policy is not Lock-to-Any (no
    matching runs), else ``"core"`` (``repro.core.ideal``) or a
    ``repro.kernels.ops`` backend.

    An explicit backend keeps its meaning.  ``None`` is the Pallas kernel on
    a TPU where the core path is the sequential bottleneck sweep, whose
    small gathers and scatters the kernel replaces with lane-wise selects,
    and where the batch's shape suits the kernel; the core path elsewhere:

    * ``n <= _HALL_MAX_N``: the core path is the loop-free Hall formulation;
    * ``n > 32``: ``ops.perfect_matching`` takes the core path anyway (its
      kernel holds one bitmask word per ring), and the bottleneck kernel is
      compiled for v5e only up to 32 channels;
    * ``trials < TRIAL_BLOCK``: the kernels run trials in 128-lane blocks,
      so a smaller batch (a fabric link's two endpoints, mapped link by
      link) would pad every call to a whole block.

    Both bounds on ``n`` are provisional until the chip compares the routes
    there (PERF.md).  Both routes give the same thresholds and booleans bit
    for bit.
    """
    if policy != "lta":
        return "none"
    from repro.kernels.bitmask_match import TRIAL_BLOCK

    return _kernel_route(
        backend, _HALL_MAX_N < n <= 32 and trials >= TRIAL_BLOCK)


def _build_tables(cfg, sys: SystemBatch, tr_mean, backend: str | None,
                  visible=None):
    """Search tables from the builder ``table_route`` picks.

    ``visible`` ((T, N_wl) or (T, N_ring, N_wl) bool) restricts the search
    to lines still on the bus — the masked re-search a ring runs while
    other rings hold locks.  Every backend threads it to the same
    streaming top-E builder semantics (parity-tested).  The route is noted
    (``kernels.table_route``) in the installed phase recorder when traced.
    """
    route = table_route(backend)
    note("kernels.table_route", backend=backend, route=route,
         n=int(sys.laser.shape[-1]))
    with stage("table_build"):
        if route == "core":
            return build_search_tables(
                sys, tr_mean, visible=visible, max_alias=cfg.max_fsr_alias
            )
        from repro.kernels import ops

        delta, wl, nv = ops.build_tables(
            sys.laser, sys.ring, sys.fsr, tr_mean * sys.tr_unit,
            visible=visible, max_alias=cfg.max_fsr_alias, backend=route,
        )
        return SearchTables(delta=delta, wl=wl, n_valid=nv)


def _lta_route(sys: SystemBatch, backend: str | None) -> str:
    """LtA ``match_route`` for this (T, N) batch, noted
    (``kernels.match_route``) in the installed phase recorder when traced."""
    trials, n = (int(d) for d in sys.laser.shape[-2:])
    route = match_route("lta", backend, n, trials)
    note("kernels.match_route", backend=backend, route=route, n=n,
         trials=trials)
    return route


def _ideal_min_tr(cfg, sys: SystemBatch, policy: str, backend: str | None):
    """(T,) per-trial ideal minimum mean TR.  LtA runs on the route
    ``match_route`` picks; LtD/LtC run on the core path unless a kernel
    backend is given."""
    with stage("ideal"):
        if policy == "lta":
            route = _lta_route(sys, backend)
            if route != "core":
                from repro.kernels import ops

                return ops.bottleneck_threshold(scaled_residual(sys),
                                                backend=route)
        elif backend is not None:
            from repro.kernels import ops

            ltd, ltc = ops.feasibility(
                sys.laser, sys.ring, sys.fsr, sys.tr_unit,
                s=tuple(int(v) for v in cfg.s), backend=backend,
            )
            return ltd if policy == "ltd" else ltc
        return ideal.min_tr(sys, policy, jnp.asarray(cfg.s))


def _ideal_success(cfg, sys: SystemBatch, policy: str, tr_mean, backend: str | None):
    """(T,) bool ideal arbitration success at the given mean tuning range,
    routed as ``_ideal_min_tr`` is."""
    with stage("ideal"):
        if policy == "lta":
            route = _lta_route(sys, backend)
            if route != "core":
                from repro.kernels import ops

                adj = adjacency_bitmask(reach_matrix(sys, tr_mean))
                _, ok = ops.perfect_matching(adj, backend=route)
                return ok
        elif backend is not None:
            return _ideal_min_tr(cfg, sys, policy, backend) <= tr_mean
        return ideal.success(sys, policy, jnp.asarray(cfg.s), tr_mean)


def _eval_variations(
    variations, tr_mean, legacy: dict, *, caller: str, allow_tr: bool = True
) -> Variations:
    """Normalize an evaluator's (tr_mean, variations, legacy-kwarg) inputs."""
    # stacklevel 4: this helper adds a frame between the user and the warn
    over = merge_legacy_overrides(variations, legacy, caller=caller,
                                  stacklevel=4)
    if tr_mean is not None:
        if "tr_mean" in over:
            raise ValueError(
                f"{caller}: tr_mean passed both positionally and in variations"
            )
        over = over.replace(tr_mean=tr_mean)
    if not allow_tr and "tr_mean" in over:
        raise ValueError(
            f"{caller}: min-TR evaluation solves for the tuning range; "
            "'tr_mean' cannot be overridden"
        )
    return over


def oblivious_arbitrate(
    cfg: ArbitrationConfig,
    sys: SystemBatch,
    tr_mean,
    scheme: str,
    *,
    visible=None,
    backend: str | None = None,
) -> Assignment:
    """Run a wavelength-oblivious arbitration scheme on a system batch.

    ``visible`` ((T, N_wl) or (T, N_ring, N_wl) bool) runs the scheme on
    masked re-search tables — the arbitration a late-joining ring performs
    while earlier locks have already captured lines.

    ``backend`` selects the table builder (``table_route``: ``None`` is
    the Pallas kernel on a TPU and the core builder elsewhere) *and* is
    forwarded to the scheme's arbiter, so backend-aware schemes (the
    protocol engine) run their hot loop on the same backend.
    """
    tables = _build_tables(cfg, sys, tr_mean, backend, visible=visible)
    spec = chain_spec(cfg.s)
    with stage("arbitrate"):
        return scheme_spec(scheme).arbiter(cfg, tables, spec, backend=backend)


class EvalResult(NamedTuple):
    afp: jax.Array          # policy-level failure probability (ideal LtC)
    cafp: jax.Array         # conditional algorithmic failure (Eq. 6)
    lock_err: jax.Array     # CAFP portion from zero/dup lock errors
    order_err: jax.Array    # CAFP portion from lane-order errors
    alg_success: jax.Array  # (T,) bool
    ideal_ok: jax.Array     # (T,) bool


def evaluate_scheme_impl(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    scheme: str,
    tr_mean=None,
    variations: Variations | None = None,
    sigma_rlv=None,
    sigma_fsr_frac=None,
    sigma_tr_frac=None,
    sigma_go=None,
    sigma_llv_frac=None,
    fsr_mean=None,
    backend: str | None = None,
) -> EvalResult:
    """Instantiate systems, run the scheme, and score CAFP vs ideal LtC.

    Un-jitted body; vmap-safe (the sweep engine maps it over grid points).
    Overrides come from ``variations``; ``tr_mean`` may also be given
    positionally; the ``sigma_* =`` kwargs are deprecated shims.
    """
    over = _eval_variations(
        variations, tr_mean,
        dict(sigma_rlv=sigma_rlv, sigma_fsr_frac=sigma_fsr_frac,
             sigma_tr_frac=sigma_tr_frac, sigma_go=sigma_go,
             sigma_llv_frac=sigma_llv_frac, fsr_mean=fsr_mean),
        caller="evaluate_scheme",
    )
    tr = over.resolve("tr_mean", cfg)
    with stage("instantiate"):
        sys = instantiate(cfg, units, over)
    s = jnp.asarray(cfg.s)
    policy = scheme_spec(scheme).policy
    ideal_ok = _ideal_success(cfg, sys, policy, tr, backend)
    assign = oblivious_arbitrate(cfg, sys, tr, scheme, backend=backend)
    with stage("classify"):
        out = classify(assign, s, policy=policy)
        lock = (out.zero_lock | out.dup_lock) & ideal_ok
        order = out.order_err & ideal_ok
        return EvalResult(
            afp=metrics.afp(ideal_ok),
            cafp=metrics.cafp(out.success, ideal_ok),
            lock_err=jnp.mean(lock.astype(jnp.float32)),
            order_err=jnp.mean(order.astype(jnp.float32)),
            alg_success=out.success,
            ideal_ok=ideal_ok,
        )


evaluate_scheme = jax.jit(
    evaluate_scheme_impl, static_argnames=("cfg", "scheme", "backend")
)


def evaluate_policy_impl(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    policy: str,
    tr_mean=None,
    variations: Variations | None = None,
    sigma_rlv=None,
    sigma_go=None,
    sigma_llv_frac=None,
    sigma_fsr_frac=None,
    sigma_tr_frac=None,
    fsr_mean=None,
    backend: str | None = None,
):
    """Ideal-model policy evaluation: AFP at a given mean tuning range."""
    over = _eval_variations(
        variations, tr_mean,
        dict(sigma_rlv=sigma_rlv, sigma_go=sigma_go,
             sigma_llv_frac=sigma_llv_frac, sigma_fsr_frac=sigma_fsr_frac,
             sigma_tr_frac=sigma_tr_frac, fsr_mean=fsr_mean),
        caller="evaluate_policy",
    )
    tr = over.resolve("tr_mean", cfg)
    with stage("instantiate"):
        sys = instantiate(cfg, units, over)
    ok = _ideal_success(cfg, sys, policy, tr, backend)
    with stage("classify"):
        return metrics.afp(ok)


evaluate_policy = jax.jit(
    evaluate_policy_impl, static_argnames=("cfg", "policy", "backend")
)


def policy_trial_min_tr_impl(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    policy: str,
    variations: Variations | None = None,
    sigma_rlv=None,
    sigma_go=None,
    sigma_llv_frac=None,
    sigma_fsr_frac=None,
    sigma_tr_frac=None,
    fsr_mean=None,
    backend: str | None = None,
):
    """(T,) per-trial ideal minimum mean TR at the given variation overrides.

    The sweep engine's TR-axis fast path: ideal success at mean TR t is
    exactly ``trial_min_tr <= t`` for every policy, so one min-TR evaluation
    prices the entire TR axis.
    """
    over = _eval_variations(
        variations, None,
        dict(sigma_rlv=sigma_rlv, sigma_go=sigma_go,
             sigma_llv_frac=sigma_llv_frac, sigma_fsr_frac=sigma_fsr_frac,
             sigma_tr_frac=sigma_tr_frac, fsr_mean=fsr_mean),
        caller="policy_min_tr", allow_tr=False,
    )
    with stage("instantiate"):
        sys = instantiate(cfg, units, over)
    return _ideal_min_tr(cfg, sys, policy, backend)


def policy_min_tr_impl(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    policy: str,
    variations: Variations | None = None,
    sigma_rlv=None,
    sigma_go=None,
    sigma_llv_frac=None,
    sigma_fsr_frac=None,
    sigma_tr_frac=None,
    fsr_mean=None,
    backend: str | None = None,
):
    """Minimum mean TR for complete arbitration success over the batch."""
    per_trial = policy_trial_min_tr_impl(
        cfg, units, policy, variations,
        sigma_rlv=sigma_rlv, sigma_go=sigma_go, sigma_llv_frac=sigma_llv_frac,
        sigma_fsr_frac=sigma_fsr_frac, sigma_tr_frac=sigma_tr_frac,
        fsr_mean=fsr_mean, backend=backend,
    )
    with stage("classify"):
        return metrics.min_tr_for_complete_success(per_trial)


policy_min_tr = jax.jit(
    policy_min_tr_impl, static_argnames=("cfg", "policy", "backend")
)


def make_units(cfg: ArbitrationConfig, seed: int, n_laser: int, n_ring: int) -> UnitSamples:
    return draw_unit_samples(jax.random.key(seed), cfg.grid.n_ch, n_laser, n_ring)


def shmoo(
    cfg: ArbitrationConfig,
    units: UnitSamples,
    sigma_rlv_values: np.ndarray,
    tr_values: np.ndarray,
    *,
    policy: str | None = None,
    scheme: str | None = None,
) -> np.ndarray:
    """AFP (policy) or CAFP (scheme) over a sigma_rLV x TR grid — Fig. 4/14.

    One jitted call via the sweep engine (see ``repro.core.sweep``).
    """
    from .sweep import SweepRequest, sweep  # avoid import cycle

    assert (policy is None) != (scheme is None)
    req = SweepRequest(
        cfg=cfg, units=units, policy=policy, scheme=scheme,
        axes={"sigma_rlv": sigma_rlv_values, "tr_mean": tr_values},
    )
    res = sweep(req)
    return np.asarray(res.data if policy is not None else res.data.cafp)
