"""Jitted public wrappers for the arbitration Pallas kernels.

Handles layout (core uses (T, N); kernels put trials on lanes: (N, T)),
padding to the 128-trial lane block, and backend selection:

  backend="pallas"     compiled Pallas (TPU)
  backend="interpret"  Pallas interpret mode (CPU correctness path)
  backend="jnp"        portable pure-jnp oracle (default off-TPU)
  backend="auto"       pallas on TPU else jnp

Every wrapper is **vmap-safe**: layout moves use explicit last-two-axes
swaps (never ``.T``, which reverses all axes), and padding/slicing is
expressed on the trial axis only — so the sweep engine
(``repro.core.sweep``) can map them over sigma/TR grid points under
``backend="jnp"`` and ``"interpret"`` (guarded by tests/test_sweep.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.phase import note, stage

from . import ref
from .bitmask_match import TRIAL_BLOCK, bottleneck_pallas, match_pallas
from .feasibility import feasibility_pallas
from .probe import research_pallas
from .table_build import table_pallas


def on_tpu() -> bool:
    """Whether computations placed by default run on a TPU: the platform of
    a ``jax.default_device`` scope where one is set, else JAX's default."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend() == "tpu"
    return (dev if isinstance(dev, str) else dev.platform) == "tpu"


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if on_tpu() else "jnp"
    if backend not in ("pallas", "interpret", "jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def _to_cols(a) -> jax.Array:
    """Core (T, N) -> kernel (N, T) layout (swap of the last two axes only,
    so extra leading vmap axes pass through untouched)."""
    return jnp.swapaxes(jnp.asarray(a, jnp.float32), -1, -2)


def _pad_cols(x, t_pad):
    t = x.shape[-1]
    if t == t_pad:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, t_pad - t)]
    return jnp.pad(x, pad)


def _padded_t(t: int) -> int:
    return ((t + TRIAL_BLOCK - 1) // TRIAL_BLOCK) * TRIAL_BLOCK


def feasibility(laser, ring, fsr, tr_unit, *, s, backend="auto"):
    """(T, N) system batch -> per-trial (ltd_min_tr, ltc_min_tr)."""
    backend = _resolve(backend)
    cols = [_to_cols(a) for a in (laser, ring, fsr, tr_unit)]
    if backend == "jnp":
        return ref.feasibility_ref(*cols, s=tuple(int(v) for v in s))
    t = cols[0].shape[1]
    tp = _padded_t(t)
    cols = [_pad_cols(c, tp) for c in cols]
    # Padded trials must stay numerically benign: tr_unit=1 avoids div-by-0.
    if tp != t:
        pad_fix = jnp.zeros((cols[3].shape[0], tp), jnp.float32).at[:, t:].set(1.0)
        cols[3] = cols[3] + pad_fix
        cols[2] = cols[2] + pad_fix  # fsr > 0 for mod
    ltd, ltc = feasibility_pallas(
        *cols, s=tuple(int(v) for v in s), interpret=(backend == "interpret")
    )
    return ltd[:t], ltc[:t]


def perfect_matching(adj, *, backend="auto"):
    """adj: (T, N) int32 ring->line bitmasks -> (match_wl (T, N), ok (T,)).

    Multiword (T, N, W) uint32 adjacencies (N > 32) run the portable core
    path on every backend: the Pallas matching kernel is single-word by
    layout (one int32 lane per ring), so wide systems route to
    ``repro.core.matching.max_matching`` rather than failing.  The route is
    noted (``kernels.match_route``) in the installed phase recorder, so a
    run that asked for a kernel can see that none ran.
    """
    backend = _resolve(backend)
    adj = jnp.asarray(adj)
    if adj.ndim >= 3 and adj.dtype == jnp.uint32:      # multiword: core path
        from repro.core.matching import max_matching

        note("kernels.match_route", backend=backend, route="core",
             n=int(adj.shape[-2]))

        mw, _ = max_matching(adj)
        return mw, jnp.all(mw >= 0, axis=-1)
    with stage("matching"):
        adj_c = jnp.swapaxes(adj.astype(jnp.int32), -1, -2)
        if backend == "jnp":
            mw, ok = ref.match_ref(adj_c)
            return jnp.swapaxes(mw, -1, -2), ok
        t = adj_c.shape[1]
        tp = _padded_t(t)
        mw, ok = match_pallas(_pad_cols(adj_c, tp),
                              interpret=(backend == "interpret"))
        return jnp.swapaxes(mw, -1, -2)[:t], ok[:t]


def bottleneck_threshold(weights, *, backend="auto"):
    """weights: (T, N, N) scaled residuals -> (T,) bottleneck thresholds.

    The LtA per-trial minimum mean TR (one single-pass bottleneck matching;
    see ``repro.core.matching``).  Layout move is a last-three-axes
    ``moveaxis`` so extra leading vmap axes pass through untouched.
    """
    backend = _resolve(backend)
    with stage("matching"):
        w = jnp.moveaxis(jnp.asarray(weights, jnp.float32), -3, -1)  # (N, N, T)
        if backend == "jnp":
            return ref.bottleneck_ref(w)
        t = w.shape[-1]
        tp = _padded_t(t)
        # Padded trials see all-zero weights: threshold 0, sliced off below.
        thr = bottleneck_pallas(_pad_cols(w, tp),
                                interpret=(backend == "interpret"))
        return thr[:t]


def masked_research(wl, taken, floor, *, backend="auto"):
    """Batched masked re-search (the protocol engine's unit primitive).

    wl (T, C, E) int32 line ids of C search-table rows per trial; taken
    (T, L) bool captured-line mask; floor (T, C) int32 first admissible
    entry.  Returns (first (T, C) int32 entry or -1, found (T, C) bool) —
    semantics of ``repro.core.protocol.masked_first_entry`` (parity-tested).
    Layout moves are last-axes swaps only, so extra leading vmap axes pass
    through untouched.
    """
    backend = _resolve(backend)
    wl_c = jnp.moveaxis(jnp.asarray(wl, jnp.int32), -3, -1)       # (C, E, T)
    taken_c = jnp.swapaxes(jnp.asarray(taken, jnp.int32), -1, -2)  # (L, T)
    floor_c = jnp.swapaxes(jnp.asarray(floor, jnp.int32), -1, -2)  # (C, T)
    if backend == "jnp":
        first, found = ref.research_ref(wl_c, taken_c, floor_c)
    else:
        t = wl_c.shape[-1]
        tp = _padded_t(t)
        # Padded trials: all-invalid tables (wl = -1) -> found = 0, sliced.
        if tp != t:
            wl_c = jnp.pad(wl_c, [(0, 0)] * (wl_c.ndim - 1) + [(0, tp - t)],
                           constant_values=-1)
            taken_c = _pad_cols(taken_c, tp)
            floor_c = _pad_cols(floor_c, tp)
        first, found = research_pallas(
            wl_c, taken_c, floor_c, interpret=(backend == "interpret")
        )
        first, found = first[..., : t], found[..., : t]
    return (
        jnp.swapaxes(first, -1, -2),
        jnp.swapaxes(found, -1, -2).astype(bool),
    )


def build_tables(laser, ring, fsr, tr, *, visible=None, max_alias=8,
                 max_entries=None, backend="auto"):
    """(T, N) inputs (tr = actual per-ring TR) -> core-layout tables.

    visible: optional core-layout bool mask of lines on the bus — (T, N_wl)
    or (T, N_ring, N_wl) — for the masked re-search path (None = all lines).
    Returns (delta (T, N, E), wl (T, N, E), n_valid (T, N)).
    """
    backend = _resolve(backend)
    cols = [_to_cols(a) for a in (laser, ring, fsr, tr)]
    # Core (T, ...) -> kernel trials-last layouts, last-axes moves only.
    vis_cols = None
    if visible is not None:
        vis_cols = (jnp.swapaxes(visible, -1, -2) if visible.ndim == 2
                    else jnp.moveaxis(visible, -3, -1))
    if backend == "jnp":
        d, w, nv = ref.table_ref(
            *cols, visible=vis_cols, max_alias=max_alias, max_entries=max_entries
        )
        to_core = lambda a: jnp.moveaxis(a, -1, -3)  # (N, E, T) -> (T, N, E)
        return to_core(d), to_core(w), jnp.swapaxes(nv, -1, -2)
    t = cols[0].shape[1]
    tp = _padded_t(t)
    cols = [_pad_cols(c, tp) for c in cols]
    if tp != t:
        pad_fix = jnp.zeros((cols[2].shape[0], tp), jnp.float32).at[:, t:].set(1.0)
        cols[2] = cols[2] + pad_fix
    if vis_cols is not None:
        if vis_cols.ndim == 2:  # (N_wl, T) -> per-ring (N_ring, N_wl, T)
            vis_cols = jnp.broadcast_to(
                vis_cols[None], (cols[0].shape[0],) + vis_cols.shape
            )
        # Padded trials see an all-zero mask: empty tables, sliced off below.
        vis_cols = _pad_cols(vis_cols.astype(jnp.int32), tp)
    d, w, nv = table_pallas(
        *cols,
        vis_cols,
        max_alias=max_alias,
        max_entries=max_entries,
        interpret=(backend == "interpret"),
    )
    to_core = lambda a: jnp.moveaxis(a, -1, -3)
    return (
        to_core(d)[:t],
        to_core(w)[:t],
        jnp.swapaxes(nv, -1, -2)[:t],
    )
