"""Pallas TPU kernel: batched masked re-search (the protocol engine's unit
primitive — ``repro.core.protocol.masked_first_entry``).

One kernel invocation re-searches C search tables per trial at once against
the captured-line mask: for each (table row, trial) lane pair it returns the
first entry at-or-after the row's ``floor`` whose line id is valid and not
captured.  The protocol engine issues one such call per displacement-chain
hop (all donor candidates together) and per probe-pass rank — batching the
re-searches is what keeps an O(N^3)-probe protocol round a handful of
kernel launches instead of O(N^2) scalar searches.

Layout follows the house convention (trials on lanes):

  wl     (C, E, TB) int32   line id of each entry, -1 padding
  taken  (L, TB)    int32   0/1 captured-line mask
  floor  (C, TB)    int32   first admissible entry index per row

  first  (C, TB)    int32   chosen entry index, -1 if none visible
  found  (C, TB)    int32   0/1

The captured-line lookup runs as an L-step one-hot accumulation over the
sublane axis (the same no-cross-sublane-gather trick as
``bitmask_match``); the first-visible reduction is a masked iota min over
the entry axis.  No data-dependent control flow anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitmask_match import TRIAL_BLOCK


def _research_kernel(wl_ref, taken_ref, floor_ref, first_ref, found_ref):
    c, e, tb = wl_ref.shape
    n_lines = taken_ref.shape[0]
    wl = wl_ref[...]
    taken = taken_ref[...]
    floor = floor_ref[...]
    eiota = jax.lax.broadcasted_iota(jnp.int32, (c, e, tb), 1)
    liota = jax.lax.broadcasted_iota(jnp.int32, (n_lines, tb), 0)

    # The carry is an int32 0/1 mask: Mosaic cannot carry i1 vectors
    # through a loop.
    def acc_taken(i, acc):
        t_i = jnp.sum(jnp.where(liota == i, taken, 0), axis=0, keepdims=True)
        return acc | ((wl == i) & (t_i[None] > 0)).astype(jnp.int32)

    taken_at = jax.lax.fori_loop(
        0, n_lines, acc_taken, jnp.zeros((c, e, tb), jnp.int32)
    )
    vis = (wl >= 0) & (taken_at == 0) & (eiota >= floor[:, None, :])
    first = jnp.min(jnp.where(vis, eiota, e), axis=1)            # (C, TB)
    found = first < e
    first_ref[...] = jnp.where(found, first, -1)
    found_ref[...] = found.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def research_pallas(wl, taken, floor, *, interpret=False):
    """wl (C, E, T) int32, taken (L, T) int32, floor (C, T) int32;
    T % TRIAL_BLOCK == 0.  Returns (first (C, T) int32, found (C, T) int32).
    """
    c, e, t = wl.shape
    n_lines = taken.shape[0]
    assert t % TRIAL_BLOCK == 0, t
    grid = (t // TRIAL_BLOCK,)
    first, found = pl.pallas_call(
        _research_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, e, TRIAL_BLOCK), lambda b: (0, 0, b)),
            pl.BlockSpec((n_lines, TRIAL_BLOCK), lambda b: (0, b)),
            pl.BlockSpec((c, TRIAL_BLOCK), lambda b: (0, b)),
        ],
        out_specs=[
            pl.BlockSpec((c, TRIAL_BLOCK), lambda b: (0, b)),
            pl.BlockSpec((c, TRIAL_BLOCK), lambda b: (0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, t), jnp.int32),
            jax.ShapeDtypeStruct((c, t), jnp.int32),
        ],
        interpret=interpret,
    )(wl, taken, floor)
    return first, found
