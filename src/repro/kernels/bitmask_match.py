"""Pallas TPU kernels: batched matching for the ideal LtA arbiter.

Two kernels over the (ring x line) graph, each for a lane of 128 trials:

``_match_kernel`` — bitmask Kuhn perfect-matching existence.  All state is
int32 vectors/tiles:

  adj       (N, TB)  per-ring line bitmask           (input)
  match_wl  (N, TB)  ring -> matched line index, -1  (carried in registers)
  match_rg  (N, TB)  line -> matched ring index, -1
  parent    (N, TB)  line -> BFS-discovering ring

Per left vertex: BFS over alternating paths using lane-wise variable shifts
(TPU VPU supports per-lane shift amounts), then an augmenting walk-back of at
most N steps.

``_bottleneck_kernel`` — single-pass bottleneck matching threshold over f32
edge weights (N, N, TB), mirroring
``repro.core.matching._bottleneck_threshold_sweep``: per left vertex a
Dijkstra-style search minimizing the max edge weight on an alternating path
(``dist``/``parent``/``visited`` all (N, TB)), then the same walk-back.
Selection argmins run as min-reductions over the sublane axis with an iota
tie-break, so results stay bit-identical to the jnp path.

Dynamic row selects use the one-hot reduce trick so nothing requires
cross-sublane gathers.  No data-dependent control flow: fixed fori_loop trip
counts, masks everywhere — the kernels are oblivious to which trials already
finished, exactly like the batched hardware arbiter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TRIAL_BLOCK = 128


def _row_iota(n, tb):
    return jax.lax.broadcasted_iota(jnp.int32, (n, tb), 0)


def _select_row(mat, idx):
    """mat: (N, TB), idx: (TB,) row index per lane -> (TB,) gathered values."""
    n, tb = mat.shape
    hit = _row_iota(n, tb) == idx[None, :]
    return jnp.sum(jnp.where(hit, mat, 0), axis=0)


def _match_kernel(adj_ref, match_wl_ref, ok_ref):
    n, tb = adj_ref.shape
    adj = adj_ref[...]
    match_wl = jnp.full((n, tb), -1, jnp.int32)   # ring -> line
    match_rg = jnp.full((n, tb), -1, jnp.int32)   # line -> ring
    riota = _row_iota(n, tb)

    def per_vertex(i, carry):
        match_wl, match_rg = carry
        matched_mask = jnp.sum(
            jnp.where(match_rg >= 0, jnp.int32(1) << riota, 0), axis=0
        )
        start = _select_row(adj, jnp.full((tb,), i, jnp.int32))
        parent = jnp.where((start[None, :] >> riota) & 1 == 1, i, -1)
        free_wl = jnp.full((tb,), -1, jnp.int32)

        def bfs(_, c):
            frontier, visited, parent, free_wl = c
            free_hit = frontier & ~matched_mask
            lsb = free_hit & -free_hit
            found = (free_hit != 0) & (free_wl < 0)
            lsb_idx = 31 - jax.lax.clz(jnp.maximum(lsb, 1))
            free_wl = jnp.where(found, lsb_idx, free_wl)

            # Expand through matched rings whose line is in the frontier.
            in_front = (match_wl >= 0) & (
                (frontier[None, :] >> jnp.maximum(match_wl, 0)) & 1 == 1
            )
            newly = jnp.where(in_front, adj & ~visited[None, :], 0)

            def per_ring(r, c2):
                nf, parent = c2
                newly_r = _select_row(newly, jnp.full((tb,), r, jnp.int32))
                fresh = newly_r & ~nf
                parent = jnp.where((fresh[None, :] >> riota) & 1 == 1, r, parent)
                return nf | fresh, parent

            union, parent_new = jax.lax.fori_loop(
                0, n, per_ring, (jnp.zeros((tb,), jnp.int32), parent)
            )
            cont = free_wl < 0
            parent = jnp.where(cont[None, :], parent_new, parent)
            new_frontier = jnp.where(cont, union & ~visited, 0)
            visited = visited | union
            return new_frontier, visited, parent, free_wl

        _, _, parent, free_wl = jax.lax.fori_loop(
            0, n, bfs, (start, start, parent, free_wl)
        )

        def walk(_, c):
            match_wl, match_rg, k, active_i = c
            active = active_i != 0
            k_safe = jnp.maximum(k, 0)
            r = _select_row(parent, k_safe)
            r_safe = jnp.maximum(r, 0)
            prev = _select_row(match_wl, r_safe)
            upd_wl = active[None, :] & (riota == r_safe[None, :])
            match_wl = jnp.where(upd_wl, k_safe[None, :], match_wl)
            upd_rg = active[None, :] & (riota == k_safe[None, :])
            match_rg = jnp.where(upd_rg, r_safe[None, :], match_rg)
            active = active & (r_safe != i) & (prev >= 0)
            return (match_wl, match_rg, jnp.where(active, prev, k),
                    active.astype(jnp.int32))

        # Loop carries are int32: Mosaic cannot carry i1 vectors.
        match_wl, match_rg, _, _ = jax.lax.fori_loop(
            0, n, walk,
            (match_wl, match_rg, free_wl, (free_wl >= 0).astype(jnp.int32)),
        )
        return match_wl, match_rg

    match_wl, match_rg = jax.lax.fori_loop(0, n, per_vertex, (match_wl, match_rg))
    match_wl_ref[...] = match_wl
    ok_ref[0, :] = jnp.all(match_wl >= 0, axis=0).astype(jnp.int32)


def _bottleneck_kernel(w_ref, thr_ref):
    # Per-lane vectors are kept 2-D (1, TB) so every broadcast is a
    # lane-aligned row broadcast: Mosaic has no sublane broadcast of 1-D
    # values.  Loop carries are int32 or f32, never bool.
    n, _, tb = w_ref.shape
    w = w_ref[...]                                    # (ring, wl, trial) f32
    riota = _row_iota(n, tb)
    iota3 = jax.lax.broadcasted_iota(jnp.int32, (n, n, tb), 0)
    inf = jnp.float32(jnp.inf)

    def select_row(mat, idx):
        """mat (N, TB), idx (1, TB) row index per lane -> (1, TB)."""
        return jnp.sum(jnp.where(riota == idx, mat, 0), axis=0, keepdims=True)

    def ring_row(r):
        """(1, TB) ring index per lane -> (N, TB) that ring's weight row."""
        return jnp.sum(jnp.where(iota3 == r[None], w, 0.0), axis=0)

    def first_min(d):
        """(N, TB) -> per-lane (min value, lowest index attaining it)."""
        dmin = jnp.min(d, axis=0, keepdims=True)
        idx = jnp.min(jnp.where(d == dmin, riota, n), axis=0, keepdims=True)
        return dmin, idx

    def per_vertex(i, carry):
        match_wl, match_rg, thr = carry
        dist = jnp.sum(jnp.where(iota3 == i, w, 0.0), axis=0)   # w[i] (N, TB)
        parent = jnp.full((n, tb), i, jnp.int32)
        visited = jnp.zeros((n, tb), jnp.int32)

        def select_relax(_, c):
            dist, parent, visited = c
            d = jnp.where(visited == 1, inf, dist)
            dk, k = first_min(d)
            visited = jnp.where(riota == k, 1, visited)
            r = select_row(match_rg, k)               # matched ring or -1
            r_safe = jnp.maximum(r, 0)
            cand = jnp.maximum(dk, ring_row(r_safe))
            better = (r >= 0) & (visited == 0) & (cand < dist)
            dist = jnp.where(better, cand, dist)
            parent = jnp.where(better, r_safe, parent)
            return dist, parent, visited

        dist, parent, _ = jax.lax.fori_loop(
            0, n, select_relax, (dist, parent, visited)
        )
        df = jnp.where(match_rg < 0, dist, inf)
        best, k0 = first_min(df)
        thr = jnp.maximum(thr, best)

        def walk(_, c):
            match_wl, match_rg, k, active_i = c
            active = active_i != 0
            r = select_row(parent, k)
            r_safe = jnp.maximum(r, 0)
            prev = select_row(match_wl, r_safe)
            upd_wl = active & (riota == r_safe)
            match_wl = jnp.where(upd_wl, k, match_wl)
            upd_rg = active & (riota == k)
            match_rg = jnp.where(upd_rg, r_safe, match_rg)
            active = active & (r != i)
            return (match_wl, match_rg,
                    jnp.where(active, jnp.maximum(prev, 0), k),
                    active.astype(jnp.int32))

        match_wl, match_rg, _, _ = jax.lax.fori_loop(
            0, n, walk, (match_wl, match_rg, k0, jnp.ones((1, tb), jnp.int32))
        )
        return match_wl, match_rg, thr

    _, _, thr = jax.lax.fori_loop(
        0, n, per_vertex,
        (
            jnp.full((n, tb), -1, jnp.int32),
            jnp.full((n, tb), -1, jnp.int32),
            jnp.full((1, tb), -jnp.inf, jnp.float32),
        ),
    )
    thr_ref[...] = thr


@functools.partial(jax.jit, static_argnames=("interpret",))
def bottleneck_pallas(w, *, interpret=False):
    """w: (N, N, T) f32 edge weights (ring x wl x trial), T % TRIAL_BLOCK == 0.

    Returns (T,) f32 bottleneck matching thresholds.
    """
    n, _, t = w.shape
    assert t % TRIAL_BLOCK == 0, t
    grid = (t // TRIAL_BLOCK,)
    thr = pl.pallas_call(
        _bottleneck_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, n, TRIAL_BLOCK), lambda b: (0, 0, b))],
        out_specs=pl.BlockSpec((1, TRIAL_BLOCK), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, t), jnp.float32),
        interpret=interpret,
    )(w)
    return thr[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_pallas(adj, *, interpret=False):
    """adj: (N, T) int32 per-ring line bitmasks, T % TRIAL_BLOCK == 0.

    Returns (match_wl (N, T) int32, perfect (T,) bool).
    """
    n, t = adj.shape
    assert t % TRIAL_BLOCK == 0, t
    grid = (t // TRIAL_BLOCK,)
    match_wl, ok = pl.pallas_call(
        _match_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((n, TRIAL_BLOCK), lambda b: (0, b))],
        out_specs=[
            pl.BlockSpec((n, TRIAL_BLOCK), lambda b: (0, b)),
            pl.BlockSpec((1, TRIAL_BLOCK), lambda b: (0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t), jnp.int32),
            jax.ShapeDtypeStruct((1, t), jnp.int32),
        ],
        interpret=interpret,
    )(adj)
    return match_wl, ok[0].astype(bool)
