"""Batched exact bipartite matching for the Lock-to-Any ideal arbiter.

Three exact formulations, dispatched by channel count:

  * N <= ``_HALL_MAX_N``: Hall's condition over all 2^N ring subsets —
    loop-free elementwise/reduction work (existence and bottleneck).
  * N >  ``_HALL_MAX_N``: a single-pass *bottleneck sweep* — for each left
    vertex a Dijkstra-style search over alternating paths that minimizes the
    maximum edge weight, so the bottleneck threshold comes from ONE matching
    pass instead of ~log(N^2) full Kuhn runs under a binary search.
    Existence queries reuse the same pass on 0/1 weights.
  * Kuhn's augmenting-path algorithm (``max_matching``, and the binary
    search ``_bottleneck_threshold_kuhn``): the exactness oracle the fast
    paths are pinned against bit-for-bit, and the producer of an explicit
    matching when one is needed.

All paths are vectorized over a batch of trials with fixed trip counts and
no data-dependent control flow, so they map cleanly onto TPU (Kuhn existence
and the bottleneck sweep are mirrored by the Pallas kernels in
``repro.kernels.bitmask_match``).

For Kuhn, each left vertex (ring) BFSes over alternating paths:
  frontier of wavelengths -> matched rings -> their adjacency -> ...
recording ``parent`` (the ring from which each wavelength was first reached)
so the augmenting path can be walked back in <= N steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.phase import stage

#: Up to this many channels, matching existence and bottleneck thresholds are
#: evaluated via Hall's condition over all 2^N ring subsets — pure
#: elementwise/reduction work with no sequential augmenting loops, which is
#: far faster on CPU and vmaps cleanly inside the sweep engine.  Beyond it
#: the subset table would dominate memory and Kuhn's algorithm takes over.
_HALL_MAX_N = 10


@functools.lru_cache(maxsize=None)
def _subset_masks(n: int) -> np.ndarray:
    """(2^n, n) bool: row s = membership mask of subset s."""
    s = np.arange(1 << n, dtype=np.uint32)
    return ((s[:, None] >> np.arange(n)) & 1).astype(bool)


@functools.lru_cache(maxsize=None)
def _sorting_network(n: int) -> tuple:
    """Batcher odd-even compare-exchange pairs for a power-of-two n.

    XLA's comparator sort is far slower than a fixed min/max network on the
    small trailing lane axis of the Hall subset table, and the network is
    pure elementwise ops so it fuses and vmaps freely.
    """
    assert n & (n - 1) == 0, n
    pairs = []

    def merge(lo, m, r):
        step = r * 2
        if step < m:
            merge(lo, m, step)
            merge(lo + r, m, step)
            pairs.extend((i, i + r) for i in range(lo + r, lo + m - r, step))
        else:
            pairs.append((lo, lo + r))

    def sort(lo, m):
        if m > 1:
            h = m // 2
            sort(lo, h)
            sort(lo + h, h)
            merge(lo, m, 1)

    sort(0, n)
    return tuple(pairs)


def adjacency_bitmask(reach: jax.Array) -> jax.Array:
    """(T, N, N) bool reach[t, ring, wl] -> packed per-ring wl bitmasks.

    N <= 32 packs into a single int32 word per ring — (T, N), the layout the
    Pallas matching kernel consumes, unchanged bit-for-bit.  Wider systems
    (e.g. WDM64) pack into ``ceil(N / 32)`` little-endian uint32 words —
    (T, N, W) — consumed by the multiword Kuhn path in ``max_matching``.
    """
    n = reach.shape[-1]
    if n > 32:
        return _pack_words(reach)
    bits = (1 << jnp.arange(n, dtype=jnp.int32))[None, None, :]
    return jnp.sum(jnp.where(reach, bits, 0), axis=-1).astype(jnp.int32)


def _pack_words(bits: jax.Array) -> jax.Array:
    """(..., n) bool -> (..., W) uint32, little-endian 32-bit words."""
    n = bits.shape[-1]
    w = -(-n // 32)
    pad = w * 32 - n
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bool)], axis=-1
        )
    lanes = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    grouped = bits.reshape(bits.shape[:-1] + (w, 32))
    return jnp.sum(jnp.where(grouped, lanes, jnp.uint32(0)), axis=-1,
                   dtype=jnp.uint32)


def _unpack_words(words: jax.Array, n: int) -> jax.Array:
    """(..., W) uint32 -> (..., n) bool."""
    idx = jnp.arange(n) // 32
    shift = jnp.arange(n, dtype=jnp.uint32) % 32
    return ((words[..., idx] >> shift) & 1).astype(bool)


def _augment_one(adj: jax.Array, match_wl: jax.Array, match_ring: jax.Array, i: jax.Array):
    """Try to augment the matching from left vertex (ring) ``i``.

    adj:        (T, N) int32 — ring -> wavelength bitmask
    match_wl:   (T, N) int32 — ring -> matched wl index, -1 if free
    match_ring: (T, N) int32 — wl   -> matched ring index, -1 if free
    """
    T, N = adj.shape
    rows = jnp.arange(T)

    # --- BFS over alternating paths -------------------------------------
    start = adj[rows, i]                                   # (T,) frontier bitmask
    parent = jnp.where(start[:, None] >> jnp.arange(N) & 1 == 1, i, -1).astype(jnp.int32)
    matched_mask = _matched_bitmask(match_ring)            # (T,) int32

    def bfs_body(_, carry):
        frontier, visited, parent, free_wl = carry
        # Wavelengths in frontier that are free -> augmenting path found.
        free_hit = frontier & ~matched_mask
        found_now = (free_hit != 0) & (free_wl < 0)
        free_wl = jnp.where(found_now, _lowest_bit_index(free_hit), free_wl)
        # Expand via matched rings of (non-free) frontier wavelengths.
        new_frontier = jnp.zeros_like(frontier)
        new_parent = parent

        def ring_body(r, inner):
            nf, par = inner
            # is ring r matched to some wavelength in the frontier?
            wl_of_r = match_wl[rows, r]                    # (T,)
            in_frontier = (wl_of_r >= 0) & ((frontier >> wl_of_r) & 1 == 1)
            newly = jnp.where(in_frontier, adj[rows, r] & ~visited & ~nf, 0)
            par = jnp.where((newly[:, None] >> jnp.arange(N)) & 1 == 1, r, par)
            return nf | newly, par

        new_frontier, new_parent = jax.lax.fori_loop(
            0, N, ring_body, (new_frontier, new_parent)
        )
        cont = free_wl < 0
        frontier = jnp.where(cont, new_frontier & ~visited, 0)
        visited = visited | new_frontier
        parent = jnp.where((free_wl < 0)[:, None], new_parent, parent)
        return frontier, visited, parent, free_wl

    free_wl0 = jnp.full((T,), -1, jnp.int32)
    _, _, parent, free_wl = jax.lax.fori_loop(
        0, N, bfs_body, (start, start, parent, free_wl0)
    )

    # --- walk the augmenting path back, flipping matched edges ----------
    def walk_body(_, carry):
        match_wl, match_ring, k, active = carry
        k_safe = jnp.maximum(k, 0)
        r = parent[rows, k_safe]
        r_safe = jnp.maximum(r, 0)
        prev = match_wl[rows, r_safe]                      # wl r was matched to
        match_wl = match_wl.at[rows, r_safe].set(jnp.where(active, k_safe, match_wl[rows, r_safe]))
        match_ring = match_ring.at[rows, k_safe].set(jnp.where(active, r_safe, match_ring[rows, k_safe]))
        active = active & (r_safe != i) & (prev >= 0)
        return match_wl, match_ring, jnp.where(active, prev, k), active

    active0 = free_wl >= 0
    match_wl, match_ring, _, _ = jax.lax.fori_loop(
        0, N, walk_body, (match_wl, match_ring, free_wl, active0)
    )
    return match_wl, match_ring


def _matched_bitmask(match_ring: jax.Array) -> jax.Array:
    """(T, N) wl->ring matching -> (T,) bitmask of matched wavelengths."""
    N = match_ring.shape[1]
    bits = (1 << jnp.arange(N, dtype=jnp.int32))[None, :]
    return jnp.sum(jnp.where(match_ring >= 0, bits, 0), axis=1).astype(jnp.int32)


def _lowest_bit_index(x: jax.Array) -> jax.Array:
    """Index of lowest set bit (x != 0 assumed where used)."""
    lsb = x & -x
    return (31 - jax.lax.clz(lsb)).astype(jnp.int32)


def _augment_one_wide(adj: jax.Array, match_wl: jax.Array, match_ring: jax.Array, i: jax.Array):
    """Kuhn augmentation from ring ``i`` on an unpacked (T, N, N) bool
    adjacency — the N > 32 mirror of ``_augment_one``.  Frontier/visited
    masks are (T, N) bool lanes instead of int32 words; identical BFS order
    (lowest wavelength index first), so matchings agree with the single-word
    path wherever both apply."""
    T, N, _ = adj.shape
    rows = jnp.arange(T)

    start = adj[rows, i]                                   # (T, N) bool
    parent = jnp.where(start, i, -1).astype(jnp.int32)
    matched = match_ring >= 0                              # (T, N) bool

    def bfs_body(_, carry):
        frontier, visited, parent, free_wl = carry
        free_hit = frontier & ~matched
        found_now = free_hit.any(axis=1) & (free_wl < 0)
        free_wl = jnp.where(
            found_now, jnp.argmax(free_hit, axis=1).astype(jnp.int32), free_wl
        )
        new_frontier = jnp.zeros_like(frontier)
        new_parent = parent

        def ring_body(r, inner):
            nf, par = inner
            wl_of_r = match_wl[rows, r]                    # (T,)
            in_frontier = (wl_of_r >= 0) & jnp.take_along_axis(
                frontier, jnp.maximum(wl_of_r, 0)[:, None], axis=1
            )[:, 0]
            newly = adj[rows, r] & ~visited & ~nf & in_frontier[:, None]
            par = jnp.where(newly, r, par)
            return nf | newly, par

        new_frontier, new_parent = jax.lax.fori_loop(
            0, N, ring_body, (new_frontier, new_parent)
        )
        cont = free_wl < 0
        frontier = jnp.where(cont[:, None], new_frontier & ~visited, False)
        visited = visited | new_frontier
        parent = jnp.where(cont[:, None], new_parent, parent)
        return frontier, visited, parent, free_wl

    free_wl0 = jnp.full((T,), -1, jnp.int32)
    _, _, parent, free_wl = jax.lax.fori_loop(
        0, N, bfs_body, (start, start, parent, free_wl0)
    )

    def walk_body(_, carry):
        match_wl, match_ring, k, active = carry
        k_safe = jnp.maximum(k, 0)
        r = parent[rows, k_safe]
        r_safe = jnp.maximum(r, 0)
        prev = match_wl[rows, r_safe]
        match_wl = match_wl.at[rows, r_safe].set(jnp.where(active, k_safe, match_wl[rows, r_safe]))
        match_ring = match_ring.at[rows, k_safe].set(jnp.where(active, r_safe, match_ring[rows, k_safe]))
        active = active & (r_safe != i) & (prev >= 0)
        return match_wl, match_ring, jnp.where(active, prev, k), active

    active0 = free_wl >= 0
    match_wl, match_ring, _, _ = jax.lax.fori_loop(
        0, N, walk_body, (match_wl, match_ring, free_wl, active0)
    )
    return match_wl, match_ring


@jax.jit
def max_matching(adj: jax.Array):
    """Run Kuhn over all left vertices.  Returns (match_wl, match_ring).

    Accepts either a single-word (T, N) int32 adjacency (N <= 32, the
    original path, unchanged) or a multiword (T, N, W) uint32 one from
    ``adjacency_bitmask`` at N > 32, which runs on unpacked bool lanes.
    """
    with stage("matching"):
        if adj.ndim == 3:
            t, n, _ = adj.shape
            adj_bool = _unpack_words(adj, n)                   # square: N wls
            match_wl = jnp.full((t, n), -1, jnp.int32)
            match_ring = jnp.full((t, n), -1, jnp.int32)

            def body_wide(i, carry):
                return _augment_one_wide(adj_bool, *carry, i=i)

            return jax.lax.fori_loop(0, n, body_wide, (match_wl, match_ring))

        T, N = adj.shape
        match_wl = jnp.full((T, N), -1, jnp.int32)
        match_ring = jnp.full((T, N), -1, jnp.int32)

        def body(i, carry):
            return _augment_one(adj, *carry, i=i)

        return jax.lax.fori_loop(0, N, body, (match_wl, match_ring))


def _has_perfect_matching_hall(reach: jax.Array) -> jax.Array:
    """Hall's condition: a perfect matching exists iff every ring subset S
    reaches at least |S| laser lines.  Loop-free (the n-step accumulation
    unrolls to elementwise ops on a (T, 2^n, n) table)."""
    T, n, _ = reach.shape
    sub = jnp.asarray(_subset_masks(n))                    # (S, n)
    size = jnp.asarray(_subset_masks(n).sum(1), jnp.int32)  # (S,)
    nbr = jnp.zeros((T, sub.shape[0], n), bool)
    for i in range(n):
        nbr = jnp.where(sub[None, :, i:i + 1], nbr | reach[:, None, i, :], nbr)
    ok = nbr.sum(axis=-1) >= size[None, :]
    return ok.all(axis=1)


def has_perfect_matching(reach: jax.Array) -> jax.Array:
    """(T, N, N) bool reach -> (T,) bool perfect matching existence.

    N > ``_HALL_MAX_N`` runs the bottleneck sweep on 0/1 weights: a perfect
    matching within ``reach`` exists iff a bottleneck using only weight-0
    edges exists.  One pass, ~N x fewer sequential steps than Kuhn (whose
    BFS nests an N-trip ring expansion inside each of N levels); booleans
    are identical to ``max_matching`` (both exact).
    """
    with stage("matching"):
        if reach.shape[-1] <= _HALL_MAX_N:
            return _has_perfect_matching_hall(reach)
        weights = jnp.where(reach, jnp.float32(0), jnp.float32(1))
        return _bottleneck_threshold_sweep(weights) < 0.5


def _bottleneck_threshold_sweep(weights: jax.Array) -> jax.Array:
    """Single-pass bottleneck matching threshold for a (T, N, N) batch.

    Incremental formulation: left vertices (rings) are inserted one at a
    time; for each, a Dijkstra-style search over alternating paths finds the
    augmenting path minimizing the maximum edge weight along it
    (``dist[k]`` = cheapest achievable path bottleneck from vertex ``i`` to
    wavelength ``k`` given the current matching).  The global threshold is
    the running max of the per-vertex augmentation bottlenecks — exactly the
    minimum t such that {weights <= t} admits a perfect matching, because
    feasible thresholds for covering the first i vertices form an up-set and
    a maximum matching on cheaper edges always extends by one augmenting
    path.  Only comparisons and max-compositions of input values are
    performed, so the result is bit-for-bit one of the N^2 edge weights and
    identical to the Kuhn binary-search oracle.

    Fixed trip counts throughout: N vertices x (N selection steps + N
    walk-back steps) — one matching pass, vs ~ceil(log2 N^2)+1 full Kuhn
    runs for the binary search it replaces.
    """
    T, N, _ = weights.shape
    rows = jnp.arange(T)
    inf = jnp.float32(jnp.inf)

    def per_vertex(i, carry):
        match_wl, match_ring, thr = carry

        # --- Dijkstra over alternating paths, bottleneck (max) metric ----
        dist = weights[:, i, :]                        # (T, N)
        parent = jnp.full((T, N), i, jnp.int32)        # wl -> relaxing ring
        visited = jnp.zeros((T, N), bool)

        def select_relax(_, c):
            dist, parent, visited = c
            d = jnp.where(visited, inf, dist)
            k = jnp.argmin(d, axis=1).astype(jnp.int32)   # (T,) settled wl
            dk = jnp.min(d, axis=1)
            visited = visited.at[rows, k].set(True)
            r = match_ring[rows, k]                    # matched ring or -1
            r_safe = jnp.maximum(r, 0)
            cand = jnp.maximum(dk[:, None], weights[rows, r_safe, :])
            # Free wavelengths end the path: no expansion through them.
            better = (r[:, None] >= 0) & ~visited & (cand < dist)
            dist = jnp.where(better, cand, dist)
            parent = jnp.where(better, r_safe[:, None], parent)
            return dist, parent, visited

        dist, parent, _ = jax.lax.fori_loop(
            0, N, select_relax, (dist, parent, visited)
        )

        # --- cheapest free wavelength = this vertex's augmentation cost ---
        df = jnp.where(match_ring < 0, dist, inf)      # >= 1 free wl always
        k0 = jnp.argmin(df, axis=1).astype(jnp.int32)
        thr = jnp.maximum(thr, jnp.min(df, axis=1))

        # --- walk the augmenting path back, flipping matched edges -------
        def walk(_, c):
            match_wl, match_ring, k, active = c
            r = parent[rows, k]
            prev = match_wl[rows, r]                   # wl r was matched to
            match_wl = match_wl.at[rows, r].set(
                jnp.where(active, k, match_wl[rows, r])
            )
            match_ring = match_ring.at[rows, k].set(
                jnp.where(active, r, match_ring[rows, k])
            )
            active = active & (r != i)
            return match_wl, match_ring, jnp.where(active, jnp.maximum(prev, 0), k), active

        match_wl, match_ring, _, _ = jax.lax.fori_loop(
            0, N, walk, (match_wl, match_ring, k0, jnp.ones((T,), bool))
        )
        return match_wl, match_ring, thr

    match_wl0 = jnp.full((T, N), -1, jnp.int32)
    match_ring0 = jnp.full((T, N), -1, jnp.int32)
    thr0 = jnp.full((T,), -jnp.inf, jnp.float32)
    _, _, thr = jax.lax.fori_loop(0, N, per_vertex, (match_wl0, match_ring0, thr0))
    return thr


def _bottleneck_threshold_hall(weights: jax.Array) -> jax.Array:
    """Bottleneck threshold via Hall: subset S becomes satisfiable once the
    |S|-th smallest of (min over i in S of w[i, k]) is reached, and the
    bottleneck is the worst subset's requirement.  One shot, no search.

    Runs the subset DP on uint8 *ranks* instead of f32 weights (4x less
    traffic through the (T, 2^N, N) table; this path is memory-bound), with
    ranks from an all-pairs comparison count and the k-th selection from a
    fixed min/max sorting network — no XLA comparator sorts anywhere.  Rank
    -> value is monotone (ties share a rank and a value) so every comparison,
    selection and max lands on the same edge weight the f32 computation would
    pick — the result stays bit-for-bit equal to the binary-search reference.
    """
    T, n, _ = weights.shape
    sub = jnp.asarray(_subset_masks(n))                    # (S, n)
    size = jnp.asarray(_subset_masks(n).sum(1), jnp.int32)
    n_sub = sub.shape[0]
    flat = weights.reshape(T, n * n)
    # rank_e = |{e' : w_e' < w_e}|  (== searchsorted-left into sorted edges)
    ranks = jnp.sum(
        (flat[:, None, :] < flat[:, :, None]), axis=-1
    ).astype(jnp.uint8)                                    # (T, n^2), max n^2-1
    rank_grid = ranks.reshape(T, n, n)
    minr = jnp.full((T, n_sub, n), 255, jnp.uint8)
    for i in range(n):
        minr = jnp.where(
            sub[None, :, i:i + 1], jnp.minimum(minr, rank_grid[:, None, i, :]), minr
        )
    # Ascending per-subset lanes via the compare-exchange network (255-padded
    # to the next power of two; pads sink to the tail, past any real size).
    m = 1 << (n - 1).bit_length()
    lanes = [minr[..., k] for k in range(n)]
    lanes += [jnp.full(minr.shape[:-1], 255, jnp.uint8)] * (m - n)
    for i, j in _sorting_network(m):
        lanes[i], lanes[j] = (
            jnp.minimum(lanes[i], lanes[j]), jnp.maximum(lanes[i], lanes[j])
        )
    vals = jnp.stack(lanes, axis=-1)                       # (T, S, m) ascending
    idx = jnp.broadcast_to(
        jnp.clip(size - 1, 0)[None, :, None], (T, n_sub, 1)
    )
    req = jnp.take_along_axis(vals, idx, axis=-1)[..., 0]  # (T, S) uint8 ranks
    req = jnp.where(size[None, :] > 0, req, 0)
    bottleneck_rank = req.max(axis=1)                      # (T,)
    # The bottleneck is the edge weight carrying that rank (ties share it).
    return jnp.max(
        jnp.where(ranks == bottleneck_rank[:, None], flat, -jnp.inf), axis=-1
    )


def bottleneck_matching_threshold(weights: jax.Array) -> jax.Array:
    """Minimum t such that a perfect matching exists in {weights <= t}.

    weights: (T, N, N) scaled residuals (ring x wl).  Small N uses the
    loop-free Hall formulation; larger N the single-pass bottleneck sweep
    (``_bottleneck_threshold_sweep``).  The bottleneck value is always one
    of the N^2 edge weights, bit-for-bit equal to the retired Kuhn binary
    search (``_bottleneck_threshold_kuhn``, kept as the exactness oracle).
    Returns (T,) float32.
    """
    with stage("matching"):
        if weights.shape[-1] <= _HALL_MAX_N:
            return _bottleneck_threshold_hall(weights)
        return _bottleneck_threshold_sweep(weights)


def _bottleneck_threshold_kuhn(weights: jax.Array, n_steps: int | None = None) -> jax.Array:
    """Exactness oracle: binary search over sorted per-trial edge weights
    with a full Kuhn matching-existence query per step — the pre-sweep
    (PR 1) N > ``_HALL_MAX_N`` path, ~ceil(log2 N^2)+1 Kuhn runs."""
    T, N, _ = weights.shape
    flat = weights.reshape(T, N * N)
    cand = jnp.sort(flat, axis=1)                          # (T, N^2) ascending
    steps = n_steps if n_steps is not None else int(math.ceil(math.log2(N * N))) + 1

    lo = jnp.zeros((T,), jnp.int32)
    hi = jnp.full((T,), N * N - 1, jnp.int32)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        thr = cand[jnp.arange(T), mid]
        mw, _ = max_matching(adjacency_bitmask(weights <= thr[:, None, None]))
        ok = jnp.all(mw >= 0, axis=1)
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return cand[jnp.arange(T), hi]
