"""Pallas TPU kernel: search-table construction (record-phase hot spot).

For every (trial, ring) the wavelength sweep yields up to K = N*(2J+1)
candidate peaks  delta = laser_k - ring_i - j*FSR_i  with 0 <= delta <= TR_i.
The kernel streams the candidate axis in FSR-alias groups and
**rank-merges** each group into a persistent sorted top-E buffer — the
kernel-shaped mirror of ``repro.core.search_table.build_search_tables``:
only the *new* candidates are bitonic-sorted (pow2(N*G) rows, the full
log^2 network), and the buffer join is a single bitonic *merge* of
M = pow2(E + pow2(N*G)) rows.  The merge input [buffer (ascending), BIG
pads, block sorted descending] is ascending-then-descending —
a valid bitonic sequence — so one log2(M)-stage ladder suffices instead
of re-running the full log^2 sort over the buffer every group (at N=32,
J=17 that is ~1.3x fewer compare-exchanges; ~2.7x at N=64, where the row
bound forces single-alias groups and the old kernel re-sorted 17 times).
The group size G is the largest that keeps M at or under ``_VMEM_ROWS``
(256), so VMEM per ring is bounded by 256 rows instead of the dense
K_pad = pow2(N*J) (1024 rows at N=32, J=17: a 4x working-set cut).

Sort keys are (delta, flat candidate index = line*J + alias) compared
lexicographically, so the (unstable) bitonic network still reproduces the
dense stable-argsort tie order exactly — merge order cannot perturb the
emitted (delta, wl) entries.

Layout: trials on lanes.  Per merge step the tile is (M, TB) f32 key +
(M, TB) i32 index — at the 256-row bound and TB=128 that is 256 KiB in
VMEM, processed ring-at-a-time inside the kernel.  The bitonic network is
static (log^2 M stages); each compare-exchange is a reshape into
(blocks, 2, stride, TB) so partners are adjacent — no gathers, no captured
constants, no data-dependent control flow.  An optional ``vis`` input
((N_ring, N_wl, T) 0/1 mask) supports the visible-masked re-search path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

TRIAL_BLOCK = 128
BIG = 3.0e38  # python literal: Pallas kernels must not capture array consts
_VMEM_ROWS = 256  # per-merge sort-tile row bound (key + index pair in VMEM)


def _bitonic_sort_desc(key, idx):
    """Descending bitonic sort along axis 0 by (key, idx) lexicographically.

    The compound key makes the order total on distinct candidates, so the
    non-stable network gives exactly the core builder's stable argsort
    order, reversed.  (Sorting descending replaces a reversal of the
    ascending sort: Mosaic has no ``rev``.)
    """
    k_len, tb = key.shape
    size = 2
    while size <= k_len:
        stride = size // 2
        while stride >= 1:
            blocks = k_len // (2 * stride)
            kr = key.reshape(blocks, 2, stride, tb)
            ir = idx.reshape(blocks, 2, stride, tb)
            a_k, b_k = kr[:, 0], kr[:, 1]
            a_i, b_i = ir[:, 0], ir[:, 1]
            # Descending iff bit `size` of the element index is 0; within
            # one 2*stride block that bit is constant = f(block index).
            blk = jax.lax.broadcasted_iota(jnp.int32, (blocks, stride, tb), 0)
            asc = (blk * (2 * stride)) & size != 0
            gt = (a_k > b_k) | ((a_k == b_k) & (a_i > b_i))
            lt = (a_k < b_k) | ((a_k == b_k) & (a_i < b_i))
            swap = (asc & gt) | (~asc & lt)   # no select on i1: Mosaic
            new_a_k = jnp.where(swap, b_k, a_k)
            new_b_k = jnp.where(swap, a_k, b_k)
            new_a_i = jnp.where(swap, b_i, a_i)
            new_b_i = jnp.where(swap, a_i, b_i)
            key = jnp.stack([new_a_k, new_b_k], axis=1).reshape(k_len, tb)
            idx = jnp.stack([new_a_i, new_b_i], axis=1).reshape(k_len, tb)
            stride //= 2
        size *= 2
    return key, idx


def _bitonic_merge(key, idx):
    """One ascending bitonic *merge* ladder along axis 0 by (key, idx).

    Input rows must form a bitonic sequence (here: ascending buffer, then
    constant-BIG pads, then a descending block).  log2(M) compare-exchange
    stages — the final merge stage of a bitonic sort, without the log^2
    prefix that builds bitonicity from scratch.
    """
    k_len, tb = key.shape
    stride = k_len // 2
    while stride >= 1:
        blocks = k_len // (2 * stride)
        kr = key.reshape(blocks, 2, stride, tb)
        ir = idx.reshape(blocks, 2, stride, tb)
        a_k, b_k = kr[:, 0], kr[:, 1]
        a_i, b_i = ir[:, 0], ir[:, 1]
        swap = (a_k > b_k) | ((a_k == b_k) & (a_i > b_i))
        new_a_k = jnp.where(swap, b_k, a_k)
        new_b_k = jnp.where(swap, a_k, b_k)
        new_a_i = jnp.where(swap, b_i, a_i)
        new_b_i = jnp.where(swap, a_i, b_i)
        key = jnp.stack([new_a_k, new_b_k], axis=1).reshape(k_len, tb)
        idx = jnp.stack([new_a_i, new_b_i], axis=1).reshape(k_len, tb)
        stride //= 2
    return key, idx


def _table_kernel(*refs, max_alias, m_pad, alias_group, has_vis):
    if has_vis:
        laser_ref, ring_ref, fsr_ref, tr_ref, vis_ref = refs[:5]
        delta_ref, wl_ref, nv_ref = refs[5:]
    else:
        laser_ref, ring_ref, fsr_ref, tr_ref = refs[:4]
        vis_ref = None
        delta_ref, wl_ref, nv_ref = refs[4:]
    n, tb = laser_ref.shape
    laser = laser_ref[...]
    j_vals = np.arange(-max_alias, max_alias + 1)
    n_j = len(j_vals)
    e = delta_ref.shape[1]
    groups = [j_vals[g : g + alias_group] for g in range(0, n_j, alias_group)]
    idx_big = n * n_j  # > every real flat index; pads sort last among BIG ties

    def per_ring(i, carry):  # one ring at a time; working set stays (M, TB)
        row = pl.ds(i, 1)
        ring_i = ring_ref[row, :]
        fsr_i = fsr_ref[row, :]
        tr_i = tr_ref[row, :]
        vis_i = (vis_ref[row, :, :][0] != 0) if has_vis else None
        key = jnp.full((e, tb), BIG, jnp.float32)
        idx = jnp.full((e, tb), idx_big, jnp.int32)
        for g, group in enumerate(groups):  # streaming rank-merge over groups
            parts_k, parts_i = [], []
            for jj, j in enumerate(group):
                d = laser - ring_i - float(j) * fsr_i           # (N, TB)
                ok = (d >= 0.0) & (d <= tr_i)
                if has_vis:
                    ok = ok & vis_i
                parts_k.append(jnp.where(ok, d, BIG))
                parts_i.append(
                    jax.lax.broadcasted_iota(jnp.int32, (n, tb), 0) * n_j
                    + (g * alias_group + jj)
                )
            gb = n * len(group)
            gb_pad = 1 << int(np.ceil(np.log2(gb)))
            if gb_pad - gb:
                parts_k.append(jnp.full((gb_pad - gb, tb), BIG, jnp.float32))
                parts_i.append(jnp.full((gb_pad - gb, tb), idx_big, jnp.int32))
            # Full sort of the new block only; the buffer is already sorted.
            blk_k, blk_i = _bitonic_sort_desc(
                jnp.concatenate(parts_k, axis=0), jnp.concatenate(parts_i, axis=0)
            )
            # [buffer asc, (BIG, idx_big) pads, block desc] ascends to the
            # compound maximum and then descends — bitonic, so one merge
            # ladder joins buffer and block (masked candidates are
            # (BIG, real idx) < (BIG, idx_big), keeping the pads maximal).
            pad = m_pad - e - gb_pad
            seq_k = [key] + (
                [jnp.full((pad, tb), BIG, jnp.float32)] if pad else []
            ) + [blk_k]
            seq_i = [idx] + (
                [jnp.full((pad, tb), idx_big, jnp.int32)] if pad else []
            ) + [blk_i]
            key, idx = _bitonic_merge(
                jnp.concatenate(seq_k, axis=0), jnp.concatenate(seq_i, axis=0)
            )
            key, idx = key[:e], idx[:e]

        valid = key < BIG
        delta_ref[row, :, :] = jnp.where(valid, key, float("inf"))[None]
        wl_ref[row, :, :] = jnp.where(valid, idx // n_j, -1)[None]
        nv_ref[row, :] = jnp.sum(valid.astype(jnp.int32), axis=0, keepdims=True)
        return carry

    # A loop, not a static unroll: the compiled program holds one ring's
    # merge network instead of N copies of it.
    jax.lax.fori_loop(0, n, per_ring, 0)


@functools.partial(jax.jit, static_argnames=("max_alias", "max_entries", "interpret"))
def table_pallas(laser, ring, fsr, tr, vis=None, *, max_alias=8, max_entries=None,
                 interpret=False):
    """laser/ring/fsr/tr: (N, T) f32 (tr = actual per-ring tuning ranges);
    vis: optional (N_ring, N_wl, T) 0/1 visibility mask.

    Returns (delta (N, E, T) f32, wl (N, E, T) int32, n_valid (N, T) int32).
    """
    n, t = laser.shape
    assert t % TRIAL_BLOCK == 0, t
    n_j = 2 * max_alias + 1
    k = n * n_j
    e = 3 * n if max_entries is None else max_entries
    e = min(e, k)  # like the dense argsort, at most K entries exist
    # Alias group: as many aliases per rank-merge as fit the VMEM row bound.
    # The merge tile holds the buffer (E) plus the pow2-padded sorted block.
    def tile_rows(g: int) -> int:
        gb_pad = 1 << int(np.ceil(np.log2(n * g)))
        return 1 << int(np.ceil(np.log2(e + gb_pad)))

    rows = max(_VMEM_ROWS, tile_rows(1))
    alias_group = max(g for g in range(1, n_j + 1) if tile_rows(g) <= rows)
    m_pad = tile_rows(alias_group)
    grid = (t // TRIAL_BLOCK,)
    in_spec = pl.BlockSpec((n, TRIAL_BLOCK), lambda b: (0, b))
    has_vis = vis is not None
    in_specs = [in_spec] * 4
    args = [laser, ring, fsr, tr]
    if has_vis:
        in_specs.append(pl.BlockSpec((n, n, TRIAL_BLOCK), lambda b: (0, 0, b)))
        args.append(vis)
    delta, wl, nv = pl.pallas_call(
        functools.partial(
            _table_kernel, max_alias=max_alias, m_pad=m_pad,
            alias_group=alias_group, has_vis=has_vis,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n, e, TRIAL_BLOCK), lambda b: (0, 0, b)),
            pl.BlockSpec((n, e, TRIAL_BLOCK), lambda b: (0, 0, b)),
            pl.BlockSpec((n, TRIAL_BLOCK), lambda b: (0, b)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, e, t), jnp.float32),
            jax.ShapeDtypeStruct((n, e, t), jnp.int32),
            jax.ShapeDtypeStruct((n, t), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return delta, wl, nv
