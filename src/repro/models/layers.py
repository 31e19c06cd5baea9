"""Model layers: norms, rotary embedding, chunked (flash-style) attention,
dense/MoE FFNs, and the Mamba-2 SSD mixer.  Pure functional JAX; parameters
are plain dict pytrees.  Compute in bf16, reductions/softmax in fp32.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.ctx import constrain, current_axes

from .config import ModelConfig

COMPUTE_DTYPE = jnp.bfloat16
NEG_INF = -1e30


# ----------------------------------------------------------------- norms
def rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


# ----------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., L, H, hd); positions: (..., L)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., L, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------- chunked attention
def _largest_divisor(n: int, at_most: int) -> int:
    for c in range(at_most, 0, -1):
        if n % c == 0:
            return c
    return n


def flash_attention(q, k, v, *, causal: bool, q_chunk: int, kv_chunk: int,
                    q_offset=0, causal_skip: bool = False):
    if causal and causal_skip and q.shape[1] == k.shape[1] and q_offset == 0:
        return flash_attention_causal_pairs(
            q, k, v, chunk=min(q_chunk, kv_chunk)
        )
    return _flash_attention_dense(
        q, k, v, causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk,
        q_offset=q_offset,
    )


def _flash_attention_dense(q, k, v, *, causal: bool, q_chunk: int,
                           kv_chunk: int, q_offset=0):
    """Memory-bounded attention: lax.scan over KV chunks with online softmax,
    outer scan over Q chunks.  Never materializes (Lq, Lkv) scores beyond a
    (q_chunk, kv_chunk) tile — the pure-XLA analogue of FlashAttention,
    shaped for TPU (tile dims are multiples of 128).

    q: (B, Lq, H, hd); k/v: (B, Lkv, KVH, hd).  GQA via head grouping.
    q_offset: absolute position of q[0] (for causal masking in prefill with
    cache or chunked decode).  Returns (B, Lq, H, hd).
    """
    B, Lq, H, hd = q.shape
    _, Lkv, KVH, _ = k.shape
    group = H // KVH
    scale = hd ** -0.5

    q_chunk = _largest_divisor(Lq, min(q_chunk, Lq))
    kv_chunk = _largest_divisor(Lkv, min(kv_chunk, Lkv))
    nq, nkv = Lq // q_chunk, Lkv // kv_chunk

    # (B, nq, qc, KVH, group, hd)
    qr = constrain(
        q.reshape(B, nq, q_chunk, KVH, group, hd),
        ("batch", None, None, None, None, None),
    )
    kr = constrain(
        k.reshape(B, nkv, kv_chunk, KVH, hd), ("batch", None, None, None, None)
    )
    vr = constrain(
        v.reshape(B, nkv, kv_chunk, KVH, hd), ("batch", None, None, None, None)
    )

    def q_step(_, qi):
        qb, qidx = qi                                   # (B, qc, KVH, g, hd)
        q_pos = q_offset + qidx * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb, kidx = ki
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qb, kb, preferred_element_type=jnp.float32
            ) * scale                                   # (B, KVH, g, qc, kc)
            if causal:
                k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        # constrain the online-softmax carries: pins every tensor in the KV
        # scan (scores included) to batch-sharded layout.
        m0 = constrain(
            jnp.full((B, KVH, group, q_chunk), NEG_INF, jnp.float32),
            ("batch", None, None, None),
        )
        l0 = jnp.zeros_like(m0)
        a0 = constrain(
            jnp.zeros((B, KVH, group, q_chunk, hd), jnp.float32),
            ("batch", None, None, None, None),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kr.transpose(1, 0, 2, 3, 4), vr.transpose(1, 0, 2, 3, 4),
             jnp.arange(nkv)),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 3, 1, 2, 4)       # (B, qc, KVH, g, hd)

    _, outs = jax.lax.scan(
        q_step, None, (qr.transpose(1, 0, 2, 3, 4, 5), jnp.arange(nq))
    )
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Lq, H, hd)
    return out.astype(q.dtype)


def flash_attention_causal_pairs(q, k, v, *, chunk: int):
    """Causal flash attention over the static lower-triangle tile list.

    The nested q x kv chunk scan computes every (i, j) tile and masks half
    of them away; here the scan runs over the n(n+1)/2 needed pairs only —
    same online-softmax semantics, half the attention FLOPs and score
    traffic (§Perf).  Requires Lq == Lkv and chunk-aligned lengths.
    """
    B, L, H, hd = q.shape
    KVH = k.shape[2]
    group = H // KVH
    scale = hd ** -0.5
    chunk = _largest_divisor(L, chunk)
    n = L // chunk

    qr = constrain(
        q.reshape(B, n, chunk, KVH, group, hd).transpose(1, 0, 2, 3, 4, 5),
        (None, "batch", None, None, None, None),
    )
    kr = constrain(
        k.reshape(B, n, chunk, KVH, hd).transpose(1, 0, 2, 3, 4),
        (None, "batch", None, None, None),
    )
    vr = constrain(
        v.reshape(B, n, chunk, KVH, hd).transpose(1, 0, 2, 3, 4),
        (None, "batch", None, None, None),
    )

    pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    pi = jnp.array([p[0] for p in pairs], jnp.int32)
    pj = jnp.array([p[1] for p in pairs], jnp.int32)
    pfirst = jnp.array([p[1] == 0 for p in pairs])
    rel = jnp.arange(chunk)

    def step(carry, xs):
        m, l, acc, out = carry
        i, j, first = xs
        qb = jax.lax.dynamic_index_in_dim(qr, i, 0, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kr, j, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vr, j, 0, keepdims=False)
        m = jnp.where(first, NEG_INF, m)
        l = jnp.where(first, 0.0, l)
        acc = jnp.where(first, 0.0, acc)

        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qb, kb, preferred_element_type=jnp.float32
        ) * scale
        mask = (i * chunk + rel)[:, None] >= (j * chunk + rel)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        acc = acc * corr[..., None] + pv
        # normalize and write; the final (i, i) pair's write wins.
        o = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(0, 3, 1, 2, 4)
        out = jax.lax.dynamic_update_index_in_dim(
            out, o.astype(out.dtype), i, 0
        )
        return (m_new, l, acc, out), None

    m0 = jnp.full((B, KVH, group, chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, group, chunk), jnp.float32)
    a0 = jnp.zeros((B, KVH, group, chunk, hd), jnp.float32)
    out0 = constrain(
        jnp.zeros((n, B, chunk, KVH, group, hd), q.dtype),
        (None, "batch", None, None, None, None),
    )
    (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, out0), (pi, pj, pfirst))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, L, H, hd)


def decode_attention(q, k_cache, v_cache, kv_len):
    """Single-token attention against a (possibly longer, padded) cache.

    q: (B, 1, H, hd); caches: (B, Lmax, KVH, hd); kv_len: valid prefix length.
    """
    B, _, H, hd = q.shape
    _, Lmax, KVH, _ = k_cache.shape
    group = H // KVH
    qr = q.reshape(B, KVH, group, hd)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qr, k_cache, preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    mask = jnp.arange(Lmax)[None, None, None, :] < kv_len
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ----------------------------------------------------------------- FFNs
def dense_ffn(x, p, cfg: ModelConfig):
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.act == "squared_relu":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif cfg.act == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(cfg.act)
    return h @ p["w_down"]


class MoEStats(NamedTuple):
    aux_loss: jax.Array
    dropped_frac: jax.Array


def moe_ffn(x, p, cfg: ModelConfig):
    """Top-k token-choice MoE with capacity-bounded sort-free dispatch.

    x: (B, L, d).  Experts live on the `model` mesh axis (leading E dim of
    the expert weights); dispatch/return are scatter/gathers that GSPMD
    partitions (baseline; see EXPERIMENTS §Perf for the shard_map a2a
    variant).  Deterministic shapes: per-expert buffers of capacity C.
    """
    B, L, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * L
    cap = max(8, int(cfg.capacity_factor * T * k / E))
    cap = min(cap, T)
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each routed copy inside its expert buffer
    flat_e = idx.reshape(-1)                             # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    tok = jnp.repeat(jnp.arange(T), k)

    buf = jnp.zeros((E, cap, d), x.dtype)
    buf = buf.at[flat_e, jnp.where(keep, pos, cap - 1)].add(
        jnp.where(keep[:, None], xf[tok], 0)
    )
    buf = constrain(buf, ("model", None, None))   # experts live on `model`

    # expert computation (E-sharded einsums)
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
            "ecd,edf->ecf", buf, p["w_up"]
        )
    else:
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", buf, p["w_up"])))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    routed = out_buf[flat_e, jnp.where(keep, pos, cap - 1)]          # (T*k, d)
    routed = constrain(jnp.where(keep[:, None], routed, 0), ("batch", None))
    w = (gate.reshape(-1) * keep).astype(routed.dtype)
    y = jax.ops.segment_sum(routed * w[:, None], tok, num_segments=T)

    if cfg.n_shared_experts:
        sh = jax.nn.silu(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
        y = y + sh @ p["shared_down"]

    # Switch-style load-balance auxiliary loss.
    me = probs.mean(axis=0)
    ce = jnp.bincount(flat_e, length=E).astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(me * ce)
    dropped = 1.0 - keep.mean()
    return y.reshape(B, L, d), MoEStats(aux, dropped)


def moe_ffn_a2a(x, p, cfg: ModelConfig):
    """Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

    §Perf-optimized path: instead of letting GSPMD all-gather the
    (E, cap, d) expert buffers (the gather baseline's failure mode), tokens
    are exchanged directly between expert shards with two all-to-alls —
    wire bytes ~ capacity_factor * T * k * d per direction, the GShard
    dispatch layout (dst rank, local expert, capacity) so no indices travel.

    Falls back to the gather implementation when no mesh context is active
    or E does not divide the model axis.
    """
    axes = current_axes()
    E, k = cfg.n_experts, cfg.top_k
    if axes is None or axes.get("model") is None or cfg.act != "swiglu":
        return moe_ffn(x, p, cfg)
    mesh, model_ax = axes["mesh"], axes["model"]
    dp = axes["batch"]
    M = mesh.shape[model_ax]
    B, L, d = x.shape
    T = B * L
    n_tok_shards = M
    for a in dp:
        n_tok_shards *= mesh.shape[a]
    if E % M != 0 or T % n_tok_shards != 0:
        return moe_ffn(x, p, cfg)
    E_loc = E // M

    xf = x.reshape(T, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    T_loc = T // n_tok_shards
    cap = max(4, int(cfg.capacity_factor * T_loc * k / E))

    tok_spec = P((*dp, model_ax))
    ew_spec = P(model_ax, None, None)

    def local_moe(xf_l, idx_l, gate_l, wg, wu, wd):
        t_l = xf_l.shape[0]
        flat_e = idx_l.reshape(-1)                       # (t_l*k,)
        dst = flat_e // E_loc
        e_loc = flat_e % E_loc
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], axis=1
        )[:, 0]
        keep = pos < cap
        pos_c = jnp.where(keep, pos, cap - 1)
        tok = jnp.repeat(jnp.arange(t_l), k)

        send = jnp.zeros((M, E_loc, cap, d), xf_l.dtype)
        send = send.at[dst, e_loc, pos_c].add(
            jnp.where(keep[:, None], xf_l[tok], 0)
        )
        recv = jax.lax.all_to_all(
            send, model_ax, split_axis=0, concat_axis=0, tiled=False
        )                                                # (M_src, E_loc, cap, d)
        xbuf = recv.transpose(1, 0, 2, 3).reshape(E_loc, M * cap, d)
        if cfg.act == "swiglu":
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xbuf, wg)) * jnp.einsum(
                "ecd,edf->ecf", xbuf, wu
            )
        else:
            h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", xbuf, wu)))
        obuf = jnp.einsum("ecf,efd->ecd", h, wd)
        oback = obuf.reshape(E_loc, M, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(
            oback, model_ax, split_axis=0, concat_axis=0, tiled=False
        )                                                # (M_dst, E_loc, cap, d)
        routed = ret[dst, e_loc, pos_c]
        routed = jnp.where(keep[:, None], routed, 0)
        w = gate_l.reshape(-1) * keep.astype(gate_l.dtype)
        return jax.ops.segment_sum(routed * w[:, None], tok, num_segments=t_l)

    y = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, ew_spec, ew_spec, ew_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(xf, idx, gate.astype(x.dtype), p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        sh = jax.nn.silu(xf @ p["shared_gate"]) * (xf @ p["shared_up"])
        y = y + sh @ p["shared_down"]

    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)
    return y.reshape(B, L, d), MoEStats(aux, jnp.zeros(()))


# ------------------------------------------------------------- Mamba-2 SSD
def ssd_chunked(xh, dt, A, Bm, Cm, *, chunk: int, initial_state=None):
    """Mamba-2 state-space-duality scan (arXiv:2405.21060, simplified SSD).

    xh: (B, L, H, P) inputs per head; dt: (B, L, H) positive step sizes;
    A: (H,) negative decay rates;  Bm/Cm: (B, L, G, S) input/output maps
    (G groups broadcast over heads).  Returns (y, final_state) with
    y: (B, L, H, P), state: (B, H, P, S).

    Within a chunk the quadratic (attention-dual) form is used; across
    chunks a linear state is carried — O(L * chunk) memory and the exact
    same semantics as the sequential scan.
    """
    B, L, H, P = xh.shape
    G, S = Bm.shape[2], Bm.shape[3]
    assert L % chunk == 0
    nc = L // chunk
    rep = H // G

    xc = xh.reshape(B, nc, chunk, H, P)
    dtc = dt.reshape(B, nc, chunk, H)
    Bc = jnp.repeat(Bm.reshape(B, nc, chunk, G, S), rep, axis=3)  # (B,nc,c,H,S)
    Cc = jnp.repeat(Cm.reshape(B, nc, chunk, G, S), rep, axis=3)

    dA = dtc * A[None, None, None, :]                   # (B,nc,c,H) negative
    cum = jnp.cumsum(dA, axis=2)                        # within-chunk cumsum

    def chunk_step(state, ci):
        xb, dtb, Bb, Cb, dAb, cumb = ci
        # --- intra-chunk (quadratic dual): causal kernel L[s,t]
        seg = cumb[:, :, None, :] - cumb[:, None, :, :]   # (B, s, t, H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        # mask BEFORE exp: valid (t<=s) entries are <=0, masked -> -inf -> 0,
        # keeping both the value and its gradient finite.
        kern = jnp.exp(jnp.where(tri[None, :, :, None], seg, -1e30))
        qk = jnp.einsum("bshn,bthn->bsth", Cb, Bb, preferred_element_type=jnp.float32)
        att = qk * kern
        y_intra = jnp.einsum(
            "bsth,bthp,bth->bshp", att, xb.astype(jnp.float32), dtb
        )
        # --- inter-chunk: contribution of carried state
        decay_in = jnp.exp(cumb)                         # (B, c, H)
        y_inter = jnp.einsum(
            "bshn,bhpn,bsh->bshp", Cb, state, decay_in
        )
        # --- state update
        total = cumb[:, -1, :]                           # (B, H)
        decay_out = jnp.exp(total[:, None, :] - cumb)    # (B, c, H)
        state_in = jnp.einsum(
            "bthn,bthp,bth,bth->bhpn", Bb, xb.astype(jnp.float32), dtb, decay_out
        )
        state = state * jnp.exp(total)[:, :, None, None] + state_in
        return state, (y_intra + y_inter).astype(xh.dtype)

    state0 = (
        jnp.zeros((B, H, P, S), jnp.float32)
        if initial_state is None
        else initial_state
    )
    xs = (
        xc.transpose(1, 0, 2, 3, 4),
        dtc.transpose(1, 0, 2, 3),
        Bc.transpose(1, 0, 2, 3, 4),
        Cc.transpose(1, 0, 2, 3, 4),
        dA.transpose(1, 0, 2, 3),
        cum.transpose(1, 0, 2, 3),
    )
    state, ys = jax.lax.scan(chunk_step, state0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, L, H, P)
    return y, state


def mamba_mixer(x, p, cfg: ModelConfig, *, state=None, return_state=False):
    """Mamba-2 block (in_proj -> conv1d -> SSD -> gated out_proj).

    x: (B, L, d_model).  When ``state`` is provided (decode), L may be 1 and
    (conv_state, ssm_state) are updated incrementally.
    """
    B, L, _ = x.shape
    H, P, S, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 1
    d_in = cfg.d_inner
    conv_dim = d_in + 2 * G * S

    zxbcdt = constrain(x @ p["in_proj"], ("batch", None, "model"))
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + conv_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, L, H)

    # causal depthwise conv over the sequence
    w = p["conv_w"]                                      # (K, conv_dim)
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((B, K - 1, conv_dim), xbc.dtype)
        xb_pad = jnp.concatenate([pad, xbc], axis=1)
        new_conv_state = xb_pad[:, -(K - 1):, :] if return_state else None
    else:
        xb_pad = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)
        new_conv_state = xb_pad[:, -(K - 1):, :]
    conv = sum(
        xb_pad[:, i : i + L, :] * w[i][None, None, :] for i in range(K)
    ) + p["conv_b"]
    conv = jax.nn.silu(conv)

    xh = constrain(
        conv[..., :d_in].reshape(B, L, H, P), ("batch", None, "model", None)
    )
    Bm = conv[..., d_in : d_in + G * S].reshape(B, L, G, S)
    Cm = conv[..., d_in + G * S :].reshape(B, L, G, S)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))         # (H,) negative

    init_state = state["ssm"] if state is not None else None
    chunk = _largest_divisor(L, min(cfg.ssm_chunk, L))
    y, fin = ssd_chunked(xh, dt, A, Bm, Cm, chunk=chunk, initial_state=init_state)
    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, L, d_in) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    if return_state or state is not None:
        return out, {"conv": new_conv_state, "ssm": fin}
    return out, None
