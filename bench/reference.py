"""Shared plain steps of the benchmark's references, independent of the engine.

Each traffic file names its reference, ``bench/references/<name>.py``
(found by ``bench/spec.py``), whose ``answer`` computes one grid point of
what the traffic's target and metric ask for from the steps here:
``instantiate``, ``scaled_residual``, the ideal policies (``ltc_min_tr``,
``lta_bottleneck``, ``lta_max_min_tr``), ``search_tables``,
``relation_search``, ``single_step_matching`` and ``classify``.

Nothing here imports the program under test.  The per-trial arbitration
logic (search tables, relation search, single-step matching, outcome
classification, bottleneck matching) is copied from the program's
pure-Python oracle ``src/repro/core/reference.py``; ``instantiate``
restates the wavelength model of the paper's Eq. 3-4 (the program's
``src/repro/core/sampling.py``).  The slow scalar parts stay scalar;
only the per-trial inputs (wavelengths, residuals, table candidates) are
computed array-wise with NumPy, in float32 as the configuration states.

Every arithmetic result passes through ``q``: float32 (``q_f32``, the
identity) for the reference, bfloat16 rounding for the lower-precision
control (``q_bf16``), so the control is this same code one precision down.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import numpy as np

F32 = np.float32
Quant = Callable[[np.ndarray], np.ndarray]


def q_f32(x):
    return np.asarray(x, F32)


def q_bf16(x):
    """Round to bfloat16 and back: the control's arithmetic."""
    import ml_dtypes

    return np.asarray(x, F32).astype(ml_dtypes.bfloat16).astype(F32)


class Systems(NamedTuple):
    """T = n_laser * n_ring sampled transceivers (trial t = laser * n_ring + ring)."""

    laser: np.ndarray    # (T, N) laser lines [nm, relative to the grid centre]
    ring: np.ndarray     # (T, N) ring resonances, by physical ring index
    fsr: np.ndarray      # (T, N) per-ring free spectral range
    tr_unit: np.ndarray  # (T, N) per-ring tuning-range multiplier (1 + dTR)


# ------------------------------------------------------------ instantiate
def instantiate(cfg: dict, units: dict, point: dict, q: Quant = q_f32) -> Systems:
    """Scale unit deviates by the variation half-ranges (paper Eq. 3-4).

    ``cfg`` is a configuration file's dict, ``units`` the traffic's unit
    draw, ``point`` the request's axis values at one grid point (e.g.
    ``sigma_rlv``); axes a point does not set take the configuration's value.
    """
    n = int(cfg["n_ch"])
    gs = F32(cfg["grid_spacing_nm"])
    var = dict(cfg["variations"])
    var.update({k: v for k, v in point.items() if k != "tr_mean"})
    idx = np.arange(n, dtype=F32)
    r = np.asarray(cfg["ring_order"], F32)
    laser_grid = q((idx - (n - 1) / 2.0) * gs)
    ring_grid = q(-F32(cfg["ring_bias_nm"]) + q((r - (n - 1) / 2.0) * gs))
    s_go = F32(var["sigma_go"])
    s_llv = F32(var["sigma_llv_frac"] * cfg["grid_spacing_nm"])
    s_rlv = F32(var["sigma_rlv"])
    s_fsr = F32(var["sigma_fsr_frac"])
    s_tr = F32(var["sigma_tr_frac"])
    fsr0 = F32(cfg["fsr_nm"])
    laser = q(q(laser_grid[None, :] + q(s_go * units["u_go"])) + q(s_llv * units["u_llv"]))
    ring = q(ring_grid[None, :] + q(s_rlv * units["u_rlv"]))
    fsr = q(fsr0 * q(1.0 + q(s_fsr * units["u_fsr"])))
    tr_unit = q(1.0 + q(s_tr * units["u_tr"]))
    n_l, n_r = laser.shape[0], ring.shape[0]

    def cross(a, lasers: bool):
        b = a[:, None, :] if lasers else a[None, :, :]
        return np.broadcast_to(b, (n_l, n_r, n)).reshape(n_l * n_r, n)

    return Systems(cross(laser, True), cross(ring, False), cross(fsr, False),
                   cross(tr_unit, False))


def scaled_residual(sys: Systems, q: Quant = q_f32) -> np.ndarray:
    """(T, N_ring, N_line): red-shift from ring i to line k over the ring's
    TR multiplier (Eq. 5); success at mean TR t iff this is <= t."""
    d = q(sys.laser[:, None, :] - sys.ring[:, :, None])
    return q(q(np.mod(d, sys.fsr[:, :, None])) / sys.tr_unit[:, :, None])


# ------------------------------------------------------------ ideal policies
def ltc_min_tr(res: np.ndarray, s: np.ndarray) -> np.ndarray:
    """(T,) smallest mean TR at which some cyclic shift of s is reachable."""
    n = res.shape[-1]
    rows = np.arange(n)
    per_shift = [res[:, rows, (s + c) % n].max(axis=-1) for c in range(n)]
    return np.min(per_shift, axis=0)


def _perfect_matching(res_t: list, thr: float) -> bool:
    """Kuhn's augmenting paths on the graph {(i, k): res[i][k] <= thr}."""
    n = len(res_t)
    adj = [[k for k in range(n) if row[k] <= thr] for row in res_t]
    match_ring = [-1] * n  # line -> ring

    def augment(i: int, seen: list) -> bool:
        for k in adj[i]:
            if not seen[k]:
                seen[k] = True
                if match_ring[k] < 0 or augment(match_ring[k], seen):
                    match_ring[k] = i
                    return True
        return False

    return all(augment(i, [False] * n) for i in range(n))


def lta_bottleneck(res_t: np.ndarray) -> float:
    """Smallest threshold with a perfect ring-to-line matching (one trial)."""
    values = np.unique(res_t)
    rows = res_t.tolist()
    lo, hi = 0, len(values) - 1   # the largest entry always admits a matching
    while lo < hi:
        mid = (lo + hi) // 2
        if _perfect_matching(rows, float(values[mid])):
            hi = mid
        else:
            lo = mid + 1
    return float(values[lo])


def lta_max_min_tr(res: np.ndarray) -> float:
    """max over trials of the per-trial LtA bottleneck value, exactly.

    Each trial's bottleneck lies between a lower bound (every ring and every
    line needs one reachable partner) and its LtC value (a cyclic shift is a
    perfect matching).  Trials are visited by falling upper bound; one is
    solved exactly only if no matching exists at the running maximum.
    """
    lower = np.maximum(res.min(axis=2).max(axis=1), res.min(axis=1).max(axis=1))
    upper = ltc_min_tr(res, np.arange(res.shape[-1]))
    best = float(lower.max())
    for t in np.argsort(-upper, kind="stable"):
        if upper[t] <= best:
            break
        if not _perfect_matching(res[t].tolist(), best):
            best = lta_bottleneck(res[t])
    return best


# ------------------------------------------------------------ search tables
def search_tables(sys: Systems, tr_mean, max_alias: int, max_entries: int,
                  q: Quant = q_f32):
    """Per-ring ascending (delta, line) peak lists, as (wl, n_valid) arrays.

    A ring tuned red by delta in [0, TR_i] meets line k at every
    delta = (laser_k - ring_i) - j * FSR_i, |j| <= max_alias.  Peaks are
    ordered by (delta, line); at most ``max_entries`` are kept.  Only the
    few aliases near the window are enumerated: delta falls as j grows, so
    the valid j form one run next to floor((laser - ring) / FSR).
    """
    tr = q(F32(tr_mean) * sys.tr_unit)                       # (T, N)
    diff = q(sys.laser[:, None, :] - sys.ring[:, :, None])   # (T, ring, line)
    fsr = sys.fsr[:, :, None]
    span = int(math.ceil(float(np.max(tr / sys.fsr)))) + 2
    j_top = np.floor(diff.astype(np.float64) / fsr) + 1
    j = j_top[..., None] - np.arange(span + 1)               # (T, ring, line, S)
    j = np.clip(j, -max_alias, max_alias).astype(F32)
    d = q(diff[..., None] - q(j * fsr[..., None]))
    ok = (d >= 0) & (d <= tr[:, :, None, None])
    # A clipped j repeats a candidate: keep only the first of equal j.
    ok[..., 1:] &= j[..., 1:] != j[..., :-1]
    t_, n_, _, s_ = d.shape
    key = np.where(ok, d, np.inf).reshape(t_, n_, -1)        # line-major order
    order = np.argsort(key, axis=-1, kind="stable")[..., :max_entries]
    line = (order // s_).astype(np.int32)
    n_valid = np.minimum(ok.reshape(t_, n_, -1).sum(-1), max_entries)
    return line, n_valid


# ------------------------------------------------------------ relation search
def _unit_search(tables: list, agg: int, vic: int, entry: int):
    """Aggressor locks its table entry; the victim's first masked entry minus
    that entry is the relation index (None if either is missing)."""
    st_a = tables[agg]
    if not 0 <= entry < len(st_a):
        return None
    try:
        return tables[vic].index(st_a[entry]) - entry
    except ValueError:
        return None


def _pair_search(tables: list, agg: int, vic: int, n: int, tolerant: bool):
    last = _unit_search(tables, agg, vic, len(tables[agg]) - 1)
    first = _unit_search(tables, agg, vic, 0)
    if last is not None and first is not None:
        ri = last if (last - first) % n == 0 else None
    else:
        ri = last if last is not None else first
    if ri is None and tolerant and len(tables[agg]) >= 2:
        ri = _unit_search(tables, agg, vic, 1)
    return ri


def relation_search(tables: list, chain: list, tolerant: bool) -> list:
    """Chain-oriented relation index per link pos -> pos + 1 (None = phi)."""
    n = len(chain)
    out = []
    for pos in range(n):
        a, b = chain[pos], chain[(pos + 1) % n]
        agg, vic = min(a, b), max(a, b)
        ri = _pair_search(tables, agg, vic, n, tolerant)
        if ri is not None and agg != a:
            ri = -ri
        out.append(ri)
    return out


# ------------------------------------------------------------ single-step matching
def single_step_matching(tables: list, chain: list, ri: list) -> list:
    """Per physical ring: the locked line, or None (paper Fig. 13)."""
    n = len(chain)
    cuts = [pos for pos in range(n) if ri[pos] is None]
    if not cuts:
        segments, anchored = [list(range(n))], [False]
    else:
        segments, anchored = [], []
        for ci, cpos in enumerate(cuts):
            end = cuts[(ci + 1) % len(cuts)]
            seg, p = [], (cpos + 1) % n
            while True:
                seg.append(p)
                if p == end:
                    break
                p = (p + 1) % n
            segments.append(seg)
            anchored.append(True)
    entry = [None] * n
    for seg, has_tail in zip(segments, anchored):
        acc, diag = 0, {}
        for u, pos in enumerate(seg):
            if u:
                acc += ri[seg[u - 1]]
            diag[pos] = u + acc if u else 0
        if not has_tail:
            for rho0 in range(n):
                cand = {pos: (e + rho0) % n for pos, e in diag.items()}
                if all(cand[pos] < len(tables[chain[pos]]) for pos in seg):
                    diag = cand
                    break
            else:
                diag = {pos: e % n for pos, e in diag.items()}
        else:
            diag = {pos: e % n for pos, e in diag.items()}
        for pos, e in diag.items():
            if has_tail and pos == seg[-1]:
                e = len(tables[chain[pos]]) - 1
            entry[pos] = e
    locks = [None] * n
    for pos in range(n):
        ring_i, e = chain[pos], entry[pos]
        if e is not None and 0 <= e < len(tables[ring_i]):
            locks[ring_i] = tables[ring_i][e]
    return locks


def classify(locks: list, s: list, policy: str) -> str:
    n = len(s)
    if any(line is None for line in locks):
        return "zero_lock"
    if len(set(locks)) != n:
        return "dup_lock"
    if policy == "ltc":
        ok = len({(locks[i] - s[i]) % n for i in range(n)}) == 1
    else:
        ok = True
    return "success" if ok else "order_err"

