"""Plain reference of an oblivious scheme's evaluation (``metric="eval"``).

Every trial is instantiated, its search tables built, and the scheme's
relation search and single-step matching run on them; each outcome is
classified and scored against the ideal policy, with the shared plain
steps of ``bench/reference.py``.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.reference import (F32, Quant, classify, instantiate, ltc_min_tr, q_f32,
                             relation_search, scaled_residual, search_tables,
                             single_step_matching)

#: Oblivious schemes the reference knows: name -> (VT-RS retry, CAFP policy).
#: Both are scored against ideal LtC, as the program registers them.
SCHEMES = {"rs_ssm": (False, "ltc"), "vtrs_ssm": (True, "ltc")}


class SchemePoint(NamedTuple):
    """One grid point of a scheme evaluation, as the engine reports it."""

    afp: float
    cafp: float
    lock_err: float
    order_err: float
    alg_success: np.ndarray  # (T,) bool
    ideal_ok: np.ndarray     # (T,) bool


def scheme_point(cfg: dict, units: dict, point: dict, scheme: str,
                 q: Quant = q_f32) -> SchemePoint:
    """Instantiate, arbitrate with the scheme, and score it against the
    ideal policy at one grid point (mean TR ``point["tr_mean"]``)."""
    tolerant, policy = SCHEMES[scheme]
    s = np.asarray(cfg["target_order"], np.int64)
    chain = [int(c) for c in np.argsort(s, kind="stable")]
    sys = instantiate(cfg, units, point, q)
    tr = F32(point["tr_mean"])
    res = scaled_residual(sys, q)
    ideal_ok = ltc_min_tr(res, s) <= tr
    n = int(cfg["n_ch"])
    line, n_valid = search_tables(sys, tr, int(cfg["max_fsr_alias"]), 3 * n, q)
    line, n_valid = line.tolist(), n_valid.tolist()
    s_list = s.tolist()
    outcome = []
    for t in range(len(line)):
        tables = [line[t][i][:n_valid[t][i]] for i in range(n)]
        ri = relation_search(tables, chain, tolerant)
        outcome.append(classify(single_step_matching(tables, chain, ri), s_list, policy))
    outcome = np.asarray(outcome)
    success = outcome == "success"
    lock = np.isin(outcome, ("zero_lock", "dup_lock")) & ideal_ok
    order = (outcome == "order_err") & ideal_ok
    return SchemePoint(
        afp=1.0 - float(ideal_ok.mean()),
        cafp=float((~success & ideal_ok).mean()),
        lock_err=float(lock.mean()), order_err=float(order.mean()),
        alg_success=success, ideal_ok=ideal_ok,
    )


def answer(cfg: dict, target: dict, metric: str, units: dict, point: dict,
           q: Quant = q_f32) -> dict:
    """One grid point of a ``{"scheme": name}`` evaluation, laid out like
    the program's read-back (``bench/program.readback``)."""
    if metric != "eval" or target.get("scheme") not in SCHEMES:
        raise ValueError(f"no oblivious reference for {target} with metric {metric!r}; "
                         f"known schemes: {sorted(SCHEMES)} with metric 'eval'")
    return scheme_point(cfg, units, point, target["scheme"], q)._asdict()
