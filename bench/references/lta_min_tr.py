"""Plain reference of the paper's minimum tuning range under ideal
Lock-to-Any (``{"policy": "lta"}``, ``metric="min_tr"``): the smallest mean
TR at which every trial of the batch has a perfect ring-to-line matching,
solved exactly with the shared steps of ``bench/reference.py``."""
from __future__ import annotations

from bench.reference import Quant, instantiate, lta_max_min_tr, q_f32, scaled_residual


def min_tr_point(cfg: dict, units: dict, point: dict, policy: str,
                 q: Quant = q_f32) -> float:
    """The paper's minimum tuning range at one point: the smallest mean TR
    at which every trial of the batch succeeds under the policy."""
    if policy != "lta":
        raise ValueError(f"the reference knows the minimum TR of LtA only, not {policy!r}")
    return lta_max_min_tr(scaled_residual(instantiate(cfg, units, point, q), q))


def answer(cfg: dict, target: dict, metric: str, units: dict, point: dict,
           q: Quant = q_f32) -> dict:
    """One grid point of the request, laid out like the program's read-back
    (``bench/program.readback``)."""
    if metric != "min_tr" or "policy" not in target:
        raise ValueError(f"no minimum-TR reference for {target} with metric {metric!r}")
    return {"min_tr": min_tr_point(cfg, units, point, target["policy"], q)}
