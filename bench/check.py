"""Whether what the timed path returned is correct: a comparison with the
plain reference (``bench/reference.py``) on a sample of grid points drawn
from the seed, once the window has closed.

Each compared number has a limit of its own, in the cell's file
``bench/workloads/<cell>.json`` (``PERF.md`` gives the readings it was set
from):

- scheme requests (``metric="eval"``): ``outcome_mismatch``, the trials
  whose per-trial outcome (``alg_success``, ``ideal_ok``) differs from the
  reference's, plus the trials by which each grid field (``afp``,
  ``cafp``, ``lock_err``, ``order_err``) differs, rounded to whole trials,
  as a share of the per-trial outcomes compared.  float32 rounding of a
  grid field is a small part of one trial and counts nothing;
- minimum-TR requests: ``min_tr_rel_gap``, the largest relative difference.

A sampled point whose answer is missing or malformed reads as infinitely
far off.  ``compare`` also serves the control, which puts the reference at
a lower precision in the program's place (``bench/control.py``).
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference
from bench.traffic import sample_points

_FIELDS = ("afp", "cafp", "lock_err", "order_err")


def reference_point(traffic, req, flat: int, q=reference.q_f32) -> dict:
    """The reference's answer at one grid point of a request, laid out like
    the program's read-back at that point."""
    point = req.point(flat)
    if "scheme" in traffic.target:
        ans = reference.scheme_point(traffic.cfg, req.units, point,
                                     traffic.target["scheme"], q)
        return ans._asdict()
    if traffic.metric == "min_tr":
        return {"min_tr": reference.min_tr_point(
            traffic.cfg, req.units, point, traffic.target["policy"], q)}
    raise ValueError(f"no reference for {traffic.target} with metric {traffic.metric!r}")


def program_point(answer: dict, shape: tuple, flat: int) -> dict:
    """One grid point of the program's read-back (fields keep trailing axes)."""
    idx = np.unravel_index(flat, shape)
    return {k: np.asarray(v)[idx] for k, v in answer.items()}


def _gaps(got: dict, want: dict) -> dict:
    """Compared numbers of one point, accumulated as (numerator, denominator)
    for shares and as plain maxima for gaps."""
    if "min_tr" in want:
        w = float(want["min_tr"])
        try:
            g = float(got["min_tr"])
        except (KeyError, TypeError, ValueError):
            g = math.nan
        gap = abs(g - w) / abs(w) if w else abs(g - w)
        return {"min_tr_rel_gap": gap if math.isfinite(gap) else math.inf}
    bad, compared = 0, 0
    n_trials = np.asarray(want["alg_success"]).size
    for name in ("alg_success", "ideal_ok"):
        w = np.asarray(want[name])
        g = np.asarray(got.get(name, ()))
        bad += int(np.sum(g != w)) if g.shape == w.shape else w.size
        compared += w.size
    for name in _FIELDS:
        try:
            trials = round(abs(float(got[name]) - float(want[name])) * n_trials)
        except (KeyError, TypeError, ValueError, OverflowError):
            trials = n_trials
        bad += min(int(trials), n_trials)
    return {"outcome_mismatch": [bad, compared]}


def compare(pairs) -> dict:
    """Compared numbers over (got, want) point pairs: name -> value."""
    acc: dict = {}
    for got, want in pairs:
        for name, v in _gaps(got, want).items():
            if isinstance(v, list):
                n, d = acc.get(name, (0, 0))
                acc[name] = (n + v[0], d + v[1])
            else:
                acc[name] = max(acc.get(name, 0.0), v)
    return {k: (v[0] / v[1] if v[1] else math.inf) if isinstance(v, tuple) else v
            for k, v in acc.items()}


def check(traffic, seed: int, requests: list, answers: list, limits: dict,
          sample: int) -> tuple[bool, dict]:
    """Compare a sample of the window's answers with the reference.

    ``requests``/``answers`` are the completed requests and their read-backs
    (None where a request failed).  Returns ``(correct, numbers)`` with
    ``numbers`` mapping each compared name to ``{"value", "limit"}``.
    """
    pairs = []
    for r, flat in sample_points(seed, requests, sample):
        req = requests[r]
        shape = tuple(len(v) for v in req.axes.values())
        want = reference_point(traffic, req, flat)
        got = program_point(answers[r], shape, flat) if answers[r] is not None else {}
        pairs.append((got, want))
    values = compare(pairs)
    numbers = {name: {"value": values.get(name, math.inf), "limit": limit}
               for name, limit in limits.items()}
    correct = bool(pairs) and all(
        n["value"] <= n["limit"] for n in numbers.values()
    ) and all(a is not None for a in answers)
    return correct, numbers
