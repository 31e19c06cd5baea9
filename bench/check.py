"""Whether what the timed path returned is correct: a comparison with the
plain reference on a sample of grid points drawn from the seed, once the
window has closed.

The reference is the one the traffic file names under ``reference``:
``bench/references/<name>.py``, loaded with the cell (``bench/spec.py``)
and carried by the traffic, whose ``answer`` gives one grid point laid out
like the program's read-back.  A new scheme or policy is a new reference
module, and nothing here changes.

Each compared number has a limit of its own, in the cell's file
``bench/workloads/<cell>.json`` (``PERF.md`` gives the readings it was set
from).  Which numbers a point gives follows from the answer's layout:

- an answer with per-trial outcomes (``alg_success``, ``ideal_ok``):
  ``outcome_mismatch``, the trials whose per-trial outcome differs from
  the reference's, plus the trials by which each grid field (``afp``,
  ``cafp``, ``lock_err``, ``order_err``) differs, rounded to whole trials,
  as a share of the per-trial outcomes compared.  float32 rounding of a
  grid field is a small part of one trial and counts nothing;
- an answer of single values (such as ``min_tr``): ``<field>_rel_gap``,
  the largest relative difference of each.

A compared number with no limit in the cell's file, or a per-trial answer
with a field that the comparison does not know, is refused: no number a
reference gives goes unchecked.

A sampled point whose answer is missing or malformed reads as infinitely
far off.  ``compare`` also serves the control, which puts the reference at
a lower precision in the program's place (``bench/control.py``).
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference
from bench.traffic import sample_points

_OUTCOMES = ("alg_success", "ideal_ok")
_FIELDS = ("afp", "cafp", "lock_err", "order_err")


def reference_point(traffic, req, flat: int, q=reference.q_f32) -> dict:
    """The reference's answer at one grid point of a request, laid out like
    the program's read-back at that point."""
    return traffic.answer(traffic.cfg, traffic.target, traffic.metric, req.units,
                          req.point(flat), q)


def program_point(answer: dict, shape: tuple, flat: int) -> dict:
    """One grid point of the program's read-back (fields keep trailing axes)."""
    idx = np.unravel_index(flat, shape)
    return {k: np.asarray(v)[idx] for k, v in answer.items()}


def _gaps(got: dict, want: dict) -> dict:
    """Compared numbers of one point, accumulated as (numerator, denominator)
    for shares and as plain maxima for gaps."""
    if "alg_success" not in want:   # single values, each by its relative gap
        gaps = {}
        for name, w in want.items():
            w = float(w)
            try:
                g = float(got[name])
            except (KeyError, TypeError, ValueError):
                g = math.nan
            gap = abs(g - w) / abs(w) if w else abs(g - w)
            gaps[f"{name}_rel_gap"] = gap if math.isfinite(gap) else math.inf
        return gaps
    unknown = set(want) - set(_OUTCOMES) - set(_FIELDS)
    if unknown:
        raise ValueError(f"per-trial answer with fields the comparison does not know: "
                         f"{sorted(unknown)}")
    bad, compared = 0, 0
    n_trials = np.asarray(want["alg_success"]).size
    for name in _OUTCOMES:
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
    unlimited = sorted(set(values) - set(limits))
    if unlimited:
        raise ValueError(f"compared numbers with no limit in the cell file: {unlimited}")
    numbers = {name: {"value": values.get(name, math.inf), "limit": limit}
               for name, limit in limits.items()}
    correct = bool(pairs) and all(
        n["value"] <= n["limit"] for n in numbers.values()
    ) and all(a is not None for a in answers)
    return correct, numbers
