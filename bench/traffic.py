"""The one request generator: turns a traffic file and ``--seed`` into requests.

A traffic file (``bench/traffic/<name>.json``) is data only:

- ``target``: ``{"scheme": name}`` or ``{"policy": name}``, and ``metric``
  (``"eval"`` or ``"min_tr"``): what every request asks the engine for;
- ``axes``: the grid every request carries.  Each axis lists its values
  in a unit of the configuration (``grid_spacing``, ``fsr``, ``nm``), as
  ``{"unit": u, "values": [...]}``, or ``{"linspace": [[a, u], [b, u], n]}``
  with each end in its own unit;
- ``deck`` (optional): axes whose one value is drawn per request, as
  ``{"unit": u, "values": [...]}``.  Every block of ``len(values)``
  requests holds each value once, in an order drawn from the seed, so
  every seed gets the same work in another order;
- ``trials``: ``[n_laser, n_ring]`` unit samples per request, crossed into
  ``n_laser * n_ring`` Monte Carlo trials per grid point.

Each request gets its own unit draw from (seed, request index): the same
seed gives the same requests.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

#: Request index of the warm-up request: never one of the window's.
WARMUP_INDEX = 2**40

_UNITS, _DECK, _SAMPLE = 0, 1, 2   # independent random streams per seed


def rng(seed: int, stream: int, *index: int) -> np.random.Generator:
    """A generator for (seed, stream, index...); any integer seed works."""
    return np.random.default_rng([seed % 2**64, stream, *index])


def draw_units(n_ch: int, n_laser: int, n_ring: int, seed: int, index: int) -> dict:
    """Unit deviates, uniform on [-1, 1), in the layout the engine takes.

    Same distribution and shapes as the program's
    ``repro.core.sampling.draw_unit_samples`` (a copy, drawn with NumPy from
    the benchmark's own seed): one grid offset per laser sample, and a local
    deviate per laser line, ring resonance, ring FSR and ring TR.
    """
    g = rng(seed, _UNITS, index)
    u = lambda shape: g.uniform(-1.0, 1.0, shape).astype(np.float32)
    return {
        "u_go": u((n_laser, 1)),
        "u_llv": u((n_laser, n_ch)),
        "u_rlv": u((n_ring, n_ch)),
        "u_fsr": u((n_ring, n_ch)),
        "u_tr": u((n_ring, n_ch)),
    }


def unit_value(cfg: dict, unit: str) -> float:
    """Size in nm of one ``unit`` of the configuration."""
    sizes = {"nm": 1.0, "grid_spacing": cfg["grid_spacing_nm"], "fsr": cfg["fsr_nm"]}
    try:
        return float(sizes[unit])
    except KeyError:
        raise ValueError(f"unknown axis unit {unit!r}; known: {sorted(sizes)}") from None


def axis_values(cfg: dict, spec: dict) -> np.ndarray:
    if "linspace" in spec:
        (a, ua), (b, ub), n = spec["linspace"]
        return np.linspace(a * unit_value(cfg, ua), b * unit_value(cfg, ub),
                           int(n)).astype(np.float32)
    scale = unit_value(cfg, spec["unit"])
    return (np.asarray(spec["values"], np.float64) * scale).astype(np.float32)


class Request(NamedTuple):
    axes: dict          # axis name -> (len,) float32 coordinates
    units: dict         # unit deviates (see ``draw_units``)
    n_points: int
    n_trials: int       # Monte Carlo trials per grid point

    @property
    def trial_evaluations(self) -> int:
        return self.n_points * self.n_trials

    def point(self, flat: int) -> dict:
        """Axis values at one grid point, in row-major order of ``axes``."""
        shape = [len(v) for v in self.axes.values()]
        idx = np.unravel_index(flat, shape)
        return {k: float(v[i]) for (k, v), i in zip(self.axes.items(), idx)}


class Traffic:
    """Requests of one traffic file under one configuration."""

    def __init__(self, spec: dict, cfg: dict, trials: tuple | None = None):
        self.cfg = cfg
        self.target = dict(spec["target"])
        self.metric = spec.get("metric", "eval")
        self.n_laser, self.n_ring = trials or spec["trials"]
        self.axes = {k: axis_values(cfg, a) for k, a in spec["axes"].items()}
        self.deck = {k: axis_values(cfg, a) for k, a in spec.get("deck", {}).items()}

    def request(self, seed: int, index: int) -> Request:
        axes = {}
        for name, values in self.deck.items():
            block, pos = divmod(index, len(values))
            order = rng(seed, _DECK, block).permutation(len(values))
            axes[name] = values[order[pos]:order[pos] + 1]
        axes.update(self.axes)
        units = draw_units(int(self.cfg["n_ch"]), self.n_laser, self.n_ring, seed, index)
        n_points = int(np.prod([len(v) for v in axes.values()]))
        return Request(axes, units, n_points, self.n_laser * self.n_ring)


def sample_points(seed: int, requests: list, k: int) -> list:
    """Up to ``k`` distinct (request position, grid point) pairs, drawn from
    the seed among the requests a window completed."""
    pairs = [(r, p) for r, req in enumerate(requests) for p in range(req.n_points)]
    if len(pairs) <= k:
        return pairs
    pick = rng(seed, _SAMPLE).choice(len(pairs), size=k, replace=False)
    return [pairs[i] for i in sorted(pick)]
