"""What ``BENCHMARK.json`` names, resolved to files by name.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found from its name alone, so a new cell or
metric is new files plus new entries and no edit:

- ``bench/configs/<config>.json``: a configuration, as it is run;
- ``bench/traffic/<traffic>.json``: a traffic mix (``bench/traffic.py``);
- ``bench/workloads/<cell>.json``: the cell's correctness sample and limits;
- ``bench/metrics/<metric>.py``: a metric's reader, ``read(run) -> float | None``;
- ``bench/references/<reference>.py``: the plain reference that a traffic
  file names under ``reference``, ``answer(cfg, target, metric, units,
  point, q) -> dict`` (``bench/check.py``), loaded once with the cell.
"""
from __future__ import annotations

import importlib.util
import json
from collections.abc import Callable
from pathlib import Path
from typing import NamedTuple

BENCH_DIR = Path(__file__).resolve().parent


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict         # the configuration file
    traffic: dict        # the traffic file
    workload: dict       # the cell file (check sample and limits)
    end_to_end: list     # BENCHMARK.json metric entries that this cell reports
    per_layer: list
    reference: Callable  # the traffic's reference: its ``answer`` function


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if "reference" not in traffic:
        raise KeyError(f"traffic {w['traffic']!r} names no reference")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=read_json(root / configs[w["config"]]["file"]),
        traffic=traffic,
        workload=read_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        reference=reference_answer(traffic["reference"], bench_dir),
    )


def _module(kind: str, name: str, bench_dir: Path):
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded from its file."""
    path = bench_dir / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, bench_dir).read


def reference_answer(name: str, bench_dir: Path = BENCH_DIR):
    """The ``answer`` function of ``bench/references/<name>.py``."""
    return _module("references", name, bench_dir).answer
