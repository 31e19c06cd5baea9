"""What ``BENCHMARK.json`` names, resolved to files by name.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found from its name alone, so a new cell or
metric is new files plus new entries and no edit:

- ``bench/configs/<config>.json``: a configuration, as it is run;
- ``bench/traffic/<traffic>.json``: a traffic mix (``bench/traffic.py``);
- ``bench/workloads/<cell>.json``: the cell's correctness sample and limits;
- ``bench/metrics/<metric>.py``: a metric's reader, ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
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
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=read_json(root / configs[w["config"]]["file"]),
        traffic=read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        workload=read_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if mod_spec is None or mod_spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
