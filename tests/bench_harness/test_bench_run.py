"""The benchmark's command: it refuses to run without a TPU and without the
program, and on the CPU (its look for a chip skipped) a whole run prints
the metrics its cell reports, with the compared numbers last."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import run, spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
#: Metrics read from the device's trace: on the CPU there is no device plane.
DEVICE_TRACE = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                if m["source"] == "device_trace"}


def _bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "wdm16-lta-mintr",
         "--seed", "1", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_non_zero_without_a_tpu():
    out = _bench(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_find_chips_refuses_the_cpu():
    with pytest.raises(run.NoChip):
        run.find_chips(1)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_run_on_cpu_reports_the_cells_metrics(name, trace):
    cell = spec.load_cell(ROOT, name)
    lines = []
    result = run.run_cell(cell, 2**33 + 1, 0.3, trace, jax.devices(), trials=(4, 4),
                          log=lines.append)
    assert result["correct"], result["checks"]
    assert list(result) == (["correct", "attempted", "failed", "metrics", "device"]
                            + (["breakdown"] if trace else []) + ["checks"])
    assert result["attempted"] >= 1 and result["failed"] == 0
    info = json.loads(lines[-1])["run"]
    assert info["compiles_in_window"] == 0 and info["window_s"] > 0
    wanted = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    got = set(result["metrics"])
    assert got == wanted - DEVICE_TRACE
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert result["device"]["platform"] == "cpu"
    json.dumps(result, allow_nan=False)
