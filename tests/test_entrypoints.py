"""Entry points: ``chip_smoke.py`` never falls back to the CPU, and the
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to the
fixed ``<repo>/.jax_cache``."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.cache import DEFAULT_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _run_smoke(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds ``src`` itself
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def _prints_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is not None:
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_chip_smoke_refuses_cpu():
    out = _run_smoke(ROOT)
    assert out.returncode != 0
    assert not _prints_result(out.stdout)
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run_smoke(tmp_path)
    assert out.returncode != 0
    assert not _prints_result(out.stdout)


def test_compile_cache_placement(monkeypatch, tmp_path):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", prev[keys[0]])
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; no directory is set in code
        assert jax.config.jax_compilation_cache_dir == prev[keys[0]]
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
