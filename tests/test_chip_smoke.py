"""The chip smoke refuses to report success anywhere but on a TPU, and the
entry points place the persistent compile cache where it can be found
again."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _run_smoke(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run_smoke(REPO, env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run_smoke(tmp_path, env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "checkout" in r.stderr


def test_compile_cache_prefers_the_environment(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was   # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(REPO / ".jax_cache")
        assert compile_cache.use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
