"""The benchmark refuses to run where it cannot measure the chip."""
import json
import shutil
import subprocess
import sys

from gnsbench import harness

ROOT = harness.ROOT
RUN = ["benchmarks/gnsbench/run.py", "--workload", "products_train",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def run_in(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *RUN], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_off_a_tpu():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_unknown_workload():
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN[0], "--workload", "nope",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for path in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns(".data", ".cache",
                                                      "__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
