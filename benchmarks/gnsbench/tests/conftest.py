"""The benchmark's own tests run on the CPU at a test's size:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/gnsbench/tests
"""
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH.parent), str(ROOT / "src")]

import pytest  # noqa: E402

TINY = json.loads((HERE / "data" / "tiny.json").read_text())


# The serving cell is not admitted to BENCHMARK.json yet (PERF.md §7); its
# entries are kept here so that its driver stays tested.
SERVE = json.loads((HERE / "data" / "serve_cell.json").read_text())


def bench_with_serving() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] += SERVE["workload"]
    bench["end_to_end"] += SERVE["end_to_end"]
    bench["per_layer"] += SERVE["per_layer"]
    return bench


@pytest.fixture
def tiny_cell(tmp_path, monkeypatch):
    """A cell with its configuration swapped for the tiny one (the refresh
    period kept), its graph cached under ``tmp_path``."""
    from gnsbench import graphgen, harness
    monkeypatch.setattr(graphgen, "DATA_DIR", tmp_path)

    def make(name: str):
        cell = harness.load_cell(name, bench=bench_with_serving())
        cfg = dict(TINY, cache_period=cell.config["cache_period"])
        return dataclasses.replace(cell, config=cfg)
    return make


@pytest.fixture
def cpu():
    import jax
    return jax.devices()[:1]
