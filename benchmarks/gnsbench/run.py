"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/gnsbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  See ``harness.py`` for what a run does.
"""
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

if __name__ == "__main__":
    if not (ROOT / "src" / "repro").is_dir() or not (
            ROOT / "BENCHMARK.json").is_file():
        sys.exit(f"run.py: {ROOT} is not a checkout of the repository "
                 "(no src/repro or BENCHMARK.json)")
    # JAX's persistent compilation cache lives inside the checkout, at a
    # fixed path, whatever the machine sets: only a cell's first run in a
    # checkout compiles, and two checkouts never share programs.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".cache" / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(HERE / ".cache" / "tpu_logs"))
    sys.path[:0] = [str(HERE.parent), str(ROOT / "src")]
    from gnsbench import harness
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
