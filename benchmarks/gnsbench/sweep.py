"""Find a serving cell's knee: the highest offered rate the system sustains.

    python3 benchmarks/gnsbench/sweep.py --workload <serving cell> \
        --rates 20,40,80,160 --seconds 20 --seed 7

One process builds the cell once and offers each rate in turn for
``--seconds`` (the cell's traffic with only ``rate`` changed).  Per rate
it prints one JSON line: requests offered, answered and failed, p50/p99
latency from due time, and the backlog trend (mean latency of the last
quarter of requests minus the first quarter's; a queue that grows all
through the window shows as a trend of the order of the window).  Benchmark
runs never run this; the readings that fixed a cell's rate are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np


def main(argv=None) -> int:
    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here.parent), str(here.parents[1] / "src")]
    from gnsbench import harness, traffic
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    drv = cell.driver
    st = drv.setup(harness.Context(cell=cell, seed=args.seed,
                                   devices=devices))
    deg = np.diff(st.data.indptr)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, rate=rate)
        sched = traffic.make_schedule(tr, deg, args.seconds, args.seed)
        t0 = time.perf_counter()
        subs, rejected, late = drv.offer(st.fab, sched)
        done, failed = drv.collect(subs, sched, time.monotonic() + 60.0)
        wall = time.perf_counter() - t0
        order = sorted(done, key=lambda x: x[0])
        lat = np.array([x[1] for x in order]) * 1e3
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate": rate, "offered": sched.n, "answered": len(done),
            "refused": rejected, "failed": failed, "drain_s": wall,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "trend_ms": float(lat[-q:].mean() - lat[:q].mean()),
            "late_p99_ms": float(np.percentile(late, 99) * 1e3)}),
            flush=True)
    drv.release(st)
    return 0


if __name__ == "__main__":
    sys.exit(main())
