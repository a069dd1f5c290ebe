"""Multi-tenant fabric quickstart: a worker fleet over one shared cache.

Fits a small GNS engine, then serves two tenants with very different
contracts through :class:`~repro.serve.ServeFabric`:

* ``mobile`` — latency-sensitive, weight 2.0, small per-tenant queue;
* ``batch``  — throughput traffic, weight 1.0, deep queue, oversubscribed
  on purpose so it sheds (``QueueFull``) at ITS OWN quota.

Each worker runs a weighted-fair stride scheduler feeding the same
size-bucketed micro-batcher `GNSServer` uses, so the fleet inherits the
zero-recompilation serving path while adding tenant isolation, routing,
and failover on top.  Midway through the stream one worker is killed to
show the watchdog reclaiming its in-flight requests onto the survivor.
Prints the per-tenant latency/shed breakdown at the end.

Run:  PYTHONPATH=src python examples/serve_fabric.py [--requests 200]
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig
from repro.gns import (EngineConfig, FabricConfig, GNSEngine, ServeConfig,
                       TenantConfig)
from repro.gns.config import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.serve import QueueFull


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--kill-worker", action="store_true",
                    help="kill worker 0 mid-stream to exercise failover")
    args = ap.parse_args()
    use_compile_cache()

    cfg = EngineConfig(
        sampler="gns",
        data=DataConfig(name="ogbn-products", scale=args.scale),
        sampling=SamplerConfig(batch_size=128, fanouts=(5, 10)),
        cache=CacheConfig(fraction=0.05, strategy="adaptive"),
        serve=ServeConfig(buckets=(16, 64), max_wait_ms=2.0))
    engine = GNSEngine(cfg)
    print(f"fitting on {engine.ds.graph.num_nodes:,} nodes ...")
    engine.fit(1, max_batches=20)

    fab = engine.serve_fabric(FabricConfig(
        workers=args.workers,
        tenants=(
            TenantConfig("mobile", weight=2.0, max_queue=args.requests + 8),
            # oversubscribed on purpose: sheds at its own quota
            TenantConfig("batch", weight=1.0, max_queue=16))))

    rng = np.random.default_rng(0)
    pool = engine.ds.val_idx
    futs, shed = [], 0
    print(f"serving {args.requests} mobile + {args.requests} batch requests "
          f"across {args.workers} workers ...")
    with fab:
        for i in range(args.requests):
            ids = rng.choice(pool, size=int(rng.integers(2, 10)),
                             replace=False)
            futs.append(fab.submit(ids, tenant="mobile"))
            try:
                fab.submit(rng.choice(pool, size=4), tenant="batch")
            except QueueFull:
                shed += 1                     # batch's problem, not mobile's
            if args.kill_worker and i == args.requests // 2:
                fab.workers[0].kill()
                print("killed worker 0 — watchdog re-routes its queue "
                      "and reclaims in-flight requests ...")
        for f in futs:
            r = f.result(timeout=600)
            assert r.status == "ok" and np.isfinite(r.logits).all()

    snap = fab.meter.snapshot()
    t = snap["tenants"]
    print(f"served {snap['served']}/{snap['submitted']} in "
          f"{snap['batches']} micro-batches "
          f"(fill {snap['fill_fraction']:.0%}, shed {shed} batch requests)")
    for name in ("mobile", "batch"):
        ts = t[name]
        print(f"  {name:>6}: served {ts['served']:>4}  "
              f"rejected {ts['rejected']:>4}  "
              f"p50/p99 {ts['total_p50_ms']}/{ts['total_p99_ms']} ms")
    if args.kill_worker:
        rt = snap["routing"]
        print(f"failovers {rt['failovers']}, retries {rt['retries']}, "
              f"healthy workers at exit: {sorted(fab.healthy())}")
    assert t["mobile"]["rejected"] == 0       # isolation: mobile never shed


if __name__ == "__main__":
    main()
