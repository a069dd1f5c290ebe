"""Benchmark harness entry point — one module per paper table/figure.

  Table 3  bench_throughput        F1 + time/epoch, 4 samplers
  Table 4  bench_input_nodes       #input nodes per batch NS vs GNS
  Table 5  bench_isolated          LADIES isolated-node pathology
  Table 6  bench_cache_sensitivity GNS cache size x refresh period
  Fig 1/2  bench_breakdown         runtime breakdown + byte ledger
  Fig 3    bench_convergence       F1 vs epoch, 4 samplers
  §Roofline bench_roofline         aggregates dry-run JSONs (no compute)
  Serving  bench_serve             micro-batched GNSServer vs infer() loop
  Fabric   bench_fabric            multi-tenant fairness/isolation/routing
  Stream   bench_stream            serve-while-mutating temporal replay
  RPC      bench_rpc               tcp transport overhead vs inproc fabric

``python -m benchmarks.run`` runs all at CI scale (--full for paper scale);
each prints CSV and persists JSON under benchmarks/results/.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets/epochs (hours on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. throughput,roofline)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (bench_breakdown, bench_cache_sensitivity,
                            bench_convergence, bench_fabric,
                            bench_input_nodes, bench_isolated,
                            bench_roofline, bench_rpc, bench_serve,
                            bench_stream, bench_throughput)
    all_benches = {
        "throughput": bench_throughput.run,
        "input_nodes": bench_input_nodes.run,
        "isolated": bench_isolated.run,
        "cache_sensitivity": bench_cache_sensitivity.run,
        "breakdown": bench_breakdown.run,
        "convergence": bench_convergence.run,
        "roofline": bench_roofline.run,
        "serve": bench_serve.run,
        "fabric": bench_fabric.run,
        "stream": bench_stream.run,
        "rpc": bench_rpc.run,
    }
    names = (args.only.split(",") if args.only else list(all_benches))
    for name in names:
        t0 = time.perf_counter()
        print(f"\n{'=' * 60}\n== bench: {name}\n{'=' * 60}")
        all_benches[name](fast=not args.full)
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
