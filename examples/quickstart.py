"""Quickstart: GNS vs node-wise sampling on a synthetic power-law graph.

Reproduces the paper's core claim at laptop scale in ~a minute: GNS reaches
the same F1 as NS while moving far fewer feature bytes host->device and
far fewer distinct input nodes per minibatch (paper Tables 3 & 4).

Everything runs through the unified engine API (``repro.gns``): one
declarative ``EngineConfig`` preset, one ``GNSEngine`` per sampler.

Run:  PYTHONPATH=src python examples/quickstart.py [--epochs 3]
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.gns import EngineConfig, GNSEngine
from repro.gns.config import DataConfig
from repro.graph.datasets import get_dataset
from repro.launch.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    # Table-4 regime: sample tree (batch x prod(fanouts)) << |V|, power-law
    # hubs intact — see EXPERIMENTS.md §Repro regime note.
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--max-batches", type=int, default=30)
    args = ap.parse_args()
    use_compile_cache()

    base = EngineConfig.preset(
        "quickstart",
        data=DataConfig(name=args.dataset, scale=args.scale))
    base = dataclasses.replace(
        base, sampling=dataclasses.replace(base.sampling,
                                           batch_size=args.batch_size))
    ds = get_dataset(args.dataset, scale=args.scale)
    print(f"dataset: {ds.name}  |V|={ds.graph.num_nodes:,} "
          f"|E|={ds.graph.num_edges:,} feat={ds.feat_dim}")

    results = {}
    for name in ("ns", "gns"):
        engine = GNSEngine(dataclasses.replace(base, sampler=name),
                           dataset=ds)
        rep = engine.fit(args.epochs, max_batches=args.max_batches,
                         eval_every=args.epochs)
        results[name] = (rep, engine.meter)
        print(f"\n== {name.upper()} ==")
        print(f"  epoch time:        {rep.epoch_times[-1]:.2f}s")
        print(f"  final loss:        {rep.losses[-1]:.4f}")
        print(f"  val micro-F1:      {rep.val_acc[-1]:.4f}")
        print(f"  input nodes/batch: {rep.input_nodes_per_batch:,.0f}"
              f"  (cached: {rep.cached_nodes_per_batch:,.0f})")
        print(f"  bytes streamed:    {engine.meter.bytes_streamed/1e6:,.1f} MB")

    ns_bytes = results["ns"][1].bytes_streamed
    gns_bytes = results["gns"][1].bytes_streamed
    ns_in = results["ns"][0].input_nodes_per_batch
    gns_in = results["gns"][0].input_nodes_per_batch
    print(f"\nGNS vs NS:  input nodes {ns_in/max(gns_in,1):.1f}x fewer, "
          f"streamed bytes {ns_bytes/max(gns_bytes,1):.1f}x fewer "
          f"(paper Table 4: 3-6x fewer input nodes)")


if __name__ == "__main__":
    main()
