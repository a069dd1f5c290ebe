"""End-to-end driver: 3-layer GraphSAGE + GNS on an ogbn-products-like graph.

The paper's training setup (§4.1) end to end, through the unified engine API
(``repro.gns``): degree-based cache sampling (1% of |V|), cache-prioritized
neighbor sampling with eq. (10)-(12) importance correction, prefetched host
pipeline, AdamW(3e-3), periodic checkpointing with restart, and the Fig. 1/2
runtime breakdown printed at the end.  A few hundred steps by default.

``--mesh DxM`` builds a (data=D, model=M) host mesh (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to mock N devices):
the cache table row-shards over 'model', the engine collates one minibatch
per DP group per step, and the fused input layer rides the device-resident
home-shard vector — the DP > 1 fast-path regime in one compiled step.

Run:  PYTHONPATH=src python examples/train_gns_graphsage.py \
          [--sampler gns|ns|ladies|lazygcn] [--steps 300] [--scale 1.0] \
          [--mesh 2x2] [--infer 64]
"""
from __future__ import annotations

import argparse
import json

from repro.checkpoint import CheckpointManager
from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig
from repro.gns import EngineConfig, GNSEngine
from repro.gns.config import DataConfig, MeshConfig, ModelConfig
from repro.launch.compile_cache import use_compile_cache


def main():
    from repro.featurestore import POLICIES

    ap = argparse.ArgumentParser()
    ap.add_argument("--sampler", default="gns",
                    choices=["gns", "ns", "ladies", "lazygcn"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--batch-size", type=int, default=1000)
    ap.add_argument("--cache-frac", type=float, default=0.01)
    ap.add_argument("--cache-policy", default="auto",
                    choices=["auto", *sorted(POLICIES)],
                    help="cache-admission policy (featurestore registry)")
    ap.add_argument("--async-refresh", action="store_true",
                    help="double-buffered background cache refresh")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="host mesh, e.g. 2x2 = (data=2, model=2): sharded "
                         "cache + fused input + DP>1 home-shard fast path")
    ap.add_argument("--infer", type=int, default=0, metavar="N",
                    help="after training, run mini-batch inference on N "
                         "validation nodes through the live cache")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--prefetch", action="store_true", default=True)
    args = ap.parse_args()
    use_compile_cache()

    mesh_cfg, model_cfg = None, ModelConfig()
    if args.mesh:
        import jax

        d, m = (int(x) for x in args.mesh.lower().split("x"))
        mesh_cfg = MeshConfig(data=d, model=m)
        # a sharded cache table wants the fused input path (the "where"
        # path cannot exploit the row-sharded layout).  Off-TPU the Pallas
        # kernel runs in interpret mode — Python-per-lane, minutes per step
        # at these fanouts — so use the jnp reference backend inside the
        # same shard_map body (identical sharding/fast-path logic; the
        # dry-run lowers the same way).
        kernel = "pallas" if jax.default_backend() == "tpu" else "reference"
        model_cfg = ModelConfig(input_impl="fused", input_kernel=kernel)

    cfg = EngineConfig(
        sampler=args.sampler,
        data=DataConfig(name=args.dataset, scale=args.scale),
        sampling=SamplerConfig(batch_size=args.batch_size,
                               fanouts=(5, 10, 15)),
        cache=CacheConfig(fraction=args.cache_frac, period=1,
                          strategy=args.cache_policy,
                          async_refresh=args.async_refresh),
        model=model_cfg, mesh=mesh_cfg, prefetch=args.prefetch)
    engine = GNSEngine(cfg)
    ds = engine.ds
    print(f"{ds.name}: |V|={ds.graph.num_nodes:,} |E|={ds.graph.num_edges:,} "
          f"train={len(ds.train_idx):,} feat={ds.feat_dim}"
          + (f"  mesh={args.mesh} dp_groups={engine.num_groups}"
             if args.mesh else ""))

    # one optimizer step consumes num_groups minibatches at DP > 1
    steps_per_epoch = max(
        len(ds.train_idx) // (args.batch_size * max(engine.num_groups, 1)), 1)
    epochs = max(args.steps // steps_per_epoch, 1)
    mgr = CheckpointManager(args.ckpt_dir, every=1) if args.ckpt_dir else None

    rep = engine.fit(epochs, eval_every=1)
    if mgr:
        mgr.maybe_save(epochs, (engine.params, engine.opt_state))

    print(f"\n== {args.sampler.upper()} on {ds.name} "
          f"({epochs} epochs x {steps_per_epoch} steps) ==")
    print(f"loss: {rep.losses[0]:.4f} -> {rep.losses[-1]:.4f}")
    print(f"val micro-F1: {[round(a, 4) for a in rep.val_acc]}")
    print(f"epoch times (s): {[round(t, 2) for t in rep.epoch_times]}")
    print(f"input nodes/batch: {rep.input_nodes_per_batch:,.0f} "
          f"(cached {rep.cached_nodes_per_batch:,.0f}, "
          f"isolated {rep.isolated_per_batch:.1f})")
    print("runtime breakdown (paper Fig. 2):")
    print(json.dumps(engine.meter.breakdown(), indent=2))
    if engine.store is not None:
        dev = engine.meter.tier("device")
        print(f"feature store: policy={engine.store.policy.name} "
              f"generations={engine.store.refreshes} "
              f"swaps={engine.store.swaps} "
              f"device hit-rate={dev.hit_rate:.3f}")
    if args.infer:
        ids = ds.val_idx[:args.infer]
        logits = engine.infer(ids)
        preds = logits.argmax(axis=-1)
        acc = float((preds == ds.labels[ids]).mean())
        print(f"infer: {len(ids)} nodes through the live cache generation, "
              f"top-1 agreement with labels {acc:.3f}")


if __name__ == "__main__":
    main()
