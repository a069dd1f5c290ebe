#!/usr/bin/env python3
"""Smoke of the GNS main path on a TPU, through the engine's own entry points.

GraphSAGE at the paper's widths (the ``paper_train`` preset: GNS sampler,
batch 1000, fanouts (5, 10, 15), hidden 256, an ogbn-products-shaped graph
of 30,500 nodes with 100 features generated from a seed, degree cache at 1%
of the nodes), all in this one process:

1. train  — ``GNSEngine(...).fit()`` for a few steps, then ``evaluate()``.
   Losses must be finite and the last below the first (so in phase 2).
2. device — the same preset with ``SamplerConfig(backend="device")``: the
   layer-0 draw and the Pallas slot gather run inside the compiled step.
   The lowered step must contain the kernel (``tpu_custom_call``), and on
   one batch the kernel must agree with the jnp reference.
3. serve  — ``engine.serve_fabric()`` in process answers a few dozen
   requests of 2 to 10 validation ids; every one must come back ``ok`` with
   finite logits.

``--chips 4`` runs only the sharded path instead: ``paper_train`` on a
(data=2, model=2) mesh — cache rows sharded over ``model``, fused input
through the Pallas cache-lookup kernel, one minibatch per DP group and the
home-shard vector — and, step by step, the same batches on one device
through the unsharded fused path.  The losses must match.

Per-step losses, step wall times (ending in ``block_until_ready``) and
compile seconds are printed first; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure exits non-zero, and the script stops at once when JAX finds no
TPU.

Run from the repository root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EPOCHS = 2              # training epochs per phase: 3 steps each at
                        # paper_train's 3,050 training nodes
MESH_EPOCHS = 5         # on the mesh a step takes one batch per DP group,
                        # so an epoch holds one step
REQUESTS = 36           # serving requests
KERNEL_MARK = "tpu_custom_call"
PARITY_TOL = 1e-5       # |pallas - reference| <= tol * max(1, max|reference|)
LOSS_TOL = 1e-3         # |sharded - single| <= tol * max(1, |single|)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache reads
    included) and the number of persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def timed_steps(engine) -> list:
    """Record ``(loss, wall seconds)`` for every optimizer step the engine
    runs; each wall time ends once the updated parameters are ready."""
    import jax

    steps = []
    run = engine.run_batch

    def run_batch(mb, home_shards=None):
        t0 = time.perf_counter()
        loss, acc = run(mb, home_shards)
        jax.block_until_ready(engine.params)
        steps.append((loss, time.perf_counter() - t0))
        return loss, acc

    engine.run_batch = run_batch
    return steps


def report_steps(phase: str, steps: list, expected: int,
                 compile_s: float) -> list:
    for i, (loss, wall) in enumerate(steps):
        log(f"[{phase}] step {i} loss {loss!r} wall {wall:.4f}s")
    log(f"[{phase}] compile {compile_s:.2f}s")
    losses = [loss for loss, _ in steps]
    check(len(losses) == expected, f"{phase}: {len(losses)} steps ran, "
                                   f"expected {expected}")
    check(all(math.isfinite(x) for x in losses),
          f"{phase}: non-finite loss in {losses}")
    return losses


def check_falls(phase: str, losses: list) -> None:
    check(losses[-1] < losses[0],
          f"{phase}: loss did not fall ({losses[0]!r} -> {losses[-1]!r})")


def build(phase: str, cfg):
    from repro.gns import GNSEngine

    t0 = time.perf_counter()
    engine = GNSEngine(cfg)
    ds = engine.ds
    log(f"[{phase}] engine built in {time.perf_counter() - t0:.2f}s: "
        f"|V|={ds.graph.num_nodes} |E|={ds.graph.num_edges} "
        f"feat={ds.feat_dim} batch={engine.scfg.batch_size} "
        f"fanouts={tuple(engine.scfg.fanouts)} "
        f"hidden={engine.mcfg.hidden_dim}")
    return engine


def steps_per_epoch(engine) -> int:
    return (len(engine.ds.train_idx) // engine.scfg.batch_size
            // max(engine.num_groups, 1))


def phase_train(cfg, clock: CompileClock):
    engine = build("train", cfg)
    steps = timed_steps(engine)
    c0 = clock.seconds
    engine.fit(epochs=EPOCHS)
    check_falls("train", report_steps(
        "train", steps, EPOCHS * steps_per_epoch(engine), clock.seconds - c0))
    c0 = clock.seconds
    acc = engine.evaluate()
    check(0.0 <= acc <= 1.0, f"train: evaluate() gave {acc!r}")
    log(f"[train] evaluate: val micro-F1 {acc:.4f} "
        f"(compile {clock.seconds - c0:.2f}s)")
    return engine


def phase_device(cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.sampling.kernels import gns_sample_agg

    cfg = dataclasses.replace(
        cfg, sampling=dataclasses.replace(cfg.sampling, backend="device"))
    engine = build("device", cfg)
    check(engine.mcfg.sample_kernel == "pallas",
          f"device: sample kernel resolved to "
          f"{engine.mcfg.sample_kernel!r}, not 'pallas'")
    steps = timed_steps(engine)
    c0 = clock.seconds
    engine.fit(epochs=EPOCHS)
    check_falls("device", report_steps(
        "device", steps, EPOCHS * steps_per_epoch(engine), clock.seconds - c0))

    # one batch: the compiled step holds the kernel, and the kernel agrees
    # with the jnp reference on the chip
    mb = engine.sampler.sample(engine.ds.train_idx[:engine.scfg.batch_size],
                               np.random.default_rng(7))
    dev = jax.device_put(mb.device)
    table, adj = engine._cache_table(mb), engine._device_adj(mb)
    lowered = engine._train_step.lower(
        engine.params, engine.opt_state, dev, table,
        jnp.full((1,), -1, jnp.int32), adj)
    check(KERNEL_MARK in lowered.as_text(),
          f"device: {KERNEL_MARK} not in the lowered train step")
    log(f"[device] lowered train step contains {KERNEL_MARK}")
    out = {impl: np.asarray(gns_sample_agg(
        adj, table, dev.input_cache_slots, dev.input_fb_rows, dev.input_fb_w,
        dev.sample_key, impl=impl)) for impl in ("pallas", "reference")}
    gap = float(np.max(np.abs(out["pallas"] - out["reference"])))
    scale = float(np.max(np.abs(out["reference"])))
    log(f"[device] gns_sample_agg pallas vs reference on "
        f"{out['pallas'].shape}: max|diff| {gap!r} (max|ref| {scale!r}, "
        f"tolerance {PARITY_TOL} x max(1, max|ref|))")
    check(np.isfinite(out["pallas"]).all(), "device: non-finite aggregate")
    check(gap <= PARITY_TOL * max(1.0, scale),
          f"device: kernel differs from the reference by {gap!r}")


def phase_serve(engine, clock: CompileClock) -> None:
    rng = np.random.default_rng(0)
    pool = engine.ds.val_idx
    t0, c0 = time.perf_counter(), clock.seconds
    with engine.serve_fabric() as fab:
        futs = [fab.submit(rng.choice(pool, size=int(rng.integers(2, 11)),
                                      replace=False))
                for _ in range(REQUESTS)]
        results = [f.result(timeout=600) for f in futs]
    bad = [r.status for r in results
           if r.status != "ok" or not np.isfinite(r.logits).all()]
    log(f"[serve] {len(results) - len(bad)}/{REQUESTS} requests ok in "
        f"{time.perf_counter() - t0:.2f}s (compile {clock.seconds - c0:.2f}s,"
        f" summed over worker threads)")
    check(not bad, f"serve: failed requests {bad}")


def phase_mesh(cfg, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import EpochLoader
    from repro.gns.config import MeshConfig
    from repro.gns.engine import collate_groups, make_train_step

    cfg = dataclasses.replace(
        cfg, mesh=MeshConfig(data=2, model=2),
        model=dataclasses.replace(cfg.model, input_impl="fused",
                                  input_kernel="pallas"))
    engine = build("mesh", cfg)
    groups = engine.num_groups
    check(groups == 2 and engine.mcfg.cache_shard_axis == "model",
          f"mesh: {groups} DP groups, cache axis "
          f"{engine.mcfg.cache_shard_axis!r}")

    # the reference: the same model, initial state and batches on one
    # device, through the unsharded fused op (layer-0 lanes offset per
    # group, since that op sees the global arrays)
    dev0 = jax.devices()[0]
    ref_step = jax.jit(make_train_step(
        dataclasses.replace(engine.mcfg, cache_shard_axis=None), engine.opt))
    ref_state = jax.device_put((engine.params, engine.opt_state), dev0)
    no_home = jax.device_put(jnp.full((1,), -1, jnp.int32), dev0)

    loader = EpochLoader(engine.sampler, engine.ds.train_idx, seed=engine.seed,
                         max_batches=groups, dp_groups=groups)
    c0 = clock.seconds
    steps, gaps = [], []
    for i in range(MESH_EPOCHS):
        mbs = list(loader.epoch(i))         # one step: a batch per group
        sharded, home = collate_groups(mbs, fused=True)
        t0 = time.perf_counter()
        loss, _ = engine.run_batch(sharded, home)
        jax.block_until_ready(engine.params)
        steps.append((loss, time.perf_counter() - t0))

        flat, _ = collate_groups(mbs, fused=False)
        params, opt_state, ref_loss, _ = ref_step(
            *ref_state, jax.device_put(flat.device, dev0),
            jax.device_put(engine._cache_table(mbs[0]), dev0), no_home)
        ref_state = (params, opt_state)
        ref_loss = float(ref_loss)
        gap = abs(loss - ref_loss) / max(1.0, abs(ref_loss))
        gaps.append(gap)
        log(f"[mesh] step {i} home {home.tolist()} sharded {loss!r} "
            f"single-device {ref_loss!r} rel gap {gap!r}")
    report_steps("mesh", steps, MESH_EPOCHS, clock.seconds - c0)
    log(f"[mesh] max rel loss gap {max(gaps)!r} (tolerance {LOSS_TOL})")
    check(max(gaps) <= LOSS_TOL,
          f"mesh: sharded and single-device losses differ by {max(gaps)!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded (data=2, model=2) path")
    args = ap.parse_args(argv)
    try:
        from repro.gns import EngineConfig
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke: run it from a checkout of the repository "
                 f"({e})")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} devices only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, JAX found {len(devices)}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    cfg = EngineConfig.preset("paper_train")
    if args.chips == 4:
        phase_mesh(cfg, clock)
    else:
        engine = phase_train(cfg, clock)
        phase_device(cfg, clock)
        phase_serve(engine, clock)
    log(f"total {time.perf_counter() - t0:.2f}s, compile {clock.seconds:.2f}s,"
        f" persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
