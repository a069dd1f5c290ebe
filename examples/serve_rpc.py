"""Cross-host serving quickstart: endpoint replicas over localhost TCP.

The deployment shape this demonstrates (one process per box in production;
here everything runs on localhost so the example is self-contained):

* N ``WorkerEndpoint`` processes, each hosting a full engine replica —
  its own feature-store cache, compiled inference buckets, and fair
  scheduler — started with::

      python -m repro.rpc.endpoint --config engine.json --index 0 --port 7001

* ONE coordinator that connects a :class:`~repro.serve.ServeFabric` with
  ``transport="tcp"`` to those endpoints.  Clients talk to the coordinator
  exactly as they would to an in-process fabric — routing, tenancy,
  heartbeat liveness, and failover all ride the same code path, just with
  :class:`~repro.rpc.RemoteWorkerProxy` in place of a worker thread.

By default this script spawns the endpoints as REAL subprocesses (the
honest cross-host rehearsal: separate interpreters, separate caches, bytes
on a socket).  A chip belongs to one process, so on an accelerator host
each endpoint process is given a chip of its own (``TPU_VISIBLE_CHIPS``),
the script refuses to start more endpoints than the host has chips, and the
coordinator — which only routes — keeps its engine on the CPU.  This
process never touches the chips itself: a short-lived child counts them.
``--in-thread`` serves the endpoints on threads of this one process
instead, which is faster to start when you just want to see the API.
``--kill-endpoint`` SIGKILLs endpoint 0 mid-stream to show lossless
failover onto the survivor.

Run:  PYTHONPATH=src python examples/serve_rpc.py [--requests 100]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig
from repro.gns import (EngineConfig, FabricConfig, GNSEngine, ServeConfig,
                       TenantConfig)
from repro.gns.config import DataConfig
from repro.launch.compile_cache import use_compile_cache


def _engine_config(scale: float) -> EngineConfig:
    return EngineConfig(
        sampler="gns",
        data=DataConfig(name="ogbn-products", scale=scale),
        sampling=SamplerConfig(batch_size=128, fanouts=(5, 10)),
        cache=CacheConfig(fraction=0.05, strategy="adaptive"),
        serve=ServeConfig(buckets=(16, 64), max_wait_ms=2.0))


def _host_devices() -> tuple[str, int]:
    """(platform, device count) as JAX sees them on this host, asked of a
    short-lived child: the process that touches JAX holds the chips."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        capture_output=True, text=True, check=True)
    platform, count = out.stdout.split()[-2:]
    return platform, int(count)


def _endpoint_env(index: int, platform: str) -> dict:
    env = dict(os.environ, PYTHONPATH="src")
    if platform == "tpu":                 # one chip per endpoint process
        env.update(TPU_VISIBLE_CHIPS=str(index),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_PORT=str(8476 + index))
    return env


def _spawn_subprocess_endpoints(cfg: EngineConfig, n: int):
    """One ``python -m repro.rpc.endpoint`` process per replica, each on a
    chip of its own where the host has accelerators."""
    platform, count = _host_devices()
    if platform != "cpu" and count < n:
        sys.exit(f"serve_rpc: {n} endpoint processes need a {platform} chip "
                 f"each, and this host has {count}; pass --endpoints "
                 f"{count} or --in-thread")
    fd, cfg_path = tempfile.mkstemp(suffix=".json")
    with os.fdopen(fd, "w") as f:
        json.dump(cfg.to_dict(), f)
    procs, ports = [], []
    for i in range(n):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.rpc.endpoint",
             "--config", cfg_path, "--index", str(i), "--port", "0"],
            env=_endpoint_env(i, platform),
            stdout=subprocess.PIPE, text=True))
    for i, p in enumerate(procs):
        line = p.stdout.readline()           # blocks until the replica is up
        if "GNS_ENDPOINT_READY" not in line:
            for q in procs:
                q.kill()
            sys.exit(f"serve_rpc: endpoint {i} exited before it was ready "
                     f"(exit code {p.wait()})")
        ports.append(int(dict(kv.split("=")
                              for kv in line.split()[1:])["port"]))
        print(f"  endpoint up: pid={p.pid} port={ports[-1]}")
    return procs, ports, cfg_path


def _spawn_thread_endpoints(cfg: EngineConfig, n: int):
    from repro.rpc import WorkerEndpoint
    eps = []
    for i in range(n):
        ep = WorkerEndpoint(GNSEngine(cfg), index=i)
        ep.serve_in_thread()
        eps.append(ep)
        print(f"  endpoint up (thread): port={ep.port}")
    return eps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--endpoints", type=int, default=2)
    ap.add_argument("--in-thread", action="store_true",
                    help="serve endpoints on threads instead of subprocesses")
    ap.add_argument("--kill-endpoint", action="store_true",
                    help="SIGKILL endpoint 0 mid-stream (subprocess mode)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = _engine_config(args.scale)
    print(f"starting {args.endpoints} endpoint replicas ...")
    procs, eps = [], []
    if args.in_thread:
        eps = _spawn_thread_endpoints(cfg, args.endpoints)
        ports = [ep.port for ep in eps]
    else:
        procs, ports, _ = _spawn_subprocess_endpoints(cfg, args.endpoints)
        # the coordinator only routes: its engine stays off the chips the
        # endpoint processes hold
        jax.config.update("jax_platforms", "cpu")

    try:
        coordinator = GNSEngine(cfg)
        fab = coordinator.serve_fabric(FabricConfig(
            workers=args.endpoints, transport="tcp",
            endpoints=tuple(f"127.0.0.1:{p}" for p in ports),
            tenants=(TenantConfig("mobile", weight=2.0,
                                  max_queue=args.requests + 8),
                     TenantConfig("batch", weight=1.0,
                                  max_queue=args.requests + 8))))

        rng = np.random.default_rng(0)
        pool = coordinator.ds.val_idx
        print(f"serving {args.requests} requests over TCP ...")
        with fab:
            futs = []
            for i in range(args.requests):
                ids = rng.choice(pool, size=int(rng.integers(2, 10)),
                                 replace=False)
                futs.append(fab.submit(
                    ids, tenant="mobile" if i % 2 == 0 else "batch"))
                if (args.kill_endpoint and procs
                        and i == args.requests // 2):
                    os.kill(procs[0].pid, signal.SIGKILL)
                    print("SIGKILLed endpoint 0 — the heartbeat lapses, the "
                          "watchdog reclaims its in-flight requests, and "
                          "the survivor re-serves them ...")
            for f in futs:
                r = f.result(timeout=600)
                assert r.status == "ok" and np.isfinite(r.logits).all()
            remote = fab.pull_remote_stats(timeout=30.0)
            snap = fab.snapshot()

        print(f"served {args.requests}/{args.requests}; wire bytes "
              f"tx={snap['rpc']['bytes_rpc_tx']:,} "
              f"rx={snap['rpc']['bytes_rpc_rx']:,}")
        for idx, stats in sorted(remote.items()):
            c = stats["counters"]
            print(f"  endpoint {idx}: served {c['served']:>4}  "
                  f"rx {c['bytes_rpc_rx']:>9,}B  tx {c['bytes_rpc_tx']:>9,}B")
        if args.kill_endpoint:
            rt = snap["routing"]
            print(f"failovers {rt['failovers']}, retries {rt['retries']}, "
                  f"healthy at exit: {fab.healthy()}")
    finally:
        for ep in eps:
            ep.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)


if __name__ == "__main__":
    main()
