"""ServeFabric chaos battery: stall, death, failover, swap-in-flight.

In-process (meshless, tiny dataset, runtime lock sanitizer armed by
conftest):

* a STALLED worker leaves the routing rotation, its queued requests are
  re-routed to a healthy worker, and it re-enters the rotation when it
  wakes up;
* a KILLED worker (thread aborts mid-batch) has its in-flight batch
  reclaimed by the watchdog and re-routed — the request is still served;
* with every worker dead, requests fail fast with :class:`WorkerDown`;
* a mid-stream generation swap UNDER an in-flight (stalled) batch leaves
  its result bitwise-identical to a no-swap fabric run and pinned to the
  old generation — the single-server guarantee survives the fleet.

Subprocess (4 forced host devices, ``@pytest.mark.dryrun`` — the CI
``fabric-smoke`` acceptance): a 2-worker fabric on the 2x2 sharded fused
mesh serving two tenants with skewed disjoint hot sets — per-tenant
isolation holds, routing is majority-local (> 0.5), p99 stays bounded,
and a worker kill mid-stream fails over without losing a request.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.analysis import TrackedLock
from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig
from repro.gns import (EngineConfig, FabricConfig, GNSEngine, ServeConfig,
                       TenantConfig)
from repro.graph.datasets import get_dataset
from repro.serve import ServeFabric, WorkerDown


@pytest.fixture(scope="module")
def tiny_ds():
    return get_dataset("tiny", seed=0)


def _engine(tiny_ds, seed=0):
    scfg = SamplerConfig(fanouts=(3, 4), batch_size=32,
                         cache=CacheConfig(fraction=0.1))
    cfg = EngineConfig(sampler="gns", sampling=scfg, cache=scfg.cache,
                       seed=seed,
                       serve=ServeConfig(buckets=(8, 32), max_wait_ms=5.0))
    return GNSEngine(cfg, dataset=tiny_ds)


def _fabric(eng, **kw):
    kw.setdefault("workers", 2)
    kw.setdefault("stall_timeout_ms", 100.0)
    kw.setdefault("watch_interval_ms", 20.0)
    return ServeFabric(eng, cfg=FabricConfig(**kw))


def _wait(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------

def test_stalled_worker_requests_rerouted_then_recovers(tiny_ds):
    eng = _engine(tiny_ds)
    fab = _fabric(eng)
    assert isinstance(fab._sample_lock, TrackedLock)   # sanitizer sees it
    with fab:
        fab.infer(tiny_ds.val_idx[:4], timeout=120)    # warm both workers'
        fab.infer(tiny_ds.val_idx[4:8], timeout=120)   # compiled step
        w0 = fab.workers[0]
        w0.stall_s = 0.8                               # >> stall_timeout
        stuck = fab.submit(tiny_ds.val_idx[:4], worker=0)
        # wait until the batch is actually in flight (prepare done, stalled)
        assert _wait(lambda: len(w0._inflight) > 0)
        # these pile up in worker 0's scheduler behind the stall ...
        queued = [fab.submit(tiny_ds.val_idx[i * 4:(i + 1) * 4], worker=0)
                  for i in range(1, 4)]
        # ... until the watchdog declares the stall and re-routes them
        assert _wait(lambda: fab.healthy() == [1]), fab.healthy()
        for f in queued:
            assert f.result(timeout=120).status == "ok"
        # the stalled batch itself still completes (the worker lives)
        assert stuck.result(timeout=120).status == "ok"
        w0.stall_s = 0.0
        # a fresh heartbeat puts worker 0 back into the rotation
        assert _wait(lambda: fab.healthy() == [0, 1]), fab.healthy()
    m = fab.meter
    assert m.failovers >= 1
    assert m.retries_total >= 3
    snap = m.snapshot()
    assert snap["errors"] == 0
    assert snap["routing"]["worker_batches"].get(1, 0) >= 1


def test_killed_worker_inflight_reclaimed_and_served(tiny_ds):
    eng = _engine(tiny_ds)
    fab = _fabric(eng)
    with fab:
        fab.infer(tiny_ds.val_idx[:4], timeout=120)    # warm
        w0 = fab.workers[0]
        w0.stall_s = 0.3          # hold the batch so the kill flag is seen
        w0.kill()                 # next batch aborts the thread mid-flight
        fut = fab.submit(tiny_ds.val_idx[:8], worker=0)
        assert _wait(lambda: not w0.alive()), "worker thread did not die"
        # the watchdog reclaims the in-flight batch and re-routes it
        res = fut.result(timeout=120)
        assert res.status == "ok"
        # a dead worker never recovers
        assert fab.healthy() == [1]
        # un-pinned traffic keeps flowing through the survivor
        assert fab.infer(tiny_ds.val_idx[:4], timeout=120).shape[0] == 4
    m = fab.meter
    assert m.failovers >= 1 and m.retries_total >= 1
    assert m.errors == 0


def test_all_workers_dead_fails_fast(tiny_ds):
    eng = _engine(tiny_ds)
    fab = _fabric(eng, workers=1)
    with fab:
        fab.infer(tiny_ds.val_idx[:4], timeout=120)    # warm
        w0 = fab.workers[0]
        w0.kill()
        fut = fab.submit(tiny_ds.val_idx[:4], worker=0)
        assert _wait(lambda: not w0.alive())
        with pytest.raises(WorkerDown):
            fut.result(timeout=120)
        with pytest.raises(WorkerDown):                # un-pinned submit too
            _wait(lambda: fab.healthy() == [], timeout=5.0)
            fab.submit(tiny_ds.val_idx[:4])


# ---------------------------------------------------------------------------
# swap under an in-flight batch: bitwise identity across the fleet
# ---------------------------------------------------------------------------

def test_inflight_results_bitwise_identical_across_swap(tiny_ds):
    """Two fabrics, same seed, all requests pinned to worker 0 and served
    one at a time.  Fabric B's last request is held in flight (stall hook,
    after sampling) while the live generation is swapped under it — its
    logits must equal fabric A's no-swap run bitwise, still pinned to the
    old generation; the NEXT request adopts the new one."""
    chunks = [tiny_ds.val_idx[i * 8:(i + 1) * 8] for i in range(5)]

    def run(swap_under_last):
        eng = _engine(tiny_ds, seed=3)
        # huge stall timeout: the stall must NOT trigger failover here
        fab = _fabric(eng, stall_timeout_ms=60_000.0)
        out = []
        with fab:
            w0 = fab.workers[0]
            for i, ids in enumerate(chunks):
                if swap_under_last and i == len(chunks) - 1:
                    w0.stall_s = 1.5
                    # a worker lists a batch in flight before it samples
                    # it: wait for the sample itself, which pins the
                    # generation, before swapping
                    sampled = threading.Event()
                    prepare = fab._prepare

                    def _prepare(*args, **kw):
                        mb = prepare(*args, **kw)
                        sampled.set()
                        return mb

                    fab._prepare = _prepare
                    fut = fab.submit(ids, worker=0)
                    assert sampled.wait(timeout=60)
                    assert len(w0._inflight) > 0
                    # the batch is sampled and pinned; swap the live
                    # generation UNDER it
                    v0 = eng.store.version
                    eng.store.refresh(np.random.default_rng(99),
                                      version=v0 + 1)
                    assert eng.store.version == v0 + 1
                    out.append(fut.result(timeout=120))
                    w0.stall_s = 0.0
                else:
                    out.append(fab.submit(ids, worker=0).result(timeout=120))
            if swap_under_last:
                # a fresh request adopts the new generation (monotonic)
                follow = fab.submit(chunks[0], worker=0).result(timeout=120)
                assert follow.cache_version == out[-1].cache_version + 1
        return out

    plain = run(swap_under_last=False)
    swapped = run(swap_under_last=True)
    assert all(r.status == "ok" for r in plain + swapped)
    for a, b in zip(plain, swapped):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.cache_version == b.cache_version == 0


# ---------------------------------------------------------------------------
# subprocess: the CI fabric-smoke acceptance (4 forced host devices)
# ---------------------------------------------------------------------------

FABRIC_SMOKE_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_LOCK_SANITIZER"] = "1"
import time
import numpy as np
import jax

from repro.analysis import enable_sanitizer
enable_sanitizer(True)

from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig
from repro.gns import (EngineConfig, FabricConfig, GNSEngine, ServeConfig,
                       TenantConfig)
from repro.gns.config import MeshConfig, ModelConfig

assert len(jax.devices()) == 4

# production shape at CI scale: 2 DP groups x 2 cache shards, fused input,
# locality placement — each fabric worker owns one DP group/home shard
scfg = SamplerConfig(fanouts=(3, 4), batch_size=32,
                     cache=CacheConfig(fraction=0.05, strategy="adaptive",
                                       placement="locality"))
cfg = EngineConfig(sampler="gns", sampling=scfg, cache=scfg.cache,
                   model=ModelConfig(input_impl="fused", hidden_dim=16),
                   mesh=MeshConfig(data=2, model=2),
                   serve=ServeConfig(buckets=(8, 32), max_wait_ms=2.0),
                   seed=0)
eng = GNSEngine(cfg)
assert eng.store.n_shards == 2
ds = eng.ds

fab = eng.serve_fabric(FabricConfig(
    workers=2,
    tenants=(TenantConfig("mobile", weight=2.0, max_queue=64),
             TenantConfig("batch", weight=1.0, max_queue=64)),
    stall_timeout_ms=2000.0, watch_interval_ms=50.0))

rng = np.random.default_rng(7)
# skewed DISJOINT per-tenant hot sets: routing + placement should converge
# each tenant's traffic onto one worker's home shard
half = len(ds.val_idx) // 2
hot_a = rng.choice(ds.val_idx[:half], size=30, replace=False)
hot_b = rng.choice(ds.val_idx[half:], size=30, replace=False)

with fab:
    futs = []
    for i in range(60):
        tenant, hot = (("mobile", hot_a) if i % 2 == 0 else ("batch", hot_b))
        ids = rng.choice(hot, size=int(rng.integers(2, 8)), replace=False)
        futs.append(fab.submit(ids, tenant=tenant))
    res = [f.result(timeout=600) for f in futs]
    assert all(r.status == "ok" for r in res), [r.status for r in res]

    # chaos mid-stream: kill worker 0, traffic fails over losslessly
    fab.workers[0].kill()
    fut = fab.submit(rng.choice(hot_a, size=4, replace=False),
                     tenant="mobile", worker=0)
    deadline = time.monotonic() + 60
    while fab.workers[0].alive() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not fab.workers[0].alive()
    assert fut.result(timeout=600).status == "ok"      # re-routed + served
    tail = [fab.submit(rng.choice(hot_b, size=4, replace=False),
                       tenant="batch") for _ in range(6)]
    assert all(f.result(timeout=600).status == "ok" for f in tail)
    assert fab.healthy() == [1]

snap = fab.meter.snapshot()

# 1) per-tenant isolation ledger: both tenants fully served, nothing shed
for t in ("mobile", "batch"):
    assert snap["tenants"][t]["rejected"] == 0, snap["tenants"]
assert snap["tenants"]["mobile"]["served"] >= 31
assert snap["tenants"]["batch"]["served"] >= 36

# 2) placement-aware routing: majority of owned ids routed to their owner
rt = snap["routing"]
assert rt["routed_known_ids"] > 0, rt
assert rt["route_local_fraction"] > 0.5, rt
# both workers actually served before the kill
assert set(rt["worker_batches"]) == {0, 1}, rt

# 3) failover happened and was lossless
assert rt["failovers"] >= 1 and rt["retries"] >= 1, rt
assert snap["errors"] == 0, snap

# 4) p99 bounded on the CI box
assert snap["total_p99_ms"] is not None and snap["total_p99_ms"] < 60000, snap

print("FABRIC_SMOKE_OK", "local=", rt["route_local_fraction"],
      "p99_ms=", snap["total_p99_ms"], "failovers=", rt["failovers"])
"""


def _run_sub(code: str, timeout: int = 900) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.dryrun
def test_fabric_smoke_on_mesh_subprocess():
    """The CI fabric-smoke acceptance: 2 workers on the forced-host 2x2
    mesh, two skewed tenants — isolation, majority-local routing, lossless
    kill-failover, bounded p99, lock sanitizer armed throughout."""
    out = _run_sub(FABRIC_SMOKE_CODE)
    assert "FABRIC_SMOKE_OK" in out, out[-3000:]
