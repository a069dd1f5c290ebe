"""Serving cells: open-loop arrivals into ``engine.serve_fabric()``.

Set-up builds the engine and its in-process fabric, serves one request of
each bucket's size twice (every padded shape the traffic can reach is
compiled before the window), then offers ``warm_seconds`` of the cell's own
traffic (another seed's schedule).  The window offers ``--seconds`` of
arrivals (``traffic.make_schedule``) through ``ServeFabric.submit`` from
this thread, then waits for every answer, at most a minute past the last
arrival.  Each latency runs from the request's due time to its completion
(the fabric's ``total_s`` after submit, plus how late the submit was).

The benchmark wraps the engine's ``infer_prepare`` (host sampling under
the fabric's sample lock) and ``infer_compute`` (copy and compiled step) on
the instance: spans for the trace, and a record of every batch's targets
and logits.  A seeded reservoir keeps ``check_batches`` whole batches, and
the batch with the most ids, for the reference.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from gnsbench import batches, compare, engine_model, gnsref, graphgen, traffic


@dataclasses.dataclass
class Recorder:
    keep: int
    rng: np.random.Generator
    on: bool = False
    n: int = 0
    served: list = dataclasses.field(default_factory=list)   # (ids, logits, version)
    kept: dict = dataclasses.field(default_factory=dict)     # batch no -> mb
    largest: tuple = (-1, None)                 # (ids, (batch no, mb))
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def observe(self, mb, out) -> None:
        n = int(np.asarray(mb.device.label_mask).sum())
        with self.lock:
            if not self.on:
                return
            i = self.n
            self.n += 1
            self.served.append((np.array(mb.input_node_ids[:n]),
                                np.array(out[:n]), mb.cache_version))
            if len(self.kept) < self.keep:            # reservoir sample
                self.kept[i] = mb
            else:
                j = int(self.rng.integers(0, i + 1))
                if j < self.keep:
                    del self.kept[sorted(self.kept)[j]]
                    self.kept[i] = mb
            if n > self.largest[0]:
                self.largest = (n, (i, mb))


@dataclasses.dataclass
class State:
    ctx: object
    data: graphgen.BenchData
    eng: object
    fab: object
    rec: Recorder
    params0: object
    cache_ids: np.ndarray
    version: int
    results: list = dataclasses.field(default_factory=list)  # (ids, ServeResult)


def _engine_config(cfg: dict, tr: dict, seed: int):
    from repro.core.sampler import SamplerConfig
    from repro.featurestore import CacheConfig
    from repro.gns import EngineConfig
    from repro.gns.config import FabricConfig, ServeConfig
    return EngineConfig(
        sampler="gns",
        sampling=SamplerConfig(batch_size=cfg["batch_size"],
                               fanouts=tuple(cfg["fanouts"])),
        cache=CacheConfig(fraction=cfg["cache_fraction"],
                          period=cfg["cache_period"],
                          strategy=cfg["cache_policy"]),
        model=engine_model.model_config(cfg),
        serve=ServeConfig(buckets=tuple(tr["buckets"]),
                          max_wait_ms=tr["max_wait_ms"],
                          max_queue=tr["max_queue"],
                          fabric=FabricConfig(workers=tr["workers"])),
        seed=seed)


def instrument(eng, rec: Recorder, span) -> None:
    prepare, compute = eng.infer_prepare, eng.infer_compute

    def infer_prepare(*a, **kw):
        with span("prepare"):
            return prepare(*a, **kw)

    def infer_compute(mb, meter=None):
        with span("compute"):
            out = compute(mb, meter)
        rec.observe(mb, out)
        return out

    eng.infer_prepare = infer_prepare
    eng.infer_compute = infer_compute


def offer(fab, sched: traffic.Schedule) -> tuple[list, int, np.ndarray]:
    """Submit ``sched`` open-loop from this thread.

    Returns ``(submitted, refused, lateness)``: per submitted request its
    index, its submit time from the schedule's start and its future; how
    many the fabric refused at submit; and how late each submit ran behind
    its due time (s)."""
    from repro.serve import QueueFull, WorkerDown
    subs, rejected = [], 0
    late = np.zeros(sched.n)
    t0 = time.monotonic()
    for i in range(sched.n):
        due = t0 + sched.due[i]
        now = time.monotonic()
        if due > now:
            time.sleep(due - now)
        ts = time.monotonic()
        late[i] = ts - due
        try:
            subs.append((i, ts - t0, fab.submit(sched.requests[i])))
        except (QueueFull, WorkerDown):    # refused at the door
            rejected += 1
    return subs, rejected, late


def collect(subs, sched, deadline: float) -> tuple[list, int]:
    """``[(request index, latency from due s, result)]`` of the answered
    requests, and how many failed or never came by ``deadline``."""
    done, failed = [], 0
    for i, ts, fut in subs:
        try:
            res = fut.result(timeout=max(deadline - time.monotonic(), 1e-3))
        except Exception:                   # failed, refused or never came
            failed += 1
            continue
        if res.status != "ok":
            failed += 1
            continue
        done.append((i, ts - sched.due[i] + res.total_s, res))
    return done, failed


def setup(ctx) -> State:
    import jax
    from repro.gns import GNSEngine
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    data = graphgen.load_data(cfg, log=ctx.log)
    t0 = time.perf_counter()
    eng = GNSEngine(_engine_config(cfg, tr, ctx.engine_seed),
                    dataset=graphgen.as_program_dataset(data, cfg["name"]))
    params0 = jax.device_get(eng.params)
    rec = Recorder(keep=tr["check_batches"],
                   rng=np.random.default_rng([ctx.seed, 1]))
    instrument(eng, rec, ctx.span)
    fab = eng.serve_fabric()
    fab.start()
    ctx.log(f"engine and fabric up in {time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng([ctx.seed, 2])
    for _ in range(2):
        for b in tr["buckets"]:
            fab.infer(rng.choice(data.num_nodes, size=b, replace=False),
                      timeout=600)
    deg = np.diff(data.indptr)
    sched = traffic.make_schedule(tr, deg, tr["warm_seconds"],
                                  ctx.seed + 1)
    subs, _, _ = offer(fab, sched)
    collect(subs, sched, time.monotonic() + 60)
    gen = eng.store.generation
    return State(ctx=ctx, data=data, eng=eng, fab=fab, rec=rec,
                 params0=params0, version=gen.version,
                 cache_ids=np.array(gen.state.node_ids, copy=True))


def measure(st: State, seconds: float, traced: bool):
    from gnsbench.harness import Measured
    tr = st.ctx.cell.traffic
    deg = np.diff(st.data.indptr)
    sched = traffic.make_schedule(tr, deg, seconds, st.ctx.seed)
    st.rec.on = True
    with st.ctx.window():
        t0 = time.perf_counter()
        subs, rejected, late = offer(st.fab, sched)
        done, failed = collect(subs, sched, time.monotonic() + 60.0)
        wall = time.perf_counter() - t0
    st.rec.on = False
    st.results = [(sched.requests[i], res) for i, _, res in done]
    lat = np.array([x[1] for x in done]) * 1e3
    qw = np.array([x[2].queue_wait_s for x in done]) * 1e3
    st.ctx.log(
        f"window: {sched.n} requests at {tr['rate']}/s over {seconds}s, "
        f"{len(done)} answered, {rejected} refused, {failed} failed, "
        f"{st.rec.n} batches, drained after {wall:.3f}s; generator late "
        f"p50 {np.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms")
    record = {"requests": sched.n,
              "queue_wait_p99_ms": float(np.percentile(qw, 99)) if len(qw)
              else None}
    e2e = {"serve_p99_ms": float(np.percentile(lat, 99)),
           "serve_p50_ms": float(np.percentile(lat, 50))}
    return Measured(end_to_end=e2e, attempted=sched.n,
                    failed=rejected + failed, record=record)


def release(st: State) -> None:
    st.fab.stop()
    st.fab = st.eng = None


def kept_batches(st: State) -> list:
    """``(batch no, mb)`` the reference recomputes: the reservoir and the
    batch with the most ids."""
    out = dict(st.rec.kept)
    if st.rec.largest[1] is not None:
        i, mb = st.rec.largest[1]
        out[i] = mb
    return sorted(out.items(), key=lambda kv: kv[0])


def answer_mismatch(st: State) -> int:
    """Answered requests whose logits are not their ids' rows of a batch
    the fabric ran in the window, plus batches off the checked generation."""
    index: dict = {}
    for b, (ids, logits, version) in enumerate(st.rec.served):
        for j in range(len(ids)):
            index.setdefault(logits[j].tobytes(), []).append((b, j))
    bad = sum(v != st.version for _, _, v in st.rec.served)
    for ids, res in st.results:
        ok = False
        for b, j in index.get(res.logits[0].tobytes(), ()):
            bids, blog, _ = st.rec.served[b]
            n = len(ids)
            if (np.array_equal(bids[j:j + n], ids)
                    and np.array_equal(blog[j:j + n], res.logits)):
                ok = True
                break
        bad += not ok
    return int(bad)


def check_inputs(st: State, dtype=np.float64):
    cfg, data = st.ctx.cell.config, st.data
    size = int(data.num_nodes * cfg["cache_fraction"])
    cv = gnsref.CacheView(data.indptr, data.indices, st.cache_ids, size)
    bad = int(len(st.cache_ids) != size)
    out, gap, w_all = [], 0.0, []
    for i, mb in kept_batches(st):
        b, nb, g, wl = batches.reference_batch(cv, data, mb, cfg, "host",
                                               dtype)
        n = len(b.labels)
        out.append((b, np.asarray(st.rec.served[i][1])[:n]))
        bad += nb
        gap = max(gap, g)
        w_all.append(wl)
    return cv, out, bad, gap, w_all


def reference_params0(st: State):
    return st.ctx.cell.reference.init_params(st.ctx.engine_seed,
                                             st.ctx.cell.config)


def check(st: State) -> dict:
    ref = st.ctx.cell.reference
    _, pairs, bad, gap, _ = check_inputs(st)
    p0 = reference_params0(st)
    lg = max((compare.logit_gap(prog, ref.logits(p0, b)) for b, prog in pairs),
             default=float("inf"))
    return {"logit_gap": lg, "answer_mismatch": answer_mismatch(st),
            "bad_lanes": bad, "weight_gap": gap}
