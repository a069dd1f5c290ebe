"""TrafficMeter spans: the accumulator, the prefetch wait, the training
path's spans and counters, and the landing fence."""
from __future__ import annotations

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import Prefetcher
from repro.core.sampler import SamplerConfig
from repro.featurestore import CacheConfig, TrafficMeter
from repro.featurestore.meter import (H2D, PIPELINE_WAIT, LandingFence,
                                      Span, SpanStats)
from repro.gns import EngineConfig, GNSEngine
from repro.graph.datasets import get_dataset

TRAIN_SPANS = ("repro.train.put", "repro.train.dispatch", "repro.train.sync")


def _busy(seconds: float) -> None:
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def test_span_accumulator_nests_counts_and_sums():
    m = TrafficMeter()
    assert m.span_stats("outer") == SpanStats()
    walls = []
    for _ in range(3):
        with m.span("outer") as outer:
            with m.span("inner") as inner:
                _busy(0.002)
            time.sleep(0.002)
        walls.append((outer.wall_s, inner.wall_s, inner.cpu_s))
    o, i = m.span_stats("outer"), m.span_stats("inner")
    assert (o.count, i.count) == (3, 3)
    assert o.wall_s == pytest.approx(sum(w[0] for w in walls))
    assert i.wall_s == pytest.approx(sum(w[1] for w in walls))
    assert i.cpu_s == pytest.approx(sum(w[2] for w in walls))
    assert o.wall_s > i.wall_s                  # the outer span holds the sleep
    assert i.cpu_s >= 0.006 * 0.9               # the inner one burnt CPU
    assert o.cpu_s < o.wall_s                   # sleeping costs no CPU
    assert set(m.span_totals()) == {"outer", "inner"}
    assert m.breakdown()["spans"]["inner"]["count"] == 3


def test_span_accumulator_many_threads_one_name():
    """More threads than cores book one name with a short switch interval:
    a lost update would show in the count or the sum."""
    m = TrafficMeter()
    n_threads, per_thread = 16, 200
    walls: list = [[] for _ in range(n_threads)]

    def work(k):
        for _ in range(per_thread):
            with m.span("shared") as sp:
                pass
            walls[k].append(sp.wall_s)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in ts)
    st = m.span_stats("shared")
    assert st.count == n_threads * per_thread
    assert st.wall_s == pytest.approx(sum(map(sum, walls)))


def test_backdated_span_times_from_t0():
    m = TrafficMeter()
    t0 = time.perf_counter()
    time.sleep(0.02)
    with Span(m, "late", t0=t0) as sp:
        pass
    assert sp.wall_s >= 0.02 > sp.cpu_s        # the CPU clock starts at entry
    with m.span("late") as now:
        pass
    assert now.wall_s < 0.02
    assert m.span_stats("late").count == 2


def test_t_prefetch_wait_is_the_wait_span_total():
    m = TrafficMeter()

    def slow():
        for i in range(4):
            time.sleep(0.02)
            yield i

    p = Prefetcher(slow(), depth=2, meter=m)
    assert list(p) == [0, 1, 2, 3]
    st = m.span_stats(PIPELINE_WAIT)
    assert st.count == 5                         # 4 items + the end marker
    assert m.t_prefetch_wait == st.wall_s == pytest.approx(p.wait_s)
    assert m.t_prefetch_wait > 0.05
    assert m.breakdown()["prefetch_wait_s"] == round(st.wall_s, 4)


def test_prefetch_straggler_waits_are_booked():
    """A take that times out and the blocking take after it are both waits
    (the same two intervals as before the spans)."""
    m = TrafficMeter()

    def slow():
        time.sleep(0.1)
        yield 0

    p = Prefetcher(slow(), depth=2, timeout_s=0.01, meter=m)
    assert list(p) == [0]
    st = m.span_stats(PIPELINE_WAIT)
    assert st.count == 3             # timeout, blocking take, end marker
    assert st.wall_s == pytest.approx(p.wait_s)
    assert st.wall_s >= 0.09


@pytest.fixture(scope="module")
def tiny_ds():
    return get_dataset("tiny", seed=0)


def _engine(ds, backend="host"):
    scfg = SamplerConfig(fanouts=(3, 4), batch_size=32, backend=backend,
                         cache=CacheConfig(fraction=0.1, period=1))
    return GNSEngine(EngineConfig(sampler="gns", sampling=scfg,
                                  cache=scfg.cache, seed=0), dataset=ds)


def _capture_puts(eng) -> list:
    """Record every host batch handed to ``_put_batch`` on the training
    meter."""
    put, shipped = eng._put_batch, []

    def capture(host_batch, meter=None):
        if meter is None:
            shipped.append(host_batch)
        return put(host_batch, meter)

    eng._put_batch = capture
    return shipped


def _leaf_nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("backend", ["host", "device"])
def test_bytes_h2d_counts_every_leaf_put(tiny_ds, backend):
    eng = _engine(tiny_ds, backend)
    shipped = _capture_puts(eng)
    eng.fit(epochs=1, max_batches=3, prefetch=True)
    assert len(shipped) == eng.meter.steps == 3
    per_step = [_leaf_nbytes(b) for b in shipped]
    assert len(set(per_step)) == 1                   # static padded shapes
    assert eng.meter.bytes_h2d == sum(per_step)
    assert eng.meter.breakdown()["bytes_h2d"] == sum(per_step)
    # what was shipped, beside the real uncached rows of bytes_streamed
    assert eng.meter.bytes_h2d > eng.meter.bytes_streamed


def test_training_path_spans(tiny_ds):
    eng = _engine(tiny_ds)
    eng.fit(epochs=2, max_batches=3, prefetch=True)
    assert eng.fence.flush(timeout=30)
    sp = eng.meter.span_totals()
    steps = eng.meter.steps
    assert steps == 6
    assert sp["repro.train.fit"].count == 1
    for name in TRAIN_SPANS + (H2D, "repro.sample", "repro.sample.slice"):
        assert sp[name].count == steps, name
    # one wait per batch and one for each epoch's end marker
    assert sp[PIPELINE_WAIT].count == steps + 2
    inner = sum(sp[n].wall_s for n in TRAIN_SPANS + (PIPELINE_WAIT,))
    assert inner < sp["repro.train.fit"].wall_s
    assert sp["repro.sample.slice"].wall_s < sp["repro.sample"].wall_s


@pytest.mark.parametrize("backend", ["host", "device"])
def test_draw_span_inside_sample(tiny_ds, backend):
    """``repro.sample.draw`` books the neighbour draws of every batch on
    the prefetch thread, inside ``repro.sample``."""
    eng = _engine(tiny_ds, backend)
    eng.fit(epochs=1, max_batches=3, prefetch=True)
    sp = eng.meter.span_totals()
    draw, sample = sp["repro.sample.draw"], sp["repro.sample"]
    assert sample.count == 3
    assert draw.count >= sample.count
    assert draw.wall_s < sample.wall_s


def test_training_spans_without_prefetch(tiny_ds):
    """The loader is iterated directly: sampling is timed on the main
    thread by its own span, and nothing waits on a queue."""
    eng = _engine(tiny_ds)
    eng.fit(epochs=1, max_batches=3, prefetch=False)
    sp = eng.meter.span_totals()
    assert PIPELINE_WAIT not in sp
    assert sp["repro.sample"].count == sp["repro.train.sync"].count == 3


def test_eval_books_no_training_spans(tiny_ds):
    eng = _engine(tiny_ds)
    eng.evaluate(num_batches=2)
    assert eng.fence.flush(timeout=30)
    assert "repro.sample" not in eng.meter.span_totals()
    ev = eng.meter_eval.span_totals()
    assert ev["repro.train.put"].count == ev[H2D].count == 2


def test_fence_books_one_landing_per_put_and_fit_never_waits(tiny_ds):
    eng = _engine(tiny_ds)
    gate = threading.Event()

    def gated_wait(tree):
        gate.wait(60)
        return jax.block_until_ready(tree)

    eng.fence = LandingFence(wait=gated_wait)
    eng.fit(epochs=1, max_batches=4, prefetch=True)
    # fit returned while the fence was still blocked on the first copy
    assert eng.meter.span_stats(H2D).count == 0
    assert eng.meter.span_stats("repro.train.put").count == 4
    gate.set()
    assert eng.fence.flush(timeout=30)
    h2d = eng.meter.span_stats(H2D)
    assert h2d.count == 4
    assert h2d.wall_s > 0.0


def test_fence_survives_a_failing_wait():
    m = TrafficMeter()
    calls = []

    def wait(tree):
        calls.append(tree)
        if tree == "bad":
            raise RuntimeError("copy failed")

    fence = LandingFence(wait=wait)
    t0 = time.perf_counter()
    fence.land("bad", t0, m)
    fence.land("good", t0, m)
    assert fence.flush(timeout=10)
    assert calls == ["bad", "good"]
    assert m.span_stats(H2D).count == 2


def test_device_names_of_the_gather_and_the_step(tiny_ds):
    from repro.sampling.kernels import slot_gather_agg_pallas
    table = jnp.ones((16, 128), jnp.float32)
    rows = jnp.zeros((8, 3), jnp.int32)
    w = jnp.ones((8, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda t, r, x: slot_gather_agg_pallas(
        t, r, x, interpret=True))(table, rows, w)
    assert "gns_sample_gather" in str(jaxpr)

    eng = _engine(tiny_ds)
    eng.fit(epochs=1, max_batches=1, prefetch=False)
    mb = eng.sampler.sample(tiny_ds.train_idx[:32], np.random.default_rng(0))
    hlo = eng._train_step.lower(
        eng.params, eng.opt_state, mb.device, eng._cache_table(mb),
        np.array([-1], np.int32), None).as_text(debug_info=True)
    assert "gns_train_step" in hlo
