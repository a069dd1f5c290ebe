"""Sampler pipeline: epoch iteration + asynchronous prefetch.

The paper parallelizes sampling with multiprocessing (§3.3) so the GPU never
waits for the CPU.  This container has one core, so we use a bounded-queue
*thread* prefetcher — the numpy sampler releases the GIL in its hot loops and
at pod scale there is one sampler pipeline per host anyway.

Straggler mitigation (DESIGN.md §4): the queue is bounded and the consumer
can specify a timeout; on timeout it *reuses the previous cache version /
last batch* rather than blocking the whole data-parallel step — exploiting
the paper's own Table 6 result that stale caches (refresh period P ≤ 5) are
accuracy-neutral.

The same contract covers slow shard **uploads** (PR 3): ``swap_if_ready``
only ever publishes a *completed* build (upload included), so the between-
batches poll below never blocks on one; and with
``CacheConfig(refresh_timeout_s=...)`` the epoch-boundary absorb in
``GNSSampler.start_epoch`` gives a straggling upload a bounded grace window
and then keeps training on the old generation instead of stalling the
producer (which would in turn trip the Prefetcher's batch-reuse path
downstream).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import numpy as np

from repro.analysis import guarded_by
from repro.core.minibatch import MiniBatch
from repro.featurestore.meter import PIPELINE_WAIT, Span


class EpochLoader:
    """Shuffles targets, drives the sampler's cache lifecycle, yields batches.

    Drop-last semantics (static shapes want full batches; the paper's epoch
    is |V_s|/batch_size iterations, same convention).

    When the sampler sits on a :class:`repro.featurestore.FeatureStore` with
    an async refresh in flight, the loader polls ``swap_if_ready`` between
    batches: a completed shadow generation is atomically published and the
    sampler adopts it before the next ``sample`` call, so refresh cost
    overlaps sampling/compute instead of stalling the step.
    """

    def __init__(self, sampler, train_idx: np.ndarray, seed: int = 0,
                 max_batches: Optional[int] = None, dp_groups: int = 1):
        """``dp_groups`` > 1 is the engine's DP regime: batch ``i`` belongs
        to DP group ``i % dp_groups`` (the store's per-group histograms and
        home-shard metering follow), the epoch is truncated to whole group
        rounds, and generation swaps are only polled at round boundaries so
        the ``dp_groups`` batches collated into one train step always share
        one cache generation."""
        self.sampler = sampler
        self.train_idx = np.asarray(train_idx, dtype=np.int64)
        self.seed = seed
        self.max_batches = max_batches
        self.dp_groups = max(int(dp_groups), 1)

    def _poll_store(self):
        """Swap point: publish a completed shadow generation, then have the
        sampler adopt it BEFORE the next ``sample`` call.

        Ordering matters for the swap-race contract (see
        ``GNSSampler.adopt_generation``): the swap and the adoption both
        happen here, between batches, on the sampling thread — never while a
        batch is being assembled — so a single batch's slot map, weights and
        cache adjacency all come from one generation.  Already-queued batches
        keep their own ``cache_gen`` (and its immutable device table /
        per-device shards); only future batches see the new generation.
        """
        store = getattr(self.sampler, "store", None)
        if store is not None and store.swap_if_ready():
            adopt = getattr(self.sampler, "adopt_generation", None)
            if adopt is not None:
                adopt()

    def epoch(self, epoch: int) -> Iterator[MiniBatch]:
        rng = np.random.default_rng(self.seed + 7919 * epoch)
        self.sampler.start_epoch(epoch, rng)
        b = self.sampler.cfg.batch_size if hasattr(self.sampler, "cfg") \
            else self.sampler.inner.cfg.batch_size
        perm = rng.permutation(len(self.train_idx))
        n_batches = len(self.train_idx) // b
        if self.max_batches is not None:
            n_batches = min(n_batches, self.max_batches)
        rounded = n_batches - n_batches % self.dp_groups   # whole rounds only
        if n_batches and not rounded:
            raise ValueError(
                f"epoch yields {n_batches} minibatch(es) but dp_groups="
                f"{self.dp_groups} needs at least one full round per step — "
                f"lower batch_size or raise max_batches")
        n_batches = rounded
        store = getattr(self.sampler, "store", None)
        for i in range(n_batches):
            if i % self.dp_groups == 0:
                self._poll_store()
            if store is not None and self.dp_groups > 1:
                store.dp_group = i % self.dp_groups
            targets = self.train_idx[perm[i * b:(i + 1) * b]]
            # per-batch seeded generator: batch (epoch, i) draws the same
            # sample no matter how the prefetcher thread interleaves with
            # cache refreshes or how many batches preceded it — the
            # host-vs-device statistical parity tests (and any replay)
            # depend on this; the epoch rng above stays dedicated to the
            # permutation + cache lifecycle
            batch_rng = np.random.default_rng(
                np.random.SeedSequence((self.seed & 0xFFFFFFFF, epoch, i)))
            yield self.sampler.sample(targets, batch_rng)


@guarded_by("_lock", writes_only=("_err",))
class Prefetcher:
    """Bounded-queue background prefetch with straggler timeout.

    ``wait_s`` accumulates the consumer's time blocked on the queue — the
    *sampler-stall* metric (ROADMAP item 2): when the host sampler is the
    bottleneck the consumer idles here instead of stepping the device.
    Each wait is a ``repro.pipeline.wait`` span, booked on ``meter`` when
    one is given (``TrafficMeter.t_prefetch_wait`` reads its total).
    """

    _SENTINEL = object()
    _EMPTY = object()

    def __init__(self, it: Iterator[MiniBatch], depth: int = 2,
                 timeout_s: Optional[float] = None, meter=None):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._timeout = timeout_s
        self._meter = meter
        self._lock = threading.Lock()   # guards the producer's _err publish
                                        # (consumer reads it lock-free after
                                        # the SENTINEL — queue.put/get is the
                                        # happens-before edge)
        self._err: Optional[BaseException] = None
        self._last: Optional[MiniBatch] = None
        self.reused = 0                       # straggler-mitigation reuse count
        self.wait_s = 0.0                     # consumer time blocked on queue
        self._thread = threading.Thread(target=self._run, args=(it,), daemon=True)
        self._thread.start()

    def _run(self, it):
        try:
            for item in it:
                self._q.put(item)
        except BaseException as e:  # surfaced on the consumer side
            with self._lock:
                self._err = e
        finally:
            self._q.put(self._SENTINEL)

    def _take(self, timeout: Optional[float] = None):
        """One blocking take from the queue (``_EMPTY`` on timeout), timed
        as a wait."""
        with Span(self._meter, PIPELINE_WAIT) as sp:
            try:
                item = self._q.get(timeout=timeout)
            except queue.Empty:
                item = self._EMPTY
        self.wait_s += sp.wall_s
        return item

    def __iter__(self):
        while True:
            item = self._take(self._timeout)
            if item is self._EMPTY:
                # straggler: reuse the last batch instead of stalling the step
                if self._last is None:
                    item = self._take()       # nothing to reuse yet: block
                else:
                    self.reused += 1
                    yield self._last
                    continue
            if item is self._SENTINEL:
                if self._err is not None:
                    raise self._err
                return
            self._last = item
            yield item
