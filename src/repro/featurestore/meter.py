"""Traffic accounting for the multi-tier feature store.

The paper's central systems claim is that a small device-pinned cache removes
most of the host→device feature traffic (Fig. 1: 60–80% of step time is data
copy).  :class:`TrafficMeter` accounts every byte that crosses a tier
boundary so the benchmark harness can reproduce the paper's breakdown
(Fig. 2, Table 4) — now per tier:

* ``device``  — the device-resident cache table (tier 0)
* ``staging`` — the pinned-host staging buffer mirroring the device table
* ``host``    — the full host feature array (tier 2, the slow path)

Locality accounting (PR 3): the meter additionally grows **per-DP-group
request histograms** (``observe_group`` — node-id request counts per group,
the input to ``featurestore.placement.solve_placement``) and counts each
cache hit as *local* or *remote* depending on whether the row's shard is the
requesting group's home shard (``lanes_local`` / ``lanes_remote`` /
``local_hit_fraction``) — the cross-shard lookup traffic the locality-aware
placement minimizes.

Spans: the meter's one timing mechanism.  ``with meter.span(name):`` adds
the interval's wall seconds and its thread's CPU seconds to a per-name
accumulator (``span_stats``), and opens a ``jax.profiler.TraceAnnotation``
of the same name, so that under a profiler every span sits on the device
trace's clock.  The training path's spans:

* ``repro.train.fit``      — ``GNSEngine.fit``'s epoch loop (main thread);
* ``repro.pipeline.wait``  — the step loop blocked on the prefetch queue;
* ``repro.sample``         — one ``GNSSampler.sample`` call (prefetch thread);
* ``repro.sample.draw``    — the ``CSRGraph.sample_neighbors`` calls of
  ``GNSSampler._sample_layer`` (cache draw and top-up draw);
* ``repro.sample.slice``   — the host feature slice in ``assemble_input``;
* ``repro.train.put``      — the ``device_put`` call, which returns at enqueue;
* ``repro.train.h2d``      — from the enqueue until every leaf of the copy is
  ready on the device, booked by :class:`LandingFence`'s thread;
* ``repro.train.dispatch`` — the call into the jitted step, to its return;
* ``repro.train.sync``     — the loss readback that waits for the step.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
import traceback
import weakref
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.analysis import guarded_by

PIPELINE_WAIT = "repro.pipeline.wait"
H2D = "repro.train.h2d"


def tree_nbytes(tree) -> int:
    """Bytes of every array leaf of a pytree, from shapes: what one
    host-to-device copy of it ships."""
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)))


@dataclasses.dataclass
class SpanStats:
    """What one span name has accumulated."""
    count: int = 0
    wall_s: float = 0.0
    cpu_s: float = 0.0             # CPU time of the thread inside the span

    def as_dict(self) -> dict:
        return {"count": self.count, "wall_s": round(self.wall_s, 6),
                "cpu_s": round(self.cpu_s, 6)}


class Span:
    """One timed interval (module docstring), booked on ``meter`` at exit
    (``meter=None`` times and annotates but books nowhere).

    ``t0`` backdates the wall clock's start to an earlier
    ``time.perf_counter()`` reading, for an interval that began on another
    thread; the trace annotation and the CPU clock start at entry.  After
    exit, ``wall_s`` and ``cpu_s`` hold the interval.
    """
    __slots__ = ("meter", "name", "t0", "wall_s", "cpu_s", "_w0", "_c0",
                 "_ann")

    def __init__(self, meter: Optional["TrafficMeter"], name: str,
                 t0: Optional[float] = None):
        self.meter, self.name, self.t0 = meter, name, t0

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._w0 = time.perf_counter() if self.t0 is None else self.t0
        self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self._w0
        self.cpu_s = time.thread_time() - self._c0
        self._ann.__exit__(*exc)
        if self.meter is not None:
            self.meter.book(self.name, self.wall_s, self.cpu_s)


@dataclasses.dataclass
class TierStats:
    """Hit/miss/byte counters for one storage tier."""
    name: str
    hits: int = 0
    misses: int = 0
    bytes_read: int = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "bytes_read": self.bytes_read,
                "hit_rate": round(self.hit_rate, 4)}


@guarded_by("_span_lock", "_spans")
@dataclasses.dataclass
class TrafficMeter:
    """Aggregate host↔device + host-memory traffic counters (bytes) and
    spans (seconds; module docstring).  The prefetch and fence threads book
    spans beside the main thread, so the span accumulator takes a lock."""
    bytes_streamed: int = 0        # host -> device feature rows (PCIe analog)
    bytes_h2d: int = 0             # every leaf handed to device_put by
                                   # GNSEngine._put_batch, from shapes:
                                   # what was shipped, padding included
    bytes_sliced: int = 0          # host-memory gather (CPU bandwidth, step 2)
    bytes_cache_fill: int = 0      # cache refresh host-side gather (|C| rows)
    bytes_cache_upload: int = 0    # cache refresh host->device transfer: sum of
                                   # bytes actually landed on each device — a
                                   # shard-aware upload pays table/n_shards per
                                   # device, a replicated one pays the full table
    bytes_adj_upload: int = 0      # per-generation cache-adjacency CSR
                                   # host->device transfer (backend="device"
                                   # sampling) — kept separate from
                                   # bytes_cache_upload so the 1/n sharded-
                                   # upload acceptance ratio stays a pure
                                   # feature-table number
    bytes_delta_upload: int = 0    # streaming-ingest payload absorbed at
                                   # generation merges (edge-op log + new-
                                   # node feature/label rows) — separate
                                   # from bytes_cache_upload/bytes_adj_upload
                                   # for the same reason: the 1/n upload-
                                   # ratio assert must never see ingest bytes
    bytes_rpc_tx: int = 0          # host->host RPC frames shipped (wire
                                   # header + meta + payload) — the fabric's
                                   # cross-host serving transport
    bytes_rpc_rx: int = 0          # host->host RPC frames received
    uploads: int = 0               # device-table uploads (one per generation)
    lanes_local: int = 0           # cache hits served by the requesting
                                   # group's home shard (no cache-axis hop)
    lanes_remote: int = 0          # cache hits resolved on another shard
                                   # (cross-shard traffic the placement
                                   # solver exists to remove)
    bytes_cross_shard: int = 0     # remote-hit rows x row bytes
    t_refresh: float = 0.0         # background cache-generation build time
    steps: int = 0
    tiers: Dict[str, TierStats] = dataclasses.field(default_factory=dict)
    group_hist: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
                                   # DP group -> per-node request counts
    _spans: Dict[str, SpanStats] = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    _span_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, init=False, repr=False,
        compare=False)

    def span(self, name: str) -> Span:
        """``with meter.span(name):`` times and books one interval."""
        return Span(self, name)

    def book(self, name: str, wall_s: float, cpu_s: float) -> None:
        """Add one interval to ``name``'s accumulator (any thread)."""
        with self._span_lock:
            st = self._spans.get(name)
            if st is None:
                st = self._spans[name] = SpanStats()
            st.count += 1
            st.wall_s += wall_s
            st.cpu_s += cpu_s

    def span_stats(self, name: str) -> SpanStats:
        """A copy of ``name``'s accumulator (zeros before its first span)."""
        with self._span_lock:
            return dataclasses.replace(self._spans.get(name, SpanStats()))

    def span_totals(self) -> Dict[str, SpanStats]:
        """A copy of every span name's accumulator."""
        with self._span_lock:
            return {k: dataclasses.replace(v) for k, v in self._spans.items()}

    @property
    def t_prefetch_wait(self) -> float:
        """Seconds the step loop waited on the prefetch queue (the sampler
        stall; device-backend sampling exists to drive it to ~0)."""
        return self.span_stats(PIPELINE_WAIT).wall_s

    def tier(self, name: str) -> TierStats:
        """Per-tier counters, created on first touch."""
        ts = self.tiers.get(name)
        if ts is None:
            ts = self.tiers[name] = TierStats(name)
        return ts

    @property
    def local_hit_fraction(self) -> float:
        """Fraction of cache hits the requesting group's home shard served."""
        total = self.lanes_local + self.lanes_remote
        return self.lanes_local / total if total else 0.0

    def observe_group(self, group: int, ids: np.ndarray,
                      num_nodes: int) -> None:
        """Accumulate one DP group's requested node ids (hits AND misses —
        the placement solver wants the demand, not the current hit set)."""
        if len(ids) == 0:
            return
        hist = self.group_hist.get(group)
        if hist is None or len(hist) > num_nodes:
            hist = self.group_hist[group] = np.zeros(num_nodes, np.float64)
        elif len(hist) < num_nodes:
            # id space grew (streaming merge): PAD, never reset — the
            # placement solver's demand signal must survive the merge or
            # every generation after an ingest would cold-start contiguous
            grown = np.zeros(num_nodes, np.float64)
            grown[:len(hist)] = hist
            hist = self.group_hist[group] = grown
        np.add.at(hist, np.asarray(ids, dtype=np.int64), 1.0)

    def group_slot_traffic(self, node_ids: np.ndarray,
                           table_rows: int) -> Optional[np.ndarray]:
        """Histograms restricted to one generation's membership, padded to
        the device-table rows — the [n_groups, table_rows] input of
        ``placement.solve_placement`` (None until any traffic is seen).
        Padding slots (``len(node_ids) <= slot < table_rows``) carry zero
        counts, so the solver parks them on whatever capacity is left."""
        if not self.group_hist:
            return None
        groups = sorted(self.group_hist)
        node_ids = np.asarray(node_ids, dtype=np.int64)
        out = np.zeros((len(groups), table_rows), np.float64)
        for gi, g in enumerate(groups):
            hist = self.group_hist[g]
            # ids beyond the histogram are nodes merged in after the last
            # observation — zero demand until traffic touches them
            known = node_ids < len(hist)
            out[gi, :len(node_ids)][known] = hist[node_ids[known]]
        return out

    def group_ids(self) -> list:
        return sorted(self.group_hist)

    def add_batch(self, bytes_streamed: int):
        self.bytes_streamed += bytes_streamed
        self.bytes_sliced += bytes_streamed
        self.steps += 1

    def breakdown(self) -> dict:
        out = {
            "refresh_s": round(self.t_refresh, 4),
            "prefetch_wait_s": round(self.t_prefetch_wait, 4),
            "spans": {k: v.as_dict()
                      for k, v in sorted(self.span_totals().items())},
            "bytes_streamed": self.bytes_streamed,
            "bytes_h2d": self.bytes_h2d,
            "bytes_cache_fill": self.bytes_cache_fill,
            "bytes_cache_upload": self.bytes_cache_upload,
            "bytes_adj_upload": self.bytes_adj_upload,
            "bytes_delta_upload": self.bytes_delta_upload,
            "bytes_rpc_tx": self.bytes_rpc_tx,
            "bytes_rpc_rx": self.bytes_rpc_rx,
            "uploads": self.uploads,
            "steps": self.steps,
            "lanes_local": self.lanes_local,
            "lanes_remote": self.lanes_remote,
            "local_hit_fraction": round(self.local_hit_fraction, 4),
            "bytes_cross_shard": self.bytes_cross_shard,
        }
        if self.tiers:
            out["tiers"] = {k: v.as_dict() for k, v in self.tiers.items()}
        return out


class LandingFence:
    """Books when host-to-device copies land, off the caller's thread.

    ``device_put`` returns at enqueue.  :meth:`land` hands its result, the
    enqueue time and a meter to one long-lived daemon thread, which waits
    until every leaf is ready on the device (``wait``) and books the
    interval from the enqueue as a ``repro.train.h2d`` span.  The caller
    never waits, so the step loop keeps its schedule.  The copies must not
    be donated to a step, since the thread reads them.
    """

    def __init__(self, wait: Callable = jax.block_until_ready):
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        threading.Thread(target=_fence_loop, args=(self._q, wait),
                         daemon=True, name="h2d-fence").start()
        # the thread holds the queue, never the fence: it stops once the
        # fence is collected
        weakref.finalize(self, self._q.put, None)

    def land(self, tree, t0: float, meter: TrafficMeter) -> None:
        """Book ``tree``'s landing on ``meter``, timed from ``t0``."""
        self._q.put((tree, t0, meter))

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait until every copy handed over before the call is booked."""
        done = threading.Event()
        self._q.put(done)
        return done.wait(timeout)


def _fence_loop(q: queue.SimpleQueue, wait: Callable) -> None:
    while True:
        item = q.get()
        if item is None:
            return
        if isinstance(item, threading.Event):
            item.set()
            continue
        tree, t0, meter = item
        del item                     # hold no device batch while idle
        try:
            with Span(meter, H2D, t0=t0):
                wait(tree)
        except Exception:
            # a failed copy also fails the step that reads it; report it
            # and keep the fence alive for the copies after it
            traceback.print_exc()
        del tree, meter
