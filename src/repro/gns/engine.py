"""GNSEngine — the unified engine behind every GNS surface.

One object owns the wiring the trainer, the examples, the benchmarks and the
pod-scale dry-run each used to hand-assemble:

    FeatureStore  →  sampler  →  EpochLoader / Prefetcher  →  compiled step

built from one declarative :class:`~repro.gns.config.EngineConfig`, and
exposing the four verbs every surface needs:

* :meth:`fit`      — the paper's §2.2 training loop (sample → slice → copy →
  compute) with the Fig. 1/2 timing/traffic breakdown on the meter;
* :meth:`evaluate` — micro-F1 over held-out targets (meter suspended);
* :meth:`infer`    — mini-batch inference reusing the LIVE cache generation:
  the first serving-shaped entry point — logits for arbitrary node ids at
  cache-hit feature cost, no refresh, no training side effects;
* :meth:`describe` — the lowering/traffic report ``launch.dryrun_gnn``
  prints, for THIS config.

**DP > 1 in one compiled step** (the PR-3 follow-up this engine closes): on
a mesh with data-parallel axes the engine samples one minibatch per DP group
per step, collates them into a single group-ordered batch
(:func:`collate_groups`), and passes a device-resident int32 **home-shard
vector** — one entry per group, ``-1`` when that group's batch has no
locality contract — to the train step.  The fused input op branches on the
owner shard at RUNTIME (``lax.cond`` on the traced vector,
``kernels.ops._fused_forward``), so a single jit cache entry serves batches
with any mix of home shards; the old path retraced on every distinct
``MiniBatch.local_shard`` because it was a static jit argument.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.minibatch import DeviceBatch, LayerBlock, MiniBatch
from repro.core.pipeline import EpochLoader, Prefetcher
from repro.core.sampler import GNSSampler, make_sampler
from repro.featurestore import FeatureStore, TrafficMeter
from repro.featurestore.meter import LandingFence, tree_nbytes
from repro.gns.config import EngineConfig
from repro.kernels.ops import dp_group_count
from repro.launch import sharding as shlib
from repro.models import graphsage
from repro.optim.adam import AdamW


@dataclasses.dataclass
class TrainReport:
    epoch_times: list
    losses: list
    val_acc: list
    meter: TrafficMeter
    input_nodes_per_batch: float = 0.0
    cached_nodes_per_batch: float = 0.0
    isolated_per_batch: float = 0.0


def make_train_step(mcfg: graphsage.SageConfig, opt: AdamW):
    """The one train step every surface compiles.

    ``home_shards`` is the device-resident per-group home-shard vector (or
    None to lower the plain psum input path); it is a TRACED operand, so the
    jitted step never retraces when a batch's home shard changes.
    ``device_adj`` (a DeviceCacheAdj pytree, or None for host-backend runs)
    switches layer 0 to the on-device GNS draw — it is also traced, so
    generation swaps reuse the same compiled step.
    """
    def train_step(params, opt_state, batch, cache_table, home_shards,
                   device_adj=None):
        with jax.named_scope("gns_train_step"):
            (loss, acc), grads = jax.value_and_grad(
                graphsage.loss_fn, has_aux=True)(params, batch, cache_table,
                                                 mcfg, home_shards, device_adj)
            params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss, acc
    return train_step


def collate_groups(mbs: Sequence[MiniBatch], fused: bool
                   ) -> tuple[MiniBatch, np.ndarray]:
    """Collate one MiniBatch per DP group into a single step batch.

    Group-order concatenation of every device array; block pads stay
    PER-GROUP (``SageConfig.num_groups`` tells the model to gather each
    group's leading rows instead of slicing a global prefix).  Gather
    indices are group-local per assembly, so upper-layer blocks — consumed
    by GLOBAL gathers in the model — are offset by ``g·num_src``; the input
    block stays group-local when the fused op consumes it (its shard_map
    body sees exactly one group's slice) and is offset otherwise.

    Returns the collated batch plus the int32 home-shard vector (one entry
    per group, -1 where the group's batch had no locality contract).  All
    batches must carry the SAME cache generation — the loader only polls
    generation swaps at step boundaries, so a swap can never tear a step.
    """
    if len(mbs) == 1:
        mb = mbs[0]
        ls = mb.local_shard if mb.local_shard is not None else -1
        return mb, np.array([ls], np.int32)
    gens = {mb.cache_gen.version if mb.cache_gen is not None else -1
            for mb in mbs}
    assert len(gens) == 1, f"step spans cache generations {gens}"
    blocks = []
    for li in range(len(mbs[0].device.blocks)):
        bs = [mb.device.blocks[li] for mb in mbs]
        s, d = bs[0].num_src, bs[0].num_dst
        offset = li > 0 or not fused
        blocks.append(LayerBlock(
            nbr_idx=np.concatenate(
                [b.nbr_idx + (g * s if offset else 0)
                 for g, b in enumerate(bs)]).astype(np.int32),
            nbr_w=np.concatenate([b.nbr_w for b in bs]),
            dst_mask=np.concatenate([b.dst_mask for b in bs]),
            num_src=s, num_dst=d))
    def _cat(field):
        vals = [getattr(mb.device, field) for mb in mbs]
        return None if vals[0] is None else np.concatenate(vals)

    dev = DeviceBatch(
        blocks=tuple(blocks),
        input_cache_slots=_cat("input_cache_slots"),
        input_streamed=_cat("input_streamed"),
        input_mask=_cat("input_mask"),
        labels=_cat("labels"),
        label_mask=_cat("label_mask"),
        # device-backend fields: fallback lanes concat like any row array;
        # the [1, 2] per-batch keys stack to [G, 2] (draw_lanes indexes the
        # key by group, counters by group-LOCAL row)
        input_fb_rows=_cat("input_fb_rows"),
        input_fb_w=_cat("input_fb_w"),
        sample_key=_cat("sample_key"))
    home = np.array([mb.local_shard if mb.local_shard is not None else -1
                     for mb in mbs], np.int32)
    out = MiniBatch(
        device=dev,
        input_node_ids=np.concatenate([mb.input_node_ids for mb in mbs]),
        num_input=sum(mb.num_input for mb in mbs),
        num_cached=sum(mb.num_cached for mb in mbs),
        bytes_streamed=sum(mb.bytes_streamed for mb in mbs),
        num_isolated=sum(mb.num_isolated for mb in mbs),
        cache_gen=mbs[0].cache_gen)
    return out, home


class GNSEngine:
    """The wired pipeline for one :class:`EngineConfig` (module docstring)."""

    def __init__(self, cfg: EngineConfig, *, dataset=None, mesh=None,
                 model_cfg: Optional[graphsage.SageConfig] = None,
                 cache_shard_axis: Optional[str] = None):
        """``dataset`` / ``mesh`` / ``model_cfg`` override the declarative
        sub-configs with concrete objects (the GNNTrainer shim's path)."""
        self.cfg = cfg
        if dataset is None:
            from repro.graph.datasets import get_dataset
            dataset = get_dataset(cfg.data.name, scale=cfg.data.scale,
                                  seed=cfg.data.seed)
        self.ds = dataset
        if mesh is None and cfg.mesh is not None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(cfg.mesh.data, cfg.mesh.model)
        self.mesh = mesh
        self.seed = cfg.seed
        self.scfg = cfg.sampler_config()
        if getattr(self.scfg, "backend", "host") == "device":
            assert cfg.sampler == "gns", (
                "backend='device' is the GNS device sampler — "
                f"sampler={cfg.sampler!r} has no device backend")
        mcfg = model_cfg
        if mcfg is None:
            m = cfg.model
            sk = getattr(m, "sample_kernel", "auto")
            if sk == "auto":
                # interpret-mode Pallas grids at bench shapes are
                # uncompilably slow off-TPU; the jnp reference is the
                # production path there (same bits — see sampling/rng.py)
                sk = "pallas" if jax.default_backend() == "tpu" else "reference"
            mcfg = graphsage.SageConfig(
                feat_dim=self.ds.feat_dim, hidden_dim=m.hidden_dim,
                num_classes=self.ds.num_classes,
                num_layers=len(self.scfg.fanouts),
                aggregate_impl=m.aggregate_impl, input_impl=m.input_impl,
                input_kernel=m.input_kernel, sample_kernel=sk)
        self.meter = TrafficMeter()
        # side-channel transfer meters: eval and one-shot-inference copies
        # must book their wall time SOMEWHERE (the meterlint pass pairs
        # every transfer with an accounting write) without skewing the
        # training breakdown the paper's tables are built from
        self.meter_eval = TrafficMeter()
        self.meter_infer = TrafficMeter()
        # books when each copy lands on the device, off the caller's thread
        self.fence = LandingFence()
        if cfg.sampler == "gns":
            # the facade owns all three feature tiers + the refresh lifecycle
            self.store = FeatureStore(
                self.ds.features, self.ds.graph, self.scfg.cache,
                train_idx=self.ds.train_idx, mesh=mesh,
                shard_axis=cache_shard_axis, meter=self.meter,
                importance_mode=self.scfg.importance_mode,
                build_adjacency=True, seed=cfg.seed)
        else:
            self.store = None
        if (self.store is not None and mesh is not None
                and mcfg.cache_shard_axis is None
                and (mcfg.input_impl == "fused"
                     or getattr(self.scfg, "backend", "host") == "device")):
            # fused input AND device sampling must psum over the SAME axis
            # the upload shards on
            mcfg = dataclasses.replace(mcfg,
                                       cache_shard_axis=self.store.shard_axis)
        # DP groups: one minibatch per group per step, collated (module doc)
        self.num_groups = dp_group_count(mesh, mcfg.cache_shard_axis)
        if self.num_groups > 1:
            from repro.core.minibatch import block_pad_sizes
            s0 = block_pad_sizes(self.scfg.batch_size, self.scfg.fanouts)[0][1]
            assert self.scfg.batch_size % self.num_groups == 0 \
                and s0 % self.num_groups == 0, (
                    f"batch_size={self.scfg.batch_size} (input pad {s0}) "
                    f"must divide the {self.num_groups} DP groups so eval "
                    f"batches can shard over the DP axes")
        self.mcfg = dataclasses.replace(mcfg, num_groups=self.num_groups)
        self.mcfg_eval = dataclasses.replace(self.mcfg, num_groups=1)
        self.sampler = make_sampler(cfg.sampler, self.ds.graph, self.scfg,
                                    self.ds.features, self.ds.labels,
                                    train_idx=self.ds.train_idx,
                                    store=self.store)
        self.params = graphsage.init_params(jax.random.PRNGKey(cfg.seed),
                                            self.mcfg)
        self.opt = AdamW(cfg.optim)
        self.opt_state = self.opt.init(self.params)
        self._dummy_cache = graphsage.dummy_cache_table(self.ds.feat_dim)

        # collation must keep layer-0 indices group-local ONLY when the
        # fused op will actually shard_map them (mesh + cache axis); a fused
        # model without a cache axis runs the op on the GLOBAL arrays, so
        # layer 0 needs the same per-group offsets as the upper layers
        self._collate_fused = (
            self.mcfg.input_impl == "fused" and mesh is not None
            and self.mcfg.cache_shard_axis in getattr(mesh, "axis_names", ()))
        self._train_step = jax.jit(make_train_step(self.mcfg, self.opt))
        mcfg_eval = self.mcfg_eval

        @jax.jit
        def eval_step(params, batch, cache_table, device_adj=None):
            return graphsage.loss_fn(params, batch, cache_table, mcfg_eval,
                                     None, device_adj)

        @jax.jit
        def logits_step(params, batch, cache_table, device_adj=None):
            return graphsage.forward(params, batch, cache_table, mcfg_eval,
                                     None, device_adj)

        self._eval_step = eval_step
        self._logits_step = logits_step
        # serving-shaped inference: one sampler per padded batch size
        # ("bucket"), all sharing THE store — so every bucket rides the same
        # live cache generation and feeds the same policy/placement signals,
        # while jax.jit keys the one logits step per bucket shape (a small
        # fixed set of compiled steps, never retraced in steady state)
        self._bucket_samplers: dict = {}
        # streaming ingest (repro.stream): wired eagerly when the config
        # declares it, lazily on the first ingest() otherwise
        self._stream = None
        if cfg.stream is not None and self.store is not None:
            self._init_stream(cfg.stream)

    # ------------------------------------------------------------------
    def _cache_table(self, mb: Optional[MiniBatch] = None):
        """The device table the batch's slots index into.

        Each MiniBatch carries the :class:`Generation` it was assembled
        against, so even when an async refresh swaps the live generation
        between sampling and stepping, the step reads the table matching the
        batch's slot map — a swap can never tear a batch.
        """
        gen = getattr(mb, "cache_gen", None) if mb is not None else None
        if gen is not None:
            return gen.table
        return self._dummy_cache

    def _put_batch(self, host_batch, meter: Optional[TrafficMeter] = None):
        """Host->device transfer with paired accounting.

        Every engine transfer funnels through here so each copy books to
        exactly one :class:`TrafficMeter` — training by default, the
        eval/infer side meters or a serving meter when passed: its bytes
        (``bytes_h2d``), the enqueue (``repro.train.put``) and, through
        :attr:`fence`, its landing (``repro.train.h2d``).  The meterlint
        pass enforces the pairing repo-wide (error tier).
        """
        m = meter if meter is not None else self.meter
        m.bytes_h2d += tree_nbytes(host_batch)
        t0 = time.perf_counter()
        with m.span("repro.train.put"):
            out = jax.device_put(host_batch)
        self.fence.land(out, t0, m)
        return out

    @staticmethod
    def _device_adj(mb: Optional[MiniBatch]):
        """The batch's pinned generation's device CSR (None = host backend).

        Resolved from ``cache_gen`` exactly like :meth:`_cache_table`, so a
        mid-swap batch draws against the SAME generation it gathers from.
        """
        gen = getattr(mb, "cache_gen", None) if mb is not None else None
        return getattr(gen, "device_adj", None) if gen is not None else None

    def run_batch(self, mb: MiniBatch,
                  home_shards: Optional[np.ndarray] = None
                  ) -> tuple[float, float]:
        """One optimizer step on a (possibly group-collated) minibatch."""
        if self.num_groups > 1:
            expect = self.num_groups * self.scfg.batch_size
            got = int(mb.device.labels.shape[0])
            assert got == expect, (
                f"DP={self.num_groups} steps consume GROUP-COLLATED batches "
                f"({expect} labels, got {got}): use fit(), or collate "
                f"{self.num_groups} per-group minibatches via collate_groups")
        if home_shards is None:
            ls = mb.local_shard if mb.local_shard is not None else -1
            home_shards = np.full(max(self.num_groups, 1), -1, np.int32)
            home_shards[0] = ls
        m = self.meter
        dev_batch = self._put_batch(mb.device)
        m.add_batch(mb.bytes_streamed)
        # no-op mesh scope when mesh is None
        with m.span("repro.train.dispatch"), shlib.use_mesh(self.mesh):
            self.params, self.opt_state, loss, acc = self._train_step(
                self.params, self.opt_state, dev_batch, self._cache_table(mb),
                jax.numpy.asarray(home_shards, jax.numpy.int32),
                self._device_adj(mb))
        with m.span("repro.train.sync"):
            return float(loss), float(acc)

    # ------------------------------------------------------------------
    def fit(self, epochs: int, max_batches: Optional[int] = None,
            prefetch: Optional[bool] = None,
            eval_every: Optional[int] = None,
            eval_batches: int = 8) -> TrainReport:
        """The §2.2 training loop; ``max_batches`` bounds STEPS per epoch
        (at DP > 1 each step consumes ``num_groups`` minibatches)."""
        if prefetch is None:
            prefetch = self.cfg.prefetch
        G = max(self.num_groups, 1)
        loader = EpochLoader(self.sampler, self.ds.train_idx, seed=self.seed,
                             max_batches=(max_batches * G
                                          if max_batches is not None else None),
                             dp_groups=G)
        report = TrainReport([], [], [], self.meter)
        n_inputs, n_cached, n_iso, n_b = 0, 0, 0, 0
        fused = self._collate_fused
        with self.meter.span("repro.train.fit"):
            for ep in range(epochs):
                t_ep = time.perf_counter()
                # epoch start (cache refresh happens in sampler.start_epoch)
                it = loader.epoch(ep)
                if prefetch:
                    it = Prefetcher(it, depth=2, meter=self.meter)
                ep_losses = []
                group_buf: list = []
                for mb in it:
                    group_buf.append(mb)
                    if len(group_buf) < G:
                        continue
                    step_mb, home = collate_groups(group_buf, fused)
                    group_buf = []
                    loss, _ = self.run_batch(step_mb, home)
                    ep_losses.append(loss)
                    n_inputs += step_mb.num_input
                    n_cached += step_mb.num_cached
                    n_iso += step_mb.num_isolated
                    n_b += 1
                report.epoch_times.append(time.perf_counter() - t_ep)
                report.losses.append(float(np.mean(ep_losses)) if ep_losses
                                     else float("nan"))
                if eval_every and (ep + 1) % eval_every == 0:
                    report.val_acc.append(
                        self.evaluate(self.ds.val_idx, eval_batches))
        if n_b:
            # per MINIBATCH, not per step: a DP>1 step consumes G of them,
            # and the paper's Table 3/4 comparisons are per-minibatch
            n_mb = n_b * G
            report.input_nodes_per_batch = n_inputs / n_mb
            report.cached_nodes_per_batch = n_cached / n_mb
            report.isolated_per_batch = n_iso / n_mb
        return report

    # ------------------------------------------------------------------
    def evaluate(self, idx: Optional[np.ndarray] = None,
                 num_batches: int = 8) -> float:
        """Micro-F1 (= accuracy for single-label tasks, as in the paper)."""
        if idx is None:
            idx = self.ds.val_idx
        b = self.scfg.batch_size
        idx = np.asarray(idx)
        if len(idx) < b:  # pad by wrapping; mask handles duplicates' weight
            idx = np.concatenate([idx, idx[: b - len(idx)]])
        rng = np.random.default_rng(1234)
        if isinstance(self.sampler, GNSSampler):
            self.sampler.ensure_cache(rng)
        if self.store is not None:
            self.store.record = False   # eval must not skew training metrics
                                        # or the adaptive policy's miss EMA
        correct, total = 0.0, 0.0
        try:
            for i in range(num_batches):
                lo = (i * b) % (len(idx) - b + 1)
                targets = idx[lo:lo + b]
                mb = self.sampler.sample(targets, rng)
                with shlib.use_mesh(self.mesh):
                    _, acc = self._eval_step(
                        self.params,
                        self._put_batch(mb.device, meter=self.meter_eval),
                        self._cache_table(mb), self._device_adj(mb))
                correct += float(acc)
                total += 1.0
        finally:
            if self.store is not None:
                self.store.record = True
        return correct / max(total, 1.0)

    # ------------------------------------------------------------------
    # serving-shaped inference (the repro.serve subsystem's engine surface)
    # ------------------------------------------------------------------
    def _bucket_sampler(self, bucket: int):
        """A sampler whose padded shapes are sized for ``bucket`` targets.

        Separate instances per bucket (never ``self.sampler``): each bucket
        is a distinct set of static pad sizes, and a dedicated instance keeps
        the serving path off the training sampler's scratch state.  All
        bucket samplers share ``self.store``, so they resolve against the
        SAME live generation and feed the same adaptive-policy/placement
        traffic signals.
        """
        s = self._bucket_samplers.get(bucket)
        if s is None:
            scfg = dataclasses.replace(self.scfg, batch_size=int(bucket))
            s = make_sampler(self.cfg.sampler, self.ds.graph, scfg,
                             self.ds.features, self.ds.labels,
                             train_idx=self.ds.train_idx, store=self.store)
            self._bucket_samplers[bucket] = s
        return s

    def ensure_cache(self, rng: Optional[np.random.Generator] = None) -> None:
        """Cold-start the cache generation (no-op for storeless samplers)."""
        if isinstance(self.sampler, GNSSampler):
            self.sampler.ensure_cache(rng)

    def infer_prepare(self, node_ids: np.ndarray, bucket: Optional[int] = None,
                      rng: Optional[np.random.Generator] = None,
                      sampler=None) -> MiniBatch:
        """Sample one inference minibatch padded to ``bucket`` targets.

        The returned batch PINS the cache generation it was assembled
        against (``MiniBatch.cache_gen``), so :meth:`infer_compute` reads a
        matching slot-map/table pair even if an async refresh swaps the live
        generation in between — the serving loop's in-flight safety contract.
        Accounting follows the store's current mode (the server wraps this
        in ``FeatureStore.serving``; :meth:`infer` suspends it entirely).

        ``sampler`` overrides the per-bucket serving sampler (its pad sizes
        must match ``bucket``) — the one-shot :meth:`infer` passes the
        training sampler so it never duplicates the O(V) sampler scratch.
        """
        ids = np.asarray(node_ids, dtype=np.int64)
        if bucket is None:
            bucket = self.scfg.batch_size
        assert len(ids) <= bucket, (len(ids), bucket)
        if rng is None:
            rng = np.random.default_rng(4321)
        if sampler is None:
            sampler = self._bucket_sampler(bucket)
        else:
            assert sampler.cfg.batch_size == bucket, (
                sampler.cfg.batch_size, bucket)
        if isinstance(sampler, GNSSampler):
            if self.store.generation is None:
                self.ensure_cache(rng)
            sampler.adopt_generation()    # follow the live gen (monotonic)
        return sampler.sample(ids, rng)

    def infer_compute(self, mb: MiniBatch,
                      meter: Optional[TrafficMeter] = None) -> np.ndarray:
        """Run the compiled inference step on a prepared batch.

        Returns logits ``[bucket, classes]`` (padded rows included — slice
        the leading real rows off).  One jit cache entry per bucket shape:
        the device table is an UNTRACED operand resolved per batch from the
        batch's pinned generation, so generation swaps never retrace.

        ``meter`` receives the host->device copy time (serving callers pass
        their own so concurrent workers never race one meter; default is
        the engine's inference side meter).
        """
        with shlib.use_mesh(self.mesh):
            logits = self._logits_step(
                self.params,
                self._put_batch(mb.device,
                                meter=meter if meter is not None
                                else self.meter_infer),
                self._cache_table(mb), self._device_adj(mb))
        return np.asarray(logits)

    @property
    def infer_step(self):
        """The one compiled inference step (jit-cached per bucket shape)."""
        return self._logits_step

    def serve(self, serve_cfg=None):
        """A :class:`repro.serve.GNSServer` over this engine (not started).

        The default config goes through :meth:`EngineConfig.serve_config`,
        so the unified ``EngineConfig.refresh`` hint (when set) decides
        ``refresh_every`` for serving exactly as it decides the training
        path's cache period.
        """
        from repro.serve import GNSServer
        return GNSServer(self, serve_cfg if serve_cfg is not None
                         else self.cfg.serve_config())

    def serve_fabric(self, fabric_cfg=None, serve_cfg=None):
        """A :class:`repro.serve.ServeFabric` fleet over this engine (not
        started).  Defaults come from ``EngineConfig.serve.fabric`` (per
        :meth:`EngineConfig.serve_config`, so the unified refresh hint
        applies) — a bare ``FabricConfig()`` when unset."""
        from repro.serve import ServeFabric
        return ServeFabric(self, cfg=fabric_cfg, serve_cfg=serve_cfg)

    def infer(self, node_ids: np.ndarray) -> np.ndarray:
        """Mini-batch inference over arbitrary node ids.  [N, classes] f32.

        The one-shot entry point: reuses the LIVE cache generation (no
        refresh is triggered beyond the cold-start one), suspends all
        traffic/policy accounting, and leaves the training state untouched.
        For a request stream, use :meth:`serve` — the persistent loop
        micro-batches into size buckets and feeds the adaptive policy.
        """
        ids = np.asarray(node_ids, dtype=np.int64)
        b = self.scfg.batch_size
        rng = np.random.default_rng(4321)
        self.ensure_cache(rng)
        out = np.zeros((len(ids), self.mcfg.num_classes), np.float32)
        if self.store is not None:
            self.store.record = False
        try:
            for lo in range(0, len(ids), b):
                chunk = ids[lo:lo + b]
                targets = np.resize(chunk, b)    # wrap-pad the tail batch
                # one-shot path: reuse the TRAINING sampler (documented as
                # not concurrent with fit) — a bucket sampler here would
                # duplicate its O(V) scratch for nothing
                mb = self.infer_prepare(targets, bucket=b, rng=rng,
                                        sampler=self.sampler)
                out[lo:lo + len(chunk)] = self.infer_compute(mb)[:len(chunk)]
        finally:
            if self.store is not None:
                self.store.record = True
        return out

    # ------------------------------------------------------------------
    # streaming ingest (repro.stream)
    # ------------------------------------------------------------------
    def _init_stream(self, scfg=None):
        """Attach a :class:`repro.stream.DeltaBuffer` to the store."""
        from repro.gns.config import StreamConfig
        from repro.stream import DeltaBuffer
        assert self.store is not None, (
            "streaming ingest rides the GNS feature store's generation "
            f"machinery — sampler={self.cfg.sampler!r} has no store")
        if scfg is None:
            scfg = (self.cfg.stream if self.cfg.stream is not None
                    else StreamConfig())
        buf = DeltaBuffer(self.ds.graph.num_nodes, self.ds.feat_dim,
                          max_pending=scfg.max_pending)
        self.store.labels = self.ds.labels
        self.store.attach_stream(buf, scfg)
        self.store.add_merge_listener(self._on_merge)
        self._stream = buf
        return buf

    def _on_merge(self, store, batch) -> None:
        """Builder-thread merge callback: re-point the engine's dataset view
        at the post-merge host tiers (pure reference swaps — samplers adopt
        structure separately, at their own swap point)."""
        self.ds.graph = store.graph
        self.ds.features = store.features
        if store.labels is not None:
            self.ds.labels = store.labels

    @property
    def stream(self):
        """The delta staging buffer (created on first touch)."""
        return self._stream if self._stream is not None \
            else self._init_stream()

    @property
    def pending_deltas(self) -> int:
        """Staged mutations awaiting the next generation merge."""
        return self.store.pending_deltas() if self.store is not None else 0

    def ingest(self, src, dst, op: str = "insert") -> int:
        """Stage edge mutations for the next generation merge.

        Non-blocking and thread-safe (serving stays live); raises
        :class:`repro.serve.QueueFull` past ``stream.max_pending``.  The
        edges become visible to sampling/serving only when a generation
        built after the merge is adopted — in-flight batches replay
        bitwise-identically against their pinned pre-merge generation.
        Returns the first assigned sequence number.
        """
        buf = self.stream
        if op == "insert":
            return buf.add_edges(src, dst)
        assert op == "delete", f"op must be insert|delete, got {op!r}"
        return buf.delete_edges(src, dst)

    def ingest_nodes(self, features: np.ndarray,
                     labels: Optional[np.ndarray] = None) -> np.ndarray:
        """Stage new nodes (+feature rows); returns their assigned ids.

        Ids are allocated contiguously above the current id space, so
        staged edges may reference them immediately.
        """
        return self.stream.add_nodes(features, labels)

    def ingest_events(self, ev) -> int:
        """Stage one :class:`repro.data.temporal.EventBatch` (nodes first,
        then the edges that may reference them)."""
        buf = self.stream
        if ev.node_feats is not None and len(ev.node_feats):
            ids = buf.add_nodes(ev.node_feats, ev.node_labels)
            assert int(ids[0]) == ev.node_base, (
                "event batches must be ingested in stream order",
                int(ids[0]), ev.node_base)
        return buf.add_edges(ev.src, ev.dst)

    def save(self, directory, step: int = 0, *, keep: int = 3):
        """Checkpoint model + optimizer state AND the un-merged delta log.

        The streaming buffer's seq-stamped ops ride the checkpoint's ``aux``
        side-payload (variable shapes between saves), so a crash between an
        ingest and the next generation merge loses nothing: :meth:`restore`
        replays them with their original seqs and last-op-wins resolution
        makes the replay idempotent.
        """
        from repro import checkpoint as ckpt
        tree = {"params": self.params, "opt_state": self.opt_state}
        aux = {}
        extra: dict = {"seed": self.cfg.seed}
        if self._stream is not None:
            st = self._stream.state()
            extra["stream"] = {"next_node": int(st["next_node"]),
                               "next_seq": int(st["next_seq"])}
            aux = {f"stream/{k}": v for k, v in st.items()}
        return ckpt.save_checkpoint(directory, step, tree, extra=extra,
                                    keep=keep, aux=aux)

    def restore(self, directory, step: Optional[int] = None) -> int:
        """Resume from :meth:`save`: params/opt state plus the staged delta
        log (re-staged into this engine's buffer when the checkpoint carried
        one).  Returns the restored step."""
        from repro import checkpoint as ckpt
        tree_like = {"params": self.params, "opt_state": self.opt_state}
        tree, step, _extra = ckpt.load_checkpoint(directory, tree_like,
                                                  step=step)
        self.params, self.opt_state = tree["params"], tree["opt_state"]
        aux = ckpt.load_aux(directory, step)
        stream_state = {k.split("/", 1)[1]: v for k, v in aux.items()
                        if k.startswith("stream/")}
        if stream_state:
            self.stream.restore(stream_state)
        return step

    def merge_deltas(self):
        """Force a merge NOW: synchronous refresh (drains the buffer at the
        build boundary) + adoption by the training sampler.  The serving
        path instead lets the fabric watchdog kick an ASYNC refresh when
        ``store.stream_merge_due()`` — same machinery, no pause.
        """
        assert self.store is not None
        gen = self.store.refresh(version=self.store.version + 1)
        self.sampler.adopt_generation()
        return gen

    # ------------------------------------------------------------------
    def describe(self) -> dict:
        """Lowering/traffic report for THIS config (what dryrun_gnn prints).

        With a mesh: the full pod-scale record — compiled-step cost
        analysis, per-chip cache bytes, shard-aware upload bytes per
        generation, and the locality-placement cross-shard traffic
        simulation.  Without one: the host-side subset (no lowering).
        """
        from repro.gns.describe import describe_lowering, traffic_report
        if self.mesh is None:
            rec = traffic_report(
                num_nodes=self.ds.graph.num_nodes, feat_dim=self.ds.feat_dim,
                cache_frac=self.scfg.cache.fraction,
                batch=self.scfg.batch_size, fanouts=self.scfg.fanouts,
                n_shards=(self.store.n_shards if self.store else 1),
                meter=self.meter,
                backend=getattr(self.scfg, "backend", "host"))
        else:
            rec = describe_lowering(
                mesh=self.mesh, num_nodes=self.ds.graph.num_nodes,
                feat_dim=self.ds.feat_dim, num_classes=self.ds.num_classes,
                cache_frac=self.scfg.cache.fraction,
                batch=self.scfg.batch_size * max(self.num_groups, 1),
                fanouts=tuple(self.scfg.fanouts),
                hidden_dim=self.mcfg.hidden_dim,
                input_impl=self.mcfg.input_impl,
                backend=getattr(self.scfg, "backend", "host"),
                sample_kernel=getattr(self.mcfg, "sample_kernel", "reference"),
                optim=self.cfg.optim)
        if self._stream is not None and self.store is not None:
            # run-state fields are volatile by design — repro.gns.describe's
            # diff() excludes them by name, like meter/compile_s
            rec["stream"] = {
                "enabled": True,
                "max_pending": self.store.stream_cfg.max_pending,
                "incremental_placement":
                    self.store.stream_cfg.incremental_placement,
                "pending_deltas": self.store.pending_deltas(),
                "merges_applied": self.store.merges_applied,
                "rows_migrated": self.store.rows_migrated,
            }
        return rec
