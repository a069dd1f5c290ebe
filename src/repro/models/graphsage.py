"""GraphSAGE (mean aggregator) on static padded minibatch blocks.

The paper trains 3-layer GraphSAGE (§4.1).  The forward pass consumes the
:class:`repro.core.minibatch.DeviceBatch` format shared by all four samplers,
so a single compiled step serves NS / GNS / LADIES / LazyGCN — the importance
weighting of eq. (10) is entirely inside ``nbr_w``.

Layer ℓ (paper eq. 1/3 with mean aggregator + concat update):

    a_v = Σ_k  w[v,k] · h_src[idx[v,k]]          (weighted neighbor mean)
    h'_v = g(W · [h_v ; a_v] + b)

The aggregation is the compute hot-spot and maps to the Pallas
``gather_agg`` kernel (kernels/gather_agg.py); ``aggregate_impl`` picks the
kernel or the pure-jnp reference (CPU/dry-run default).

The input layer assembles features from the device cache (hits) and the
streamed rows (misses) — the data-movement core of the paper:

    h0 = where(slot >= 0, cache_table[slot], streamed)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.minibatch import DeviceBatch, LayerBlock


@dataclasses.dataclass(frozen=True)
class SageConfig:
    feat_dim: int
    hidden_dim: int = 256              # paper: 256/512
    num_classes: int = 32
    num_layers: int = 3
    aggregate_impl: str = "reference"  # "reference" | "pallas"
    input_impl: str = "where"          # "where" | "fused"  (fused = Pallas
                                       # cache-lookup + layer-0 gather in one
                                       # pass; h0 is never materialized)
    input_kernel: str = "pallas"       # fused-op backend: "pallas" | "reference"
                                       # (the pod dry-run lowers "reference" —
                                       # interpret-mode grids at paper scale
                                       # are uncompilable from a CPU host)
    sample_kernel: str = "reference"   # device-sampling gather backend:
                                       # "pallas" | "reference" (same split as
                                       # input_kernel; engine resolves "auto"
                                       # by jax.default_backend())
    cache_shard_axis: Optional[str] = None
                                       # mesh axis the cache table is row-
                                       # sharded over; with a mesh in scope
                                       # the fused op runs per-shard + psum
    num_groups: int = 1                # DP groups collated into one batch:
                                       # every device array is the group-
                                       # order concat of per-group arrays
                                       # (block pads stay PER-GROUP), so dst
                                       # selection gathers each group's
                                       # leading rows instead of slicing a
                                       # global prefix — the GNSEngine's
                                       # DP > 1 regime


def reference_aggregate(h_src: jnp.ndarray, nbr_idx: jnp.ndarray,
                        nbr_w: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle for the gather + weighted-mean aggregation.

    Gathers k-major (``[K, D, F]``): with the narrow K as the index array's
    minor dim, the TPU compiler spends minutes on the paper's layer-0
    gather (176k x 5 lanes) that it compiles in seconds this way round."""
    gathered = jnp.take(h_src, nbr_idx.T, axis=0)      # [K, D, F]
    return jnp.einsum("kd,kdf->df", nbr_w.T, gathered)


def _get_aggregate(impl: str) -> Callable:
    if impl == "pallas":
        from repro.kernels.ops import gather_agg
        return gather_agg
    return reference_aggregate


def init_params(rng: jax.Array, cfg: SageConfig) -> dict:
    keys = jax.random.split(rng, cfg.num_layers)
    params = {"layers": []}
    in_dim = cfg.feat_dim
    for i in range(cfg.num_layers):
        out_dim = cfg.num_classes if i == cfg.num_layers - 1 else cfg.hidden_dim
        scale = jnp.sqrt(2.0 / (2 * in_dim))
        w = jax.random.normal(keys[i], (2 * in_dim, out_dim), jnp.float32) * scale
        b = jnp.zeros((out_dim,), jnp.float32)
        params["layers"].append({"w": w, "b": b})
        in_dim = out_dim
    return params


def assemble_input(batch: DeviceBatch, cache_table: jnp.ndarray,
                   prefix: Optional[int] = None,
                   rows: Optional[np.ndarray] = None) -> jnp.ndarray:
    """h0 from cache hits + streamed misses (the GNS data path).

    ``prefix`` statically truncates to the first N rows — the fused input
    path only needs the destination self-rows, not the full padded h0.
    ``rows`` (a static index vector) generalizes the prefix to non-leading
    selections: a group-collated batch's destination self-rows are each
    group's leading block, not a global prefix (see ``_dst_rows``).
    """
    slots = batch.input_cache_slots
    streamed = batch.input_streamed
    mask = batch.input_mask
    if rows is not None:
        rows = jnp.asarray(rows, jnp.int32)
        slots = jnp.take(slots, rows, axis=0)
        streamed = jnp.take(streamed, rows, axis=0)
        mask = jnp.take(mask, rows, axis=0)
    elif prefix is not None:
        slots, streamed, mask = slots[:prefix], streamed[:prefix], mask[:prefix]
    hit = slots >= 0
    cached_rows = jnp.take(cache_table, jnp.clip(slots, 0), axis=0)
    h0 = jnp.where(hit[:, None], cached_rows, streamed)
    return h0 * mask[:, None]


def _dst_rows(num_groups: int, blk: LayerBlock) -> Optional[np.ndarray]:
    """Global rows of the destination self-representations, group-collated.

    With one group the destinations are the array's leading ``num_dst`` rows
    (slice, no gather).  A collated batch concatenates G groups' per-group-
    padded arrays, so group g's destinations live at ``g·num_src + [0,
    num_dst)`` of the layer's global source array — a static index vector.
    """
    if num_groups <= 1:
        return None
    return np.concatenate([g * blk.num_src + np.arange(blk.num_dst)
                           for g in range(num_groups)]).astype(np.int32)


def forward(params: dict, batch: DeviceBatch, cache_table: jnp.ndarray,
            cfg: SageConfig, local_shard=None, device_adj=None) -> jnp.ndarray:
    """Returns logits [B_padded, num_classes].

    ``local_shard`` forwards the locality fast-path gate to the fused input
    op: a static int when the batch assembler established that every cache
    hit of THIS batch resolves on that shard (see
    ``FeatureStore.assemble_input``), or a TRACED int32 home-shard vector
    (one entry per DP group, -1 = no contract) — the device-resident form
    that lets one compiled step serve any mix of home shards (GNSEngine).

    ``device_adj`` (a :class:`repro.sampling.DeviceCacheAdj`, paired with a
    ``backend="device"`` batch carrying ``sample_key``) switches layer 0 to
    the on-device GNS draw: the neighbor aggregate comes straight from the
    fused draw→gather op and the batch ships NO layer-0 neighbor lanes.
    """
    agg = _get_aggregate(cfg.aggregate_impl)
    device = device_adj is not None and batch.sample_key is not None
    fused = cfg.input_impl == "fused" and not device
    h = None if (fused or device) else assemble_input(batch, cache_table)
    for i, (blk, layer) in enumerate(zip(batch.blocks, params["layers"])):
        dst_rows = _dst_rows(cfg.num_groups, blk)
        if i == 0 and device:
            # device-resident GNS input layer: draw + importance weights +
            # feature gather inside the step (repro.sampling.kernels).  The
            # aggregate has no parameter dependence — stop_gradient keeps
            # the backward out of the (forward-only) Pallas op entirely.
            from repro.launch.sharding import current_mesh
            from repro.sampling.kernels import gns_sample_agg
            mesh = current_mesh()
            axis = cfg.cache_shard_axis
            if mesh is None or axis not in getattr(mesh, "axis_names", ()):
                mesh = axis = None
            sg = jax.lax.stop_gradient
            a = gns_sample_agg(
                jax.tree_util.tree_map(sg, device_adj), sg(cache_table),
                sg(batch.input_cache_slots), sg(batch.input_fb_rows),
                sg(batch.input_fb_w), sg(batch.sample_key),
                impl=cfg.sample_kernel, mesh=mesh, shard_axis=axis,
                num_groups=cfg.num_groups)
            h_dst = assemble_input(batch, cache_table,
                                   prefix=blk.num_dst, rows=dst_rows)
        elif i == 0 and fused:
            # one Pallas pass: cache/streamed select + layer-0 gather-agg;
            # self rows come from a statically-sliced prefix assembly.  On a
            # mesh with the cache table row-sharded over cfg.cache_shard_axis
            # each device runs the kernel on its own shard (psum'd partials,
            # or the psum-free local fast path when the batch is fully local).
            from repro.kernels.ops import cache_lookup_agg
            from repro.launch.sharding import current_mesh
            mesh = current_mesh()
            axis = cfg.cache_shard_axis
            if mesh is None or axis not in getattr(mesh, "axis_names", ()):
                mesh = axis = None
            if local_shard is None or isinstance(local_shard,
                                                 (int, np.integer)):
                ls_static, ls_vec = local_shard, None
            else:                     # traced per-group home-shard vector
                ls_static, ls_vec = None, local_shard
            a = cache_lookup_agg(cache_table, batch.input_streamed,
                                 batch.input_cache_slots,
                                 blk.nbr_idx, blk.nbr_w,
                                 impl=cfg.input_kernel,
                                 mesh=mesh, shard_axis=axis,
                                 local_shard=ls_static,
                                 local_shards=ls_vec)
            h_dst = assemble_input(batch, cache_table,
                                   prefix=blk.num_dst, rows=dst_rows)
        else:
            h_dst = (h[: blk.num_dst] if dst_rows is None
                     else jnp.take(h, jnp.asarray(dst_rows), axis=0))
            a = agg(h, blk.nbr_idx, blk.nbr_w)
        z = jnp.concatenate([h_dst, a], axis=-1) @ layer["w"] + layer["b"]
        h = jax.nn.relu(z) if i < len(batch.blocks) - 1 else z
        h = h * blk.dst_mask[:, None]
    return h


def loss_fn(params: dict, batch: DeviceBatch, cache_table: jnp.ndarray,
            cfg: SageConfig, local_shard=None,
            device_adj=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    logits = forward(params, batch, cache_table, cfg, local_shard=local_shard,
                     device_adj=device_adj)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch.labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    denom = jnp.maximum(batch.label_mask.sum(), 1.0)
    loss = (nll * batch.label_mask).sum() / denom
    acc = ((jnp.argmax(logits, -1) == batch.labels) * batch.label_mask).sum() / denom
    return loss, acc


def dummy_cache_table(feat_dim: int) -> jnp.ndarray:
    """1-row zero cache for samplers without a device cache (NS/LADIES)."""
    return jnp.zeros((1, feat_dim), jnp.float32)
