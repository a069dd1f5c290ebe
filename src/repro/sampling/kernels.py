"""Device-resident GNS layer-0 sampling: fused draw → slot lookup → gather.

The host GNS input layer (``GNSSampler._sample_layer(allow_topup=False)``)
does three things per destination node: draw up to ``k`` cached neighbors,
compute the eq. (10)–(12) importance weights, and emit lanes the feature
gather consumes.  This module does the same ON DEVICE against the
generation's :class:`~repro.sampling.adjacency.DeviceCacheAdj`:

* :func:`draw_lanes` — the candidate draw + weight computation in plain jnp
  (counter-based stateless RNG, ``rng.mix32``): per destination row, if the
  row has ``n_c <= k`` cached neighbors it takes ALL of them (the host
  sampler's take-all regime — lanes beyond ``n_c`` are dead); otherwise it
  makes ``k`` uniform draws WITH replacement (``bits mod n_c``).  Both
  regimes weight lanes ``w = 1/(p^C_u · min(k, n_c)/n_c · deg(v))`` — the
  exact host formula — so the conditional estimator
  ``E[Σ w·f | cache] = Σ_{u∈N_C(v)} f_u / (p^C_u · deg(v))`` is identical
  to the host sampler's (per-lane marginals match; the joint differs by
  with- vs without-replacement, a documented approximation whose modulo
  bias is < n_c/2³² and whose unbiasedness is property-tested).
* :func:`slot_gather_agg_pallas` — the Pallas gather-aggregate
  (``kernels/gather_agg.py``) over the drawn table rows, dead lanes masked.
* :func:`gns_sample_agg` — the jitted entry the model's layer 0 calls:
  draw, merge host-fallback lanes (destination rows NOT in the cache are
  sampled by the host — ``top-up misses fall back to the host path``), and
  dispatch the gather to the Pallas kernel, the jnp reference, or the
  shard_map-over-cache-axis path (draw stays GLOBAL — the adjacency is
  replicated and tiny next to the feature table; only the feature gather
  runs per-shard + psum, mirroring ``kernels.ops._fused_forward``).

The draw itself stays jnp rather than living inside the Pallas body: it is
a handful of int ops per lane that XLA fuses into the surrounding step for
free, while the gather is the bandwidth-bound part that needs the kernel —
the same split (lane math XLA-side, row DMA Pallas-side) the fused
cache-lookup kernel documents for its SMEM budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.gather_agg import gather_agg_pallas
from repro.sampling.adjacency import DeviceCacheAdj
from repro.sampling.ref import slot_gather_agg_ref
from repro.sampling.rng import mix32


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# candidate draw + importance weights (eq. 10-12 on device rows)
# ---------------------------------------------------------------------------

def draw_lanes(adj: DeviceCacheAdj, dst_rows: jax.Array, keys: jax.Array,
               k: int, num_groups: int = 1
               ) -> tuple[jax.Array, jax.Array]:
    """Per-destination cached-neighbor draw with importance weights.

    Args:
      adj: the generation's device CSR.
      dst_rows: int32 [B] device-table row per destination (-1 = not cached
        or padding — those rows draw nothing here; the host fallback covers
        real uncached destinations).
      keys: uint32 [num_groups, 2] per-batch RNG key (one per DP group).
      k: the input-layer fanout (static).
      num_groups: DP groups collated into the batch (static); row ``r``'s
        counter is its GROUP-LOCAL index so each group's draw matches the
        same batch sampled ungrouped.

    Returns ``(lane_rows, lane_w)`` of shape [B, k]: device-table rows
    (-1 = dead lane) and f32 weights (0 on dead lanes).
    """
    B = dst_rows.shape[0]
    assert B % max(num_groups, 1) == 0, (B, num_groups)
    pad = B // max(num_groups, 1)
    dst = dst_rows.astype(jnp.int32)
    rowc = jnp.clip(dst, 0)
    start = jnp.take(adj.indptr, rowc)
    n_c = jnp.take(adj.indptr, rowc + 1) - start              # int32 [B]

    # lanes are built k-major ([k, B]) and transposed on return: gathers
    # with a narrow minor index dim cost the TPU compiler minutes at the
    # paper's 176k destination rows (see kernels/gather_agg.py)
    key_lo = jnp.repeat(keys[:, 0], pad, total_repeat_length=B)
    key_hi = jnp.repeat(keys[:, 1], pad, total_repeat_length=B)
    local = jnp.arange(B, dtype=jnp.uint32) % jnp.uint32(max(pad, 1))
    lane = jnp.arange(k, dtype=jnp.uint32)[:, None]
    bits = mix32(key_lo[None, :], key_hi[None, :],
                 local[None, :], lane)                        # [k, B] u32

    take_all = (n_c <= k)[None, :]
    ncs = jnp.maximum(n_c, 1)
    off_draw = (bits % ncs[None, :].astype(jnp.uint32)).astype(jnp.int32)
    off_seq = jnp.minimum(lane.astype(jnp.int32),
                          jnp.maximum(n_c - 1, 0)[None, :])
    off = jnp.where(take_all, off_seq, off_draw)
    flat = jnp.clip(start[None, :] + off, 0, adj.indices.shape[0] - 1)
    rows = jnp.take(adj.indices, flat)                        # [k, B]

    alive = ((dst >= 0) & (n_c > 0))[None, :]
    alive = alive & jnp.where(
        take_all, lane.astype(jnp.int32) < n_c[None, :], True)

    # the exact host weight: coeff = p^C_u * min(k, n_c)/n_c (clamped),
    # w = 1/(coeff * max(deg, 1))  — importance.importance_coefficients
    # with the hit probabilities precomputed per row at build time
    ncf = jnp.maximum(n_c.astype(jnp.float32), 1.0)[None, :]
    hitp = jnp.take(adj.hitp, jnp.clip(rows, 0))
    coeff = jnp.maximum(hitp * (jnp.minimum(float(k), ncf) / ncf), 1e-6)
    deg = jnp.maximum(jnp.take(adj.deg, rowc), 1.0)[None, :]
    w = jnp.where(alive, 1.0 / (coeff * deg), 0.0)
    rows = jnp.where(alive, rows, -1)
    return rows.T, w.T


# ---------------------------------------------------------------------------
# Pallas gather-aggregate over drawn table rows
# ---------------------------------------------------------------------------

def slot_gather_agg_pallas(cache_table: jax.Array, lane_rows: jax.Array,
                           w: jax.Array, block_d: int = 2048,
                           interpret: bool = False) -> jax.Array:
    """out[b] = Σ_k w[b,k] · cache_table[lane_rows[b,k]]  ([B, D] f32).

    The row gather of ``kernels/gather_agg.py`` with dead lanes
    (``lane_rows < 0``) masked XLA-side: their weight is 0 and their row is
    clamped to 0, so the DMA'd tile is discarded by the multiply.  The
    accumulation order (K innermost, ascending) matches
    ``slot_gather_agg_ref`` bitwise for exactly-representable products.
    """
    lr = lane_rows.astype(jnp.int32)
    w_eff = jnp.where(lr >= 0, w.astype(jnp.float32), 0.0)
    return gather_agg_pallas(cache_table, jnp.maximum(lr, 0), w_eff,
                             block_d=block_d, interpret=interpret,
                             name="gns_sample_gather")


# ---------------------------------------------------------------------------
# the fused entry point the model's layer 0 calls
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("impl", "block_d", "mesh",
                                             "shard_axis", "num_groups"))
def gns_sample_agg(adj: DeviceCacheAdj, cache_table: jax.Array,
                   dst_rows: jax.Array, fb_rows: jax.Array,
                   fb_w: jax.Array, keys: jax.Array, *,
                   impl: str = "reference", block_d: int = 512,
                   mesh=None, shard_axis: Optional[str] = None,
                   num_groups: int = 1) -> jax.Array:
    """Fused device GNS input layer: draw + weight + gather.  [B, D] f32.

    ``dst_rows`` is the batch's ``input_cache_slots`` vector (device rows of
    the destination nodes, -1 for uncached/padding); ``fb_rows``/``fb_w``
    carry the host-sampled fallback lanes for uncached real destinations
    (-1/0 elsewhere).  Cached rows draw on device; uncached rows use their
    fallback lanes verbatim — the miss path falls back to the host sampler.

    Not differentiable and deliberately so: the layer-0 aggregate has no
    parameter dependence, so the model wraps every operand in
    ``stop_gradient`` and the backward never enters this op (no custom VJP
    needed — contrast ``kernels.ops.cache_lookup_agg`` whose streamed rows
    sit on the grad path of its fused h_dst assembly).
    """
    k = fb_rows.shape[1]
    drawn, w = draw_lanes(adj, dst_rows, keys, k, num_groups=num_groups)
    uncached = (dst_rows.astype(jnp.int32) < 0)[:, None]
    lane_rows = jnp.where(uncached, fb_rows.astype(jnp.int32), drawn)
    lane_w = jnp.where(uncached, fb_w.astype(jnp.float32), w)

    if mesh is not None and shard_axis in getattr(mesh, "axis_names", ()):
        from jax.sharding import PartitionSpec as P

        from repro.kernels.cache_lookup import shard_slot_map
        from repro.kernels.ops import _dp_spec

        n = mesh.shape[shard_axis]
        rows_tot = cache_table.shape[0]
        assert rows_tot % n == 0, (rows_tot, n)
        rps = rows_tot // n
        dp, bspec = _dp_spec(mesh, shard_axis)

        def body(tbl, lr, lw):
            # each shard gathers only the lanes whose row it owns (the
            # elementwise shard_slot_map works on [B, K]); dead + foreign
            # lanes are zero-weighted and the partials psum — only zero
            # terms are added, so integer-exact inputs stay bitwise equal
            # to the single-device gather
            shard = jax.lax.axis_index(shard_axis)
            local = shard_slot_map(lr, shard, rps)
            w_eff = jnp.where(local >= 0, lw, 0.0)
            if impl == "reference":
                part = slot_gather_agg_ref(tbl, local, w_eff)
            else:
                part = slot_gather_agg_pallas(tbl, local, w_eff,
                                              block_d=block_d,
                                              interpret=_interpret())
            return jax.lax.psum(part, shard_axis)

        fn = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(P(shard_axis, None), P(bspec, None), P(bspec, None)),
            out_specs=P(bspec, None))
        return fn(cache_table, lane_rows, lane_w)

    if impl == "reference":
        return slot_gather_agg_ref(cache_table, lane_rows, lane_w)
    return slot_gather_agg_pallas(cache_table, lane_rows, lane_w,
                                  block_d=block_d, interpret=_interpret())
