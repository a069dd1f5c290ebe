"""Pallas TPU kernel: fused cache-lookup + first-layer gather-aggregation.

The GNS input layer resolves every input row against the device cache and
immediately aggregates it into the first GraphSAGE layer:

    h0[r]    = slots[r] >= 0 ? cache_table[slots[r]] : streamed[r]
    out[b,:] = Σ_k  w[b, k] · h0[idx[b, k], :]

The seed did this in two XLA ops (a [S0, F] ``where``-assembled h0, then the
gather-aggregate), materializing the full padded input-layer feature matrix
in HBM.  This kernel fuses both: the *scalar-prefetched* ``idx`` and
pre-gathered per-lane ``slots[idx]`` arrays (both [B, K] — the full [S0]
slot map would blow SMEM at paper scale) drive the BlockSpec index maps of
BOTH source operands — per grid step the pipeline DMAs one (1, block_d)
row tile from the cache table at row ``max(slots[idx[b,k]], 0)`` and one from
the streamed buffer at row ``idx[b,k]``, and the VPU selects the live lane
and accumulates.  h0 never exists in memory.

Grid: ``(B, num_d_blocks, K)`` — K innermost so the output tile stays
resident in VMEM across the accumulation, exactly like ``gather_agg``.
Cost per output row: K·block_d·4B from each source stream (the dead lane's
DMA is the price of branch-free pipelining) vs. the unfused path's extra
S0·F·4B h0 round-trip through HBM; for the paper's shapes (S0 ≈ 176k per
batch vs B·K = 16k lanes) the fused path moves strictly fewer bytes.

**Sharded tables** (production mesh): when the cache table is row-partitioned
into contiguous shards over the mesh's cache axis, each device runs the SAME
kernel against its local shard with a shard-local view of the slot map:
global slots owned by the shard become local rows (``shard_slot_map``),
every other lane's weight is zeroed (``shard_lane_weights`` — misses are
contributed by shard 0 only, from the replicated streamed buffer), and the
per-shard partials are psum-ed over the cache axis.  The decomposition only
inserts zero terms and regroups the fixed-order sum, so integer-exact inputs
stay bitwise identical to the single-device kernel.

**Local fast path** (locality-aware placement, PR 3): when the host
established at batch-assembly time that EVERY hit slot of the batch lives on
one known shard (``FeatureStore.assemble_input`` returns ``local_shard``
after the placement solver co-located the group's hot rows), the cross-shard
psum is unnecessary: that shard's partial — the plain single-device kernel
on its local block with a shard-local slot map and UNMASKED lane weights
(``claim_all=True``: hits and misses alike are claimed by the local shard,
misses riding the replicated streamed buffer) — already *is* the full
result.  ``kernels.ops._fused_forward`` then runs the kernel only on the
owner shard (``lax.cond``) and broadcasts the finished rows with a one-to-
all ``ppermute`` instead of all-reducing zero partials from every shard.
The contract (all hits local) lives in the store; violating it silently
drops the non-local hit lanes' contributions, which is why only
``assemble_input`` may produce a ``local_shard``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_agg import (block_width, chunk_lanes, lane_chunks,
                                     take_lanes)


def _kernel(idx_ref, lane_slots_ref, w_ref, cache_ref, streamed_ref, out_ref):
    b = pl.program_id(0)
    k = pl.program_id(2)
    lane = k * pl.num_programs(0) + b           # k-major lane order

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hit = lane_slots_ref[lane] >= 0
    w = w_ref[lane]
    # both candidate tiles were DMA'd by the index maps; select on the VPU.
    # Accumulation order is fixed (K innermost, ascending) and matches the
    # sequential reference; XLA may contract the mul+add into an FMA, so
    # bitwise parity holds whenever the products are exactly representable
    # (the parity test uses integer-valued f32) and to ~1 ulp otherwise.
    val = jnp.where(hit, cache_ref[...], streamed_ref[...])
    out_ref[...] += w * val.astype(out_ref.dtype)


def cache_lookup_agg_pallas(cache_table: jax.Array, streamed: jax.Array,
                            slots: jax.Array, idx: jax.Array, w: jax.Array,
                            block_d: int = 2048,
                            interpret: bool = False) -> jax.Array:
    """out[b] = Σ_k w[b,k] · (slots[idx[b,k]] >= 0 ? cache[slots[idx[b,k]]]
                                                   : streamed[idx[b,k]]).

    Args:
      cache_table: [C, D] device cache tier (f32 or bf16).
      streamed:    [S0, D] host-gathered miss rows (0 where cached).
      slots:       [S0] int32 cache slot per input row, -1 = miss.
      idx:         [B, K] int32 input-row indices (padded lanes carry w == 0).
      w:           [B, K] f32 aggregation weights.
    Returns [B, D] f32.

    Row tiles, SMEM lane chunks and the k-major lane order follow
    ``kernels/gather_agg.py``.
    """
    c, d = cache_table.shape
    s0 = streamed.shape[0]
    assert streamed.shape[1] == d
    bsz, num_k = idx.shape
    block_d = block_width(d, block_d)
    rows, chunks = lane_chunks(bsz, num_k)

    # Pre-gather the per-lane slots on the XLA side: SMEM then holds only
    # lane arrays, never the full [S0] slot map (~700 KB at the paper's
    # 176k-row input layer, beyond TPU SMEM).  Gathered as [K, B]: see
    # ``chunk_lanes``.
    idx_t = idx.astype(jnp.int32).T
    slots_t = jnp.take(slots.astype(jnp.int32), idx_t, axis=0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # idx + lane_slots + w ride in SMEM
        grid=(rows, d // block_d, num_k),
        in_specs=[
            # cache rows: slot of the gathered input row (clamped for misses —
            # the dead tile is discarded by the select)
            pl.BlockSpec((None, 1, block_d),
                         lambda b, db, k, idx_ref, sl_ref, w_ref:
                         (jnp.maximum(sl_ref[k * rows + b], 0), 0, db)),
            # streamed rows: the gathered input row itself
            pl.BlockSpec((None, 1, block_d),
                         lambda b, db, k, idx_ref, sl_ref, w_ref:
                         (idx_ref[k * rows + b], 0, db)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_d),
                               lambda b, db, k, idx_ref, sl_ref, w_ref:
                               (b, 0, db)),
    )
    call = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, d), jnp.float32),
        interpret=interpret,
    )
    cache3 = cache_table.reshape(c, 1, d)
    streamed3 = streamed.reshape(s0, 1, d)
    out = jax.lax.map(
        lambda lanes: call(*lanes, cache3, streamed3),
        (chunk_lanes(idx_t, rows, chunks),
         chunk_lanes(slots_t, rows, chunks),
         chunk_lanes(w.astype(jnp.float32).T, rows, chunks)))
    return out.reshape(rows * chunks, d)[:bsz]


# ---------------------------------------------------------------------------
# shard-local views (global slot -> (shard, local row), contiguous blocks)
# ---------------------------------------------------------------------------

def shard_slot_map(slots: jax.Array, shard, rows_per_shard: int) -> jax.Array:
    """Global slot map -> this shard's local rows; everything else -> -1.

    Shard ``s`` owns the contiguous global slots [s·rps, (s+1)·rps) — the
    same row blocks a ``NamedSharding(mesh, P(axis, None))`` places on device
    ``s`` along the cache axis.  ``shard`` may be a traced scalar
    (``jax.lax.axis_index`` inside shard_map) or a Python int (tests).
    """
    slots = slots.astype(jnp.int32)
    lo = shard * rows_per_shard
    owned = (slots >= lo) & (slots < lo + rows_per_shard)
    return jnp.where(owned, slots - lo, -1)


def shard_lane_weights(w: jax.Array, lane_slots: jax.Array, shard,
                       rows_per_shard: int) -> jax.Array:
    """Zero every lane this shard does not contribute.

    A lane is contributed by exactly one shard: cache hits by the shard
    owning the slot, misses (slot < 0, served from the replicated streamed
    buffer) by shard 0.  Summing the per-shard partials therefore recovers
    the single-device result — with only zero terms added, so integer-exact
    inputs reproduce it bitwise.
    """
    lo = shard * rows_per_shard
    owned = (lane_slots >= lo) & (lane_slots < lo + rows_per_shard)
    miss = lane_slots < 0
    contribute = owned | (miss & (shard == 0))
    return jnp.where(contribute, w.astype(jnp.float32), 0.0)


def cache_lookup_agg_shard_partial(local_table: jax.Array,
                                   streamed: jax.Array, slots: jax.Array,
                                   idx: jax.Array, w: jax.Array, shard,
                                   rows_per_shard: int,
                                   block_d: int = 2048,
                                   interpret: bool = False,
                                   use_kernel: bool = True,
                                   claim_all: bool = False) -> jax.Array:
    """One shard's partial of the fused lookup: kernel on the LOCAL table.

    Used as the ``shard_map`` body over the cache axis (``shard`` =
    ``axis_index``) and, shard-by-shard in a Python loop, by the parity
    tests that validate the slot mapping without a multi-device mesh.
    ``use_kernel=False`` runs the pure-jnp oracle instead of the Pallas
    kernel (the dry-run path: interpret-mode Pallas at pod-scale grids is
    not lowerable economically from a CPU host).

    ``claim_all=True`` is the LOCAL FAST PATH partial: every lane — hit and
    miss — is claimed by this shard (weights unmasked), so under the host
    contract that all hit slots live here, this single partial equals the
    full single-device kernel bitwise and no psum is needed.  Hit slots NOT
    on this shard map to -1 and would wrongly read the (zeroed) streamed
    row — the caller must hold the contract.
    """
    idx = idx.astype(jnp.int32)
    local_slots = shard_slot_map(slots, shard, rows_per_shard)
    if claim_all:
        w_eff = w.astype(jnp.float32)
    else:
        lane_slots = take_lanes(slots.astype(jnp.int32), idx)
        w_eff = shard_lane_weights(w, lane_slots, shard, rows_per_shard)
    if not use_kernel:
        from repro.kernels import ref
        return ref.cache_lookup_agg_ref(local_table, streamed, local_slots,
                                        idx, w_eff)
    return cache_lookup_agg_pallas(local_table, streamed, local_slots, idx,
                                   w_eff, block_d=block_d,
                                   interpret=interpret)
