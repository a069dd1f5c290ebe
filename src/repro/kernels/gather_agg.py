"""Pallas TPU kernel: fused row-gather + weighted segment aggregation.

This is the GNS minibatch hot-spot (DESIGN.md §2): the padded-block layout
turns the GraphSAGE neighbor aggregation into

    out[b, :] = Σ_k  w[b, k] · feat[idx[b, k], :]

i.e. a gather of K rows per destination followed by a weighted reduction.
On GPU the paper relies on cuSPARSE-style SpMM; the TPU-native adaptation is
a *scalar-prefetch gather*: the neighbor indices are scalar-prefetched (SMEM)
and drive the BlockSpec ``index_map`` of the feature operand, so the Pallas
pipeline DMAs exactly the needed feature rows HBM→VMEM, double-buffered, one
row tile per grid step.  The weighted accumulation runs on the VPU while the
next row is in flight.

Grid: ``(rows, num_d_blocks, K)`` — K innermost so the output tile stays
resident in VMEM across the accumulation; the feature table itself never
materializes in VMEM (only gathered rows do), which is what makes a
device-cache table of 10⁵–10⁶ rows workable.

TPU layout rules shape the operands:

* A block's last two dims must be multiples of (8, 128) or span the array.
  One table row is therefore moved as a ``(1, block_d)`` tile of the
  ``[N, 1, D]`` view of the table (the middle dim spans the array), and the
  output is written through the same ``[B, 1, D]`` view.
* SMEM holds about 1 MiB, and the paper's layer-0 input has 176k·5 lanes.
  The index and weight lanes therefore ride scalar prefetch in chunks of
  :data:`SMEM_LANES` (``lane_chunks``): one ``pallas_call`` per chunk of
  destination rows, looped by ``lax.map``.  Lanes are flattened k-major
  (``lane = k·rows + b``) from the transposed ``[K, B]`` lanes: a
  row-major flatten of (or a gather into) a ``[B, K]`` array with a narrow
  K is a relayout the TPU compiler expands into very large code and
  minutes of compile time.

Memory/roofline: per output row this moves K·block_d·4B of features and
writes block_d·4B — arithmetic intensity ≈ 2 FLOPs/4 bytes; the kernel is
HBM-bandwidth-bound by construction, matching the paper's data-movement
framing.  Block sizes default to the full feature dim (≤ 2048 lanes ≈ 8 KB
per buffer), far under VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lanes per scalar-prefetched array per chunk: 128 KiB of SMEM each, so the
# cache-lookup kernel's three lane arrays stay well inside SMEM.
SMEM_LANES = 32768


def lane_chunks(bsz: int, num_k: int) -> tuple[int, int]:
    """(destination rows per chunk, number of chunks) for a [bsz, K] batch."""
    rows = max(1, min(bsz, SMEM_LANES // max(num_k, 1)))
    return rows, -(-bsz // rows)


def chunk_lanes(x_t: jax.Array, rows: int, chunks: int) -> jax.Array:
    """Lanes [K, B] (transposed) -> [chunks, K·rows], k-major within a
    chunk, zero-padded."""
    num_k, bsz = x_t.shape
    x_t = jnp.pad(x_t, ((0, 0), (0, rows * chunks - bsz)))
    return x_t.reshape(num_k, chunks, rows).transpose(1, 0, 2).reshape(
        chunks, num_k * rows)


def take_lanes(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for [B, K] lane indices into a 1-D table, gathered
    k-major (see the module docstring) and returned as [B, K]."""
    return jnp.take(table, idx.T, axis=0).T


def block_width(d: int, block_d: int) -> int:
    """Largest divisor of ``d`` not above the requested block."""
    block_d = min(block_d, d)
    while d % block_d:
        block_d -= 1
    return block_d


def _kernel(idx_ref, w_ref, feat_ref, out_ref):
    b = pl.program_id(0)
    k = pl.program_id(2)
    lane = k * pl.num_programs(0) + b           # k-major lane order

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # feat_ref holds the row tile of idx[b, k], DMA'd by the index_map
    # below; accumulate on the VPU.
    out_ref[...] += w_ref[lane] * feat_ref[...].astype(out_ref.dtype)


def gather_agg_pallas(feat: jax.Array, idx: jax.Array, w: jax.Array,
                      block_d: int = 2048, interpret: bool = False,
                      name: str = "gather_agg") -> jax.Array:
    """out[b] = sum_k w[b,k] * feat[idx[b,k]].

    Args:
      feat: [N, D] feature/cache table (f32 or bf16).
      idx:  [B, K] int32 row indices (padded lanes must carry w == 0).
      w:    [B, K] f32 weights.
      name: the kernel's name on the device (profiles, HLO).
    Returns [B, D] f32.
    """
    n, d = feat.shape
    bsz, num_k = idx.shape
    block_d = block_width(d, block_d)
    rows, chunks = lane_chunks(bsz, num_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # idx + w lanes ride in SMEM
        grid=(rows, d // block_d, num_k),
        in_specs=[
            # feature rows: gathered by the scalar-prefetched indices
            pl.BlockSpec((None, 1, block_d),
                         lambda b, db, k, idx_ref, w_ref:
                         (idx_ref[k * rows + b], 0, db)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_d),
                               lambda b, db, k, idx_ref, w_ref: (b, 0, db)),
    )
    call = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, d), jnp.float32),
        interpret=interpret,
        name=name,
    )
    feat3 = feat.reshape(n, 1, d)
    out = jax.lax.map(
        lambda lanes: call(lanes[0], lanes[1], feat3),
        (chunk_lanes(idx.astype(jnp.int32).T, rows, chunks),
         chunk_lanes(w.astype(jnp.float32).T, rows, chunks)))
    return out.reshape(rows * chunks, d)[:bsz]
