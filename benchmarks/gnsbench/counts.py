"""Operations and bytes, counted from shapes.

These functions are the benchmark's yardstick for utilisation and roofline
shares: they count what the algorithm needs, from the shapes of the batch
the program shipped, never from the program's own estimates.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def tree_nbytes(tree) -> int:
    """Bytes of every array leaf of a pytree (what one host-to-device copy
    of it moves)."""
    import jax
    return int(sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(
        tree) if x is not None))


def sage_train_flops(real_dst: list, fanouts: list, feat_dim: int,
                     hidden: int, classes: int) -> float:
    """FLOPs the forward and backward passes of one GraphSAGE step require.

    ``real_dst[l]`` is the number of real (unpadded) destination rows of
    layer ``l`` (input layer first) and ``fanouts[l]`` its lanes per row.
    Layer ``l`` aggregates ``a = Σ_k w·h_src`` (2·D·K·F_in) and applies
    ``[h_dst ; a] @ W`` (2·D·2F_in·F_out).  The backward pass needs the
    weight gradient of every layer, and the input gradient and the
    aggregation's transpose only above the input layer (the features are
    not trained), so the input layer counts its linear part twice and its
    aggregation once, every other layer counts both three times.
    """
    total = 0.0
    f_in = feat_dim
    n = len(fanouts)
    for li, (d, k) in enumerate(zip(real_dst, fanouts)):
        f_out = classes if li == n - 1 else hidden
        agg = 2.0 * d * k * f_in
        lin = 2.0 * d * 2 * f_in * f_out
        total += (agg + 2 * lin) if li == 0 else 3 * (agg + lin)
        f_in = f_out
    return total


def gather_cost(rows: int, lanes: int, feat_dim: int) -> tuple[float, float]:
    """``(flops, bytes)`` of ``out[b] = Σ_k w[b,k]·table[idx[b,k]]`` over a
    ``[rows, lanes]`` lane block: one multiply-add per lane and feature; each
    lane's row read once, each output row written once, and an int32 index
    and an f32 weight read per lane."""
    flops = 2.0 * rows * lanes * feat_dim
    nbytes = (rows * lanes * feat_dim * F32 + rows * feat_dim * F32
              + rows * lanes * 2 * F32)
    return flops, float(nbytes)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
