"""Plain replica of GNS sampling and training, shared by every
architecture's reference (``references/<name>.py``): the cache's inclusion
probabilities, the lane weights, the checks of the program's draws, the
device draw, the batch format, its row padding, Adam and the training loop,
in straightforward ``numpy`` and ``jax.numpy``.

It imports nothing of the program.  From a run it takes only the random
choices the program made — which nodes the cache holds, which neighbours
each lane drew, and each batch's draw key — and checks that each choice is
legal; degrees, inclusion probabilities and the GNS weights it computes
from the graph.

Formulas (Dong et al., KDD 2021, §3, with the Horvitz–Thompson inclusion
probability the program documents for eq. 12):

* cache distribution ``p_u = deg(u) / Σ deg``; inclusion ``p^C_u = 1 -
  exp(-λ p_u)`` with λ solving ``Σ_u (1 - exp(-λ p_u)) = |C|``;
* a lane drawn from the cache for destination ``v`` with ``n_c`` cached
  neighbours and fanout ``k``: ``w = 1 / (max(p^C_u · min(k, n_c)/n_c,
  1e-6) · max(deg v, 1))``;
* above the input layer, a row with ``n_c < k`` takes all its cached
  neighbours at ``w = 1/deg v`` and tops up with ``t`` uncached ones at
  ``w = (deg v - n_c) / (t · deg v)``;
* the optimizer is AdamW without decay.

``dtype`` computes everything in another precision: ``bfloat16`` is the
control, which has to fail the comparison.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# the GNS cache, as the reference sees it
# ---------------------------------------------------------------------------

def solve_lambda(p: np.ndarray, size: int) -> float:
    """λ with Σ(1 - exp(-λ p)) = size, by bisection to 1e-9 relative."""
    p = p[p > 0]
    lo = hi = float(size)
    while -np.expm1(-hi * p).sum() < size:
        hi *= 2.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if -np.expm1(-mid * p).sum() < size:
            lo = mid
        else:
            hi = mid
        if hi - lo <= 1e-9 * lo:
            break
    return 0.5 * (lo + hi)


class CacheView:
    """Degrees, inclusion probabilities and cached-neighbour counts for one
    cache membership, in float64."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 cache_ids: np.ndarray, size: int):
        self.indptr, self.indices = indptr, indices
        n = len(indptr) - 1
        self.deg = np.diff(indptr).astype(np.float64)
        p = self.deg / self.deg.sum()
        self.lam = solve_lambda(p, size)
        self.pc = -np.expm1(-self.lam * p)
        self.ids = np.asarray(cache_ids, dtype=np.int64)
        self.in_cache = np.zeros(n, dtype=bool)
        self.in_cache[self.ids] = True
        hit = self.in_cache[indices]
        c = np.concatenate([[0], np.cumsum(hit, dtype=np.int64)])
        self.n_c = (c[indptr[1:]] - c[indptr[:-1]]).astype(np.float64)
        # the induced cached-neighbour lists, in the graph's (ascending) order
        self.c_start = c[indptr[:-1]]
        self.c_idx = indices[hit]

    def is_edge(self, v: np.ndarray, u: np.ndarray) -> np.ndarray:
        """Vectorised binary search of ``u`` in each row ``v`` (rows are
        sorted)."""
        lo = self.indptr[v].copy()
        hi = self.indptr[v + 1].copy()
        while True:
            act = lo < hi
            if not act.any():
                break
            mid = (lo + hi) // 2
            less = np.zeros(len(v), dtype=bool)
            less[act] = self.indices[mid[act]] < u[act]
            lo = np.where(act & less, mid + 1, lo)
            hi = np.where(act & ~less, mid, hi)
        ok = lo < self.indptr[v + 1]
        ok[ok] = self.indices[lo[ok]] == u[ok]
        return ok

    def w_cache(self, v, u, k, dtype=np.float64):
        """GNS weight of a lane drawn from the cache (see module doc)."""
        f = lambda x: np.asarray(x, dtype=np.float64).astype(dtype)
        nc = np.maximum(f(self.n_c[v]), f(1.0))
        coeff = np.maximum(f(self.pc[u]) * (np.minimum(f(k), nc) / nc),
                           f(1e-6))
        return f(1.0) / (coeff * np.maximum(f(self.deg[v]), f(1.0)))


@dataclasses.dataclass
class LaneCheck:
    bad: int = 0                 # lanes or rows that break the sampler's rules
    weight_gap: float = 0.0      # max |w_prog - w_ref| / w_ref over lanes


def check_lanes(cv: CacheView, dst: np.ndarray, lane_nodes: np.ndarray,
                w_prog: np.ndarray, k: int, topup: bool,
                dtype=np.float64) -> tuple[LaneCheck, np.ndarray]:
    """Check one host-drawn block; return the check and the reference's own
    weights for its lanes (0 on dead lanes).

    ``dst`` [D] destination ids, ``lane_nodes`` [D, K] neighbour ids,
    ``w_prog`` [D, K] the program's weights (a lane is live where > 0).
    Rules: every live lane is an edge of ``dst``; a row holds no neighbour
    twice; cache-only rows (the input layer, or ``n_c >= k``) hold
    ``min(k, n_c)`` cached lanes and nothing else; a row with ``n_c < k``
    above the input layer holds all ``n_c`` cached neighbours and at most
    ``k - n_c`` uncached ones.
    """
    live = w_prog > 0
    d_rep = np.repeat(dst, live.sum(axis=1))
    u = lane_nodes[live]
    bad = int((~cv.is_edge(d_rep, u)).sum())
    srt = np.sort(np.where(live, lane_nodes, -1 - np.arange(lane_nodes.shape[1])),
                  axis=1)
    bad += int((srt[:, 1:] == srt[:, :-1]).any(axis=1).sum())
    nc = cv.n_c[dst]
    cached = live & cv.in_cache[lane_nodes]
    n_cached = cached.sum(axis=1)
    n_unc = (live & ~cached).sum(axis=1)
    cache_only = (~np.asarray(topup)) | (nc >= k)
    want = np.minimum(k, nc)
    bad += int((cache_only & ((n_cached != want) | (n_unc > 0))).sum())
    bad += int((~cache_only & ((n_cached != nc) | (n_unc > k - nc))).sum())

    w_ref = np.zeros(w_prog.shape, dtype=dtype)
    vv = np.broadcast_to(dst[:, None], lane_nodes.shape)
    w_ref[cached & cache_only[:, None]] = cv.w_cache(
        vv[cached & cache_only[:, None]], lane_nodes[cached & cache_only[:, None]],
        k, dtype)
    if topup:
        f = lambda x: np.asarray(x, dtype=np.float64).astype(dtype)
        cond = cached & ~cache_only[:, None]
        w_ref[cond] = f(1.0) / np.maximum(f(cv.deg[vv[cond]]), f(1.0))
        unc = live & ~cached
        t = np.maximum(n_unc, 1)[:, None]
        non_c = (cv.deg[dst] - nc)[:, None]
        val = f(non_c) / (f(t) * np.maximum(f(cv.deg[dst])[:, None], f(1.0)))
        w_ref[unc] = np.broadcast_to(val, unc.shape)[unc]
    ok = live & (w_ref != 0)
    gap = (np.abs(w_prog[ok].astype(np.float64) - w_ref[ok].astype(np.float64))
           / np.abs(w_ref[ok].astype(np.float64)))
    return LaneCheck(bad=bad, weight_gap=float(gap.max()) if gap.size else 0.0), w_ref


# ---------------------------------------------------------------------------
# the device draw (counter-based: murmur3 fmix32 chained over key and lane)
# ---------------------------------------------------------------------------

def _fmix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def draw_bits(key: np.ndarray, rows: np.ndarray, k: int) -> np.ndarray:
    """uint32 ``[len(rows), k]``: fmix32 chained from 0x9E3779B9 over
    (key_lo, key_hi, row, lane)."""
    with np.errstate(over="ignore"):
        h = np.full((len(rows), k), 0x9E3779B9, dtype=np.uint32)
        words = (np.uint32(key[0]), np.uint32(key[1]),
                 rows.astype(np.uint32)[:, None],
                 np.arange(k, dtype=np.uint32)[None, :])
        for w in words:
            h = _fmix(h ^ w)
    return h


def device_draw(cv: CacheView, dst: np.ndarray, rows: np.ndarray, key,
                k: int, dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
    """Lanes (node ids) and weights of the on-device draw for cached
    destinations ``dst`` sitting at batch rows ``rows``.  A row with
    ``n_c <= k`` takes its cached neighbours in ascending id order;
    otherwise lane ``j`` takes neighbour ``bits mod n_c``.  Dead lanes have
    weight 0."""
    bits = draw_bits(np.asarray(key), rows, k)
    nc = cv.n_c[dst].astype(np.int64)[:, None]
    lane = np.arange(k)[None, :]
    take_all = nc <= k
    off = np.where(take_all, np.minimum(lane, np.maximum(nc - 1, 0)),
                   (bits % np.maximum(nc, 1).astype(np.uint32)).astype(np.int64))
    live = (nc > 0) & np.where(take_all, lane < nc, True)
    flat = np.clip(cv.c_start[dst][:, None] + off, 0, len(cv.c_idx) - 1)
    nodes = np.where(live, cv.c_idx[flat], 0).astype(np.int64)
    w = np.zeros(nodes.shape, dtype=dtype)
    vv = np.broadcast_to(dst[:, None], nodes.shape)
    w[live] = cv.w_cache(vv[live], nodes[live], k, dtype)
    return nodes, w


# ---------------------------------------------------------------------------
# the batch format, the optimizer and the training loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Layer:
    """One layer on real rows only: the destination rows are the first
    ``num_dst`` source rows, and lanes index the source rows (weight 0 on
    dead lanes)."""
    num_dst: int
    idx: np.ndarray
    w: np.ndarray


@dataclasses.dataclass
class Batch:
    x: np.ndarray              # input-layer source rows: features, float32
    layers: list               # input layer first
    labels: np.ndarray         # [B] class of each target
    label_w: np.ndarray        # [B] 1 for targets the loss averages over


def bucket(n: int) -> int:
    return max(8, 1 << (int(n) - 1).bit_length())


def batch_args(batch: Batch):
    """``(x, lanes, num_dst, labels, label_w)``: device arrays of one batch,
    every row count padded to a power of two so that batches and runs share
    compiled programs.  ``lanes`` holds an ``(idx, w)`` pair per layer.
    Padded rows are never read by a real row's lanes and carry no loss
    weight."""
    def pad(a, n):
        out = np.zeros((n,) + a.shape[1:], dtype=a.dtype)
        out[:len(a)] = a
        return jnp.asarray(out)
    lanes, num_dst = [], []
    for lay in batch.layers:
        n = bucket(lay.num_dst)
        lanes.append((pad(np.maximum(lay.idx, 0), n), pad(lay.w, n)))
        num_dst.append(n)
    out = num_dst[-1]
    return (pad(np.asarray(batch.x), bucket(len(batch.x))), tuple(lanes),
            tuple(num_dst), pad(np.asarray(batch.labels), out),
            pad(np.asarray(batch.label_w), out))


@functools.partial(jax.jit, static_argnames=("t", "opt"))
def adam_step(params, grads, m, v, t: int, opt: tuple):
    """One AdamW step (no decay); ``opt`` is ``(lr, b1, b2, eps)``.  The
    hyper-parameters and bias corrections are Python floats, so the arrays
    keep their own dtype."""
    lr, b1, b2, eps = opt
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new_m = jax.tree_util.tree_map(lambda M, G: M * b1 + (1 - b1) * G, m, grads)
    new_v = jax.tree_util.tree_map(lambda V, G: V * b2 + (1 - b2) * G * G,
                                   v, grads)
    new_p = jax.tree_util.tree_map(
        lambda P, M, V: P - lr * (M / bc1) / (jnp.sqrt(V / bc2) + eps),
        params, new_m, new_v)
    return new_p, new_m, new_v


@functools.cache
def _value_and_grad(loss):
    # One compile per architecture and batch shape.
    return jax.jit(jax.value_and_grad(loss),
                   static_argnames=("num_dst", "dtype"))


def train(loss, params0, batches: list, opt: dict, dtype=jnp.float32
          ) -> dict:
    """Run Adam over ``batches`` from ``params0`` on an architecture's
    ``loss(params, x, lanes, labels, label_w, num_dst=, dtype=)``, which
    takes :func:`batch_args`' arrays.  Returns the step losses, the first
    gradient and the parameters after the last step, as float64 numpy."""
    grad = _value_and_grad(loss)
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    hp = (opt["lr"], opt["b1"], opt["b2"], opt["eps"])
    losses, grad0 = [], None
    for t, b in enumerate(batches, start=1):
        x, lanes, nd, labels, label_w = batch_args(b)
        val, g = grad(params, x, lanes, labels, label_w, num_dst=nd,
                      dtype=dtype)
        losses.append(float(val))
        if grad0 is None:
            grad0 = g
        params, m, v = adam_step(params, g, m, v, t=t, opt=hp)
    host = lambda tree: jax.tree_util.tree_map(
        lambda a: np.asarray(a, dtype=np.float64), tree)
    return {"losses": losses, "grad0": host(grad0), "params": host(params)}
