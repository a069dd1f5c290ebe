"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled Pallas kernels run natively; on CPU
(this container, CI) they run in ``interpret=True`` mode — same kernel body,
Python-evaluated — so every test exercises the real kernel logic.  Callers
can force the reference path with ``impl="reference"`` (the dry-run uses it:
interpret-mode Pallas cannot be lowered into an XLA-for-TPU HLO from a CPU
host, and the reference path gives XLA the fusion freedom the roofline
analysis measures).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.cache_lookup import cache_lookup_agg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gather_agg import block_width, gather_agg_pallas, take_lanes


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("impl", "block_d"))
def gather_agg(feat: jax.Array, idx: jax.Array, w: jax.Array,
               impl: str = "pallas", block_d: int = 512) -> jax.Array:
    """Fused gather + weighted aggregation (GNS hot-spot).  [B,D] f32."""
    if impl == "reference":
        return ref.gather_agg_ref(feat, idx, w)
    return gather_agg_pallas(feat, idx, w, block_d=block_d,
                             interpret=_interpret())


def _dp_spec(mesh, shard_axis):
    """(dp axes, batch PartitionSpec entry) for the fused op's shard_map.

    The batch operands ride whatever logical-batch axes the mesh has, minus
    the cache axis (a 1-D benchmark mesh sharded over its only axis leaves
    the batch replicated).  Uses ``sharding.batch_axes`` so the axis-role
    rule lives in one place.
    """
    from repro.launch.sharding import batch_axes

    dp = tuple(a for a in batch_axes(mesh) if a != shard_axis)
    return dp, (dp if len(dp) > 1 else (dp[0] if dp else None))


def _fused_forward(cache_table, streamed, slots, idx, w, local_shards,
                   impl, block_d, mesh, shard_axis, local_shard=None,
                   dynamic=False):
    """Forward of the fused input op; shard_map over the cache axis if given.

    Sharded contract (the production regime): the table is row-partitioned
    into contiguous shards over ``shard_axis``; batch operands ride the DP
    axes (each data-parallel group resolves its OWN minibatch, so inside the
    body ``idx``/``slots`` are group-local); each shard contributes the lanes
    it owns (misses ride shard 0's replicated streamed buffer) and the
    partials are psum-ed over the cache axis — see
    ``kernels.cache_lookup.shard_lane_weights`` for why the regrouped sum is
    exact.

    ``local_shard`` (static int) is the locality fast path: the host
    verified at batch assembly that EVERY hit slot lives on that shard
    (locality-aware placement, ``FeatureStore.assemble_input``), so the
    owner's ``claim_all`` partial is already the full result — the other
    shards skip the kernel entirely (``lax.cond``) and the finished rows are
    ppermute-broadcast from the owner instead of all-reduced.  Bitwise equal
    to the psum path whenever the host contract holds.

    ``local_shards`` + ``dynamic=True`` is the DEVICE-RESIDENT variant of the
    same fast path: a traced int32 vector carrying one home shard per DP
    group (-1 = no locality contract for that group's batch), sharded over
    the DP axes so each group's body instance reads its own scalar.  The
    owner test becomes a runtime branch — ``lax.cond`` skips the kernel on
    every non-owner shard and the owner runs the ``claim_all`` partial — so
    ONE compiled step serves batches with any mix of home shards (including
    none) without retracing, which is what makes the fast path usable at
    DP > 1 where each group's batch may be homed on a different shard.  The
    combine stays the single psum: with the non-owner partials skipped to
    exact zeros it reproduces the owner's rows bitwise (only +0.0 terms are
    added), while a psum-free broadcast would need the owner in the ppermute
    permutation — a *static* quantity — and collectives inside a
    data-dependent ``lax.cond`` deadlock multi-group meshes.
    """
    from repro.kernels.cache_lookup import cache_lookup_agg_shard_partial

    use_kernel = impl != "reference"
    if mesh is not None and shard_axis in mesh.axis_names:
        from jax.sharding import PartitionSpec as P

        n = mesh.shape[shard_axis]
        rows = cache_table.shape[0]
        assert rows % n == 0, (
            f"cache table rows {rows} must divide the cache axis "
            f"{shard_axis}={n} (pad via CacheConfig.shards / padded_rows)")
        rps = rows // n
        dp, bspec = _dp_spec(mesh, shard_axis)

        if dynamic and n > 1:
            from repro.kernels.cache_lookup import (shard_lane_weights,
                                                    shard_slot_map)

            def body(tbl, st, sl, ix, ww, lsv):
                shard = jax.lax.axis_index(shard_axis)
                ls = lsv[0]                  # this group's home shard or -1
                fast = ls >= 0
                lane_slots = take_lanes(sl.astype(jnp.int32), ix)
                # fast: claim-all weights (owner serves hits AND misses);
                # slow: the usual owner-per-lane masking, psum reassembles
                w_eff = jnp.where(fast, ww.astype(jnp.float32),
                                  shard_lane_weights(ww, lane_slots, shard,
                                                     rps))
                local_slots = shard_slot_map(sl, shard, rps)

                def _run(t, s_, sl_, ix_, we):
                    if not use_kernel:
                        return ref.cache_lookup_agg_ref(t, s_, sl_, ix_, we)
                    return cache_lookup_agg_pallas(t, s_, sl_, ix_, we,
                                                   block_d=block_d,
                                                   interpret=_interpret())

                def _skip(t, s_, sl_, ix_, we):
                    return jnp.zeros((ix_.shape[0], t.shape[1]), jnp.float32)

                part = jax.lax.cond(fast & (shard != ls), _skip, _run,
                                    tbl, st, local_slots, ix, w_eff)
                # single combine for both regimes: on fast batches every
                # non-owner term is an exact zero, so the psum returns the
                # owner partial bitwise and only the owner paid the kernel
                return jax.lax.psum(part, shard_axis)

            fn = jax.shard_map(
                body, mesh=mesh, check_vma=False,
                in_specs=(P(shard_axis, None), P(bspec, None), P(bspec),
                          P(bspec, None), P(bspec, None), P(bspec)),
                out_specs=P(bspec, None))
            return fn(cache_table, streamed, slots, idx, w, local_shards)

        if local_shard is not None and n > 1:
            ls = int(local_shard)
            assert 0 <= ls < n, (local_shard, n)

            def body(tbl, st, sl, ix, ww):
                shard = jax.lax.axis_index(shard_axis)

                def _owner(t, s_, sl_, ix_, ww_):
                    return cache_lookup_agg_shard_partial(
                        t, s_, sl_, ix_, ww_, ls, rps, block_d=block_d,
                        interpret=_interpret(), use_kernel=use_kernel,
                        claim_all=True)

                def _skip(t, s_, sl_, ix_, ww_):
                    return jnp.zeros((ix_.shape[0], t.shape[1]), jnp.float32)

                part = jax.lax.cond(shard == ls, _owner, _skip,
                                    tbl, st, sl, ix, ww)
                # broadcast the finished rows from the owner by recursive
                # doubling: round k sends from the 2^k devices that already
                # hold them (a static set — ppermute sources must be unique,
                # so one-to-all is built as a log2(n) tree).  Each device
                # receives the rows exactly once -> (n-1)·|out| total bytes,
                # half an all-reduce's, with no adds — the psum skip.
                j = (shard - ls) % n        # my distance from the owner
                out = part
                shift = 1
                while shift < n:
                    senders = min(shift, n - shift)
                    perm = [((ls + a) % n, (ls + a + shift) % n)
                            for a in range(senders)]
                    recv = jax.lax.ppermute(out, shard_axis, perm)
                    newly = (j >= shift) & (j < shift + senders)
                    out = jnp.where(newly, recv, out)
                    shift *= 2
                return out
        else:
            def body(tbl, st, sl, ix, ww):
                shard = jax.lax.axis_index(shard_axis)
                part = cache_lookup_agg_shard_partial(
                    tbl, st, sl, ix, ww, shard, rps, block_d=block_d,
                    interpret=_interpret(), use_kernel=use_kernel)
                return jax.lax.psum(part, shard_axis)

        fn = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(P(shard_axis, None), P(bspec, None), P(bspec),
                      P(bspec, None), P(bspec, None)),
            out_specs=P(bspec, None))
        return fn(cache_table, streamed, slots, idx, w)
    if not use_kernel:
        return ref.cache_lookup_agg_ref(cache_table, streamed, slots, idx, w)
    return cache_lookup_agg_pallas(cache_table, streamed, slots, idx, w,
                                   block_d=block_d, interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _fused(cache_table, streamed, slots, idx, w, local_shards, impl, block_d,
           mesh, shard_axis, local_shard, dynamic):
    return _fused_forward(cache_table, streamed, slots, idx, w, local_shards,
                          impl, block_d, mesh, shard_axis, local_shard,
                          dynamic)


def _fused_fwd(cache_table, streamed, slots, idx, w, local_shards, impl,
               block_d, mesh, shard_axis, local_shard, dynamic):
    out = _fused_forward(cache_table, streamed, slots, idx, w, local_shards,
                         impl, block_d, mesh, shard_axis, local_shard,
                         dynamic)
    return out, (cache_table, streamed, slots, idx, w, local_shards)


def _fused_bwd(impl, block_d, mesh, shard_axis, local_shard, dynamic, res, g):
    """Hand-written VJP in plain jnp: Pallas kernels carry no AD rules.

    ``local_shard`` (and the traced ``local_shards`` vector) is deliberately
    ignored: under the fast-path contract (every hit lane owned by that one
    shard) the generic owner-claims-its-lanes backward already scatters each
    gradient on exactly the right shard — hits land on the home shard
    because it owns them, misses are replicated as always — so forward-fast
    and forward-psum share one backward and cannot drift apart.

    The sharded path MUST mirror the forward's shard_map rather than run
    global-array math: inside the forward each DP group's ``idx``/``slots``
    are group-local, so a global ``take``/scatter would resolve group g>0's
    lanes against group 0's rows.  The backward therefore shard_maps with
    the same specs — each cache shard owns its lanes' table gradient
    (psum-ed over the DP axes, since every group writes the same table),
    streamed/weight gradients stay group-local, and the per-lane h0 needed
    for dw is psum-ed over the cache axis exactly like the forward output.
    """
    cache_table, streamed, slots, idx, w, local_shards = res
    f0 = jax.dtypes.float0
    zslots = np.zeros(slots.shape, f0)
    zidx = np.zeros(idx.shape, f0)
    zls = np.zeros(local_shards.shape, f0)

    if mesh is not None and shard_axis in mesh.axis_names:
        from jax.sharding import PartitionSpec as P

        n = mesh.shape[shard_axis]
        rps = cache_table.shape[0] // n
        dp, bspec = _dp_spec(mesh, shard_axis)

        def body(tbl, st, sl, ix, ww, gg):
            from repro.kernels.cache_lookup import (shard_lane_weights,
                                                    shard_slot_map)

            shard = jax.lax.axis_index(shard_axis)
            gg = gg.astype(jnp.float32)
            # lanes k-major ([k, b, ...], see kernels/gather_agg.py)
            ix_t = ix.T
            lane_slots = jnp.take(sl.astype(jnp.int32), ix_t, axis=0)
            # the lane-claim rule (owner for hits, shard 0 for misses) and
            # the local-row mapping come from the SAME helpers the forward
            # kernel uses — forward and backward cannot desync
            lane_local = shard_slot_map(lane_slots, shard, rps)
            own = lane_local >= 0
            miss = lane_slots < 0
            claim = shard_lane_weights(jnp.ones_like(lane_slots, jnp.float32),
                                       lane_slots, shard, rps)       # 0/1
            rows_own = jnp.take(tbl, jnp.maximum(lane_local, 0), axis=0)
            rows_miss = jnp.take(st, ix_t, axis=0)
            # each lane's h0 comes from exactly one shard (the claim mask) —
            # the psum below reassembles it, like the forward
            h0_part = jnp.where(own[..., None],
                                rows_own.astype(jnp.float32),
                                rows_miss.astype(jnp.float32)) * claim[..., None]
            dw = jax.lax.psum(jnp.einsum("bd,kbd->bk", gg, h0_part),
                              shard_axis).astype(ww.dtype)
            dlane = ww.astype(jnp.float32).T[..., None] * gg[None, :, :]
            dcache = jnp.zeros((rps, tbl.shape[1]), tbl.dtype).at[
                jnp.maximum(lane_local, 0)].add(
                jnp.where(own[..., None], dlane, 0.0).astype(tbl.dtype))
            if dp:
                dcache = jax.lax.psum(dcache, dp)
            # miss lanes are shard-independent: every shard computes the
            # identical (replicated-over-cache-axis) streamed gradient
            dstreamed = jnp.zeros(st.shape, st.dtype).at[ix_t].add(
                jnp.where(miss[..., None], dlane, 0.0).astype(st.dtype))
            return dcache, dstreamed, dw

        fn = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(P(shard_axis, None), P(bspec, None), P(bspec),
                      P(bspec, None), P(bspec, None), P(bspec, None)),
            out_specs=(P(shard_axis, None), P(bspec, None), P(bspec, None)))
        dcache, dstreamed, dw = fn(cache_table, streamed, slots, idx, w, g)
        return dcache, dstreamed, zslots, zidx, dw, zls

    g = g.astype(jnp.float32)
    idx_t = idx.T                                                   # [K, B]
    lane_slots = jnp.take(slots.astype(jnp.int32), idx_t, axis=0)
    hit = (lane_slots >= 0)[..., None]
    rows_hit = jnp.take(cache_table, jnp.clip(lane_slots, 0), axis=0)
    rows_miss = jnp.take(streamed, idx_t, axis=0)
    h0 = jnp.where(hit, rows_hit, rows_miss).astype(jnp.float32)    # [K, B, D]
    dw = jnp.einsum("bd,kbd->bk", g, h0).astype(w.dtype)
    dlane = w.astype(jnp.float32).T[..., None] * g[None, :, :]      # [K, B, D]
    dcache = jnp.zeros(cache_table.shape, cache_table.dtype).at[
        jnp.clip(lane_slots, 0)].add(
        jnp.where(hit, dlane, 0.0).astype(cache_table.dtype))
    dstreamed = jnp.zeros(streamed.shape, streamed.dtype).at[idx_t].add(
        jnp.where(hit, 0.0, dlane).astype(streamed.dtype))
    return dcache, dstreamed, zslots, zidx, dw, zls


_fused.defvjp(_fused_fwd, _fused_bwd)


def dp_group_count(mesh, shard_axis: Optional[str]) -> int:
    """Number of data-parallel groups the fused op's batch operands span.

    One rule for the op, the engine's collation and the dry-run's batch
    structs: the groups are the product of the mesh's batch axes minus the
    cache axis (1 without a mesh) — the length a ``local_shards`` home-shard
    vector must have.
    """
    if mesh is None:
        return 1
    dp, _ = _dp_spec(mesh, shard_axis)
    g = 1
    for a in dp:
        g *= mesh.shape[a]
    return g


@functools.partial(jax.jit,
                   static_argnames=("impl", "block_d", "mesh", "shard_axis",
                                    "local_shard"))
def cache_lookup_agg(cache_table: jax.Array, streamed: jax.Array,
                     slots: jax.Array, idx: jax.Array, w: jax.Array,
                     impl: str = "pallas", block_d: int = 512,
                     mesh=None, shard_axis: Optional[str] = None,
                     local_shard: Optional[int] = None,
                     local_shards=None) -> jax.Array:
    """Fused GNS input layer: cache/streamed select + gather-agg.  [B,D] f32.

    Differentiable (custom VJP) so the train step's backward flows into the
    cache table / streamed rows / weights.  Pass ``mesh`` + ``shard_axis``
    (both static) to run the shard-aware path: per-device kernel on the
    local table shard, psum over the cache axis.  ``local_shard`` (static;
    only meaningful with a mesh) switches to the psum-free local fast path —
    the caller must hold the all-hits-local contract established by
    ``FeatureStore.assemble_input`` (which is where the value comes from).

    ``local_shards`` is the TRACED variant of the same gate: an int32 vector
    with one home shard per DP group (-1 = psum path for that group),
    sharded over the DP axes inside the op.  Because it is a device operand
    rather than a static argument, one compiled step serves batches with any
    mix of home shards without retracing — the DP > 1 regime.  Mutually
    exclusive with ``local_shard`` (the static argument wins).
    """
    bd = block_width(cache_table.shape[1], block_d)
    if mesh is None or shard_axis not in getattr(mesh, "axis_names", ()):
        local_shard = None          # nothing to skip without a cache axis
        local_shards = None
    if local_shard is not None:
        local_shards = None         # static gate wins (legacy callers)
    dynamic = local_shards is not None
    if dynamic:
        groups = dp_group_count(mesh, shard_axis)
        local_shards = jnp.asarray(local_shards, jnp.int32).reshape(-1)
        assert local_shards.shape == (groups,), (
            f"local_shards must carry one home shard per DP group "
            f"({groups}), got shape {local_shards.shape}")
    else:
        local_shards = jnp.full((1,), -1, jnp.int32)   # placeholder operand
    return _fused(cache_table, streamed, slots.astype(jnp.int32),
                  idx.astype(jnp.int32), w, local_shards, impl, bd, mesh,
                  shard_axis, local_shard, dynamic)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "impl", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, impl: str = "pallas",
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Blocked attention; pads seq dims to block multiples and unpads."""
    if impl == "reference":
        return ref.mha_ref(q, k, v, causal=causal, window=window, scale=scale)
    sq, sk = q.shape[2], k.shape[2]
    bq = min(block_q, max(16, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(16, 1 << (sk - 1).bit_length()))
    qp = _pad_axis(q, 2, bq)
    kp = _pad_axis(k, 2, bk)
    vp = _pad_axis(v, 2, bk)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 scale=scale, block_q=bq, block_k=bk,
                                 kv_len=sk, q_offset=sk - sq,
                                 interpret=_interpret())
    return out[:, :, :sq, :]
