"""Attention variants: MHA/GQA/MQA (+bias, +sliding window), MLA, cross-attn.

Layout conventions:
  activations x: [B, S, d_model]
  q/k/v heads:   [B, H, S, Dh]
  KV cache:      {"k": [B, Hkv, S_max, Dh], "v": ..., } updated at a traced
                 position; MLA caches the *compressed* c_kv + shared k_rope
                 (the whole point of MLA: 576 B/token/layer at any head count)

Both a reference jnp path (dry-run / CPU) and the Pallas flash kernel are
supported via ``cfg.attn_impl``; the reference path lets XLA fuse/shard
freely under GSPMD, the kernel path is the TPU-native execution plan.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ref as kref
from repro.launch.sharding import axis_size, constrain, constrain_hard
from repro.models.common import apply_rope, dense_init, rms_norm


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attn(key, cfg: ArchConfig, cross: bool = False) -> dict:
    if cfg.mla is not None and not cross:
        return _init_mla(key, cfg)
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    dh = cfg.head_dim_eff
    ks = jax.random.split(key, 4)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    p = {
        "wq": dense_init(ks[0], d, h * dh, dt),
        "wk": dense_init(ks[1], d, hkv * dh, dt),
        "wv": dense_init(ks[2], d, hkv * dh, dt),
        "wo": dense_init(ks[3], h * dh, d, dt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((h * dh,), dt)
        p["bk"] = jnp.zeros((hkv * dh,), dt)
        p["bv"] = jnp.zeros((hkv * dh,), dt)
    return p


def _init_mla(key, cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 7)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return {
        "q_down": dense_init(ks[0], d, m.q_lora, dt),
        "q_norm": jnp.zeros((m.q_lora,), jnp.float32),
        "q_up": dense_init(ks[1], m.q_lora, h * (m.qk_nope + m.qk_rope), dt),
        "kv_down": dense_init(ks[2], d, m.kv_lora + m.qk_rope, dt),
        "kv_norm": jnp.zeros((m.kv_lora,), jnp.float32),
        "k_up": dense_init(ks[3], m.kv_lora, h * m.qk_nope, dt),
        "v_up": dense_init(ks[4], m.kv_lora, h * m.v_head, dt),
        "wo": dense_init(ks[5], h * m.v_head, d, dt),
    }


# ---------------------------------------------------------------------------
# forward — GQA family
# ---------------------------------------------------------------------------

def _split_heads(x, n_heads, dh):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, dh).transpose(0, 2, 1, 3)


def _attend(q, k, v, *, causal, window, impl, kv_len=None, q_pos=None,
            kv_pos=None):
    if impl == "pallas" and kv_len is None and q_pos is None:
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window)
    return kref.mha_ref(q, k, v, causal=causal, window=window, kv_len=kv_len,
                        q_pos=q_pos, kv_pos=kv_pos)


def _batch_spec_axes(mesh) -> Optional[tuple]:
    from repro.launch.sharding import batch_axes
    axes = batch_axes(mesh)
    return axes if axes else None


def sharded_attention(q, k, v, *, causal, window, impl,
                      q_pos=None, kv_pos=None):
    """Multi-token attention with shard_map-pinned parallelism.

    GSPMD's free choice on the reference attention produced involuntary
    full-rematerialization copies of [B,H,S,S] scores (§Perf iteration 0-2).
    shard_map removes the choice: inside the mapped body everything is LOCAL.

      * heads mode (Hq and Hkv both divide 'model'): q/k/v head-sharded —
        attention contributes ZERO collectives fwd AND bwd;
      * seq mode (otherwise): q sharded over Sq on 'model', k/v replicated —
        forward local; backward psums only dk/dv ([B,Hkv,S,Dh], tiny next to
        the [B,H,S,S] tensors GSPMD all-reduced);
      * fallback to plain GSPMD when shapes don't divide (smoke tests).

    Masking is entirely positional: q_pos [Sq] / kv_pos [Sk] (defaults
    arange) drive causal + sliding-window + unwritten-slot masks inside the
    pure mha_ref oracle, so train, dense-cache prefill (kv_pos = -1 beyond
    kv_len) and SWA ring prefill all share this one wrapper.
    """
    from repro.launch.sharding import current_mesh

    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if q_pos is None:
        q_pos = jnp.arange(sq, dtype=jnp.int32)
    if kv_pos is None:
        kv_pos = jnp.arange(sk, dtype=jnp.int32)

    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return _attend(q, k, v, causal=causal, window=window, impl=impl,
                       q_pos=q_pos, kv_pos=kv_pos)
    tp = mesh.shape["model"]
    baxes = _batch_spec_axes(mesh)
    bsz = 1
    for a in (baxes or ()):
        bsz *= mesh.shape[a]
    if b % max(bsz, 1) != 0:
        baxes, bsz = None, 1
    bspec = (baxes if baxes and len(baxes) > 1 else
             (baxes[0] if baxes else None))

    from jax.sharding import PartitionSpec as P

    def body(qb, kb, vb, qp, kp):
        return kref.mha_ref(qb, kb, vb, causal=causal, window=window,
                            q_pos=qp, kv_pos=kp)

    if baxes and "model" in baxes:
        # pure-DP scope: the whole mesh is batch — attention fully local
        qspec = P(bspec, None, None, None)
        io = dict(in_specs=(qspec, qspec, qspec, P(None), P(None)),
                  out_specs=qspec)
    elif hq % tp == 0 and hkv % tp == 0:
        qspec = P(bspec, "model", None, None)
        io = dict(in_specs=(qspec, qspec, qspec, P(None), P(None)),
                  out_specs=qspec)
    elif sq % tp == 0:
        qspec = P(bspec, None, "model", None)
        kvspec = P(bspec, None, None, None)
        io = dict(in_specs=(qspec, kvspec, kvspec, P("model"), P(None)),
                  out_specs=qspec)
    else:
        return _attend(q, k, v, causal=causal, window=window, impl=impl,
                       q_pos=q_pos, kv_pos=kv_pos)

    fn = jax.shard_map(body, mesh=mesh, check_vma=False, **io)
    return fn(q, k, v, q_pos, kv_pos)


def attn_forward(p: dict, cfg: ArchConfig, x: jnp.ndarray,
                 positions: jnp.ndarray, *, causal: bool = True,
                 kv_cache: Optional[dict] = None,
                 cache_pos: Optional[jnp.ndarray] = None,
                 cross_kv: Optional[tuple] = None):
    """Returns (out [B,S,d], new_kv_cache | None).

    Train/prefill: kv_cache None.  Decode: kv_cache holds [B,Hkv,S_max,Dh];
    the S new tokens are written at ``cache_pos`` and attention runs over the
    cache with dynamic kv_len = cache_pos + S.
    """
    if cfg.mla is not None and cross_kv is None:
        return mla_forward(p, cfg, x, positions, causal=causal,
                           kv_cache=kv_cache, cache_pos=cache_pos)
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_eff
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = _split_heads(q, h, dh)

    if cross_kv is not None:
        k, v = cross_kv                            # precomputed encoder K/V
        if x.shape[1] > 1:
            out = sharded_attention(q, k, v, causal=False, window=None,
                                    impl=cfg.attn_impl)
        else:
            out = _attend(q, k, v, causal=False, window=None,
                          impl=cfg.attn_impl)
        b, s = x.shape[:2]
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
        return out @ p["wo"], None

    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = _split_heads(k, hkv, dh)
    v = _split_heads(v, hkv, dh)
    q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
    k = apply_rope(k, positions[:, None, :], cfg.rope_theta)

    new_cache = None
    kv_len = None
    q_pos = kv_pos = None
    if kv_cache is not None:
        s_new = x.shape[1]
        if "slot_pos" in kv_cache:
            # SWA ring buffer: window-sized cache, slots addressed mod window,
            # per-slot absolute positions drive the mask (order-free).
            # Attention runs over [old ring contents ++ new tokens] so that a
            # multi-token prefill sees its own in-window keys even when they
            # will be evicted from the ring right after (write happens below).
            max_len = kv_cache["k"].shape[2]
            abs_pos = cache_pos + jnp.arange(s_new, dtype=jnp.int32)
            q_pos = abs_pos
            kv_pos = jnp.concatenate([kv_cache["slot_pos"], abs_pos])
            k_att = jnp.concatenate(
                [kv_cache["k"].astype(k.dtype), k], axis=2)
            v_att = jnp.concatenate(
                [kv_cache["v"].astype(v.dtype), v], axis=2)
            # ring write: keep only the last `window` new tokens
            kk, vv, wpos = k, v, abs_pos
            if s_new >= max_len:
                kk, vv = kk[:, :, -max_len:], vv[:, :, -max_len:]
                wpos = wpos[-max_len:]
            slots = wpos % max_len
            ck = kv_cache["k"].at[:, :, slots].set(kk.astype(kv_cache["k"].dtype))
            cv = kv_cache["v"].at[:, :, slots].set(vv.astype(kv_cache["v"].dtype))
            spos = kv_cache["slot_pos"].at[slots].set(wpos)
            new_cache = {"k": ck, "v": cv, "slot_pos": spos}
            k, v = k_att, v_att
        else:
            ck = jax.lax.dynamic_update_slice(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), (0, 0, cache_pos, 0))
            cv = jax.lax.dynamic_update_slice(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), (0, 0, cache_pos, 0))
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv
            kv_len = cache_pos + s_new
            if s_new > 1:            # cache prefill: positional mask form
                q_pos = cache_pos + jnp.arange(s_new, dtype=jnp.int32)
                sk = k.shape[2]
                idx = jnp.arange(sk, dtype=jnp.int32)
                kv_pos = jnp.where(idx < kv_len, idx, -1)
                kv_len = None

    if kv_cache is None or x.shape[1] > 1:
        # train / prefill (multi-token): shard_map-pinned parallel attention
        # (heads or seq mode — see sharded_attention; §Perf iterations 0-3)
        out = sharded_attention(q, k, v, causal=causal,
                                window=cfg.sliding_window,
                                impl=cfg.attn_impl, q_pos=q_pos, kv_pos=kv_pos)
    else:
        # single-token decode: batch/head sharding under GSPMD
        q = constrain(q, "batch", "model", None, None)
        k = constrain(k, "batch", "model", "seq", None)
        out = _attend(q, k, v, causal=causal, window=cfg.sliding_window,
                      impl=cfg.attn_impl, kv_len=kv_len, q_pos=q_pos,
                      kv_pos=kv_pos)
    b, s = x.shape[:2]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * dh)
    return out @ p["wo"], new_cache


def make_cross_kv(p: dict, cfg: ArchConfig, enc_out: jnp.ndarray):
    """Precompute encoder K/V for the decoder's cross-attention."""
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_eff
    k = _split_heads(enc_out @ p["wk"], hkv, dh)
    v = _split_heads(enc_out @ p["wv"], hkv, dh)
    return k, v


def cache_dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None,
                  ring: Optional[bool] = None):
    dtype = dtype or cache_dtype(cfg)
    ring = (cfg.sliding_window is not None) if ring is None else ring
    hkv, dh = cfg.num_kv_heads, cfg.head_dim_eff
    cache = {"k": jnp.zeros((batch, hkv, max_len, dh), dtype),
             "v": jnp.zeros((batch, hkv, max_len, dh), dtype)}
    if ring:
        cache["slot_pos"] = jnp.full((max_len,), -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2)
# ---------------------------------------------------------------------------

def mla_forward(p: dict, cfg: ArchConfig, x: jnp.ndarray,
                positions: jnp.ndarray, *, causal: bool = True,
                kv_cache: Optional[dict] = None,
                cache_pos: Optional[jnp.ndarray] = None):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads

    ql = rms_norm(x @ p["q_down"], p["q_norm"])
    q = (ql @ p["q_up"]).reshape(b, s, h, m.qk_nope + m.qk_rope).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, positions[:, None, :], cfg.rope_theta)

    kvd = x @ p["kv_down"]
    c_kv = rms_norm(kvd[..., :m.kv_lora], p["kv_norm"])       # [B,S,kv_lora]
    k_rope = apply_rope(kvd[..., None, m.kv_lora:].transpose(0, 2, 1, 3),
                        positions[:, None, :], cfg.rope_theta)  # [B,1,S,rope]

    new_cache = None
    if kv_cache is not None:
        c_all = jax.lax.dynamic_update_slice(
            kv_cache["c_kv"], c_kv.astype(kv_cache["c_kv"].dtype), (0, cache_pos, 0))
        r_all = jax.lax.dynamic_update_slice(
            kv_cache["k_rope"], k_rope[:, 0].astype(kv_cache["k_rope"].dtype),
            (0, cache_pos, 0))
        new_cache = {"c_kv": c_all, "k_rope": r_all}
        kv_len = cache_pos + s
        if s == 1:
            # single-token decode: absorbed projections, attention in the
            # compressed c_kv space (the MLA cache-size win)
            return _mla_absorbed_attend(p, cfg, q_nope, q_rope, c_all, r_all,
                                        kv_len, b, s), new_cache
        # multi-token PREFILL: expand-form over the written cache (absorbed
        # form would build [B,H,S,S] f32 logits without flash blocking).
        q_pos = cache_pos + jnp.arange(s, dtype=jnp.int32)
        sk = c_all.shape[1]
        idx = jnp.arange(sk, dtype=jnp.int32)
        kv_pos = jnp.where(idx < kv_len, idx, -1)
        c_src, r_src, s_kv = c_all, r_all[:, None], sk
    else:
        q_pos = kv_pos = None
        c_src, r_src, s_kv = c_kv, k_rope, s

    # train/prefill: expand keys/values per head (standard formulation)
    k_nope = (c_src @ p["k_up"]).reshape(b, s_kv, h, m.qk_nope).transpose(0, 2, 1, 3)
    v = (c_src @ p["v_up"]).reshape(b, s_kv, h, m.v_head).transpose(0, 2, 1, 3)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(r_src.astype(k_nope.dtype),
                                                  (b, h, s_kv, m.qk_rope))],
                        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.qk_nope + m.qk_rope) ** -0.5
    out = _mla_attend(qf, k, v, scale, causal, q_pos, kv_pos)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head)
    return out @ p["wo"], new_cache


def _mla_attend(qf, k, v, scale, causal, q_pos, kv_pos):
    """MLA expand-form attention: scale folded into q, then the shared
    sharded_attention wrapper (128 heads divide the model axis -> heads
    mode, zero attention collectives)."""
    dh = qf.shape[-1]
    qs = qf * (scale * dh ** 0.5)        # mha_ref rescales by dh^-0.5
    return sharded_attention(qs, k, v, causal=causal, window=None,
                             impl="reference", q_pos=q_pos, kv_pos=kv_pos)


def _mla_absorbed_attend(p, cfg, q_nope, q_rope, c_all, r_all, kv_len, b, s):
    """Decode path with absorbed projections (attention in c_kv space).

    k_up absorbed into q:  q_c = q_nope · W_kup  -> [B,H,S,kv_lora]
    v_up absorbed out:     ctx · W_vup per head.
    KV cache bytes/token = kv_lora + rope = 576 (bf16: 1152B) regardless of
    the 128 heads — this is what makes deepseek-v2 long_500k feasible.
    """
    m = cfg.mla
    h = cfg.num_heads
    w_kup = p["k_up"].reshape(m.kv_lora, h, m.qk_nope)
    q_c = jnp.einsum("bhsn,lhn->bhsl", q_nope.astype(jnp.float32),
                     w_kup.astype(jnp.float32))               # [B,H,S,kv_lora]
    s_kv = c_all.shape[1]
    logits = jnp.einsum("bhsl,btl->bhst", q_c, c_all.astype(jnp.float32))
    logits += jnp.einsum("bhsr,btr->bhst", q_rope.astype(jnp.float32),
                         r_all.astype(jnp.float32))
    logits *= (m.qk_nope + m.qk_rope) ** -0.5
    t_idx = jnp.arange(s_kv)[None, None, None, :]
    q_idx = (kv_len - s) + jnp.arange(s)[None, None, :, None]
    mask = t_idx <= q_idx
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhst,btl->bhsl", probs, c_all.astype(jnp.float32))
    w_vup = p["v_up"].reshape(m.kv_lora, h, m.v_head)
    out = jnp.einsum("bhsl,lhv->bhsv", ctx, w_vup.astype(jnp.float32))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head).astype(q_nope.dtype)
    return out @ p["wo"]


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cache_dtype(cfg)
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora), dtype),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope), dtype)}
