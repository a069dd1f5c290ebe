"""Mixture-of-Experts layer with expert parallelism (deepseek-v2, arctic).

Dispatch is the capacity-based gather/scatter formulation (MaxText-style,
TPU-friendly — no [T, E, C] one-hot tensor):

  1. router scores [T, E] (f32), token-choice top-k gate values;
  2. per expert, ``top_k(C)`` over the token axis selects which tokens the
     expert processes (capacity C = ceil(T·k/E·cf)); tokens beyond capacity
     are dropped (standard capacity drops — gate mass renormalized);
  3. gather  x_e = x[idx_e]  -> [E, C, d]   (E sharded on 'model' = EP),
  4. expert FFN via stacked einsum  [E, C, d] x [E, d, f],
  5. scatter-add back with gate weights.

Under GSPMD the gather/scatter happen per data shard (token axis stays on
'data'/'pod'); the [E, ...] tensors shard on 'model', so the only cross-chip
traffic is the activation all-to-all XLA inserts around the expert einsums —
exactly the EP traffic the roofline analysis counts.

deepseek-v2 extras: 2 shared (always-on) experts + first layer dense.
arctic extra: a dense FFN residual in parallel with the routed experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoECfg
from repro.launch.sharding import constrain
from repro.models.common import activation, dense_init
from repro.models.ffn import ffn_forward, init_ffn


def init_moe(key, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(key, 6)
    scale = d ** -0.5

    def experts_w(k, din, dout):
        return (jax.random.normal(k, (m.num_experts, din, dout), jnp.float32)
                * scale).astype(dt)

    p = {
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32),
        "experts_w1": experts_w(ks[1], d, m.d_expert),
        "experts_w3": experts_w(ks[2], d, m.d_expert),
        "experts_w2": (jax.random.normal(ks[3], (m.num_experts, m.d_expert, d),
                                         jnp.float32) * m.d_expert ** -0.5).astype(dt),
    }
    if m.num_shared:
        p["shared"] = init_ffn(ks[4], d, m.d_expert * m.num_shared, True, dt)
    if m.dense_residual:
        p["dense"] = init_ffn(ks[5], d, cfg.d_ff, True, dt)
    return p


def _routed_experts(xt, router, w1, w3, w2, *, cfg: ArchConfig,
                    num_local_experts: int, expert_offset) -> jnp.ndarray:
    """Routed-expert computation over LOCAL tokens and LOCAL experts.

    xt [T_loc, d]; w1/w3 [E_loc, d, f]; w2 [E_loc, f, d].  Pure function —
    runs identically as the single-device path (E_loc = E, offset 0) and as
    the shard_map body (E_loc = E/tp, offset = rank*E_loc).
    """
    m = cfg.moe
    t, d = xt.shape
    scores = xt.astype(jnp.float32) @ router                    # [T_loc, E]
    probs = jax.nn.softmax(scores, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)         # [T_loc, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # affinity[t, e] = gate value if e in token t's top-k else 0  (scatter,
    # avoids a [T, k, E] one-hot intermediate)
    affinity = jnp.zeros((t, m.num_experts), jnp.float32)
    affinity = affinity.at[jnp.arange(t)[:, None], gate_idx].add(gate_vals)
    aff_loc = jax.lax.dynamic_slice(
        affinity, (0, expert_offset), (t, num_local_experts))   # [T_loc, E_loc]

    # per-(shard, expert) capacity selection — LOCAL top_k over T_loc tokens,
    # the standard distributed-MoE capacity semantics (EXPERIMENTS.md §Perf
    # iteration 0.e: a global [E, T] top_k all-gathered 1M tokens per layer)
    cap = int(max(1, round(t * m.top_k / m.num_experts * m.capacity_factor)))
    cap = min(cap, t)
    top_aff, top_idx = jax.lax.top_k(aff_loc.T, cap)            # [E_loc, C]
    x_e = jnp.take(xt, top_idx, axis=0)                         # [E_loc, C, d]

    act = activation(cfg.ffn_act)
    h = jnp.einsum("ecd,edf->ecf", x_e, w1)
    g = jnp.einsum("ecd,edf->ecf", x_e, w3)
    h = act(h) * g
    y_e = jnp.einsum("ecf,efd->ecd", h, w2)                     # [E_loc, C, d]
    # slots an expert filled with zero-affinity tokens (under-subscription)
    # carry weight 0 and vanish here.  Gate weights are f32; cast the product
    # back to the activation dtype or the f32 result promotes the whole
    # residual stream — doubling every downstream activation/grad/collective
    # (EXPERIMENTS.md §Perf deepseek iteration 1).
    y_e = (y_e.astype(jnp.float32) * top_aff[..., None]).astype(xt.dtype)

    out = jnp.zeros((t, d), xt.dtype)
    out = out.at[top_idx.reshape(-1)].add(y_e.reshape(-1, d))
    return out


def moe_forward(p: dict, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, S, d] -> [B, S, d].

    On a mesh: shard_map with tokens on ('pod','data') and experts on
    'model' — router + gating replicated per model rank (tiny), expert
    FFNs fully local, ONE psum over 'model' combines each token's top-k
    expert outputs.  No global [E, T] top_k, no token all-gathers.
    """
    from repro.launch.sharding import current_mesh

    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    mesh = current_mesh()
    tp = (mesh.shape["model"] if mesh is not None
          and "model" in mesh.axis_names else 1)
    if (mesh is None or tp == 1 or m.num_experts % tp != 0
            or t % _dp_size(mesh) != 0):
        out = _routed_experts(xt, p["router"], p["experts_w1"],
                              p["experts_w3"], p["experts_w2"], cfg=cfg,
                              num_local_experts=m.num_experts,
                              expert_offset=0)
    else:
        from jax.sharding import PartitionSpec as P
        e_loc = m.num_experts // tp
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp = dp if len(dp) > 1 else dp[0]

        def body(xt_loc, router, w1, w3, w2):
            from repro.models.common import grad_cast
            # d(xt_loc) is promoted to f32 by the f32 router/gating path and
            # would cross the shard_map transpose psum at double width;
            # grad_cast pins the outgoing cotangent to xt's dtype BEFORE the
            # psum (§Perf deepseek iteration 3).
            xt_loc = grad_cast(xt_loc)
            rank = jax.lax.axis_index("model")
            out = _routed_experts(xt_loc, router, w1, w3, w2, cfg=cfg,
                                  num_local_experts=e_loc,
                                  expert_offset=rank * e_loc)
            return jax.lax.psum(out, "model")   # combine top-k expert outputs

        out = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(P(dp, None), P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=P(dp, None),
        )(xt, p["router"], p["experts_w1"], p["experts_w3"], p["experts_w2"])

    out = constrain(out, "batch", None)
    if m.num_shared:
        out = out + ffn_forward(p["shared"], cfg.ffn_act, xt, gated=True)
    if m.dense_residual:
        out = out + ffn_forward(p["dense"], cfg.ffn_act, xt, gated=True)
    return out.reshape(b, s, d)


def _dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def moe_aux_loss(p: dict, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style f·P)."""
    m = cfg.moe
    xt = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ p["router"], axis=-1)
    _, gate_idx = jax.lax.top_k(probs, m.top_k)
    frac = jax.nn.one_hot(gate_idx, m.num_experts).sum((0, 1)) / gate_idx.size
    imp = probs.mean(0)
    return m.num_experts * jnp.sum(frac * imp)
