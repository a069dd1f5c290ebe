"""Logical-axis sharding rules (DP / TP / EP / SP) with divisibility fallback.

Model code annotates activations/params with *logical* axes:

    x = constrain(x, "batch", "seq", None)      # activations
    spec = param_spec(path, shape)               # parameters (rule table)

and this module maps logical -> physical mesh axes:

    batch  -> ('pod', 'data')     data parallel (pods are extra DP)
    model  -> 'model'             tensor/expert parallel
    expert -> 'model'             MoE expert parallel (same axis as TP)
    seq    -> 'data'              sequence parallel (long-context decode only,
                                  applied when batch can't fill 'data')
    None   -> replicated

Divisibility fallback: a logical axis whose dimension does not divide by the
physical axis size is silently replicated (e.g. xlstm-125m has 4 heads on a
model=16 axis -> heads replicate, its 1536-wide inner dim still shards).
This is what makes ONE rule table serve architectures from 125M to 480B.

`current_mesh()` is a context set by the launcher / dry-run; with no mesh in
scope every constraint is a no-op, so smoke tests on 1 CPU device run the
exact same model code.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_LOGICAL_TO_PHYSICAL = {
    "batch": ("pod", "data"),
    "model": ("model",),
    "expert": ("model",),
    "seq": ("data",),
    "attn_sq": ("model",),     # seq-sharded attention (heads % tp != 0 path)
    "cache": ("model",),       # feature-store device-table rows (GNS cache
                               # sharding rides the TP axis — mesh.py §roles)
    "pod": ("pod",),
    "data": ("data",),
}

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def logical_table() -> dict:
    return {**_LOGICAL_TO_PHYSICAL, **getattr(_state, "overrides", {})}


@contextlib.contextmanager
def logical_overrides(**kw):
    """Remap logical axes for a scope (e.g. pure-DP: batch spans all axes)."""
    prev = getattr(_state, "overrides", {})
    _state.overrides = {**prev, **kw}
    try:
        yield
    finally:
        _state.overrides = prev


@contextlib.contextmanager
def arch_scope(cfg):
    """Per-arch distribution scope.  pure_dp (§Perf): the whole mesh is data
    parallelism (batch -> pod x data x model), TP/EP disabled, parameters
    ZeRO-3-sharded over everything (see param_sharding fsdp_axes)."""
    if getattr(cfg, "pure_dp", False):
        assert cfg.moe is None, "pure_dp is invalid for MoE archs (EP needs 'model')"
        with logical_overrides(batch=("pod", "data", "model"),
                               model=(), expert=(), attn_sq=(), seq=()):
            yield
    else:
        yield


def batch_axes(mesh: Mesh) -> tuple:
    """Mesh axes carrying the logical batch (override-aware)."""
    return tuple(a for a in logical_table()["batch"] if a in mesh.axis_names)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Thread-local mesh scope. ``constrain``/``sharding_for`` build explicit
    NamedShardings from it, so no jax-global ambient mesh is needed."""
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def _physical_axes(mesh: Mesh, logical: Optional[str], dim: int):
    """Resolve one logical axis -> tuple of mesh axes that divide `dim`."""
    if logical is None:
        return None
    axes = [a for a in logical_table().get(logical, ()) if a in mesh.axis_names]
    if not axes:
        return None
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    if dim % total != 0:
        # fallback: try a prefix of the axes, else replicate
        keep = []
        prod = 1
        for a in axes:
            if dim % (prod * mesh.shape[a]) == 0:
                keep.append(a)
                prod *= mesh.shape[a]
            else:
                break
        if not keep:
            return None
        return tuple(keep)
    return tuple(axes)


def spec_for(mesh: Mesh, logical_axes: Sequence[Optional[str]],
             shape: Sequence[int], *, unconstrained_fallback: bool = False) -> P:
    """Logical -> physical PartitionSpec.

    ``unconstrained_fallback=True`` (activation constraints): dims whose
    logical axis is None or fails divisibility become UNCONSTRAINED, letting
    GSPMD propagate from the (always shardable) weights.  A hard None here
    would mean "replicate", which forces an all-gather whenever a head count
    does not divide the axis (e.g. qwen2's 28 heads on model=16) — measured
    as a per-layer collective storm in EXPERIMENTS.md §Perf iteration 0.
    In/out shardings (in_shardings must be concrete) keep None = replicated.
    """
    assert len(logical_axes) == len(shape), (logical_axes, shape)
    used: set = set()
    parts = []
    fallback = P.UNCONSTRAINED if unconstrained_fallback else None
    for name, dim in zip(logical_axes, shape):
        ax = _physical_axes(mesh, name, dim)
        if ax is not None and any(a in used for a in ax):
            ax = None                       # each mesh axis used at most once
        if ax is not None:
            used.update(ax)
            parts.append(ax if len(ax) > 1 else ax[0])
        else:
            parts.append(fallback)
    return P(*parts)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(mesh, logical_axes, x.shape, unconstrained_fallback=True)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_hard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Like constrain, but None / failed axes mean REPLICATED (hard).

    Used where GSPMD free choice is known-bad: e.g. the seq-sharded attention
    path must keep dh and Sk unsharded or backward grows partial-sum
    all-reduces of [B,H,S,S] score gradients (§Perf iteration 0)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(mesh, logical_axes, x.shape, unconstrained_fallback=False)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def axis_size(name: str) -> int:
    """Size of a mesh axis in the current scope (1 if absent / no mesh)."""
    mesh = current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def sharding_for(x_shape: Sequence[int], *logical_axes: Optional[str],
                 mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, spec_for(mesh, logical_axes, x_shape))


# ---------------------------------------------------------------------------
# Parameter rule table (name-suffix based)
# ---------------------------------------------------------------------------
# Model params are plain nested dicts with conventional leaf names; the rules
# below map a leaf's path suffix to logical axes (Megatron-style TP):
#   column-parallel ("in -> sharded hidden"):  wq/wk/wv/w1/w3/in_proj ...
#   row-parallel   ("sharded hidden -> out"):  wo/w2/out_proj ...
#   expert-parallel: experts_* leading E dim
#   embeddings: vocab dim on model
# Stacked-layer params carry a leading L dim -> rules are right-aligned.
# For ZeRO/FSDP (giant MoE archs) `fsdp=True` additionally shards the largest
# replicated dim over the DP axes.

_PARAM_RULES: list[tuple[tuple[str, ...], tuple]] = [
    (("embed",),            ("model", None)),     # tied: unembed-side local
    (("embed_in",),         (None, "model")),     # untied input: local gather
    (("unembed",),          (None, "model")),
    (("experts_w1", "experts_w3"), ("expert", None, "model")),
    (("experts_w2",),       ("expert", "model", None)),
    (("wq", "wk", "wv", "w_qkv", "w1", "w3", "in_proj", "q_up", "k_up", "v_up",
      "w_gate_up", "conv_w", "w_ih"), (None, "model")),
    (("wo", "w2", "out_proj", "w_down"), ("model", None)),
    (("bq", "bk", "bv", "b1", "b3", "b_in"), ("model",)),
    (("q_down", "kv_down", "router", "w_hh"), (None, None)),
    (("a_log", "ssm_d", "dt_bias", "heads_scale"), ("model",)),
]


def infer_logical_axes(path: str, shape) -> tuple:
    """Logical axes for a param leaf, right-aligned to its shape."""
    leaf = path.split("/")[-1]
    rule = None
    for names, axes in _PARAM_RULES:
        if leaf in names:
            rule = axes
            break
    if rule is None:
        rule = (None,) * len(shape)
    if len(rule) < len(shape):                 # stacked-layer leading dims
        rule = (None,) * (len(shape) - len(rule)) + tuple(rule)
    elif len(rule) > len(shape):
        rule = tuple(rule[-len(shape):])
    return tuple(rule)


def tree_param_shardings(mesh: Mesh, params, fsdp: bool = False):
    """NamedSharding pytree for a param pytree, by name rules."""
    def one(kp, x):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        axes = infer_logical_axes(path, x.shape)
        return param_sharding(mesh, axes, x.shape, fsdp=fsdp)
    return jax.tree_util.tree_map_with_path(one, params)

def param_sharding(mesh: Mesh, logical_axes, shape, fsdp: bool = False):
    spec = spec_for(mesh, logical_axes, shape)
    if fsdp:
        # shard the largest still-replicated dim over the DP axes (ZeRO-3).
        # Under pure_dp overrides the DP axes are the whole mesh.
        dp_axes = batch_axes(mesh)
        if dp_axes:
            used = {a for part in spec if part for a in
                    ((part,) if isinstance(part, str) else tuple(part))}
            if not any(a in used for a in dp_axes):
                dp_total = 1
                for a in dp_axes:
                    dp_total *= mesh.shape[a]
                # pick the largest dim divisible by the dp extent
                best, best_dim = None, 0
                for i, (part, dim) in enumerate(zip(spec, shape)):
                    if part is None and dim % dp_total == 0 and dim > best_dim:
                        best, best_dim = i, dim
                if best is not None:
                    parts = list(spec)
                    parts[best] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                    spec = P(*parts)
    return NamedSharding(mesh, spec)
