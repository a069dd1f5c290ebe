"""Production mesh construction.

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init; smoke
tests must keep seeing 1 device).

Mesh layout (DESIGN.md §4):
  single pod:  (data=16, model=16)            = 256 chips (v5e pod)
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips

Axis roles: DP over ('pod', 'data'); TP / EP / cache-sharding over 'model';
SP (sequence sharding for long-context decode) borrows 'data' when the batch
cannot occupy it.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]      # dry-run exposes 512 host devices;
    assert len(devices) == ndev, (      # single-pod uses the first 256
        f"need {ndev} devices, have {len(jax.devices())} — the dry-run must "
        f"set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
        f"any jax import")
    import numpy as _np
    return jax.sharding.Mesh(_np.asarray(devices).reshape(shape), axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many devices this host exposes (tests).

    Auto axis types: the engine's steps place operands with explicit
    NamedShardings and shard_map, so the mesh must not type every array's
    sharding (Explicit axes make ``jnp.take`` on a row-sharded cache table
    refuse to pick an output sharding)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def cache_shard_axis(mesh: jax.sharding.Mesh) -> str:
    """Mesh axis carrying the feature-store cache shards.

    The cache table rides the 'model' axis (TP / EP / cache-sharding share
    it, see the layout note above): DP groups each consume their own
    minibatch, so the row shards must live across an axis every DP group
    spans.  Falls back to the first axis on meshes without 'model'
    (1-D benchmark meshes)."""
    return "model" if "model" in mesh.axis_names else mesh.axis_names[0]
