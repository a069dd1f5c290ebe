"""Plain reference for the GraphSAGE configurations: parameters, the
forward pass, its loss and its FLOP count, in straightforward
``jax.numpy``.  The GNS weights, the batch format, Adam and the training
loop are the shared replica's (``gnsref``).

It imports nothing of the program, and computes features, labels,
parameters and the forward and backward pass from the configuration and
the seed.

* layer ``h' = relu([h_v ; Σ_k w·h_u] W + b)`` (no relu on the last);
* loss the mean cross entropy over the batch's targets.

``dtype`` computes everything in another precision: ``bfloat16`` is the
control, which has to fail the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from gnsbench import counts, gnsref

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(seed: int, cfg: dict) -> list:
    """He-style normal init, ``W ~ N(0, 1/F_in)`` of shape ``[2F_in, F_out]``,
    zero bias, one key per layer split from ``PRNGKey(seed)``."""
    layers = len(cfg["fanouts"])
    keys = jax.random.split(jax.random.PRNGKey(seed), layers)
    out, f_in = [], cfg["feat_dim"]
    for i in range(layers):
        f_out = cfg["num_classes"] if i == layers - 1 else cfg["hidden_dim"]
        w = jax.random.normal(keys[i], (2 * f_in, f_out), jnp.float32)
        out.append({"w": w * jnp.sqrt(2.0 / (2 * f_in)),
                    "b": jnp.zeros((f_out,), jnp.float32)})
        f_in = f_out
    return out


def train_flops(real_dst: list, cfg: dict) -> float:
    """FLOPs one training step requires (``counts.sage_train_flops``)."""
    return counts.sage_train_flops(real_dst, cfg["fanouts"], cfg["feat_dim"],
                                   cfg["hidden_dim"], cfg["num_classes"])


def _forward(params, x, lanes, num_dst, dtype):
    h = x.astype(dtype)
    for i, ((idx, w), nd, p) in enumerate(zip(lanes, num_dst, params)):
        agg = jnp.sum(w.astype(dtype)[..., None] * h[idx], axis=1)
        z = jnp.dot(jnp.concatenate([h[:nd], agg], axis=-1),
                    p["w"].astype(dtype), precision=HIGHEST) + p["b"].astype(dtype)
        h = jax.nn.relu(z) if i < len(lanes) - 1 else z
    return h


def _loss(params, x, lanes, labels, label_w, num_dst, dtype):
    logp = jax.nn.log_softmax(_forward(params, x, lanes, num_dst, dtype),
                              axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    lw = label_w.astype(dtype)
    return jnp.sum(nll * lw) / jnp.sum(lw)


# One compile per batch shape; the precision is fixed inside, so a trace
# never depends on the caller's matmul-precision setting.
_logits = jax.jit(_forward, static_argnames=("num_dst", "dtype"))
_loss_only = jax.jit(_loss, static_argnames=("num_dst", "dtype"))


def loss(params, batch: gnsref.Batch, dtype=jnp.float32):
    x, lanes, nd, labels, label_w = gnsref.batch_args(batch)
    return _loss_only(params, x, lanes, labels, label_w, num_dst=nd,
                      dtype=dtype)


def train(params0, batches: list, opt: dict, dtype=jnp.float32) -> dict:
    return gnsref.train(_loss, params0, batches, opt, dtype)


def logits(params, batch: gnsref.Batch, dtype=jnp.float32) -> np.ndarray:
    x, lanes, nd, _, _ = gnsref.batch_args(batch)
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    out = _logits(params, x, lanes, num_dst=nd, dtype=dtype)
    return np.asarray(out, dtype=np.float64)[:len(batch.labels)]
