"""Primitive layers as pure functions over explicit param pytrees.

Hand-rolled (SURVEY §7.1 "flax.nnx or hand-rolled") so that:
  * pipeline stages are literal slices of stacked block params,
  * sharding annotations attach to raw arrays with no framework indirection,
  * everything works identically inside shard_map.

Normalisation is LayerNorm/GroupNorm rather than BatchNorm: BN's cross-device
batch statistics would entangle nodes with each other *outside* the
trust-gated aggregation path, corrupting per-node attribution of anomalies
(and needing extra collectives).  GroupNorm is the standard TPU-friendly
substitution and keeps every node's forward self-contained.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def uniform_scaling_init(key: jax.Array, shape: Tuple[int, ...], scale: float
                         ) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * scale


def dense_init(key: jax.Array, in_dim: int, out_dim: int,
               scale: Optional[float] = None) -> Params:
    if scale is None:
        scale = 1.0 / math.sqrt(in_dim)
    return {
        "w": uniform_scaling_init(key, (in_dim, out_dim), scale),
        "b": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(params: Params, x: jax.Array, dtype: jnp.dtype = jnp.float32
          ) -> jax.Array:
    return x @ params["w"].astype(dtype) + params["b"].astype(dtype)


def layernorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * params["scale"] + params["bias"]


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm over the last axis, in x's dtype (feed it float32): no mean,
    no bias, ``scale`` [dim] (or any shape that broadcasts)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def silu_gated_mlp(w_gate_up: jax.Array, w_down: jax.Array, x: jax.Array
                   ) -> jax.Array:
    """``W_down(SiLU(W_gate x) * W_up x)`` with the gate and the up
    projection side by side in ONE matrix ``[dim, 2 * width]`` (gate first);
    products in the weights' dtype with float32 accumulation, float32 out."""
    width = w_down.shape[0]
    h = jnp.matmul(x.astype(w_gate_up.dtype), w_gate_up,
                   preferred_element_type=jnp.float32)
    a = jax.nn.silu(h[..., :width]) * h[..., width:]
    return jnp.matmul(a.astype(w_down.dtype), w_down,
                      preferred_element_type=jnp.float32)


def groupnorm_init(channels: int) -> Params:
    return {"scale": jnp.ones((channels,), jnp.float32),
            "bias": jnp.zeros((channels,), jnp.float32)}


def groupnorm(params: Params, x: jax.Array, groups: int = 8, eps: float = 1e-5
              ) -> jax.Array:
    """x: [..., H, W, C] NHWC."""
    *lead, h, w, c = x.shape
    groups = min(groups, c)
    while c % groups:
        groups -= 1
    xg = x.reshape(*lead, h, w, groups, c // groups)
    mean = jnp.mean(xg, axis=(-4, -3, -1), keepdims=True)
    var = jnp.mean((xg - mean) ** 2, axis=(-4, -3, -1), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    y = xg.reshape(*lead, h, w, c)
    return y * params["scale"] + params["bias"]


def conv_init(key: jax.Array, kh: int, kw: int, cin: int, cout: int) -> Params:
    fan_in = kh * kw * cin
    return {
        "w": uniform_scaling_init(key, (kh, kw, cin, cout),
                                  math.sqrt(2.0 / fan_in)),
        "b": jnp.zeros((cout,), jnp.float32),
    }


def conv2d(params: Params, x: jax.Array, stride: int = 1,
           padding: str = "SAME", dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """NHWC conv — lowers straight onto the MXU via lax.conv_general_dilated."""
    y = jax.lax.conv_general_dilated(
        x.astype(dtype),
        params["w"].astype(dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + params["b"].astype(dtype)


def max_pool(x: jax.Array, window: int = 2, stride: int = 2) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID",
    )


def avg_pool_global(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(-3, -2))


def embedding_init(key: jax.Array, vocab: int, dim: int) -> jax.Array:
    return jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       ignore_index: Optional[int] = None) -> jax.Array:
    """Mean token/example cross-entropy — the reference's criterion
    (distributed_trainer.py:435-439).

    Written as ``logsumexp(logits) - logits[target]`` rather than
    ``-log_softmax(logits)[target]``: log_softmax materialises a second
    [..., V] f32 tensor the size of the logits (≈0.8 GB for a b=8, T=512
    GPT-2 batch), while logsumexp is a fused reduction and the target
    gather touches one column.  Same math, same gradient
    (softmax − one-hot), a full logits-sized round-trip less HBM traffic.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if ignore_index is not None:
        mask = (targets != ignore_index).astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def accuracy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32))
