"""Plain GPT-2: forward, loss, gradients and AdamW in straightforward
``jax.numpy``, float32, matrix products at ``highest`` precision.

No kernels, no cache, no batching tricks; imports nothing of the program.
It follows the published GPT-2 (pre-LayerNorm blocks, eps 1e-5, tanh GELU,
learned positions, head tied to the embedding).  ``precision`` selects the
arithmetic of the matrix products, for the controls: ``f32`` is the
reference; ``fp8`` rounds both operands first (e4m3 with a per-tensor scale,
straight-through in the backward pass).  There is no ``bf16`` here: XLA on
the TPU folds a float32 -> bfloat16 -> float32 round trip away, so such a
control read 2e-7 on the chip, that is, nothing.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "f32":
        return x
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _layernorm(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(block: Dict[str, Any], x: jax.Array, n_head: int,
           precision: str) -> jax.Array:
    b, t, d = x.shape
    y = _layernorm(block["ln_1"], x)
    qkv = _mm(y, block["attn"]["qkv"]["w"], precision) \
        + block["attn"]["qkv"]["b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = lambda a: a.reshape(b, t, n_head, d // n_head).transpose(
        0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    scores = _mm(q, k.transpose(0, 1, 3, 2), precision) \
        / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm(probs, v, precision).transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _mm(out, block["attn"]["proj"]["w"], precision) \
        + block["attn"]["proj"]["b"]
    y = _layernorm(block["ln_2"], x)
    y = _gelu(_mm(y, block["mlp"]["fc"]["w"], precision)
              + block["mlp"]["fc"]["b"])
    return x + _mm(y, block["mlp"]["proj"]["w"], precision) \
        + block["mlp"]["proj"]["b"]


def hidden(params: Dict[str, Any], tokens: jax.Array, n_head: int,
           precision: str = "f32") -> jax.Array:
    """tokens [B, T] -> final normed hidden states [B, T, D], layer by
    layer."""
    t = tokens.shape[-1]
    x = params["wte"][tokens] + params["wpe"][jnp.arange(t)]

    def layer(x, block):
        return _block(block, x, n_head, precision), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _layernorm(params["ln_f"], x)


def _sum_loss(params, tokens, targets, n_head, precision):
    lg = _mm(hidden(params, tokens, n_head, precision), params["wte"].T,
             precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block_loss_grads(params, tokens, targets, n_head, precision):
    return jax.value_and_grad(_sum_loss)(params, tokens, targets, n_head,
                                         precision)


def loss_and_grads(params: Dict[str, Any], batch: Dict[str, Any],
                   n_head: int, precision: str = "f32", rows: int = 2
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Mean next-token cross entropy over ALL rows of the batch and its
    gradient, computed ``rows`` rows at a time so that it fits."""
    tokens, targets = jnp.asarray(batch["input"]), jnp.asarray(
        batch["target"])
    total, grads = None, None
    for lo in range(0, tokens.shape[0], rows):
        loss, g = _block_loss_grads(params, tokens[lo:lo + rows],
                                    targets[lo:lo + rows], n_head, precision)
        total = loss if total is None else total + loss
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    count = float(targets.size)
    return total / count, jax.tree_util.tree_map(lambda g: g / count, grads)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def adamw_step(params, grads, mu, nu, step: int, lr: float, b1: float,
               b2: float, eps_wd: Tuple[float, float]):
    """One AdamW update (bias-corrected moments, decoupled decay), as
    Loshchilov and Hutter state it; ``step`` counts from 1."""
    eps, wd = eps_wd
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu,
                                grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu,
                                grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def update(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

    return jax.tree_util.tree_map(update, params, mu, nu), mu, nu


def train_steps(params: Dict[str, Any], batches: List[Dict[str, Any]],
                n_head: int, opt: Dict[str, float], precision: str = "f32",
                rows: int = 2, fault: str = ""
                ) -> Dict[str, Any]:
    """Follow ``len(batches)`` steps from ``params``.  Returns each step's
    loss, the first step's gradient and the parameters after the last.

    ``fault`` plants one of the faults a training cell can have, for the
    controls: ``half_batch`` (the second half of every batch left out, the
    mean taken over the rest) and ``no_exchange`` (only the first of
    ``opt['nodes']`` equal node shards reaches the optimizer)."""
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros(), zeros()
    losses, first_grads = [], None
    for i, batch in enumerate(batches):
        if fault == "half_batch":
            half = batch["input"].shape[0] // 2
            batch = {k: v[:half] for k, v in batch.items()}
        loss, grads = loss_and_grads(params, batch, n_head, precision, rows)
        if fault == "no_exchange":
            shard = batch["input"].shape[0] // int(opt["nodes"])
            _, grads = loss_and_grads(
                params, {k: v[:shard] for k, v in batch.items()}, n_head,
                precision, rows)
        if first_grads is None:
            first_grads = grads
        losses.append(float(loss))
        params, mu, nu = adamw_step(
            params, grads, mu, nu, i + 1, float(opt["learning_rate"]),
            float(opt["b1"]), float(opt["b2"]),
            (float(opt["eps"]), float(opt["weight_decay"])))
    return {"losses": losses, "first_grads": first_grads, "params": params}
