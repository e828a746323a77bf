"""Plain Solar Open 2 for the serving cells: ONE full forward over a prompt
and its reply, float32 at ``highest``, one request at a time; no cache, no
chunks, no kernels, no grouped products, importing nothing of the program.

The layers, as the configuration's file states and assumes them (pre-norm
residual, ``x~ = RMSNorm(x)`` before each half, no positional term):

* attention layers (``gqa_layers``): ``q = x~ W_q`` (query heads), ``k, v =
  x~ W_k, x~ W_v`` (K/V heads), a causal softmax of ``q k / sqrt(width)``
  over ALL positions, query head ``h`` reading K/V head ``h // (heads /
  kv heads)``, the output times ``sigmoid(x~ W_gate)`` elementwise, then
  ``W_o``;
* every other layer is KDA, computed as the RECURRENCE in a ``lax.scan`` over
  time: ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``, with q, k, v through a causal depthwise
  convolution and SiLU, q and k L2-normalised a head (q also over
  ``sqrt(width)``), ``g = -exp(A_log) softplus(W_f^b W_f^a x~ + dt_bias)``,
  ``beta = 2 sigmoid(x~ W_beta)``, the output RMS-normalised a head and
  gated by ``sigmoid(W_g^b W_g^a x~)``;
* every layer's second half: ``s = sigmoid(x~ W_r)`` over all published
  experts, the ``k`` largest of ``s + b`` chosen, weights ``s_e`` over their
  sum; a LOOP over the held experts over every token with the routing
  weights as a mask, plus the shared expert unweighted.  What absent experts
  would add is left out, as in the program.

The weights arrive in bfloat16 (``solar_open2.make_weights``) and are upcast
where they are used, a matrix or an expert at a time, so that both sides
compute with identical numbers and the float32 copy never exists whole.
``precision`` ``"fp8"`` rounds both operands of every matrix product first
(e4m3, per-tensor scale): the control.  The router's product and the state's
recurrence stay float32 in every precision, as the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "f32":
        return x
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    return jnp.matmul(_round(a.astype(jnp.float32), precision),
                      _round(b.astype(jnp.float32), precision),
                      precision=HIGHEST)


def _rms(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


def _attention(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
               precision: str) -> jax.Array:
    t = xn.shape[0]
    heads, kv, width = shape["q_heads"], shape["kv_heads"], shape["head_dim"]
    q = _mm(xn, p["wq"], precision).reshape(t, heads, width)
    k = _mm(xn, p["wk"], precision).reshape(t, kv, width)
    v = _mm(xn, p["wv"], precision).reshape(t, kv, width)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(h):
        # One query head at a time: the [T, T] scores of all of them at
        # once would not fit beside the weights at 8k positions.
        scores = _mm(q[:, h], k[:, h // (heads // kv)].T, precision) \
            / math.sqrt(width)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm(probs, v[:, h // (heads // kv)], precision)

    out = jax.lax.map(one_head, jnp.arange(heads))          # [H, T, width]
    out = out.transpose(1, 0, 2).reshape(t, heads * width)
    gate = jax.nn.sigmoid(_mm(xn, p["wg"], precision))
    return _mm(out * gate, p["wo"], precision)


def _kda(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
         precision: str) -> jax.Array:
    t = xn.shape[0]
    heads, width = shape["kda_heads"], shape["kda_head_dim"]
    taps = p["conv"].astype(jnp.float32)                      # [K, 3·H·d]
    size = taps.shape[0]
    pre = jnp.concatenate([_mm(xn, p[w], precision)
                           for w in ("wq", "wk", "wv")], axis=-1)
    rows = jnp.concatenate(
        [jnp.zeros((size - 1, pre.shape[1]), jnp.float32), pre], axis=0)
    mixed = _silu(sum(taps[j] * rows[j:j + t] for j in range(size)))
    q, k, v = (a.reshape(t, heads, width)
               for a in jnp.split(mixed, 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
    q = unit(q) / math.sqrt(width)
    k = unit(k)
    decay = jax.nn.softplus(
        _mm(_mm(xn, p["f_a"], precision), p["f_b"], precision)
        + p["dt_bias"]).reshape(t, heads, width)
    g = -jnp.exp(p["a_log"])[None, :, None] * decay
    beta = 2.0 * jax.nn.sigmoid(_mm(xn, p["w_beta"], precision))  # [T, H]

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = s * jnp.exp(g_t)[:, :, None]
        read = jnp.einsum("hkv,hk->hv", s, k_t, precision=HIGHEST)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((heads, width, width), jnp.float32),
                        (q, k, v, g, beta))                   # [T, H, d]
    o = _rms(p["o_norm"], o, shape["norm_eps"]).reshape(t, heads * width)
    gate = jax.nn.sigmoid(
        _mm(_mm(xn, p["g_a"], precision), p["g_b"], precision))
    return _mm(o * gate, p["wo"], precision)


def _gated_mlp(x: jax.Array, gate_up: jax.Array, down: jax.Array,
               precision: str) -> jax.Array:
    width = down.shape[0]
    h = _mm(x, gate_up, precision)
    return _mm(_silu(h[:, :width]) * h[:, width:], down, precision)


def routing_weights(p: Dict[str, Any], xn: jax.Array,
                    shape: Dict[str, Any]) -> jax.Array:
    """f32[T, E]: each token's weight on each PUBLISHED expert, 0 where the
    expert was not chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(
        xn, p["router"].astype(jnp.float32), precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + p["router_bias"],
                              shape["experts_per_tok"])
    mask = jnp.any(chosen[:, :, None] == jnp.arange(scores.shape[1]), axis=1)
    weights = jnp.where(mask, scores, 0.0)
    if shape["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * shape["routed_scaling_factor"]


def expert_layer(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
                 precision: str = "f32") -> jax.Array:
    """The held experts' part, one expert at a time over EVERY token with
    its routing weight as the mask, plus the shared expert."""
    first, held = shape["first_expert"], shape["n_experts_held"]
    weights = routing_weights(p, xn, shape)[:, first:first + held]

    def one_expert(y, at):
        gate_up, down, w = at
        return y + w[:, None] * _gated_mlp(xn, gate_up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xn),
                        (p["w_gate_up"], p["w_down"], weights.T))
    return y + _gated_mlp(xn, p["shared_gate_up"], p["shared_down"],
                          precision)


def hidden(params: Dict[str, Any], tokens: jax.Array, shape: Dict[str, Any],
           precision: str = "f32") -> jax.Array:
    """tokens i32[T] -> f32[T, D], the residual stream after the last layer
    (before the final norm)."""
    x = params["embed"][tokens].astype(jnp.float32)
    eps = shape["norm_eps"]
    for index in range(shape["n_periods"]):
        for kind, stacked in zip(shape["period"], params["periods"]):
            p = jax.tree_util.tree_map(lambda a: a[index], stacked)
            xn = _rms(p["norm1"], x, eps)
            if kind == "attn":
                x = x + _attention(p["attn"], xn, shape, precision)
            else:
                x = x + _kda(p["kda"], xn, shape, precision)
            x = x + expert_layer(p["moe"], _rms(p["norm2"], x, eps), shape,
                                 precision)
    return x


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _logits_at(params, tokens, start, rows: int,
               shape: Tuple[Tuple[str, Any], ...], precision: str):
    """tokens [T] -> logits [rows, V] of positions start..start+rows."""
    sizes = dict(shape)
    x = hidden(params, tokens, sizes, precision)
    picked = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    picked = _rms(params["final_norm"], picked, sizes["norm_eps"])
    return _mm(picked, params["head"], precision)


def reply_logits(params: Dict[str, Any], prompt: Sequence[int],
                 reply: Sequence[int], shape: Dict[str, Any], max_seq: int,
                 max_reply: int, precision: str = "f32") -> jax.Array:
    """f32[len(reply), V]: the logits that predict each token of ``reply``
    after ``prompt``, teacher-forced, from one forward padded to
    ``max_seq`` (causal, so the padding changes nothing before it)."""
    plen, rlen = len(prompt), len(reply)
    if plen + rlen > max_seq or rlen > max_reply:
        raise ValueError(f"{plen} + {rlen} tokens do not fit {max_seq}")
    tokens = np.zeros(max_seq, np.int32)
    tokens[:plen] = prompt
    tokens[plen:plen + rlen] = reply
    start = min(plen - 1, max_seq - max_reply)
    logits = _logits_at(params, jnp.asarray(tokens), start, max_reply,
                        tuple(sorted(shape.items())), precision)
    first = plen - 1 - start
    return logits[first:first + rlen]


def chosen_tokens(logits: jax.Array) -> Tuple[int, ...]:
    return tuple(int(t) for t in np.asarray(jnp.argmax(logits, axis=-1)))
