"""Plain Kimi Linear for the serving cells: ONE full forward over a prompt
and its reply, float32 at ``highest``, one request at a time; no cache, no
chunks, no kernels, no grouped products, no absorbed weights, importing
nothing of the program.

The layers, as the configuration's file states and assumes them (pre-norm
residual, ``x~ = RMSNorm(x)`` before each half, no positional term anywhere:
``mla_use_nope``):

* latent attention (``full_attn_layers``), in the EXPANDED form: ``q = x~
  W_q`` (heads of ``qk_nope_head_dim + qk_rope_head_dim``); ``a = x~ W_kv_a``
  split ``c [kv_lora_rank] || k_r [qk_rope_head_dim]``, ``c~ = RMSNorm(c)``;
  per head ``k_h = (c~ W_kb)[h, :nope] || k_r`` (``k_r`` shared by the heads
  and NOT rotated), ``v_h = (c~ W_kb)[h, nope:]``; a causal softmax of ``q_h
  k_h / sqrt(nope + rope)`` over ALL positions, a head at a time in blocks of
  queries; ``W_o``; no output gate;
* every other layer is KDA, computed as the RECURRENCE in a ``lax.scan`` over
  time (in segments of positions, so that 32k positions of its inputs never
  exist at once; the state and the convolution's last rows pass from one
  segment to the next): ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
  S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, with q, k, v through a
  causal depthwise convolution and SiLU, q and k L2-normalised a head (q also
  over ``sqrt(width)``), ``g = -exp(A_log) softplus(W_f^b W_f^a x~ +
  dt_bias)``, ``beta = sigmoid(x~ W_beta)`` (no ``kda_allow_neg_eigval`` in
  the source: FLA's default, no factor 2), the output RMS-normalised a head
  and gated by ``sigmoid(W_g^b W_g^a x~)``;
* the second half of the ``first_k_dense_replace`` leading layers: ``W_down
  (SiLU(W_gate x~) * W_up x~)`` at ``intermediate_size``;
* of every other layer: ``s = sigmoid(x~ W_r)`` over all published experts,
  the ``k`` largest of ``s + b`` chosen, weights ``s_e`` over their sum
  (``moe_renormalize``) times ``routed_scaling_factor``; a LOOP over the held
  experts over every token with the routing weights as a mask, plus the
  shared expert unweighted.  What absent experts would add is left out, as in
  the program.

The weights arrive in bfloat16 (``kimi_linear.make_weights``) and are upcast
where they are used, a matrix or an expert at a time.  ``precision`` ``"fp8"``
rounds both operands of every matrix product first (e4m3, per-tensor scale):
the control.  The router's product and the state's recurrence stay float32 in
every precision, as the configuration states.
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
#: Positions a forward is padded to a multiple of (causal, so the padding
#: changes nothing before it): one compiled program a bucket, and a request
#: of 9k positions does not pay for 32k.
BUCKET = 8192
#: Positions of one segment of the KDA scan, and queries of one block of the
#: attention's softmax.
SEGMENT = 2048


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


def _segments(t: int) -> int:
    """The segment length for ``t`` positions: ``SEGMENT`` where it divides
    them, else all of them at once (the tiny sizes of the tests)."""
    return SEGMENT if t % SEGMENT == 0 else t


def latent_attention(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
                     precision: str = "f32") -> jax.Array:
    """The expanded form: per-head keys and values made from the latent of
    EVERY position, a masked softmax a head at a time."""
    t = xn.shape[0]
    heads, nope, rope = (shape["q_heads"], shape["qk_nope_head_dim"],
                         shape["qk_rope_head_dim"])
    value, rank = shape["v_head_dim"], shape["kv_lora_rank"]
    q = _mm(xn, p["wq"], precision).reshape(t, heads, nope + rope)
    a = _mm(xn, p["w_kv_a"], precision)
    latent = _rms(p["kv_norm"], a[:, :rank], shape["norm_eps"])
    k_rope = a[:, rank:]                                     # [T, rope]
    w_b = p["w_kv_b"].reshape(rank, heads, nope + value)
    block = _segments(t)
    kpos = jnp.arange(t)

    def one_head(h):
        kv = _mm(latent, w_b[:, h], precision)               # [T, nope + v]
        k = jnp.concatenate([kv[:, :nope], k_rope], axis=-1)
        v = kv[:, nope:]

        def one_block(b):
            rows = jax.lax.dynamic_slice_in_dim(q[:, h], b * block, block)
            scores = _mm(rows, k.T, precision) / math.sqrt(nope + rope)
            seen = kpos[None, :] <= (b * block + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            return _mm(probs, v, precision)

        return jax.lax.map(one_block, jnp.arange(t // block)).reshape(
            t, value)

    out = jax.lax.map(one_head, jnp.arange(heads))           # [H, T, v]
    out = out.transpose(1, 0, 2).reshape(t, heads * value)
    return _mm(out, p["wo"], precision)


def _kda(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
         precision: str) -> jax.Array:
    t = xn.shape[0]
    heads, width = shape["kda_heads"], shape["kda_head_dim"]
    taps = p["conv"].astype(jnp.float32)                      # [K, 3·H·d]
    size = taps.shape[0]
    seg = _segments(t)
    unit = lambda a: a / jnp.sqrt(
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = s * jnp.exp(g_t)[:, :, None]
        read = jnp.einsum("hkv,hk->hv", s, k_t, precision=HIGHEST)
        s = s + k_t[:, :, None] * (b_t[:, None] * (v_t - read))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    def segment(carry, xs):
        s, tail = carry                     # state, the last K-1 input rows
        pre = jnp.concatenate([_mm(xs, p[w], precision)
                               for w in ("wq", "wk", "wv")], axis=-1)
        rows = jnp.concatenate([tail, pre], axis=0)
        mixed = _silu(sum(taps[j] * rows[j:j + seg] for j in range(size)))
        q, k, v = (a.reshape(seg, heads, width)
                   for a in jnp.split(mixed, 3, axis=-1))
        q = unit(q) / math.sqrt(width)
        k = unit(k)
        decay = jax.nn.softplus(
            _mm(_mm(xs, p["f_a"], precision), p["f_b"], precision)
            + p["dt_bias"]).reshape(seg, heads, width)
        g = -jnp.exp(p["a_log"])[None, :, None] * decay
        beta = shape["kda_beta_scale"] * jax.nn.sigmoid(
            _mm(xs, p["w_beta"], precision))                  # [seg, H]
        s, o = jax.lax.scan(step, s, (q, k, v, g, beta))      # [seg, H, d]
        o = _rms(p["o_norm"], o, shape["norm_eps"]).reshape(
            seg, heads * width)
        gate = jax.nn.sigmoid(
            _mm(_mm(xs, p["g_a"], precision), p["g_b"], precision))
        return (s, rows[seg:]), _mm(o * gate, p["wo"], precision)

    start = (jnp.zeros((heads, width, width), jnp.float32),
             jnp.zeros((size - 1, taps.shape[1]), jnp.float32))
    _, out = jax.lax.scan(segment, start, xn.reshape(t // seg, seg, -1))
    return out.reshape(t, -1)


def gated_mlp(x: jax.Array, gate_up: jax.Array, down: jax.Array,
              precision: str = "f32") -> jax.Array:
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
    its routing weight as the mask, plus the shared expert.  ``held_shift``
    is the hook of a planted fault (``kimi_linear.planted``): the routing
    weights of the held experts rolled by it, so that expert ``e``'s weight
    meets expert ``e + 1``'s matrices (a second copy of 7 GB of experts,
    rolled, does not fit beside the first)."""
    first, held = shape["first_expert"], shape["n_experts_held"]
    weights = routing_weights(p, xn, shape)[:, first:first + held]
    if "held_shift" in p:
        weights = jnp.roll(weights, p["held_shift"], axis=1)

    def one_expert(y, at):
        gate_up, down, w = at
        return y + w[:, None] * gated_mlp(xn, gate_up, down, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(xn),
                        (p["w_gate_up"], p["w_down"], weights.T))
    return y + gated_mlp(xn, p["shared_gate_up"], p["shared_down"],
                         precision)


def _mixed(kind: str, p: Dict[str, Any], x: jax.Array, shape: Dict[str, Any],
           precision: str) -> jax.Array:
    xn = _rms(p["norm1"], x, shape["norm_eps"])
    if kind == "mla":
        return x + latent_attention(p["mla"], xn, shape, precision)
    return x + _kda(p["kda"], xn, shape, precision)


def hidden(params: Dict[str, Any], tokens: jax.Array, shape: Dict[str, Any],
           precision: str = "f32") -> jax.Array:
    """tokens i32[T] -> f32[T, D], the residual stream after the last layer
    (before the final norm)."""
    x = params["embed"][tokens].astype(jnp.float32)
    eps = shape["norm_eps"]
    seg = _segments(x.shape[0])
    for kind, p in zip(shape["lead"], params.get("lead", ())):
        x = _mixed(kind, p, x, shape, precision)
        # In segments of positions: 32k rows of the 2 x 9,216 gate and up
        # values at once would be 2.4 GB beside the weights.
        x = x + jax.lax.map(
            lambda rows: gated_mlp(rows, p["mlp"]["gate_up"],
                                   p["mlp"]["down"], precision),
            _rms(p["norm2"], x, eps).reshape(-1, seg, x.shape[1])
        ).reshape(x.shape)
    for index in range(shape["n_periods"]):
        for kind, stacked in zip(shape["period"], params["periods"]):
            p = jax.tree_util.tree_map(lambda a: a[index], stacked)
            x = _mixed(kind, p, x, shape, precision)
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


def padded_length(positions: int, max_seq: int) -> int:
    """The positions a forward over ``positions`` is padded to: the next
    multiple of ``BUCKET``, never past ``max_seq``."""
    return min(max_seq, -(-positions // BUCKET) * BUCKET)


def reply_logits(params: Dict[str, Any], prompt: Sequence[int],
                 reply: Sequence[int], shape: Dict[str, Any], max_seq: int,
                 max_reply: int, precision: str = "f32") -> jax.Array:
    """f32[len(reply), V]: the logits that predict each token of ``reply``
    after ``prompt``, teacher-forced, from one forward padded to the
    request's bucket (causal, so the padding changes nothing before it)."""
    plen, rlen = len(prompt), len(reply)
    if plen + rlen > max_seq or rlen > max_reply:
        raise ValueError(f"{plen} + {rlen} tokens do not fit {max_seq}")
    length = max(padded_length(plen + rlen, max_seq), max_reply)
    tokens = np.zeros(length, np.int32)
    tokens[:plen] = prompt
    tokens[plen:plen + rlen] = reply
    start = min(plen - 1, length - max_reply)
    logits = _logits_at(params, jnp.asarray(tokens), start, max_reply,
                        tuple(sorted(shape.items())), precision)
    first = plen - 1 - start
    return logits[first:first + rlen]


def chosen_tokens(logits: jax.Array) -> Tuple[int, ...]:
    return tuple(int(t) for t in np.asarray(jnp.argmax(logits, axis=-1)))
