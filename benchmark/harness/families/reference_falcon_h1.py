"""Plain Falcon-H1 for the serving cells: ONE full forward over a prompt and
its reply, float32 at ``highest``, one request at a time; no cache, no
chunks, no kernels, importing nothing of the program.

Every layer, as the configuration's file states and assumes it (``x~ =
RMSNorm(x)``; ``m_*`` the file's multipliers):

* the Mamba-2 half, as the RECURRENCE in a ``lax.scan`` over time (in
  segments of positions; the state and the convolution's last rows pass from
  one segment to the next): ``[z | x B C | dt] = (m_ssm_in x~) W_in``, each
  of the five parts times its ``ssm_multipliers`` entry; ``x B C`` through a
  4-tap causal depthwise convolution with a bias, then SiLU; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t = exp(dt_t
  A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` (head ``h``
  reading group ``h // (heads / groups)``'s B and C); ``W_out
  RMSNorm_groups(y * SiLU(z)) m_ssm_out``;
* beside it on the same ``x~``, attention: ``q = (m_attn_in x~) W_q``, ``k =
  (m_attn_in x~) W_k m_key``, ``v = (m_attn_in x~) W_v``, q and k rotated
  (rotate-half over the whole head, ``rope_theta``) at their positions, a
  causal softmax of ``q k / sqrt(head_dim)`` over ALL positions, a query head
  at a time in blocks of queries (query head ``h`` reading K/V head ``h //
  (q_heads / kv_heads)``), ``W_o``, ``m_attn_out``; no output gate;
* ``x = x + mamba + attn``, then ``x + m_down W_down(SiLU(m_gate W_gate
  RMSNorm(x)) * W_up RMSNorm(x))``;
* the embedding times ``embedding_multiplier``, the logits ``W_head
  RMSNorm(x)`` times ``lm_head_multiplier``.

Departures from the source: none in the mathematics; the weights are the
family's random ones (``falcon_h1.make_weights``), which arrive in bfloat16
and are upcast where they are used.  ``precision`` ``"fp8"`` rounds both
operands of every matrix product first (e4m3, per-tensor scale) and carries
the state from one position to the next in bfloat16 (the file states
float32): the control.  Three hooks serve planted faults
(``falcon_h1.planted``) and are absent from sound weights:
``state_unwritten`` (a layer whose decode step never writes its state row
back: every decode step reads the state the prompt's last chunk left),
``state_reset`` (a layer whose state row is never written at all: every
program call, a prompt's chunk or a decode step, starts from the zero state
its slot was admitted with) and ``conv_unwritten`` (a layer whose
convolution sees no row of an earlier program call).
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
#: changes nothing before it).
BUCKET = 8192
#: Positions of one segment of the recurrence's scan, of the MLP's map, and
#: queries of one block of the attention's softmax.
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


def rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """``x [T, heads, d]`` rotated at ``positions [T]``: pairs ``(i, i +
    d/2)`` by ``position x theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angle = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
              precision: str = "f32", key_shift: int = 0) -> jax.Array:
    """A masked softmax a query head at a time.  ``key_shift`` rotates the
    keys that many positions later than they are (a planted fault)."""
    t = xn.shape[0]
    heads, kv, d = shape["q_heads"], shape["kv_heads"], shape["head_dim"]
    theta = shape["rope_theta"]
    xn = xn * shape["attn_in_multiplier"]
    pos = jnp.arange(t)
    q = rotate(_mm(xn, p["wq"], precision).reshape(t, heads, d), pos, theta)
    k = _mm(xn, p["wk"], precision).reshape(t, kv, d) \
        * shape["key_multiplier"]
    k = rotate(k, pos + key_shift, theta)
    v = _mm(xn, p["wv"], precision).reshape(t, kv, d)
    block = _segments(t)

    def one_head(h):
        keys, values = k[:, h // (heads // kv)], v[:, h // (heads // kv)]

        def one_block(b):
            rows = jax.lax.dynamic_slice_in_dim(q[:, h], b * block, block)
            scores = _mm(rows, keys.T, precision) / math.sqrt(d)
            seen = pos[None, :] <= (b * block + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            return _mm(probs, values, precision)

        return jax.lax.map(one_block, jnp.arange(t // block)).reshape(t, d)

    out = jax.lax.map(one_head, jnp.arange(heads))            # [H, T, d]
    out = out.transpose(1, 0, 2).reshape(t, heads * d)
    return _mm(out, p["wo"], precision) * shape["attn_out_multiplier"]


def _same_call(t: jax.Array, lag: int, written: jax.Array, chunk: int
               ) -> jax.Array:
    """Whether the row ``lag`` positions before position ``t`` was fed in
    the same program call as ``t``: a chunk of the prompt, or (past
    ``written``, the prompt's end) the decode step of ``t`` alone."""
    return (t < written) & ((t - lag) // chunk == t // chunk) & (t >= lag)


def _first_of_call(t: jax.Array, written: jax.Array, chunk: int
                   ) -> jax.Array:
    """Whether position ``t`` is the first its program call fed."""
    return (t >= written) | (t % chunk == 0)


def mamba(p: Dict[str, Any], xn: jax.Array, shape: Dict[str, Any],
          precision: str = "f32", written: Any = None, chunk: int = 1
          ) -> jax.Array:
    """The recurrence over every position, in segments.  ``written`` (the
    prompt's length) and ``chunk`` place the program's calls for the fault
    hooks; sound weights ignore them."""
    t = xn.shape[0]
    heads, width = shape["mamba_heads"], shape["mamba_head_dim"]
    groups, n = shape["mamba_groups"], shape["mamba_state"]
    inner = heads * width
    bc = groups * n
    taps = p["conv"].astype(jnp.float32)                      # [K, ch]
    size = taps.shape[0]
    seg = _segments(t)
    a = -jnp.exp(p["a_log"])
    m_z, m_x, m_b, m_c, m_dt = shape["ssm_multipliers"]
    mup = jnp.concatenate([jnp.full((inner,), m_x), jnp.full((bc,), m_b),
                           jnp.full((bc,), m_c)])
    frozen = p.get("state_unwritten", False)
    lost = p.get("state_reset", False)
    unwritten = p.get("conv_unwritten", False)
    written = t if written is None else written

    def step(h, at):
        x_t, dt_t, b_t, c_t, reset, kept = at
        h = jnp.where(reset, 0.0, h)
        b_h = jnp.repeat(b_t, heads // groups, axis=0)        # [H, N]
        c_h = jnp.repeat(c_t, heads // groups, axis=0)
        new = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        y = jnp.einsum("hpn,hn->hp", new, c_h, precision=HIGHEST) \
            + p["d_skip"][:, None] * x_t
        if precision != "f32":
            new = new.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.where(kept, h, new), y

    def segment(carry, at):
        h, tail = carry                    # state, the last K-1 input rows
        xs, first = at
        proj = _mm(xs * shape["ssm_in_multiplier"], p["w_in"], precision)
        z = proj[:, :inner] * m_z
        pre = proj[:, inner:inner + inner + 2 * bc] * mup
        dt = proj[:, inner + inner + 2 * bc:] * m_dt
        rows = jnp.concatenate([tail, pre], axis=0)
        pos = first + jnp.arange(seg)
        conv = taps[size - 1] * pre
        for lag in range(1, size):
            seen = jnp.where(unwritten, _same_call(pos, lag, written, chunk),
                             True)
            conv = conv + jnp.where(seen[:, None], taps[size - 1 - lag]
                                    * rows[size - 1 - lag:size - 1 - lag
                                           + seg], 0.0)
        mixed = _silu(conv + p["conv_bias"])
        x = mixed[:, :inner].reshape(seg, heads, width)
        b = mixed[:, inner:inner + bc].reshape(seg, groups, n)
        c = mixed[:, inner + bc:].reshape(seg, groups, n)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        reset = jnp.logical_and(lost, _first_of_call(pos, written, chunk))
        kept = jnp.logical_and(frozen, pos >= written)
        h, y = jax.lax.scan(step, h, (x, dt, b, c, reset, kept))
        g = y.reshape(seg, groups, inner // groups) * _silu(
            z.reshape(seg, groups, inner // groups))
        g = g / jnp.sqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                         + shape["norm_eps"])
        g = g.reshape(seg, inner) * p["gate_norm"]
        out = _mm(g, p["w_out"], precision) * shape["ssm_out_multiplier"]
        return (h, rows[seg:]), out

    start = (jnp.zeros((heads, width, n), jnp.float32),
             jnp.zeros((size - 1, taps.shape[1]), jnp.float32))
    _, out = jax.lax.scan(segment, start, (
        xn.reshape(t // seg, seg, -1), jnp.arange(0, t, seg)))
    return out.reshape(t, -1)


def gated_mlp(x: jax.Array, gate_up: jax.Array, down: jax.Array,
              shape: Dict[str, Any], precision: str = "f32") -> jax.Array:
    width = down.shape[0]
    m_gate, m_down = shape["mlp_multipliers"]
    h = _mm(x, gate_up, precision)
    return _mm(_silu(m_gate * h[:, :width]) * h[:, width:], down,
               precision) * m_down


def hidden(params: Dict[str, Any], tokens: jax.Array, shape: Dict[str, Any],
           precision: str = "f32", written: Any = None, chunk: int = 1
           ) -> jax.Array:
    """tokens i32[T] -> f32[T, D], the residual stream after the last layer
    (before the final norm)."""
    x = params["embed"][tokens].astype(jnp.float32) \
        * shape["embedding_multiplier"]
    eps = shape["norm_eps"]
    seg = _segments(x.shape[0])
    (stacked,) = params["periods"]
    for index in range(shape["n_periods"]):
        p = jax.tree_util.tree_map(lambda a: a[index], stacked)
        xn = _rms(p["norm1"], x, eps)
        shift = p["attn"].get("key_shift", 0)
        x = x + mamba(p["mamba"], xn, shape, precision, written, chunk) \
            + attention(p["attn"], xn, shape, precision, shift)
        # In segments of positions: 8k rows of the 2 x 21,504 gate and up
        # values at once would be 2.8 GB beside the weights.
        x = x + jax.lax.map(
            lambda rows: gated_mlp(rows, p["mlp"]["gate_up"],
                                   p["mlp"]["down"], shape, precision),
            _rms(p["norm2"], x, eps).reshape(-1, seg, x.shape[1])
        ).reshape(x.shape)
    return x


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _logits_at(params, tokens, start, written, rows: int,
               shape: Tuple[Tuple[str, Any], ...], precision: str,
               chunk: int):
    """tokens [T] -> logits [rows, V] of positions start..start+rows."""
    sizes = dict(shape)
    x = hidden(params, tokens, sizes, precision, written, chunk)
    picked = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    picked = _rms(params["final_norm"], picked, sizes["norm_eps"])
    return _mm(picked, params["head"], precision) \
        * sizes["lm_head_multiplier"]


def padded_length(positions: int, max_seq: int) -> int:
    """The positions a forward over ``positions`` is padded to: the next
    multiple of ``BUCKET``, never past ``max_seq``."""
    return min(max_seq, -(-positions // BUCKET) * BUCKET)


def reply_logits(params: Dict[str, Any], prompt: Sequence[int],
                 reply: Sequence[int], shape: Dict[str, Any], max_seq: int,
                 max_reply: int, precision: str = "f32", chunk: int = 1
                 ) -> jax.Array:
    """f32[len(reply), V]: the logits that predict each token of ``reply``
    after ``prompt``, teacher-forced, from one forward padded to the
    request's bucket (causal, so the padding changes nothing before it).
    ``chunk``: the program's prefill chunk, which only a fault hook reads."""
    plen, rlen = len(prompt), len(reply)
    if plen + rlen > max_seq or rlen > max_reply:
        raise ValueError(f"{plen} + {rlen} tokens do not fit {max_seq}")
    length = max(padded_length(plen + rlen, max_seq), max_reply)
    tokens = np.zeros(length, np.int32)
    tokens[:plen] = prompt
    tokens[plen:plen + rlen] = reply
    start = min(plen - 1, length - max_reply)
    logits = _logits_at(params, jnp.asarray(tokens), start, jnp.int32(plen),
                        max_reply, tuple(sorted(shape.items())), precision,
                        chunk)
    first = plen - 1 - start
    return logits[first:first + rlen]


def chosen_tokens(logits: jax.Array) -> Tuple[int, ...]:
    return tuple(int(t) for t in np.asarray(jnp.argmax(logits, axis=-1)))
