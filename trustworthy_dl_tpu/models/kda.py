"""The gated delta rule with a decay a channel (Kimi Delta Attention,
arXiv:2510.26692): a linear-attention layer whose cache is a STATE, not keys
and values.

Per head, with key width ``dk`` and value width ``dv``, the state
``S [dk, dv]`` (float32) follows

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log decay of each of the ``dk`` channels, ``beta_t`` in
[0, 2] the write strength (above 1 the transition has a negative
eigenvalue).  Two spellings of the same mathematics:

* :func:`kda_step`: the recurrence itself, one position; decode runs it for
  every slot.
* :func:`kda_chunk`: the chunked (WY) form prefill runs.  Writing
  ``S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T`` defines the pseudo-value
  ``u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t)``; inside a
  sub-chunk of ``C`` positions with cumulative log decay ``G_t`` the ``u``
  solve the unit lower-triangular system
  ``(I + Diag(beta) A) U = Diag(beta) (V - K+ S_0)`` with
  ``A[t, i] = sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])`` for ``i < t`` and
  ``K+_t = k_t exp(G_t)``, and then ``O = Q+ S_0 + B U`` (``B`` the same
  sum over ``q_t, k_i`` for ``i <= t``) and
  ``S_C = Diag(exp(G_C)) S_0 + (k_i exp(G_C - G_i))^T U``.  The state is
  carried across sub-chunks by a scan, and across calls by the caller.
  The system is solved through its exact inverse, formed by blocked
  doubling (:func:`unit_lower_inverse`): ``log2 C`` levels of batched
  matrix products and one more with the right-hand side, where a
  triangular solve is ``C`` dependent steps.

Every exponent taken is <= 0, so a decay near 0 underflows to an exact 0 and
nothing overflows: pairs inside a block of ``block`` positions take
``exp(G_t - G_i)`` directly (masked BEFORE the exponential), pairs in
different blocks factor it through the later block's first position,
``exp(G_t - R) exp(R - G_i)``, which are two matrix products.  All products
over the state run at ``HIGHEST`` precision in float32: the recurrence feeds
its own rounding back for thousands of positions.

A position whose ``beta`` and ``g`` are both 0 leaves the state as it was:
that is how the callers mask padded rows and idle slots.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, s: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One position of the recurrence for any leading dimensions (slots,
    heads): ``q, k, g [..., dk]``, ``v [..., dv]``, ``beta [...]``,
    ``s [..., dk, dv]`` -> ``(o [..., dv], s)``."""
    s = s * jnp.exp(g)[..., None]
    read = jnp.einsum("...kv,...k->...v", s, k, precision=HIGHEST)
    u = beta[..., None] * (v - read)
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("...kv,...k->...v", s, q, precision=HIGHEST), s


def _pair_sums(a: jax.Array, k: jax.Array, cum: jax.Array, block: int,
               inclusive: bool) -> jax.Array:
    """``out[t, i] = sum_d a_t[d] k_i[d] exp(cum_t[d] - cum_i[d])`` for
    ``i < t`` (``i <= t`` if ``inclusive``), else 0, over the positions of
    one sub-chunk: ``a, k, cum [..., C, dk]`` -> ``[..., C, C]``."""
    lead, (c, dk) = a.shape[:-2], a.shape[-2:]
    nb = c // block
    blocks = lambda x: x.reshape(lead + (nb, block, dk))
    ab, kb, cb = blocks(a), blocks(k), blocks(cum)
    # Cumulative decay up to a block's first position (0 for the first).
    ref = jnp.concatenate(
        [jnp.zeros_like(cb[..., :1, -1, :]), cb[..., :-1, -1, :]], axis=-2)
    # Pairs in different blocks: a_t exp(cum_t - ref_B) against every
    # earlier k_i exp(ref_B - cum_i); later positions are masked out
    # before the exponential, whose argument would be positive there.
    later = jnp.arange(c) >= (jnp.arange(nb) * block)[:, None]   # [nb, C]
    a_dec = ab * jnp.exp(cb - ref[..., None, :])
    k_dec = k[..., None, :, :] * jnp.exp(jnp.where(
        later[..., None], -jnp.inf, ref[..., None, :] - cum[..., None, :, :]))
    cross = jnp.einsum("...btd,...bid->...bti", a_dec, k_dec,
                       precision=HIGHEST)                 # [.., nb, b, C]
    # Pairs inside a block: the decay between the two positions directly.
    t = jnp.arange(block)
    seen = t[:, None] >= t[None, :] if inclusive else t[:, None] > t[None, :]
    gap = jnp.where(seen[..., None],
                    cb[..., :, None, :] - cb[..., None, :, :], -jnp.inf)
    inside = jnp.sum(ab[..., :, None, :] * kb[..., None, :, :]
                     * jnp.exp(gap), axis=-1)             # [.., nb, b, b]
    out = cross.reshape(lead + (nb, block, nb, block)) \
        + inside[..., :, :, None, :] * jnp.eye(nb, dtype=a.dtype)[
            :, None, :, None]
    return out.reshape(lead + (c, c))


def unit_lower_inverse(system: jax.Array) -> jax.Array:
    """The inverse of the unit lower-triangular ``system [..., C, C]`` by
    blocked doubling, in matrix products alone.  The system is padded with
    the identity to a power of two ``P``.  ``T`` starts as the inverse of
    the diagonal (I), and each of the ``log2 P`` levels joins pairs of
    diagonal blocks of ``s`` into blocks of ``2s``:
    ``[[L11, 0], [N21, L22]]^-1 = [[T11, 0], [-T22 N21 T11, T22]]`` with
    ``T11, T22`` the blocks' inverses so far.  That is
    ``T <- T - T (N o M_s) T``, ``N`` the strictly lower part and ``M_s``
    the lower-left ``s x s`` quarter of every diagonal block of ``2s``.
    No power of ``N`` is formed, so nothing grows only to cancel later."""
    c = system.shape[-1]
    p = 1 << (c - 1).bit_length()
    if p > c:
        system = jnp.pad(system, [(0, 0)] * (system.ndim - 2)
                         + [(0, p - c), (0, p - c)])
    square = (1,) * (system.ndim - 2) + (p, p)
    row = jax.lax.broadcasted_iota(jnp.int32, square, system.ndim - 2)
    col = jax.lax.broadcasted_iota(jnp.int32, square, system.ndim - 1)
    quarter = lambda s: jnp.where(
        (row // (2 * s) == col // (2 * s)) & (row // s > col // s),
        system, 0.0)
    inv = (row == col).astype(system.dtype) - quarter(1)
    s = 2
    while s < p:
        inv = inv - jnp.matmul(jnp.matmul(inv, quarter(s), precision=HIGHEST),
                               inv, precision=HIGHEST)
        s *= 2
    return inv[..., :c, :c]


def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              beta: jax.Array, s0: jax.Array, sub_chunk: int = 64,
              block: int = 16) -> Tuple[jax.Array, jax.Array]:
    """The chunked form over ``T`` positions: ``q, k, g [..., T, dk]``,
    ``v [..., T, dv]``, ``beta [..., T]``, ``s0 [..., dk, dv]`` ->
    ``(o [..., T, dv], s_T)``, equal to ``T`` calls of :func:`kda_step`.
    ``T`` is a multiple of the sub-chunk (clipped to ``T``), the sub-chunk
    of the block."""
    lead, (t, dk) = q.shape[:-2], q.shape[-2:]
    dv = v.shape[-1]
    c = min(sub_chunk, t)
    b = min(block, c)
    if t % c or c % b:
        raise ValueError(f"{t} positions are not whole sub-chunks of {c} "
                         f"in blocks of {b}")
    n = t // c
    subs = lambda x: jnp.moveaxis(
        x.reshape(lead + (n, c) + x.shape[len(lead) + 1:]), len(lead), 0)
    q, k, v, g, beta = (subs(x.astype(jnp.float32))
                        for x in (q, k, v, g, beta))   # [n, ..., c, .]
    cum = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(cum)
    a_kk = _pair_sums(k, k, cum, b, inclusive=False)
    a_qk = _pair_sums(q, k, cum, b, inclusive=True)
    # (I + Diag(beta) A) [W | U0] = Diag(beta) [K+ | V]: one product with
    # the inverse gives what the state is read through (W) and what it is
    # not (U0).
    system = jnp.eye(c, dtype=jnp.float32) + beta[..., None] * a_kk
    rhs = beta[..., None] * jnp.concatenate([k * decay, v], axis=-1)
    with jax.named_scope("kda.solve"):
        solved = jnp.matmul(unit_lower_inverse(system), rhs,
                            precision=HIGHEST)
    w, u0 = solved[..., :dk], solved[..., dk:]
    q_dec = q * decay
    k_end = k * jnp.exp(cum[..., -1:, :] - cum)
    end = decay[..., -1, :]                              # [n, ..., dk]

    def sub(s, xs):
        w_n, u0_n, q_n, a_n, k_n, end_n = xs
        u = u0_n - jnp.matmul(w_n, s, precision=HIGHEST)
        o = jnp.matmul(q_n, s, precision=HIGHEST) \
            + jnp.matmul(a_n, u, precision=HIGHEST)
        s = end_n[..., None] * s + jnp.matmul(
            jnp.swapaxes(k_n, -1, -2), u, precision=HIGHEST)
        return s, o

    s, o = jax.lax.scan(sub, s0.astype(jnp.float32),
                        (w, u0, q_dec, a_qk, k_end, end))
    o = jnp.moveaxis(o, 0, len(lead)).reshape(lead + (t, dv))
    return o, s


def causal_conv(x: jax.Array, tail: jax.Array, taps: jax.Array
                ) -> jax.Array:
    """Causal depthwise convolution over time: ``x [..., T, ch]`` after the
    ``K - 1`` rows before it, ``tail [..., K-1, ch]`` (zeros at a sequence's
    start), ``taps [K, ch]`` with the last tap on the current row:
    ``y_t = sum_j taps[j] * rows[t + j]`` over ``rows = tail ++ x``."""
    rows = jnp.concatenate([tail, x], axis=-2)
    t = x.shape[-2]
    return sum(taps[j] * rows[..., j:j + t, :] for j in range(taps.shape[0]))
