"""The Mamba-2 mixer's state-space recurrence (the state-space duality of
arXiv:2405.21060): a layer whose cache is a STATE a head, not keys and
values.

Per head ``h`` with a scalar decay ``A_h < 0``, head width ``P`` and state
width ``N``, the state ``h_t [P, N]`` (float32) follows

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t + D x_t

``x_t [P]`` is the head's slice of the convolved input, ``dt_t > 0`` its step
(a softplus), and ``B_t, C_t [N]`` are shared by the heads of a GROUP (head
``h`` reads group ``h // (heads / groups)``).  Two spellings of the same
mathematics:

* :func:`ssd_step`: the recurrence itself, one position; decode runs it for
  every slot (``ops/ssm_step.py`` is its kernel).
* :func:`ssd_chunk`: the chunked form prefill runs.  Inside a block of ``Q``
  positions with cumulative log decay ``c_t = sum_{s <= t} dt_s A`` the
  outputs are a masked product, ``y_t = sum_{s <= t} (C_t . B_s)
  exp(c_t - c_s) dt_s x_s``, plus what the state at the block's start
  contributes, ``exp(c_t) h_0 C_t``; the block's end state is
  ``exp(c_Q) h_0 + sum_s exp(c_Q - c_s) dt_s x_s B_s^T``, carried to the
  next block by a scan and across calls by the caller.

Every exponent taken is <= 0 (masked BEFORE the exponential where it would
not be), so nothing overflows.  The state's products run at ``HIGHEST``
precision in float32: the recurrence feeds its own rounding back for
thousands of positions.  A position whose ``dt`` is 0 leaves the state as it
was: that is how the callers mask padded rows and idle slots.

Around the recurrence: :func:`gated_group_norm`, the output's RMSNorm over
groups of channels after the gate (``norm_before_gate`` false).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _by_head(bc: jax.Array, heads: int) -> jax.Array:
    """``[..., G, N]`` -> ``[..., H, N]``: each head its group's row."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssd_step(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, h: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One position for every row: ``x [R, H, P]``, ``dt [R, H]``, ``a, d
    [H]``, ``b, c [R, G, N]``, ``h [R, H, P, N]`` -> ``(y [R, H, P], h)``."""
    heads = x.shape[-2]
    h = jnp.exp(dt * a)[..., None, None] * h \
        + (dt[..., None] * x)[..., None] * _by_head(b, heads)[..., None, :]
    y = jnp.einsum("rhpn,rhn->rhp", h, _by_head(c, heads), precision=HIGHEST)
    return y + d[:, None] * x, h


def ssd_chunk(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
              c: jax.Array, d: jax.Array, h0: jax.Array, block: int
              ) -> Tuple[jax.Array, jax.Array]:
    """The chunked form over ``T`` positions: ``x [R, T, H, P]``, ``dt [R,
    T, H]``, ``b, c [R, T, G, N]``, ``h0 [R, H, P, N]`` -> ``(y [R, T, H,
    P], h_T)``, equal to ``T`` calls of :func:`ssd_step`.  ``T`` is whole
    blocks of ``block`` positions (clipped to ``T``)."""
    r, t, heads, p = x.shape
    groups = b.shape[-2]
    q = min(block, t)
    if t % q:
        raise ValueError(f"{t} positions are not whole blocks of {q}")
    n = t // q
    blocks = lambda z: z.reshape((r, n, q) + z.shape[2:])
    x_, dt_, b_, c_ = (blocks(z.astype(jnp.float32)) for z in (x, dt, b, c))
    cum = jnp.cumsum(dt_ * a, axis=2)                     # [R, n, Q, H]
    # Inside a block: (C_i . B_j) exp(c_i - c_j) for j <= i, a group's
    # scores shared by its heads.
    i = jnp.arange(q)
    seen = (i[:, None] >= i[None, :])[..., None]
    decay = jnp.exp(jnp.where(
        seen, cum[:, :, :, None, :] - cum[:, :, None, :, :], -jnp.inf))
    scores = jnp.einsum("rnigs,rnjgs->rnijg", c_, b_, precision=HIGHEST)
    pairs = jnp.repeat(scores, heads // groups, axis=-1) * decay
    y = jnp.einsum("rnijh,rnjh,rnjhp->rnihp", pairs, dt_, x_,
                   precision=HIGHEST)
    # Each block's inputs carried to its end, then the blocks chained.
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dt_         # [R, n, Q, H]
    states = jnp.einsum("rnjh,rnjhs,rnjhp->rnhps", to_end,
                        _by_head(b_, heads), x_, precision=HIGHEST)
    end = jnp.exp(cum[:, :, -1, :])                         # [R, n, H]

    def chain(h, at):
        state, e = at
        return e[..., None, None] * h + state, h

    h_t, starts = jax.lax.scan(
        chain, h0.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(end, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                     # [R, n, H, P, N]
    y = y + jnp.einsum("rnihs,rnhps->rnihp", _by_head(c_, heads), starts,
                       precision=HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(r, t, heads, p) + d[:, None] * x, h_t


def gated_group_norm(scale: jax.Array, y: jax.Array, z: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """``RMSNorm(y * SiLU(z))`` over ``groups`` equal groups of the last
    axis, then ``scale``: the gate applied BEFORE the norm."""
    g = y * jax.nn.silu(z)
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * scale
