"""Pallas TPU flash attention: blockwise softmax attention, fwd + bwd.

The reference has no attention code at all (SURVEY §5.7 — models came from
an implied ModelFactory and only the layer list was touched); long-context
support in this framework is first-class, and this kernel is its native
tier (SURVEY §7.1).  ``full_attention`` (models/gpt2.py) materialises the
[T, T] score matrix in HBM; this kernel streams K/V blocks through VMEM
with an online-softmax accumulator, so attention costs O(T·D) memory at
any sequence length, and the two matmuls per block land on the MXU in one
fused pass per tile.

Three kernels:
  * forward — per Q block: stream K/V blocks, keep (m, l, acc) running
    max / normaliser / weighted sum; emits output AND the row logsumexp
    (the residual that makes the backward recomputation exact).
  * dq — per Q block: re-stream K/V, rebuild P = exp(S − lse), accumulate
    dQ = scale · (P ∘ (dO·Vᵀ − Δ)) · K.
  * dkv — per K/V block: stream Q/dO blocks, accumulate
    dV = Pᵀ·dO and dK = scale · (P ∘ (dO·Vᵀ − Δ))ᵀ · Q.

Causal masking skips fully-masked tiles at the grid level (half the work)
and masks the diagonal tile elementwise.  Crucially the skip also kills the
tile's HBM traffic: ``pl.when`` alone only skips compute — Pallas's
pipeline still DMAs every block named by the BlockSpec — so the index maps
CLAMP masked iterations to the last useful block index; Pallas issues no
copy when the block index repeats, making the causal skip save bandwidth
as well as FLOPs (this was the round-2 "advantage shrinks with T" bug: at
long T the kernel is bandwidth-bound and was streaming twice the needed
K/V).  Numerics are f32 throughout the accumulators regardless of input
dtype; outputs cast back.

Registered with the GPT-2 attention registry as ``attn_impl="flash"``.
Shapes that don't tile (T not a multiple of the block) fall back to the
XLA path — same math, so the swap is always safe.  Off-TPU the kernel runs
in Pallas interpret mode; tests pin fwd/bwd equality against
``full_attention`` on the CPU backend.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30          # finite stand-in: exp(NEG_INF - m) flushes to 0
_LANES = 128


def _block_for(t: int) -> int:
    """Largest supported Q block size dividing T (0 = no tiling, fall
    back)."""
    for b in (512, 256, 128, 64):
        if t % b == 0 and t >= b:
            return b
    return 0


def _blocks_for(t: int) -> Tuple[int, int]:
    """(bq, bk) tile sizes: large tiles — per-tile bookkeeping and
    online-softmax rescales amortise, and the K loop (inner, streaming)
    benefits most, so bk runs up to 1024.  The choice against smaller
    tiles and against XLA full attention is not measured (PERF.md)."""
    bq = _block_for(t)
    if not bq:
        return 0, 0
    bk = bq
    for cand in (1024, 512):
        if t % cand == 0 and t >= cand and cand > bk:
            bk = cand
            break
    return bq, bk


MAX_HEAD_DIM = 512


def supports_flash(t: int, d: int) -> bool:
    """THE kernel-eligibility predicate — every dispatch site (the public
    flash_attention wrapper, ring attention's chunk path) must use this so
    the fallback condition can never drift from the kernel's real
    constraints."""
    return _block_for(t) != 0 and d <= MAX_HEAD_DIM


def _dot(a: jax.Array, b: jax.Array, trans_a: bool = False,
         trans_b: bool = False) -> jax.Array:
    """f32-accumulating matmul for the MXU."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=jnp.float32
    )


def _causal_mask(qi, ki, bq: int, bk: int) -> jax.Array:
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0]
        s = _dot(q, k_ref[0], trans_b=True) * scale          # [bq, bk] f32
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, NEG_INF)
        m_prev = m_ref[:, :1]                                # [bq, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                               # masked -> 0
        corr = jnp.exp(m_prev - m_cur)
        l_ref[:] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape,
        )
        acc_ref[:] = acc_ref[:] * corr + _dot(
            p.astype(v_ref.dtype), v_ref[0]
        )
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)

    if causal:
        # Tiles entirely above the diagonal contribute nothing: skip.
        pl.when(ki * bk <= (qi + 1) * bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l)          # [bq, 1] column


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
               bq: int, bk: int, interpret: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """[BH, T, D] x3 -> (o [BH, T, D], lse f32[BH, T])."""
    bh, t, d = q.shape
    nq, nk = t // bq, t // bk
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
    )
    # Masked-tile DMA clamp (see module docstring): causal Q block i needs
    # K/V blocks j ≤ jmax(i); beyond that the index pins to jmax so the
    # pipeline issues no further copies for this row.
    if causal:
        kv_idx = lambda b, i, j: (
            b, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0
        )
    else:
        kv_idx = lambda b, i, j: (b, j, 0)
    o, lse_col = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_idx),
            pl.BlockSpec((1, bk, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            # lse rides as a [BH, T, 1] column: a (1, bq) row block would
            # violate Mosaic's (8, 128) tiling rule (sublane dim 1), while
            # (1, bq, 1) is legal because the lane dim equals the array's.
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse_col[..., 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale: float, causal: bool, bq: int, bk: int,
               nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0]
        s = _dot(q, k_ref[0], trans_b=True) * scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, NEG_INF)
        lse = lse_ref[0]                                      # [bq, 1]
        p = jnp.exp(s - lse)                                  # [bq, bk]
        dp = _dot(do_ref[0], v_ref[0], trans_b=True)          # [bq, bk] f32
        ds = p * (dp - delta_ref[0])
        dq_acc[:] += _dot(ds.astype(k_ref.dtype), k_ref[0]) * scale

    if causal:
        pl.when(ki * bk <= (qi + 1) * bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                causal: bool, bq: int, bk: int, nq: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0]
        s = _dot(q, k_ref[0], trans_b=True) * scale           # [bq, bk]
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bq, bk), s, NEG_INF)
        lse = lse_ref[0]                                      # [bq, 1]
        p = jnp.exp(s - lse)
        do = do_ref[0]
        dv_acc[:] += _dot(p.astype(do.dtype), do, trans_a=True)
        dp = _dot(do, v_ref[0], trans_b=True)
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += _dot(ds.astype(q.dtype), q, trans_a=True) * scale

    if causal:
        pl.when((qi + 1) * bq - 1 >= ki * bk)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def _flash_bwd(q, k, v, o, lse, do, causal: bool, bq: int, bk: int,
               interpret: bool, dlse=None):
    bh, t, d = q.shape
    nq, nk = t // bq, t // bk
    scale = 1.0 / math.sqrt(d)
    # Δ_i = Σ_d dO_i·O_i — one fused XLA reduction, reused by both kernels.
    # A logsumexp cotangent (ring-attention chunk merging differentiates
    # through the lse-dependent combine weights) enters the shared
    # dS = P ∘ (dP − Δ) term with opposite sign: dS += P ∘ dlse, i.e.
    # Δ_eff = Δ − dlse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # Column layout for the same Mosaic tiling reason as the forward's lse.
    lse_col = lse[..., None]
    delta_col = delta[..., None]

    # Same masked-tile DMA clamps as the forward (module docstring).
    if causal:
        kv_idx = lambda b, i, j: (
            b, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0
        )
        q_idx = lambda b, j, i: (b, jnp.maximum(i, (j * bk) // bq), 0)
    else:
        kv_idx = lambda b, i, j: (b, j, 0)
        q_idx = lambda b, j, i: (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_idx),
            pl.BlockSpec((1, bk, d), kv_idx),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse_col, delta_col)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_idx),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), q_idx),
            pl.BlockSpec((1, bq, 1), q_idx),
            pl.BlockSpec((1, bq, 1), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_col, delta_col)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry
# ---------------------------------------------------------------------------


def _interpret() -> bool:
    # The shared ops-package interpret helper (one gate for all four
    # kernels); kept as a module-local name because the custom_vjp
    # plumbing below calls it at every trace.
    from trustworthy_dl_tpu.ops import pallas_interpret

    return pallas_interpret()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal: bool, bq: int, bk: int):
    o, _ = _flash_fwd(q, k, v, causal, bq, bk, _interpret())
    return o


def _flash_vjp_fwd(q, k, v, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, causal, bq, bk, _interpret())
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, bq, bk, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal, bq, bk, _interpret())
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_chunk(q, k, v, causal: bool, bq: int, bk: int):
    """[BH, T, D] -> (o, lse f32[BH, T]) with full AD support INCLUDING the
    lse output — the building block for ring attention's per-rotation
    chunk, whose cross-chunk combine weights depend on lse."""
    return _flash_fwd(q, k, v, causal, bq, bk, _interpret())


def _flash_chunk_vjp_fwd(q, k, v, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, causal, bq, bk, _interpret())
    return (o, lse), (q, k, v, o, lse)


def _flash_chunk_vjp_bwd(causal, bq, bk, res, cot):
    q, k, v, o, lse = res
    do, dlse = cot
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal, bq, bk,
                            _interpret(), dlse=dlse)
    return dq, dk, dv


flash_chunk.defvjp(_flash_chunk_vjp_fwd, _flash_chunk_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """[B, H, T, D] (or [BH, T, D]) blockwise flash attention.

    Drop-in for ``full_attention``: same math (pinned by
    tests/test_flash_attention.py), O(T·D) memory instead of O(T²).
    Non-tiling sequence lengths fall back to the XLA path.
    """
    from trustworthy_dl_tpu.models.gpt2 import full_attention

    squeeze = q.ndim == 3
    if squeeze:
        q, k, v = q[None], k[None], v[None]
    b, h, t, d = q.shape
    if not supports_flash(t, d):
        out = full_attention(q, k, v, causal)
        return out[0] if squeeze else out
    bq, bk = _blocks_for(t)

    merge = lambda a: a.reshape(b * h, t, d)
    out = _flash(merge(q), merge(k), merge(v), causal, bq, bk)
    out = out.reshape(b, h, t, d)
    return out[0] if squeeze else out


__all__ = ["flash_attention", "flash_chunk", "supports_flash"]
