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

Causal masking works at two levels (``_blocks_for`` has the sizes).  The
grid's tiles are square: one above the diagonal is skipped whole, and the
skip also kills the tile's HBM traffic: ``pl.when`` alone only skips
compute — Pallas's pipeline still DMAs every block named by the BlockSpec —
so the index maps CLAMP masked iterations to the last useful block index;
Pallas issues no copy when the block index repeats (this was the round-2
"advantage shrinks with T" bug: at long T the kernel was streaming twice
the needed K/V).  A tile ON the diagonal (at T <= 1,024 the only tile) is
walked in sub-tiles by a static unroll: those above the diagonal are left
out, those it crosses are masked elementwise, those under it take the plain
path.  Until PR 28 the diagonal tile was scored whole, which at T = 1,024
was every pair, masked half included.  Numerics are f32 throughout the
accumulators regardless of input dtype; outputs cast back.

Registered with the GPT-2 attention registry as ``attn_impl="flash"``.
Shapes that don't tile (T not a multiple of the block) fall back to the
XLA path — same math, so the swap is always safe.  Off-TPU the kernel runs
in Pallas interpret mode; tests pin fwd/bwd equality against
``full_attention`` on the CPU backend.
"""

from __future__ import annotations

import functools
import math
from typing import Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30          # finite stand-in: exp(NEG_INF - m) flushes to 0
_LANES = 128
MAX_HEAD_DIM = 512


class Blocks(NamedTuple):
    """The schedule of one (T, d): what a grid step holds and how a
    kernel walks it."""

    tile: int   # edge of the square [tile, tile] score tile of a grid step
    sub: int    # edge of the [sub, sub] sub-tiles a kernel walks it in


def _block_for(t: int, cap: int = 1024) -> int:
    """Largest supported block size up to ``cap`` dividing T (0 = no
    tiling, fall back)."""
    for b in (1024, 512, 256, 128, 64):
        if b <= cap and t % b == 0 and t >= b:
            return b
    return 0


def _blocks_for(t: int, d: int) -> Optional[Blocks]:
    """THE place that decides tile sizes, from static shapes alone (None =
    T does not tile).

    Grid tiles are large and square.  A grid step costs about 0.35 us
    whatever it holds, and up to T = 1,024 every operand block, the
    lane-padded lse and delta columns included, is read once a (batch,
    head).  The causal saving comes from INSIDE the tile: the kernels walk
    it in [sub, sub] sub-tiles (``_sub_tiles``), leave out those above the
    diagonal and mask only those it crosses.  At T = 1,024 that is 10
    sub-tiles of 16 (1.249 times the causal pairs; one [512, 1024] tile a
    grid step, the schedule before PR 28, scored 1.998 times them).  The
    tile shrinks with the head width so that the backward's blocks (four
    operands, two outputs, two f32 accumulators, double-buffered, f32 at
    worst) stay inside Mosaic's 16 MiB of scoped VMEM.

    ``sub`` = 256 by the chip's time at (96, 1024, 64) and (12, 8192, 64),
    forward + backward, against 128 and 512 and unequal edges (PERF.md §6,
    PR 28); other head widths take the same sizes unmeasured."""
    tile = _block_for(t, cap=1024 if d <= 128 else 512 if d <= 256 else 256)
    if not tile:
        return None
    return Blocks(tile, sub=min(tile, 256))


def supports_flash(t: int, d: int) -> bool:
    """THE kernel-eligibility predicate — every dispatch site (the public
    flash_attention wrapper, ring attention's chunk path) must use this so
    the fallback condition can never drift from the kernel's real
    constraints."""
    return d <= MAX_HEAD_DIM and _blocks_for(t, d) is not None


# ---------------------------------------------------------------------------
# The causal schedule: which tiles a grid step scores, which sub-tiles of
# a tile, and where the mask is applied.  The kernels and the counter
# (``scheduled_pairs``) both read THESE functions.
# ---------------------------------------------------------------------------


def _tile_cases(qi, ki, causal: bool):
    """((diagonal, live), ...) for the grid tile of Q block ``qi`` and K
    block ``ki`` (ints or traced): the walk it takes and whether it runs.
    A causal tile above the diagonal is in no case: the grid visits it and
    does nothing (its DMA is clamped away by the index maps)."""
    if not causal:
        return ((False, True),)
    return ((False, ki < qi), (True, ki == qi))


def _sub_tiles(blocks: Blocks, diagonal: bool, own_is_q: bool
               ) -> Tuple[Tuple[int, Tuple[Tuple[int, bool], ...]], ...]:
    """The walk of one grid tile: for each sub-block of the operand the
    kernel accumulates for (Q rows in forward and dq, ``own_is_q``; K rows
    in dkv), its offset in the tile and the sub-blocks of the other
    operand scored against it as (offset, masked).  Off the diagonal every
    sub-tile is scored, none masked.  On it a sub-tile no query of which
    sees any of its keys is left out, and only one the diagonal crosses is
    masked."""
    tile, sub = blocks
    walk = []
    for own in range(0, tile, sub):
        inner = []
        for other in range(0, tile, sub):
            r0, c0 = (own, other) if own_is_q else (other, own)
            if diagonal and r0 + sub - 1 < c0:     # last query < first key
                continue
            inner.append((other, diagonal and r0 < c0 + sub - 1))
        walk.append((own, tuple(inner)))
    return tuple(walk)


def scheduled_sub_tiles(t: int, d: int, causal: bool, own_is_q: bool = True
                        ) -> Iterator[Tuple[int, int, int, int, bool]]:
    """Every score sub-tile one (batch, head) computes, as (first query,
    queries, first key, keys, masked) in sequence positions; ``own_is_q``
    picks the forward's and dq's walk or dkv's."""
    blocks = _blocks_for(t, d)
    n = t // blocks.tile
    for qi in range(n):
        for ki in range(n):
            for diagonal, live in _tile_cases(qi, ki, causal):
                if not live:
                    continue
                for own, inner in _sub_tiles(blocks, diagonal, own_is_q):
                    for other, masked in inner:
                        r0, c0 = (own, other) if own_is_q else (other, own)
                        yield (qi * blocks.tile + r0, blocks.sub,
                               ki * blocks.tile + c0, blocks.sub, masked)


def scheduled_pairs(t: int, d: int, causal: bool) -> int:
    """Query-key pairs one (batch, head) SCORES under the schedule (the
    algorithm needs t (t + 1) / 2 of them when causal): what the forward
    and each backward kernel form, exponentiate and multiply."""
    return sum(nr * nc for _, nr, _, nc, _ in scheduled_sub_tiles(t, d, causal))


def _dot(a: jax.Array, b: jax.Array, trans_a: bool = False,
         trans_b: bool = False) -> jax.Array:
    """f32-accumulating matmul for the MXU."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), preferred_element_type=jnp.float32
    )


def _mask_above_diagonal(s: jax.Array, r0: int, c0: int) -> jax.Array:
    """``s`` [queries from r0, keys from c0] of a diagonal tile, NEG_INF
    where the key lies ahead of the query."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + (r0 - c0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _lanes(x: jax.Array, width: int) -> jax.Array:
    """A lane-replicated [rows, 128] statistic at ``width`` lanes."""
    if width <= _LANES:
        return x[:, :width]
    if width % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], width))
    return pltpu.repeat(x, width // _LANES, axis=1)


def _tile_ids(n: int):
    """(outer, inner) tile indices of this grid step; plain zeros where
    the grid has one tile, so that the schedule resolves while tracing."""
    return (pl.program_id(1), pl.program_id(2)) if n > 1 else (0, 0)


def _run_tile(qi, ki, causal: bool, compute) -> None:
    for diagonal, live in _tile_cases(qi, ki, causal):
        pl.when(live)(functools.partial(compute, diagonal))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *carry, scale: float,
                causal: bool, blocks: Blocks, n: int):
    """One [tile, tile] grid step.  ``carry`` (acc, m, l scratch) holds the
    online softmax between the K tiles of one Q tile and is not there when
    the sequence is one tile: the rows are then finished in registers."""
    qi, ki = _tile_ids(n)
    sub, d = blocks.sub, q_ref.shape[-1]

    if carry:
        acc_ref, m_ref, l_ref = carry

        @pl.when(ki == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    def _finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows] = (acc / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[0, rows] = (m + jnp.log(l))[:, :1]      # [rows, 1] column

    def _compute(diagonal: bool):
        for r0, inner in _sub_tiles(blocks, diagonal, own_is_q=True):
            rows = pl.ds(r0, sub)
            q = q_ref[0, rows]
            # The running max and normaliser ride lane-replicated,
            # [sub, 128]: as columns loaded from scratch they were
            # broadcast over the lanes again in every sub-tile, and the
            # forward took twice the time (my chip run, PR 28).
            if carry:
                m, l, acc = m_ref[rows], l_ref[rows], acc_ref[rows]
            else:
                m = jnp.full((sub, _LANES), NEG_INF, jnp.float32)
                l = jnp.zeros((sub, _LANES), jnp.float32)
                acc = jnp.zeros((sub, d), jnp.float32)
            for c0, masked in inner:
                cols = pl.ds(c0, sub)
                s = _dot(q, k_ref[0, cols], trans_b=True) * scale  # f32
                if masked:
                    s = _mask_above_diagonal(s, r0, c0)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - _lanes(m_new, sub))          # masked -> 0
                corr = jnp.exp(m - m_new)
                l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * _lanes(corr, d) + _dot(p.astype(v_ref.dtype),
                                                   v_ref[0, cols])
                m = m_new
            if carry:
                acc_ref[rows], m_ref[rows], l_ref[rows] = acc, m, l
            else:
                _finish(rows, m, l, acc)

    _run_tile(qi, ki, causal, _compute)

    if carry:
        @pl.when(ki == n - 1)
        def _finalize():
            _finish(slice(None), m_ref[:], l_ref[:], acc_ref[:])


def _kv_index(causal: bool):
    """Index map of the K/V (streamed) blocks over grid (b, i, j).  Masked
    tiles' DMA is clamped away (see module docstring): causal Q tile i
    needs K/V tiles j <= i; beyond that the index pins to i so the
    pipeline issues no further copies for this row."""
    if causal:
        return lambda b, i, j: (b, jnp.minimum(j, i), 0)
    return lambda b, i, j: (b, j, 0)


def _own_index(b, i, j):
    return (b, i, 0)


@functools.partial(
    jax.jit, static_argnames=("causal", "blocks", "interpret")
)
def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
               blocks: Blocks, interpret: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """[BH, T, D] x3 -> (o [BH, T, D], lse f32[BH, T])."""
    bh, t, d = q.shape
    tile = blocks.tile
    n = t // tile
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(d), causal=causal, blocks=blocks,
        n=n,
    )
    kv_idx = _kv_index(causal)
    o, lse_col = pl.pallas_call(
        kernel,
        grid=(bh, n, n),
        in_specs=[
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, d), kv_idx),
            pl.BlockSpec((1, tile, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, d), _own_index),
            # lse rides as a [BH, T, 1] column: a (1, tile) row block would
            # violate Mosaic's (8, 128) tiling rule (sublane dim 1), while
            # (1, tile, 1) is legal because the lane dim equals the array's.
            pl.BlockSpec((1, tile, 1), _own_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile, d), jnp.float32),
            pltpu.VMEM((tile, _LANES), jnp.float32),
            pltpu.VMEM((tile, _LANES), jnp.float32),
        ] if n > 1 else [],
        interpret=interpret,
    )(q, k, v)
    return o, lse_col[..., 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _p_and_ds(q, k, v, do, lse, delta, scale: float, mask_at):
    """One score sub-tile of the backward: P = exp(S - lse) rebuilt from
    the saved row logsumexp and dS = P o (dO V^T - delta), both f32
    [queries, keys].  ``mask_at`` is (r0, c0) where the diagonal crosses
    the sub-tile, else None."""
    s = _dot(q, k, trans_b=True) * scale
    if mask_at is not None:
        s = _mask_above_diagonal(s, *mask_at)
    p = jnp.exp(s - lse)
    dp = _dot(do, v, trans_b=True)
    return p, p * (dp - delta)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *carry, scale: float, causal: bool, blocks: Blocks, n: int):
    qi, ki = _tile_ids(n)
    sub = blocks.sub

    if carry:
        (dq_acc,) = carry

        @pl.when(ki == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(diagonal: bool):
        for r0, inner in _sub_tiles(blocks, diagonal, own_is_q=True):
            rows = pl.ds(r0, sub)
            q, do = q_ref[0, rows], do_ref[0, rows]
            lse, delta = lse_ref[0, rows], delta_ref[0, rows]   # [sub, 1]
            dq = jnp.zeros((sub, q.shape[-1]), jnp.float32)
            for c0, masked in inner:
                k = k_ref[0, pl.ds(c0, sub)]
                _, ds = _p_and_ds(q, k, v_ref[0, pl.ds(c0, sub)], do, lse,
                                  delta, scale, (r0, c0) if masked else None)
                dq = dq + _dot(ds.astype(k.dtype), k)
            if carry:
                dq_acc[rows] += dq * scale
            else:
                dq_ref[0, rows] = (dq * scale).astype(dq_ref.dtype)

    _run_tile(qi, ki, causal, _compute)

    if carry:
        @pl.when(ki == n - 1)
        def _finalize():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *carry, scale: float, causal: bool,
                blocks: Blocks, n: int):
    ki, qi = _tile_ids(n)
    sub = blocks.sub

    if carry:
        dk_acc, dv_acc = carry

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(diagonal: bool):
        for c0, inner in _sub_tiles(blocks, diagonal, own_is_q=False):
            cols = pl.ds(c0, sub)
            k, v = k_ref[0, cols], v_ref[0, cols]
            dk = jnp.zeros((sub, k.shape[-1]), jnp.float32)
            dv = jnp.zeros((sub, k.shape[-1]), jnp.float32)
            for r0, masked in inner:
                rows = pl.ds(r0, sub)
                q, do = q_ref[0, rows], do_ref[0, rows]
                p, ds = _p_and_ds(q, k, v, do, lse_ref[0, rows],
                                  delta_ref[0, rows], scale,
                                  (r0, c0) if masked else None)
                dv = dv + _dot(p.astype(do.dtype), do, trans_a=True)
                dk = dk + _dot(ds.astype(q.dtype), q, trans_a=True)
            if carry:
                dk_acc[cols] += dk * scale
                dv_acc[cols] += dv
            else:
                dk_ref[0, cols] = (dk * scale).astype(dk_ref.dtype)
                dv_ref[0, cols] = dv.astype(dv_ref.dtype)

    _run_tile(qi, ki, causal, _compute)

    if carry:
        @pl.when(qi == n - 1)
        def _finalize():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "blocks", "interpret")
)
def _flash_bwd(q, k, v, o, lse, do, causal: bool, blocks: Blocks,
               interpret: bool, dlse=None):
    bh, t, d = q.shape
    tile = blocks.tile
    n = t // tile
    static = dict(scale=1.0 / math.sqrt(d), causal=causal, blocks=blocks, n=n)
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

    # Same masked-tile DMA clamps as the forward (module docstring): K
    # tile j needs the Q-side tiles i >= j.
    kv_idx = _kv_index(causal)
    if causal:
        q_idx = lambda b, j, i: (b, jnp.maximum(i, j), 0)
    else:
        q_idx = lambda b, j, i: (b, i, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(bh, n, n),
        in_specs=[
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, d), kv_idx),
            pl.BlockSpec((1, tile, d), kv_idx),
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, 1), _own_index),
            pl.BlockSpec((1, tile, 1), _own_index),
        ],
        out_specs=pl.BlockSpec((1, tile, d), _own_index),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)] if n > 1 else [],
        interpret=interpret,
    )(q, k, v, do, lse_col, delta_col)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(bh, n, n),
        in_specs=[
            pl.BlockSpec((1, tile, d), q_idx),
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, d), q_idx),
            pl.BlockSpec((1, tile, 1), q_idx),
            pl.BlockSpec((1, tile, 1), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, d), _own_index),
            pl.BlockSpec((1, tile, d), _own_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile, d), jnp.float32),
            pltpu.VMEM((tile, d), jnp.float32),
        ] if n > 1 else [],
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


def _fwd(q, k, v, causal: bool):
    _, t, d = q.shape
    return _flash_fwd(q, k, v, causal, _blocks_for(t, d), _interpret())


def _bwd(causal: bool, res, do, dlse=None):
    q, k, v, o, lse = res
    _, t, d = q.shape
    return _flash_bwd(q, k, v, o, lse, do, causal, _blocks_for(t, d),
                      _interpret(), dlse=dlse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal: bool):
    return _fwd(q, k, v, causal)[0]


def _flash_vjp_fwd(q, k, v, causal):
    o, lse = _fwd(q, k, v, causal)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_vjp_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_chunk(q, k, v, causal: bool):
    """[BH, T, D] -> (o, lse f32[BH, T]) with full AD support INCLUDING the
    lse output — the building block for ring attention's per-rotation
    chunk, whose cross-chunk combine weights depend on lse.  The caller
    checks ``supports_flash``; the tiles come from ``_blocks_for``."""
    return _fwd(q, k, v, causal)


def _flash_chunk_vjp_fwd(q, k, v, causal):
    o, lse = _fwd(q, k, v, causal)
    return (o, lse), (q, k, v, o, lse)


def _flash_chunk_vjp_bwd(causal, res, cot):
    return _bwd(causal, res, *cot)


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
    merge = lambda a: a.reshape(b * h, t, d)
    out = _flash(merge(q), merge(k), merge(v), causal)
    out = out.reshape(b, h, t, d)
    return out[0] if squeeze else out


__all__ = ["flash_attention", "flash_chunk", "scheduled_pairs",
           "scheduled_sub_tiles", "supports_flash"]
