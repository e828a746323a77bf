"""Chunked-prefill latent attention over the paged LATENT cache, in the
EXPANDED form: the chunk program's half of latent attention
(``models/decoder.py`` ``"mla"``; the decode program's half is the paged
kernel's latent shape in ``ops/paged_attention.py``, the absorbed form).

The cache keeps ONE row ``c~ || k_r || 0`` a position and no per-head K or V.
A decode step has one query a head, so it absorbs ``W_kb`` into the query and
reads the rows as they lie.  A chunk has a thousand queries a head: absorbed,
every (query, cached position) pair costs ``2 x (576 + 512)`` operations a
head where the per-head widths cost ``2 x (192 + 128)``, 3.4 times fewer.  On
the chip, a 1,024-row chunk over 4k / 16k / 30k cached positions (PERF.md
section 6): the absorbed paged kernel 3.70 / 10.9 / 19.2 ms, an XLA loop that
expands a block at a time 1.89 / 5.64 / 10.0 ms, this kernel 1.81 / 4.43 /
7.68 ms.  So the chunk EXPANDS, inside the kernel, a block at a time, and no
per-head K or V ever reaches HBM:

* grid ``(H / g head groups, NBPS logical blocks)``, the blocks innermost; the
  block table, the chunk's first position, its last useful block and the
  layer ride as scalar prefetch, as in the paged kernels: logical block ``j``
  maps to physical block ``table[0, min(j, jmax)]`` of the layer, past
  ``jmax`` the index repeats (no copy) and the body is skipped;
* a step copies ONE block ``[BLOCK, lanes]`` and the ``g`` heads' columns of
  ``W_kb`` (resident across the blocks of a group), expands ``c~ W_kb`` for
  those heads, and runs the online softmax of ALL the chunk's queries of each
  of the ``g`` heads against it: ONE product ``(k_n || k_r) . (q_n || q_r)``
  a head (``k_r`` the row's lanes behind ``rank``, shared by the heads, the
  pool's zero padding included so that the window is whole lane columns), then
  ``v . p``;
* a block is expanded once a head GROUP, not once a query tile: the chunk's
  queries of a group are resident (``g`` from :func:`head_group`, the most
  heads whose blocks fit ``paged_attention.VMEM_BLOCK_BUDGET``: 2 at a chunk
  of 1,024), so the expansion is a quarter of a step's products;
* the scores are held TRANSPOSED, cached positions down the sublanes and
  queries along the lanes: the softmax's maximum and sum over a block are
  then elementwise between registers.  Row-major (the paged kernels'
  spelling) they are lane reductions, and those, not the MXU, were what the
  first spelling of this kernel and the absorbed one spent their time in
  (2.99 / 9.47 / 17.0 ms before the transposition, whatever else was taken
  out of the step).

bfloat16 operands where the pool is bfloat16, float32 sums.  The call carries
the name ``latent_prefill`` on the device trace.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trustworthy_dl_tpu.ops import pallas_interpret
from trustworthy_dl_tpu.ops.paged_attention import (NEG_INF,
                                                    VMEM_BLOCK_BUDGET, _dot,
                                                    _tile_bytes)


def step_bytes(g: int, t: int, *, nope: int, value: int, rank: int,
               lanes: int, block_size: int, dtype) -> int:
    """VMEM bytes one grid step pins with ``g`` heads resident: the
    pipelined blocks (the heads' queries ``[g, t, nope + lanes - rank]``,
    their key columns of ``W_kb`` and their value columns transposed, one
    block of the pool, the output ``[g, value, t]``), double buffered, and
    the float32 scratch of the online softmax (the sums ``[g, value, t]``;
    the running maximum and denominator lie along the lanes, a sublane tile
    each)."""
    blocks = (g * _tile_bytes(t, nope + lanes - rank, dtype)
              + _tile_bytes(rank, g * nope, dtype)
              + _tile_bytes(g * value, rank, dtype)
              + _tile_bytes(block_size, lanes, dtype)
              + g * _tile_bytes(value, t, dtype))
    scratch = g * (_tile_bytes(value, t, jnp.float32)
                   + 2 * _tile_bytes(8, t, jnp.float32))
    return 2 * blocks + scratch


def head_group(heads: int, t: int, **shape) -> int:
    """THE rule for what a step holds: the most heads (a divisor of
    ``heads``) whose :func:`step_bytes` fit the budget; 0 where not even one
    head's do (:func:`supports_latent_prefill` refuses that geometry)."""
    return next((g for g in range(heads, 0, -1) if heads % g == 0
                 and step_bytes(g, t, **shape) <= VMEM_BLOCK_BUDGET), 0)


def supports_latent_prefill(*, heads: int, rows: int, nope: int, value: int,
                            rank: int, lanes: int, block_size: int, dtype,
                            interpret: bool) -> bool:
    """Whether the kernel takes a chunk of ``rows`` positions at this
    geometry: compiled, one head's resident blocks have to fit the budget
    and the windows have to be whole 128-lane columns (``rank``, ``nope``,
    ``value``, the row's lanes and the chunk's rows, which lie along the
    lanes of the scores); the interpreter takes any."""
    if interpret:
        return True
    if any(n % 128 for n in (rank, nope, value, lanes, rows)):
        return False
    return head_group(heads, rows, nope=nope, value=value, rank=rank,
                      lanes=lanes, block_size=block_size, dtype=dtype) > 0


def _kernel(table_ref, start_ref, jmax_ref, layer_ref, q_ref, wk_ref,
            wvt_ref, pool_ref, o_ref, acc_ref, m_ref, l_ref, *, bsz: int,
            g: int, rank: int, nope: int, value: int):
    """One (head group, logical block) step: expand the block for the
    group's heads, then each head's online softmax over all the chunk's
    queries.  The scores are held TRANSPOSED, ``[bsz, t]``: the softmax's
    maximum and sum over the cached positions then run down the sublanes
    (elementwise between registers) and not across the lanes, whose
    reductions (seven rotate-and-combine steps a register) are what the
    row-major spelling spends its time in; every product is plain or has
    its right operand transposed, which the MXU takes as it is."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    jmax = jmax_ref[0]
    # A block that ends at or before the chunk's first query is visible to
    # every query: no mask to build or apply (all but the chunk's own
    # blocks).
    whole = (j + 1) * bsz - 1 <= start_ref[0]

    def compute(masked: bool) -> None:
        t = q_ref.shape[1]
        rows = pool_ref[0, 0]                            # [bsz, lanes]
        latent = rows[:, :rank]
        k_n = _dot(latent, wk_ref[...]).astype(rows.dtype)   # [bsz, g*nope]
        v_t = _dot(wvt_ref[...], latent, trans_b=True).astype(
            rows.dtype)                                  # [g*value, bsz]
        k_r = rows[:, rank:]                             # k_r || 0
        if masked:
            kpos = j * bsz + jax.lax.broadcasted_iota(
                jnp.int32, (bsz, t), 0)
            qpos = start_ref[0] + jax.lax.broadcasted_iota(
                jnp.int32, (bsz, t), 1)
            visible = kpos <= qpos
        for i in range(g):
            # ONE product a head: k_n || k_r against q_n || q_r (the queries
            # arrive scaled), so the two parts are summed inside the MXU.
            k = jnp.concatenate([k_n[:, i * nope:(i + 1) * nope], k_r],
                                axis=1)
            s = _dot(k, q_ref[i], trans_b=True)          # [bsz, t] f32
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[i, :1]                        # [1, t]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_cur)
            corr = jnp.exp(m_prev - m_cur)
            l_ref[i] = jnp.broadcast_to(
                l_ref[i, :1] * corr + jnp.sum(p, axis=0, keepdims=True),
                l_ref.shape[1:])
            acc_ref[i] = acc_ref[i] * corr + _dot(
                v_t[i * value:(i + 1) * value], p.astype(rows.dtype))
            m_ref[i] = jnp.broadcast_to(m_cur, m_ref.shape[1:])

    pl.when((j <= jmax) & whole)(lambda: compute(False))
    pl.when((j <= jmax) & jnp.logical_not(whole))(lambda: compute(True))

    @pl.when(j == jmax)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "nope"))
def _latent_prefill_call(q: jax.Array, w_kb: jax.Array, pool: jax.Array,
                         table: jax.Array, start: jax.Array,
                         jmax: jax.Array, layer: jax.Array, interpret: bool,
                         nope: int) -> jax.Array:
    """q ``[H, T, nope + lanes - rank]`` (scaled) x ``W_kb [rank, H x (nope
    + value)]`` x the STACKED pool ``[L, NB, BLOCK, lanes]`` at ``layer``
    i32[1] -> ``[H, value, T]`` (transposed, as the kernel holds it)."""
    heads, t, width = q.shape
    lanes, bsz = pool.shape[3], pool.shape[2]
    rank = w_kb.shape[0]
    value = w_kb.shape[1] // heads - nope
    g = head_group(heads, t, nope=nope, value=value, rank=rank, lanes=lanes,
                   block_size=bsz, dtype=pool.dtype) or 1
    # The heads' key columns side by side, and their value columns
    # transposed: every product in the kernel is then plain or NT.
    w = w_kb.reshape(rank, heads, nope + value)
    w_k = w[..., :nope].reshape(rank, heads * nope)
    w_vt = w[..., nope:].reshape(rank, heads * value).T
    kernel = functools.partial(_kernel, bsz=bsz, g=g, rank=rank, nope=nope,
                               value=value)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(heads // g, table.shape[1]),
        in_specs=[
            pl.BlockSpec((g, t, width), lambda gi, ji, *_: (gi, 0, 0)),
            pl.BlockSpec((rank, g * nope), lambda gi, ji, *_: (0, gi)),
            pl.BlockSpec((g * value, rank), lambda gi, ji, *_: (gi, 0)),
            pl.BlockSpec((1, 1, bsz, lanes),
                         lambda gi, ji, tbl, st, jm, ly: (
                             ly[0], tbl[0, jnp.minimum(ji, jm[0])], 0, 0)),
        ],
        out_specs=pl.BlockSpec((g, value, t), lambda gi, ji, *_: (gi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, value, t), jnp.float32),
                        pltpu.VMEM((g, 8, t), jnp.float32),
                        pltpu.VMEM((g, 8, t), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, value, t), q.dtype),
        interpret=interpret, name="latent_prefill",
    )(table, start, jmax, layer, q, w_k, w_vt, pool)


def latent_prefill_attention(q: jax.Array, w_kb: jax.Array, pool: jax.Array,
                             table: jax.Array, start: jax.Array, *,
                             layer: jax.Array = 0, nope: int,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Causal latent attention of ONE slot's chunk over layer ``layer`` of
    the stacked latent pool, expanded.

    ``q`` [H, T, nope + rope] the chunk's queries at absolute positions
    ``start + t`` (``start`` an i32 scalar); ``w_kb`` [rank, H x (nope +
    value)] (a head's key columns, then its value columns); ``pool`` [L,
    NB, BLOCK, lanes], a position's row ``c~ [rank] || k_r [rope] || 0``;
    ``table`` i32[1, NBPS] the slot's physical blocks.  The chunk's own rows
    must already be in the pool (write-then-attend).  Scores are ``(q_n .
    k_n + q_r . k_r) / sqrt(nope + rope)``.  Returns [H, T, value] in q's
    dtype, float32 sums throughout.  Pinned against
    :func:`latent_prefill_reference` by tests/test_latent_serving.py."""
    heads, t, width = q.shape
    lanes, bsz = pool.shape[3], pool.shape[2]
    if interpret is None:
        interpret = pallas_interpret()
    rank = w_kb.shape[0]
    # The queries scaled here (one multiply a query, none a score), their
    # rope part padded with zeros to the row's lanes behind ``rank`` (where
    # the pool's own padding is zero too).
    q = jnp.pad(q.astype(jnp.float32) / math.sqrt(width),
                ((0, 0), (0, 0), (0, lanes - rank - (width - nope))))
    start = jnp.reshape(start, (1,)).astype(jnp.int32)
    jmax = jnp.clip((start + t - 1) // bsz, 0, table.shape[1] - 1)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    out = _latent_prefill_call(
        q.astype(pool.dtype), w_kb, pool, table.astype(jnp.int32), start,
        jmax.astype(jnp.int32), layer, interpret=interpret, nope=nope)
    return jnp.swapaxes(out, 1, 2)


def latent_prefill_reference(q: jax.Array, w_kb: jax.Array, pool: jax.Array,
                             table: jax.Array, start: jax.Array, *,
                             layer: jax.Array = 0, nope: int) -> jax.Array:
    """The gathered semantics the kernel is pinned against, and the jnp
    path of the chunk program: the slot's rows gathered through its table,
    expanded for every head at once, a full-width masked softmax."""
    heads, t, width = q.shape
    rank = w_kb.shape[0]
    rows = pool[layer][table[0]].reshape(-1, pool.shape[3]).astype(
        jnp.float32)                                      # [S, lanes]
    kv = jnp.matmul(rows[:, :rank], w_kb.astype(jnp.float32)).reshape(
        rows.shape[0], heads, -1)                         # [S, H, nope + v]
    k_r = rows[:, rank:rank + width - nope]
    qf = q.astype(jnp.float32)
    s = (jnp.einsum("htn,shn->hts", qf[..., :nope], kv[..., :nope])
         + jnp.einsum("htr,sr->hts", qf[..., nope:], k_r)) / math.sqrt(width)
    qpos = jnp.reshape(start, ()) + jnp.arange(t)
    seen = jnp.arange(rows.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hts,shv->htv", p, kv[..., nope:]).astype(q.dtype)
