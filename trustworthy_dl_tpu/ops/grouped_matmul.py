"""Pallas TPU kernel: grouped matmul whose row tile follows the rows a group
gets.

``grouped_matmul(rows [M, K], w [G, K, N], group_sizes i32[G])`` is
``jax.lax.ragged_dot(..., preferred_element_type=float32)`` as
``models.moe.held_experts`` uses it: the rows lie sorted by group, group
``g``'s rows meet ``w[g]`` alone, the products accumulate in float32, and
the rows behind the last group are left as they fall (the caller selects
them away).

Left to XLA on the TPU, the op multiplies a tile of 512 rows a group
whatever the group holds: 40 held experts of 1.6 rows each in a decode call
cost what 40 x 512 rows cost, and the MXU multiplies padding.  What the
hardware needs is each group's weights ONCE; this kernel streams them and
multiplies a row tile chosen from the shapes (:func:`row_tile`).

The schedule is the known one of the grouped matmul that ships with JAX
(``jax/experimental/pallas/ops/tpu/megablox/gmm.py``), on a STATIC grid:

* the work is a list of VISITS, one a (row tile, group) pair that share a
  row, in row order; a tile that straddles groups is visited once a group
  with the other groups' rows masked out of the store;
* the grid is ``(N tiles, ceil(M / tm) + G visits, K tiles)``: there are
  never more live visits than that, and the rest are DEAD: their block
  indices are the last live visit's, so nothing is fetched, and their
  body is skipped;
* the group offsets, each visit's group and row tile and the live visits'
  count go in as scalar prefetch, so the index maps pick the weights'
  block from the visit's group;
* with K whole in a block (the first choice of :func:`tiling`) the visits
  are the innermost axis that changes a block index, so a group that
  straddles two row tiles finds its weights resident at the second.

Dispatch as every kernel of this package: the kernel where
``pallas_enabled("TDDL_GROUPED_MATMUL")`` (default: the TPU backend and no
GSPMD partitioning) and the shapes tile, ``jax.lax.ragged_dot`` elsewhere;
off the TPU the kernel runs in Pallas interpret mode (tests only).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trustworthy_dl_tpu.ops import pallas_enabled, pallas_interpret
from trustworthy_dl_tpu.ops.fused_stats import LANES
from trustworthy_dl_tpu.ops.paged_attention import VMEM_BLOCK_BUDGET

#: The smallest row tile: bfloat16's sublane count (a float32 tile of 16
#: rows is two sublane groups, legal too).
MIN_ROW_TILE = 16
#: The widest: the MXU's height.  A weights tile meets at most this many
#: rows a visit, which a v5e multiplies in about half the time it takes to
#: fetch the tile, so wider tiles only add padding.
MAX_ROW_TILE = 128


def row_tile(m: int, groups: int) -> int:
    """THE row tile, from shapes alone: the power of two at or above the
    rows a group gets when every row is live, ``m / groups``, held to
    [:data:`MIN_ROW_TILE`, :data:`MAX_ROW_TILE`].  16 for a decode call's
    512 sorted rows over 40 held experts, 128 for a chunk call's 8,192.
    The dispatch and :func:`scheduled_rows` both take it from here."""
    tm = MIN_ROW_TILE
    while tm < MAX_ROW_TILE and tm * groups < m:
        tm *= 2
    return tm


def _lane_divisors(n: int):
    """The block widths a dimension of ``n`` may be cut into, widest
    first: ``n`` whole, then its divisors that are whole 128-lane
    columns."""
    yield n
    for width in range(n - n % LANES, 0, -LANES):
        if width < n and n % width == 0:
            yield width


def tiling(m: int, k: int, n: int, groups: int, itemsize: int
           ) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` of one call, or None where no legal weights tile
    fits: ``tm`` is :func:`row_tile`; ``tk`` then ``tn`` the widest whose
    double-buffered weights tile ``[tk, tn]`` fits
    :data:`~trustworthy_dl_tpu.ops.paged_attention.VMEM_BLOCK_BUDGET`,
    K whole before a wider N (so that a straddled group's weights stay
    resident, module docstring)."""
    for tk in _lane_divisors(k):
        for tn in _lane_divisors(n):
            if 2 * tk * tn * itemsize <= VMEM_BLOCK_BUDGET:
                return row_tile(m, groups), tk, tn
    return None


def _schedule(group_sizes: Any, tiles: int, tm: int):
    """The visits of a call over ``tiles`` row tiles of ``tm``: ``(offsets
    [G + 1], group [V], tile [V], live [1])`` with ``V = tiles + G``.
    Works on numpy and on traced arrays alike (``scheduled_rows`` counts
    on the host what the kernel's scalar prefetch is built from)."""
    xp = jnp if isinstance(group_sizes, jax.Array) else np
    sizes = group_sizes.astype(xp.int32)
    groups = sizes.shape[0]
    ends = xp.cumsum(sizes, dtype=xp.int32)
    starts = ends - sizes
    first = starts // tm
    # An empty group is visited by no tile.
    count = xp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = xp.cumsum(count, dtype=xp.int32)
    live = visit_end[-1:]
    # A dead visit repeats the last live one: no block index moves.
    visit = xp.minimum(xp.arange(tiles + groups, dtype=xp.int32),
                       xp.maximum(live - 1, 0))
    # The groups whose visits all lie before this one: its group.
    group = xp.minimum((visit[:, None] >= visit_end[None, :]).sum(axis=1),
                       groups - 1).astype(xp.int32)
    tile = (first[group] + visit - (visit_end - count)[group]).astype(
        xp.int32)
    offsets = xp.concatenate([xp.zeros(1, xp.int32), ends])
    return offsets, group, tile, live


def scheduled_rows(m: int, group_sizes: Any) -> int:
    """Rows of products ONE call's schedule multiplies (a weights column
    each) at these concrete group sizes over ``m`` sorted rows: the live
    visits times :func:`row_tile`.  The live rows themselves are
    ``sum(group_sizes)``; XLA's op multiplies 512 a group."""
    sizes = np.asarray(group_sizes)
    tm = row_tile(m, sizes.shape[0])
    live = _schedule(sizes, -(-m // tm), tm)[3]
    return int(live[0]) * tm


def _gmm_kernel(offsets, group, tile, live, rows_ref, w_ref, out_ref, *acc,
                tm: int, tiles_k: int):
    """One visit's ``[tm, tk] x [tk, tn]``; ``acc`` is the float32
    accumulator where K comes in more than one tile."""
    visit = pl.program_id(1)
    k_i = pl.program_id(2)

    def store(value):
        g = group[visit]
        row = tile[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, value.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        # The other rows of the tile are another visit's, or nobody's.
        out_ref[...] = jnp.where(mine, value, out_ref[...])

    @pl.when(visit < live[0])
    def _visit():
        product = jnp.dot(rows_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
        if tiles_k == 1:
            store(product)
            return
        (acc_ref,) = acc

        @pl.when(k_i == 0)
        def _first():
            acc_ref[...] = product

        @pl.when(k_i > 0)
        def _more():
            acc_ref[...] += product

        @pl.when(k_i == tiles_k - 1)
        def _last():
            store(acc_ref[...])


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _gmm_call(rows: jax.Array, w: jax.Array, group_sizes: jax.Array,
              tiles: Tuple[int, int, int], interpret: bool = False
              ) -> jax.Array:
    """The kernel at the tiling given (``M`` a multiple of ``tm``)."""
    m, k = rows.shape
    groups, _, n = w.shape
    tm, tk, tn = tiles
    tiles_k = k // tk
    schedule = _schedule(group_sizes, m // tm, tm)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, m // tm + groups, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, i, off, grp, til, live:
                             (til[v], i)),
                pl.BlockSpec((None, tk, tn),
                             lambda j, v, i, off, grp, til, live:
                             (grp[v], i, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, i, off, grp, til, live:
                                   (til[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tiles_k > 1 else [],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*schedule, rows, w)


def grouped_matmul(rows: jax.Array, w: jax.Array, group_sizes: jax.Array
                   ) -> jax.Array:
    """``rows [M, K]``, sorted by group, times ``w [G, K, N]`` a group of
    ``group_sizes i32[G]`` rows -> ``[M, N]`` float32; what
    ``jax.lax.ragged_dot(rows, w, group_sizes,
    preferred_element_type=float32)`` gives on the rows of a group, and
    whatever falls on the rows behind the last.

    The kernel runs where ``pallas_enabled("TDDL_GROUPED_MATMUL")`` and
    :func:`tiling` finds a weights tile that fits; anything else (the CPU
    tier, a partitioned program, a width off the lanes that is too large
    whole) takes ``jax.lax.ragged_dot``."""
    m, k = rows.shape
    groups, _, n = w.shape
    tiles = tiling(m, k, n, groups, jnp.dtype(w.dtype).itemsize)
    if tiles is None or not pallas_enabled("TDDL_GROUPED_MATMUL"):
        return jax.lax.ragged_dot(rows, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    pad = -m % tiles[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = _gmm_call(rows, w, group_sizes.astype(jnp.int32), tiles=tiles,
                    interpret=pallas_interpret())
    return out[:m] if pad else out
