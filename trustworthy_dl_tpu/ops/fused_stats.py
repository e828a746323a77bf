"""Pallas TPU kernel: fused detector moment battery (SURVEY §7.1 native tier).

The in-step detector needs eight reductions of every gradient/feature tensor
(Σx, Σx², Σx³, Σx⁴, min, max, Σ|x|, max|x| — detect/stats.py raw-moment
battery).  XLA fuses same-shaped reductions well but still emits several
passes for the mixed sum/min/max combination on large inputs; this kernel
makes the single pass explicit: each grid step streams one [BLOCK_ROWS, 128]
tile HBM→VMEM and accumulates per-lane partials for all eight statistics in
one VMEM accumulator, so every gradient byte is read exactly once.

The kernel is TPU-shaped (lane width 128, f32 sublane 8) but runs anywhere
via ``interpret=True`` — tests exercise it on the CPU mesh.  The XLA
implementation in detect/stats.py remains the reference semantics; equality
is pinned by tests/test_ops.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The ONE shared Pallas gate (ops/__init__.py) — re-exported here because
# this module introduced it and call sites (detect/stats.py, PARITY.md)
# name it as ``fused_stats.pallas_enabled``.  Why this kernel: XLA may
# emit several HBM passes for the eight reductions (seen on conv
# gradients); the kernel makes the single pass explicit.  Its effect on
# step time is not measured (PERF.md).
from trustworthy_dl_tpu.ops import pallas_enabled, pallas_interpret  # noqa: F401

LANES = 128
BLOCK_ROWS = 512          # 512×128 f32 tile = 256 KB VMEM per step
_MIN_FUSED_SIZE = BLOCK_ROWS * LANES  # below this, XLA's fusion wins anyway

# Accumulator row layout.
_ROW_S1, _ROW_S2, _ROW_S3, _ROW_S4 = 0, 1, 2, 3
_ROW_MIN, _ROW_MAX, _ROW_L1, _ROW_LINF = 4, 5, 6, 7


def _moments_kernel(x_ref, acc_ref):
    """One [BLOCK_ROWS, LANES] tile: accumulate per-lane partials."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        acc_ref[_ROW_MIN, :] = jnp.full((LANES,), jnp.inf, jnp.float32)
        acc_ref[_ROW_MAX, :] = jnp.full((LANES,), -jnp.inf, jnp.float32)

    x = x_ref[:]
    x2 = x * x
    ax = jnp.abs(x)
    acc_ref[_ROW_S1, :] += jnp.sum(x, axis=0)
    acc_ref[_ROW_S2, :] += jnp.sum(x2, axis=0)
    acc_ref[_ROW_S3, :] += jnp.sum(x2 * x, axis=0)
    acc_ref[_ROW_S4, :] += jnp.sum(x2 * x2, axis=0)
    acc_ref[_ROW_MIN, :] = jnp.minimum(acc_ref[_ROW_MIN, :], jnp.min(x, axis=0))
    acc_ref[_ROW_MAX, :] = jnp.maximum(acc_ref[_ROW_MAX, :], jnp.max(x, axis=0))
    acc_ref[_ROW_L1, :] += jnp.sum(ax, axis=0)
    acc_ref[_ROW_LINF, :] = jnp.maximum(
        acc_ref[_ROW_LINF, :], jnp.max(ax, axis=0)
    )


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _fused_tile_moments_impl(tiles: jax.Array,
                             interpret: bool = False) -> jax.Array:
    """[R, 128] f32 (R a multiple of BLOCK_ROWS) -> [8, 128] lane partials.

    custom_jvp with zero tangents: the battery is diagnostics — nothing
    intentionally differentiates it — but it runs on values INSIDE the
    engine's value_and_grad (feature activations depend on params), and
    ``pallas_call`` has no JVP rule (AD through the kernel asserts inside
    pallas' program_id at trace time).  Treating the statistics as
    constant under differentiation is both the fix and the correct
    semantics (fused_moments also stop-gradients its input so the XLA
    tail/fallback paths share that contract)."""
    grid = tiles.shape[0] // BLOCK_ROWS
    return pl.pallas_call(
        _moments_kernel,
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, LANES),
                lambda i: (i, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(tiles)


@_fused_tile_moments_impl.defjvp
def _fused_tile_moments_jvp(interpret, primals, tangents):
    (tiles,) = primals
    out = _fused_tile_moments_impl(tiles, interpret)
    return out, jnp.zeros_like(out)


_fused_tile_moments = jax.jit(_fused_tile_moments_impl,
                              static_argnames=("interpret",))


def _xla_moments(x: jax.Array) -> Tuple[jax.Array, ...]:
    """Reference XLA path (identical math, detect/stats.py:212-220)."""
    x = x.astype(jnp.float32)
    x2 = x * x
    return (jnp.sum(x), jnp.sum(x2), jnp.sum(x2 * x), jnp.sum(x2 * x2),
            jnp.min(x) if x.size else jnp.asarray(jnp.inf),
            jnp.max(x) if x.size else jnp.asarray(-jnp.inf),
            jnp.sum(jnp.abs(x)), jnp.max(jnp.abs(x)) if x.size else jnp.asarray(0.0))


def fused_moments(x: jax.Array,
                  interpret: Optional[bool] = None) -> Tuple[jax.Array, ...]:
    """(s1, s2, s3, s4, min, max, l1, linf) of a flattened f32 vector in one
    HBM pass.  The aligned prefix streams through the Pallas kernel; the
    ≤BLOCK_ROWS·LANES-1 element tail and small inputs use XLA (negligible and
    keeps shapes static).

    Constant under differentiation on EVERY path (stop_gradient here, plus
    the kernel's zero-tangent custom_jvp): the statistics are diagnostics,
    and per-path gradient behaviour must not flip with input size or the
    dispatch env var."""
    x = jax.lax.stop_gradient(x.reshape(-1))
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    n = x.shape[0]
    if interpret is None:
        interpret = pallas_interpret()
    chunk = BLOCK_ROWS * LANES
    n_aligned = (n // chunk) * chunk
    if n_aligned == 0:
        return _xla_moments(x)
    tiles = x[:n_aligned].reshape(-1, LANES)
    acc = _fused_tile_moments(tiles, interpret=interpret)
    head = (
        jnp.sum(acc[_ROW_S1]), jnp.sum(acc[_ROW_S2]),
        jnp.sum(acc[_ROW_S3]), jnp.sum(acc[_ROW_S4]),
        jnp.min(acc[_ROW_MIN]), jnp.max(acc[_ROW_MAX]),
        jnp.sum(acc[_ROW_L1]), jnp.max(acc[_ROW_LINF]),
    )
    if n_aligned == n:
        return head
    tail = _xla_moments(x[n_aligned:])
    return (
        head[0] + tail[0], head[1] + tail[1], head[2] + tail[2],
        head[3] + tail[3], jnp.minimum(head[4], tail[4]),
        jnp.maximum(head[5], tail[5]), head[6] + tail[6],
        jnp.maximum(head[7], tail[7]),
    )
