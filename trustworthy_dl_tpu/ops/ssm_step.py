"""One decode step of the Mamba-2 recurrence for every slot of one layer,
the state updated IN PLACE (``models/mamba2.py`` has the mathematics and
:func:`models.mamba2.ssd_step`, the XLA spelling this kernel is pinned
against).

The state of all layers is one array ``[L, S, H, P, N]`` float32 (``serve/
kv_slots.RecurrentState.ssm``), carried through the layer scan of the
decode program and donated: the kernel reads the rows of layer ``layer``
(scalar prefetch) where they lie and writes them back into the same buffer
(``input_output_aliases``), so no copy of a layer's state, 268 MB at the
serving cell's size, is ever made.  A grid step holds one slot's
``heads_a_step`` heads, all of one group (so one row of ``B`` and of ``C``):
it reads their state once, multiplies it by ``exp(dt A)``, adds ``dt x
B^T``, writes it and reduces it against ``C``.  The step is bound by the
state's bytes alone: ``2 x 4 x H P N`` a slot against some ``6 H P N``
operations.

The ``pallas_call`` is named ``ssm_step``: the name the benchmark's trace
reader finds the kernel by.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Bytes of one input block of the state: the pipeline holds two of it in
#: and two out, and the step's temporaries about two more, well inside the
#: default scoped VMEM.
STATE_BLOCK_BYTES = 1 << 20


def heads_a_step(heads: int, groups: int, head_dim: int, state: int) -> int:
    """The heads one grid step holds: the most that divide a group's heads
    and whose state fits ``STATE_BLOCK_BYTES``, a whole number of 8-row
    sublane tiles where one exists (a block's rows are heads)."""
    per_group = heads // groups
    fits = [n for n in range(1, per_group + 1) if per_group % n == 0
            and n * head_dim * state * 4 <= STATE_BLOCK_BYTES] or [1]
    tiled = [n for n in fits if n % 8 == 0 or n == heads]
    return max(tiled or fits)


def _kernel(layer_ref, s_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
            y_ref, out_ref):
    h = s_ref[0, 0]                                   # [hb, P, N]
    x = x_ref[0]                                      # [hb, P]
    dt = dt_ref[0]                                    # [hb, 1]
    b = b_ref[0, 0]                                   # [1, N]
    c = c_ref[0, 0]
    decay = jnp.exp(dt * a_ref[...])                  # [hb, 1]
    h = h * decay[:, :, None] + (dt * x)[:, :, None] * b[None, :, :]
    out_ref[0, 0] = h
    y_ref[0] = jnp.sum(h * c[None, :, :], axis=-1) + d_ref[...] * x


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_call(state: jax.Array, layer: jax.Array, x: jax.Array,
                   dt: jax.Array, a: jax.Array, d: jax.Array, b: jax.Array,
                   c: jax.Array, interpret: bool
                   ) -> Tuple[jax.Array, jax.Array]:
    """``state [L, S, H, P, N]``, ``layer`` i32[1], ``x [S, H, P]``, ``dt
    [S, H, 1]``, ``a, d [H, 1]``, ``b, c [S, G, 1, N]`` -> ``(y [S, H, P],
    state)``, the state written where it lay."""
    _, slots, heads, p, n = state.shape
    groups = b.shape[1]
    hb = heads_a_step(heads, groups, p, n)
    per_group = heads // groups
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, heads // hb),
        in_specs=[
            pl.BlockSpec((1, 1, hb, p, n),
                         lambda s, j, ly: (ly[0], s, j, 0, 0)),
            pl.BlockSpec((1, hb, p), lambda s, j, ly: (s, j, 0)),
            pl.BlockSpec((1, hb, 1), lambda s, j, ly: (s, j, 0)),
            pl.BlockSpec((hb, 1), lambda s, j, ly: (j, 0)),
            pl.BlockSpec((hb, 1), lambda s, j, ly: (j, 0)),
            pl.BlockSpec((1, 1, 1, n),
                         lambda s, j, ly: (s, j * hb // per_group, 0, 0)),
            pl.BlockSpec((1, 1, 1, n),
                         lambda s, j, ly: (s, j * hb // per_group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), lambda s, j, ly: (s, j, 0)),
            pl.BlockSpec((1, 1, hb, p, n),
                         lambda s, j, ly: (ly[0], s, j, 0, 0)),
        ],
    )
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((slots, heads, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="ssm_step",
    )(layer, state, x, dt, a, d, b, c)


def ssm_step(state: jax.Array, layer: jax.Array, x: jax.Array,
             dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
             d: jax.Array, *, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """One position for every slot of layer ``layer`` of the stacked state
    ``[L, S, H, P, N]``: ``x [S, H, P]``, ``dt [S, H]``, ``a, d [H]``, ``b,
    c [S, G, N]`` -> ``(y [S, H, P], state)``, float32.  A slot whose ``dt``
    is 0 keeps its state.  Equal to :func:`models.mamba2.ssd_step` on the
    layer's rows (tests/test_hybrid_serving.py)."""
    f32 = jnp.float32
    col = lambda v: v.astype(f32)[..., None]
    row = lambda v: v.astype(f32)[:, :, None, :]
    return _ssm_step_call(
        state, jnp.reshape(layer, (1,)).astype(jnp.int32), x.astype(f32),
        col(dt), col(a), col(d), row(b), row(c), interpret=interpret)
