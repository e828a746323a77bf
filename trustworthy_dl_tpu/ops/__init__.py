"""Native ops tier: Pallas TPU kernels (SURVEY §7.1).

The reference has no native code at all (SURVEY §0: pure Python); this
package is where the TPU build drops below XLA when the compiler's fusion
isn't enough.  Current kernels:

* ``fused_stats`` — single-pass detector moment battery (Σx..Σx⁴, min/max,
  L1/L∞) feeding detect/stats.leafwise_statistics.
* ``flash_attention`` — blockwise softmax attention, fwd + bwd, O(T·D)
  memory (``attn_impl="flash"`` in the GPT-2 registry).
* ``fused_dequant_matmul`` — int8-weight dequant matmul tile for the
  serving engine's weight-only-int8 decode path (quant/): streams int8
  weight tiles HBM→VMEM, upcasts in-register, scales per output channel.
* ``paged_attention`` — the serving-kernel TIER over the engine's block
  pool: ragged paged-decode attention (one program per block-table row,
  int8 KV tiles dequantized in-register, online softmax, early exit at
  each row's true length), the query-tiled chunked-prefill program
  (per-tile causal bounds over the same scalar-prefetch tables), the
  fused speculative-verify tail (logits projection + trust stats in one
  streaming vocab pass), the in-grid adapter low-rank gather (per-slot
  page table as scalar prefetch) and the fused logit trust epilogue
  (entropy / top-1 margin in one pass over the vocab).
* ``latent_attention`` — the chunk program's latent attention over the
  paged LATENT cache (one shared row a position, no per-head K or V), in
  the expanded form: a block's rows times ``W_kb`` inside the kernel, the
  scores held transposed so that the softmax reduces down the sublanes;
  dispatched with ``paged_attention``'s gate and path names (the decode
  program's half is the paged kernel's latent shape).
* ``grouped_matmul`` — the grouped products of the dropless expert layer
  (``models.moe.held_experts``): rows sorted by expert, each expert's
  weights streamed once against a row tile chosen from the shapes (16
  rows for a decode call, 128 for a chunk), group offsets and the
  visits' groups as scalar prefetch; ``jax.lax.ragged_dot`` elsewhere.

All six dispatch through the ONE shared gate below: :func:`pallas_enabled`
(env-var opt-in/out, TPU-backend default) and :func:`pallas_interpret`
(off-TPU kernels run in Pallas interpret mode — tests only).  The gate
lives HERE, above the kernel imports, so the kernels can import it from
the package without a cycle.

A compiled Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels
cannot be automatically partitioned" — the lowering refuses), and the
trusted step over a multi-device mesh IS a GSPMD program: its per-node
work is a ``vmap`` whose node axis the compiler shards.  So the default
also asks whether the program being traced will be partitioned:
:func:`for_mesh` is how the code that jits a program over a mesh says
so, and :func:`mosaic_dispatchable` is the answer the dispatch
predicates read.  A partitioned program takes the XLA spelling of every
kernel here; one device takes the kernels.
"""

import contextvars
import functools
import os

#: True while a program that GSPMD will partition is being traced.
_PARTITIONED = contextvars.ContextVar("tddl_gspmd_partitioned",
                                      default=False)


def for_mesh(fn, mesh):
    """``fn``, about to be jitted over ``mesh``, traced knowing whether
    the compiler will partition it (the mesh holds more than one
    device).  The wrapper's body runs only while tracing."""
    partitioned = mesh.size > 1

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        token = _PARTITIONED.set(partitioned)
        try:
            return fn(*args, **kwargs)
        finally:
            _PARTITIONED.reset(token)

    return traced


def mosaic_dispatchable() -> bool:
    """Can the program being traced hold a COMPILED Mosaic kernel: the
    TPU backend, and not under GSPMD partitioning (see the module
    docstring).  THE default of every kernel dispatch in this package
    and of ``models.gpt2.auto_picks_flash``."""
    import jax

    return jax.default_backend() == "tpu" and not _PARTITIONED.get()


def pallas_enabled(env: str = "TDDL_FUSED_STATS") -> bool:
    """THE dispatch gate every Pallas kernel in this package shares:
    default ON where :func:`mosaic_dispatchable`, opt-out via
    ``<env>=0`` (and opt-in via ``=1`` off-TPU, where the kernel runs in
    interpret mode — tests only).

    Env-var map: ``TDDL_FUSED_STATS`` gates fused_stats AND
    dequant_matmul (the int8 decode tier shipped riding the stats gate
    and keeps that coupling — flipping it off disables both kernels);
    ``TDDL_PAGED_ATTN`` gates paged_attention; ``TDDL_GROUPED_MATMUL``
    gates grouped_matmul.  The policy is
    deliberately identical everywhere: the jnp/XLA path stays the
    always-available reference semantics, and the CPU container tier
    never compiles Mosaic."""
    flag = os.environ.get(env)
    if flag is not None:
        return flag != "0"
    return mosaic_dispatchable()


def pallas_interpret() -> bool:
    """Interpret-mode helper shared by every kernel's dispatch: compiled
    Mosaic on the TPU backend, Pallas interpret mode anywhere else (the
    CPU test tier pins kernel-vs-jnp equality through this)."""
    import jax

    return jax.default_backend() != "tpu"


from trustworthy_dl_tpu.ops.flash_attention import flash_attention
from trustworthy_dl_tpu.ops.fused_dequant_matmul import dequant_matmul
from trustworthy_dl_tpu.ops.fused_stats import (
    BLOCK_ROWS,
    LANES,
    fused_moments,
)
# NOTE: the ``paged_attention`` ENTRY-POINT FUNCTION is deliberately not
# re-exported here: ``from ops import paged_attention`` must keep
# resolving to the submodule — generate/scheduler import it as a module
# for the whole kernel surface (attention + trust epilogue + resolver),
# unlike ``flash_attention`` where the function deliberately shadows its
# submodule and callers only ever want the one entry point.  Likewise
# ``grouped_matmul``: ``ops.grouped_matmul`` is the submodule (its entry
# point, tile rule and ``scheduled_rows`` counter), imported where used.
from trustworthy_dl_tpu.ops.paged_attention import (
    adapter_delta,
    fused_verify_tail,
    logit_trust_stats,
    paged_prefill_attention,
    resolve_attn_impl,
    resolve_attn_impls,
    supports_paged_attention,
)

__all__ = [
    "BLOCK_ROWS",
    "LANES",
    "adapter_delta",
    "dequant_matmul",
    "flash_attention",
    "fused_moments",
    "for_mesh",
    "fused_verify_tail",
    "logit_trust_stats",
    "mosaic_dispatchable",
    "paged_prefill_attention",
    "pallas_enabled",
    "pallas_interpret",
    "resolve_attn_impl",
    "resolve_attn_impls",
    "supports_paged_attention",
]
