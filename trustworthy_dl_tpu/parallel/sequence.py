"""Sequence / context parallelism — first-class long-context support.

Entirely absent from the reference (SURVEY §5.7: no sequence-dimension
handling, no attention code at all); required by the build charter.  Two
strategies over the 'seq' mesh axis:

* **Ulysses** (`ulysses_attention`): activations outside attention are
  sharded on the sequence dim; around the attention core they reshard to
  head-sharding via GSPMD constraints, so XLA inserts the all_to_all pair.
  Simple, exact, bandwidth-heavy — the easier first implementation.

* **Ring attention** (`ring_attention`): each device keeps its Q chunk and
  rotates K/V chunks around the ICI ring with ``ppermute``, accumulating
  flash-style online softmax (running max + normaliser), so attention over
  the full sequence costs O(T/s) memory per device and overlaps compute
  with neighbour transfers.  Exact (not approximate) — verified against
  full attention in tests.

Both register with the GPT-2 attention registry (models/gpt2.py) under
"ulysses" / "ring"; a mesh context (``use_sequence_mesh``) supplies the mesh
since model forwards run under plain ``jit``.  With no context set they fall
back to full attention so models stay runnable anywhere.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from trustworthy_dl_tpu.core import sharding as shreg
from trustworthy_dl_tpu.core.mesh import SEQ_AXIS

#: Registry rules for this mode: the Ulysses exchange is exactly the
#: head<->seqlen logical rename the table encodes (both map onto the
#: 'seq' mesh axis).
_SP_RULES = shreg.rules_for("sequence")
from trustworthy_dl_tpu.models.gpt2 import full_attention, register_attention

_SEQ_MESH: Optional[Mesh] = None

NEG_INF = -1e30


def set_sequence_mesh(mesh: Optional[Mesh]) -> None:
    global _SEQ_MESH
    _SEQ_MESH = mesh


def get_sequence_mesh() -> Optional[Mesh]:
    if _SEQ_MESH is not None and SEQ_AXIS in _SEQ_MESH.axis_names:
        return _SEQ_MESH
    return None


@contextlib.contextmanager
def use_sequence_mesh(mesh: Mesh):
    prev = _SEQ_MESH
    set_sequence_mesh(mesh)
    try:
        yield
    finally:
        set_sequence_mesh(prev)


# ---------------------------------------------------------------------------
# Ulysses: all_to_all head<->sequence reshard around full attention
# ---------------------------------------------------------------------------


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True) -> jax.Array:
    """[B, H, T, D] attention with Ulysses-style resharding.

    Inputs arrive sequence-sharded (P(None, None, 'seq', None) — the natural
    layout of seq-sharded activations after the QKV projection); constraints
    flip them to head-sharding for the exact attention core and back, which
    GSPMD lowers to the canonical all_to_all pair over ICI.
    """
    mesh = get_sequence_mesh()
    if mesh is None:
        return full_attention(q, k, v, causal)
    heads_sharded = _SP_RULES.named_sharding(
        mesh, None, shreg.HEAD, None, None)
    seq_sharded = _SP_RULES.named_sharding(
        mesh, None, None, shreg.SEQLEN, None)
    q, k, v = (jax.lax.with_sharding_constraint(a, heads_sharded)
               for a in (q, k, v))
    out = full_attention(q, k, v, causal)
    out = jax.lax.with_sharding_constraint(out, heads_sharded)
    return jax.lax.with_sharding_constraint(out, seq_sharded)


# ---------------------------------------------------------------------------
# Ring attention: ppermute K/V rotation + online softmax
# ---------------------------------------------------------------------------


def _use_flash_chunks(tl: int, d: int) -> bool:
    """The Pallas flash kernel handles the per-rotation chunk attention
    when the chunk shape is kernel-eligible (ops/flash_attention.
    supports_flash — the single predicate shared with the public wrapper);
    otherwise the einsum body below runs.  For long-context runs (the
    reason ring attention exists) the kernel path is what makes the memory
    story real: the einsum body materialises [B, H, Tl, Tl] scores per
    rotation — at Tl = 8k that is gigabytes — while the kernel streams
    K/V blocks through VMEM at O(Tl·D)."""
    from trustworthy_dl_tpu.ops.flash_attention import supports_flash

    return supports_flash(tl, d)


def _merge_chunk(lse_run, out_run, lse_i, o_i):
    """Combine a normalized chunk result (o_i, lse_i) into the running
    (lse, out) accumulator — the cross-chunk half of online softmax.

    The "no contribution" sentinel is the finite NEG_INF (-1e30), not
    -inf (which would NaN the logaddexp/exp gradients), so the guards
    test against the sentinel explicitly rather than isfinite."""
    new_lse = jnp.logaddexp(lse_run, lse_i)
    w_run = jnp.where(lse_run > NEG_INF / 2, jnp.exp(lse_run - new_lse), 0.0)
    w_i = jnp.where(lse_i > NEG_INF / 2, jnp.exp(lse_i - new_lse), 0.0)
    out = out_run * w_run[..., None] + o_i.astype(jnp.float32) * w_i[..., None]
    return new_lse, out


def _ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool, ring_size: int) -> jax.Array:
    """Per-device body under shard_map: q/k/v are this device's sequence
    chunk [B, H, Tl, D].  K/V rotate ``ring_size`` times; online-softmax
    accumulation keeps the result exact across chunks.  Per-rotation chunk
    attention runs through the Pallas flash kernel when the chunk tiles
    (see _use_flash_chunks), else through a fused einsum."""
    stage = jax.lax.axis_index(SEQ_AXIS)
    b, h, tl, d = q.shape
    scale = 1.0 / math.sqrt(d)
    q_pos = stage * tl + jnp.arange(tl)

    if _use_flash_chunks(tl, d):
        from trustworthy_dl_tpu.ops.flash_attention import flash_chunk

        merge = lambda a: a.reshape(b * h, tl, d)

        def chunk(k_cur, v_cur, chunk_causal: bool):
            o, lse = flash_chunk(merge(q), merge(k_cur), merge(v_cur),
                                 chunk_causal)
            return (o.reshape(b, h, tl, d),
                    lse.reshape(b, h, tl))

        def attend(k_cur, v_cur, i):
            src = (stage - i) % ring_size
            if not causal:
                return chunk(k_cur, v_cur, False)
            # src > stage: chunk entirely in the future — skip.
            # src == stage: the diagonal chunk — causal kernel.
            # src < stage: entirely visible — non-causal kernel.
            return jax.lax.switch(
                jnp.clip(jnp.sign(src - stage) + 1, 0, 2).astype(jnp.int32),
                [
                    lambda: chunk(k_cur, v_cur, False),
                    lambda: chunk(k_cur, v_cur, True),
                    lambda: (jnp.zeros((b, h, tl, d), q.dtype),
                             jnp.full((b, h, tl), NEG_INF, jnp.float32)),
                ],
            )

        def body(carry, i):
            # Rotate FIRST, then attend: the i=0 chunk is consumed outside
            # the scan, so only ring_size-1 rotations happen and no K/V
            # ppermute pair is ever computed just to be discarded.
            k_cur, v_cur, lse, out = carry
            perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
            k_cur = jax.lax.ppermute(k_cur, SEQ_AXIS, perm)
            v_cur = jax.lax.ppermute(v_cur, SEQ_AXIS, perm)
            o_i, lse_i = attend(k_cur, v_cur, i)
            lse, out = _merge_chunk(lse, out, lse_i, o_i)
            return (k_cur, v_cur, lse, out), None

        out0 = jnp.zeros((b, h, tl, d), jnp.float32)
        lse0 = jnp.full((b, h, tl), NEG_INF, jnp.float32)
        o_0, lse_0 = attend(k, v, jnp.zeros((), jnp.int32))
        lse0, out0 = _merge_chunk(lse0, out0, lse_0, o_0)
        (_, _, _, out), _ = jax.lax.scan(
            body, (k, v, lse0, out0), jnp.arange(1, ring_size)
        )
        return out.astype(q.dtype)

    def accumulate(m, l, acc, k_cur, v_cur, i):
        # After i rotations this device holds the chunk originating at
        # stage - i (mod ring).
        src = (stage - i) % ring_size
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur).astype(
            jnp.float32
        ) * scale
        if causal:
            k_pos = src * tl + jnp.arange(tl)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        else:
            mask = jnp.ones((tl, tl), bool)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # Masked entries contribute exactly zero probability mass.
        p = jnp.where(mask[None, None],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        correction = jnp.exp(m - m_new)
        l = l * correction + jnp.sum(p, axis=-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32)
        )
        return m_new, l, acc

    def body(carry, i):
        # Rotate first (see the flash body): ring_size-1 rotations total.
        k_cur, v_cur, m, l, acc = carry
        perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]
        k_cur = jax.lax.ppermute(k_cur, SEQ_AXIS, perm)
        v_cur = jax.lax.ppermute(v_cur, SEQ_AXIS, perm)
        m, l, acc = accumulate(m, l, acc, k_cur, v_cur, i)
        return (k_cur, v_cur, m, l, acc), None

    m0 = jnp.full((b, h, tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tl), jnp.float32)
    acc0 = jnp.zeros((b, h, tl, d), jnp.float32)
    m0, l0, acc0 = accumulate(m0, l0, acc0, k, v, jnp.zeros((), jnp.int32))
    (_, _, m, l, acc), _ = jax.lax.scan(
        body, (k, v, m0, l0, acc0), jnp.arange(1, ring_size)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True) -> jax.Array:
    """[B, H, T, D] exact blockwise ring attention over the 'seq' axis
    (SURVEY §5.7; ring schedule over ICI)."""
    mesh = get_sequence_mesh()
    if mesh is None:
        return full_attention(q, k, v, causal)
    ring_size = dict(zip(mesh.axis_names, mesh.devices.shape))[SEQ_AXIS]
    if q.shape[2] % ring_size:
        return full_attention(q, k, v, causal)
    spec = _SP_RULES.partition_spec(None, None, shreg.SEQLEN, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: _ring_attention_local(q_, k_, v_, causal, ring_size),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


register_attention("ulysses", ulysses_attention)
register_attention("ring", ring_attention)
