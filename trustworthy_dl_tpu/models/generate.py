"""Autoregressive generation for the GPT-2 family (beyond-reference).

The reference trains GPT-2 but offers no way to sample from it; a complete
framework does.  TPU-native decode loop:

* **KV cache as a pytree of static-shape arrays** ``[L, B, H, S, Dh]`` —
  no dynamic shapes anywhere, so the whole generate call jits once per
  (prompt_len, max_new_tokens) pair and runs as a single XLA program.
* **Prefill** runs the stacked-block scan over the full prompt (MXU-sized
  matmuls), writing the cache; **decode** steps a ``lax.scan`` over new
  positions, each step attending to the cache via one [B,H,1,S] product.
* Sampling: greedy, temperature, top-k and top-p.  Pure top-k selects
  its k candidates hierarchically (``_exact_topk``: segment-wise
  ``lax.top_k`` then re-select — exact, ~10× cheaper than full-vocab
  top-k on TPU) and samples among them, so no full-vocab mask or
  categorical ever runs; composed top-k+top-p falls back to the
  threshold-mask path (the nucleus filter needs full-vocab order
  anyway).

Numerics are pinned to the training forward: tests assert prefill+decode
logits equal ``gpt2.forward``'s at every position (same params, same
layernorm/attention code via models/layers.py primitives).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models import layers as L

Params = Dict[str, Any]


class KVCache(NamedTuple):
    k: jax.Array       # [L, B, H, S, Dh]
    v: jax.Array       # [L, B, H, S, Dh]
    # i32[] — number of valid positions, shared by every row (batch
    # generate), OR i32[B] — per-row valid lengths (the serving engine's
    # slotted cache, where each slot decodes at its own position).  The
    # rank is static under jit, so the two spellings trace to different
    # programs but share all the code below.
    length: jax.Array
    # int8 KV tier (quant/int8.py): when k/v store int8, these hold the
    # per-(head, position) f32 scales [L, B, H, S]; None selects the
    # full-precision path.  The presence branch is on pytree STRUCTURE,
    # resolved at trace time — each engine still compiles exactly one
    # decode program, and None adds zero leaves to the batch-generate
    # pytree (its program is bit-identical to the pre-quant one).
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None


def init_cache(cfg: gpt2.GPT2Config, batch: int, max_len: int,
               kv_dtype: Optional[Any] = None) -> KVCache:
    """``kv_dtype=None`` keeps the model compute dtype; ``jnp.int8``
    selects the quantized cache (int8 values + f32 per-(head, position)
    scales, initialised to 0 so untouched rows dequantise to exact
    zeros, same as the dense zeros of the plain cache)."""
    kv_dtype = cfg.dtype if kv_dtype is None else kv_dtype
    shape = (cfg.n_layer, batch, cfg.n_head, max_len,
             cfg.n_embd // cfg.n_head)
    if kv_dtype == jnp.int8:
        scales = jnp.zeros(shape[:-1], jnp.float32)
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            length=jnp.zeros((), jnp.int32),
            k_scale=scales, v_scale=scales,
        )
    return KVCache(
        k=jnp.zeros(shape, kv_dtype),
        v=jnp.zeros(shape, kv_dtype),
        length=jnp.zeros((), jnp.int32),
    )


def _split_heads(a: jax.Array, n_head: int) -> jax.Array:
    b, t, d = a.shape
    return a.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)


def _write_cache_rows(layer_kv: jax.Array, new: jax.Array,
                      start: jax.Array) -> jax.Array:
    """Write [B, H, T, ...] new rows into the [B, H, S, ...] cache at
    ``start`` — scalar (all rows aligned) or i32[B] (per-row offsets;
    the vmap'd dynamic_update_slice lowers to a static-shape scatter)."""
    new = new.astype(layer_kv.dtype)
    trail = (0,) * (layer_kv.ndim - 3)
    if jnp.ndim(start) == 0:
        return jax.lax.dynamic_update_slice(
            layer_kv, new, (0, 0, start) + trail
        )
    row_update = jax.vmap(
        lambda cache_row, new_row, off: jax.lax.dynamic_update_slice(
            cache_row, new_row, (0, off) + trail
        )
    )
    return row_update(layer_kv, new, start)


def _attn_qkv(block: Params, x: jax.Array,
              cfg: gpt2.GPT2Config) -> Tuple[jax.Array, jax.Array,
                                             jax.Array]:
    """The pre-attention scaffolding EVERY cached-decode block shares
    (gathered-view path and kernel path alike — one spelling, so a
    numerics fix cannot diverge them): ln_1 + fused qkv projection +
    head split.  [B, T, D] -> q, k, v [B, H, T, Dh]."""
    from trustworthy_dl_tpu.quant import int8 as q8

    dtype = cfg.dtype
    y = L.layernorm(block["ln_1"], x).astype(dtype)
    qkv = q8.qdense(block["attn"]["qkv"], y, dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return tuple(_split_heads(a, cfg.n_head) for a in (q, k, v))


def _attn_mlp_tail(block: Params, x: jax.Array, out: jax.Array,
                   cfg: gpt2.GPT2Config,
                   adapter: Optional[tuple] = None,
                   adapter_pool: Optional[tuple] = None,
                   adapter_impl: str = "jnp") -> jax.Array:
    """The post-attention scaffolding every cached-decode block shares:
    merge heads, attention projection + residual, ln_2 + MLP +
    residual.  ``out`` [B, H, T, Dh] is the attention output.

    ``adapter`` (serve/adapters.py) is the per-row gathered adapter
    slice ``(a [B, 2, D, r], b [B, 2, r, D], a_scale, b_scale)`` —
    scales None except on the int8 tier.  Site 0 rides the attention
    output projection's input, site 1 the MLP's ln_2 input; a row
    pointing at the reserved zero page contributes an exactly-zero
    delta.  ``None`` (every non-serving caller, and every serve program
    with ``adapter_rank == 0``) keeps this function bit-for-bit the
    pre-adapter tail — structural absence, not a traced branch.

    ``adapter_pool`` is the UNGATHERED pool form ``(a_l [P+1, 2, D, r],
    b_l [P+1, 2, r, D], a_scale_l, b_scale_l, apages [B])`` for the
    in-grid kernel path (``adapter_impl`` "pallas"/"interpret"): the
    per-slot page row joins the kernel's scalar-prefetch operands and
    the A/B tiles stream HBM→VMEM inside ``ops.adapter_delta`` — no
    gathered page copy exists.  Exactly one of ``adapter`` /
    ``adapter_pool`` may be given."""
    from trustworthy_dl_tpu.ops.fused_dequant_matmul import lowrank_delta
    from trustworthy_dl_tpu.quant import int8 as q8

    dtype = cfg.dtype
    b, t, d = x.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)

    def delta(site_x: jax.Array, site: int) -> Optional[jax.Array]:
        if adapter_pool is not None:
            from trustworthy_dl_tpu.ops import paged_attention as pattn

            a_l, b_l, as_l, bs_l, apages = adapter_pool
            return pattn.adapter_delta(
                site_x, a_l[:, site], b_l[:, site], apages,
                a_scale=None if as_l is None else as_l[:, site],
                b_scale=None if bs_l is None else bs_l[:, site],
                interpret=(adapter_impl == "interpret"),
            )
        if adapter is not None:
            a_s, b_s, a_sc, b_sc = adapter
            return lowrank_delta(
                site_x, a_s[:, site], b_s[:, site],
                None if a_sc is None else a_sc[:, site],
                None if b_sc is None else b_sc[:, site],
            )
        return None

    x = x + q8.qdense(block["attn"]["proj"], out, dtype).astype(x.dtype)
    d0 = delta(out, 0)
    if d0 is not None:
        x = x + d0.astype(x.dtype)
    y = L.layernorm(block["ln_2"], x).astype(dtype)
    ln2 = y
    y = q8.qdense(block["mlp"]["fc"], y, dtype)
    y = jax.nn.gelu(y)
    mlp = q8.qdense(block["mlp"]["proj"], y, dtype).astype(x.dtype)
    d1 = delta(ln2, 1)
    if d1 is not None:
        mlp = mlp + d1.astype(x.dtype)
    return x + mlp


def _block_with_cache(block: Params, x: jax.Array, layer_k: jax.Array,
                      layer_v: jax.Array, start: jax.Array,
                      cfg: gpt2.GPT2Config,
                      layer_k_scale: Optional[jax.Array] = None,
                      layer_v_scale: Optional[jax.Array] = None,
                      adapter: Optional[tuple] = None,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 Optional[jax.Array], Optional[jax.Array]]:
    """One transformer block over [B, T, D] new positions, attending to
    cached K/V [B, H, S, Dh] plus itself (causal).  ``start`` is the write
    offset — positions [start, start+T) land in the cache.  Scalar
    ``start`` writes every row at the same offset (batch generate);
    ``start`` i32[B] writes each row at its own offset (the serving
    engine's slotted cache).

    int8 KV tier: when ``layer_k_scale``/``layer_v_scale`` [B, H, S] are
    given, the cache stores int8 and new K/V rows are quantized at the
    write site (symmetric per-(head, position), quant/int8.py).  The
    reads never materialise a dequantized cache copy: a cached key's
    scale is constant along the contracted Dh axis, so it multiplies the
    score AFTER the int8 dot product, and a cached value's scale folds
    into the attention probabilities before the PV contraction — exact
    algebra, only the int8 rounding differs from the dense path.

    Returns (activations, layer_k, layer_v, layer_k_scale,
    layer_v_scale); scales pass through as None on the dense path."""
    from trustworthy_dl_tpu.quant import int8 as q8

    dtype = cfg.dtype
    b, t, d = x.shape
    h = cfg.n_head
    s = layer_k.shape[-2]
    quantized = layer_k_scale is not None

    q, k, v = _attn_qkv(block, x, cfg)                 # [B, H, T, Dh]

    if quantized:
        k_q, k_s = q8.quantize_kv(k)                   # int8, f32 [B,H,T]
        v_q, v_s = q8.quantize_kv(v)
        layer_k = _write_cache_rows(layer_k, k_q, start)
        layer_v = _write_cache_rows(layer_v, v_q, start)
        layer_k_scale = _write_cache_rows(layer_k_scale, k_s, start)
        layer_v_scale = _write_cache_rows(layer_v_scale, v_s, start)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                            layer_k.astype(dtype))
        scores = scores * layer_k_scale[:, :, None, :] / math.sqrt(d // h)
    else:
        layer_k = _write_cache_rows(layer_k, k, start)
        layer_v = _write_cache_rows(layer_v, v, start)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, layer_k) \
            / math.sqrt(d // h)
    # Causal vs cache: query at absolute position start+i may see cache
    # slots [0, start+i].
    if jnp.ndim(start) == 0:
        q_pos = start + jnp.arange(t)[:, None]         # [T, 1]
        k_pos = jnp.arange(s)[None, :]                 # [1, S]
        mask = k_pos <= q_pos                          # [T, S]
        mask = mask[None, None]                        # [1, 1, T, S]
    else:
        q_pos = start[:, None, None] + jnp.arange(t)[None, :, None]
        k_pos = jnp.arange(s)[None, None, :]
        mask = (k_pos <= q_pos)[:, None]               # [B, 1, T, S]
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    if quantized:
        pv = (probs * layer_v_scale[:, :, None, :]).astype(dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", pv, layer_v.astype(dtype))
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, layer_v)
    x = _attn_mlp_tail(block, x, out, cfg, adapter=adapter)
    return x, layer_k, layer_v, layer_k_scale, layer_v_scale


def _decode_view(params: Params, cfg: gpt2.GPT2Config) -> Params:
    """Pre-cast the bandwidth-dominant weights to the compute dtype ONCE.

    ``dense()``/``project_logits()`` cast their f32 master weights to
    ``cfg.dtype`` at every use; inside the decode scan that cast re-reads
    the f32 copy from HBM every token.  b=1 decode is pure
    weight-bandwidth, so hoisting the cast halves the per-token HBM
    traffic (f32 → bf16 reads).  Numerics are bit-identical: it is the
    same cast, done once — ``dense``'s ``astype`` becomes a no-op on the
    pre-cast leaves.  Embedding lookups and layernorms keep their f32
    params (their numerics are defined in f32)."""
    if cfg.dtype == jnp.float32:
        return params

    def cast_dense(d):
        return {"w": d["w"].astype(cfg.dtype),
                "b": d["b"].astype(cfg.dtype)}

    blocks = params["blocks"]
    out = dict(params)
    out["blocks"] = {
        "ln_1": blocks["ln_1"],
        "ln_2": blocks["ln_2"],
        "attn": {"qkv": cast_dense(blocks["attn"]["qkv"]),
                 "proj": cast_dense(blocks["attn"]["proj"])},
        "mlp": {"fc": cast_dense(blocks["mlp"]["fc"]),
                "proj": cast_dense(blocks["mlp"]["proj"])},
    }
    # Pre-cast tied head for the per-token [B,D]x[D,V] projection — the
    # single largest weight read of a decode step.  params["wte"] itself
    # stays f32 for the embedding lookup.
    out["wte_head"] = params["wte"].astype(cfg.dtype)
    return out


def _apply_with_cache(params: Params, tokens: jax.Array, cache: KVCache,
                      cfg: gpt2.GPT2Config,
                      last_pos: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, KVCache]:
    """Run all blocks over ``tokens`` [B, T] starting at cache.length;
    returns (logits of the LAST position [B, V], updated cache).

    ``cache.length`` may be scalar (all rows aligned — batch generate) or
    i32[B] (per-row offsets — the serving engine's slotted decode); see
    _block_with_cache.  ``last_pos`` (traced i32[], optional) overrides
    WHICH position's logits are returned: the serving prefill pads prompts
    to a bucket length, so the logits it needs live at real_len-1, not at
    the (padded) last position.  None keeps the static [-1] slice — the
    batch-generate program is unchanged."""
    start = cache.length
    t = tokens.shape[-1]
    if jnp.ndim(start) == 0:
        pos = start + jnp.arange(t)                        # [T]
    else:
        pos = start[:, None] + jnp.arange(t)[None, :]      # [B, T]
    x = (params["wte"][tokens] + params["wpe"][pos]).astype(jnp.float32)

    def scan_fn(carry, layer):
        x = carry
        block, lk, lv, lks, lvs = layer
        x, lk, lv, lks, lvs = _block_with_cache(block, x, lk, lv, start,
                                                cfg, lks, lvs)
        return x, (lk, lv, lks, lvs)

    # Rolled layer scan: one compiled block body regardless of depth
    # (rolled against unrolled decode is not measured — PERF.md).  The
    # int8 scale planes (None on the dense path — zero leaves, same
    # program) ride the same scan.
    x, (new_k, new_v, new_ks, new_vs) = jax.lax.scan(
        scan_fn, x,
        (params["blocks"], cache.k, cache.v, cache.k_scale, cache.v_scale),
    )
    logits = _final_logits(params, x, cfg, last_pos)
    return logits, KVCache(k=new_k, v=new_v, length=start + t,
                           k_scale=new_ks, v_scale=new_vs)


def _final_logits(params: Params, x: jax.Array, cfg: gpt2.GPT2Config,
                  last_pos: Optional[jax.Array]) -> jax.Array:
    """Project ONE position's activations to logits [B, V] — the shared
    tail of the dense and paged cache paths.  ``last_pos=None`` keeps the
    static [-1] slice (batch generate); a traced value selects the real
    last prompt position under bucket/chunk padding: i32[] for every row,
    or i32[B] a row's own (the chunk program's rows are different
    prompts)."""
    if last_pos is None:
        x_last = x[:, -1:, :]
    elif jnp.ndim(last_pos) == 0:
        x_last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    else:
        x_last = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)
    wte_head = params.get("wte_head")
    if wte_head is None:
        return gpt2.unembed(params, x_last, cfg)[:, 0, :]  # [B, V]
    normed = L.layernorm(params["ln_f"], x_last)
    return (normed.astype(cfg.dtype) @ wte_head.T).astype(jnp.float32)[:, 0, :]


def _all_logits(params: Params, x: jax.Array,
                cfg: gpt2.GPT2Config) -> jax.Array:
    """Project EVERY fed position to logits [B, T, V] — the speculative
    verify pass needs the target model's choice at each draft position,
    not just the last one.  Per-position math is identical to
    :func:`_final_logits` (same layernorm + head matmul, row-wise), so
    position i of a T-wide projection is bit-identical to a 1-wide
    projection of the same activations."""
    wte_head = params.get("wte_head")
    if wte_head is None:
        return gpt2.unembed(params, x, cfg)
    normed = L.layernorm(params["ln_f"], x)
    return (normed.astype(cfg.dtype) @ wte_head.T).astype(jnp.float32)


def fused_verify_logits(params: Params, x: jax.Array,
                        cfg: gpt2.GPT2Config, *, interpret: bool
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel twin of :func:`_all_logits` + the trust epilogue for the
    speculative-verify tail: pre-``ln_f`` activations ``x`` [R, T, D]
    -> (logits [R, T, V] f32, entropy [R·T], margin [R·T]) in ONE
    streaming pass over the vocab (``ops.fused_verify_tail``) — the
    [R, T, V] materialise-then-re-read of the jnp tail collapses into
    per-tile reductions while each head tile is still in VMEM.

    The head operand is exactly the one ``_all_logits`` contracts with:
    ``wte_head`` when the decode view split one out, else the tied
    ``wte`` cast to the compute dtype (``gpt2.project_logits``' own
    cast); the layernorm + dtype rounding discipline matches
    position-for-position, so the verify sampler sees bit-identical
    logits and the scheduler's trust stats keep the pinned epilogue
    algebra."""
    from trustworthy_dl_tpu.ops import paged_attention as pattn

    r, t, d = x.shape
    wte_head = params.get("wte_head")
    if wte_head is None:
        wte_head = params["wte"].astype(cfg.dtype)
    normed = L.layernorm(params["ln_f"], x).astype(cfg.dtype)
    logits, ent, mar = pattn.fused_verify_tail(
        normed.reshape(r * t, d), wte_head, interpret=interpret)
    return logits.reshape(r, t, -1), ent, mar


# ---------------------------------------------------------------------------
# Paged-KV read/write path (serve/kv_slots.PagedKV pools).
#
# The paged pool stores K/V in fixed-size token blocks, every layer in ONE
# array [L, NB, BLOCK, H·Dh]: a position's K (or V) of every head is one
# contiguous row.  A slot's logical cache is reassembled through its block
# table (i32 per-slot physical ids — traced VALUES, so block churn never
# recompiles).  Inside a serving program the pool never leaves its buffer
# or its layout: the layer loop CARRIES the stacked pool, a layer writes
# its R·T new rows in place at (layer, physical block, offset) and reads
# the pool through (layer, table) — the kernels through their index map,
# the jnp path through one gather.  Row write, kernel block and resting
# layout agree on this shape (tests/test_chip_compile.py holds the
# compiler to it: no copy of the pool or of a layer in either program).
#
# The jnp attention core is the untouched _block_with_cache: the gathered
# view is numerically the same [R, H, S, Dh] cache a contiguous KVCache
# holds (valid positions carry identical values; garbage positions are
# masked to exactly-zero probabilities), so paged decode is bit-identical
# to generate()'s by construction.  Positions outside the slot's table
# land in the reserved trash block 0.
# ---------------------------------------------------------------------------


def _paged_gather(pool: jax.Array, table: jax.Array, layer: jax.Array,
                  n_head: int) -> jax.Array:
    """Layer ``layer`` of a stacked pool array [L, NB, BLOCK, H·X] + block
    table [R, NBPS] -> contiguous per-row view [R, H, NBPS*BLOCK, X]:
    X = Dh for K and V, 1 for the int8 tier's scale planes."""
    g = pool[layer, table]                      # [R, NBPS, BLOCK, H·X]
    return g.reshape(g.shape[0], -1, n_head, g.shape[-1] // n_head) \
        .transpose(0, 2, 1, 3)


def _pool_write_coords(table_read: jax.Array, start: jax.Array, r: int,
                       t: int, bsz: int, nbps: int
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(positions [R, T], physical block [R·T], in-block offset [R·T])
    for the T positions each row writes this call — positions past the
    slot's real table land in the reserved trash block 0.  ONE spelling
    shared by the gather path (which extracts the written rows from its
    view at ``pos``) and the kernel path (which writes the fresh K/V
    directly), so for the SAME block input the two paths write identical
    values to identical pool coordinates (across a multi-layer scan,
    deeper layers inherit the attention paths' f32-rounding epsilon
    through their activations)."""
    if jnp.ndim(start) == 0:
        pos = jnp.broadcast_to((start + jnp.arange(t))[None, :], (r, t))
    else:
        pos = start[:, None] + jnp.arange(t)[None, :]      # [R, T]
    lb = pos // bsz
    valid = lb < nbps
    phys = jnp.take_along_axis(table_read, jnp.minimum(lb, nbps - 1),
                               axis=1)
    phys = jnp.where(valid, phys, 0).reshape(-1)           # 0 = trash
    offs = (pos % bsz).reshape(-1)
    return pos, phys, offs


def _pool_write_rows(pool: jax.Array, rows: jax.Array, layer: jax.Array,
                     phys: jax.Array, offs: jax.Array) -> jax.Array:
    """Write the call's R·T new positions into a stacked pool array in
    place, one contiguous row a position at (layer, phys, offs): ``rows``
    [R, H, T, Dh] (K or V) into [L, NB, BLOCK, H·Dh], the int8 tier's
    scales [R, H, T] into [L, NB, BLOCK, H], or a latent layer's ONE row a
    position [R, 1, T, lanes] into [L, NB, BLOCK, lanes]
    (``models/decoder.py``)."""
    r, _, t = rows.shape[:3]
    rows = jnp.moveaxis(rows, 1, 2).reshape(r * t, -1)
    return pool.at[layer, phys, offs].set(rows.astype(pool.dtype))


def _paged_block(block: Params, x: jax.Array, pool_k: jax.Array,
                 pool_v: jax.Array, table: jax.Array, start: jax.Array,
                 cfg: gpt2.GPT2Config, layer: jax.Array,
                 pool_ks: Optional[jax.Array] = None,
                 pool_vs: Optional[jax.Array] = None,
                 attn_impl: str = "jnp",
                 adapter_l: Optional[tuple] = None,
                 adapter_impl: str = "jnp",
                 ) -> Tuple[jax.Array, jax.Array, jax.Array,
                            Optional[jax.Array], Optional[jax.Array]]:
    """Transformer block ``layer`` over [R, T, D] new positions against
    the STACKED paged pool [L, NB, BLOCK, H·Dh] (scale planes [L, NB,
    BLOCK, H]); returns the activations and the pool arrays with this
    layer's R·T new rows written, nothing else touched.  ``attn_impl``
    (trace-time static — the scheduler bakes its resolved path into each
    compiled program) selects the attention read:

    * ``"jnp"`` (default, the reference semantics): gather each row's
      view through ``(layer, table)``, run the dense ``_block_with_cache``
      core on it (one numerics source for generate and paged serve), then
      write the newly written rows back into the pool.
    * ``"pallas"`` / ``"interpret"``: write the fresh K/V into the pool
      FIRST (same quantize-at-write values, same ``_pool_write_coords``),
      then run the ragged ``ops.paged_attention`` kernel straight over
      the stacked pool at ``layer``: no [R, H, S, Dh] view is ever
      materialised, int8 tiles dequantise in-register, rows stop
      streaming at their true length.  Write-then-attend equals the jnp
      path's write-into-view because writes only ever land in blocks the
      row owns exclusively (kv_slots' COW discipline) — no row can
      observe another row's same-tick write on either path.

    ``start`` follows the dense contract: scalar (every row at one
    offset) or i32[R], a row's own (the fused decode, T=1, and the chunk
    program, a mid-prefill slot a row).

    ``adapter_l`` is one layer's slice of the paged adapter pool plus
    the per-slot page table: ``(a_l [P+1, 2, D, r], b_l [P+1, 2, r, D],
    a_scale_l, b_scale_l, apages [R])``.  On the jnp paths the page
    gather happens HERE, inside the layer scan — exactly one layer's
    gathered pages are ever live, mirroring the KV view discipline —
    and feeds ``_attn_mlp_tail``.  When ``adapter_impl`` (trace-time
    static, resolved per-program by ``ops.resolve_attn_impls``) is
    "pallas"/"interpret" AND the attention read is on a kernel path,
    the gather disappears entirely: the pool form is handed down and
    ``ops.adapter_delta`` streams exactly the pages it needs HBM→VMEM
    inside its own grid, per-slot page row as scalar prefetch."""
    adapter_s: Optional[tuple] = None
    if attn_impl != "jnp":
        adapter_pool = None
        if adapter_l is not None and adapter_impl != "jnp":
            adapter_pool = adapter_l
        elif adapter_l is not None:
            a_l, b_l, as_l, bs_l, apages = adapter_l
            adapter_s = (a_l[apages], b_l[apages],
                         None if as_l is None else as_l[apages],
                         None if bs_l is None else bs_l[apages])
        return _paged_block_kernel(block, x, pool_k, pool_v, table,
                                   start, cfg, layer, pool_ks, pool_vs,
                                   interpret=(attn_impl == "interpret"),
                                   adapter=adapter_s,
                                   adapter_pool=adapter_pool,
                                   adapter_impl=adapter_impl)
    if adapter_l is not None:
        a_l, b_l, as_l, bs_l, apages = adapter_l
        adapter_s = (a_l[apages], b_l[apages],
                     None if as_l is None else as_l[apages],
                     None if bs_l is None else bs_l[apages])
    r, t, _ = x.shape
    nbps = table.shape[1]
    bsz = pool_k.shape[2]
    if t > 1:
        # A prefill chunk may extend past the logical view (its start is
        # only block-aligned, not chunk-aligned, after a prefix hit) —
        # pad the table with trash columns so the in-view write never
        # clamps onto real positions.  Width is static; the extra
        # columns are masked (k_pos > q_pos) so numerics are unchanged.
        pad = jnp.zeros((r, t // bsz + 1), table.dtype)
        table_read = jnp.concatenate([table, pad], axis=1)
    else:
        table_read = table
    view_k = _paged_gather(pool_k, table_read, layer, cfg.n_head)
    view_v = _paged_gather(pool_v, table_read, layer, cfg.n_head)
    view_ks = (_paged_gather(pool_ks, table_read, layer, cfg.n_head)[..., 0]
               if pool_ks is not None else None)
    view_vs = (_paged_gather(pool_vs, table_read, layer, cfg.n_head)[..., 0]
               if pool_vs is not None else None)
    x, view_k, view_v, view_ks, view_vs = _block_with_cache(
        block, x, view_k, view_v, start, cfg, view_ks, view_vs,
        adapter=adapter_s
    )
    # Positions this call wrote into the view -> (physical block, offset).
    pos, phys, offs = _pool_write_coords(table_read, start, r, t, bsz,
                                         nbps)
    idx = pos[:, None, :, None]                            # [R, 1, T, 1]

    def rows_of(view):                     # [R, H, S(, Dh)] -> [R, H, T(, Dh)]
        if view.ndim == 4:
            return jnp.take_along_axis(view, idx, axis=2)
        return jnp.take_along_axis(view, idx[..., 0], axis=2)

    pool_k = _pool_write_rows(pool_k, rows_of(view_k), layer, phys, offs)
    pool_v = _pool_write_rows(pool_v, rows_of(view_v), layer, phys, offs)
    if pool_ks is not None:
        pool_ks = _pool_write_rows(pool_ks, rows_of(view_ks), layer,
                                   phys, offs)
        pool_vs = _pool_write_rows(pool_vs, rows_of(view_vs), layer,
                                   phys, offs)
    return x, pool_k, pool_v, pool_ks, pool_vs


def _paged_block_kernel(block: Params, x: jax.Array, pool_k: jax.Array,
                        pool_v: jax.Array, table: jax.Array,
                        start: jax.Array, cfg: gpt2.GPT2Config,
                        layer: jax.Array,
                        pool_ks: Optional[jax.Array],
                        pool_vs: Optional[jax.Array],
                        interpret: bool,
                        adapter: Optional[tuple] = None,
                        adapter_pool: Optional[tuple] = None,
                        adapter_impl: str = "jnp",
                        ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                   Optional[jax.Array],
                                   Optional[jax.Array]]:
    """The kernel-path twin of the gather branch in :func:`_paged_block`:
    write-then-attend.  The fresh K/V (quantized at the write on the int8
    tier — the exact values the gather path writes) go into the stacked
    pool first, in place; a ``ops.paged_attention`` program then reads
    positions [0, start+T) of ``layer`` straight from the pool with the
    causal window masked in absolute positions, which is precisely what
    the gathered view exposes to ``_block_with_cache``.  T selects the
    program (static — each serve program compiles one shape): the decode
    program up to ``QROWS`` rows (decode T=1, speculative verify T=k+1),
    the chunked-prefill program above it (the same kernel; the chunk in
    one query tile where VMEM allows, else in tiles whose causal block
    bounds skip the KV blocks a whole tile cannot see)."""
    from trustworthy_dl_tpu.ops import paged_attention as pattn
    from trustworthy_dl_tpu.quant import int8 as q8

    r, t, _ = x.shape
    nbps = table.shape[1]
    bsz = pool_k.shape[2]

    # Shared pre/post-attention scaffolding (_attn_qkv/_attn_mlp_tail):
    # only the attention READ differs from _block_with_cache.
    q, k, v = _attn_qkv(block, x, cfg)                     # [R, H, T, Dh]

    _, phys, offs = _pool_write_coords(table, start, r, t, bsz, nbps)
    if pool_ks is not None:
        k, k_s = q8.quantize_kv(k)                         # int8, f32 [R,H,T]
        v, v_s = q8.quantize_kv(v)
        pool_ks = _pool_write_rows(pool_ks, k_s, layer, phys, offs)
        pool_vs = _pool_write_rows(pool_vs, v_s, layer, phys, offs)
    pool_k = _pool_write_rows(pool_k, k, layer, phys, offs)
    pool_v = _pool_write_rows(pool_v, v, layer, phys, offs)

    attend = (pattn.paged_prefill_attention if t > pattn.QROWS
              else pattn.paged_attention)
    out = attend(
        q, pool_k, pool_v, table, start, layer=layer,
        k_scale=pool_ks, v_scale=pool_vs, interpret=interpret,
    ).astype(cfg.dtype)                                    # [R, H, T, Dh]
    x = _attn_mlp_tail(block, x, out, cfg, adapter=adapter,
                       adapter_pool=adapter_pool, adapter_impl=adapter_impl)
    return x, pool_k, pool_v, pool_ks, pool_vs


def _apply_with_cache_paged(params: Params, tokens: jax.Array,
                            pool_k: jax.Array, pool_v: jax.Array,
                            pool_ks: Optional[jax.Array],
                            pool_vs: Optional[jax.Array],
                            table: jax.Array, start: jax.Array,
                            cfg: gpt2.GPT2Config,
                            last_pos: Optional[jax.Array] = None,
                            all_logits: bool = False,
                            attn_impl: str = "jnp",
                            adapter: Optional[tuple] = None,
                            adapter_impl: str = "jnp",
                            hidden: bool = False,
                            ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       Optional[jax.Array],
                                       Optional[jax.Array]]:
    """Paged twin of :func:`_apply_with_cache`: run all blocks over
    ``tokens`` [R, T] against the stacked block pool [L, NB, BLOCK, H·Dh].
    The pool arrays (on the int8 tier the scale planes too) are the layer
    scan's CARRY beside the activations: each layer writes its R·T new
    rows in place and reads through ``(layer, table)``, so the pool is
    never sliced, relaid out or re-stacked — under donation the program
    updates the caller's buffers.  Returns (logits [R, V], updated pool
    arrays) — pool updates are functional, the scheduler threads them.
    ``all_logits`` (trace-time bool) returns [R, T, V] logits at every
    fed position instead — the speculative-verify program's tail, where
    the target's token choice is needed at each draft position.
    ``hidden`` (trace-time bool) skips the projection entirely and
    returns the pre-``ln_f`` activations [R, T, D] — the fused-verify
    caller hands them to :func:`fused_verify_logits`, which streams the
    vocab ONCE for logits AND trust stats instead of materialising
    [R, T, V] and re-reading it.
    ``attn_impl`` (trace-time static, see :func:`_paged_block`) swaps the
    gathered-view attention for the ragged ``ops.paged_attention``
    kernel, and ``adapter_impl`` likewise swaps the per-layer adapter
    page gather for the in-grid ``ops.adapter_delta`` stream;
    tables/starts/pages stay traced values every way, so the
    compile-once pin holds on all paths.

    ``adapter`` is the paged adapter-pool pytree ``(a [L, P+1, 2, D,
    r], b, a_scale, b_scale, apages [R])`` (serve/adapters.py): the
    pool sides join the layer scan's xs (leading L axis, beside the
    blocks' weights and the layer's index) and the per-slot page table
    is closed over — both traced values, so adapter churn and tenant-mix
    changes never recompile.  ``None`` (adapter_rank == 0) contributes
    zero pytree leaves: the compiled program is structurally identical
    to the pre-adapter one."""
    t = tokens.shape[-1]
    if jnp.ndim(start) == 0:
        pos = start + jnp.arange(t)                        # [T]
    else:
        pos = start[:, None] + jnp.arange(t)[None, :]      # [R, T]
    x = (params["wte"][tokens] + params["wpe"][pos]).astype(jnp.float32)

    if adapter is not None:
        ad_a, ad_b, ad_as, ad_bs, apages = adapter
    else:
        ad_a = ad_b = ad_as = ad_bs = apages = None

    def scan_fn(carry, layer_xs):
        x, pk, pv, pks, pvs = carry
        block, layer, a_l, b_l, as_l, bs_l = layer_xs
        adapter_l = (None if a_l is None
                     else (a_l, b_l, as_l, bs_l, apages))
        return _paged_block(block, x, pk, pv, table, start, cfg, layer,
                            pks, pvs, attn_impl=attn_impl,
                            adapter_l=adapter_l,
                            adapter_impl=adapter_impl), None

    layers = jnp.arange(pool_k.shape[0], dtype=jnp.int32)
    (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
        scan_fn, (x, pool_k, pool_v, pool_ks, pool_vs),
        (params["blocks"], layers, ad_a, ad_b, ad_as, ad_bs),
    )
    if hidden:
        return x, new_k, new_v, new_ks, new_vs
    if all_logits:
        return _all_logits(params, x, cfg), new_k, new_v, new_ks, new_vs
    return _final_logits(params, x, cfg, last_pos), new_k, new_v, \
        new_ks, new_vs


def _exact_topk(logits: jax.Array, k: int, rows: int = 32
                ) -> Tuple[jax.Array, jax.Array]:
    """[B, V] -> (values [B, k], indices [B, k]) — exact top-k,
    hierarchically.

    ``lax.top_k`` straight over a 50k-wide vocab row costs ~0.47 ms/token
    on v5e — as much as the entire 12-layer decode body.  Splitting the
    vocab into ``rows`` segments, taking top-k per segment (parallel,
    log-factor on a 32× smaller extent) and re-selecting over the
    rows·k candidates is EXACT — every global top-k element is within its
    own segment's top-k.  -inf padding never enters the top k real values
    since k ≤ segment width."""
    b, v = logits.shape
    seg = -(-v // rows)          # ceil
    if k > seg:                  # degenerate: segments smaller than k
        return jax.lax.top_k(logits, k)
    pad = rows * seg - v
    padded = jnp.pad(logits, ((0, 0), (0, pad)),
                     constant_values=-jnp.inf)
    seg_vals, seg_idx = jax.lax.top_k(
        padded.reshape(b, rows, seg), k
    )                                                       # [B, R, k]
    global_idx = seg_idx + (jnp.arange(rows) * seg)[None, :, None]
    vals, sel = jax.lax.top_k(seg_vals.reshape(b, rows * k), k)  # [B, k]
    idx = jnp.take_along_axis(global_idx.reshape(b, rows * k), sel,
                              axis=-1)
    return vals, idx


def _sample(logits: jax.Array, rng: jax.Array, temperature: jax.Array,
            greedy: bool, top_k: int, top_p: jax.Array,
            use_top_p: bool) -> jax.Array:
    """[B, V] -> [B] next tokens.  ``greedy``, ``top_k`` and ``use_top_p``
    are static (top_k changes lax.top_k output shapes; the nucleus filter
    costs a full-vocab sort per token, so it is compiled out entirely when
    not requested); ``temperature`` and ``top_p`` are traced so sampling
    sweeps reuse one compiled program."""
    if greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0 and not use_top_p:
        # Pure top-k fast path: select the k candidates hierarchically
        # (exact) and sample AMONG them — the categorical runs over
        # [B, k] instead of the full vocab.  Identical distribution: the
        # kept set is the exact top-k and softmax is shift-invariant, so
        # restricting to the candidate values IS the filtered softmax.
        vals, idx = _exact_topk(logits, top_k)
        choice = jax.random.categorical(rng, vals, axis=-1)   # [B]
        return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    if top_k > 0:
        kth = _exact_topk(logits, top_k)[0][:, -1:]      # [B, 1], exact
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if use_top_p:
        # Nucleus: keep the smallest prefix of the sorted distribution
        # whose mass exceeds top_p.  One sort, no scatter — the keep-mask
        # is mapped back by threshold comparison.
        probs = jax.nn.softmax(logits, axis=-1)
        sorted_probs = jnp.sort(probs, axis=-1)[:, ::-1]
        cum = jnp.cumsum(sorted_probs, axis=-1)
        # Threshold = probability of the last kept token: smallest sorted
        # index where cumulative mass reaches top_p.
        keep_sorted = cum - sorted_probs < top_p
        threshold = jnp.min(
            jnp.where(keep_sorted, sorted_probs, jnp.inf), axis=-1,
            keepdims=True,
        )
        logits = jnp.where(probs >= threshold, logits, -jnp.inf)
    return jax.random.categorical(rng, logits, axis=-1)


@partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _generate_jit(params: Params, prompt: jax.Array, rng: jax.Array,
                  temperature: jax.Array, top_p: jax.Array,
                  cfg: gpt2.GPT2Config,
                  max_new_tokens: int, greedy: bool, top_k: int,
                  use_top_p: bool) -> jax.Array:
    b, t_prompt = prompt.shape
    params = _decode_view(params, cfg)
    cache = init_cache(cfg, b, t_prompt + max_new_tokens)
    logits, cache = _apply_with_cache(params, prompt, cache, cfg)
    first = _sample(logits, rng, temperature, greedy, top_k, top_p,
                    use_top_p)

    def body(carry, step_rng):
        tok, cache = carry
        logits, cache = _apply_with_cache(
            params, tok[:, None], cache, cfg
        )
        nxt = _sample(logits, step_rng, temperature, greedy, top_k, top_p,
                      use_top_p)
        return (nxt, cache), nxt

    if max_new_tokens == 1:
        return jnp.concatenate([prompt, first[:, None]], axis=1)
    step_rngs = jax.random.split(jax.random.fold_in(rng, 1),
                                 max_new_tokens - 1)
    (_, _), rest = jax.lax.scan(body, (first, cache), step_rngs)
    out = jnp.concatenate(
        [prompt, first[:, None], rest.T], axis=1
    )
    return out


def generate(params: Params, cfg: gpt2.GPT2Config, prompt: jax.Array,
             max_new_tokens: int, temperature: float = 0.0, top_k: int = 0,
             top_p: float = 1.0, rng: Optional[jax.Array] = None
             ) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` [B, T].

    Returns [B, T + max_new_tokens].  ``temperature=0`` decodes greedily;
    ``top_k>0`` restricts sampling to the k most likely tokens;
    ``top_p<1`` restricts to the nucleus holding that probability mass
    (filters compose: top-k first, then top-p).  The whole call is one
    jitted XLA program (static-shape KV cache), compiled once per
    (shape, greedy, top_k) — temperature and top_p are traced, so
    sampling sweeps do not recompile.

    ``rng=None`` defaults to ``PRNGKey(0)``: sampling is DETERMINISTIC
    across identical calls by design (reproducibility-first, like every
    other seed in this framework) — pass a fresh key per call for variety.

    Decode always runs the fused XLA attention over the cache; numerics
    are pinned token-for-token against an XLA-attention training forward
    (the default ``attn_impl='auto'`` resolves to that path for contexts
    below AUTO_FLASH_MIN_T; tests/test_generate.py).  A forward that ran
    the Pallas flash kernel instead — explicit ``attn_impl='flash'``, or
    auto at T ≥ AUTO_FLASH_MIN_T on TPU — agrees to kernel-vs-XLA
    epsilon, where near-tie logits can flip under greedy decode."""
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    total = prompt.shape[-1] + max_new_tokens
    if total > cfg.n_positions:
        raise ValueError(
            f"prompt+new = {total} exceeds n_positions={cfg.n_positions}"
        )
    if not 0 <= top_k <= cfg.vocab_size:
        raise ValueError(
            f"top_k={top_k} out of range [0, vocab_size={cfg.vocab_size}]"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} out of range (0, 1]")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return _generate_jit(params, prompt, rng,
                         jnp.asarray(max(temperature, 1e-6), jnp.float32),
                         jnp.asarray(top_p, jnp.float32),
                         cfg, int(max_new_tokens),
                         float(temperature) <= 0.0, int(top_k),
                         float(top_p) < 1.0)
