"""Continuous (iteration-level) batching over the paged KV block pool.

Orca's insight (Yu et al., OSDI '22): schedule at token granularity, not
request granularity — every tick admits queued requests, advances the
prompts still being prefilled, runs ONE fused decode step for all live
sequences, and retires finished ones immediately so their rows and blocks
free up mid-flight.  ``PagedBatchingScheduler`` is the one scheduler, over
the one pool layout (``kv_slots.PagedKV``):

* **admission** — pure host work: a request claims a decode row
  (``SlotAllocator``) and ``ceil((prompt + max_new) / BLOCK)`` physical
  blocks (``BlockAllocator``), reusing the blocks of the longest prompt
  prefix the radix ``PrefixCache`` holds (refcounted).  No row, or no
  blocks even after evicting cached prefixes, is backpressure: the task
  stays queued, untouched.
* **chunked prefill** — the unshared suffix of a prompt is fed
  ``prefill_chunk`` positions a tick, beside the decode step, so a long
  prompt never head-of-line-blocks the live streams.  One call of
  ``paged_chunk`` holds the next chunk of up to ``chunk_call_rows``
  mid-prefill slots, padded to that many rows (ONE compiled program,
  compiled at its first call; a call a slot where the description's
  chunk is one slot's); a fresh prompt that fits one chunk
  takes ``paged_prefill``.  The chunks are pulled after the decode step's
  dispatch, so the device runs the two back to back.
* **decode** — one program for the scheduler's lifetime
  (``paged_decode``): [MAX_SLOTS] tokens in, [MAX_SLOTS] next tokens out,
  attending through per-slot BLOCK TABLES.  Tables and lengths are traced
  values, so admission, retirement, block churn and prefix sharing never
  change its shapes: it compiles exactly once.  With ``spec_k > 0`` a
  tick drafts and verifies a window instead (``spec_draft``,
  ``spec_verify``).

Inactive rows still compute inside the decode step (static shapes); their
outputs are ignored and their garbage cache writes land in the trash block
(see kv_slots module docstring).

Sampling is per-slot: greedy is a *traced* bool (mixing greedy and
temperature-sampled requests in one batch cannot recompile), temperature is
traced, and each slot consumes its own key stream — laid out exactly like
``models/generate.generate``'s (first token from the request key, step i
from ``split(fold_in(key, 1), max_new-1)[i-1]``), so a single-slot greedy
or sampled request reproduces the batch sampler token-for-token.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trustworthy_dl_tpu.obs.compilewatch import guarded
from trustworthy_dl_tpu.models import decoder
from trustworthy_dl_tpu.models import generate as gen
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.quant import int8 as q8
from trustworthy_dl_tpu.serve.adapters import ZERO_PAGE, adapter_page_row
from trustworthy_dl_tpu.serve.kv_slots import (
    BlockAllocator,
    PagedKV,
    PrefixCache,
    SlotAllocator,
    TRASH_BLOCK,
    blocks_for_span,
    init_paged_pool,
    init_state_pool,
    kv_geometry,
    latent_value_lanes,
    resolve_prefill_chunk,
    validate_paged_geometry,
    zero_state_rows,
)
from trustworthy_dl_tpu.utils.profiling import span

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# Device programs.  Jitted lazily (first use) so importing this module never
# initialises a backend; donation of the big cache buffers is enabled only
# where XLA implements it (TPU) to keep CPU test runs warning-free.
# --------------------------------------------------------------------------


def _sample_tokens(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                   greedy: jax.Array) -> jax.Array:
    """[B, V] -> [B] per-slot sampling.  ``greedy`` and ``temps`` are
    traced per-slot values — heterogeneous sampling settings share the one
    compiled program (unlike generate's static flags, which are uniform
    across its batch)."""
    greedy_tok = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(greedy, greedy_tok, sampled)


def _logit_signals(logits: jax.Array, attn_impl: str = "jnp"
                   ) -> Tuple[jax.Array, jax.Array]:
    """Per-slot trust signals from the step's logits [B, V]: softmax
    entropy (collapse → ~0, garbage → ~log V) and top-1 logit margin.
    Computed in-step — the [B, V] logits never leave the device.

    On the kernel path (``attn_impl`` "pallas"/"interpret" — the same
    static the paged-attention dispatch bakes in) the two reductions run
    as the fused ``ops.paged_attention.logit_trust_stats`` epilogue: one
    streaming pass over the vocab instead of a log_softmax pass, an
    exp/sum pass and a hierarchical top-k — the margin is bit-exact vs
    this jnp spelling, the entropy f32-epsilon-equal (pinned by
    tests/test_paged_attention.py)."""
    if attn_impl != "jnp":
        from trustworthy_dl_tpu.ops import paged_attention as pattn

        return pattn.logit_trust_stats(
            logits, interpret=(attn_impl == "interpret"))
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=-1)
    top2 = gen._exact_topk(logits, 2)[0]
    return entropy, top2[:, 0] - top2[:, 1]


def _pack_step_outputs(next_tok: jax.Array, ent: jax.Array,
                       margin: jax.Array) -> jax.Array:
    """[3, B] f32 host-facing pack — token ids, entropies, margins in ONE
    array so the scheduler pays a single device→host pull per step
    instead of three (and the copy can start asynchronously while the
    host books the previous tick).  Token ids survive the f32 round-trip
    exactly: vocab sizes (GPT-2: 50257) sit far below 2**24."""
    return jnp.stack([next_tok.astype(jnp.float32), ent, margin])


def _local_prefill(cfg: gpt2.GPT2Config, view: Any, tokens: jax.Array,
                   real_len: jax.Array, quantized: bool):
    """The parity-critical prologue of the whole-prompt prefill program:
    run the stacked blocks over the padded prompt through a FULL-PRECISION
    local cache — the contiguous ``KVCache`` path ``generate()`` itself
    prefills through — so prompt self-attention sees exact K/V and the
    first sampled token is bit-identical to ``generate()``'s whatever the
    pool's tier, and sample logits at ``real_len - 1`` (the prompt's last
    REAL position; padding beyond it is causally invisible and
    overwritten before any decode step can attend to it).
    ``quantized``: quantize once HERE, at the pool write — every scale
    in the written span is fresh, so a reused block cannot leak a stale
    scale (pinned by tests/test_quant.py).
    Returns (logits, k_rows, v_rows, k_scales, v_scales) with scales None
    on the full-precision path."""
    local = gen.init_cache(cfg, 1, tokens.shape[0])
    logits, local = gen._apply_with_cache(
        view, tokens[None, :], local, cfg, last_pos=real_len - 1
    )
    if quantized:
        k_rows, k_s = q8.quantize_kv(local.k)   # int8, f32 [L,1,H,width]
        v_rows, v_s = q8.quantize_kv(local.v)
        return logits, k_rows, v_rows, k_s, v_s
    return logits, local.k, local.v, None, None


def _sample_pack(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                 greedy: jax.Array, attn_impl: str = "jnp") -> jax.Array:
    """The paged programs' sampling tail, a row each: the sampled token and
    the trust signals of ``logits`` [R, V] as one packed f32[3, R] — a
    single host sync per call, not three."""
    token = _sample_tokens(logits, keys, temps, greedy)
    ent, margin = _logit_signals(logits, attn_impl)
    return _pack_step_outputs(token, ent, margin)


def _paged_prefill_impl(cfg: gpt2.GPT2Config, pool_k: jax.Array,
                        pool_v: jax.Array, pool_ks: Any, pool_vs: Any,
                        view: Any, tokens: jax.Array, real_len: jax.Array,
                        block_ids: jax.Array, key: jax.Array,
                        temp: jax.Array, greedy: jax.Array,
                        attn_impl: str = "jnp"):
    """Fresh whole-prompt prefill into PAGED blocks: the
    ``_local_prefill`` prologue — so prompt self-attention and the first
    sampled token match ``generate()`` bit-for-bit (under the int8 tier
    quantization happens once, at the block write) — then the local
    cache is re-laid-out block-wise and scattered into the pool at
    ``block_ids`` (i32[C/BLOCK]; entries past the slot's allocation
    point at the trash block).  Dispatched when the whole prompt fits
    one chunk and no prefix blocks were reused; longer or prefix-sharing
    prompts go through ``_paged_chunk_impl``."""
    c = tokens.shape[0]
    bsz = pool_k.shape[2]
    logits, k_rows, v_rows, k_s, v_s = _local_prefill(
        cfg, view, tokens, real_len, pool_ks is not None
    )
    if pool_ks is None:
        k_rows = k_rows.astype(pool_k.dtype)
        v_rows = v_rows.astype(pool_v.dtype)

    def to_blocks(a):       # [L, 1, H, C(, Dh)] -> pool rows [L, nCB, B, H(·Dh)]
        a = jnp.moveaxis(a[:, 0], 1, 2)                  # [L, C, H(, Dh)]
        return a.reshape(a.shape[0], c // bsz, bsz, -1)

    new_k = pool_k.at[:, block_ids].set(to_blocks(k_rows))
    new_v = pool_v.at[:, block_ids].set(to_blocks(v_rows))
    if pool_ks is not None:
        new_ks = pool_ks.at[:, block_ids].set(to_blocks(k_s))
        new_vs = pool_vs.at[:, block_ids].set(to_blocks(v_s))
    else:
        new_ks, new_vs = pool_ks, pool_vs
    return new_k, new_v, new_ks, new_vs, _sample_pack(
        logits, key[None], temp[None], greedy[None], attn_impl)


def _paged_chunk_impl(cfg: gpt2.GPT2Config, pool_k: jax.Array,
                      pool_v: jax.Array, pool_ks: Any, pool_vs: Any,
                      view: Any, tokens: jax.Array, table: jax.Array,
                      start: jax.Array, last_idx: jax.Array,
                      keys: jax.Array, temps: jax.Array, greedy: jax.Array,
                      attn_impl: str = "jnp", adapter_impl: str = "jnp",
                      adapter_a: Any = None, adapter_b: Any = None,
                      adapter_as: Any = None, adapter_bs: Any = None,
                      apages: Any = None, state: Any = None,
                      slot: Any = None):
    """One CHUNK of paged prefill for each of R mid-prefill slots, in one
    call: row ``r`` feeds ``tokens[r]`` (C prompt positions) from
    ``start[r]`` (block-aligned — a prefix-cache hit starts the suffix at a
    block boundary) through its block table ``table[r]``, attending to
    everything already in the slot's blocks (shared prefix included) and
    scattering its own K/V into the pool; ``last_idx[r]`` locates the
    prompt's last real position within the row's chunk.  ``keys`` u32[R,
    2], ``temps`` and ``greedy`` sample a token a row, meaningful only on a
    prompt's final chunk (the host ignores it otherwise).  A padding row
    (an all-trash table, start 0, ``last_idx`` 0, tokens 0) writes only the
    trash block and is never read.  One compiled program (R =
    ``chunk_call_rows``) serves every chunk of every prompt.  Returns the
    pool and the packed f32[3, R] (token, entropy, margin).

    The trailing adapter args are the paged adapter pool's device sides
    plus the per-row page table ``apages`` i32[R] (serve/adapters.py;
    ZERO_PAGE rows add an exact-zero delta) — None on adapterless
    engines, where they contribute zero pytree leaves and the trace is the
    pre-adapter one (bit-identity).  ``adapter_impl`` (static, like
    ``attn_impl``) routes the per-layer page gather through the in-grid
    ``ops.adapter_delta`` kernel.

    A ``models.decoder.DecoderConfig`` (the description's type decides)
    runs ``decoder.apply_paged`` instead, whose chunk is one slot's (R =
    ``decoder.CHUNK_ROWS``): ``state`` is the recurrent state beside the
    pool (``kv_slots.RecurrentState``, donated like the pool) and ``slot``
    i32[R] the row of it this chunk reads and writes; the chunk's real
    positions are those up to ``last_idx``.  The updated state is returned
    last.  Where its layers keep LATENT rows, ``pool_k`` is the one array
    of them and ``pool_v`` None, in and out (no leaf: none is carried or
    donated)."""
    if isinstance(cfg, decoder.DecoderConfig):
        valid = jnp.arange(tokens.shape[1])[None, :] <= last_idx[:, None]
        logits, new_k, new_v, state = decoder.apply_paged(
            view, tokens, pool_k, pool_v, state, table, start[0], cfg,
            valid, slot=slot[0], last_pos=last_idx[0], attn_impl=attn_impl)
        return new_k, new_v, None, None, _sample_pack(
            logits, keys, temps, greedy, attn_impl), state
    adapter = (None if adapter_a is None
               else (adapter_a, adapter_b, adapter_as, adapter_bs, apages))
    logits, new_k, new_v, new_ks, new_vs = gen._apply_with_cache_paged(
        view, tokens, pool_k, pool_v, pool_ks, pool_vs, table, start, cfg,
        last_pos=last_idx, attn_impl=attn_impl, adapter=adapter,
        adapter_impl=adapter_impl,
    )
    return new_k, new_v, new_ks, new_vs, _sample_pack(
        logits, keys, temps, greedy, attn_impl)


def _paged_decode_impl(cfg: gpt2.GPT2Config, pool_k: jax.Array,
                       pool_v: jax.Array, pool_ks: Any, pool_vs: Any,
                       view: Any, tokens: jax.Array, tables: jax.Array,
                       lengths: jax.Array, keys: jax.Array,
                       temps: jax.Array, greedy: jax.Array,
                       attn_impl: str = "jnp", adapter_impl: str = "jnp",
                       adapter_a: Any = None, adapter_b: Any = None,
                       adapter_as: Any = None, adapter_bs: Any = None,
                       apages: Any = None, state: Any = None,
                       active: Any = None):
    """THE fused paged decode step: one token for every slot, live or
    not.  ``tables`` i32[MAX_SLOTS, NBPS] are the per-slot block maps
    (inactive rows all-trash — their garbage writes land in block 0) and
    ``lengths`` the per-slot write offsets; both are traced VALUES, so
    admission, retirement, block churn and prefix sharing never change
    the program.  The attention core is the same
    ``models/generate._block_with_cache`` batch generate runs, over the
    gathered view — bit-identical streams.

    The trailing adapter args are the paged adapter pool's device sides
    plus the per-slot page table ``apages`` i32[MAX_SLOTS]
    (serve/adapters.py; ZERO_PAGE rows add an exact-zero delta).  All
    traced values: adapter churn, eviction and tenant-mix changes never
    change this program.  None (adapterless engine) contributes zero
    pytree leaves — the compiled program IS the pre-adapter one.

    A ``models.decoder.DecoderConfig`` runs ``decoder.apply_paged``: row
    ``r`` of the call is row ``r`` of the recurrent ``state`` (donated like
    the pool, returned last), and ``active`` bool[MAX_SLOTS] says which
    rows decode this tick: a slot that is free or mid-prefill keeps its
    state, where a K/V row would go to the trash block.  ``pool_v`` is None
    where the pool keeps latent rows, as in ``_paged_chunk_impl``."""
    if isinstance(cfg, decoder.DecoderConfig):
        logits, new_k, new_v, state = decoder.apply_paged(
            view, tokens[:, None], pool_k, pool_v, state, tables, lengths,
            cfg, active[:, None], attn_impl=attn_impl)
        new_ks = new_vs = None
    else:
        adapter = (None if adapter_a is None else
                   (adapter_a, adapter_b, adapter_as, adapter_bs, apages))
        logits, new_k, new_v, new_ks, new_vs = gen._apply_with_cache_paged(
            view, tokens[:, None], pool_k, pool_v, pool_ks, pool_vs,
            tables, lengths, cfg, attn_impl=attn_impl, adapter=adapter,
            adapter_impl=adapter_impl,
        )
    packed = _sample_pack(logits, keys, temps, greedy, attn_impl)
    if state is not None:
        return packed, new_k, new_v, new_ks, new_vs, state
    return packed, new_k, new_v, new_ks, new_vs


def _spec_draft_impl(cfg: gpt2.GPT2Config, pool_k: jax.Array,
                     pool_v: jax.Array, pool_ks: Any, pool_vs: Any,
                     view: Any, tokens: jax.Array, tables: jax.Array,
                     lengths: jax.Array, keys: jax.Array,
                     temps: jax.Array, greedy: jax.Array,
                     attn_impl: str = "jnp"):
    """ONE draft step of the speculative tick: the fused paged decode
    body run with the int8 DRAFT view (quant.draft_decode_view).  Same
    shapes and table/length discipline as ``_paged_decode_impl`` —
    block churn never recompiles it — but it returns the next tokens as
    a separate i32[R] array so the k-step draft chain feeds entirely
    on-device (no host sync until the verify pull), and it skips the
    entropy/margin reductions: draft logits never reach the trust
    monitor, only the verify pass's target logits do."""
    logits, new_k, new_v, new_ks, new_vs = gen._apply_with_cache_paged(
        view, tokens[:, None], pool_k, pool_v, pool_ks, pool_vs,
        tables, lengths, cfg, attn_impl=attn_impl,
    )
    next_tok = _sample_tokens(logits, keys, temps, greedy)
    return next_tok.astype(jnp.int32), new_k, new_v, new_ks, new_vs


def _spec_verify_impl(cfg: gpt2.GPT2Config, pool_k: jax.Array,
                      pool_v: jax.Array, pool_ks: Any, pool_vs: Any,
                      view: Any, tokens: jax.Array, tables: jax.Array,
                      lengths: jax.Array, keys: jax.Array,
                      temps: jax.Array, greedy: jax.Array,
                      attn_impl: str = "jnp", verify_impl: str = "jnp"):
    """THE batched verify: one MODEL-dtype forward over every slot's
    whole draft window ``tokens`` [R, k+1] = [last emitted, d_1 .. d_k],
    attending through the same paged cache at the PRE-draft lengths and
    OVERWRITING the draft positions with target-computed K/V (so every
    accepted position's cache entry is exactly what sequential
    single-token decode would have written — the int8 KV tier included,
    quantization happens at this write).  Per-position sampling uses
    the request's own key stream (``keys`` [R, k+1, 2], position i =
    emission index emitted+i), so the target tokens ARE the spec-off
    stream, greedy and sampled alike; per-position entropy/margin ride
    the packed output for the trust monitor and the near-tie acceptance
    rule.  Returns (packed f32[3, R, k+1], updated pool arrays).

    ``verify_impl`` (static, resolved per-program like ``attn_impl``)
    selects the tail: "jnp" materialises the [R, T, V] logits
    (``all_logits``) and re-reads them for the trust reductions;
    "pallas"/"interpret" runs the fused verify tail — the layer scan
    returns pre-``ln_f`` activations and ``gen.fused_verify_logits``
    streams each vocab tile ONCE for the logits write AND the
    entropy/margin fold (bit-identical logits, pinned epilogue
    algebra), so the all-positions projection never does a second
    HBM round-trip."""
    r, t = tokens.shape
    if verify_impl != "jnp":
        x, new_k, new_v, new_ks, new_vs = gen._apply_with_cache_paged(
            view, tokens, pool_k, pool_v, pool_ks, pool_vs,
            tables, lengths, cfg, hidden=True, attn_impl=attn_impl,
        )
        logits, ent, margin = gen.fused_verify_logits(
            view, x, cfg, interpret=(verify_impl == "interpret"))
        flat = logits.reshape(r * t, -1)
    else:
        logits, new_k, new_v, new_ks, new_vs = gen._apply_with_cache_paged(
            view, tokens, pool_k, pool_v, pool_ks, pool_vs,
            tables, lengths, cfg, all_logits=True, attn_impl=attn_impl,
        )
        flat = logits.reshape(r * t, -1)
        ent, margin = _logit_signals(flat, attn_impl)
    tok = _sample_tokens(flat, keys.reshape(r * t, 2),
                         jnp.repeat(temps, t), jnp.repeat(greedy, t))
    packed = jnp.stack([tok.astype(jnp.float32), ent, margin])
    return packed.reshape(3, r, t), new_k, new_v, new_ks, new_vs


_PROGRAMS: Dict[str, Any] = {}


def _programs() -> Dict[str, Any]:
    if not _PROGRAMS:
        # Donation covers the KV pool AND its scale planes (args 1-4);
        # donating a None (full-precision pool has no scales) donates
        # zero buffers, so one entry serves both tiers.
        donate = (1, 2, 3, 4) if jax.default_backend() == "tpu" else ()
        # The paged programs also take ``attn_impl`` (and, where the
        # program touches adapters or the verify tail, ``adapter_impl``/
        # ``verify_impl``) as STATIC keywords — the scheduler's
        # construction-resolved per-program paths: the jit cache keys on
        # them, so a kernel-on engine and a jnp-fallback engine with
        # identical geometry trace separate programs instead of silently
        # aliasing each other through this process-global table (bench
        # A/B arms and the kernel tests depend on that).
        _PROGRAMS["paged_prefill"] = jax.jit(
            _paged_prefill_impl, static_argnums=(0,),
            static_argnames=("attn_impl",), donate_argnums=donate
        )
        # The recurrent state rides as a keyword (None, and no buffer, for
        # a description without one) and is donated by name.
        donate_state = ("state",) if donate else ()
        _PROGRAMS["paged_chunk"] = jax.jit(
            _paged_chunk_impl, static_argnums=(0,),
            static_argnames=("attn_impl", "adapter_impl"),
            donate_argnums=donate, donate_argnames=donate_state
        )
        _PROGRAMS["paged_decode"] = jax.jit(
            _paged_decode_impl, static_argnums=(0,),
            static_argnames=("attn_impl", "adapter_impl"),
            donate_argnums=donate, donate_argnames=donate_state
        )
        _PROGRAMS["zero_state"] = jax.jit(
            zero_state_rows, donate_argnums=(0,) if donate else ())
        # Speculative tier: draft + verify get their OWN jit wrappers so
        # the fused-decode compile-once pin (decode_cache_size == 1)
        # stays meaningful — a spec engine runs exactly THREE
        # decode-phase programs: spec_draft (int8 view, dispatched k
        # times per tick), spec_verify (one batched model-dtype pass),
        # and paged_decode as the single-token fallback.
        _PROGRAMS["spec_draft"] = jax.jit(
            _spec_draft_impl, static_argnums=(0,),
            static_argnames=("attn_impl",), donate_argnums=donate
        )
        _PROGRAMS["spec_verify"] = jax.jit(
            _spec_verify_impl, static_argnums=(0,),
            static_argnames=("attn_impl", "verify_impl"),
            donate_argnums=donate
        )
    return _PROGRAMS


def request_key_stream(rng: jax.Array, max_new_tokens: int) -> np.ndarray:
    """uint32[max_new, 2] per-token sampling keys, laid out exactly like
    generate's stream: token 0 uses the request key itself, token i>0 uses
    ``split(fold_in(key, 1), max_new-1)[i-1]``."""
    keys = [np.asarray(rng, np.uint32)]
    if max_new_tokens > 1:
        # ``split(key, n)`` is a prefix of ``split(key, m)`` for n <= m
        # (the partitionable threefry this jax defaults to), so the split
        # is made at the next power of two and cut: the same keys from a
        # handful of XLA programs, not one a distinct reply length.
        count = max_new_tokens - 1
        padded = 1 << (count - 1).bit_length()
        if not jax.config.jax_threefry_partitionable:
            padded = count
        rest = jax.random.split(jax.random.fold_in(rng, 1), padded)
        keys.extend(np.asarray(rest, np.uint32)[:count])
    return np.stack(keys)


#: The most prompt positions one call of the chunk program holds.  At 512
#: rows a dense product is past the chip's ridge (v5e: 197 TFLOP/s over
#: 819 GB/s, some 240 rows against a bf16 weight), so a larger call buys no
#: device time, while each further compiled size costs a trace and a
#: lowering before serving (1.1 to 1.4 s each for GPT-2 large on a v5e
#: host).
CHUNK_CALL_POSITIONS = 512


def chunk_call_rows(chunk: int, max_slots: int) -> int:
    """The rows of the one compiled chunk program, where a chunk is
    ``chunk`` positions: as many as ``CHUNK_CALL_POSITIONS`` holds, at
    least one and at most ``max_slots`` (8 at GPT-2 large's 24 slots and
    chunk 64).  A call of fewer mid-prefill slots is padded up to it; more
    take a call per that many."""
    return max(1, min(max_slots, CHUNK_CALL_POSITIONS // chunk))


@dataclasses.dataclass
class SlotTask:
    """Host-side record of one in-flight sequence (scheduler's view)."""

    request_id: int
    prompt: np.ndarray            # i32[P] token ids
    max_new_tokens: int
    temperature: float
    keys: np.ndarray              # uint32[max_new, 2] sampling key stream
    eos_id: Optional[int] = None
    slot: int = -1
    emitted: List[int] = dataclasses.field(default_factory=list)
    next_token: int = -1          # last emitted token = next decode input
    entropies: List[float] = dataclasses.field(default_factory=list)
    margins: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    # Tokens this task gained in the CURRENT tick, in emission order —
    # set only by the speculative tick (which can emit several per
    # tick); None means "one token, read emitted[-1]" (the single-token
    # paths never pay the list).  The engine streams from it and the
    # normal decode path resets it so a fallback tick after a spec tick
    # can never replay stale tokens.
    tick_tokens: Optional[List[int]] = None
    # False = this task's completed prompt blocks are NEVER published to
    # the shared PrefixCache (the fleet's verdict-vote replays are
    # transient audits: they may READ cached prefixes, but must leave
    # the cache exactly as they found it).
    publish_prefix: bool = True
    # Adapter tier (serve/adapters.py): the tenant's adapter id (None =
    # base model) and the pool page admit() claimed for it — ZERO_PAGE
    # until admission, and again after retirement releases the claim.
    adapter: Optional[str] = None
    adapter_page: int = ZERO_PAGE
    # The id of the request's ``serve.request`` span where the engine has
    # a SpanTracker attached (None otherwise): what a phase span that
    # works for this ONE request names as its parent.
    span_root: Optional[int] = None

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    def _record(self, token: int, ent: float, margin: float) -> None:
        self.emitted.append(token)
        self.next_token = token
        self.entropies.append(ent)
        self.margins.append(margin)
        if (len(self.emitted) >= self.max_new_tokens
                or (self.eos_id is not None and token == self.eos_id)):
            self.done = True


def request_args(task: SlotTask) -> Dict[str, Any]:
    """What a phase span that works for ONE request carries: the id every
    span of the request shares and, where a tracker holds the request's
    root span, that span as the one that caused it."""
    if task.span_root is None:
        return {"request_id": task.request_id}
    return {"request_id": task.request_id, "parent_id": task.span_root}


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------


def refuse_unsupported(cfg: Any, *, prefix_cache: bool, spec_k: int,
                       adapter_rank: int, kv_dtype: str, weight_dtype: str,
                       tp_size: int = 1) -> None:
    """Loud construction-time refusal of what a description with recurrent
    state rows (``models.decoder.DecoderConfig``: KDA's or a Mamba-2
    mixer's) cannot be served with yet, one ``ValueError`` a mechanism; each
    stays queued in ROADMAP.md.  A ``GPT2Config`` passes."""
    if not isinstance(cfg, decoder.DecoderConfig):
        return
    if prefix_cache:
        raise ValueError(
            "prefix_cache: a recurrent state row cannot be truncated to a "
            "shared prefix (no state snapshots at block edges yet); build "
            "the engine with prefix_cache=False")
    if spec_k > 0:
        raise ValueError(
            f"spec_k={spec_k}: speculative decoding needs a rollback of "
            "rejected drafts, and a recurrent state row has none yet")
    if adapter_rank > 0:
        raise ValueError(
            f"adapter_rank={adapter_rank}: the paged adapter tier has no "
            "sites in this decoder's layers yet")
    if kv_dtype == "int8" or weight_dtype == "int8":
        raise ValueError(
            f"kv_dtype={kv_dtype!r}, weight_dtype={weight_dtype!r}: the "
            "int8 KV and weight tiers are GPT-2's (quant/int8.py); this "
            "decoder serves in its model dtype")
    if tp_size > 1:
        raise ValueError(
            f"tp_size={tp_size}: this decoder has no tensor-parallel "
            "layout in the sharding registry yet")


#: One prefill call as the tick keeps it between its dispatch and its pull:
#: the packed (token, entropy, margin) of its rows, still on the device, and
#: the (row, slot) of each prompt the call finished.
_PrefillCall = Tuple[jax.Array, List[Tuple[int, int]]]


@dataclasses.dataclass
class _PrefillProgress:
    """Host record of a slot mid-prefill (chunked): ``pos`` is the next
    prompt position to feed (block-aligned; starts past the shared
    prefix), advanced one chunk per engine tick so long prompts never
    head-of-line-block the fused decode step."""

    task: SlotTask
    pos: int
    plen: int
    shared_len: int


class PagedBatchingScheduler:
    """Continuous batching over the paged block pool (kv_slots.PagedKV).

    Engine-facing surface: admit / decode_tick / retire / allocator /
    lengths / kv.  A request claims ``ceil((prompt + max_new) / BLOCK)``
    blocks at admission — occupancy is bounded by tokens in flight, not
    by request count — reusing cached prefix blocks where its prompt
    matches the radix cache (refcounted; prefill then covers only the
    unshared suffix, fed in bounded chunks interleaved with decode
    ticks).  Decode stays ONE compiled program for the scheduler's
    lifetime: block tables are traced gather indices.
    """

    def __init__(self, params: Any, cfg: Any, max_slots: int,
                 max_seq: int,
                 kv_dtype: str = "model", weight_dtype: str = "model",
                 view: Any = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 spec_k: int = 0, draft_view: Any = None,
                 attn_impl: str = "auto",
                 adapters: Any = None):
        q8.validate_dtypes(kv_dtype, weight_dtype)
        validate_paged_geometry(max_seq, block_size, num_blocks,
                                prefill_chunk)
        refuse_unsupported(
            cfg, prefix_cache=prefix_cache, spec_k=spec_k,
            adapter_rank=getattr(adapters, "rank", 0) or 0,
            kv_dtype=kv_dtype, weight_dtype=weight_dtype)
        #: True where the description keeps recurrent state beside the
        #: pool: the programs then carry ``self.state`` too.
        self.recurrent = isinstance(cfg, decoder.DecoderConfig)
        if max_seq > cfg.n_positions:
            # The pool allocates per-block, so check the LOGICAL depth
            # here — a sequence past the position table would silently
            # gather clamped position embeddings, not raise.
            raise ValueError(
                f"max_seq={max_seq} exceeds the model's position table "
                f"(n_positions={cfg.n_positions})"
            )
        self.cfg = cfg
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        if view is not None:
            self.view = view
        elif self.recurrent:
            self.view = decoder.decode_view(params, cfg)
        elif weight_dtype == "int8":
            self.view = q8.quantize_decode_view(params, cfg)
        else:
            self.view = gen._decode_view(params, cfg)
        self.block_size = block_size
        self.nbps = max_seq // block_size          # blocks per slot table
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_slots * self.nbps)
        if prefill_chunk is None and kv_dtype == "int8":
            # Full-prompt prefill by default under int8 KV: a chunked
            # continuation attends to the previous chunk's
            # already-QUANTIZED blocks, while ``_local_prefill`` runs
            # the whole prompt through a full-precision local cache —
            # the first token's parity with ``generate()`` holds only on
            # the one-chunk path.  An explicit prefill_chunk opts back
            # into chunking (near-tie caveat in README §Serving;
            # prefix-cache hits read quantized prefix blocks the same
            # way).
            self.chunk = max_seq
        else:
            self.chunk = resolve_prefill_chunk(max_seq, block_size,
                                               prefill_chunk)
        #: The rows of one chunk call, the one size the chunk program is
        #: compiled for: the mid-prefill slots a call holds, padding
        #: included.  One where the description's chunk is one slot's.
        self.chunk_rows = (decoder.CHUNK_ROWS if self.recurrent
                           else chunk_call_rows(self.chunk, max_slots))
        self.kv = init_paged_pool(cfg, self.num_blocks, block_size,
                                  kv_dtype=q8.resolve_kv_dtype(kv_dtype,
                                                               cfg))
        # The second kind of cache: state rows by slot (None for GPT-2).
        self.state = init_state_pool(cfg, max_slots)
        # Serving-kernel paths, resolved ONCE here (never inside a
        # traced program) and baked into the paged programs as statics:
        # "pallas" (compiled Mosaic kernels, TPU), "interpret" (same
        # kernels through the Pallas interpreter — tests), or "jnp"
        # (the gather/materialise fallbacks, the default wherever the
        # gate is off or the blocks overflow VMEM).  One dict covers the
        # whole tier — decode attention, chunked-prefill attention, the
        # fused verify tail, the in-grid adapter gather — each program
        # downgrading independently (ops/paged_attention.py documents
        # the gate TDDL_PAGED_ATTN and the per-program VMEM rule);
        # ``self.attn_impl`` stays the decode path, the tier's anchor.
        from trustworthy_dl_tpu.ops import paged_attention as pattn

        _, kv_heads, head_dim = kv_geometry(cfg)
        v_lanes = latent_value_lanes(cfg)
        self.attn_impls = pattn.resolve_attn_impls(
            attn_impl, head_dim=head_dim,
            block_size=block_size,
            kv_dtype=q8.resolve_kv_dtype(kv_dtype, cfg),
            n_embd=kv_heads * head_dim,
            adapter_rank=getattr(adapters, "rank", None),
            rows=max(self.chunk, max_slots * (spec_k + 1)),
            **({"satellites": () if v_lanes else ("prefill",),
                "v_lanes": v_lanes} if self.recurrent else {}),
        )
        self.attn_impl = self.attn_impls["decode"]
        if v_lanes and self.attn_impl != "jnp":
            # A chunk of latent attention is a kernel of its own (the
            # expanded form) with its own eligibility.
            from trustworthy_dl_tpu.ops import latent_attention as lattn

            if lattn.supports_latent_prefill(
                    heads=cfg.q_heads, rows=self.chunk,
                    nope=cfg.qk_nope_head_dim, value=cfg.v_head_dim,
                    rank=cfg.kv_lora_rank, lanes=cfg.latent_lanes,
                    block_size=block_size, dtype=self.kv.k.dtype,
                    interpret=(self.attn_impl == "interpret")):
                self.attn_impls["prefill"] = self.attn_impl
            else:
                logger.warning(
                    "latent prefill kernel unsupported at chunk %d, block "
                    "%d; the chunk program falls back to jnp", self.chunk,
                    block_size)
        self.allocator = SlotAllocator(max_slots)  # decode rows
        self.blocks = BlockAllocator(self.num_blocks)
        self.prefix = (PrefixCache(block_size, self.blocks)
                       if prefix_cache else None)
        self.lengths = np.zeros(max_slots, np.int32)
        self.tables: List[List[int]] = [[] for _ in range(max_slots)]
        self.tasks: Dict[int, SlotTask] = {}       # slot -> task
        self._prefill: Dict[int, _PrefillProgress] = {}
        self._q_blocks_by_slot: Dict[int, List[int]] = {}
        # slot -> attribution snapshot taken at admission (block table,
        # prefix reuse, publishers) — the ledger reads it at retirement,
        # AFTER retire() has already cleared the live table.
        self._attrib: Dict[int, Dict[str, Any]] = {}
        # The engine's obs.report.StepTimeReporter: every phase span
        # below is ``span(name, self.timer)``; None (a scheduler driven
        # without an engine) leaves the profiler's annotation alone.
        self.timer: Any = None
        # Optional obs.compilewatch.CompileWatcher (engine) — the fused
        # paged decode dispatch runs under its "serve_decode" guard.
        self.compilewatch: Any = None
        # Optional serve.adapters.AdapterPool (engine-built, HBM-gated):
        # the second paged resource.  admit() claims a page per
        # adapter-carrying request with the SAME backpressure-and-unwind
        # semantics as KV blocks; every decode tick threads the pool
        # sides plus the per-slot page row into the fused programs as
        # traced values.  None = adapterless engine: the device programs
        # are called without adapter args and trace bit-identically to
        # the pre-adapter ones.
        self.adapters: Any = adapters
        # slot -> block ids the slot's request PUBLISHED to the prefix
        # cache (newly cached at its prefill completion) — what a
        # quarantine-retire must purge from the cache.
        self._published: Dict[int, List[int]] = {}
        self.max_seq = max_seq
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # -- speculative decoding (spec_k > 0; README §Serving) --------
        # Per tick: draft spec_k tokens per active slot with the int8
        # ``draft_view`` (k dispatches of ONE compiled draft program,
        # fed on-device), verify the whole window in ONE batched
        # model-dtype forward, accept the longest draft/target-matching
        # prefix, and roll back rejected draft KV by releasing the
        # speculative COW block claims (host refcount decrement).
        self.spec_k = int(spec_k)
        self.draft_view = draft_view
        if self.spec_k > 0 and draft_view is None:
            raise ValueError(
                "spec_k > 0 needs a draft_view (the int8 weight tier; "
                "quant.draft_decode_view — the engine builds it)"
            )
        self._spec_claims: Dict[int, List[int]] = {}
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_near_tie_flips = 0
        self.spec_ticks = 0
        self.spec_fallback_ticks = 0

    # -- admission ---------------------------------------------------------

    @property
    def has_free_slot(self) -> bool:
        return self.allocator.free_count > 0

    @property
    def active_count(self) -> int:
        return len(self.tasks)

    @property
    def occupancy(self) -> float:
        return len(self.tasks) / max(self.allocator.max_slots, 1)

    @property
    def tokens_in_flight(self) -> int:
        """Cached tokens currently backing live sequences (decode-phase
        lengths plus prefill progress, shared prefix included)."""
        total = sum(int(self.lengths[s]) for s in self.tasks
                    if s not in self._prefill)
        total += sum(min(st.pos, st.plen) for st in self._prefill.values())
        return int(total)

    @property
    def blocks_in_use(self) -> int:
        return self.blocks.in_use

    def attribution_info(self, task: SlotTask) -> Dict[str, Any]:
        """The admission-time placement snapshot for the attribution
        ledger: physical block table, which blocks came from the prefix
        cache, and their publisher request ids.  Read it BEFORE
        ``retire`` (which drops the snapshot with the row)."""
        info = self._attrib.get(task.slot)
        if info is None or self.tasks.get(task.slot) is not task:
            return {"layout": "paged", "slot": int(task.slot),
                    "block_ids": [], "prefix_block_ids": [],
                    "prefix_publishers": {},
                    "adapter": task.adapter,
                    "adapter_page": int(task.adapter_page)}
        return {**info, "prefix_publishers": dict(info["prefix_publishers"]),
                "block_ids": list(info["block_ids"]),
                "prefix_block_ids": list(info["prefix_block_ids"])}

    def admit(self, task: SlotTask) -> bool:
        """Claim a decode row and the request's blocks (reusing cached
        prefix blocks), enqueue its chunked prefill.  Pure host work — no
        device program runs until the next ``decode_tick``.  Returns
        False (task untouched) when no row is free or the block pool
        cannot cover the request even after prefix-cache eviction
        (out-of-blocks backpressure)."""
        p = len(task.prompt)
        total = p + task.max_new_tokens
        if total > self.max_seq:
            raise ValueError(
                f"request {task.request_id}: prompt+new = {total} exceeds "
                f"max_seq={self.max_seq}"
            )
        slot = self.allocator.alloc()
        if slot is None:
            return False
        shared: List[int] = []
        if self.prefix is not None:
            self.prefix_lookups += 1
            with span("serve.prefix_lookup", self.timer,
                      request_id=task.request_id) as noted:
                # Cap at (p-1)//block: at least one prompt token always
                # prefills, so the first sampled token has fresh logits.
                shared = self.prefix.lookup(task.prompt.tolist(),
                                            (p - 1) // self.block_size)
                noted.update(hit=bool(shared), shared_blocks=len(shared))
        n_total = -(-total // self.block_size)             # ceil
        n_new = n_total - len(shared)
        fresh = self.blocks.alloc(n_new)
        if fresh is None and self.prefix is not None:
            self.prefix.evict(n_new - self.blocks.free_count)
            fresh = self.blocks.alloc(n_new)
        if fresh is None:
            for b in shared:
                self.blocks.release(b)
            self.allocator.free(slot)
            return False
        if task.adapter is not None and self.adapters is not None:
            # Second paged resource: claim the tenant's adapter page with
            # the SAME backpressure-and-unwind semantics as the KV blocks
            # above — a full pool (every resident page live) or a
            # quarantined adapter refuses admission and the task stays
            # queued, untouched.
            page = self.adapters.acquire(task.adapter)
            if page is None:
                for b in shared + fresh:
                    self.blocks.release(b)
                self.allocator.free(slot)
                return False
            task.adapter_page = page
        if shared:
            self.prefix_hits += 1
            self.prefix_tokens_reused += len(shared) * self.block_size
        self.tables[slot] = shared + fresh
        self.lengths[slot] = 0
        self._zero_state(slot)
        task.slot = slot
        self.tasks[slot] = task
        self._attrib[slot] = {
            "layout": "paged", "slot": slot,
            "block_ids": list(shared + fresh),
            "prefix_block_ids": list(shared),
            "prefix_publishers": (self.prefix.publishers(shared)
                                  if self.prefix is not None else {}),
            "adapter": task.adapter,
            "adapter_page": int(task.adapter_page),
        }
        self._prefill[slot] = _PrefillProgress(
            task=task, pos=len(shared) * self.block_size, plen=p,
            shared_len=len(shared) * self.block_size,
        )
        return True

    def _zero_state(self, slot: int) -> None:
        """A slot's recurrent state starts from zero: at admission, and
        when a quarantined slot goes back into service."""
        if self.state is not None:
            with span("serve.tick.admit.zero_state", self.timer,
                      slot=int(slot)):
                self.state = _programs()["zero_state"](
                    self.state, jnp.asarray(slot, jnp.int32))

    # -- decode ------------------------------------------------------------

    def _table_row(self, slot: int) -> np.ndarray:
        row = np.full(self.nbps, TRASH_BLOCK, np.int32)
        t = self.tables[slot]
        row[:len(t)] = t
        return row

    def _takes_whole_prompt(self, st: _PrefillProgress) -> bool:
        """Whole prompt in one chunk, nothing shared: the full-precision
        local prefill (``generate()``'s numerics, bit-for-bit — the int8
        tier quantizes once at the block write).  An adapter-carrying
        request takes the chunk program instead (its prompt must run
        through the adapter-delta'd layers), and so does every prompt of a
        description with recurrent state (it has no whole-prompt
        program)."""
        return (st.pos == 0 and st.plen <= self.chunk
                and st.task.adapter_page == ZERO_PAGE and not self.recurrent)

    def _dispatch_prefill(self) -> List[_PrefillCall]:
        """Dispatch this tick's prefill, ONE chunk for every mid-prefill
        slot: a whole-prompt call for each prompt that takes one, then the
        chunk program over all the other slots, ``chunk_rows`` of them a
        call (a call a slot where the description's chunk is one slot's).
        Nothing is pulled."""
        whole, rest = [], []
        for slot in sorted(self._prefill):
            (whole if self._takes_whole_prompt(self._prefill[slot])
             else rest).append(slot)
        most = self.chunk_rows
        return ([self._prefill_call([slot], whole=True) for slot in whole]
                + [self._prefill_call(rest[i:i + most])
                   for i in range(0, len(rest), most)])

    def _prefill_call(self, slots: List[int],
                      whole: bool = False) -> _PrefillCall:
        """One program call for ``slots``' next chunks; every slot whose
        chunk does not end its prompt moves on by a chunk."""
        progress = [self._prefill[slot] for slot in slots]
        fed = [min(st.plen - st.pos, self.chunk) for st in progress]
        final = [(row, slot) for row, (slot, st, n)
                 in enumerate(zip(slots, progress, fed))
                 if st.pos + n >= st.plen]
        rows = len(slots)
        padded = 0 if whole else self.chunk_rows - rows
        one = request_args(progress[0].task) if rows == 1 else {}
        with span("serve.prefill_chunk", self.timer, rows=rows,
                  padded=padded, final=len(final), **one):
            if whole:
                with span("serve.prefill_chunk.dispatch", self.timer):
                    packed = self._dispatch_whole_prompt(slots[0],
                                                         progress[0])
            else:
                packed = self._dispatch_chunk(slots, progress, fed,
                                              rows + padded)
        if self.timer is not None:
            self.timer.tally("serve.prefill_chunk", rows=rows, padded=padded)
        done = {slot for _, slot in final}
        for slot, st in zip(slots, progress):
            if slot not in done:
                st.pos += self.chunk
        return packed, final

    def _dispatch_whole_prompt(self, slot: int,
                               st: _PrefillProgress) -> jax.Array:
        """The whole-prompt program for one fresh prompt that fits a chunk;
        its pool becomes the scheduler's.  Returns the packed (token,
        entropy, margin) still on the device."""
        task = st.task
        c = self.chunk
        chunk = np.zeros(c, np.int32)
        chunk[:st.plen] = task.prompt
        ids = np.full(c // self.block_size, TRASH_BLOCK, np.int32)
        n_ids = min(len(self.tables[slot]), len(ids))
        ids[:n_ids] = self.tables[slot][:n_ids]
        kv = self.kv
        new_k, new_v, new_ks, new_vs, packed = _programs()["paged_prefill"](
            self.cfg, kv.k, kv.v, kv.k_scale, kv.v_scale, self.view,
            jnp.asarray(chunk), jnp.asarray(st.plen, jnp.int32),
            jnp.asarray(ids), jnp.asarray(task.keys[0], jnp.uint32),
            jnp.asarray(max(task.temperature, 1e-6), jnp.float32),
            jnp.asarray(task.greedy), attn_impl=self.attn_impl,
        )
        self.kv = PagedKV(k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs)
        return packed

    def _chunk_args(self, slots: List[int], progress: List[_PrefillProgress],
                    fed: List[int], rows: int
                    ) -> Tuple[tuple, Dict[str, Any]]:
        """The chunk program's arguments for ``slots`` (their chunks of
        ``fed`` positions) padded to ``rows``: a padding row has an
        all-trash table, start 0, ``last_idx`` 0 and tokens 0.  With no
        slot at all, every row is padding (what the cost ledger is
        handed)."""
        c = self.chunk
        tokens = np.zeros((rows, c), np.int32)
        tables = np.full((rows, self.nbps), TRASH_BLOCK, np.int32)
        start = np.zeros(rows, np.int32)
        last = np.zeros(rows, np.int32)
        keys = np.zeros((rows, 2), np.uint32)
        temps = np.ones(rows, np.float32)
        greedy = np.ones(rows, bool)
        pages = np.full(rows, ZERO_PAGE, np.int32)
        state_rows = np.zeros(rows, np.int32)
        for row, (slot, st, n) in enumerate(zip(slots, progress, fed)):
            task = st.task
            tokens[row, :n] = task.prompt[st.pos:st.pos + n]
            tables[row] = self._table_row(slot)
            start[row] = st.pos
            last[row] = n - 1
            keys[row] = task.keys[0]
            temps[row] = max(task.temperature, 1e-6)
            greedy[row] = task.greedy
            pages[row] = task.adapter_page
            state_rows[row] = slot
        kv = self.kv
        args = (self.cfg, kv.k, kv.v, kv.k_scale, kv.v_scale, self.view,
                jnp.asarray(tokens), jnp.asarray(tables), jnp.asarray(start),
                jnp.asarray(last), jnp.asarray(keys), jnp.asarray(temps),
                jnp.asarray(greedy))
        kwargs: Dict[str, Any] = dict(
            attn_impl=self.attn_impls["prefill"],
            adapter_impl=self.attn_impls["adapter"])
        if self.adapters is not None:
            a, b, a_s, b_s = self.adapters.device_args()
            kwargs.update(adapter_a=a, adapter_b=b, adapter_as=a_s,
                          adapter_bs=b_s, apages=jnp.asarray(pages))
        if self.recurrent:
            kwargs.update(state=self.state, slot=jnp.asarray(state_rows))
        return args, kwargs

    def _dispatch_chunk(self, slots: List[int],
                        progress: List[_PrefillProgress],
                        fed: List[int], rows: int) -> jax.Array:
        """Upload the rows, padded to ``rows`` (``chunk_rows``), and call
        the chunk program once; the pool (and the state) the call
        returns become the scheduler's.  Returns the packed (token,
        entropy, margin) of every row, still on the device."""
        args, kwargs = self._chunk_args(slots, progress, fed, rows)
        with span("serve.prefill_chunk.dispatch", self.timer), \
                guarded(self.compilewatch, "serve_chunk"):
            new_k, new_v, new_ks, new_vs, packed, *state = _programs()[
                "paged_chunk"](*args, **kwargs)
        if self.recurrent:
            (self.state,) = state
        self.kv = PagedKV(k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs)
        return packed

    def _record_prefill(self, calls: List[_PrefillCall]
                        ) -> List[SlotTask]:
        """Pull each prefill call that finished a prompt, once, and record
        the first tokens in slot order: the prompt's length becomes the
        slot's and its full blocks are published to the prefix cache.
        Returns the tasks that received their first token."""
        firsts: Dict[int, np.ndarray] = {}
        for packed, final in calls:
            if not final:
                continue
            with span("serve.prefill_chunk.pull", self.timer):
                # tddl-lint: disable=host-sync — a prefill call's one pull
                host = np.asarray(packed)
            for row, slot in final:
                firsts[slot] = host[:, row]
        ticked: List[SlotTask] = []
        for slot in sorted(firsts):
            token, ent, margin = firsts[slot]
            st = self._prefill.pop(slot)
            task = st.task
            task._record(int(token), float(ent), float(margin))
            self.lengths[slot] = st.plen
            if self.prefix is not None and task.publish_prefix:
                # The prompt's FULL blocks are now authoritative in the
                # pool — publish them so later same-prefix requests skip
                # their prefill.  (Generated tokens are never cached; a
                # publish_prefix=False audit replay caches nothing at
                # all.)  The newly cached ids are remembered: if THIS
                # request is later flagged, its publications must leave
                # the cache with it.
                self._published[slot] = self.prefix.insert(
                    task.prompt.tolist(),
                    self.tables[slot][:st.plen // self.block_size],
                    publisher=task.request_id,
                )
            ticked.append(task)
        return ticked

    def decode_tick(self) -> List[SlotTask]:
        """One engine tick: dispatch ONE chunk of every mid-prefill slot
        (``_dispatch_prefill``), then build and dispatch the fused decode
        step for every decode-phase slot, and only then pull: the prompts
        that finished this tick record their first tokens, then the
        decode step's rows record theirs.  A slot finishing its prompt is
        not in this tick's decode, so the decode call needs nothing from
        the chunk's pull and the device runs the two back to back.  A
        speculative tick pulls the chunks first (its draft chain pulls
        before its verify).  Returns the tasks that received a token this
        tick, the first tokens first."""
        calls = self._dispatch_prefill()
        active = {s: t for s, t in self.tasks.items()
                  if s not in self._prefill and not t.done}
        if not active:
            return self._record_prefill(calls)
        if self.spec_k > 0 and any(
                t.max_new_tokens - len(t.emitted) > 1
                for t in active.values()):
            ticked = self._record_prefill(calls)
            ticked.extend(self._spec_tick(active))
            return ticked
        if self.spec_k > 0:
            # Every live slot has exactly one token left: drafting would
            # be pure waste — dispatch the single-token FALLBACK program
            # (today's fused decode, the third compiled decode-phase
            # program of a spec engine).
            self.spec_fallback_ticks += 1
        packed = self._dispatch_decode(active)
        ticked = self._record_prefill(calls)
        ticked.extend(self._record_decode(active, packed))
        return ticked

    def _dispatch_decode(self, active: Dict[int, SlotTask]) -> jax.Array:
        """Build and dispatch the fused decode step for the ``active``
        slots; returns its packed outputs, still on the device."""
        ms = self.allocator.max_slots
        with span("serve.decode_tick.build", self.timer):
            tokens = np.zeros(ms, np.int32)
            keys = np.zeros((ms, 2), np.uint32)
            temps = np.ones(ms, np.float32)
            greedy = np.ones(ms, bool)
            tables = np.full((ms, self.nbps), TRASH_BLOCK, np.int32)
            for slot, task in active.items():
                tokens[slot] = task.next_token
                keys[slot] = task.keys[len(task.emitted)]
                temps[slot] = max(task.temperature, 1e-6)
                greedy[slot] = task.greedy
                tables[slot] = self._table_row(slot)
            kv = self.kv
            extra: Dict[str, Any] = {}
            if self.adapters is not None:
                # The adapter pool rides every tick: pool sides as traced
                # arrays, per-slot pages as ONE traced i32[MAX_SLOTS] row
                # (inactive and adapterless slots at ZERO_PAGE — an exact
                # zero delta).  Residency churn changes buffer VALUES
                # only; the program under the compile-once guard never
                # changes.
                a, b, a_s, b_s = self.adapters.device_args()
                row = adapter_page_row(
                    {s: t.adapter_page for s, t in active.items()}, ms)
                extra = dict(adapter_a=a, adapter_b=b, adapter_as=a_s,
                             adapter_bs=b_s, apages=jnp.asarray(row))
            if self.recurrent:
                live = np.zeros(ms, bool)
                live[list(active)] = True
                extra = dict(state=self.state, active=jnp.asarray(live))
        with span("serve.decode_tick.dispatch", self.timer), \
                guarded(self.compilewatch, "serve_decode"):
            # The lengths go up as a copy: the prompts this tick finishes
            # set theirs before this call's pull.
            packed, new_k, new_v, new_ks, new_vs, *state = \
                _programs()["paged_decode"](
                    self.cfg, kv.k, kv.v, kv.k_scale, kv.v_scale,
                    self.view,
                    jnp.asarray(tokens), jnp.asarray(tables),
                    jnp.asarray(self.lengths.copy()),
                    jnp.asarray(keys), jnp.asarray(temps),
                    jnp.asarray(greedy),
                    attn_impl=self.attn_impl,
                    adapter_impl=self.attn_impls["adapter"],
                    **extra,
                )
        self.kv = PagedKV(k=new_k, v=new_v, k_scale=new_ks, v_scale=new_vs)
        if self.recurrent:
            (self.state,) = state
        return packed

    def _record_decode(self, active: Dict[int, SlotTask],
                       packed: jax.Array) -> List[SlotTask]:
        """Pull the decode step's rows once and record a token for every
        ``active`` slot."""
        with span("serve.decode_tick.pull", self.timer):
            # tddl-lint: disable=host-sync — the tick's one intended pull
            host = np.asarray(packed)
        ticked: List[SlotTask] = []
        with span("serve.decode_tick.record", self.timer):
            next_tok, ent, margin = host[0], host[1], host[2]
            for slot in active:
                self.lengths[slot] += 1
            for slot, task in active.items():
                task.tick_tokens = None   # single-token tick: emitted[-1]
                task._record(int(next_tok[slot]), float(ent[slot]),
                             float(margin[slot]))
                ticked.append(task)
        return ticked

    def _spec_tick(self, active: Dict[int, SlotTask]) -> List[SlotTask]:
        """One speculative tick for every decode-phase slot: claim the
        draft window's blocks, draft ``spec_k`` tokens with the int8
        view (k dispatches of the compiled draft program, chained
        on-device), verify the whole window in ONE batched model-dtype
        forward (which also overwrites the draft KV with target-exact
        values), accept per slot the longest prefix where the draft
        matched the target (greedy near-ties under the parity-probe
        margin tolerated as draft-token flips), then release the claims
        — rejection is a refcount decrement plus NOT advancing the
        host-side length past the accepted prefix."""
        k = self.spec_k
        ms = self.allocator.max_slots
        tokens0 = np.zeros(ms, np.int32)
        temps = np.ones(ms, np.float32)
        greedy = np.ones(ms, bool)
        tables = np.full((ms, self.nbps), TRASH_BLOCK, np.int32)
        keys = np.zeros((ms, k + 1, 2), np.uint32)
        # Per-slot PROPOSABLE draft count: a slot with r tokens of
        # budget left can emit at most r this tick, of which at most
        # r-1 can come from drafts (the verify bonus is always one of
        # the emissions) — counting the full k for it would make
        # accepted_rate conflate budget truncation with real draft/
        # target disagreement, and the sentinel would page a workload
        # shift toward short requests as a draft-quality regression.
        proposable: Dict[int, int] = {}
        for slot, task in active.items():
            tokens0[slot] = task.next_token
            temps[slot] = max(task.temperature, 1e-6)
            greedy[slot] = task.greedy
            tables[slot] = self._table_row(slot)
            base = len(task.emitted)
            proposable[slot] = min(k, task.max_new_tokens - base - 1)
            for i in range(k + 1):
                # Emission index base+i — the SAME key spec-off decode
                # would consume there (over-draft past the request's
                # budget clamps; those emissions are discarded anyway).
                keys[slot, i] = task.keys[
                    min(base + i, task.max_new_tokens - 1)]
            claimed = blocks_for_span(
                self.tables[slot], self.block_size,
                int(self.lengths[slot]), int(self.lengths[slot]) + k + 1,
            )
            self.blocks.claim_speculative(claimed)
            self._spec_claims[slot] = claimed
        lengths0 = self.lengths.copy()
        prog = _programs()
        kv = self.kv
        pool = (kv.k, kv.v, kv.k_scale, kv.v_scale)
        tables_dev = jnp.asarray(tables)
        temps_dev = jnp.asarray(temps)
        greedy_dev = jnp.asarray(greedy)
        with span("serve.spec_draft", self.timer, slots=len(active)):
            cur = jnp.asarray(tokens0)
            draft_dev = []
            for j in range(k):
                with span("serve.spec_draft.dispatch", self.timer), \
                        guarded(self.compilewatch, "serve_spec_draft"):
                    cur, pk, pv, pks, pvs = prog["spec_draft"](
                        self.cfg, *pool, self.draft_view, cur, tables_dev,
                        jnp.asarray(lengths0 + j), jnp.asarray(keys[:, j]),
                        temps_dev, greedy_dev, attn_impl=self.attn_impl,
                    )
                pool = (pk, pv, pks, pvs)
                draft_dev.append(cur)
            # ONE host sync point for the whole draft chain: the k draft
            # token rows land together and become the verify inputs.
            with span("serve.spec_draft.pull", self.timer):
                # tddl-lint: disable=host-sync — the chain's one sync
                drafts = np.stack([np.asarray(d) for d in draft_dev], axis=1)
        with span("serve.spec_verify", self.timer,
                  slots=len(active)) as noted:
            tokens_v = np.concatenate([tokens0[:, None], drafts], axis=1)
            with span("serve.spec_verify.dispatch", self.timer), \
                    guarded(self.compilewatch, "serve_spec_verify"):
                packed, pk, pv, pks, pvs = prog["spec_verify"](
                    self.cfg, *pool, self.view, jnp.asarray(tokens_v),
                    tables_dev, jnp.asarray(lengths0), jnp.asarray(keys),
                    temps_dev, greedy_dev, attn_impl=self.attn_impl,
                    verify_impl=self.attn_impls["verify"],
                )
            self.kv = PagedKV(k=pk, v=pv, k_scale=pks, v_scale=pvs)
            with span("serve.spec_verify.pull", self.timer):
                # tddl-lint: disable=host-sync — all windows, one pull
                host = np.asarray(packed)                 # [3, ms, k+1]
            self.spec_ticks += 1
            ticked: List[SlotTask] = []
            tick_proposed = tick_accepted = 0
            for slot, task in active.items():
                tgt = host[0, slot]
                ent = host[1, slot]
                margin = host[2, slot]
                d = drafts[slot]
                # Acceptance walk: position i emits the TARGET token v_{i+1}
                # (bit-identical to spec-off by construction — same logits,
                # same key); the walk continues past i only when the draft
                # guessed the emitted token, so every later target token was
                # conditioned on the true stream.  A greedy mismatch under a
                # near-tie top-1 margin (< the int8 parity probe's
                # tolerance) emits the DRAFT token instead and continues —
                # the same numerics-equivalence class the kv parity probe
                # accepts, counted in ``spec_near_tie_flips``.
                window: List[Tuple[int, float, float]] = []
                for i in range(k + 1):
                    tok = int(tgt[i])
                    cont = False
                    if i < k:
                        if int(d[i]) == tok:
                            cont = True
                        elif task.greedy and \
                                float(margin[i]) < q8.PARITY_MARGIN_TOL:
                            tok = int(d[i])
                            self.spec_near_tie_flips += 1
                            cont = True
                    window.append((tok, float(ent[i]), float(margin[i])))
                    if not cont:
                        break
                task.tick_tokens = []
                n_fed = 0
                for tok, e_sig, m_sig in window:
                    task._record(tok, e_sig, m_sig)
                    task.tick_tokens.append(tok)
                    n_fed += 1
                    if task.done:
                        break          # eos / budget: later wins discarded
                # Commit exactly the accepted inputs' KV: positions
                # [len, len + n_fed) hold target-exact K/V for the emitted
                # stream; everything beyond is rejected-draft garbage,
                # causally invisible and rewritten before it could be seen.
                self.lengths[slot] += n_fed
                tick_proposed += proposable[slot]
                tick_accepted += max(n_fed - 1, 0)
                self.blocks.release_speculative(
                    self._spec_claims.pop(slot, []))
                ticked.append(task)
            self.spec_proposed += tick_proposed
            self.spec_accepted += tick_accepted
            noted.update(proposed=tick_proposed, accepted=tick_accepted)
        return ticked

    # -- retirement --------------------------------------------------------

    def retire(self, task: SlotTask, quarantine: bool = False) -> None:
        """Release the task's decode row and drop its block references.
        Blocks still shared (prefix cache, other requests) stay resident;
        under ``quarantine`` the task's UNSHARED blocks leave the pool
        with the row, and any blocks the task itself PUBLISHED to the
        prefix cache are purged from it first (the trust mirror: a
        flagged request's private KV — generated tail AND the prompt
        blocks it prefilled — is suspect; a prefix a different clean
        request published and others share is not)."""
        slot = task.slot
        if slot < 0 or self.tasks.get(slot) is not task:
            return
        del self.tasks[slot]
        self._prefill.pop(slot, None)
        self._attrib.pop(slot, None)
        if task.adapter is not None and self.adapters is not None:
            # Drop the request's residency claim on its adapter page.
            # The pool's OWN ref keeps the page resident (warm for the
            # tenant's next request) unless the adapter was quarantined
            # mid-flight — then this last release impounds it.  Replica
            # ``quarantine`` does NOT quarantine the adapter: adapter
            # trust is a fleet-level verdict (serve/fleet.py), scoped to
            # the adapter across replicas, not to this replica's pool.
            self.adapters.release(task.adapter)
            task.adapter_page = ZERO_PAGE
        # Outstanding speculative claims MUST unwind before the table
        # release: a leftover claim would make the quarantine release
        # below see the block as "shared" and FREE it on the claim's
        # decrement instead of impounding it — un-verified draft KV from
        # a flagged request would re-enter the pool.  (A normal tick
        # releases its claims inline; this is the abort path — e.g.
        # quarantine-at-retire racing a failed tick.)
        self.blocks.release_speculative(self._spec_claims.pop(slot, []))
        published = self._published.pop(slot, [])
        if quarantine and self.prefix is not None and published:
            # The flagged request's own PUBLISHED prompt blocks leave
            # the cache FIRST — otherwise the cache's reference keeps
            # them "shared" in the release loop below and a later
            # same-prefix request would decode straight off suspect KV
            # without any prefill.  (A prefix published by a DIFFERENT,
            # clean request stays cached: this request merely read it.)
            self.prefix.purge(set(published))
        q_blocks: List[int] = []
        for b in self.tables[slot]:
            if self.blocks.release(b, quarantine=quarantine) \
                    == "quarantined":
                q_blocks.append(b)
        self.tables[slot] = []
        if quarantine:
            self._q_blocks_by_slot[slot] = q_blocks
            self.allocator.quarantine(slot)
            logger.warning(
                "slot %d quarantined after request %d was flagged "
                "anomalous (%d private block(s) impounded, %d slots "
                "remain in service)",
                slot, task.request_id, len(q_blocks),
                self.allocator.capacity,
            )
        else:
            self.allocator.free(slot)

    def release_quarantine(self, slot: int) -> None:
        """Operator action: return a quarantined slot AND the blocks
        impounded with it to service."""
        self.allocator.release(slot)
        for b in self._q_blocks_by_slot.pop(slot, []):
            self.blocks.unquarantine(b)
        self._zero_state(slot)

    # -- live migration (serve/migrate.py) ---------------------------------

    def export_migration(self, task: SlotTask) -> Optional[Dict[str, Any]]:
        """Source-side snapshot of a DECODE-PHASE task for a live
        hand-off: the physical block table, the committed length and the
        admission-time placement (the destination's provenance record).
        Refuses (None, nothing touched) mid-prefill — chunk progress is
        not block state, the destination would have to re-prefill anyway
        — and unknown/stale tasks.  Outstanding speculative claims
        unwind FIRST (abort semantics, same ordering rule as retire):
        a migration never travels with un-verified draft claims, and
        the accepted ``lengths`` already exclude rejected draft KV."""
        if self.recurrent:
            raise ValueError(
                "live migration snapshots blocks, and this description "
                "keeps recurrent state beside them: no state snapshot "
                "exists yet (serve/migrate.can_migrate says so first)")
        slot = task.slot
        if slot < 0 or self.tasks.get(slot) is not task:
            return None
        if slot in self._prefill or not task.emitted:
            return None
        self.blocks.release_speculative(self._spec_claims.pop(slot, []))
        return {
            "task": task,
            "length": int(self.lengths[slot]),
            "block_ids": list(self.tables[slot]),
            "placement": self.attribution_info(task),
        }

    def claim_migration(self, n_blocks: int, adapter: Optional[str]
                        ) -> Optional[Dict[str, Any]]:
        """Destination-side CLAIM phase: reserve a decode row,
        ``n_blocks`` fresh physical blocks (prefix-evict retry — the
        same out-of-blocks backpressure as ``admit``) and, for an
        adapter-carrying request, the tenant's adapter page.  Returns
        None with NOTHING claimed on any shortage — a refusal here must
        leave both replicas exactly as they were.  No prefill and no
        prefix sharing: the blocks' contents arrive by device copy."""
        slot = self.allocator.alloc()
        if slot is None:
            return None
        fresh = self.blocks.alloc(n_blocks)
        if fresh is None and self.prefix is not None:
            self.prefix.evict(n_blocks - self.blocks.free_count)
            fresh = self.blocks.alloc(n_blocks)
        if fresh is None:
            self.allocator.free(slot)
            return None
        page = ZERO_PAGE
        if adapter is not None:
            page = (self.adapters.acquire(adapter)
                    if self.adapters is not None else None)
            if page is None:
                # Adapterless destination, full pool, or quarantined
                # adapter: full unwind, refusal leaves the source alone.
                for b in fresh:
                    self.blocks.release(b)
                self.allocator.free(slot)
                return None
        return {"slot": slot, "block_ids": list(fresh),
                "adapter": adapter, "adapter_page": int(page)}

    def abort_migration(self, claim: Dict[str, Any]) -> None:
        """Unwind a CLAIM that never committed (copy failed upstream or
        the orchestrator gave up): releases the blocks, the row and the
        adapter page — the exact inverse of ``claim_migration``."""
        if claim.get("adapter") is not None and self.adapters is not None:
            self.adapters.release(claim["adapter"])
        for b in claim["block_ids"]:
            self.blocks.release(b)
        self.allocator.free(claim["slot"])

    def commit_migration(self, task: SlotTask, claim: Dict[str, Any],
                         length: int,
                         migrated_from: Optional[Dict[str, Any]] = None
                         ) -> None:
        """COMMIT phase: register the migrated task on the claimed row.
        Pure host bookkeeping — the physical block copy already happened
        (serve/migrate.py) — so commit cannot fail.  The attribution
        snapshot names only the DESTINATION's fresh blocks as owned;
        ``migrated_from`` carries the source journal key + source block
        ids so ``verify_attribution`` reconciles the hand-off across
        both allocators' journals."""
        slot = claim["slot"]
        task.slot = slot
        task.adapter_page = int(claim["adapter_page"])
        task.tick_tokens = None
        self.tables[slot] = list(claim["block_ids"])
        self.lengths[slot] = int(length)
        self.tasks[slot] = task
        info: Dict[str, Any] = {
            "layout": "paged", "slot": slot,
            "block_ids": list(claim["block_ids"]),
            "prefix_block_ids": [], "prefix_publishers": {},
            "adapter": task.adapter,
            "adapter_page": int(claim["adapter_page"]),
        }
        if migrated_from is not None:
            info["migrated_from"] = dict(migrated_from)
        self._attrib[slot] = info

    def expert_counters(self) -> Optional[Dict[str, Any]]:
        """The expert layers' device counters, pulled now (one sync: ask
        when a summary is wanted, not every tick): the (token, expert)
        pairs each HELD expert took, summed over the layers, and the
        tokens fed through an expert layer, a layer each.  None for a
        description without routed experts."""
        if self.state is None or self.state.expert_pairs is None:
            return None
        # tddl-lint: disable=host-sync — pulled only for a summary
        pairs = np.asarray(self.state.expert_pairs).sum(axis=0)
        return {"held_expert_pairs": [int(n) for n in pairs],
                "tokens_fed": int(self.state.expert_tokens)}

    def decode_cache_size(self) -> int:
        """Number of compiled paged-decode programs (the compile-once
        pin: block-table churn must keep this at 1)."""
        prog = _PROGRAMS.get("paged_decode")
        return prog._cache_size() if prog is not None else 0

    def spec_cache_sizes(self) -> Dict[str, int]:
        """Compiled-program counts for the three decode-phase programs
        of a speculative engine (the extended compile-once pin: draft,
        verify and the single-token fallback each compile exactly once
        for the engine's lifetime — accept/reject churn, block churn
        and draft-window block crossings never recompile)."""
        out: Dict[str, int] = {}
        for name in ("spec_draft", "spec_verify", "paged_decode"):
            prog = _PROGRAMS.get(name)
            out[name] = prog._cache_size() if prog is not None else 0
        return out

    @property
    def accepted_rate(self) -> float:
        """Fraction of PROPOSABLE drafted tokens that became emitted
        stream tokens — the draft-quality headline the bench A/B and
        the perf sentinel fingerprint track.  The denominator is
        budget-clamped per slot (min(k, remaining-1)), so the rate
        measures int8-draft-vs-target agreement, not how short the
        workload's requests were; eos truncation still counts against
        it (an eos is a property of the stream both arms share)."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    def analyze_costs(self, ledger: Any,
                      memory: Optional[bool] = None) -> None:
        """Stamp the paged serve programs into an obs.hbm.CostLedger
        (lowering-only by default — no extra backend compile)."""
        kv = self.kv
        ms = self.allocator.max_slots
        c = self.chunk
        bsz = self.block_size
        prog = _programs()
        pool = (kv.k, kv.v, kv.k_scale, kv.v_scale)
        # A description with recurrent state has no whole-prompt program,
        # and its two programs take the state beside the pool.
        decode_state: Dict[str, Any] = {}
        if self.recurrent:
            decode_state = dict(state=self.state,
                                active=jnp.ones(ms, bool))
        if not self.recurrent:
            ledger.analyze(
                "serve.paged_prefill", prog["paged_prefill"], self.cfg,
                *pool, self.view, jnp.zeros(c, jnp.int32),
                jnp.asarray(1, jnp.int32),
                jnp.zeros(c // bsz, jnp.int32), jnp.zeros(2, jnp.uint32),
                jnp.asarray(1.0, jnp.float32), jnp.asarray(True),
                memory=memory, attn_impl=self.attn_impl,
            )
        args, kwargs = self._chunk_args([], [], [], self.chunk_rows)
        ledger.analyze("serve.paged_chunk", prog["paged_chunk"], *args,
                       memory=memory, **kwargs)
        ledger.analyze(
            "serve.paged_decode", prog["paged_decode"], self.cfg,
            *pool, self.view, jnp.zeros(ms, jnp.int32),
            jnp.zeros((ms, self.nbps), jnp.int32),
            jnp.asarray(self.lengths), jnp.zeros((ms, 2), jnp.uint32),
            jnp.ones(ms, jnp.float32), jnp.ones(ms, bool),
            memory=memory, attn_impl=self.attn_impl, **decode_state,
        )
