"""GPT-2 family, TPU-first.

The reference only ever touches GPT-2 through ``model.transformer.h`` — a
python list of blocks it slices into contiguous per-node chunks
(distributed_trainer.py:124-135).  Here the blocks are a *stacked* pytree
(leading axis = layer), which is the TPU-native analogue: a pipeline stage is
a leading-axis slice, `lax.scan` applies the stack with one compiled block
body, and sharding the leading axis over the 'stage' mesh axis IS the
reference's layer partitioning.

Sizes follow the public GPT-2 family: small 12L/768/12H, medium 24L/1024/16H,
large 36L/1280/20H, xl 48L/1600/25H (vocab 50257, context 1024).

The attention implementation is pluggable (``attn_impl``): "full" (fused
softmax attention), "ring" / "ulysses" (sequence-parallel variants from
trustworthy_dl_tpu.parallel.sequence) — long-context support is first-class,
not bolted on.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import layers as L

Params = Dict[str, Any]

GPT2_SIZES = {
    "gpt2": dict(n_layer=12, n_embd=768, n_head=12),
    "gpt2-small": dict(n_layer=12, n_embd=768, n_head=12),
    "gpt2-medium": dict(n_layer=24, n_embd=1024, n_head=16),
    "gpt2-large": dict(n_layer=36, n_embd=1280, n_head=20),
    "gpt2-xl": dict(n_layer=48, n_embd=1600, n_head=25),
}


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_layer: int = 12
    n_embd: int = 768
    n_head: int = 12
    dtype: Any = jnp.bfloat16
    # full | flash | ring | ulysses | auto.  "auto" (the default) picks
    # the Pallas flash kernel for T >= AUTO_FLASH_MIN_T and the fused XLA
    # path below it (where the threshold belongs is not measured —
    # PERF.md); the branch resolves at trace time from the static shape,
    # so short-T programs are bit-identical to attn_impl="full".
    attn_impl: str = "auto"
    remat: bool = False
    # Remat granularity when ``remat`` is on: "block" rematerialises the
    # whole transformer block (max memory saving, max recompute);
    # "attention" saves every intermediate EXCEPT the O(T²) attention
    # scores/probs — the dominant residuals — so only the attention core
    # recomputes in the backward pass (less recompute, slightly more
    # memory).
    remat_policy: str = "block"
    # Vocab-chunked fused lm-head+CE (ops/fused_ce.py): the loss never
    # materialises the [B, T, V] logits.  0 forces the materialised-logits
    # path, an int > 0 forces chunking with that width, and "auto" (the
    # default, mirroring attn_impl) resolves per shape at trace time:
    # chunked only where the materialised logits would pressure HBM
    # (auto_picks_chunked_ce) — below that the materialised path, which
    # does not recompute the head in the backward pass.
    lm_head_chunk: Any = "auto"

    @staticmethod
    def from_name(name: str, **overrides: Any) -> "GPT2Config":
        key = name.lower()
        if key not in GPT2_SIZES:
            raise ValueError(f"unknown GPT-2 size {name!r}")
        kwargs = dict(GPT2_SIZES[key])
        kwargs.update(overrides)
        return GPT2Config(**kwargs)


# --------------------------------------------------------------------------
# Attention registry — parallel/sequence.py registers "ring" and "ulysses".
# --------------------------------------------------------------------------

AttnFn = Callable[[jax.Array, jax.Array, jax.Array, bool], jax.Array]
_ATTN_REGISTRY: Dict[str, AttnFn] = {}


def register_attention(name: str, fn: AttnFn) -> None:
    _ATTN_REGISTRY[name] = fn


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True) -> jax.Array:
    """[B, H, T, D] softmax attention.  XLA fuses the softmax chain; the
    matmuls land on the MXU in bf16.  The O(T²) intermediates are tagged
    with checkpoint_name so the "attention" remat policy can drop exactly
    them (see apply_blocks)."""
    from jax.ad_checkpoint import checkpoint_name

    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        t_q, t_k = q.shape[-2], k.shape[-2]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    scores = checkpoint_name(scores, "attn_scores")
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    probs = checkpoint_name(probs, "attn_probs")
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


register_attention("full", full_attention)


AUTO_FLASH_MIN_T = 1024


def auto_picks_flash(t: int, d: int) -> bool:
    """THE attn_impl='auto' dispatch predicate — shared by the attention
    registry AND the remat-policy classifier (apply_blocks), so 'does auto
    resolve to the flash kernel here?' has exactly one answer.  Flash is
    picked for long sequences, only for kernel-eligible shapes, and only
    where a compiled Mosaic kernel can be dispatched: the TPU backend
    (off-TPU the kernel would run in interpret mode — orders of
    magnitude slower, correctness-test territory) and a program GSPMD
    does not partition (``ops.mosaic_dispatchable``)."""
    from trustworthy_dl_tpu.ops import mosaic_dispatchable
    from trustworthy_dl_tpu.ops.flash_attention import supports_flash

    return (t >= AUTO_FLASH_MIN_T and supports_flash(t, d)
            and mosaic_dispatchable())


def _auto_attention(q, k, v, causal=True):
    """Per-shape dispatch (see auto_picks_flash): the Pallas flash kernel
    where its advantage is real, the fused XLA path everywhere else —
    shapes are static under jit, so the branch resolves at trace time."""
    from trustworthy_dl_tpu.ops.flash_attention import flash_attention

    if auto_picks_flash(q.shape[-2], q.shape[-1]):
        return flash_attention(q, k, v, causal)
    return _ATTN_REGISTRY["full"](q, k, v, causal)


# lm_head_chunk="auto": chunk width used when the predicate picks the
# fused path, and the per-node materialised-logits budget above which
# it engages.  By arithmetic, 4 nodes × b16 × T512 × V50257 bf16
# logits are ~0.82 GiB/node and b32/node ~1.65 GiB/node; 1 GiB/node
# splits the two.  Where the crossover really lies on the chip is not
# measured (PERF.md).
AUTO_CE_CHUNK = 8192
AUTO_CE_MAX_LOGITS_BYTES = 1 << 30


def auto_picks_chunked_ce(num_tokens: int, vocab: int,
                          itemsize: int = 2) -> bool:
    """THE lm_head_chunk='auto' dispatch predicate — one answer to 'does
    auto use the vocab-chunked fused CE here?', shared by the train loss,
    both eval steps, and the tests.  Picks chunked exactly when this
    node's materialised [tokens, vocab] logits would exceed
    AUTO_CE_MAX_LOGITS_BYTES."""
    return num_tokens * vocab * itemsize > AUTO_CE_MAX_LOGITS_BYTES


def resolve_lm_head_chunk(cfg: "GPT2Config", num_tokens: int) -> int:
    """Trace-time resolution of ``cfg.lm_head_chunk`` for a loss over
    ``num_tokens`` target positions: explicit settings pass through
    ("auto" is the only non-int value), auto applies the predicate.
    Shapes are static under jit, so the branch costs nothing."""
    chunk = cfg.lm_head_chunk
    if chunk == "auto":
        itemsize = jnp.dtype(cfg.dtype).itemsize
        if auto_picks_chunked_ce(num_tokens, cfg.vocab_size, itemsize):
            return AUTO_CE_CHUNK
        return 0
    return int(chunk or 0)


def get_attention(name: str) -> AttnFn:
    if name not in _ATTN_REGISTRY:
        # Late registration: sequence-parallel impls live in parallel/,
        # the Pallas blockwise kernel in ops/.
        if name in ("ring", "ulysses"):
            import trustworthy_dl_tpu.parallel.sequence  # noqa: F401
        elif name == "flash":
            from trustworthy_dl_tpu.ops.flash_attention import flash_attention
            register_attention("flash", flash_attention)
        elif name == "auto":
            register_attention("auto", _auto_attention)
        if name not in _ATTN_REGISTRY:
            raise ValueError(f"unknown attention impl {name!r}")
    return _ATTN_REGISTRY[name]


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------


def init_block_params(key: jax.Array, cfg: GPT2Config) -> Params:
    ks = jax.random.split(key, 4)
    d = cfg.n_embd
    scale = 0.02
    return {
        "ln_1": L.layernorm_init(d),
        "attn": {
            "qkv": {
                "w": L.uniform_scaling_init(ks[0], (d, 3 * d), scale),
                "b": jnp.zeros((3 * d,), jnp.float32),
            },
            "proj": {
                "w": L.uniform_scaling_init(
                    ks[1], (d, d), scale / math.sqrt(2 * cfg.n_layer)
                ),
                "b": jnp.zeros((d,), jnp.float32),
            },
        },
        "ln_2": L.layernorm_init(d),
        "mlp": {
            "fc": {
                "w": L.uniform_scaling_init(ks[2], (d, 4 * d), scale),
                "b": jnp.zeros((4 * d,), jnp.float32),
            },
            "proj": {
                "w": L.uniform_scaling_init(
                    ks[3], (4 * d, d), scale / math.sqrt(2 * cfg.n_layer)
                ),
                "b": jnp.zeros((d,), jnp.float32),
            },
        },
    }


def init_params(key: jax.Array, cfg: GPT2Config) -> Params:
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layer)
    # Stacked blocks: every leaf has leading axis n_layer — the
    # `transformer.h` equivalent, partitionable by slicing axis 0.
    blocks = jax.vmap(lambda k: init_block_params(k, cfg))(block_keys)
    return {
        "wte": L.embedding_init(k_wte, cfg.vocab_size, cfg.n_embd),
        "wpe": L.embedding_init(k_wpe, cfg.n_positions, cfg.n_embd),
        "blocks": blocks,
        "ln_f": L.layernorm_init(cfg.n_embd),
        # lm_head is tied to wte (standard GPT-2 weight tying).
    }


def logical_axes() -> Params:
    """The model's sharding declaration — named once, HERE, and resolved
    per parallelism mode by the registry (core/sharding.py).  Each leaf
    is a tuple of logical axis names, one per dim of the matching param
    (blocks carry the stacked ``layer`` leading dim).  Megatron layout:
    qkv/fc shard their output dim (column parallel, ``w_tp``), the two
    proj weights shard their input dim (row parallel) so the pair needs
    one all-reduce; col-parallel biases shard, row-parallel biases and
    norms/embeddings replicate."""
    from trustworthy_dl_tpu.core import sharding as shreg

    LYR, HID, TP = shreg.LAYER, shreg.HIDDEN, shreg.W_TP
    block = {
        "ln_1": {"scale": (LYR, HID), "bias": (LYR, HID)},
        "attn": {
            "qkv": {"w": (LYR, HID, TP), "b": (LYR, TP)},
            "proj": {"w": (LYR, TP, HID), "b": (LYR, HID)},
        },
        "ln_2": {"scale": (LYR, HID), "bias": (LYR, HID)},
        "mlp": {
            "fc": {"w": (LYR, HID, TP), "b": (LYR, TP)},
            "proj": {"w": (LYR, TP, HID), "b": (LYR, HID)},
        },
    }
    return {
        "wte": (None, HID),
        "wpe": (None, HID),
        "blocks": block,
        "ln_f": {"scale": (HID,), "bias": (HID,)},
    }


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def block_forward(block: Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    """One transformer block on [B, T, D] activations."""
    dtype = cfg.dtype
    attn_fn = get_attention(cfg.attn_impl)
    b, t, d = x.shape
    h = cfg.n_head

    y = L.layernorm(block["ln_1"], x).astype(dtype)
    qkv = L.dense(block["attn"]["qkv"], y, dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    # [B, T, D] -> [B, H, T, D/H]
    reshape = lambda a: a.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)
    out = attn_fn(reshape(q), reshape(k), reshape(v), True)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + L.dense(block["attn"]["proj"], out, dtype).astype(x.dtype)

    y = L.layernorm(block["ln_2"], x).astype(dtype)
    y = L.dense(block["mlp"]["fc"], y, dtype)
    y = jax.nn.gelu(y)
    x = x + L.dense(block["mlp"]["proj"], y, dtype).astype(x.dtype)
    return x


def apply_blocks(blocks: Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    """Scan the stacked block params over the activations — one compiled
    block body regardless of depth."""
    body = block_forward
    if cfg.remat:
        # "auto" resolves per shape: wherever it does NOT pick the flash
        # kernel it IS the full XLA path, so the attention policy's tagged
        # names exist and the cheap policy applies (the shared
        # auto_picks_flash predicate keeps this classification and the
        # dispatch itself from ever drifting apart).
        t, d_head = x.shape[-2], cfg.n_embd // cfg.n_head
        effectively_full = cfg.attn_impl == "full" or (
            cfg.attn_impl == "auto" and not auto_picks_flash(t, d_head)
        )
        if cfg.remat_policy == "attention" and effectively_full:
            # Save everything except the O(T²) scores/probs: only the
            # attention core recomputes in the backward pass.  Only the
            # "full" impl tags those names — the Pallas/ring paths never
            # materialise them (that is their point), so for any other
            # impl the policy would match nothing and silently save ALL
            # intermediates; fall through to block remat instead.
            from jax.ad_checkpoint import checkpoint_policies as cp

            policy = cp.save_anything_except_these_names(
                "attn_scores", "attn_probs"
            )
            body = jax.checkpoint(body, static_argnums=(2,), policy=policy)
        else:
            body = jax.checkpoint(body, static_argnums=(2,))

    def scan_fn(h, block):
        return body(block, h, cfg), None

    x, _ = jax.lax.scan(scan_fn, x, blocks)
    return x


def embed(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    t = tokens.shape[-1]
    pos = jnp.arange(t)
    x = params["wte"][tokens] + params["wpe"][pos]
    return x.astype(jnp.float32)


def project_logits(params: Params, normed: jax.Array, cfg: GPT2Config
                   ) -> jax.Array:
    """Tied-embedding projection [..., D] -> [..., vocab] (shared by
    forward and forward_with_monitor so the monitored logits can never
    drift from the trained ones)."""
    return (normed.astype(cfg.dtype)
            @ params["wte"].T.astype(cfg.dtype)).astype(jnp.float32)


def unembed(params: Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    return project_logits(params, L.layernorm(params["ln_f"], x), cfg)


def forward(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] -> logits [B, T, vocab]."""
    x = embed(params, tokens, cfg)
    x = apply_blocks(params["blocks"], x, cfg)
    return unembed(params, x, cfg)


def forward_with_monitor(params: Params, tokens: jax.Array, cfg: GPT2Config
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """tokens [B, T] -> (logits [B,T,V], features [B,T,D], mean_logits [V]).

    ``features`` are the final hidden activations *before* ln_f — the
    node-boundary output the reference's detector actually monitored
    (distributed_trainer.py:160-170 watches partition outputs, which are
    hidden activations, not logits).  Pre-norm matters: LayerNorm is
    scale/shift-invariant per position, so post-ln features would read
    identical under an activation-scaling corruption and blind the output
    battery.  They are vocab_size/n_embd ≈ 65× smaller than the logits, so
    detector batteries over them are nearly free and leave the
    cross-entropy's logits computation free to fuse.  ``mean_logits`` (for
    Byzantine/backdoor consensus signatures) is exact: the tied projection
    is linear, so mean over positions commutes with it —
    mean(normed) @ W == mean(normed @ W)."""
    x = embed(params, tokens, cfg)
    x = apply_blocks(params["blocks"], x, cfg)
    normed = L.layernorm(params["ln_f"], x)
    logits = project_logits(params, normed, cfg)
    mean_normed = jnp.mean(normed, axis=tuple(range(normed.ndim - 1)))
    mean_logits = project_logits(params, mean_normed, cfg)
    return logits, x, mean_logits


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: GPT2Config
            ) -> jax.Array:
    """Next-token cross entropy on {'input','target'} batches (targets are
    the shifted stream, produced by data/loader.py)."""
    if resolve_lm_head_chunk(cfg, int(batch["target"].size)):
        loss, _, _ = loss_with_monitor(params, batch, cfg)
        return loss
    logits = forward(params, batch["input"], cfg)
    return L.cross_entropy_loss(logits, batch["target"])


def head_loss_and_signature(params: Params, x: jax.Array,
                            targets: jax.Array, cfg: GPT2Config
                            ) -> Tuple[jax.Array, jax.Array]:
    """Final ln_f + tied head on [B, T, D] hiddens -> (mean CE, mean_logits).

    One implementation shared by the GPT-2 and MoE loss paths.  When
    ``cfg.lm_head_chunk`` is set the cross-entropy goes through the
    vocab-chunked fused head (ops/fused_ce.py), so the [B, T, V] logits
    are never materialised.  ``mean_logits`` (the Byzantine/backdoor
    consensus signature) stays exact and cheap either way: the tied
    projection is linear, so it is computed from the position-mean of the
    normed activations ([D] @ [D, V])."""
    normed = L.layernorm(params["ln_f"], x)
    mean_normed = jnp.mean(normed, axis=tuple(range(normed.ndim - 1)))
    mean_logits = project_logits(params, mean_normed, cfg)
    chunk = resolve_lm_head_chunk(cfg, int(targets.size))
    if chunk:
        from trustworthy_dl_tpu.ops.fused_ce import fused_lm_loss

        loss = fused_lm_loss(normed, params["wte"], targets,
                             chunk, cfg.dtype)
    else:
        logits = project_logits(params, normed, cfg)
        loss = L.cross_entropy_loss(logits, targets)
    return loss, mean_logits


def loss_with_monitor(params: Params, batch: Dict[str, jax.Array],
                      cfg: GPT2Config
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """{'input','target'} -> (loss, features [B,T,D], mean_logits [V]).

    The loss-bearing twin of ``forward_with_monitor`` for the engine's hot
    path: same detector features (pre-ln_f hidden states) and consensus
    signature, with the head fused via ``head_loss_and_signature``."""
    x = embed(params, batch["input"], cfg)
    x = apply_blocks(params["blocks"], x, cfg)
    loss, mean_logits = head_loss_and_signature(
        params, x, batch["target"], cfg
    )
    return loss, x, mean_logits


def num_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))
