"""A decoder described by its layers, for the serving path: the
architectures the paged engine runs beside GPT-2 (``models/gpt2.py``, which
keeps its own description and path; folding it in here is ROADMAP R0/D5).

:class:`DecoderConfig` states the layer pattern by kind, first the LEADING
layers (``lead``, each once, its second half a dense SiLU-gated MLP of
``intermediate_size``) and then whole periods (``period`` x ``n_periods``,
the second half routed experts, or the dense MLP where ``n_experts`` is 0),
and what this chip HOLDS of each layer: every layer is pre-norm residual,
``x + mix(RMSNorm(x))`` then ``x + mlp_or_experts(RMSNorm(x))``.  The only
positional term is the optional rotary one of softmax attention
(``rope_theta``; 0, no positional term at all: the causal mask and the
recurrence carry the order).  Four kinds of mixing:

* ``"attn"``: softmax attention over the paged K/V pool, ``q_heads`` query
  heads reading ``kv_heads`` shared K/V heads (query head ``h`` reads K/V
  head ``h // (q_heads // kv_heads)``), its output gated elementwise by
  ``sigmoid`` of a full-width projection of the normed input where
  ``attn_gate`` (scope ``attn.gqa``).  With ``rope_theta`` q and k are
  rotated (rotate-half over the whole head, scope ``attn.rope``) at their
  absolute positions, k BEFORE it is written to the pool: the paged kernels
  read keys that are already rotated and stay position-free.
* ``"hybrid"``: that attention (without the gate, where the description
  says so) and a Mamba-2 mixer (``models/mamba2.py``) side by side on ONE
  normed input, their outputs added: ``x + mamba(x~) + attn(x~)``.  The
  mixer: ``[z | x B C | dt] = x~ W_in``; a causal depthwise convolution
  with a bias and SiLU over ``x B C`` (scope ``ssm.conv``); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence (scopes
  ``ssm.chunk``, ``ssm.step``); ``W_out RMSNorm_groups(y * SiLU(z))``
  (scope ``ssm.norm``).  Its cache is BOTH the layer's K/V in the pool and
  a state ``[mamba_heads, mamba_head_dim, mamba_state]`` with the
  convolution's last ``conv_size - 1`` input rows, one row of each a slot.
  The muP multipliers of the description (``*_multiplier``, all 1 where
  the source has none) scale where the source scales: the embedding, the
  head's logits, each mixer's input and output, the key, the five parts of
  ``W_in``'s output, the MLP's gate and output.
* ``"mla"``: latent attention without a rotary term.  ``q = x~ W_q``
  (``q_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``); ``x~
  W_kv_a`` gives ``kv_lora_rank + qk_rope_head_dim`` values a position, the
  first ``kv_lora_rank`` RMS-normalised: THAT ROW, ``c~ || k_r``, is all the
  cache keeps of a position (``latent_lanes`` wide in the pool, padded with
  zeros to whole 128-lane columns; no per-head K or V exists anywhere).
  Per head ``k_h = (c~ W_kb)[h, :nope] || k_r``, ``v_h = (c~ W_kb)[h,
  nope:]``, a causal softmax of ``q_h k_h / sqrt(nope + rope)``.  A DECODE
  step runs the ABSORBED form: ``q'_h = q_n,h W_kb,h^K`` (scope
  ``mla.absorb``), scores ``q'_h . c~ + q_r,h . k_r`` and ``u_h =
  softmax(.) c~`` by the ONE paged kernel in its latent shape
  (``mla.attend``: one shared row whose first ``kv_lora_rank`` lanes are
  also the values), ``o_h = u_h W_kb,h^V`` (``mla.expand``).  A CHUNK runs
  the EXPANDED form (``ops/latent_attention.py``, all of it under
  ``mla.attend``): each cached block's rows times ``W_kb`` inside the
  kernel, then attention at the per-head widths, 3.4 times fewer products a
  pair and half the absorbed kernel's time on the chip (PERF.md section
  6).  Then ``W_o``.  A description has ``"attn"``, ``"hybrid"`` or
  ``"mla"`` layers, not two of them: the pool has one geometry.
* ``"kda"``: the gated delta rule (``models/kda.py``): q, k and v pass a
  short causal depthwise convolution and SiLU, q and k are L2-normalised a
  head, a low-rank pair gives the decay a channel, ``beta = kda_beta_scale
  x sigmoid(.)`` (2 where the source allows negative eigenvalues, 1 where
  it does not: a shape of the model, stated by its family);
  the output is RMS-normalised a head and gated by a second low-rank pair.
  Its cache is a state ``[dk, dv]`` a head and the convolution's last
  ``conv_size - 1`` input rows, one row of each a slot
  (``serve/kv_slots.RecurrentState``).
* the second half of a leading layer, and of every layer of a period
  without experts: ``layers.silu_gated_mlp`` (scope ``mlp.dense``);
* the second half of every layer of a period: ``models/moe.route_top_k`` over all
  ``n_experts`` published experts, ``models/moe.held_experts`` for the
  ``n_experts_held`` from ``first_expert`` that live here, plus the shared
  expert(s), added unweighted.  What absent experts would add is left out;
  that partial sum goes on to the next layer.

The weights' layout (the system's interface; ``benchmark/harness/families``
makes trees in it): ``embed [V, D]``, ``head [D, V]``, ``final_norm [D]``
``lead``, a tuple with one dict a leading layer (no leading axis; present
only where the description has leading layers), and ``periods``, a tuple
with one dict a position of the period whose every
leaf carries a leading axis over the periods; the layer scan runs over that
axis and a period's layers are unrolled inside it.  Matrices are served in
``cfg.dtype``; norm scales, ``a_log``, ``dt_bias``, the Mamba-2 mixer's
``d_skip`` and convolution bias and the router's bias stay float32.  A
layer's cache (K/V or latent rows, state rows) is indexed by its place
among the layers of its kind, leading layers first.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import kda
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.models import mamba2
from trustworthy_dl_tpu.models import moe
from trustworthy_dl_tpu.ops.fused_stats import LANES

Params = Dict[str, Any]

ATTN, KDA, MLA, HYBRID = "attn", "kda", "mla", "hybrid"
KINDS = (ATTN, KDA, MLA, HYBRID)
#: Leaves that stay float32 in the served view.
F32_LEAVES = ("norm1", "norm2", "final_norm", "o_norm", "a_log", "dt_bias",
              "router_bias", "kv_norm", "gate_norm", "d_skip", "conv_bias")


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """What shapes the decoder and what of it is held here.  Frozen and
    hashable: the serving programs take it as a static argument."""

    vocab_size: int                 # ids HELD: a slice of the vocabulary
    hidden_size: int
    period: Tuple[str, ...]         # the kinds of one period's layers
    n_periods: int
    q_heads: int                    # softmax attention: query heads
    kv_heads: int                   # shared K/V heads
    head_dim: int
    kda_heads: int
    kda_head_dim: int               # key width = value width
    conv_size: int
    kda_rank: int                   # width of the two low-rank pairs
    n_experts: int                  # published: the router's width
    n_experts_held: int
    first_expert: int
    experts_per_tok: int
    n_shared_experts: int
    moe_intermediate_size: int
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_positions: int = 1 << 20    # no positional term: the source's bound
    kda_sub_chunk: int = 64
    kda_block: int = 16
    dtype: Any = jnp.bfloat16
    lead: Tuple[str, ...] = ()      # the kinds of the leading dense layers
    intermediate_size: int = 0      # their MLP's width
    kda_beta_scale: float = 2.0     # beta = this x sigmoid(.)
    qk_nope_head_dim: int = 0       # latent attention's four widths
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    kv_lora_rank: int = 0
    rope_theta: float = 0.0         # 0: no rotary term
    attn_gate: bool = True          # softmax attention's sigmoid gate
    mamba_heads: int = 0            # the Mamba-2 mixer of a hybrid layer
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_groups: int = 1           # B and C are shared by a group of heads
    mamba_block: int = 128          # positions of a block of the chunked form
    # muP multipliers (1 where the source has none).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: Over ``W_in``'s five outputs ``[z | x | B | C | dt]``.
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    #: The MLP's gate (before its SiLU) and its output.
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)

    def __post_init__(self) -> None:
        kinds = set(self.period) | set(self.lead)
        if not self.period or kinds - set(KINDS):
            raise ValueError(
                f"the leading layers and a period are made of layers of "
                f"the kinds {KINDS!r}, got {self.lead!r} + {self.period!r}")
        if len(kinds & {ATTN, MLA, HYBRID}) > 1:
            raise ValueError(
                f"{sorted(kinds & {ATTN, MLA, HYBRID})} layers in one "
                "description: the pool keeps one kind of attention cache, "
                "not both")
        if (self.lead or not self.n_experts) and self.intermediate_size < 1:
            raise ValueError("dense MLP layers need intermediate_size")
        if HYBRID in kinds and min(self.mamba_heads, self.mamba_head_dim,
                                   self.mamba_state) < 1:
            raise ValueError(
                f"{HYBRID!r} layers need mamba_heads, mamba_head_dim and "
                "mamba_state")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError(f"{self.mamba_heads} Mamba-2 heads do not "
                             f"divide over {self.mamba_groups} groups")
        if len(self.ssm_multipliers) != 5:
            raise ValueError("ssm_multipliers: one for each of z, x, B, C "
                             "and dt")
        if MLA in kinds and min(self.qk_nope_head_dim, self.v_head_dim,
                                self.kv_lora_rank) < 1:
            raise ValueError(
                f"{MLA!r} layers need qk_nope_head_dim, v_head_dim and "
                "kv_lora_rank (and qk_rope_head_dim, which may be 0)")
        if self.q_heads % self.kv_heads:
            raise ValueError(f"{self.q_heads} query heads do not divide "
                             f"over {self.kv_heads} K/V heads")
        if not 0 <= self.first_expert <= self.n_experts \
                - self.n_experts_held:
            raise ValueError(
                f"experts {self.first_expert} to {self.first_expert} + "
                f"{self.n_experts_held} are not among {self.n_experts}")

    @property
    def n_positions(self) -> int:
        """The name the paged engine asks a description's depth by."""
        return self.max_positions

    def _count(self, kind: str) -> int:
        return self.lead.count(kind) + self.n_periods * self.period.count(
            kind)

    @property
    def n_layer(self) -> int:
        return len(self.lead) + self.n_periods * len(self.period)

    @property
    def n_expert_layers(self) -> int:
        """The layers whose second half is experts: the periods' where the
        description has experts, else none."""
        return self.n_periods * len(self.period) if self.n_experts else 0

    @property
    def n_attn_layers(self) -> int:
        """The layers that keep K and V in the pool: softmax attention,
        alone or beside a Mamba-2 mixer."""
        return self._count(ATTN) + self._count(HYBRID)

    @property
    def n_hybrid_layers(self) -> int:
        return self._count(HYBRID)

    @property
    def n_kda_layers(self) -> int:
        return self._count(KDA)

    @property
    def n_mla_layers(self) -> int:
        return self._count(MLA)

    @property
    def latent_width(self) -> int:
        """What a latent layer caches of a position: ``c~ || k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """The pool's row for it: whole 128-lane columns, the padding zero
        (at 576 the compiler relays the whole pool out for the kernel's
        lane windows; tests/test_chip_compile.py holds 640 to no copy)."""
        return -(-self.latent_width // LANES) * LANES

    @property
    def conv_channels(self) -> int:
        return 3 * self.kda_heads * self.kda_head_dim

    @property
    def mamba_width(self) -> int:
        """The Mamba-2 mixer's inner width: ``z``'s, ``x``'s and ``y``'s."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """What its convolution mixes: ``x``, then ``B`` and ``C``."""
        return self.mamba_width + 2 * self.mamba_groups * self.mamba_state


def decode_view(params: Params, cfg: DecoderConfig) -> Params:
    """The weights as the serving programs read them: matrices in
    ``cfg.dtype`` (a no-op for a tree that arrives in it), the leaves of
    :data:`F32_LEAVES` in float32."""
    def cast(path, leaf):
        name = path[-1].key
        return leaf.astype(jnp.float32 if name in F32_LEAVES else cfg.dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` in the weight's dtype, float32 accumulation and result."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``x`` times a constant of the description; no operation at 1, so a
    description without multipliers traces the program it always did."""
    return x if multiplier == 1.0 else x * multiplier


def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half rotary positions over the whole last axis: ``x [R, H, T,
    d]`` at ``positions i32[R, T]`` (or ``[1, T]``), float32 angles
    ``position x theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angle = positions.astype(jnp.float32)[:, None, :, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# -- the kinds of mixing layer ------------------------------------------------


def _gated_attention(p: Params, xn: jax.Array, pool_k: jax.Array,
                     pool_v: jax.Array, table: jax.Array, start: jax.Array,
                     layer: jax.Array, cfg: DecoderConfig, attn_impl: str
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``xn [R, T, D]`` -> the layer's output, with this call's K and V
    rows written into layer ``layer`` of the pool ``[L_attn, NB, BLOCK,
    kv_heads * head_dim]`` first (write-then-attend, as GPT-2's kernel
    path does: a row only ever writes blocks it owns).  Row ``r``'s
    positions start at ``start`` (a chunk's scalar, a decode step's ``[R]``);
    with ``rope_theta`` q and k are rotated there."""
    from trustworthy_dl_tpu.models import generate as gen
    from trustworthy_dl_tpu.ops import paged_attention as pattn

    r, t, _ = xn.shape
    heads = lambda a, n: a.reshape(r, t, n, cfg.head_dim).transpose(
        0, 2, 1, 3)
    xn = _scaled(xn, cfg.attn_in_multiplier)
    q = heads(_mm(xn, p["wq"]), cfg.q_heads)
    k = heads(_scaled(_mm(xn, p["wk"]), cfg.key_multiplier), cfg.kv_heads)
    v = heads(_mm(xn, p["wv"]), cfg.kv_heads)
    if cfg.rope_theta:
        with jax.named_scope("attn.rope"):
            positions = jnp.reshape(start, (-1, 1)) + jnp.arange(t)
            q = rotary(q, positions, cfg.rope_theta)
            k = rotary(k, positions, cfg.rope_theta)
    q = q.astype(cfg.dtype)
    gate = jax.nn.sigmoid(_mm(xn, p["wg"])) if cfg.attn_gate else None
    _, phys, offs = gen._pool_write_coords(
        table, start, r, t, pool_k.shape[2], table.shape[1])
    pool_k = gen._pool_write_rows(pool_k, k, layer, phys, offs)
    pool_v = gen._pool_write_rows(pool_v, v, layer, phys, offs)
    if attn_impl == "jnp":
        out = pattn.paged_attention_reference(q, pool_k, pool_v, table,
                                              start, layer=layer)
    else:
        attend = (pattn.paged_prefill_attention if t > pattn.QROWS
                  else pattn.paged_attention)
        out = attend(q, pool_k, pool_v, table, start, layer=layer,
                     interpret=(attn_impl == "interpret"))
    out = out.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(r, t, -1)
    if gate is not None:
        out = out * gate
    return (_scaled(_mm(out, p["wo"]), cfg.attn_out_multiplier), pool_k,
            pool_v)


def latent_rows(p: Params, xn: jax.Array, cfg: DecoderConfig) -> jax.Array:
    """``xn [R, T, D]`` -> what a latent layer caches of each position,
    ``RMSNorm(c) || k_r || 0`` as float32 ``[R, T, latent_lanes]``."""
    a = _mm(xn, p["w_kv_a"])
    rank = cfg.kv_lora_rank
    c = L.rmsnorm(p["kv_norm"], a[..., :rank], cfg.norm_eps)
    pad = jnp.zeros(a.shape[:-1] + (cfg.latent_lanes - cfg.latent_width,),
                    a.dtype)
    return jnp.concatenate([c, a[..., rank:], pad], axis=-1)


def _latent_split(p: Params, cfg: DecoderConfig
                  ) -> Tuple[jax.Array, jax.Array]:
    """``W_kb`` as its two halves a head: ``[rank, H, nope]`` (the keys')
    and ``[rank, H, v]`` (the values')."""
    w = p["w_kv_b"].reshape(cfg.kv_lora_rank, cfg.q_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def absorbed_queries(p: Params, xn: jax.Array, cfg: DecoderConfig
                     ) -> jax.Array:
    """``xn [R, T, D]`` -> the heads' queries against a cached row,
    ``q_n W_kb^K || q_r || 0`` as float32 ``[R, H, T, latent_lanes]``."""
    r, t, _ = xn.shape
    nope = cfg.qk_nope_head_dim
    q = _mm(xn, p["wq"]).reshape(r, t, cfg.q_heads,
                                 nope + cfg.qk_rope_head_dim)
    w_k, _ = _latent_split(p, cfg)
    absorbed = jnp.einsum("rthn,chn->rhtc", q[..., :nope].astype(w_k.dtype),
                          w_k, preferred_element_type=jnp.float32)
    pad = jnp.zeros((r, cfg.q_heads, t,
                     cfg.latent_lanes - cfg.latent_width), jnp.float32)
    return jnp.concatenate(
        [absorbed, q[..., nope:].transpose(0, 2, 1, 3), pad], axis=-1)


def expanded_output(p: Params, u: jax.Array, cfg: DecoderConfig
                    ) -> jax.Array:
    """``u [R, H, T, rank]``, the heads' softmax-weighted sums of cached
    ``c~``, -> ``concat_h(u_h W_kb,h^V) W_o`` as ``[R, T, D]``."""
    r, _, t, _ = u.shape
    _, w_v = _latent_split(p, cfg)
    o = jnp.einsum("rhtc,chv->rthv", u.astype(w_v.dtype), w_v,
                   preferred_element_type=jnp.float32)
    return _mm(o.reshape(r, t, -1), p["wo"])


def _latent_attention(p: Params, xn: jax.Array, pool: jax.Array,
                      table: jax.Array, start: jax.Array, layer: jax.Array,
                      cfg: DecoderConfig, attn_impl: str
                      ) -> Tuple[jax.Array, jax.Array]:
    """``xn [R, T, D]`` -> the layer's output, with this call's latent rows
    written into layer ``layer`` of the pool ``[L_mla, NB, BLOCK,
    latent_lanes]`` first (write-then-attend).  One position a row (a
    decode step) runs the absorbed form, the cache holding no per-head K or
    V to read; a chunk (``R = CHUNK_ROWS``, which ``apply_paged`` holds it
    to) the expanded form."""
    from trustworthy_dl_tpu.models import generate as gen
    from trustworthy_dl_tpu.ops import latent_attention as lattn
    from trustworthy_dl_tpu.ops import paged_attention as pattn

    r, t, _ = xn.shape
    _, phys, offs = gen._pool_write_coords(
        table, start, r, t, pool.shape[2], table.shape[1])
    pool = gen._pool_write_rows(pool, latent_rows(p, xn, cfg)[:, None],
                                layer, phys, offs)
    if t > 1:
        nope = cfg.qk_nope_head_dim
        q = _mm(xn[0], p["wq"]).reshape(t, cfg.q_heads, -1).transpose(
            1, 0, 2).astype(cfg.dtype)                   # [H, T, nope + rope]
        with jax.named_scope("mla.attend"):
            if attn_impl == "jnp":
                o = lattn.latent_prefill_reference(
                    q, p["w_kv_b"], pool, table, start, layer=layer,
                    nope=nope)
            else:
                o = lattn.latent_prefill_attention(
                    q, p["w_kv_b"], pool, table, start, layer=layer,
                    nope=nope, interpret=(attn_impl == "interpret"))
        o = o.astype(jnp.float32).transpose(1, 0, 2).reshape(1, t, -1)
        return _mm(o, p["wo"]), pool
    with jax.named_scope("mla.absorb"):
        q = absorbed_queries(p, xn, cfg).astype(cfg.dtype)
    shape = dict(layer=layer, v_lanes=cfg.kv_lora_rank, scale=1.0 / math.sqrt(
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    with jax.named_scope("mla.attend"):
        if attn_impl == "jnp":
            u = pattn.paged_attention_reference(q, pool, None, table, start,
                                                **shape)
        else:
            u = pattn.paged_attention(q, pool, None, table, start,
                                      interpret=(attn_impl == "interpret"),
                                      **shape)
    with jax.named_scope("mla.expand"):
        return expanded_output(p, u, cfg), pool


def _kda_inputs(p: Params, xn: jax.Array, tail: jax.Array,
                cfg: DecoderConfig, valid: jax.Array):
    """Everything the delta rule takes, from ``xn [R, T, D]`` and the
    convolution's tail ``[R, K-1, ch]``: ``q, k, v, g [R, H, T, d]``,
    ``beta [R, H, T]``, the rows ``tail ++ projections`` (the next tail is
    cut from them) and the output gate ``[R, T, H * d]``.  A position that
    is not ``valid [R, T]`` gets ``beta = g = 0``: it leaves the state as
    it was."""
    r, t, _ = xn.shape
    h, d = cfg.kda_heads, cfg.kda_head_dim
    pre = jnp.concatenate([_mm(xn, p["wq"]), _mm(xn, p["wk"]),
                           _mm(xn, p["wv"])], axis=-1)       # [R, T, 3·H·d]
    rows = jnp.concatenate([tail, pre], axis=-2)
    mixed = jax.nn.silu(kda.causal_conv(
        pre, tail, p["conv"].astype(jnp.float32)))
    heads = lambda a: a.reshape(r, t, h, d).transpose(0, 2, 1, 3)
    q, k, v = (heads(a) for a in jnp.split(mixed, 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q = unit(q) * (1.0 / math.sqrt(d))
    k = unit(k)
    decay = jax.nn.softplus(_mm(_mm(xn, p["f_a"]), p["f_b"]) + p["dt_bias"])
    g = -jnp.exp(p["a_log"])[None, :, None, None] * heads(decay)
    beta = cfg.kda_beta_scale * jax.nn.sigmoid(
        _mm(xn, p["w_beta"])).transpose(0, 2, 1)
    keep = valid[:, None, :]
    g = jnp.where(keep[..., None], g, 0.0)
    beta = jnp.where(keep, beta, 0.0)
    gate = jax.nn.sigmoid(_mm(_mm(xn, p["g_a"]), p["g_b"]))
    return q, k, v, g, beta, rows, gate


def _kda_output(p: Params, o: jax.Array, gate: jax.Array,
                cfg: DecoderConfig) -> jax.Array:
    """``o [R, H, T, d]`` -> RMSNorm a head, gate, output projection."""
    r, _, t, _ = o.shape
    o = L.rmsnorm(p["o_norm"], o, cfg.norm_eps)
    o = o.transpose(0, 2, 1, 3).reshape(r, t, -1)
    return _mm(o * gate, p["wo"])


def _mamba_inputs(p: Params, xn: jax.Array, tail: jax.Array,
                  cfg: DecoderConfig, valid: jax.Array):
    """Everything the Mamba-2 recurrence takes, from ``xn [R, T, D]`` and
    the convolution's tail ``[R, K-1, ch]``: ``x [R, T, H, P]``, ``dt [R, T,
    H]`` (0 where not ``valid``: the state stays as it was), ``B, C [R, T,
    G, N]``, the rows ``tail ++ (x B C before the convolution)`` (the next
    tail is cut from them) and the gate ``z [R, T, H * P]``."""
    r, t, _ = xn.shape
    width, ch = cfg.mamba_width, cfg.mamba_conv_channels
    bc = cfg.mamba_groups * cfg.mamba_state
    proj = _mm(_scaled(xn, cfg.ssm_in_multiplier), p["w_in"])
    z, pre, dt = (proj[..., :width], proj[..., width:width + ch],
                  proj[..., width + ch:])
    m_z, m_x, m_b, m_c, m_dt = cfg.ssm_multipliers
    if (m_x, m_b, m_c) != (1.0, 1.0, 1.0):
        pre = pre * jnp.concatenate([
            jnp.full((width,), m_x, jnp.float32),
            jnp.full((bc,), m_b, jnp.float32),
            jnp.full((bc,), m_c, jnp.float32)])
    rows = jnp.concatenate([tail, pre], axis=-2)
    with jax.named_scope("ssm.conv"):
        mixed = jax.nn.silu(kda.causal_conv(
            pre, tail, p["conv"].astype(jnp.float32)) + p["conv_bias"])
    group = lambda a: a.reshape(r, t, cfg.mamba_groups, cfg.mamba_state)
    x = mixed[..., :width].reshape(r, t, cfg.mamba_heads, cfg.mamba_head_dim)
    b, c = group(mixed[..., width:width + bc]), group(mixed[..., width + bc:])
    dt = jax.nn.softplus(_scaled(dt, m_dt) + p["dt_bias"])
    dt = jnp.where(valid[..., None], dt, 0.0)
    return x, dt, b, c, rows, _scaled(z, m_z)


def _mamba_output(p: Params, y: jax.Array, z: jax.Array, cfg: DecoderConfig
                  ) -> jax.Array:
    """``y [R, T, H, P]`` -> the gated group RMSNorm, ``W_out``, the
    mixer's output multiplier."""
    r, t = y.shape[:2]
    with jax.named_scope("ssm.norm"):
        y = mamba2.gated_group_norm(p["gate_norm"], y.reshape(r, t, -1), z,
                                    cfg.mamba_groups, cfg.norm_eps)
    return _scaled(_mm(y, p["w_out"]), cfg.ssm_out_multiplier)


def _expert_half(p: Params, xn: jax.Array, cfg: DecoderConfig,
                 valid: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The routed experts held here plus the shared expert over ``xn
    [R, T, D]``; also the pairs each held expert took."""
    r, t, d = xn.shape
    flat = xn.reshape(r * t, d)
    with jax.named_scope("moe.route"):
        chosen, weights = moe.route_top_k(
            flat, p["router"], p["router_bias"], cfg.experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor)
    with jax.named_scope("moe.experts"):
        y, pairs = moe.held_experts(
            flat, chosen, weights, p["w_gate_up"], p["w_down"],
            cfg.first_expert, valid.reshape(-1))
    with jax.named_scope("moe.shared"):
        y = y + L.silu_gated_mlp(p["shared_gate_up"], p["shared_down"],
                                 flat)
    return y.reshape(r, t, d), pairs


# -- the serving forward -------------------------------------------------------

#: The rows a CHUNK call of :func:`apply_paged` takes: one slot's.  The
#: chunked delta rule reads and writes the state row of ONE traced slot, the
#: latent chunk kernel attends for one row, and the expert layer routes one
#: row's positions; the scheduler sizes its chunk calls by this.
CHUNK_ROWS = 1


def apply_paged(view: Params, tokens: jax.Array, pool_k: jax.Array,
                pool_v: Optional[jax.Array], state: Any, table: jax.Array,
                start: jax.Array, cfg: DecoderConfig, valid: jax.Array,
                slot: Optional[jax.Array] = None,
                last_pos: Optional[jax.Array] = None,
                attn_impl: str = "jnp"
                ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array], Any]:
    """Run every layer over ``tokens [R, T]`` against the paged pool and
    the recurrent state (``serve/kv_slots.RecurrentState``); both are the
    layer loop's carry, written in place under donation.  Returns (logits
    ``[R, V]`` at ``last_pos``, or at the one position fed, the pool, the
    state).  With latent layers the pool is ONE array of latent rows
    (``pool_k``) and ``pool_v`` is None, in and out: no V half is
    allocated, carried or donated.

    Two shapes, as the scheduler's two programs call it.  DECODE: ``T = 1``,
    row ``r`` of the call IS slot ``r`` (``slot`` None, ``start i32[R]``):
    the recurrence, one step a slot.  A CHUNK of prefill: ``R =
    CHUNK_ROWS``, ``slot`` the traced row of the state, ``start`` a scalar:
    the chunked form, from the slot's state and convolution tail and back
    into them (a latent layer's chunk runs the expanded form).
    ``valid bool[R, T]`` marks the real positions: an idle slot and a
    chunk's padding leave state, tail and counters as they were (their K/V
    or latent rows land in the trash block through ``table``, as GPT-2's
    do).  The leading layers run first, each once; then the scan over the
    periods.
    """
    r, t = tokens.shape
    if t > 1 and r != CHUNK_ROWS:
        raise ValueError(f"a chunk is one slot's, got {r} rows of {t}")
    x = _scaled(view["embed"][tokens].astype(jnp.float32),
                cfg.embedding_multiplier)
    kinds = cfg.period
    n_real = jnp.sum(valid, axis=1).astype(jnp.int32)            # [R]
    tail_len = cfg.conv_size - 1
    kernel = attn_impl != "jnp"

    def read(rows: jax.Array, layer: jax.Array) -> jax.Array:
        """Layer ``layer`` of a state array ``[L, slots, ...]``: every
        slot's row (decode) or the one of ``slot``."""
        if slot is None:
            return rows[layer]
        return rows[layer, slot][None]

    def write(rows: jax.Array, layer: jax.Array, new: jax.Array
              ) -> jax.Array:
        if slot is None:
            return rows.at[layer].set(new.astype(rows.dtype))
        return rows.at[layer, slot].set(new[0].astype(rows.dtype))

    def next_tail(rows: jax.Array) -> jax.Array:
        """The K-1 convolution rows before each row's first unfed one."""
        return jax.vmap(lambda a, n: jax.lax.dynamic_slice_in_dim(
            a, n, tail_len, axis=0))(rows, n_real)

    def mamba(p: Params, xn: jax.Array, ssm_all: jax.Array,
              conv_all: jax.Array, layer: Any):
        """The Mamba-2 half of a hybrid layer: its output and the state
        rows.  A decode step of the kernel path updates layer ``layer`` of
        the whole state where it lies (``ops/ssm_step.py``)."""
        tail = read(conv_all, layer).astype(jnp.float32)
        xs, dt, b, c, rows, z = _mamba_inputs(p, xn, tail, cfg, valid)
        a = -jnp.exp(p["a_log"])
        if t == 1 and kernel:
            from trustworthy_dl_tpu.ops import ssm_step

            with jax.named_scope("ssm.step"):
                y, ssm_all = ssm_step.ssm_step(
                    ssm_all, layer, xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                    p["d_skip"], interpret=(attn_impl == "interpret"))
            y = y[:, None]
        else:
            s = read(ssm_all, layer)
            if t == 1:
                with jax.named_scope("ssm.step"):
                    y, s = mamba2.ssd_step(xs[:, 0], dt[:, 0], a, b[:, 0],
                                           c[:, 0], p["d_skip"], s)
                y = y[:, None]
            else:
                with jax.named_scope("ssm.chunk"):
                    y, s = mamba2.ssd_chunk(xs, dt, a, b, c, p["d_skip"], s,
                                            math.gcd(t, cfg.mamba_block))
            ssm_all = write(ssm_all, layer, s)
        conv_all = write(conv_all, layer, next_tail(rows))
        return _mamba_output(p, y, z, cfg), ssm_all, conv_all

    def mix(kind: str, p: Params, x: jax.Array, caches: Tuple[Any, ...],
            layer: Any) -> Tuple[jax.Array, Tuple[Any, ...]]:
        """The first half of one layer of ``kind`` whose cache is the
        ``layer``-th of its kind: ``x + mix(RMSNorm(x))`` and the caches."""
        pk, pv, s_all, conv_all, ssm_all, ssm_conv_all = caches
        xn = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        if kind in (ATTN, HYBRID):
            with jax.named_scope("attn.gqa"):
                y, pk, pv = _gated_attention(
                    p["attn"], xn, pk, pv, table, start, layer, cfg,
                    attn_impl)
            if kind == HYBRID:
                y_ssm, ssm_all, ssm_conv_all = mamba(
                    p["mamba"], xn, ssm_all, ssm_conv_all, layer)
                y = y_ssm + y
        elif kind == MLA:
            with jax.named_scope("attn.mla"):
                y, pk = _latent_attention(
                    p["mla"], xn, pk, table, start, layer, cfg, attn_impl)
        else:
            tail = read(conv_all, layer).astype(jnp.float32)
            q, k, v, g, beta, rows, gate = _kda_inputs(
                p["kda"], xn, tail, cfg, valid)
            s = read(s_all, layer)
            if t == 1:
                with jax.named_scope("kda.step"):
                    o, s = kda.kda_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                        g[:, :, 0], beta[:, :, 0], s)
                o = o[:, :, None]
            else:
                with jax.named_scope("kda.chunk"):
                    o, s = kda.kda_chunk(q, k, v, g, beta, s,
                                         cfg.kda_sub_chunk, cfg.kda_block)
            s_all = write(s_all, layer, s)
            conv_all = write(conv_all, layer, next_tail(rows))
            y = _kda_output(p["kda"], o, gate, cfg)
        return x + y, (pk, pv, s_all, conv_all, ssm_all, ssm_conv_all)

    def dense(p: Params, x: jax.Array) -> jax.Array:
        m_gate, m_down = cfg.mlp_multipliers
        with jax.named_scope("mlp.dense"):
            return x + _scaled(L.silu_gated_mlp(
                p["mlp"]["gate_up"], p["mlp"]["down"],
                L.rmsnorm(p["norm2"], x, cfg.norm_eps), m_gate), m_down)

    caches = (pool_k, pool_v, state.s, state.conv, state.ssm,
              state.ssm_conv)
    for j, (kind, p) in enumerate(zip(cfg.lead, view.get("lead", ()))):
        x, caches = mix(kind, p, x, caches, cfg.lead[:j].count(kind))
        x = dense(p, x)

    def period_fn(carry, xs):
        x, caches, pairs_all = carry
        layers_p, index = xs
        for j, (kind, p) in enumerate(zip(kinds, layers_p)):
            x, caches = mix(kind, p, x, caches,
                            cfg.lead.count(kind) + index * kinds.count(kind)
                            + kinds[:j].count(kind))
            if not cfg.n_experts:
                x = dense(p, x)
                continue
            y, pairs = _expert_half(
                p["moe"], L.rmsnorm(p["norm2"], x, cfg.norm_eps), cfg,
                valid)
            x = x + y
            pairs_all = pairs_all.at[index * len(kinds) + j].add(pairs)
        return (x, caches, pairs_all), None

    index = jnp.arange(cfg.n_periods, dtype=jnp.int32)
    (x, caches, pairs_all), _ = jax.lax.scan(
        period_fn, (x, caches, state.expert_pairs),
        (view["periods"], index))
    pool_k, pool_v, s_all, conv_all, ssm_all, ssm_conv_all = caches
    state = state._replace(s=s_all, conv=conv_all, ssm=ssm_all,
                           ssm_conv=ssm_conv_all)
    if cfg.n_experts:
        state = state._replace(
            expert_pairs=pairs_all, expert_tokens=state.expert_tokens
            + cfg.n_expert_layers * jnp.sum(n_real))
    if last_pos is not None:
        x = jax.lax.dynamic_index_in_dim(x, last_pos, axis=1, keepdims=False)
    else:
        x = x[:, -1]
    x = L.rmsnorm(view["final_norm"], x, cfg.norm_eps)
    return (_scaled(_mm(x, view["head"]), cfg.lm_head_multiplier), pool_k,
            pool_v, state)
