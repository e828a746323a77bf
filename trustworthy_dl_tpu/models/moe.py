"""Mixture-of-Experts GPT-2 with expert parallelism over the 'expert' axis.

Out of the reference's scope (SURVEY §2.4 lists EP/MoE as absent — "optional
stretch"), built here because the charter makes every parallelism strategy
first-class.  The design is the classic TPU-native dense-dispatch MoE
(GShard/Switch): routing is expressed as two einsums against a
[tokens, experts, capacity] dispatch/combine tensor, so the whole layer is
MXU matmuls with static shapes — no scatters, no dynamic shapes, nothing
XLA can't tile.  Expert weights carry a leading E axis sharded on the
'expert' mesh axis; under a mesh context (``use_expert_mesh``) sharding
constraints on the [E, C, d] expert blocks make GSPMD insert the canonical
all_to_all pair around the expert FFNs.

Routing: top-k (default 2) softmax gating, combine weights renormalised
over the selected experts; per-expert capacity C = ceil(k·S/E · factor);
overflow tokens fall through the residual stream untouched (standard drop
behavior).  The Switch load-balance auxiliary loss
(E · Σ_e fraction_e · mean_prob_e, =1 at perfect balance) is averaged over
layers and added to the LM loss with weight ``aux_weight``.

Everything outside the MLP is exactly models/gpt2.py (attention registry
included), and the params keep the stacked-blocks layout, so pipeline
slicing, checkpointing, and the detector battery all work unchanged.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.core.mesh import EXPERT_AXIS
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.ops.grouped_matmul import grouped_matmul

Params = Dict[str, Any]

_EXPERT_MESH = None


def set_expert_mesh(mesh) -> None:
    global _EXPERT_MESH
    _EXPERT_MESH = mesh


@contextlib.contextmanager
def use_expert_mesh(mesh):
    """Make MoE forwards constrain expert blocks to the 'expert' mesh axis
    (same pattern as parallel/sequence.use_sequence_mesh)."""
    global _EXPERT_MESH
    prev = _EXPERT_MESH
    _EXPERT_MESH = mesh
    try:
        yield
    finally:
        _EXPERT_MESH = prev


def _expert_sharding():
    from trustworthy_dl_tpu.core import sharding as shreg

    mesh = _EXPERT_MESH
    if mesh is None or EXPERT_AXIS not in mesh.axis_names:
        return None
    return shreg.rules_for("expert").named_sharding(
        mesh, shreg.EXPERT, None, None)


@dataclasses.dataclass(frozen=True)
class MoEConfig(gpt2.GPT2Config):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    # Slot assignment under capacity pressure:
    #   "positional" — GShard's in-order claim (rank-0 before rank-1,
    #                  earlier tokens before later); overflow drops are
    #                  position-biased (late tokens lose).
    #   "priority"   — per-expert sort by gate probability (one
    #                  [E, S] top_k, static shapes): overflow drops the
    #                  LOWEST-prob assignments, minimising dropped gate
    #                  mass.  The TPU-friendly form of sorted dispatch.
    dispatch: str = "positional"

    def __post_init__(self) -> None:
        if self.dispatch not in ("positional", "priority"):
            raise ValueError(
                f"dispatch must be 'positional' or 'priority', got "
                f"{self.dispatch!r}"
            )

    @staticmethod
    def from_name(name: str, **overrides: Any) -> "MoEConfig":
        key = name.lower().replace("-moe", "")
        if key not in gpt2.GPT2_SIZES:
            raise ValueError(f"unknown GPT-2 size {name!r}")
        kwargs = dict(gpt2.GPT2_SIZES[key])
        kwargs.update(overrides)
        return MoEConfig(**kwargs)


# --------------------------------------------------------------------------
# Parameters: gpt2 block with the dense MLP swapped for router + experts
# --------------------------------------------------------------------------


def init_block_params(key: jax.Array, cfg: MoEConfig) -> Params:
    base = gpt2.init_block_params(key, cfg)
    k_router, k_fc, k_proj = jax.random.split(jax.random.fold_in(key, 17), 3)
    d, e, f = cfg.n_embd, cfg.n_experts, 4 * cfg.n_embd
    del base["mlp"]
    base["moe"] = {
        # Router kept f32: gating decisions are control flow, not compute.
        "router": {"w": L.uniform_scaling_init(k_router, (d, e), 0.02)},
        "fc": {
            "w": L.uniform_scaling_init(k_fc, (e, d, f), 0.02),
            "b": jnp.zeros((e, f), jnp.float32),
        },
        "proj": {
            "w": L.uniform_scaling_init(
                k_proj, (e, f, d), 0.02 / math.sqrt(2 * cfg.n_layer)
            ),
            "b": jnp.zeros((e, d), jnp.float32),
        },
    }
    return base


def init_params(key: jax.Array, cfg: MoEConfig) -> Params:
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_layer)
    blocks = jax.vmap(lambda k: init_block_params(k, cfg))(block_keys)
    return {
        "wte": L.embedding_init(k_wte, cfg.vocab_size, cfg.n_embd),
        "wpe": L.embedding_init(k_wpe, cfg.n_positions, cfg.n_embd),
        "blocks": blocks,
        "ln_f": L.layernorm_init(cfg.n_embd),
    }


# --------------------------------------------------------------------------
# Routing + expert FFN
# --------------------------------------------------------------------------


def _capacity(num_tokens: int, cfg: MoEConfig) -> int:
    """Per-expert slot count: ceil(k·S/E · factor), floored at 4 (tiny
    batches would otherwise drop most assignments) and ALWAYS clamped to
    ``num_tokens`` — the num_tokens clamp must come last, because a
    capacity above S is meaningless (an expert can hold at most every
    token) and the priority dispatcher's ``lax.top_k(rank.T, capacity)``
    trace-crashes when capacity exceeds its [E, S] operand width."""
    c = math.ceil(num_tokens * cfg.top_k / cfg.n_experts
                  * cfg.capacity_factor)
    return min(max(4, int(c)), num_tokens)


def _topk_gating(probs: jax.Array, top_k: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared by both dispatchers: (raw top-k probs [S, k], renormalised
    combine weights [S, k], expert indices [S, k])."""
    raw_probs, topk_idx = jax.lax.top_k(probs, top_k)
    norm = jnp.sum(raw_probs, axis=-1, keepdims=True)
    return raw_probs, raw_probs / jnp.maximum(norm, 1e-9), topk_idx


def _switch_aux_loss(probs: jax.Array, topk_idx: jax.Array) -> jax.Array:
    """Switch load-balance aux on rank-0 assignments: E · Σ_e f_e · P̄_e
    (=1 at perfect balance).  Shared so the dispatchers cannot drift."""
    e = probs.shape[1]
    top1 = jax.nn.one_hot(topk_idx[:, 0], e, dtype=jnp.float32)
    return e * jnp.sum(jnp.mean(top1, axis=0) * jnp.mean(probs, axis=0))


def router_dispatch(
    probs: jax.Array, cfg: MoEConfig, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """[S, E] gate probs -> (combine f32[S, E, C], aux f32[]).

    Top-k assignment with in-order positions: rank-0 choices claim slots
    before rank-1 (GShard's ordering), positions past capacity drop.  The
    dispatch mask is ``combine > 0``.
    """
    s, e = probs.shape
    _, topk_probs, topk_idx = _topk_gating(probs, cfg.top_k)

    combine = jnp.zeros((s, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.int32)
    for r in range(cfg.top_k):                                # static k
        onehot = jax.nn.one_hot(topk_idx[:, r], e, dtype=jnp.int32)  # [S,E]
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]       # [S,E]
        within = (pos < capacity) & (onehot > 0)
        slot = jax.nn.one_hot(
            jnp.where(within, pos, capacity), capacity, dtype=jnp.float32
        )                                                     # OOB -> all-0
        combine = combine + topk_probs[:, r, None, None] * slot * \
            within[..., None].astype(jnp.float32)
        counts = counts + jnp.sum(onehot, axis=0)

    return combine, _switch_aux_loss(probs, topk_idx)


def router_dispatch_priority(
    probs: jax.Array, cfg: MoEConfig, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """[S, E] gate probs -> (combine f32[S, E, C], aux f32[]).

    Sorted dispatch: each expert keeps its top-``capacity`` assignments
    BY GATE PROBABILITY (one ``lax.top_k`` over the [E, S] assignment
    matrix — the static-shape TPU spelling of sorting assignments within
    each expert), so capacity overflow sheds the lowest-confidence
    routes instead of whatever arrived last.  Same contract as
    ``router_dispatch``; identical result when nothing overflows.
    """
    s, e = probs.shape
    raw_probs, renorm_probs, topk_idx = _topk_gating(probs, cfg.top_k)

    # Two assignment matrices over (token, expert): rank by the RAW gate
    # probability (the router's confidence — renormalisation would make
    # every top-1 weight 1.0 and destroy the ordering), combine with the
    # renormalised weight (the usual mixture semantics).
    rank = jnp.zeros((s, e), jnp.float32)
    weight = jnp.zeros((s, e), jnp.float32)
    for r in range(cfg.top_k):
        onehot = jax.nn.one_hot(topk_idx[:, r], e, dtype=jnp.float32)
        rank = rank + onehot * raw_probs[:, r, None]
        weight = weight + onehot * renorm_probs[:, r, None]

    vals, token_idx = jax.lax.top_k(rank.T, capacity)        # [E, C]
    keep = (vals > 0.0).astype(jnp.float32)                  # real routes
    w = jnp.take_along_axis(weight.T, token_idx, axis=1)     # [E, C]
    # combine[s, e, c] = w[e, c] iff token_idx[e, c] == s and kept.
    sel = jax.nn.one_hot(token_idx, s, dtype=jnp.float32)    # [E, C, S]
    combine = jnp.einsum("ecs,ec->sec", sel, w * keep)
    return combine, _switch_aux_loss(probs, topk_idx)


def moe_mlp(moe: Params, x: jax.Array, cfg: MoEConfig
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """[B, T, d] -> ([B, T, d], aux loss [], drop fraction []).  Two
    dispatch einsums around the per-expert FFN; expert blocks constrained
    to the 'expert' axis when a mesh context is live.  The drop fraction
    is the share of the S·k routed assignments that exceeded expert
    capacity and fell through the residual stream — invisible in the loss
    on any single step, so it is surfaced as a metric (VERDICT r4 weak #5)."""
    b, t, d = x.shape
    s = b * t
    xf = x.reshape(s, d)
    capacity = _capacity(s, cfg)

    gate_logits = xf.astype(jnp.float32) @ moe["router"]["w"]
    probs = jax.nn.softmax(gate_logits, axis=-1)
    dispatch_fn = (router_dispatch_priority if cfg.dispatch == "priority"
                   else router_dispatch)
    combine, aux = dispatch_fn(probs, cfg, capacity)          # [S, E, C]
    dispatch = (combine > 0).astype(cfg.dtype)
    kept = jnp.sum((combine > 0).astype(jnp.float32))
    drop = 1.0 - kept / (s * cfg.top_k)

    shard = _expert_sharding()
    constrain = (
        (lambda a: jax.lax.with_sharding_constraint(a, shard))
        if shard is not None else (lambda a: a)
    )

    # Token -> expert slots: [E, C, d] (GSPMD: all_to_all when sharded).
    expert_in = constrain(
        jnp.einsum("sec,sd->ecd", dispatch, xf.astype(cfg.dtype))
    )
    h = jnp.einsum("ecd,edf->ecf", expert_in,
                   moe["fc"]["w"].astype(cfg.dtype))
    h = jax.nn.gelu(h + moe["fc"]["b"][:, None].astype(cfg.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, moe["proj"]["w"].astype(cfg.dtype))
    out = constrain(out + moe["proj"]["b"][:, None].astype(cfg.dtype))
    # Expert slots -> tokens, combine-weighted (f32 for the residual add).
    yf = jnp.einsum("sec,ecd->sd", combine, out.astype(jnp.float32))
    return yf.reshape(b, t, d), aux, drop


def block_forward(block: Params, x: jax.Array, cfg: MoEConfig
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """gpt2.block_forward with the MoE MLP; returns (x, aux, drop)."""
    dtype = cfg.dtype
    attn_fn = gpt2.get_attention(cfg.attn_impl)
    b, t, d = x.shape
    h = cfg.n_head

    y = L.layernorm(block["ln_1"], x).astype(dtype)
    qkv = L.dense(block["attn"]["qkv"], y, dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    reshape = lambda a: a.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)
    out = attn_fn(reshape(q), reshape(k), reshape(v), True)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + L.dense(block["attn"]["proj"], out, dtype).astype(x.dtype)

    y = L.layernorm(block["ln_2"], x)
    y, aux, drop = moe_mlp(block["moe"], y, cfg)
    return x + y.astype(x.dtype), aux, drop


def apply_blocks(blocks: Params, x: jax.Array, cfg: MoEConfig
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (x, mean aux loss, mean capacity-drop fraction)."""
    body = block_forward
    if cfg.remat:
        body = jax.checkpoint(body, static_argnums=(2,))

    def scan_fn(carry, block):
        h, aux_sum, drop_sum = carry
        h, aux, drop = body(block, h, cfg)
        return (h, aux_sum + aux, drop_sum + drop), None

    (x, aux_sum, drop_sum), _ = jax.lax.scan(
        scan_fn,
        (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        blocks,
    )
    return x, aux_sum / cfg.n_layer, drop_sum / cfg.n_layer


def forward(params: Params, tokens: jax.Array, cfg: MoEConfig) -> jax.Array:
    x = gpt2.embed(params, tokens, cfg)
    x, _, _ = apply_blocks(params["blocks"], x, cfg)
    return gpt2.unembed(params, x, cfg)


def forward_with_monitor(params: Params, tokens: jax.Array, cfg: MoEConfig
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Same contract as gpt2.forward_with_monitor (pre-ln features +
    mean-logits signature) so the in-step detector works unchanged."""
    x = gpt2.embed(params, tokens, cfg)
    x, _, _ = apply_blocks(params["blocks"], x, cfg)
    normed = L.layernorm(params["ln_f"], x)
    logits = gpt2.project_logits(params, normed, cfg)
    mean_normed = jnp.mean(normed, axis=tuple(range(normed.ndim - 1)))
    mean_logits = gpt2.project_logits(params, mean_normed, cfg)
    return logits, x, mean_logits


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: MoEConfig
            ) -> jax.Array:
    loss = loss_with_monitor(params, batch, cfg)[0]
    return loss


def loss_with_monitor(params: Params, batch: Dict[str, jax.Array],
                      cfg: MoEConfig
                      ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                 Dict[str, jax.Array]]:
    """Same contract as gpt2.loss_with_monitor, with the Switch
    load-balance aux loss folded in (the apply_monitor + external-CE path
    cannot carry it), plus a 4th element: model-aux diagnostics
    ({"moe_drop_fraction": f32[]}) that the trusted step surfaces into
    StepMetrics.  The head — incl. the ``cfg.lm_head_chunk`` fused
    vocab-chunked path — is gpt2.head_loss_and_signature, shared so the
    two families cannot drift."""
    x = gpt2.embed(params, batch["input"], cfg)
    x, aux, drop = apply_blocks(params["blocks"], x, cfg)
    lm, mean_logits = gpt2.head_loss_and_signature(
        params, x, batch["target"], cfg
    )
    return (lm + cfg.aux_weight * aux, x, mean_logits,
            {"moe_drop_fraction": drop})


def moe_ep_specs(params: Params):
    """PartitionSpec tree for expert parallelism: expert-dim arrays shard on
    'expert' (leading axis after the stacked-layer axis), everything else
    replicated.  Feed to NamedSharding/device_put like gpt2_tp_specs."""
    from trustworthy_dl_tpu.core import sharding as shreg

    rules = shreg.rules_for("expert")

    def spec(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if "moe" in keys and "router" not in keys:
            # [L, E, ...]: layer axis replicated, expert axis sharded.
            return rules.partition_spec(
                shreg.LAYER, shreg.EXPERT, *([None] * (leaf.ndim - 2)))
        return rules.partition_spec()

    return jax.tree_util.tree_map_with_path(spec, params)


def num_params(params: Params) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# A dropless routed layer that is TOLD which experts it holds (one chip's
# share of an expert-parallel layer).  No mesh, no capacity: it stands apart
# from the capacity path above (ROADMAP D7).
# ---------------------------------------------------------------------------


def route_top_k(x: jax.Array, router: jax.Array, bias: jax.Array, k: int,
                normalise: bool = True, scaling: float = 1.0
                ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing over EVERY published expert: ``x [N, D]`` float32,
    ``router [D, E]``, the selection bias ``bias [E]`` -> the ``k`` experts
    with the largest ``sigmoid(x W) + bias`` a token, ``chosen i32[N, k]``,
    and their weights ``[N, k]``: the sigmoid scores themselves (the bias
    only selects), over their sum where ``normalise``, times ``scaling``.
    The product runs in float32 at ``HIGHEST``: a rounded score would move
    a near tie between two experts, and with it a whole expert's output."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weights * scaling


def held_experts(x: jax.Array, chosen: jax.Array, weights: jax.Array,
                 w_gate_up: jax.Array, w_down: jax.Array, first: int,
                 valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a routed layer, dropless: ``sum over the
    (token, expert) pairs whose expert e lies in [first, first + held) of
    weight * W_down[e](SiLU(W_gate[e] x) * W_up[e] x)``.  ``w_gate_up
    [held, D, 2F]`` (gate first), ``w_down [held, F, D]``, ``chosen`` and
    ``weights`` as :func:`route_top_k` gives them, ``valid bool[N]`` the
    tokens that count (padding otherwise).  What the absent experts would
    add is left out; no pair is dropped whatever the load.

    The pairs are sorted by expert and multiplied as grouped products
    (``ops.grouped_matmul``: each expert's weights meet only its own
    rows); the pairs of absent experts and of padding sort behind the last
    group, where no product is computed.  On one TPU chip the products
    are this repo's Pallas kernel, whose row tile follows ``N * k / held``
    (16 rows for a decode call, 128 for a chunk); on the CPU and in a
    program GSPMD partitions they are ``jax.lax.ragged_dot``, the same
    sums.  Returns ``(y [N, D] float32, pairs i32[held])``, the pairs
    each held expert took."""
    n, k = chosen.shape
    held = w_down.shape[0]
    width = w_down.shape[1]
    local = chosen - first
    here = (local >= 0) & (local < held)
    if valid is not None:
        here &= valid[:, None]
    group = jnp.where(here, local, held).reshape(-1)          # [N·k]
    order = jnp.argsort(group, stable=True)
    pairs = jnp.zeros(held + 1, jnp.int32).at[group].add(1)[:held]
    rows = x.astype(w_gate_up.dtype)[order // k]               # [N·k, D]
    h = grouped_matmul(rows, w_gate_up, pairs)
    a = jax.nn.silu(h[:, :width]) * h[:, width:]
    y = grouped_matmul(a.astype(w_down.dtype), w_down, pairs)
    # Rows behind the last group hold whatever the buffer held: select,
    # do not multiply by zero.
    live = jnp.arange(n * k) < jnp.sum(pairs)
    y = jnp.where(live[:, None], y * weights.reshape(-1)[order][:, None],
                  0.0)
    back = jnp.zeros(n * k, jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    return y[back].reshape(n, k, -1).sum(axis=1), pairs
