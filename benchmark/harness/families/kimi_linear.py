"""The Kimi Linear family (``model_type: "kimi_linear"``): no positional
term (``mla_use_nope``), RMSNorm, latent attention (MLA, no query
compression) at the ``full_attn_layers`` whose cache is ONE row of
``kv_lora_rank + qk_rope_head_dim`` values a position, gated-delta-rule (KDA)
layers with a recurrent state at the ``kda_layers`` (both lists 1-based, as
the source spells them), ``first_k_dense_replace`` leading layers whose
second half is a dense SiLU-gated MLP, and in every other layer a routed
expert layer (sigmoid scores, top-k, one shared expert) of which this chip
HOLDS ``num_experts`` of the ``deployment.num_experts_published``, from
``deployment.first_expert``; an untied head over the slice of the vocabulary
held.

It brings its own weights (made in bfloat16: the float32 tree of this size,
17 GB, fits no chip, so every matrix is drawn in float32, rounded once, and
both sides read the same numbers) and its plain reference
``reference_kimi_linear.py``.  The contract is the package's docstring; a
serving family, so no training entry.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.families import reference_kimi_linear as _reference
from benchmark.harness.families.reference_kimi_linear import chosen_tokens
# The program's description of this family, imported HERE and not inside
# ``model``: a program from before it could describe latent layers then
# fails when the family is resolved, at once, and not after the weights are
# made.
from trustworthy_dl_tpu.models.decoder import MLA, DecoderConfig

__all__ = ["attention_layers", "chosen_tokens", "compute_dtype",
           "faulty_context", "latent_layers", "make_weights", "model",
           "model_flops", "planted", "reply_logits", "sizes", "vocab"]

STD = 0.02
#: The planted faults (the family's own names; ``planted`` and
#: ``faulty_context`` say what each computes).
FAULTS = ("last_chunk_dropped", "kda_state_unwritten", "neighbour_experts",
          "neighbour_slot", "latent_rope_unwritten")


# -- the shapes ----------------------------------------------------------------


def layer_kinds(config: Dict[str, Any]) -> Tuple[str, ...]:
    """The kind of each layer held, first to last, from the two 1-based
    lists of ``linear_attn_config``."""
    linear = config["linear_attn_config"]
    kda = {int(i) for i in linear["kda_layers"]}
    full = {int(i) for i in linear["full_attn_layers"]}
    layers = int(config["num_hidden_layers"])
    if kda & full or kda | full != set(range(1, layers + 1)):
        raise ValueError(
            f"kda_layers {sorted(kda)} and full_attn_layers {sorted(full)} "
            f"do not name each of the {layers} layers once")
    return tuple("kda" if i in kda else MLA for i in range(1, layers + 1))


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """What shapes the model, under the keyword names the program's
    ``DecoderConfig`` takes.  The ``first_k_dense_replace`` leading layers
    are ``lead``; what follows has to come out in whole periods (the
    shortest pattern that tiles it)."""
    kinds = layer_kinds(config)
    dense = int(config["first_k_dense_replace"])
    lead, rest = kinds[:dense], kinds[dense:]
    length = next((n for n in range(1, len(rest) + 1)
                   if len(rest) % n == 0
                   and rest == rest[:n] * (len(rest) // n)), 0)
    if not length:
        raise ValueError(f"no layer follows the {dense} leading ones")
    if config.get("q_lora_rank") or not config.get("mla_use_nope") \
            or int(config["moe_layer_freq"]) != 1 \
            or int(config["num_expert_group"]) != 1:
        raise ValueError(
            "this family has no query compression, no rotary term, experts "
            "in every layer after the leading ones and one expert group")
    linear = config["linear_attn_config"]
    deployment = config["deployment"]
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "lead": lead,
        "intermediate_size": int(config["intermediate_size"]),
        "period": rest[:length],
        "n_periods": len(rest) // length,
        "q_heads": int(config["num_attention_heads"]),
        # No layer keeps per-head K and V: the description's two fields for
        # them are filled with what divides and what nothing reads.
        "kv_heads": 1,
        "head_dim": 0,
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_head_dim": int(linear["head_dim"]),
        "conv_size": int(linear["short_conv_kernel_size"]),
        "kda_rank": int(linear["head_dim"]),
        # No kda_allow_neg_eigval in the source: FLA's default, false.
        "kda_beta_scale": 1.0,
        "n_experts": int(deployment["num_experts_published"]),
        "n_experts_held": int(config["num_experts"]),
        "first_expert": int(deployment["first_expert"]),
        "experts_per_tok": int(config["num_experts_per_token"]),
        "n_shared_experts": int(config["num_shared_experts"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "norm_topk_prob": bool(config["moe_renormalize"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "max_positions": int(config["model_max_length"]),
    }


def latent_query_std(config: Dict[str, Any]) -> float:
    """The standard deviation the latent layers' ``W_q`` is drawn at."""
    return float(config.get("latent_query_std", STD))


def vocab(config: Dict[str, Any]) -> int:
    """The ids traffic may draw from: the slice held here."""
    return int(config["vocab_size"])


def published_layers(config: Dict[str, Any]) -> int:
    """The depth the residual projections' scale is taken from: the
    published one where the file states it beside a reduced depth."""
    return int((config.get("published") or {}).get(
        "num_hidden_layers", config["num_hidden_layers"]))


# -- the weights ---------------------------------------------------------------


def _layer_shapes(s: Dict[str, Any], kind: str, stack: Tuple[int, ...],
                  dense: bool) -> Dict[str, Any]:
    """One layer's leaves, each under the leading axes ``stack`` (the
    periods, or none for a leading layer)."""
    d = s["hidden_size"]
    out: Dict[str, Any] = {"norm1": (d,), "norm2": (d,)}
    if dense:
        width = s["intermediate_size"]
        out["mlp"] = {"gate_up": (d, 2 * width), "down": (width, d)}
    else:
        e, f = s["n_experts_held"], s["moe_intermediate_size"]
        shared = s["n_shared_experts"] * f
        out["moe"] = {"router": (d, s["n_experts"]),
                      "router_bias": (s["n_experts"],),
                      "w_gate_up": (e, d, 2 * f), "w_down": (e, f, d),
                      "shared_gate_up": (d, 2 * shared),
                      "shared_down": (shared, d)}
    if kind == MLA:
        heads, rank = s["q_heads"], s["kv_lora_rank"]
        nope, rope = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
        value = s["v_head_dim"]
        out["mla"] = {"wq": (d, heads * (nope + rope)),
                      "w_kv_a": (d, rank + rope), "kv_norm": (rank,),
                      "w_kv_b": (rank, heads * (nope + value)),
                      "wo": (heads * value, d)}
    else:
        h, w = s["kda_heads"], s["kda_heads"] * s["kda_head_dim"]
        r = s["kda_rank"]
        out["kda"] = {"wq": (d, w), "wk": (d, w), "wv": (d, w),
                      "conv": (s["conv_size"], 3 * w),
                      "f_a": (d, r), "f_b": (r, w), "a_log": (h,),
                      "dt_bias": (w,), "w_beta": (d, h),
                      "g_a": (d, r), "g_b": (r, w),
                      "o_norm": (s["kda_head_dim"],), "wo": (w, d)}
    return jax.tree_util.tree_map(lambda leaf: stack + leaf, out,
                                  is_leaf=_is_shape)


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and (not x or isinstance(x[0], int))


def _shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed": (s["vocab_size"], s["hidden_size"]),
            "head": (s["hidden_size"], s["vocab_size"]),
            "final_norm": (s["hidden_size"],),
            "lead": tuple(_layer_shapes(s, kind, (), True)
                          for kind in s["lead"]),
            "periods": tuple(_layer_shapes(s, kind, (s["n_periods"],), False)
                             for kind in s["period"])}


def _draw(key: jax.Array, name: str, shape: Tuple[int, ...], depth: int,
          std: float = STD) -> jax.Array:
    """One leaf.  Matrices N(0, 0.02) (the residual projections over
    sqrt(2 * depth)), drawn in float32 and rounded ONCE to bfloat16; what
    stays float32: norm scales 1, ``a_log`` = log U(1, 16) a head and
    ``dt_bias`` = softplus^-1 of exp(U(log 0.001, log 0.1)) a channel (as
    FLA draws them), the router's selection bias N(0, 0.02).  The
    convolution's taps U(-1/2, 1/2) (PyTorch's Conv1d default at 4 taps, as
    FLA's short convolution has it)."""
    f32 = jnp.float32
    if name in ("norm1", "norm2", "final_norm", "o_norm", "kv_norm"):
        return jnp.ones(shape, f32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(0.001),
                                        math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "router_bias":
        return jax.random.normal(key, shape, f32) * STD
    if name == "conv":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5).astype(
            jnp.bfloat16)
    if name in ("wo", "w_down", "shared_down", "down"):
        std = STD / math.sqrt(2 * depth)
    return (jax.random.normal(key, shape, f32) * std).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key: jax.Array, shape: Tuple[Tuple[str, Any], ...], depth: int,
          query_std: float):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(dict(shape)), is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))

    def std_of(path) -> float:
        latent_q = path[-1].key == "wq" and path[-2].key == MLA
        return query_std if latent_q else STD

    return jax.tree_util.tree_unflatten(treedef, [
        _draw(k, path[-1].key, leaf, depth, std_of(path))
        for k, (path, leaf) in zip(keys, leaves)])


def make_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The tree in the layout the program's decoder reads
    (``models/decoder.py``), from the seed, in ONE jitted call.  The same
    seed gives the same weights."""
    return _make(jax.random.PRNGKey(int(seed) % (1 << 63)),
                 tuple(sorted(sizes(config).items())),
                 published_layers(config), latent_query_std(config))


# -- the program's side --------------------------------------------------------


def model(config: Dict[str, Any]) -> DecoderConfig:
    return DecoderConfig(**sizes(config))


def compute_dtype(config: Dict[str, Any]) -> Any:
    return model(config).dtype


# -- the plain reference -------------------------------------------------------


def reply_logits(params: Dict[str, Any], prompt: Any, reply: Any,
                 config: Dict[str, Any], max_reply: int,
                 precision: str = "f32") -> Any:
    max_seq = int(config["deployment"]["serve_config"]["max_seq"])
    return _reference.reply_logits(params, prompt, reply, sizes(config),
                                   max_seq, max_reply, precision)


def _with(params: Dict[str, Any], where: Tuple[str, int], group: str,
          leaves: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with leaves of one group of one leading layer or one
    position of the period (``where``: ``("lead", j)`` or ``("periods",
    j)``) replaced."""
    stack, position = where
    layers = list(params[stack])
    layer = dict(layers[position])
    layer[group] = dict(layer[group], **leaves)
    layers[position] = layer
    return dict(params, **{stack: tuple(layers)})


def kda_layers(config: Dict[str, Any]) -> List[Tuple[Tuple[str, int], Any]]:
    """Where each KDA layer's weights lie, first to last: ``(where, index
    into the leading axis or None)``."""
    shape = sizes(config)
    out: List[Tuple[Tuple[str, int], Any]] = [
        (("lead", j), None) for j, kind in enumerate(shape["lead"])
        if kind == "kda"]
    out += [(("periods", j), p) for p in range(shape["n_periods"])
            for j, kind in enumerate(shape["period"]) if kind == "kda"]
    return out


def planted(params: Dict[str, Any], fault: str, config: Dict[str, Any]
            ) -> Dict[str, Any]:
    """The weights a planted fault computes with.

    ``kda_state_unwritten``: the middle KDA layer's state is never written:
    its value projection reads 0, so every ``k v^T`` is 0 and the state
    stays 0.  ``neighbour_experts``: every held expert computes with its
    neighbour's weights (expert ``e`` reads ``e + 1``'s: the grouped
    product's offsets off by one), planted as the reference's
    ``held_shift`` (the routing weights rolled the other way: the same
    sums, without a second copy of the experts).  ``latent_rope_unwritten``: the last
    ``qk_rope_head_dim`` columns of every latent row are never written (they
    read 0: the pool's padding), so ``k_r`` is 0 at every cached position
    and the scores lose ``q_r . k_r``.  The two faults of the context
    (``faulty_context``) and no fault compute with ``params``."""
    if fault == "kda_state_unwritten":
        layers = kda_layers(config)
        where, index = layers[len(layers) // 2]
        wv = params[where[0]][where[1]]["kda"]["wv"]
        return _with(params, where, "kda", {
            "wv": jnp.zeros_like(wv) if index is None
            else wv.at[index].set(0)})
    if fault == "neighbour_experts":
        shift = jnp.ones((sizes(config)["n_periods"],), jnp.int32)
        for position in range(len(params["periods"])):
            params = _with(params, ("periods", position), "moe",
                           {"held_shift": shift})
        return params
    if fault == "latent_rope_unwritten":
        shape = sizes(config)
        rank = shape["kv_lora_rank"]
        for stack, kinds in (("lead", shape["lead"]),
                             ("periods", shape["period"])):
            for position, kind in enumerate(kinds):
                if kind == MLA:
                    w = params[stack][position]["mla"]["w_kv_a"]
                    params = _with(params, (stack, position), "mla", {
                        "w_kv_a": w.at[..., rank:].set(0)})
        return params
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return params


def faulty_context(fault: str, prompt: np.ndarray, neighbour: np.ndarray,
                   chunk: int) -> np.ndarray:
    """The prompt a faulty program's reply was conditioned on:
    ``last_chunk_dropped``, the prompt's last engine chunk never run (its
    latent rows never written, the state never advanced over it);
    ``neighbour_slot``, the slot reading its neighbour's blocks AND state
    (the next sample's prompt)."""
    if fault == "last_chunk_dropped":
        last = len(prompt) - chunk * ((len(prompt) - 1) // chunk)
        return prompt[:len(prompt) - last]
    if fault == "neighbour_slot":
        return np.resize(neighbour, len(prompt))
    return prompt


# -- the work ------------------------------------------------------------------


def layer_weights(config: Dict[str, Any]) -> Dict[str, int]:
    """Matrix elements of ONE layer's parts at the configuration's sizes
    (what the by-hand tests and ``model_flops`` count from)."""
    s = sizes(config)
    d, f = s["hidden_size"], s["moe_intermediate_size"]
    heads, rank = s["q_heads"], s["kv_lora_rank"]
    nope, rope, value = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                         s["v_head_dim"])
    w, r = s["kda_heads"] * s["kda_head_dim"], s["kda_rank"]
    return {
        "mla": d * heads * (nope + rope) + d * (rank + rope)
        + rank * heads * (nope + value) + heads * value * d,
        "kda": 4 * d * w + 2 * (d * r + r * w) + d * s["kda_heads"]
        + s["conv_size"] * 3 * w,
        "dense": 3 * d * s["intermediate_size"],
        "router": d * s["n_experts"],
        "shared": 3 * d * f * s["n_shared_experts"],
        "expert": 3 * d * f,
    }


def model_flops(config: Dict[str, Any], fed_tokens: int, sampled: int
                ) -> float:
    """The matrix products of a forward over ``fed_tokens`` tokens of which
    ``sampled`` also go through the head, 2 a multiply-add: a token fed, the
    mixing weights of every layer (latent attention's ``W_kb`` once, as the
    expanded form reads it), the dense MLP of each leading layer and, in
    every other layer, the router, the shared expert and ``experts per token
    x held / published`` routed experts (what a balanced router sends here;
    ``moe_held_pairs_per_token`` reads what it did send); a token sampled,
    the head.  The state's products and attention's are left out, so a
    share of the peak this gives is a lower bound."""
    s, part = sizes(config), layer_weights(config)
    routed = s["experts_per_tok"] * s["n_experts_held"] / s["n_experts"]
    kinds = s["lead"] + s["period"] * s["n_periods"]
    body = sum(part[kind] for kind in kinds) \
        + len(s["lead"]) * part["dense"] \
        + s["n_periods"] * len(s["period"]) * (
            part["router"] + part["shared"] + routed * part["expert"])
    return 2.0 * body * fed_tokens \
        + 2.0 * s["hidden_size"] * s["vocab_size"] * sampled


def latent_layers(config: Dict[str, Any]
                  ) -> List[Tuple[int, int, int, int, int, int]]:
    """``(layers, heads, nope, rope, value, rank)``, one entry a group of
    latent layers that share a shape: what ``harness/latent_readers.py``
    counts the latent kernels' work from."""
    s = sizes(config)
    kinds = s["lead"] + s["period"] * s["n_periods"]
    return [(kinds.count(MLA), s["q_heads"], s["qk_nope_head_dim"],
             s["qk_rope_head_dim"], s["v_head_dim"], s["kv_lora_rank"])]


def attention_layers(config: Dict[str, Any]
                     ) -> List[Tuple[int, int, int, int]]:
    """No entry: a latent layer keeps ONE row a position whose width is not
    a head's and whose values are its own first lanes, so the paged
    rooflines' counts (one head width, V's bytes again) do not fit it; the
    latent readers (``harness/latent_readers.py``) count it by its own
    arithmetic.  A KDA layer has no paged attention."""
    return []
