"""The Solar Open 2 family (``model_type: "solar_open2"``): no positional
term, RMSNorm, softmax attention layers with grouped K/V heads and a sigmoid
output gate at the ``gqa_layers``, gated-delta-rule (KDA) layers with a
recurrent state everywhere else, and in every layer a routed expert layer
(sigmoid scores, top-k, one shared expert) of which this chip HOLDS
``n_routed_experts`` of the ``n_routed_experts_published``, from
``first_expert``; an untied head over the slice of the vocabulary held.

It brings its own weights (made in bfloat16: the float32 tree of this size
does not fit beside its bfloat16 view, so every matrix is drawn in float32,
rounded once, and both sides read the same numbers) and its plain reference
``reference_solar_open2.py``.  The contract is the package's docstring; a
serving family, so no training entry.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.families import reference_solar_open2 as _reference
from benchmark.harness.families.reference_solar_open2 import chosen_tokens
# The program's description of this family, imported HERE and not inside
# ``model``: a program from before it existed then fails when the family is
# resolved, at once, and not after the weights are made.
from trustworthy_dl_tpu.models.decoder import DecoderConfig

__all__ = ["attention_layers", "chosen_tokens", "compute_dtype",
           "faulty_context", "make_weights", "model", "model_flops",
           "planted", "reply_logits", "sizes", "vocab"]

STD = 0.02
#: The planted faults (the family's own names; ``planted`` and
#: ``faulty_context`` say what each computes).
FAULTS = ("last_chunk_dropped", "kda_state_unwritten", "neighbour_experts",
          "neighbour_slot")


# -- the shapes ----------------------------------------------------------------


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """What shapes the model, under the keyword names the program's
    ``DecoderConfig`` takes.  The layer pattern is read from ``gqa_layers``
    and ``gqa_interval`` (one attention layer, then ``gqa_interval`` KDA
    layers, a period) and has to come out in whole periods."""
    layers = int(config["num_hidden_layers"])
    length = int(config["gqa_interval"]) + 1
    gqa = [int(i) for i in config["gqa_layers"]]
    if layers % length or gqa != list(range(0, layers, length)):
        raise ValueError(
            f"{layers} layers with attention at {gqa} are not whole "
            f"periods of {length}")
    linear = config["linear_attn_config"]
    if config.get("use_rope") or config.get("first_k_dense_replace"):
        raise ValueError("this family has no rotary term and no leading "
                         "dense layers")
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "period": ("attn",) + ("kda",) * (length - 1),
        "n_periods": layers // length,
        "q_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_head_dim": int(linear["head_dim"]),
        "conv_size": int(linear["short_conv_kernel_size"]),
        "kda_rank": int(linear["head_dim"]),
        "n_experts": int(config["n_routed_experts_published"]),
        "n_experts_held": int(config["n_routed_experts"]),
        "first_expert": int(config["first_expert"]),
        "experts_per_tok": int(config["num_experts_per_tok"]),
        "n_shared_experts": int(config["n_shared_experts"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "max_positions": int(config["max_position_embeddings"]),
    }


def vocab(config: Dict[str, Any]) -> int:
    """The ids traffic may draw from: the slice held here."""
    return int(config["vocab_size"])


def published_layers(config: Dict[str, Any]) -> int:
    """The depth the residual projections' scale is taken from: the
    published one where the file states it beside a reduced depth."""
    return int((config.get("published") or {}).get(
        "num_hidden_layers", config["num_hidden_layers"]))


# -- the weights ---------------------------------------------------------------


def _layer_shapes(s: Dict[str, Any], kind: str) -> Dict[str, Any]:
    p, d = s["n_periods"], s["hidden_size"]
    e, f = s["n_experts_held"], s["moe_intermediate_size"]
    shared = s["n_shared_experts"] * f
    out: Dict[str, Any] = {
        "norm1": (p, d), "norm2": (p, d),
        "moe": {"router": (p, d, s["n_experts"]),
                "router_bias": (p, s["n_experts"]),
                "w_gate_up": (p, e, d, 2 * f), "w_down": (p, e, f, d),
                "shared_gate_up": (p, d, 2 * shared),
                "shared_down": (p, shared, d)}}
    if kind == "attn":
        q, kv = s["q_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        out["attn"] = {"wq": (p, d, q), "wk": (p, d, kv), "wv": (p, d, kv),
                       "wg": (p, d, q), "wo": (p, q, d)}
    else:
        h, w = s["kda_heads"], s["kda_heads"] * s["kda_head_dim"]
        r = s["kda_rank"]
        out["kda"] = {"wq": (p, d, w), "wk": (p, d, w), "wv": (p, d, w),
                      "conv": (p, s["conv_size"], 3 * w),
                      "f_a": (p, d, r), "f_b": (p, r, w), "a_log": (p, h),
                      "dt_bias": (p, w), "w_beta": (p, d, h),
                      "g_a": (p, d, r), "g_b": (p, r, w),
                      "o_norm": (p, s["kda_head_dim"]), "wo": (p, w, d)}
    return out


def _shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed": (s["vocab_size"], s["hidden_size"]),
            "head": (s["hidden_size"], s["vocab_size"]),
            "final_norm": (s["hidden_size"],),
            "periods": tuple(_layer_shapes(s, kind) for kind in s["period"])}


def _draw(key: jax.Array, name: str, shape: Tuple[int, ...], depth: int
          ) -> jax.Array:
    """One leaf.  Matrices N(0, 0.02) (the residual projections over
    sqrt(2 * depth)), drawn in float32 and rounded ONCE to bfloat16; what
    stays float32: norm scales 1, ``a_log`` = log U(1, 16) a head and
    ``dt_bias`` = softplus^-1 of exp(U(log 0.001, log 0.1)) a channel (as
    FLA draws them), the router's selection bias N(0, 0.02).  The
    convolution's taps U(-1/2, 1/2) (PyTorch's Conv1d default at 4 taps, as
    FLA's short convolution has it)."""
    f32 = jnp.float32
    if name in ("norm1", "norm2", "final_norm", "o_norm"):
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
    std = STD
    if name in ("wo", "w_down", "shared_down"):
        std = STD / math.sqrt(2 * depth)
    return (jax.random.normal(key, shape, f32) * std).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key: jax.Array, shape: Tuple[Tuple[str, Any], ...], depth: int):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(dict(shape)),
        is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        _draw(k, path[-1].key, leaf, depth)
        for k, (path, leaf) in zip(keys, leaves)])


def make_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The tree in the layout the program's decoder reads
    (``models/decoder.py``), from the seed, in ONE jitted call.  The same
    seed gives the same weights."""
    return _make(jax.random.PRNGKey(int(seed) % (1 << 63)),
                 tuple(sorted(sizes(config).items())),
                 published_layers(config))


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


def _with(params: Dict[str, Any], position: int, group: str,
          leaves: Dict[str, Any]) -> Dict[str, Any]:
    """``params`` with leaves of one group of one position of the period
    replaced."""
    periods = list(params["periods"])
    layer = dict(periods[position])
    layer[group] = dict(layer[group], **leaves)
    periods[position] = layer
    return dict(params, periods=tuple(periods))


def planted(params: Dict[str, Any], fault: str, config: Dict[str, Any]
            ) -> Dict[str, Any]:
    """The weights a planted fault computes with.

    ``kda_state_unwritten``: the middle KDA layer's state is never written:
    its value projection reads 0, so every ``k v^T`` is 0 and the state
    stays 0.  ``neighbour_experts``: every held expert computes with its
    neighbour's weights (expert ``e`` reads ``e + 1``'s: the grouped
    product's offsets off by one).  The two faults of the context
    (``faulty_context``) and no fault compute with ``params``."""
    if fault == "kda_state_unwritten":
        shape = sizes(config)
        kda = [(p, j) for p in range(shape["n_periods"])
               for j, kind in enumerate(shape["period"]) if kind == "kda"]
        period, position = kda[len(kda) // 2]
        wv = params["periods"][position]["kda"]["wv"]
        return _with(params, position, "kda",
                     {"wv": wv.at[period].set(0)})
    if fault == "neighbour_experts":
        for position in range(len(params["periods"])):
            moe = params["periods"][position]["moe"]
            params = _with(params, position, "moe", {
                name: jnp.roll(moe[name], -1, axis=1)
                for name in ("w_gate_up", "w_down")})
        return params
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return params


def faulty_context(fault: str, prompt: np.ndarray, neighbour: np.ndarray,
                   chunk: int) -> np.ndarray:
    """The prompt a faulty program's reply was conditioned on:
    ``last_chunk_dropped``, the prompt's last engine chunk never run (its
    K/V rows never written, the state never advanced over it);
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
    q, kv = s["q_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    w, r = s["kda_heads"] * s["kda_head_dim"], s["kda_rank"]
    return {
        "attn": 3 * d * q + 2 * d * kv,
        "kda": 4 * d * w + 2 * (d * r + r * w) + d * s["kda_heads"]
        + s["conv_size"] * 3 * w,
        "router": d * s["n_experts"],
        "shared": 3 * d * f * s["n_shared_experts"],
        "expert": 3 * d * f,
    }


def model_flops(config: Dict[str, Any], fed_tokens: int, sampled: int
                ) -> float:
    """The matrix products of a forward over ``fed_tokens`` tokens of which
    ``sampled`` also go through the head, 2 a multiply-add: a token fed, the
    attention, KDA, router and shared-expert weights of every layer plus
    ``experts per token x held / published`` routed experts a layer (what a
    balanced router sends here; ``moe_held_pairs_per_token`` reads what it
    did send); a token sampled, the head.  The state's products and
    attention's are left out, so a share of the peak this gives is a lower
    bound."""
    s, part = sizes(config), layer_weights(config)
    routed = s["experts_per_tok"] * s["n_experts_held"] / s["n_experts"]
    per_period = sum(part[kind] for kind in s["period"]) + len(
        s["period"]) * (part["router"] + part["shared"]
                        + routed * part["expert"])
    body = s["n_periods"] * per_period
    return 2.0 * body * fed_tokens \
        + 2.0 * s["hidden_size"] * s["vocab_size"] * sampled


def attention_layers(config: Dict[str, Any]
                     ) -> List[Tuple[int, int, int, int]]:
    """The softmax attention layers alone keep keys and values: one group,
    ``(layers, query heads, K/V heads, head width)``; a KDA layer has no
    paged attention and is in no entry."""
    s = sizes(config)
    return [(s["n_periods"] * s["period"].count("attn"), s["q_heads"],
             s["kv_heads"], s["head_dim"])]
