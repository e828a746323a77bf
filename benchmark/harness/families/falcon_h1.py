"""The Falcon-H1 family (``model_type: "falcon_h1"``): every layer a
PARALLEL hybrid, a Mamba-2 mixer and rotary grouped-query attention side by
side on one normed input, their outputs added, then a dense SiLU-gated MLP;
RMSNorm; muP multipliers on the embedding, the key, each mixer's input and
output, the five parts of the mixer's input projection, the MLP and the
head; an untied head over the whole vocabulary.

It brings its own weights (made in bfloat16: the float32 tree of the cell's
size, 17.6 GB, fits no chip, so every matrix is drawn in float32, rounded
once, and both sides read the same numbers) and its plain reference
``reference_falcon_h1.py``.  The contract is the package's docstring; a
serving family, so no training entry.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.families import reference_falcon_h1 as _reference
from benchmark.harness.families.reference_falcon_h1 import chosen_tokens
# The program's description of this family, imported HERE and not inside
# ``model``: a program from before it could describe hybrid layers then
# fails when the family is resolved, at once, and not after the weights are
# made.
from trustworthy_dl_tpu.models.decoder import HYBRID, DecoderConfig

__all__ = ["attention_layers", "chosen_tokens", "compute_dtype",
           "faulty_context", "make_weights", "model", "model_flops",
           "planted", "reply_logits", "sizes", "ssm_layers", "vocab"]

STD = 0.02
#: The most bytes one float32 draw inside ``make_weights`` may take: a
#: larger matrix is drawn in blocks of rows.
DRAW_BYTES = 128 << 20
#: The planted faults (the family's own names; ``planted`` and
#: ``faulty_context`` say what each computes).
FAULTS = ("last_chunk_dropped", "ssm_state_unwritten", "ssm_state_reset",
          "conv_tail_unwritten", "rope_position_shifted", "neighbour_slot")


# -- the shapes ----------------------------------------------------------------


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """What shapes the model, under the keyword names the program's
    ``DecoderConfig`` takes.  A period is ONE hybrid layer."""
    heads, width = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if heads * width != int(config["mamba_d_ssm"]):
        raise ValueError(f"mamba_d_ssm {config['mamba_d_ssm']} is not "
                         f"{heads} heads of {width}")
    if config.get("attn_layer_indices") is not None \
            or config.get("mamba_norm_before_gate") \
            or not config.get("mamba_rms_norm") \
            or not config.get("mamba_conv_bias") \
            or config.get("mamba_proj_bias") or config.get("attention_bias") \
            or config.get("mlp_bias") or config.get("tie_word_embeddings") \
            or config.get("rope_scaling") is not None:
        raise ValueError(
            "this family has attention beside the mixer in every layer, the "
            "gate before the mixer's group norm, a convolution bias and no "
            "other bias, unscaled rotary positions and an untied head")
    layers = int(config["num_hidden_layers"])
    return {
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "period": (HYBRID,),
        "n_periods": layers,
        "intermediate_size": int(config["intermediate_size"]),
        "q_heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "attn_gate": False,
        "mamba_heads": heads,
        "mamba_head_dim": width,
        "mamba_state": int(config["mamba_d_state"]),
        "mamba_groups": int(config["mamba_n_groups"]),
        "mamba_block": int(config["mamba_chunk_size"]),
        "conv_size": int(config["mamba_d_conv"]),
        # No KDA layer and no experts: the description's fields for them
        # are filled with what nothing reads.
        "kda_heads": 0, "kda_head_dim": 0, "kda_rank": 0,
        "n_experts": 0, "n_experts_held": 0, "first_expert": 0,
        "experts_per_tok": 0, "n_shared_experts": 0,
        "moe_intermediate_size": 0,
        "norm_eps": float(config["rms_norm_eps"]),
        "max_positions": int(config["max_position_embeddings"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "lm_head_multiplier": float(config["lm_head_multiplier"]),
        "attn_in_multiplier": float(config["attention_in_multiplier"]),
        "attn_out_multiplier": float(config["attention_out_multiplier"]),
        "key_multiplier": float(config["key_multiplier"]),
        "ssm_in_multiplier": float(config["ssm_in_multiplier"]),
        "ssm_out_multiplier": float(config["ssm_out_multiplier"]),
        "ssm_multipliers": tuple(float(m)
                                 for m in config["ssm_multipliers"]),
        "mlp_multipliers": tuple(float(m)
                                 for m in config["mlp_multipliers"]),
    }


def weight_stds(config: Dict[str, Any]) -> Dict[str, float]:
    """The matrices the file's ``weight_std`` names (by their leaf names:
    ``wq``, ``w_in``, ``wo``, ...), each with the standard deviation it is
    drawn at, a residual projection's too (not divided by the depth)."""
    return {name: float(std)
            for name, std in (config.get("weight_std") or {}).items()}


def vocab(config: Dict[str, Any]) -> int:
    """The ids traffic may draw from: the whole vocabulary."""
    return int(config["vocab_size"])


def published_layers(config: Dict[str, Any]) -> int:
    """The depth the residual projections' scale is taken from: the
    published one where the file states it beside a reduced depth."""
    return int((config.get("published") or {}).get(
        "num_hidden_layers", config["num_hidden_layers"]))


# -- the weights ---------------------------------------------------------------


def _layer_shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    """One layer's leaves, each under the leading axis of the periods."""
    p, d = s["n_periods"], s["hidden_size"]
    heads, kv, hd = s["q_heads"], s["kv_heads"], s["head_dim"]
    inner = s["mamba_heads"] * s["mamba_head_dim"]
    ch = inner + 2 * s["mamba_groups"] * s["mamba_state"]
    m = s["mamba_heads"]
    f = s["intermediate_size"]
    return {
        "norm1": (p, d), "norm2": (p, d),
        "attn": {"wq": (p, d, heads * hd), "wk": (p, d, kv * hd),
                 "wv": (p, d, kv * hd), "wo": (p, heads * hd, d)},
        "mamba": {"w_in": (p, d, inner + ch + m),
                  "conv": (p, s["conv_size"], ch), "conv_bias": (p, ch),
                  "dt_bias": (p, m), "a_log": (p, m), "d_skip": (p, m),
                  "gate_norm": (p, inner), "w_out": (p, inner, d)},
        "mlp": {"gate_up": (p, d, 2 * f), "down": (p, f, d)},
    }


def _shapes(s: Dict[str, Any]) -> Dict[str, Any]:
    return {"embed": (s["vocab_size"], s["hidden_size"]),
            "head": (s["hidden_size"], s["vocab_size"]),
            "final_norm": (s["hidden_size"],),
            "periods": (_layer_shapes(s),)}


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple) and (not x or isinstance(x[0], int))


def _normal(key: jax.Array, shape: Tuple[int, ...], std: float
            ) -> jax.Array:
    """N(0, std) in bfloat16, drawn in float32 in as many blocks of rows as
    keep each draw under ``DRAW_BYTES``."""
    rows, cols = math.prod(shape[:-1]), shape[-1]
    blocks = next(n for n in range(1, rows + 1)
                  if rows % n == 0 and rows // n * cols * 4 <= DRAW_BYTES)
    draw = lambda k, s: (jax.random.normal(k, s, jnp.float32) * std).astype(
        jnp.bfloat16)
    if blocks == 1:
        return draw(key, shape)
    out = jax.lax.map(lambda k: draw(k, (rows // blocks, cols)),
                      jax.random.split(key, blocks))
    return out.reshape(shape)


def _draw(key: jax.Array, name: str, shape: Tuple[int, ...], depth: int,
          std: Any) -> jax.Array:
    """One leaf.  Matrices N(0, ``std``) (the residual projections over
    sqrt(2 * depth) where ``std`` is not their own), drawn in float32 and
    rounded ONCE to bfloat16; what
    stays float32: norm scales 1, ``d_skip`` 1, ``a_log`` = log U(1, 16) a
    head and ``dt_bias`` = softplus^-1 of exp(U(log 0.001, log 0.1)) a head
    (as the Mamba-2 reference draws them), the convolution's bias U(-1/2,
    1/2).  Its taps U(-1/2, 1/2), in bfloat16 (PyTorch's Conv1d default at
    4 taps)."""
    f32 = jnp.float32
    if name in ("norm1", "norm2", "final_norm", "gate_norm", "d_skip"):
        return jnp.ones(shape, f32)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(0.001),
                                        math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_bias":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5)
    if name == "conv":
        return jax.random.uniform(key, shape, f32, -0.5, 0.5).astype(
            jnp.bfloat16)
    if std is None:
        std = STD / math.sqrt(2 * depth) if name in ("wo", "w_out", "down") \
            else STD
    return _normal(key, shape, std)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key: jax.Array, shape: Tuple[Tuple[str, Any], ...], depth: int,
          stds: Tuple[Tuple[str, float], ...]):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(dict(shape)), is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))

    def std_of(path) -> Any:
        return dict(stds).get(path[-1].key)

    return jax.tree_util.tree_unflatten(treedef, [
        _draw(k, path[-1].key, leaf, depth, std_of(path))
        for k, (path, leaf) in zip(keys, leaves)])


def make_weights(seed: int, config: Dict[str, Any]) -> Dict[str, Any]:
    """The tree in the layout the program's decoder reads
    (``models/decoder.py``), from the seed, in ONE jitted call, bfloat16
    but for the leaves ``_draw`` keeps float32.  The same seed gives the
    same weights."""
    return _make(jax.random.PRNGKey(int(seed) % (1 << 63)),
                 tuple(sorted(sizes(config).items())),
                 published_layers(config),
                 tuple(sorted(weight_stds(config).items())))


# -- the program's side --------------------------------------------------------


def model(config: Dict[str, Any]) -> DecoderConfig:
    return DecoderConfig(**sizes(config))


def compute_dtype(config: Dict[str, Any]) -> Any:
    return model(config).dtype


# -- the plain reference -------------------------------------------------------


def reply_logits(params: Dict[str, Any], prompt: Any, reply: Any,
                 config: Dict[str, Any], max_reply: int,
                 precision: str = "f32") -> Any:
    deployment = config["deployment"]
    return _reference.reply_logits(
        params, prompt, reply, sizes(config),
        int(deployment["serve_config"]["max_seq"]), max_reply, precision,
        int(deployment["prefill_chunk_positions"]))


def _hooked(params: Dict[str, Any], group: str, name: str, middle: bool
            ) -> Dict[str, Any]:
    """``params`` with a fault hook ``name`` in ``group`` of every layer,
    True for the middle layer alone or for every layer."""
    (layer,) = params["periods"]
    n = layer["norm1"].shape[0]
    hook = jnp.zeros((n,), bool).at[n // 2].set(True) if middle \
        else jnp.ones((n,), bool)
    return dict(params, periods=(dict(layer, **{
        group: dict(layer[group], **{name: hook})}),))


def planted(params: Dict[str, Any], fault: str, config: Dict[str, Any]
            ) -> Dict[str, Any]:
    """The weights a planted fault computes with.

    ``ssm_state_unwritten``: the middle layer's decode step never writes its
    state row back (as when the step's in-place write is lost): every decode
    step reads the state the prompt's last chunk left.
    ``ssm_state_reset``: the middle layer's state row is never written at
    all (every chunk call and every decode step starts from the zero state
    the slot was admitted with).
    ``conv_tail_unwritten``: no layer's convolution tail is ever written
    (a chunk's first rows, and every decode step, see no row of an earlier
    call).  ``rope_position_shifted``: every cached key rotated at
    its position + 1 (what only the rotary path can get wrong).  The two
    faults of the context (``faulty_context``) and no fault compute with
    ``params``."""
    if fault == "ssm_state_unwritten":
        return _hooked(params, "mamba", "state_unwritten", middle=True)
    if fault == "ssm_state_reset":
        return _hooked(params, "mamba", "state_reset", middle=True)
    if fault == "conv_tail_unwritten":
        return _hooked(params, "mamba", "conv_unwritten", middle=False)
    if fault == "rope_position_shifted":
        (layer,) = params["periods"]
        n = layer["norm1"].shape[0]
        return dict(params, periods=(dict(layer, attn=dict(
            layer["attn"], key_shift=jnp.ones((n,), jnp.int32))),))
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return params


def faulty_context(fault: str, prompt: np.ndarray, neighbour: np.ndarray,
                   chunk: int) -> np.ndarray:
    """The prompt a faulty program's reply was conditioned on:
    ``last_chunk_dropped``, the prompt's last engine chunk never run (its
    K/V never written, the state never advanced over it);
    ``neighbour_slot``, the slot reading its neighbour's blocks AND state
    row (the next sample's prompt)."""
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
    d = s["hidden_size"]
    q, kv = s["q_heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    inner = s["mamba_heads"] * s["mamba_head_dim"]
    ch = inner + 2 * s["mamba_groups"] * s["mamba_state"]
    return {
        "attention": d * (q + 2 * kv) + q * d,
        "mamba": d * (inner + ch + s["mamba_heads"]) + inner * d,
        "mlp": 3 * d * s["intermediate_size"],
    }


def model_flops(config: Dict[str, Any], fed_tokens: int, sampled: int
                ) -> float:
    """The matrix products of a forward over ``fed_tokens`` tokens of which
    ``sampled`` also go through the head, 2 a multiply-add: a token fed,
    both mixers' projections and the MLP of every layer; a token sampled,
    the head.  The recurrence's products and attention's are left out, so a
    share of the peak this gives is a lower bound."""
    s = sizes(config)
    body = s["n_periods"] * sum(layer_weights(config).values())
    return 2.0 * body * fed_tokens \
        + 2.0 * s["hidden_size"] * s["vocab_size"] * sampled


def attention_layers(config: Dict[str, Any]
                     ) -> List[Tuple[int, int, int, int]]:
    """``(layers, query heads, K/V heads, head width)``: every layer's
    attention, which reads the paged pool."""
    s = sizes(config)
    return [(s["n_periods"], s["q_heads"], s["kv_heads"], s["head_dim"])]


def ssm_layers(config: Dict[str, Any]) -> List[Tuple[int, int, int, int, int]]:
    """``(layers, heads, head width, state width, groups)`` of the Mamba-2
    mixers: what ``harness/ssm_readers.py`` counts the decode step's work
    from."""
    s = sizes(config)
    return [(s["n_periods"], s["mamba_heads"], s["mamba_head_dim"],
             s["mamba_state"], s["mamba_groups"])]
