"""The GPT-2 family (``model_type: "gpt2"``): learned positions, LayerNorm,
one fused QKV product a layer, as many K and V heads as query heads, a GELU
MLP of four times the width, the head tied to the embedding.

It binds what the harness already had: the weights of ``weights.py``, the
plain references ``reference_gpt2.py`` and ``reference_gpt2_serve.py``, and
the program's ``GPT2Config``.  The contract is the package's docstring.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmark.harness import reference_gpt2 as _reference
from benchmark.harness import reference_gpt2_serve as _serving
from benchmark.harness import weights as _weights
from benchmark.harness.reference_gpt2_serve import (FAULTS, chosen_tokens,
                                                    faulty_context)
from benchmark.harness.weights import leaf_norms, sizes

__all__ = ["attention_layers", "chosen_tokens", "compute_dtype",
           "faulty_context", "leaf_norms", "make_weights", "model",
           "model_flops", "planted", "reply_logits", "sizes", "takes_flash",
           "train_steps", "vocab"]


def vocab(config: Dict[str, Any]) -> int:
    return int(config["vocab_size"])


make_weights = _weights.make


# -- the program's side ------------------------------------------------------


def model(config: Dict[str, Any]) -> Any:
    from trustworthy_dl_tpu.models.gpt2 import GPT2Config

    return GPT2Config(**sizes(config))


def compute_dtype(config: Dict[str, Any]) -> Any:
    return model(config).dtype


def takes_flash(config: Dict[str, Any], seq_len: int, head_width: int
                ) -> bool:
    from trustworthy_dl_tpu.models.gpt2 import auto_picks_flash

    return auto_picks_flash(seq_len, head_width)


# -- the plain references ----------------------------------------------------


def reply_logits(params: Dict[str, Any], prompt: Any, reply: Any,
                 config: Dict[str, Any], max_reply: int,
                 precision: str = "f32") -> Any:
    shape = sizes(config)
    return _serving.reply_logits(params, prompt, reply, shape["n_head"],
                                 shape["n_positions"], max_reply, precision)


def planted(params: Dict[str, Any], fault: str, config: Dict[str, Any]
            ) -> Dict[str, Any]:
    """The weights a planted fault computes with: the middle layer's value
    projection zeroed for ``layer_cache_unwritten``, else ``params``."""
    if fault == "layer_cache_unwritten":
        return _serving.unwrite_layer_cache(
            params, sizes(config)["n_layer"] // 2)
    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    return params


def train_steps(params: Dict[str, Any], batches: List[Dict[str, Any]],
                config: Dict[str, Any], opt: Dict[str, float],
                precision: str = "f32", rows: int = 2, fault: str = ""
                ) -> Dict[str, Any]:
    return _reference.train_steps(params, batches, sizes(config)["n_head"],
                                  opt, precision, rows, fault=fault)


# -- the work ----------------------------------------------------------------


def param_count(cfg: Dict[str, int]) -> int:
    """Parameter count of a GPT-2 with a tied head, from its sizes.  No part
    of the contract (nothing generic asks a family for it): the by-hand
    tests hold ``model_flops`` and the trainer's own count against it."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    block = (2 * d) + (d * 3 * d + 3 * d) + (d * d + d) + (2 * d) \
        + (d * 4 * d + 4 * d) + (4 * d * d + d)
    return cfg["vocab_size"] * d + cfg["n_positions"] * d \
        + layers * block + 2 * d


def model_flops(cfg: Dict[str, int], fed_tokens: int, sampled: int) -> float:
    """The matrix products of a GPT-2 forward over ``fed_tokens`` tokens
    (prompt tokens prefilled and tokens decoded) of which ``sampled``
    positions also go through the tied head: 2 a multiply-add.  Attention's
    own products are left out, so a share of the peak this gives is a lower
    bound."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    body = layers * (d * 3 * d + d * d + d * 4 * d + 4 * d * d)
    return 2.0 * body * fed_tokens + 2.0 * cfg["vocab_size"] * d * sampled


def attention_layers(config: Dict[str, Any]
                     ) -> List[Tuple[int, int, int, int]]:
    """Every layer is one softmax attention layer of ``n_head`` query heads
    and as many K and V heads, ``n_embd // n_head`` wide."""
    shape = sizes(config)
    heads = shape["n_head"]
    return [(shape["n_layer"], heads, heads, shape["n_embd"] // heads)]
