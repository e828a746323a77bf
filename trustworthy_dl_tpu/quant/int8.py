"""Symmetric int8 quantization primitives for the serving engine.

Two quantization tiers, both weight-of-evidence standards from the
serving literature, both shaped for XLA's static-shape world:

* **KV-cache int8** (KVQuant / vLLM ``kv_cache_dtype="int8"`` practice):
  K/V rows store int8 with a per-(head, position) f32 scale — the scale
  of a cached key factors OUT of the attention dot product (it is
  constant along the contracted Dh axis), so dequantisation never
  materialises an f32 copy of the cache: scores are computed against the
  int8 values and multiplied by the scale vector afterwards.  HBM per
  slot roughly halves (Dh bytes + 4 scale bytes vs 2·Dh bf16 bytes per
  cached position), which at fixed HBM doubles MAX_SLOTS — continuous-
  batching throughput is slot-bound under load.

* **Weight-only int8** for the decode matmuls (LLM.int8 / AWQ-style W8
  without the activation half): per-OUTPUT-channel symmetric scales, so
  the scale also factors out of the contraction and the matmul runs
  ``x @ w_int8`` with one f32 multiply per output column at the end.
  b=1..MAX_SLOTS decode is weight-bandwidth-bound; int8 weights halve
  the bytes streamed per token vs bf16.  Embedding table and lm head
  stay high precision (their numerics dominate token choice).

Everything here is pure jnp and runs on the CPU test backend; the
optional Pallas fused dequant-matmul tile lives in
``ops/fused_dequant_matmul.py`` behind the same ``pallas_enabled()``
gate as ``fused_stats``.

Error contract: symmetric round-to-nearest over a [-amax, amax] range
gives per-element error <= amax/254 (half an int8 step of amax/127).
All-zero channels store scale 0 and reproduce exact zeros.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models import layers as L

logger = logging.getLogger(__name__)

Params = Dict[str, Any]

#: Accepted ServeConfig / engine dtype knob values.  "model" follows the
#: model's compute dtype (the pre-quantization behaviour).
KV_DTYPES = ("model", "bfloat16", "float32", "int8")
WEIGHT_DTYPES = ("model", "int8")

#: Largest int8 magnitude used by the symmetric scheme (clip range
#: [-127, 127]; -128 is never emitted so the range stays symmetric).
QMAX = 127.0


def validate_dtypes(kv_dtype: str, weight_dtype: str) -> None:
    """Loud construction-time validation — an unknown dtype string must
    fail where the operator typed it, not at trace time inside a jitted
    serving program."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
        )
    if weight_dtype not in WEIGHT_DTYPES:
        raise ValueError(
            f"weight_dtype must be one of {WEIGHT_DTYPES}, got "
            f"{weight_dtype!r}"
        )


def resolve_kv_dtype(kv_dtype: str, cfg: gpt2.GPT2Config) -> Any:
    """Map a ServeConfig kv_dtype string to the array dtype the slot
    pool stores (``jnp.int8`` selects the quantized variant)."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}"
        )
    return {
        "model": cfg.dtype,
        "bfloat16": jnp.bfloat16,
        "float32": jnp.float32,
        "int8": jnp.int8,
    }[kv_dtype]


# ---------------------------------------------------------------------------
# Core primitives: symmetric per-channel quantize / dequantize
# ---------------------------------------------------------------------------


def quantize_int8(x: jax.Array, axis: int = -1
                  ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel int8: reduce |max| over ``axis``.

    Returns ``(q int8, scale f32)`` with ``scale = amax / 127`` shaped
    like ``x`` minus ``axis``.  All-zero channels keep scale 0 (their
    dequantisation is exactly zero); rounding is round-half-to-even
    (jnp.rint), clipped to [-127, 127]."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis)
    scale = amax / QMAX
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.rint(x / jnp.expand_dims(safe, axis)), -QMAX, QMAX)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, axis: int = -1,
                    dtype: Any = jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_int8` (up to the rounding error)."""
    return (q.astype(jnp.float32) * jnp.expand_dims(scale, axis)
            ).astype(dtype)


def quantize_kv(kv: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantize K or V rows ``[..., Dh]`` per cached position (scale over
    the head dim) — the serving cache's per-(head, position) scheme."""
    return quantize_int8(kv, axis=-1)


# ---------------------------------------------------------------------------
# Weight-only int8 decode view
# ---------------------------------------------------------------------------


def quantize_dense(d: Params) -> Params:
    """``{"w": [..., in, out], "b": [..., out]}`` -> ``{"w_q": int8,
    "scale": f32 [..., out], "b"}`` — per-output-channel symmetric
    (reduced over the ``in`` axis), so the scale factors out of the
    ``x @ w`` contraction exactly.  Leading axes (the model's stacked
    [L, ...] block layout) pass through untouched."""
    q, scale = quantize_int8(d["w"].astype(jnp.float32), axis=-2)
    return {"w_q": q, "scale": scale, "b": d["b"]}


def is_quantized_dense(d: Params) -> bool:
    return isinstance(d, dict) and "w_q" in d


def qdense(d: Params, x: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    """Dense dispatcher for the decode path: plain ``{"w","b"}`` params
    go through ``layers.dense`` unchanged; weight-only-int8 params
    (``{"w_q","scale","b"}``) run the dequant-matmul — via the Pallas
    fused tile on TPU when shapes tile (``ops.fused_dequant_matmul``),
    else the jnp contraction with f32 accumulation.  The branch is on
    pytree *structure*, resolved at trace time — a quantized and an
    unquantized engine each still compile exactly one decode program."""
    if not is_quantized_dense(d):
        return L.dense(d, x, dtype)
    from trustworthy_dl_tpu.ops.fused_dequant_matmul import (
        dequant_matmul,
    )

    lead = x.shape[:-1]
    k = x.shape[-1]
    y = dequant_matmul(x.reshape(-1, k), d["w_q"], d["scale"])
    y = y.reshape(*lead, -1).astype(dtype) + d["b"].astype(dtype)
    return y


def quantize_decode_view(params: Params, cfg: gpt2.GPT2Config,
                         view: Optional[Params] = None) -> Params:
    """Weight-only int8 decode view: the attention projections and MLP
    matmuls carry int8 weights + per-output-channel f32 scales; the
    embedding table, position table, layernorms and (tied) lm head keep
    the precision ``models/generate._decode_view`` gives them — token
    choice is dominated by the final projection's numerics, and the
    embedding gather streams one row per token, not the whole table.

    Conversion happens ONCE here (engine construction); the decode
    programs then stream int8 weight bytes every token.  Pass ``view``
    when a dense decode view is already built (the engine also feeds it
    to the parity probe / error histogram) to skip rebuilding it."""
    from trustworthy_dl_tpu.models import generate as gen

    if view is None:
        view = gen._decode_view(params, cfg)
    blocks = view["blocks"]
    out = dict(view)
    out["blocks"] = {
        "ln_1": blocks["ln_1"],
        "ln_2": blocks["ln_2"],
        "attn": {"qkv": quantize_dense(blocks["attn"]["qkv"]),
                 "proj": quantize_dense(blocks["attn"]["proj"])},
        "mlp": {"fc": quantize_dense(blocks["mlp"]["fc"]),
                "proj": quantize_dense(blocks["mlp"]["proj"])},
    }
    return out


def draft_decode_view(params: Params, cfg: gpt2.GPT2Config,
                      dense_view: Optional[Params] = None,
                      qview: Optional[Params] = None) -> Params:
    """The int8 self-draft weight view for speculative decoding
    (serve/scheduler's draft program): the SAME weights the engine
    serves, quantized to the weight-only int8 tier — a draft model that
    costs nothing to train, nothing extra to store beyond the int8
    copy, and half the decode weight bandwidth per drafted token.

    Reuse contract (no second weight walk): pass ``qview`` when the
    engine already built its weight-only int8 view (``weight_dtype=
    "int8"`` — it IS the draft, returned as-is), else pass
    ``dense_view`` (the engine's already-pre-cast dense decode view) so
    quantization reuses it instead of re-walking the master weights."""
    if qview is not None:
        return qview
    return quantize_decode_view(params, cfg, view=dense_view)


def weight_roundtrip_errors(params: Params, cfg: gpt2.GPT2Config,
                            qview: Optional[Params] = None) -> List[float]:
    """Max relative quantization error per decode-path weight matrix
    (‖w − deq(q(w))‖_inf / ‖w‖_inf) — the numbers the engine feeds its
    quantization-error histogram, and the per-matrix safety gate for the
    weight-only tier.  Pass ``qview`` (a :func:`quantize_decode_view`
    result over the same weights) to reuse its w_q/scale instead of
    re-quantizing — the engine already paid that pass at construction."""
    errs: List[float] = []
    blocks = params["blocks"]
    qblocks = qview["blocks"] if qview is not None else None
    for group, name in (("attn", "qkv"), ("attn", "proj"),
                        ("mlp", "fc"), ("mlp", "proj")):
        w = blocks[group][name]["w"].astype(jnp.float32)
        if qblocks is not None:
            q = qblocks[group][name]["w_q"]
            scale = qblocks[group][name]["scale"]
        else:
            q, scale = quantize_int8(w, axis=-2)
        err = jnp.max(
            jnp.abs(w - q.astype(jnp.float32) * scale[..., None, :])
        )
        denom = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12)
        errs.append(float(err / denom))
    return errs


# ---------------------------------------------------------------------------
# Parity gate — the safety latch in front of the int8 KV swap
# ---------------------------------------------------------------------------

#: A greedy token flip is tolerated only when the reference path's own
#: top-1 margin is below this (a near-tie, where ANY numerics change —
#: flash vs XLA attention included — can flip the argmax).  A decisive
#: flip fails the probe.
PARITY_MARGIN_TOL = 0.05


def kv_parity_probe(view: Params, cfg: gpt2.GPT2Config,
                    prompt_len: int = 8, decode_tokens: int = 4) -> bool:
    """Construction-time greedy parity check: decode a few tokens over a
    deterministic prompt twice — full-precision KV vs int8 KV, SAME
    weight view — and require the greedy argmax to agree at every step
    (flips are tolerated only under a near-tie top-1 margin,
    PARITY_MARGIN_TOL; see tests/test_quant.py for the pinned tiny-GPT2
    fixture).  Runs eagerly on purpose: a jitted probe would add compiled
    programs to the serving process (the decode compile-count pin says
    the engine compiles exactly one decode program).

    The reference token is teacher-forced into both paths each step so
    one tolerated near-tie cannot cascade into stream divergence."""
    from trustworthy_dl_tpu.models import generate as gen

    max_len = prompt_len + decode_tokens
    prompt = (jnp.arange(prompt_len, dtype=jnp.int32)
              % cfg.vocab_size)[None, :]
    ref_cache = gen.init_cache(cfg, 1, max_len)
    q_cache = gen.init_cache(cfg, 1, max_len, kv_dtype=jnp.int8)
    ref_logits, ref_cache = gen._apply_with_cache(view, prompt, ref_cache,
                                                  cfg)
    q_logits, q_cache = gen._apply_with_cache(view, prompt, q_cache, cfg)
    for step in range(decode_tokens):
        ref_top2 = jax.lax.top_k(ref_logits[0], 2)[0]
        ref_tok = int(jnp.argmax(ref_logits[0]))
        q_tok = int(jnp.argmax(q_logits[0]))
        if q_tok != ref_tok:
            margin = float(ref_top2[0] - ref_top2[1])
            if margin >= PARITY_MARGIN_TOL:
                logger.warning(
                    "int8 KV parity probe failed: greedy token %d != %d "
                    "at top-1 margin %.4f (tolerance %.4f)",
                    q_tok, ref_tok, margin, PARITY_MARGIN_TOL,
                )
                return False
        if step == decode_tokens - 1:
            break  # nothing left to compare — skip the dead advance
        tok = jnp.asarray([[ref_tok]], jnp.int32)   # teacher-force
        ref_logits, ref_cache = gen._apply_with_cache(view, tok, ref_cache,
                                                      cfg)
        q_logits, q_cache = gen._apply_with_cache(view, tok, q_cache, cfg)
    return True
