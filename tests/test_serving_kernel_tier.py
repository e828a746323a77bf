"""The multi-query-row serving-kernel tier (ops/paged_attention.py:
chunked-prefill flash program, fused speculative-verify tail, in-grid
adapter gather) — kernel-vs-reference equality cells, the per-program
resolver contract, adapter-on stream bit-identity, and compile-once
under churn with every new program in the loop.

The single-query-row decode program and the trust epilogue keep their
pins in tests/test_paged_attention.py; this file owns what ISSUE 20
added on top.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.ops import paged_attention as pattn
from trustworthy_dl_tpu.ops.fused_dequant_matmul import lowrank_delta
from trustworthy_dl_tpu.quant import int8 as q8
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

pytestmark = pytest.mark.pagedattn

# Unique decode geometry for this file (vocab 163): the process-global
# jit cache must never hand another serve-test file's compiled program
# to this one's compile-sensitive assertions (the 97/101/103/107/109/
# 113/127/139/149/157 sequence in the other serve files).
CFG = gpt2.GPT2Config(vocab_size=163, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Chunked-prefill flash program vs the pinned jnp reference
# --------------------------------------------------------------------------


def _pools(rng, nb, h, bsz, dh, quantized):
    """Stacked pools [L, NB, BLOCK, H·Dh] of two layers (the tests read
    layer 1) and, quantized, the scale planes [L, NB, BLOCK, H]."""
    shape = (2, nb, bsz, h * dh)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.2, shape[:3] + (h,)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.2, shape[:3] + (h,)),
                         jnp.float32)
        return k, v, ks, vs
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return k, v, None, None


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["f32", "int8-scales"])
def test_prefill_kernel_matches_reference_ragged(quantized):
    """The query-tiled prefill program equals the gathered-view
    reference on ragged per-row starts with the chunk CROSSING block
    boundaries — T=13 over block_size=8 spans 2-3 blocks and the
    query tiles land mid-block, so both the per-tile causal bound and
    the absolute-position mask are exercised off the easy alignments."""
    rng = np.random.default_rng(0)
    r, h, t, dh, bsz, nbps, nb = 3, 2, 13, 16, 8, 6, 20
    q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
    k, v, ks, vs = _pools(rng, nb, h, bsz, dh, quantized)
    table = jnp.asarray(rng.permutation(nb)[:r * nbps].reshape(r, nbps),
                        jnp.int32)
    start = jnp.asarray([0, 5, 17], jnp.int32)   # ragged, non-aligned
    out = pattn.paged_prefill_attention(q, k, v, table, start, layer=1,
                                        k_scale=ks, v_scale=vs,
                                        interpret=True)
    ref = pattn.paged_attention_reference(q, k, v, table, start, layer=1,
                                          k_scale=ks, v_scale=vs)
    tol = 5e-5 if quantized else 5e-6
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


def test_prefill_kernel_scalar_start_and_tile_multiple():
    """The scalar-``start`` spelling (the chunk program's R=1 contract)
    and a T that is an exact query-tile multiple both hit the
    reference; T=16 with start mid-block crosses a boundary inside
    BOTH tiles."""
    rng = np.random.default_rng(1)
    r, h, t, dh, bsz, nbps, nb = 1, 2, 16, 16, 8, 6, 8
    q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
    k, v, _, _ = _pools(rng, nb, h, bsz, dh, False)
    table = jnp.asarray(rng.permutation(nb)[:nbps].reshape(r, nbps),
                        jnp.int32)
    start = jnp.asarray(11, jnp.int32)
    out = pattn.paged_prefill_attention(q, k, v, table, start, layer=1,
                                        interpret=True)
    ref = pattn.paged_attention_reference(q, k, v, table, start, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-6)


# --------------------------------------------------------------------------
# Fused speculative-verify tail vs the materialise-then-reduce jnp tail
# --------------------------------------------------------------------------


def test_fused_verify_tail_bit_exact_logits_and_margin():
    """The one-pass tail's logits are BIT-identical to the jnp
    projection (f32 single contraction) and the margin bit-identical
    to ``lax.top_k`` over them; entropy agrees to f32 epsilon.  The
    odd vocab (163) exercises the pad-column masking."""
    rng = np.random.default_rng(2)
    b, d, v = 5, 32, CFG.vocab_size
    normed = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    logits, ent, mar = pattn.fused_verify_tail(normed, head,
                                               interpret=True)
    ref = (normed @ head.T).astype(jnp.float32)
    assert np.array_equal(np.asarray(logits), np.asarray(ref))
    top2 = jax.lax.top_k(ref, 2)[0]
    assert np.array_equal(np.asarray(mar),
                          np.asarray(top2[:, 0] - top2[:, 1]))
    logp = jax.nn.log_softmax(ref, axis=-1)
    ent_ref = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_ref),
                               atol=1e-5)


def test_fused_verify_tail_duplicated_maxima_margin_zero():
    """Rows whose top logit value appears twice must report margin
    EXACTLY 0.0 — the one-occurrence-masked top-2 merge cannot count
    a single maximum twice, and ties across vocab TILES (indices 3 and
    600 sit in different 512-wide tiles) exercise the cross-tile
    merge."""
    d = 32
    v = 700
    normed = jnp.eye(2, d, dtype=jnp.float32) * 4.0
    head = jnp.zeros((v, d), jnp.float32)
    head = head.at[3, 0].set(2.0).at[600, 0].set(2.0)     # row-0 tie
    head = head.at[9, 1].set(1.5).at[10, 1].set(1.5)      # row-1 tie
    _, _, mar = pattn.fused_verify_tail(normed, head, interpret=True)
    assert np.asarray(mar).tolist() == [0.0, 0.0]


def test_fused_verify_tail_bf16_rounding_matches_jnp():
    """A bf16 compute dtype rounds the matmul to bf16 before the f32
    upcast on the jnp tail; the kernel mirrors that rounding, so the
    fused logits still equal the materialised ones bitwise."""
    rng = np.random.default_rng(3)
    b, d, v = 4, 32, 163
    normed = jnp.asarray(rng.normal(size=(b, d)), jnp.bfloat16)
    head = jnp.asarray(rng.normal(size=(v, d)), jnp.bfloat16)
    logits, _, _ = pattn.fused_verify_tail(normed, head, interpret=True)
    ref = (normed @ head.T).astype(jnp.float32)
    assert np.array_equal(np.asarray(logits), np.asarray(ref))


# --------------------------------------------------------------------------
# In-grid adapter gather vs the take-then-lowrank_delta jnp spelling
# --------------------------------------------------------------------------


@pytest.mark.parametrize("scaled", [False, True], ids=["f32", "int8-tier"])
def test_adapter_delta_matches_gathered_lowrank(scaled):
    """``adapter_delta`` (pages as scalar prefetch, A/B tiles streamed
    in-grid) is BIT-identical to ``lowrank_delta`` over the jnp page
    take — same contraction order, same f32 accumulation, same scale
    placement — including rows on the reserved zero page and duplicate
    page hits."""
    rng = np.random.default_rng(4)
    npg, rk, d, r, t = 5, 4, 32, 4, 3
    x = jnp.asarray(rng.normal(size=(r, t, d)), jnp.float32)
    a_pool = jnp.asarray(rng.normal(size=(npg, d, rk)), jnp.float32)
    b_pool = jnp.asarray(rng.normal(size=(npg, rk, d)), jnp.float32)
    a_pool = a_pool.at[0].set(0.0)          # the zero page
    b_pool = b_pool.at[0].set(0.0)
    pages = jnp.asarray([0, 2, 2, 4], jnp.int32)
    sa = sb = None
    if scaled:
        sa = jnp.asarray(rng.uniform(0.01, 0.3, npg), jnp.float32)
        sb = jnp.asarray(rng.uniform(0.01, 0.3, npg), jnp.float32)
    out = pattn.adapter_delta(x, a_pool, b_pool, pages,
                              a_scale=sa, b_scale=sb, interpret=True)
    ref = lowrank_delta(x, a_pool[pages], b_pool[pages],
                        None if sa is None else sa[pages],
                        None if sb is None else sb[pages])
    assert np.array_equal(np.asarray(out), np.asarray(ref))
    assert np.all(np.asarray(out)[0] == 0.0)   # zero page: exact zero


# --------------------------------------------------------------------------
# Per-program resolution: eligibility, loud downgrades, silent absence
# --------------------------------------------------------------------------


def test_resolve_attn_impls_interpret_covers_every_program():
    impls = pattn.resolve_attn_impls(
        "interpret", head_dim=8, block_size=8, kv_dtype=jnp.float32,
        n_embd=32, adapter_rank=4)
    assert impls == {"decode": "interpret", "prefill": "interpret",
                     "verify": "interpret", "adapter": "interpret"}


def test_resolve_attn_impls_unconfigured_adapter_is_silent_jnp(caplog):
    with caplog.at_level(logging.WARNING):
        impls = pattn.resolve_attn_impls(
            "interpret", head_dim=8, block_size=8,
            kv_dtype=jnp.float32, n_embd=32, adapter_rank=None)
    assert impls["adapter"] == "jnp"
    assert impls["decode"] == "interpret"
    assert not caplog.records     # nothing to fuse -> nothing to warn

    # decode resolving to jnp short-circuits the whole tier.
    impls = pattn.resolve_attn_impls(
        "jnp", head_dim=8, block_size=8, kv_dtype=jnp.float32,
        n_embd=128, adapter_rank=8)
    assert set(impls.values()) == {"jnp"}


def test_compiled_eligibility_per_program():
    """The compiled-Mosaic rule the resolver consults is VMEM (held to
    the TPU compiler in tests/test_chip_compile.py): ``n_embd`` off the
    lanes and ranks below 8 lower; a head tile or a chunk of rows past
    the block budget does not — and interpret mode waives it (how the
    CPU test tier runs the small geometries above)."""
    kw = dict(head_dim=64, block_size=16, kv_dtype=jnp.bfloat16)
    for n_embd in (768, 100):
        assert pattn.supports_paged_attention(
            program="verify", interpret=False, n_embd=n_embd, **kw)
    assert not pattn.supports_paged_attention(
        program="verify", interpret=False, n_embd=4096, **kw)
    assert not pattn.supports_paged_attention(
        program="verify", interpret=False, **kw)        # n_embd unknown
    for rank in (8, 6, 1):
        assert pattn.supports_paged_attention(
            program="adapter", interpret=False, n_embd=768,
            adapter_rank=rank, **kw)
    assert not pattn.supports_paged_attention(
        program="adapter", interpret=False, n_embd=768, adapter_rank=8,
        rows=1024, **kw)
    assert not pattn.supports_paged_attention(
        program="adapter", interpret=False, n_embd=768, adapter_rank=0,
        **kw)
    assert pattn.supports_paged_attention(
        program="adapter", interpret=True, n_embd=32, adapter_rank=2,
        **kw)
    with pytest.raises(ValueError, match="program"):
        pattn.supports_paged_attention(program="draft", interpret=True,
                                       **kw)


def test_resolve_attn_impls_partial_downgrade_warns(caplog, monkeypatch):
    """A geometry that decodes on compiled Mosaic but whose verify head
    tile and adapter rows overflow VMEM downgrades ONLY those programs,
    loudly."""
    monkeypatch.setattr(pattn, "pallas_interpret", lambda: False)
    with caplog.at_level(logging.WARNING,
                         logger="trustworthy_dl_tpu.ops.paged_attention"):
        impls = pattn.resolve_attn_impls(
            "pallas", head_dim=64, block_size=16,
            kv_dtype=jnp.bfloat16, n_embd=4096, adapter_rank=6,
            rows=512)
    assert impls["decode"] == "pallas"
    assert impls["prefill"] == "pallas"
    assert impls["verify"] == "jnp"
    assert impls["adapter"] == "jnp"
    warned = " ".join(r.getMessage() for r in caplog.records)
    assert "verify" in warned and "adapter" in warned


# --------------------------------------------------------------------------
# Engine acceptance: adapter-on streams, spec + kernels, zero storms
# --------------------------------------------------------------------------


def _engine(params, impl, **kw):
    kwargs = dict(max_slots=2, max_seq=48, queue_limit=16,
                  block_size=8, num_blocks=24, attn_impl=impl)
    kwargs.update(kw)
    return ServingEngine(params, CFG, **kwargs)


def _drain(engine, reqs):
    for r in reqs:
        assert engine.submit(r) is not None
    results = engine.run_until_idle()
    assert all(r.status == "completed" for r in results.values())
    return [results[i].tokens for i in sorted(results)]


def test_adapter_on_streams_identical_kernel_vs_jnp(params):
    """With a REAL adapter applied (non-zero page, visible delta), the
    in-grid gather path serves the same streams as the jnp take path —
    chunked prefill included (prefill_chunk=16 sends the adapter-
    carrying prompt through the chunk program's kernel arm)."""
    def run(impl):
        engine = _engine(params, impl, adapter_rank=4,
                         adapter_pool_pages=2, prefill_chunk=16,
                         adapter_map={"tx": "ad-x", "ty": "ad-y"})
        engine.adapter_pool.init_scale = 0.5
        paths = engine.attn_kernel_paths
        assert paths["adapter"] == impl
        reqs = [
            ServeRequest(prompt=[5, 17, 3, 88, 41, 2], max_new_tokens=6,
                         tenant="tx"),
            ServeRequest(prompt=[9, 1, 150, 33], max_new_tokens=5,
                         tenant="ty"),
            ServeRequest(prompt=[7, 7, 12], max_new_tokens=4),  # base
            ServeRequest(prompt=[2, 71, 8, 28, 40, 11, 5], max_new_tokens=5,
                         temperature=0.8, rng=jax.random.PRNGKey(42),
                         tenant="tx"),
        ]
        return _drain(engine, reqs)

    jnp_streams = run("jnp")
    assert run("interpret") == jnp_streams
    # And the adapter really bit: the base model disagrees.
    prompt = [5, 17, 3, 88, 41, 2]
    ref = np.asarray(generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                              6, temperature=0.0))[0, 6:].tolist()
    assert jnp_streams[0] != ref


def test_spec_streams_identical_fused_verify_vs_jnp(params):
    """spec_k=2 with the fused verify tail: streams equal the jnp-tail
    engine token for token (greedy and seeded-sampled), int8 KV
    included — the fused logits feed the same categorical draws."""
    def run(impl, **kw):
        engine = _engine(params, impl, spec_k=2, prefill_chunk=16, **kw)
        reqs = [
            ServeRequest(prompt=[5, 17, 3, 2], max_new_tokens=7),
            ServeRequest(prompt=[9, 101, 45], max_new_tokens=6),
            ServeRequest(prompt=[2, 71, 8, 28], max_new_tokens=6,
                         temperature=0.8, rng=jax.random.PRNGKey(42)),
        ]
        return _drain(engine, reqs)

    assert run("interpret") == run("jnp")
    assert (run("interpret", kv_dtype="int8", kv_parity_check=False)
            == run("jnp", kv_dtype="int8", kv_parity_check=False))


def test_zero_storms_two_waves_all_programs(params):
    """Compile-once across the WHOLE tier: an adapter-carrying engine
    and a spec engine (every new program in the loop — prefill chunks,
    fused verify, in-grid adapter gather) each serve two churn waves
    (block churn, adapter eviction churn, prefix reuse) under a
    CompileWatcher with ZERO storms, and wave 2 compiles nothing."""
    from trustworthy_dl_tpu.obs.compilewatch import (
        CompileRegistry,
        CompileWatcher,
    )

    adapter_map = {f"t{i}": f"ad{i}" for i in range(5)}
    arms = {
        "adapter": (dict(adapter_rank=2, adapter_pool_pages=2,
                         adapter_map=adapter_map),
                    (["t0", "t1", "t2"], ["t3", "t4", "t1"])),
        "spec": (dict(spec_k=2), ([None, None, None], [None, None])),
    }
    rng = np.random.default_rng(11)
    shared = rng.integers(0, CFG.vocab_size, 9).tolist()

    def wave(engine, tenants, warm=False):
        # max_new_tokens fixed at 4: per-request key-stream prep
        # (request_key_stream's host-side split) compiles per DISTINCT
        # budget — churn the prompts and tenants, not the budget, so
        # registry.total isolates the serve programs.
        reqs = [ServeRequest(prompt=shared, max_new_tokens=4)]
        if warm:
            # A longer-than-chunk prompt forces the chunk program to
            # compile in the warm wave even for an adapter-free engine:
            # wave 2's prefix-reuse hit resumes the shared prompt
            # MID-prompt, which dispatches the chunk program rather
            # than the whole-prompt prefill.
            reqs.append(ServeRequest(
                prompt=rng.integers(0, CFG.vocab_size, 21).tolist(),
                max_new_tokens=4))
        for tenant in tenants:
            plen = int(rng.integers(3, 12))
            reqs.append(ServeRequest(
                prompt=rng.integers(0, CFG.vocab_size, plen).tolist(),
                max_new_tokens=4, tenant=tenant))
        return _drain(engine, reqs)

    for label, (kw, (wave1, wave2)) in arms.items():
        registry = CompileRegistry().install()
        watcher = CompileWatcher(registry)
        try:
            engine = _engine(params, "interpret", prefill_chunk=16,
                             compilewatch=watcher, **kw)
            wave(engine, wave1, warm=True)            # warm (+ evict)
            before = registry.total
            wave(engine, wave2)                       # churned second wave
            assert registry.total == before, (label, registry.summary())
            assert watcher.storm_total == 0, label
        finally:
            registry.uninstall()
