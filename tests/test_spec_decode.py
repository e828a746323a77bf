"""Speculative decoding over the paged pool (serve/scheduler spec tick
+ quant int8 self-draft + COW rollback).

Fast tier, ``spec`` marker.  Knob validation (paged pool + model-dtype
verify required, draft depth bounded), draft-view reuse (no second
weight walk), the extended compile-once pin — a spec engine runs
exactly THREE decode-phase programs (int8 draft, batched model-dtype
verify, single-token fallback), each compiled once across accept/
reject churn — bit-parity of spec-on vs spec-off vs ``generate()`` for
greedy AND seeded-sampled streams, eos inside an accepted window, the
fallback dispatch when every live slot has one token left, and the
spec counters/span surface.

Slow tier: THE acceptance drill — heterogeneous requests (shared
prefix, mid-prompt chunked prefill, deadline expiry mid-draft) at
spec_k=4 across two waves, streams bit-identical to spec-off and
``generate()``, with the compile watcher attached and zero storms."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.core.config import (
    SPEC_K_MAX,
    ServeConfig,
    validate_spec,
)
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.quant import draft_decode_view, is_quantized_dense
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
from trustworthy_dl_tpu.serve.scheduler import PagedBatchingScheduler

pytestmark = pytest.mark.spec

# vocab_size continues the 97/101/103/107/113 process-global jit-cache
# isolation sequence: the prefill/decode/draft/verify jit caches are
# process-global (scheduler._PROGRAMS), so a config identical to a
# sibling suite's would let that file pre-warm the programs this file's
# strict compile-once pins measure (and vice versa).
CFG = gpt2.GPT2Config(vocab_size=127, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Knob validation + view reuse (host contracts)
# --------------------------------------------------------------------------


def test_spec_config_validation(params):
    """spec_k fails loudly where the operator typed it: range bound,
    model-dtype verify required (the int8 tier is the DRAFT) — at
    ServeConfig AND at a raw engine construction."""
    with pytest.raises(ValueError, match="spec_k"):
        ServeConfig(spec_k=-1)
    with pytest.raises(ValueError, match="spec_k"):
        ServeConfig(spec_k=SPEC_K_MAX + 1)
    with pytest.raises(ValueError, match="weight_dtype"):
        ServeConfig(spec_k=2, weight_dtype="int8")
    ServeConfig(spec_k=4)                       # valid: model-dtype verify
    validate_spec(0, "int8")                    # disabled: anything goes
    with pytest.raises(ValueError, match="spec_k"):
        validate_spec(SPEC_K_MAX + 1, "model")
    # Engines built without a config hit the same loud checks.
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(params, CFG, max_seq=32, spec_k=SPEC_K_MAX + 1)
    with pytest.raises(ValueError, match="weight_dtype"):
        ServingEngine(params, CFG, max_seq=32, weight_dtype="int8",
                      spec_k=2)
    # The scheduler refuses a spec depth with no draft to run it.
    with pytest.raises(ValueError, match="draft_view"):
        PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=32,
                               block_size=8, spec_k=2)


def test_from_config_threads_spec_and_builds_int8_draft(params):
    """from_config threads spec_k through; the engine builds the int8
    draft view ONCE (reusing the dense decode view — no second weight
    walk) while the serve/verify view stays dense."""
    engine = ServingEngine.from_config(
        params, CFG, ServeConfig(max_slots=2, max_seq=32, block_size=8,
                                 spec_k=2))
    sched = engine.scheduler
    assert engine.spec_k == 2 and sched.spec_k == 2
    assert is_quantized_dense(sched.draft_view["blocks"]["attn"]["qkv"])
    assert not is_quantized_dense(sched.view["blocks"]["attn"]["qkv"])
    # Reuse contract: an already-quantized view IS the draft, returned
    # as-is — weight_dtype="int8" engines never pay a second walk.
    qview = sched.draft_view
    assert draft_decode_view(params, CFG, qview=qview) is qview
    # Disabled config keeps today's path: no draft view, no spec state.
    off = ServingEngine.from_config(
        params, CFG, ServeConfig(max_slots=2, max_seq=32, block_size=8))
    assert off.spec_k == 0 and off.scheduler.draft_view is None


# --------------------------------------------------------------------------
# Bit-parity + the extended compile-once pin
# --------------------------------------------------------------------------


def _requests(seed=7):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(5):
        plen = int(rng.integers(3, 14))
        reqs.append(ServeRequest(
            prompt=rng.integers(0, CFG.vocab_size, plen).tolist(),
            max_new_tokens=int(rng.integers(2, 9))))
    reqs.append(ServeRequest(prompt=[2, 71, 8, 28], max_new_tokens=6,
                             temperature=0.8, rng=jax.random.PRNGKey(42)))
    return reqs


def test_spec_streams_bit_identical_and_three_programs(params):
    """THE pin: a spec engine serves greedy AND seeded-sampled streams
    bit-identical to the spec-off engine and to generate(), and its
    decode phase compiles exactly THREE programs — draft (int8 view),
    verify (batched model-dtype) and the single-token fallback — each
    exactly once across accept/reject churn."""
    streamed = {}
    spec = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                         queue_limit=32, rng=jax.random.PRNGKey(5),
                         block_size=8, prefill_chunk=16, spec_k=3)
    before = spec.scheduler.spec_cache_sizes()
    for req in _requests():
        req.on_token = lambda r, t: streamed.setdefault(r, []).append(t)
        spec.submit(req)
    # A lone max_new=1 straggler: its only tick has every live slot at
    # one remaining token — the FALLBACK single-token program's slot.
    spec_results = spec.run_until_idle()
    rid_one = spec.submit(ServeRequest(prompt=[9, 9, 4], max_new_tokens=1))
    spec_results.update(spec.run_until_idle())
    after = spec.scheduler.spec_cache_sizes()
    assert after["spec_draft"] - before["spec_draft"] == 1
    assert after["spec_verify"] - before["spec_verify"] == 1
    assert after["paged_decode"] - before["paged_decode"] == 1
    summary = spec.metrics_summary()
    assert summary["spec_proposed"] > 0
    assert summary["spec_fallback_ticks"] >= 1
    assert 0.0 <= summary["accepted_rate"] <= 1.0
    assert summary["spec_near_tie_flips"] == 0   # decisive margins here

    off = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                        queue_limit=32, rng=jax.random.PRNGKey(5),
                        block_size=8, prefill_chunk=16)
    for req in _requests():
        off.submit(req)
    off.submit(ServeRequest(prompt=[9, 9, 4], max_new_tokens=1))
    off_results = off.run_until_idle()
    assert {r: v.tokens for r, v in spec_results.items()} \
        == {r: v.tokens for r, v in off_results.items()}
    assert all(r.status == "completed" for r in spec_results.values())

    for rid, req in enumerate(_requests()):
        ref = generate(params, CFG,
                       jnp.asarray([list(req.prompt)], jnp.int32),
                       req.max_new_tokens, temperature=req.temperature,
                       rng=(req.rng if req.rng is not None
                            else jax.random.fold_in(jax.random.PRNGKey(5),
                                                    rid)))
        ref_tokens = np.asarray(ref)[0, len(req.prompt):].tolist()
        assert spec_results[rid].tokens == ref_tokens, f"request {rid}"
        # Streaming saw every burst token, in order.
        assert streamed[rid] == ref_tokens, f"request {rid}"
    assert spec_results[rid_one].tokens  # the fallback tick served it


def test_spec_eos_stops_inside_accepted_window(params):
    """An eos landing mid-accepted-window stops the stream AT the eos —
    accepted tokens past it are discarded, the slot frees, and the
    stream still equals generate()'s truncated-at-eos stream."""
    prompt = [9, 4, 33]
    ref = np.asarray(generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                              6, temperature=0.0))[0, 3:].tolist()
    eos = ref[0]
    stop = ref.index(eos) + 1
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                           block_size=8, spec_k=3)
    rid = engine.submit(ServeRequest(prompt=prompt, max_new_tokens=6,
                                     eos_id=eos))
    result = engine.run_until_idle()[rid]
    assert result.status == "completed"
    assert result.tokens == ref[:stop]
    assert len(result.tokens) < 6
    assert engine.scheduler.allocator.free_count == 2


def test_spec_counters_gauges_and_verify_span(params, tmp_path):
    """The obs surface: tddl_serve_spec_proposed/accepted_total ride
    the registry and agree with the summary rollup, and every spec tick
    lands a ``serve.spec_verify`` span (under the decode-tick timeline)
    carrying proposed/accepted attrs."""
    from trustworthy_dl_tpu.obs import ObsSession
    from trustworthy_dl_tpu.obs.events import read_jsonl

    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    session.enable_spans()
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                           queue_limit=16, block_size=8, spec_k=2,
                           trace=session.trace, registry=session.registry,
                           spans=session.spans)
    for i in range(3):
        engine.submit(ServeRequest(prompt=[i + 1, i + 2, i + 3],
                                   max_new_tokens=4))
    engine.run_until_idle()
    summary = engine.metrics_summary()
    assert summary["spec_proposed"] > 0
    reg = session.registry
    assert reg.get("tddl_serve_spec_proposed_total").value() \
        == float(summary["spec_proposed"])
    assert reg.get("tddl_serve_spec_accepted_total").value() \
        == float(summary["spec_accepted"])
    session.finalize()
    events = read_jsonl(str(tmp_path / "trace.jsonl"))
    spans = [e for e in events if e["type"] == "span"
             and e["name"] == "serve.spec_verify"]
    assert spans and spans[0]["proposed"] >= 2
    assert all("accepted" in s for s in spans)
    assert sum(s["proposed"] for s in spans) == summary["spec_proposed"]
    assert any(e["name"] == "serve.decode_tick" for e in events
               if e["type"] == "span")


def test_spec_int8_kv_pool_keeps_parity(params):
    """spec composes with the int8 KV tier: the verify pass overwrites
    draft positions through the same quantize-at-write path spec-off
    decode uses, so the int8-KV spec stream equals the int8-KV spec-off
    stream token for token."""
    kwargs = dict(max_slots=2, max_seq=48, queue_limit=16, block_size=8,
                  kv_dtype="int8", kv_parity_check=False,
                  rng=jax.random.PRNGKey(5))
    outs = {}
    for label, k in (("off", 0), ("spec", 2)):
        engine = ServingEngine(params, CFG, spec_k=k, **kwargs)
        for i in range(3):
            engine.submit(ServeRequest(prompt=[5, 17, 3, 2 + i],
                                       max_new_tokens=5))
        outs[label] = {r: v.tokens
                       for r, v in engine.run_until_idle().items()}
    assert outs["off"] == outs["spec"]


# --------------------------------------------------------------------------
# Slow tier: THE acceptance drill
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_drill_heterogeneous_bit_identical_zero_storms(params):
    """Acceptance drill: two waves of heterogeneous requests — a shared
    multi-block prefix, prompts crossing the chunked-prefill boundary,
    a seeded sampled stream — at spec_k=4, with the compile watcher
    attached: streams BIT-IDENTICAL to spec-off and to generate(); a
    deadline expiring mid-draft retires
    with a prefix of the reference stream; zero compile storms."""
    from trustworthy_dl_tpu.obs.compilewatch import (
        CompileRegistry,
        CompileWatcher,
    )

    rng = np.random.default_rng(11)
    common = rng.integers(0, CFG.vocab_size, 17).tolist()  # 2 full blocks

    def build_requests():
        reqs = [ServeRequest(prompt=common + [5], max_new_tokens=3)]
        for i in range(4):
            plen = 3 + 4 * i               # 3..15: spans the 8-pos chunk
            reqs.append(ServeRequest(
                prompt=[(7 * i + j) % CFG.vocab_size for j in range(plen)],
                max_new_tokens=3 + i))
        reqs.append(ServeRequest(prompt=common + [9, 9], max_new_tokens=6))
        reqs.append(ServeRequest(prompt=[2, 71, 8, 28], max_new_tokens=6,
                                 temperature=0.8,
                                 rng=jax.random.PRNGKey(42)))
        return reqs

    outputs = {}
    engines = {}
    arms = (
        ("spec", dict(block_size=8, prefill_chunk=8, spec_k=4)),
        ("off", dict(block_size=8, prefill_chunk=8)),
    )
    registry = CompileRegistry().install()
    watcher = CompileWatcher(registry)
    try:
        for label, kwargs in arms:
            engine = ServingEngine(
                params, CFG, max_slots=3, max_seq=48, queue_limit=64,
                rng=jax.random.PRNGKey(5),
                compilewatch=watcher if label == "spec" else None,
                **kwargs)
            for wave in range(2):          # wave 2 reuses freed blocks
                for req in build_requests():
                    engine.submit(req)
                results = engine.run_until_idle()
            assert len(results) == 14
            assert all(r.status == "completed" for r in results.values())
            outputs[label] = {rid: r.tokens for rid, r in results.items()}
            engines[label] = engine
    finally:
        registry.uninstall()

    assert outputs["spec"] == outputs["off"]
    # Zero storms across accept/reject churn, block churn, prefix hits
    # and both waves: the three spec programs each compiled exactly
    # once, at their declared warmup.
    assert watcher.storm_total == 0
    summary = engines["spec"].metrics_summary()
    assert summary["spec_proposed"] > 0
    assert summary["spec_near_tie_flips"] == 0
    assert summary["prefix_hits"] >= 1

    for rid, req in enumerate(build_requests()):
        ref = generate(params, CFG,
                       jnp.asarray([list(req.prompt)], jnp.int32),
                       req.max_new_tokens, temperature=req.temperature,
                       rng=(req.rng if req.rng is not None
                            else jax.random.fold_in(jax.random.PRNGKey(5),
                                                    rid)))
        ref_tokens = np.asarray(ref)[0, len(req.prompt):].tolist()
        assert outputs["spec"][rid] == ref_tokens, f"request {rid}"

    # Deadline expiry mid-draft: a long generation whose deadline is
    # yanked after its first spec tick retires with a PREFIX of the
    # reference stream and returns its row/blocks.
    engine = engines["spec"]
    req = ServeRequest(prompt=[3, 1, 4, 1, 5], max_new_tokens=16,
                       deadline_s=30.0)
    rid = engine.submit(req)
    engine.step()                          # admit (+ prefill book-keep)
    engine.step()                          # first spec tick
    req.deadline_s = -1.0                  # expire mid-stream
    engine.run_until_idle()
    result = engine.results[rid]
    assert result.status == "deadline_exceeded"
    ref = np.asarray(generate(
        params, CFG, jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32), 16,
        rng=jax.random.fold_in(jax.random.PRNGKey(5), rid)
    ))[0, 5:].tolist()
    assert 0 < len(result.tokens) < 16
    assert result.tokens == ref[:len(result.tokens)]
    assert engine.scheduler.allocator.free_count == 3
    assert not engine.scheduler._spec_claims
