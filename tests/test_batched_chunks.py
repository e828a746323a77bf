"""One call of the chunk program for the mid-prefill slots: the batched
call against the per-slot calls it replaces (tokens, entropies, margins and
the pool's live blocks, tick by tick), padding rows, the rows of a call,
nothing compiled after the first call, and the description whose chunk is
one slot's keeping a call a slot.  Tiny engines on the CPU."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.families import solar_open2 as family
from trustworthy_dl_tpu.models import decoder, generate as gen, gpt2
from trustworthy_dl_tpu.obs.compilewatch import CompileRegistry, CompileWatcher
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.obs.spans import SpanTracker
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
from trustworthy_dl_tpu.serve import scheduler as sch
from trustworthy_dl_tpu.serve.kv_slots import TRASH_BLOCK, init_paged_pool

# A vocabulary no other test file uses (the process-wide jit cache).
CFG = gpt2.GPT2Config(vocab_size=199, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4)
SLOTS, MAX_SEQ, BLOCK, CHUNK = 4, 48, 8, 8
#: (prompt length, reply length, tenant): prompts of one to four chunks
#: (the 7 takes the whole-prompt program), more requests than slots, so
#: that calls hold rows at different positions, final and not.
REQUESTS = ((21, 3, "a"), (30, 4, None), (7, 3, None), (17, 3, "b"),
            (26, 4, None), (12, 3, "a"), (28, 3, "b"), (19, 4, None))


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


def build(params, **kwargs):
    kwargs.setdefault("registry", MetricsRegistry())
    return ServingEngine(params, CFG, max_slots=SLOTS, max_seq=MAX_SEQ,
                         block_size=BLOCK, prefill_chunk=CHUNK,
                         queue_limit=32, enable_monitor=False, **kwargs)


def prompts(shared):
    """The requests' prompts; with ``shared``, every one begins with the
    same 16 tokens (two blocks), so later admissions hit the prefix cache
    and start their suffix at a block boundary."""
    rng = np.random.default_rng(5)
    head = rng.integers(1, CFG.vocab_size, 16)
    out = []
    for plen, _, _ in REQUESTS:
        p = rng.integers(1, CFG.vocab_size, plen)
        if shared and plen > 16:
            p[:16] = head
        out.append(p)
    return out


#: The int8 tier on the kernels' path, the chip's: on the gather path the
#: CPU's XLA contracts ONE row's dequantized view otherwise than several
#: rows' (a last bit of a score, which can move a value by one quantum).
CASES = {
    "bf16": dict(prefix_cache=False),
    "bf16-kernels": dict(prefix_cache=False, attn_impl="interpret"),
    "int8": dict(prefix_cache=False, kv_dtype="int8", kv_parity_check=False,
                 attn_impl="interpret"),
    "prefix": dict(prefix_cache=True),
    "adapter": dict(prefix_cache=False, adapter_rank=2,
                    adapter_map={"a": "ad-a", "b": "ad-b"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_batched_call_is_the_per_slot_calls_it_replaces(params, case):
    """Two engines in lockstep, one whose chunk calls hold every
    mid-prefill slot and one held to a call a slot: after every tick the
    same tokens, entropies and margins in every request, and the same pool
    in every block but the trash block, bit for bit."""
    kwargs = CASES[case]
    batched, single = build(params, **kwargs), build(params, **kwargs)
    single.scheduler.chunk_rows = 1             # a call a slot
    texts = prompts(shared=case == "prefix")
    for engine in (batched, single):
        if engine.adapter_pool is not None:
            engine.adapter_pool.init_scale = 0.5    # flips greedy tokens
        engine.spans = SpanTracker()
        for text, (_, new, tenant) in zip(texts, REQUESTS):
            engine.submit(ServeRequest(prompt=text.tolist(),
                                       max_new_tokens=new, tenant=tenant))
    while batched.busy or single.busy:
        batched.step()
        single.step()
        mine, theirs = batched.scheduler, single.scheduler
        assert mine.tasks.keys() == theirs.tasks.keys()
        for slot, task in mine.tasks.items():
            other = theirs.tasks[slot]
            assert task.request_id == other.request_id
            assert task.emitted == other.emitted
            np.testing.assert_array_equal(task.entropies, other.entropies)
            np.testing.assert_array_equal(task.margins, other.margins)
        for a, b in zip(mine.kv, theirs.kv):
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a)[:, 1:],
                                              np.asarray(b)[:, 1:])
    assert batched.results.keys() == single.results.keys()
    for rid, result in batched.results.items():
        assert result.tokens == single.results[rid].tokens
        assert result.status == "completed"
    calls = [s.attrs for s in batched.spans.closed_spans()
             if s.name == "serve.prefill_chunk" and s.attrs["padded"]
             + s.attrs["rows"] > 1]
    # the batched engine held rows side by side, finishing and not, and
    # padded a call up to its rows
    assert any(0 < c["final"] < c["rows"] for c in calls)
    assert any(c["padded"] for c in calls)
    per_slot = [s.attrs for s in single.spans.closed_spans()
                if s.name == "serve.prefill_chunk"]
    assert all(c["rows"] == 1 and c["padded"] == 0 for c in per_slot)
    if case == "prefix":
        assert batched.scheduler.prefix_hits > 0
    if case == "adapter":
        assert batched.adapter_pool.metrics()["hits"] + \
            batched.adapter_pool.metrics()["misses"] > 0


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_padding_rows_write_only_the_trash_block(params, kv_dtype):
    """A call of nothing but padding rows (an all-trash table, start 0,
    ``last_idx`` 0, tokens 0) leaves every block but the trash block as it
    was."""
    rng = np.random.default_rng(3)
    kv = init_paged_pool(CFG, 9, BLOCK, kv_dtype=jnp.dtype(kv_dtype))

    def fill(a):
        if a is None:
            return None
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.01, 0.5, a.shape), a.dtype)

    before = tuple(map(fill, kv))
    rows = 4
    out = sch._paged_chunk_impl(
        CFG, *before, gen._decode_view(params, CFG),
        jnp.zeros((rows, CHUNK), jnp.int32),
        jnp.full((rows, MAX_SEQ // BLOCK), TRASH_BLOCK, jnp.int32),
        jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32),
        jnp.zeros((rows, 2), jnp.uint32), jnp.ones(rows), jnp.ones(rows, bool))
    assert out[4].shape == (3, rows)
    for was, now in zip(before, out[:4]):
        if was is None:
            continue
        was, now = np.asarray(was), np.asarray(now)
        kept = np.ones(was.shape[1], bool)
        kept[TRASH_BLOCK] = False
        np.testing.assert_array_equal(was[:, kept], now[:, kept])


@pytest.mark.parametrize("chunk,max_slots,rows", [
    (64, 24, 8), (64, 8, 8), (64, 1, 1), (64, 5, 5), (128, 24, 4),
    (16, 64, 32), (512, 24, 1), (1024, 24, 1), (8, 4, 4)])
def test_a_chunk_call_holds_512_positions_at_most_and_a_slot_at_least(
        chunk, max_slots, rows):
    """The rows of the one compiled chunk program: as many chunks as 512
    positions hold, one where a chunk is longer, never more than the
    slots."""
    assert sch.chunk_call_rows(chunk, max_slots) == rows
    assert 1 <= rows <= max_slots
    assert rows * chunk <= max(sch.CHUNK_CALL_POSITIONS, chunk)


@pytest.fixture
def no_compile_cache():
    """Every compile is a compile: nothing comes out of the persistent
    cache, where a count compiled in a run before would hide."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_a_sweep_of_mid_prefill_counts_compiles_nothing_after_the_first_call(
        no_compile_cache, monkeypatch):
    """The chunk program compiles once, under its compile-once scope, at
    its first call; a run whose mid-prefill count then goes 1, 2, 3, 4 and
    back compiles nothing.  Calls of two rows, so that four mid-prefill
    slots take two calls a tick and one slot a call padded by one; a
    geometry of its own (no program of this process has it before)."""
    monkeypatch.setattr(sch, "CHUNK_CALL_POSITIONS", 2 * CHUNK)
    cfg = dataclasses.replace(CFG, vocab_size=227)
    compiles = CompileRegistry().install()
    try:
        watcher = CompileWatcher(compiles)
        engine = ServingEngine(
            gpt2.init_params(jax.random.PRNGKey(0), cfg), cfg,
            max_slots=SLOTS, max_seq=MAX_SEQ, block_size=BLOCK,
            prefill_chunk=CHUNK, prefix_cache=False, compilewatch=watcher,
            registry=MetricsRegistry(), spans=SpanTracker())
        assert engine.scheduler.chunk_rows == 2
        # the chunk and decode programs and the key stream compile with a
        # first request
        rng = np.random.default_rng(9)
        engine.submit(ServeRequest(
            prompt=rng.integers(1, 190, 20).tolist(), max_new_tokens=3))
        engine.run_until_idle()
        assert "serve_chunk" in watcher.status()
        before = compiles.total
        for _ in range(SLOTS):            # one more mid-prefill slot a tick
            engine.submit(ServeRequest(
                prompt=rng.integers(1, 190, 32).tolist(), max_new_tokens=3))
            engine.step()
        engine.run_until_idle()
        assert compiles.total == before
        assert watcher.storm_total == 0
        spans = engine.spans.closed_spans()
        calls = [s for s in spans if s.name == "serve.prefill_chunk"]
        assert {(c.attrs["rows"], c.attrs["padded"]) for c in calls} == {
            (1, 1), (2, 0)}
        most = max(sum(t.start <= c.start and c.end <= t.end for c in calls)
                   for t in spans if t.name == "serve.tick")
        assert most == 2                  # four slots, two calls a tick
    finally:
        compiles.uninstall()


# -- a description whose chunk is one slot's ---------------------------------

TINY = {
    "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 32, "num_hidden_layers": 4, "num_attention_heads": 4,
    "head_dim": 8, "num_key_value_heads": 2, "vocab_size": 223,
    "moe_intermediate_size": 16, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4096, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0],
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_expert": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 2,
    "deployment": {"serve_config": {"max_seq": 64}},
}


def test_a_decoder_description_makes_one_chunk_call_a_mid_prefill_slot():
    cfg = dataclasses.replace(family.model(TINY), kda_sub_chunk=8,
                              kda_block=4, dtype=jnp.float32)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    family.make_weights(3, TINY))
    engine = ServingEngine(params, cfg, max_slots=3, max_seq=64,
                           block_size=8, prefill_chunk=16,
                           prefix_cache=False, attn_impl="jnp",
                           registry=MetricsRegistry(), spans=SpanTracker())
    assert engine.scheduler.chunk_rows == decoder.CHUNK_ROWS == 1
    lengths = (40, 33, 20)
    rng = np.random.default_rng(1)
    for plen in lengths:
        engine.submit(ServeRequest(
            prompt=rng.integers(0, 223, plen).tolist(), max_new_tokens=3))
    most = 0
    while engine.busy:
        mid = len(engine.scheduler._prefill) if engine.scheduler.tasks \
            else None
        engine.step()
        ticks = [s for s in engine.spans.closed_spans()
                 if s.name == "serve.tick"]
        last = ticks[-1]
        calls = [s for s in engine.spans.closed_spans()
                 if s.name == "serve.prefill_chunk"
                 and last.start <= s.start and s.end <= last.end]
        if mid is not None:
            most = max(most, len(calls))
        assert all(c.attrs["rows"] == 1 and c.attrs["padded"] == 0
                   for c in calls)
    phases = engine.metrics_summary()["tick_phases"]
    chunks = sum(-(-plen // 16) for plen in lengths)
    assert phases["serve.prefill_chunk.dispatch"]["count"] == chunks
    assert phases["serve.prefill_chunk"]["rows"] == chunks
    assert phases["serve.prefill_chunk"]["padded"] == 0
    assert most == len(lengths)         # a tick held three calls
    with pytest.raises(ValueError, match="one slot's"):
        decoder.apply_paged(
            engine.scheduler.view, jnp.zeros((2, 16), jnp.int32),
            engine.scheduler.kv.k, engine.scheduler.kv.v,
            engine.scheduler.state, jnp.zeros((2, 8), jnp.int32),
            jnp.asarray(0, jnp.int32), cfg, jnp.ones((2, 16), bool),
            slot=jnp.asarray(0, jnp.int32))
