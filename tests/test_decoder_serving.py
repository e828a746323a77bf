"""The second served architecture through the engine's normal path, at a
small size on the CPU against the plain reference's full forward: chunked
prefill then decode through the paged pool AND the recurrent state, slots
interleaved; the state's hygiene; the refusals.  The tiny preset keeps the
pattern: 2 periods of (attention, KDA, KDA, KDA), 4 query heads over 2 K/V
heads, 16 experts top-2 of which 4 are held, float32 weights."""

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
from trustworthy_dl_tpu.models import decoder, gpt2
from trustworthy_dl_tpu.serve import (ServeRequest, ServingEngine,
                                      kv_slots, migrate)
from trustworthy_dl_tpu.serve.scheduler import SlotTask, request_key_stream

VOCAB = 211
MAX_SEQ, BLOCK, CHUNK = 96, 8, 16
TINY = {
    "model_type": "solar_open2",
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 32, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 8, "num_key_value_heads": 2, "vocab_size": VOCAB,
    "moe_intermediate_size": 16, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 4096, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4],
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_expert": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 2,
    "deployment": {"serve_config": {"max_seq": MAX_SEQ}},
}
#: Sub-chunks of 8 in blocks of 4, so that a chunk of 16 holds two
#: sub-chunks and each of those two blocks.
CFG = dataclasses.replace(family.model(TINY), kda_sub_chunk=8, kda_block=4,
                          dtype=jnp.float32)

#: Logits through pool and state against the full forward, float32 on both
#: sides: what differs is the ORDER of float32 sums (chunked against
#: recurrent, paged against whole), over 8 layers; the logits' standard
#: deviation is some 0.03 here, so 2e-5 is a thousandth of it.  A bf16
#: state reads 1e-3 (tested below).
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    tree = family.make_weights(3, TINY)
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def served_logits(params, cfg, prompts, replies, attn_impl="jnp",
                  state_dtype=None):
    """Teacher-forced logits of every reply position, through
    ``decoder.apply_paged`` as the scheduler's two programs call it: each
    slot's prompt a chunk a round, the slots that are past their prompt
    decoding together meanwhile (the others idle in the same call)."""
    slots = len(prompts)
    nbps = MAX_SEQ // BLOCK
    kv = kv_slots.init_paged_pool(cfg, slots * nbps, BLOCK)
    state = kv_slots.init_state_pool(cfg, slots)
    tables = np.arange(1, slots * nbps + 1, dtype=np.int32).reshape(
        slots, nbps)
    view = decoder.decode_view(params, cfg)
    pool_k, pool_v = kv.k, kv.v
    fed = [0] * slots                   # positions in pool and state
    out = [[] for _ in range(slots)]

    def settle(state):
        if state_dtype is None:
            return state
        return state._replace(s=state.s.astype(state_dtype).astype(
            jnp.float32))

    while any(len(out[s]) < len(replies[s]) for s in range(slots)):
        decoding = [s for s in range(slots) if fed[s] >= len(prompts[s])
                    and len(out[s]) < len(replies[s])]
        for s in range(slots):
            left = len(prompts[s]) - fed[s]
            if left <= 0:
                continue
            rows = min(CHUNK, left)
            chunk = np.zeros(CHUNK, np.int32)
            chunk[:rows] = prompts[s][fed[s]:fed[s] + rows]
            valid = (np.arange(CHUNK) < rows)[None]
            logits, pool_k, pool_v, state = decoder.apply_paged(
                view, jnp.asarray(chunk)[None], pool_k, pool_v, state,
                jnp.asarray(tables[s:s + 1]), jnp.asarray(fed[s], jnp.int32),
                cfg, jnp.asarray(valid), slot=jnp.asarray(s, jnp.int32),
                last_pos=jnp.asarray(rows - 1, jnp.int32),
                attn_impl=attn_impl)
            state = settle(state)
            fed[s] += rows
            if fed[s] == len(prompts[s]):
                out[s].append(logits[0])
        if decoding:
            tokens = np.zeros(slots, np.int32)
            active = np.zeros(slots, bool)
            for s in decoding:
                tokens[s] = replies[s][len(out[s]) - 1]
                active[s] = True
            rows = np.where(active[:, None], tables, 0)
            logits, pool_k, pool_v, state = decoder.apply_paged(
                view, jnp.asarray(tokens)[:, None], pool_k, pool_v, state,
                jnp.asarray(rows), jnp.asarray(np.asarray(fed, np.int32)),
                cfg, jnp.asarray(active)[:, None], attn_impl=attn_impl)
            state = settle(state)
            for s in decoding:
                fed[s] += 1
                out[s].append(logits[s])
    return [jnp.stack(rows[:len(replies[s])]) for s, rows in enumerate(out)], \
        state


#: A prompt that ends inside a chunk (21), on a chunk's edge (32), on a
#: sub-chunk's edge (24 = 16 + 8) and inside the first chunk (5).
PROMPT_LENGTHS = (21, 32, 24, 5)


def _traffic(seed=0, reply=7):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, VOCAB, n) for n in PROMPT_LENGTHS],
            [rng.integers(0, VOCAB, reply) for _ in PROMPT_LENGTHS])


@pytest.mark.parametrize("attn_impl", ["jnp", "interpret"])
def test_prefill_and_decode_through_pool_and_state_are_the_full_forward(
        params, attn_impl):
    prompts, replies = _traffic()
    got, state = served_logits(params, CFG, prompts, replies, attn_impl)
    for prompt, reply, logits in zip(prompts, replies, got):
        want = family.reply_logits(params, prompt, reply, TINY, 8)
        assert logits.shape == want.shape == (7, VOCAB)
        assert float(jnp.max(jnp.abs(logits - want))) < LOGIT_TOL
    # The counters: every token fed once a layer; pairs near k * held / E.
    fed = sum(len(p) + len(r) - 1 for p, r in zip(prompts, replies))
    assert int(state.expert_tokens) == fed * CFG.n_layer
    pairs = np.asarray(state.expert_pairs)
    assert pairs.shape == (8, 4) and 0 < pairs.sum() <= 2 * fed * 8


def test_a_bf16_state_fails_the_logit_tolerance(params):
    prompts, replies = _traffic()
    got, _ = served_logits(params, CFG, prompts, replies,
                           state_dtype=jnp.bfloat16)
    worst = max(float(jnp.max(jnp.abs(
        logits - family.reply_logits(params, prompt, reply, TINY, 8))))
        for prompt, reply, logits in zip(prompts, replies, got))
    assert worst > 5 * LOGIT_TOL


# -- the engine ---------------------------------------------------------------


def _engine(params, **kwargs):
    kwargs = {"max_slots": 3, "max_seq": MAX_SEQ, "queue_limit": 16,
              "block_size": BLOCK, "prefill_chunk": CHUNK,
              "prefix_cache": False, "attn_impl": "jnp", **kwargs}
    return ServingEngine(params, CFG, **kwargs)


def test_the_engine_serves_the_reference_s_first_choices(params):
    """submit / step / drain_results with the monitor on, more requests
    than slots: greedy tokens are the full forward's first choices (float32
    on both sides, so no near tie flips), streamed as they are made."""
    engine = _engine(params)
    compiled = engine.scheduler.decode_cache_size()   # process-wide count
    rng = np.random.default_rng(1)
    streamed, prompts = {}, {}
    for n in (16, 21, 24, 40, 5, 33):
        prompt = rng.integers(0, VOCAB, n)
        rid = engine.submit(ServeRequest(
            prompt=prompt, max_new_tokens=6, temperature=0.0,
            on_token=lambda r, t: streamed.setdefault(r, []).append(t)))
        prompts[rid] = prompt
    while engine.step() or engine.scheduler.active_count:
        pass
    results = engine.drain_results()
    assert len(results) == 6
    for rid, prompt in prompts.items():
        tokens = [int(t) for t in results[rid].tokens]
        assert results[rid].status == "completed" and len(tokens) == 6
        assert streamed[rid] == tokens
        want = family.chosen_tokens(
            family.reply_logits(params, prompt, tokens, TINY, 8))
        assert tokens == list(want)
    assert engine.scheduler.decode_cache_size() == compiled + 1   # once
    summary = engine.metrics_summary()
    fed = sum(len(p) + 5 for p in prompts.values())
    assert summary["moe"]["tokens_fed"] == fed * CFG.n_layer
    assert summary["moe"]["first_expert"] == 4
    assert len(summary["moe"]["held_expert_pairs"]) == 4
    assert summary["moe"]["since_last_summary"]["tokens_fed"] \
        == fed * CFG.n_layer
    # a state row is zeroed once an admission, one program call each; the
    # phases' window and the experts' are the same two summaries
    phases = summary["tick_phases"]
    assert phases["serve.tick.admit.zero_state"]["count"] == 6
    again = engine.metrics_summary()
    assert again["moe"]["since_last_summary"]["tokens_fed"] == 0
    assert again["tick_phases"]["since_last_summary"][
        "serve.tick.admit.zero_state"]["count"] == 0
    assert summary["state_pool_bytes"] == kv_slots.state_bytes_per_slot(
        CFG) * 3 == engine.scheduler.state.pool_bytes
    assert summary["kv_pool_bytes"] == engine.scheduler.kv.pool_bytes
    from trustworthy_dl_tpu.obs.hbm import CostLedger

    costs = engine.analyze_programs(CostLedger()).to_dict()
    assert set(costs) == {"serve.paged_chunk", "serve.paged_decode"}
    assert all(entry.get("flops") for entry in costs.values()), costs
    # Two attention layers of 2 K/V heads of 8: the pool has no other.
    assert engine.scheduler.kv.k.shape == (2, 3 * 12 + 1, BLOCK, 16)
    assert kv_slots.kv_bytes_per_token(CFG) == 2 * 2 * 2 * 8 * 4


def test_a_slot_s_state_is_zero_at_admission_and_after_release(params):
    engine = _engine(params, max_slots=2)
    sched = engine.scheduler
    rng = np.random.default_rng(2)

    def task(rid):
        return SlotTask(
            request_id=rid, max_new_tokens=4, temperature=0.0,
            prompt=np.asarray(rng.integers(0, VOCAB, 20), np.int32),
            keys=request_key_stream(jax.random.PRNGKey(rid), 4))

    used = lambda rows, slot: float(jnp.abs(rows[:, slot]).max())
    first = task(0)
    assert sched.admit(first)
    slot = first.slot
    while not first.done:
        sched.decode_tick()
    sched.retire(first)
    assert used(sched.state.s, slot) > 0 and used(sched.state.conv, slot) > 0
    # The next request admitted to the slot starts from zero.
    second = task(1)
    assert sched.admit(second) and second.slot == slot
    assert used(sched.state.s, slot) == 0.0
    assert used(sched.state.conv, slot) == 0.0
    sched.decode_tick()
    assert used(sched.state.s, slot) > 0
    # Quarantined with its request, released by the operator: zero again,
    # and the slot that was never used still is.
    sched.retire(second, quarantine=True)
    assert slot in engine.quarantined_slots
    assert used(sched.state.s, slot) > 0
    engine.release_quarantine(slot)
    assert not engine.quarantined_slots
    assert used(sched.state.s, slot) == 0.0
    assert used(sched.state.conv, slot) == 0.0
    assert used(sched.state.s, 1 - slot) == 0.0


@pytest.mark.parametrize("kwargs,mechanism", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_k": 2}, "spec_k"),
    ({"adapter_rank": 4}, "adapter_rank"),
    ({"kv_dtype": "int8", "kv_parity_check": False}, "int8"),
    ({"weight_dtype": "int8"}, "int8"),
    ({"tp_size": 2}, "tp_size"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_description_cannot_do_yet_is_refused(params, kwargs,
                                                       mechanism):
    with pytest.raises(ValueError, match=mechanism):
        _engine(params, **kwargs)


def test_a_state_snapshot_for_migration_is_refused(params):
    engine, other = _engine(params), _engine(params)
    rid = engine.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=4,
                                     temperature=0.0))
    engine.step()
    assert not migrate.can_migrate(engine, other)
    task = next(iter(engine.scheduler.tasks.values()))
    assert task.request_id == rid
    with pytest.raises(ValueError, match="snapshot"):
        engine.scheduler.export_migration(task)


def test_gpt2_keeps_its_description_and_has_no_state():
    cfg = gpt2.GPT2Config(n_layer=2, n_embd=32, n_head=4, vocab_size=193,
                          n_positions=32)
    engine = ServingEngine(gpt2.init_params(jax.random.PRNGKey(0), cfg), cfg,
                           max_slots=2, max_seq=32, block_size=8)
    assert engine.scheduler.state is None and not engine.scheduler.recurrent
    assert kv_slots.state_bytes_per_slot(cfg) == 0
    assert kv_slots.kv_geometry(cfg) == (2, 4, 8)
    engine.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=3,
                               temperature=0.0))
    while engine.step() or engine.scheduler.active_count:
        pass
    summary = engine.metrics_summary()
    assert summary["state_pool_bytes"] == 0 and "moe" not in summary
    assert summary["requests_completed"] == 1


def test_the_description_holds_whole_periods_and_a_share():
    assert CFG.n_layer == 8 and CFG.n_attn_layers == 2
    assert CFG.n_kda_layers == 6 and CFG.conv_channels == 96
    assert CFG.period == ("attn", "kda", "kda", "kda")
    hash(CFG)                                   # a static jit argument
    with pytest.raises(ValueError, match="period"):
        dataclasses.replace(CFG, period=("attn", "mamba"))
    with pytest.raises(ValueError, match="experts"):
        dataclasses.replace(CFG, first_expert=14)
    with pytest.raises(ValueError, match="K/V heads"):
        dataclasses.replace(CFG, kv_heads=3)
