"""The third kind of operator through the engine's normal path, at a small
size on the CPU against the Kimi Linear family's plain reference: a leading
dense layer, latent attention (MLA, no rotary term) over the paged LATENT
cache beside the KDA state rows, the renormalised scaled router.  The tiny
preset keeps the pattern: a leading KDA + dense-MLP layer, then 2 periods of
(KDA, KDA, MLA, KDA); 4 heads of 8 + 4 over a latent of 16 + 4; 16 experts
top-2 of which 8 are held; float32 weights, so every tolerance is float32
rounding."""

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

from benchmark.harness.families import kimi_linear as family
from benchmark.harness.families import reference_kimi_linear as ref
from benchmark.harness.families import solar_open2
from test_decoder_serving import (BLOCK, CHUNK, LOGIT_TOL, MAX_SEQ,
                                  PROMPT_LENGTHS, served_logits)
from test_decoder_serving import CFG as SOLAR_CFG
from test_decoder_serving import TINY as SOLAR_TINY
from trustworthy_dl_tpu.models import decoder, moe
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.ops import latent_attention as la
from trustworthy_dl_tpu.ops import paged_attention as pa
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine, kv_slots

VOCAB = 223
TINY = {
    "model_type": "kimi_linear", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 9, "first_k_dense_replace": 1,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7, 9],
                           "full_attn_layers": [4, 8], "head_dim": 8,
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None,
    "mla_use_nope": True, "model_max_length": 4096,
    "moe_intermediate_size": 16, "moe_layer_freq": 1,
    "moe_renormalize": True, "num_expert_group": 1, "num_experts": 8,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
    "vocab_size": VOCAB,
    "deployment": {"first_expert": 8, "num_experts_published": 16,
                   "serve_config": {"max_seq": MAX_SEQ}},
}
CFG = dataclasses.replace(family.model(TINY), kda_sub_chunk=8, kda_block=4,
                          dtype=jnp.float32)
SHAPE = family.sizes(TINY)


@pytest.fixture(scope="module", autouse=True)
def _a_registry_of_this_file_s_own():
    """The engines built here fill the program's process-wide obs registry;
    a benchmark reader's test that runs later in the same process expects
    to find nothing there."""
    from trustworthy_dl_tpu.obs import registry

    was = registry._DEFAULT_REGISTRY
    registry._DEFAULT_REGISTRY = registry.MetricsRegistry()
    yield
    registry._DEFAULT_REGISTRY = was


@pytest.fixture(scope="module")
def params():
    tree = family.make_weights(3, TINY)
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _traffic(seed=0, reply=7):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, VOCAB, n) for n in PROMPT_LENGTHS],
            [rng.integers(0, VOCAB, reply) for _ in PROMPT_LENGTHS])


# -- (a) the whole forward through the latent cache ----------------------------


def test_the_description_states_the_lead_the_period_and_the_latent_row():
    assert CFG.lead == ("kda",) and CFG.n_periods == 2
    assert CFG.period == ("kda", "kda", "mla", "kda")
    assert (CFG.n_layer, CFG.n_expert_layers) == (9, 8)
    assert (CFG.n_kda_layers, CFG.n_mla_layers, CFG.n_attn_layers) == (
        7, 2, 0)
    assert (CFG.latent_width, CFG.latent_lanes) == (20, 128)
    assert CFG.kda_beta_scale == 1.0
    hash(CFG)                                   # a static jit argument
    with pytest.raises(ValueError, match="not both"):
        dataclasses.replace(CFG, period=("attn", "mla"))
    with pytest.raises(ValueError, match="intermediate_size"):
        dataclasses.replace(CFG, intermediate_size=0)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        dataclasses.replace(CFG, kv_lora_rank=0)
    with pytest.raises(ValueError, match="do not name each"):
        family.sizes(dict(TINY, num_hidden_layers=10))


@pytest.mark.parametrize("attn_impl", ["jnp", "interpret"])
def test_prefill_and_decode_through_the_latent_cache_are_the_full_forward(
        params, attn_impl):
    """LOGITS compared: chunked prefill then decode, slots interleaved,
    through the absorbed form over the paged latent rows and the chunked
    delta rule, against the reference's expanded attention and recurrence."""
    prompts, replies = _traffic()
    got, state = served_logits(params, CFG, prompts, replies, attn_impl)
    for prompt, reply, logits in zip(prompts, replies, got):
        want = family.reply_logits(params, prompt, reply, TINY, 8)
        assert logits.shape == want.shape == (7, VOCAB)
        assert float(jnp.max(jnp.abs(logits - want))) < LOGIT_TOL
    # The counters count EXPERT layers: the leading layer feeds none.
    fed = sum(len(p) + len(r) - 1 for p, r in zip(prompts, replies))
    assert int(state.expert_tokens) == fed * 8
    pairs = np.asarray(state.expert_pairs)
    assert pairs.shape == (8, 8) and 0 < pairs.sum() <= 2 * fed * 8


def test_the_engine_serves_the_reference_s_first_choices(params):
    engine = ServingEngine(params, CFG, max_slots=3, max_seq=MAX_SEQ,
                           queue_limit=16, block_size=BLOCK,
                           prefill_chunk=CHUNK, prefix_cache=False,
                           attn_impl="jnp")
    kv = engine.scheduler.kv
    assert kv.v is None and kv.k.shape == (2, 3 * 12 + 1, BLOCK, 128)
    rng = np.random.default_rng(1)
    prompts = {}
    for n in (16, 21, 40, 5, 33):
        prompt = rng.integers(0, VOCAB, n)
        prompts[engine.submit(ServeRequest(
            prompt=prompt, max_new_tokens=6, temperature=0.0))] = prompt
    while engine.step() or engine.scheduler.active_count:
        pass
    results = engine.drain_results()
    for rid, prompt in prompts.items():
        tokens = [int(t) for t in results[rid].tokens]
        assert results[rid].status == "completed" and len(tokens) == 6
        assert tokens == list(family.chosen_tokens(
            family.reply_logits(params, prompt, tokens, TINY, 8)))
    summary = engine.metrics_summary()
    assert summary["latent_pool_bytes"] == summary["kv_pool_bytes"] \
        == kv.k.nbytes
    assert summary["state_pool_bytes"] == 3 * kv_slots.state_bytes_per_slot(
        CFG)
    fed = sum(len(p) + 5 for p in prompts.values())
    assert summary["moe"]["tokens_fed"] == fed * 8
    again = engine.metrics_summary()["moe"]["since_last_summary"]
    assert again["tokens_fed"] == 0             # a difference, modulo 2**32


@pytest.mark.parametrize("refused", [
    {"prefix_cache": True}, {"spec_k": 2}, {"kv_dtype": "int8"},
    {"weight_dtype": "int8"}], ids=lambda r: next(iter(r)))
def test_what_a_recurrent_description_refuses_is_refused_here_too(
        params, refused):
    kwargs = {"max_slots": 2, "max_seq": MAX_SEQ, "block_size": BLOCK,
              "prefill_chunk": CHUNK, "prefix_cache": False,
              "attn_impl": "jnp", **refused}
    with pytest.raises(ValueError):
        ServingEngine(params, CFG, **kwargs)


# -- (b) absorbed = expanded ---------------------------------------------------


def test_the_absorbed_form_is_the_expanded_form(params):
    """The program's three pieces (the cached row, the absorbed queries,
    the expansion of the heads' sums) with a plain causal softmax between
    them, against the reference's per-head keys and values, on random
    inputs: float32 sums in another order."""
    p = jax.tree_util.tree_map(lambda a: a[1], params["periods"][2]["mla"])
    xn = jnp.asarray(np.random.default_rng(5).normal(size=(1, 40, 32)),
                     jnp.float32)
    rows = decoder.latent_rows(p, xn, CFG)                  # [1, T, lanes]
    assert rows.shape == (1, 40, 128)
    assert float(jnp.max(jnp.abs(rows[..., 20:]))) == 0.0   # the padding
    q = decoder.absorbed_queries(p, xn, CFG)                # [1, H, T, lanes]
    scores = jnp.einsum("rhtc,rkc->rhtk", q, rows) / np.sqrt(12.0)
    causal = jnp.tril(jnp.ones((40, 40), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    u = jnp.einsum("rhtk,rkc->rhtc", probs, rows[..., :16])
    got = decoder.expanded_output(p, u, CFG)[0]
    want = ref.latent_attention(p, xn[0], SHAPE)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
    # ... and without the unrotated 'rope' columns it is another function.
    lost = ref.latent_attention(
        dict(p, w_kv_a=p["w_kv_a"].at[:, 16:].set(0)), xn[0], SHAPE)
    assert float(jnp.max(jnp.abs(lost - want))) > 1e-3 * float(
        jnp.max(jnp.abs(want)))


# -- (c) the two kernels of the latent cache -----------------------------------


@pytest.mark.parametrize("rows,starts", [
    (5, (0, 7, 8, 30, 47)),             # ragged, a block's edges
    (3, (3, 0, 9)),                     # an idle row reads the trash block
], ids=str)
def test_the_latent_decode_kernel_is_the_gathered_reference(rows, starts):
    """The ONE paged kernel in its latent shape (absorbed queries against
    the shared row, the values its first lanes), interpret mode."""
    heads, lanes, v_lanes, nbps = 4, 128, 16, 6
    rng = np.random.default_rng(rows)
    pool = jnp.asarray(rng.normal(size=(2, rows * nbps + 1, BLOCK, lanes)),
                       jnp.float32)
    table = np.arange(1, rows * nbps + 1, dtype=np.int32).reshape(rows, nbps)
    if rows == 3:
        table[1] = kv_slots.TRASH_BLOCK
    q = jnp.asarray(rng.normal(size=(rows, heads, 1, lanes)), jnp.float32)
    start = jnp.asarray(starts, jnp.int32)
    shape = dict(layer=jnp.asarray(1), v_lanes=v_lanes, scale=0.3)
    want = pa.paged_attention_reference(q, pool, None, jnp.asarray(table),
                                        start, **shape)
    got = pa.paged_attention(q, pool, None, jnp.asarray(table), start,
                             interpret=True, **shape)
    assert got.shape == want.shape == (rows, heads, 1, v_lanes)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6
    with pytest.raises(ValueError, match="latent shape"):
        pa.paged_attention(q, pool, None, jnp.asarray(table), start,
                           interpret=True)
    with pytest.raises(ValueError, match="latent shape"):
        pa._attend("prefill", q, pool, None, jnp.asarray(table), start,
                   jnp.asarray(1), None, None, True, v_lanes, 0.3)


def test_the_latent_walk_ends_where_the_row_does():
    """The walk inside the step in the latent shape (one tile a wave, no
    V): rows of length 0 (all trash: one wave all the same), 1, exactly a
    wave, one position into the second, and the full table of two and a
    half waves, against the gathered reference; and the blocks walked at
    the long-context cell's geometry by hand (blocks of 256, 128 a row: a
    row of 9,275 positions holds 37 blocks)."""
    heads, lanes, v_lanes, bsz = 4, 128, 16, 8
    wave = pa.WAVE_POSITIONS // bsz
    nbps = 5 * wave // 2
    assert pa._step_shape("decode", heads=1, head_dim=lanes, block_size=bsz,
                          kv_dtype="float32", t=1, rep=heads,
                          v_lanes=v_lanes) == (1, 2, wave)
    lengths = [0, 1, wave * bsz, wave * bsz + 1, nbps * bsz]
    rng = np.random.default_rng(11)
    pool = jnp.asarray(rng.normal(size=(2, 41, bsz, lanes)), jnp.float32)
    table = np.asarray(1 + rng.integers(0, 40, size=(5, nbps)), np.int32)
    table[0] = kv_slots.TRASH_BLOCK
    start = jnp.asarray([max(n - 1, 0) for n in lengths], jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, heads, 1, lanes)), jnp.float32)
    shape = dict(layer=jnp.asarray(1), v_lanes=v_lanes, scale=0.3)
    want = pa.paged_attention_reference(q, pool, None, jnp.asarray(table),
                                        start, **shape)
    got = pa.paged_attention(q, pool, None, jnp.asarray(table), start,
                             interpret=True, **shape)
    assert float(jnp.max(jnp.abs(got - want))) < 4e-6
    kw = dict(kv_heads=1, v_lanes=v_lanes)
    assert [pa.walked_blocks("decode", n, heads, nbps, 1, lanes, bsz,
                             "float32", **kw) for n in lengths] \
        == [wave, wave, wave, 2 * wave, 3 * wave]
    cell = (32, 128, 1, 640, 256, jnp.bfloat16)
    w = pa.WAVE_POSITIONS // 256
    assert [pa.walked_blocks("decode", n, *cell, kv_heads=1, v_lanes=512)
            for n in (9275, 0, 32768)] == [-(-37 // w) * w, w, 128]


@pytest.mark.parametrize("t,start", [(16, 0), (16, 24), (24, 8), (5, 3),
                                     (16, 40)], ids=str)
def test_the_latent_chunk_kernel_is_the_gathered_reference(t, start):
    """The expanded kernel (a block's rows times W_kb inside it, attention
    at the per-head widths) against the gathered, fully expanded softmax:
    chunks from 0, from a block's edge, from inside a block, one that runs
    past the slot's table (its padding reads the last block, masked)."""
    heads, nope, rope, value, rank, lanes = 4, 8, 4, 8, 16, 128
    rng = np.random.default_rng(t + start)
    pool = jnp.asarray(rng.normal(size=(2, 13, BLOCK, lanes)), jnp.float32)
    pool = pool.at[..., rank + rope:].set(0)
    w_kb = jnp.asarray(rng.normal(size=(rank, heads * (nope + value))),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, 13))[None, :6],
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(heads, t, nope + rope)), jnp.float32)
    want = la.latent_prefill_reference(q, w_kb, pool, table,
                                       jnp.asarray(start), layer=1,
                                       nope=nope)
    got = la.latent_prefill_attention(q, w_kb, pool, table,
                                      jnp.asarray(start),
                                      layer=jnp.asarray(1), nope=nope,
                                      interpret=True)
    assert got.shape == want.shape == (heads, t, value)
    real = min(t, 6 * BLOCK - start)            # rows inside the table
    assert float(jnp.max(jnp.abs(got[:, :real] - want[:, :real]))) < 1e-5
    assert bool(jnp.all(jnp.isfinite(got)))


def test_the_rules_for_what_a_step_holds_at_the_cell_s_shape():
    """Decode: the tile is the 32 heads of ONE position (not 8 positions
    of them) and one block feeds both products.  A chunk: 2 heads' 1,024
    queries resident, so a block is expanded 16 times a call, not 64."""
    bf16 = jnp.bfloat16
    assert pa.grid_steps("decode", 64, 32, 128, 1, 640, 256, bf16,
                         kv_heads=1, v_lanes=512) == (64, 1, 1, 128)
    assert pa._step_shape("decode", heads=1, head_dim=640, block_size=256,
                          kv_dtype=bf16, t=1, rep=32,
                          v_lanes=512) == (1, 1, pa.WAVE_POSITIONS // 256)
    with_v = pa._pipelined_block_bytes(
        "decode", head_dim=640, block_size=256, kv_dtype=bf16, q_tile=32)
    latent = pa._pipelined_block_bytes(
        "decode", head_dim=640, block_size=256, kv_dtype=bf16, q_tile=32,
        v_lanes=512)
    assert latent < with_v
    shape = dict(nope=128, value=128, rank=512, lanes=640, block_size=256,
                 dtype=bf16)
    assert la.head_group(32, 1024, **shape) == 2
    assert la.head_group(32, 256, **shape) == 4
    assert la.step_bytes(2, 1024, **shape) <= pa.VMEM_BLOCK_BUDGET \
        < la.step_bytes(4, 1024, **shape)
    assert la.supports_latent_prefill(heads=32, rows=1024, interpret=False,
                                      **shape)
    assert not la.supports_latent_prefill(heads=32, rows=8192,
                                          interpret=False, **shape)
    assert not la.supports_latent_prefill(
        heads=32, rows=1024, interpret=False, **dict(shape, lanes=576))


# -- (d) the share test --------------------------------------------------------


def test_the_two_chips_shares_add_up_to_the_whole_layer(params):
    """THE share test of the guide's section 4: the two chips' routed
    parts (experts 0..7 and 8..15, scaled router, renormalised) plus the
    shared expert counted ONCE are the uncut layer of the reference."""
    rng = np.random.default_rng(7)
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    p = {"router": draw(32, 16), "router_bias": draw(16) * 0.1,
         "w_gate_up": draw(16, 32, 32), "w_down": draw(16, 16, 32),
         "shared_gate_up": draw(32, 32), "shared_down": draw(16, 32)}
    x = draw(40, 32) / 0.3
    whole = ref.expert_layer(p, x, dict(SHAPE, first_expert=0,
                                        n_experts_held=16))
    chosen, weights = moe.route_top_k(x, p["router"], p["router_bias"], 2,
                                      True, 2.446)
    parts, pairs = zip(*(moe.held_experts(
        x, chosen, weights, p["w_gate_up"][first:first + 8],
        p["w_down"][first:first + 8], first) for first in (0, 8)))
    shared = L.silu_gated_mlp(p["shared_gate_up"], p["shared_down"], x)
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) \
        < 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert int(sum(jnp.sum(n) for n in pairs)) == 40 * 2
    one = ref.expert_layer(
        dict(p, w_gate_up=p["w_gate_up"][8:], w_down=p["w_down"][8:]), x,
        dict(SHAPE, first_expert=8, n_experts_held=8))
    assert float(jnp.max(jnp.abs(parts[1] + shared - one))) \
        < 1e-5 * float(jnp.max(jnp.abs(whole)))


# -- (e) the byte budget -------------------------------------------------------


def test_a_latent_layer_costs_one_row_a_position_and_no_v():
    big = family.model(_published())
    assert kv_slots.kv_geometry(big) == (1, 1, 640)
    assert kv_slots.kv_arrays(big) == 1
    assert kv_slots.latent_value_lanes(big) == 512
    assert kv_slots.kv_bytes_per_token(big) == 640 * 2
    assert kv_slots.kv_bytes_per_token(big, jnp.float32) == 640 * 4
    # Per-head K and V of the same 32 heads would be 32 x (192 + 128) x 2 B.
    assert 32 * (192 + 128) * 2 / kv_slots.kv_bytes_per_token(big) == 16.0
    budget = (64 * 128 + 1) * 256 * 1280
    assert kv_slots.paged_pool_blocks(big, budget, 256) == 64 * 128
    assert kv_slots.paged_pool_blocks(big, budget - 1, 256) == 64 * 128 - 1
    # The state rows: 4 KDA layers, the leading one among them.
    assert big.n_kda_layers == 4 and big.lead == ("kda",)
    assert kv_slots.state_bytes_per_slot(big) == 4 * 4 * (
        32 * 128 * 128 + 3 * 12288)
    assert kv_slots.kv_arrays(SOLAR_CFG) == 2
    assert kv_slots.latent_value_lanes(SOLAR_CFG) is None


def test_the_pools_are_sized_by_the_layers_of_each_kind():
    kv = kv_slots.init_paged_pool(CFG, 10, BLOCK)
    assert kv.v is None and kv.k.shape == (2, 11, BLOCK, 128)
    assert kv.k.dtype == jnp.float32 and not kv.quantized
    assert kv.pool_bytes == kv.k.nbytes
    assert kv.bytes_per_block == BLOCK * kv_slots.kv_bytes_per_token(CFG)
    state = kv_slots.init_state_pool(CFG, 3)
    assert state.s.shape == (7, 3, 4, 8, 8)     # lead + 2 x 3 of a period
    assert state.conv.shape == (7, 3, 3, 96)
    assert state.expert_pairs.shape == (8, 8)   # the EXPERT layers alone
    assert state.pool_bytes == 3 * kv_slots.state_bytes_per_slot(CFG)


def test_the_hbm_gate_budgets_latent_rows_beside_the_state(params):
    """A monitor with room for the state rows and 40 blocks of latent rows:
    the pool shrinks to what that leaves, by the latent row's bytes."""

    class Gate:
        last_headroom = None

        def __init__(self, headroom):
            self.headroom = headroom

        def admit(self, requested, what=""):
            self.last_headroom = self.headroom
            return requested <= self.headroom

    per_block = BLOCK * kv_slots.kv_bytes_per_token(CFG)
    state_bytes = 3 * kv_slots.state_bytes_per_slot(CFG)
    engine = ServingEngine(
        params, CFG, max_slots=3, max_seq=MAX_SEQ, block_size=BLOCK,
        num_blocks=64, prefill_chunk=CHUNK, prefix_cache=False,
        attn_impl="jnp", hbm=Gate(state_bytes + 40 * per_block + 5))
    assert engine.scheduler.kv.num_blocks == 40
    assert engine.scheduler.kv.v is None


# -- (f) the description PR 36 built runs what it ran --------------------------


def _published():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-ep2-1of2.json")) as f:
        return json.load(f)


def test_a_description_built_as_before_has_the_constants_it_had():
    """The new fields' defaults are what PR 36 hard-coded: no leading
    layer, ``beta = 2 sigmoid``, every layer an expert layer, and Solar's
    own plain reference (which hard-codes the 2) still agrees with it,
    where a description that states 1 does not."""
    built = solar_open2.model(SOLAR_TINY)
    assert (built.lead, built.kda_beta_scale, built.intermediate_size) == (
        (), 2.0, 0)
    assert built.n_expert_layers == built.n_layer == 8
    assert built.n_mla_layers == 0 and built.kv_lora_rank == 0
    weights = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        solar_open2.make_weights(3, SOLAR_TINY))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 211, n) for n in (21, 24)]
    replies = [rng.integers(0, 211, 4) for _ in prompts]
    got, _ = served_logits(weights, SOLAR_CFG, prompts, replies)
    other, _ = served_logits(
        weights, dataclasses.replace(SOLAR_CFG, kda_beta_scale=1.0),
        prompts, replies)
    for prompt, reply, logits, moved in zip(prompts, replies, got, other):
        want = solar_open2.reply_logits(weights, prompt, reply, SOLAR_TINY,
                                        8)[:4]
        assert float(jnp.max(jnp.abs(logits - want))) < LOGIT_TOL
        assert float(jnp.max(jnp.abs(moved - want))) > 5 * LOGIT_TOL
