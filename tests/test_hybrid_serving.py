"""The fourth kind of operator through the engine's normal path, at a small
size on the CPU against the Falcon-H1 family's plain reference: every layer
a Mamba-2 mixer beside rotary grouped attention on one normed input, then a
dense MLP, with the file's muP multipliers.  The tiny preset keeps what is
hard: 2 layers of 256; 10 query heads over 2 K/V heads of 16 (5 a group, the
cell's); a mixer of 8 heads of 16 with a state of 32 in 2 groups; blocks of
8 positions in the chunked form, so that a chunk of 16 crosses one; float32
weights, so every tolerance is float32 rounding."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.families import falcon_h1 as family
from benchmark.harness.families import reference_falcon_h1 as ref
from test_decoder_serving import (BLOCK, CHUNK, MAX_SEQ, PROMPT_LENGTHS,
                                  served_logits)
from trustworthy_dl_tpu.models import decoder, mamba2
from trustworthy_dl_tpu.ops import ssm_step as ss
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine, kv_slots
from trustworthy_dl_tpu.serve import migrate

VOCAB = 512
with open(os.path.join(ROOT, "benchmark", "configs",
                       "falcon-h1-34b-pp18-1of18.json")) as _f:
    FILE = json.load(_f)
TINY = dict(FILE, hidden_size=256, num_attention_heads=10,
            num_key_value_heads=2, head_dim=16, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_ssm=128, mamba_d_state=32,
            mamba_n_groups=2, mamba_chunk_size=8, num_hidden_layers=2,
            vocab_size=VOCAB, intermediate_size=384, rope_theta=10000.0,
            published={"num_hidden_layers": 2})
TINY["deployment"] = dict(
    FILE["deployment"], prefill_chunk_positions=CHUNK,
    serve_config=dict(FILE["deployment"]["serve_config"], max_seq=MAX_SEQ))
CFG = dataclasses.replace(family.model(TINY), dtype=jnp.float32)
SHAPE = family.sizes(TINY)

#: Logits through pool and state against the full forward, float32 on both
#: sides, as a share of the logits' standard deviation: what differs is the
#: ORDER of float32 sums (chunked against recurrent, paged against whole),
#: 1.7e-6 to 2.1e-6 of it here.  The layers move the logits little beside
#: what the embedding (times 5.66) carries to the head, so a bfloat16 state
#: reads only 4.9e-5 (tested below, as is a dropped multiplier, 0.06 and
#: more).
LOGIT_TOL = 5e-6


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


def _worst(got, prompts, replies, params, shape=TINY):
    """The largest gap of served logits from the reference's, over the
    reference's standard deviation, across the requests."""
    worst = 0.0
    for prompt, reply, logits in zip(prompts, replies, got):
        want = family.reply_logits(params, prompt, reply, shape, 8)
        assert logits.shape == want.shape == (len(reply), VOCAB)
        worst = max(worst, float(jnp.max(jnp.abs(logits - want))
                                 / jnp.std(want)))
    return worst


# -- (a) the whole forward through pool and state -----------------------------


def test_the_description_states_the_hybrid_layer():
    assert CFG.period == ("hybrid",) and CFG.n_periods == 2
    assert (CFG.n_layer, CFG.n_hybrid_layers, CFG.n_attn_layers,
            CFG.n_kda_layers, CFG.n_expert_layers) == (2, 2, 2, 0, 0)
    assert (CFG.mamba_width, CFG.mamba_conv_channels) == (128, 256)
    assert not CFG.attn_gate and CFG.rope_theta == 10000.0
    with pytest.raises(ValueError, match="not both"):
        dataclasses.replace(CFG, period=("hybrid", "attn"))
    with pytest.raises(ValueError, match="intermediate_size"):
        dataclasses.replace(CFG, intermediate_size=0)
    with pytest.raises(ValueError, match="mamba_heads"):
        dataclasses.replace(CFG, mamba_state=0)
    with pytest.raises(ValueError, match="groups"):
        dataclasses.replace(CFG, mamba_groups=3)
    with pytest.raises(ValueError, match="ssm_multipliers"):
        dataclasses.replace(CFG, ssm_multipliers=(1.0, 1.0))


@pytest.mark.parametrize("attn_impl", ["jnp", "interpret"])
def test_prefill_and_decode_through_pool_and_state_are_the_full_forward(
        params, attn_impl):
    """LOGITS compared: chunked paged prefill then decode, slots
    interleaved, through the rotated K/V pool and the Mamba-2 state rows
    (the interpret path through ``ssm_step`` and the paged kernels at 5
    query heads a K/V head), against the reference's recurrence and whole
    softmax."""
    prompts, replies = _traffic()
    got, state = served_logits(params, CFG, prompts, replies, attn_impl)
    assert _worst(got, prompts, replies, params) < LOGIT_TOL
    assert state.s is None and state.expert_pairs is None
    assert state.ssm.shape == (2, 4, 8, 16, 32)
    assert state.ssm_conv.shape == (2, 4, 3, 256)


def test_a_bfloat16_state_or_a_dropped_multiplier_fails_the_tolerance(
        params):
    prompts, replies = _traffic()
    got, _ = served_logits(params, CFG, prompts, replies,
                           state_dtype=jnp.bfloat16)
    assert _worst(got, prompts, replies, params) > 2 * LOGIT_TOL
    for field in ("key_multiplier", "ssm_out_multiplier",
                  "ssm_in_multiplier", "lm_head_multiplier"):
        dropped = dataclasses.replace(CFG, **{field: 1.0})
        got, _ = served_logits(params, dropped, prompts[:1], replies[:1])
        assert _worst(got, prompts[:1], replies[:1], params) \
            > 100 * LOGIT_TOL, field
    got, _ = served_logits(params, dataclasses.replace(
        CFG, ssm_multipliers=(1.0,) * 5), prompts[:1], replies[:1])
    assert _worst(got, prompts[:1], replies[:1], params) > 100 * LOGIT_TOL


# -- (b) the recurrence in its two forms ---------------------------------------


def _ssm_inputs(seed, r=2, t=24, heads=8, p=16, groups=2, n=32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, b, c = f(r, t, heads, p), f(r, t, groups, n), f(r, t, groups, n)
    dt = jax.nn.softplus(f(r, t, heads) - 2.0)
    a = -jnp.exp(f(heads) * 0.5)
    d = f(heads)
    h0 = f(r, heads, p, n) * 0.3
    return x, dt, a, b, c, d, h0


def test_the_chunked_form_is_the_recurrence():
    """From a NON-zero state, over 24 positions in blocks of 8 (two block
    edges inside the call) and in two calls that meet off a block's edge
    (13 + 11), with a padded position of dt 0 that leaves the state as it
    was: float32 sums in another order."""
    x, dt, a, b, c, d, h0 = _ssm_inputs(0)
    dt = dt.at[:, -1].set(0.0)
    h, ys = h0, []
    for t in range(x.shape[1]):
        y, h = mamba2.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], d, h)
        ys.append(y)
    want_y, want_h = jnp.stack(ys, axis=1), h
    scale = float(jnp.max(jnp.abs(want_y)))
    got_y, got_h = mamba2.ssd_chunk(x, dt, a, b, c, d, h0, 8)
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(got_h - want_h))) < 1e-5 * float(
        jnp.max(jnp.abs(want_h)))
    # The padded last position: the state after 23 positions.
    _, h23 = mamba2.ssd_chunk(x[:, :23], dt[:, :23], a, b[:, :23],
                              c[:, :23], d, h0, 23)
    assert float(jnp.max(jnp.abs(got_h - h23))) < 1e-5 * float(
        jnp.max(jnp.abs(h23)))
    cut = lambda z, s: z[:, s]
    y1, h1 = mamba2.ssd_chunk(*(cut(z, slice(0, 13)) for z in (x, dt)), a,
                              cut(b, slice(0, 13)), cut(c, slice(0, 13)), d,
                              h0, 8 if 13 % 8 == 0 else 13)
    y2, h2 = mamba2.ssd_chunk(*(cut(z, slice(13, 24)) for z in (x, dt)), a,
                              cut(b, slice(13, 24)), cut(c, slice(13, 24)),
                              d, h1, 11)
    assert float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - want_y))) \
        < 1e-5 * scale
    with pytest.raises(ValueError, match="whole blocks"):
        mamba2.ssd_chunk(x[:, :20], dt[:, :20], a, b[:, :20], c[:, :20], d,
                         h0, 8)


def test_the_step_kernel_is_the_xla_step():
    """``ssm_step`` in interpret mode on layer 1 of a stacked state: the
    XLA one-step form's output and state, the other layers untouched, a
    slot of dt 0 kept as it was."""
    x, dt, a, b, c, d, _ = _ssm_inputs(1, r=3, t=1)
    x, dt, b, c = x[:, 0], dt[:, 0].at[2].set(0.0), b[:, 0], c[:, 0]
    state = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 3, 8, 16, 32)), jnp.float32)
    want_y, want_h = mamba2.ssd_step(x, dt, a, b, c, d, state[1])
    got_y, got = ss.ssm_step(state, jnp.int32(1), x, dt, a, b, c, d,
                             interpret=True)
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-5 * float(
        jnp.max(jnp.abs(want_y)))
    assert float(jnp.max(jnp.abs(got[1] - want_h))) < 1e-6 * float(
        jnp.max(jnp.abs(want_h)))
    assert np.array_equal(got[0], state[0])
    assert np.array_equal(got[1, 2], state[1, 2])
    # A step of 16 heads of 128 x 256 holds a group's 8 heads (1 MiB).
    assert ss.heads_a_step(32, 2, 128, 256) == 8
    assert ss.heads_a_step(8, 2, 16, 32) == 4


def test_the_gate_comes_before_the_group_norm():
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.normal(size=(5, 128)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(5, 128)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(128,)), jnp.float32)
    got = mamba2.gated_group_norm(scale, y, z, 2, 1e-5)
    g = (y * jax.nn.silu(z)).reshape(5, 2, 64)
    want = (g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
            ).reshape(5, 128) * scale
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # ... a group at a time: one norm over all 128 is another function.
    whole = mamba2.gated_group_norm(scale, y, z, 1, 1e-5)
    assert float(jnp.max(jnp.abs(whole - want))) > 1e-3


# -- (c) the rotary keys in the pool -------------------------------------------


def test_the_pool_holds_each_key_rotated_at_its_position(params):
    """After a chunk of 16 from position 8 (the slot's second), the pool's
    row at position p is ``RoPE(k_p, p)`` times the key multiplier; the
    queries and the paged kernels see only rotated keys."""
    p = jax.tree_util.tree_map(lambda a: a[0], params["periods"][0]["attn"])
    nbps = MAX_SEQ // BLOCK
    kv = kv_slots.init_paged_pool(CFG, nbps, BLOCK)
    table = jnp.arange(1, nbps + 1, dtype=jnp.int32)[None]
    xn = jnp.asarray(np.random.default_rng(4).normal(size=(1, CHUNK, 256)),
                     jnp.float32)
    _, pool_k, _ = decoder._gated_attention(
        p, xn, kv.k, kv.v, table, jnp.int32(8), jnp.int32(0), CFG, "jnp")
    k = (xn[0] @ p["wk"]).reshape(CHUNK, 2, 16) * CFG.key_multiplier
    want = ref.rotate(k, 8 + jnp.arange(CHUNK), CFG.rope_theta)
    rows = pool_k[0, 1:].reshape(-1, 32)[8:8 + CHUNK].reshape(CHUNK, 2, 16)
    assert float(jnp.max(jnp.abs(rows - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
    shifted = ref.rotate(k, 9 + jnp.arange(CHUNK), CFG.rope_theta)
    assert float(jnp.max(jnp.abs(rows - shifted))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))
    # A description without the rotary term writes the keys as they are.
    _, plain, _ = decoder._gated_attention(
        p, xn, kv.k, kv.v, table, jnp.int32(8), jnp.int32(0),
        dataclasses.replace(CFG, rope_theta=0.0), "jnp")
    rows = plain[0, 1:].reshape(-1, 32)[8:8 + CHUNK].reshape(CHUNK, 2, 16)
    assert float(jnp.max(jnp.abs(rows - k))) < 1e-6 * float(
        jnp.max(jnp.abs(k)))


# -- (d) the engine ------------------------------------------------------------


def test_the_engine_serves_the_reference_s_first_choices(params):
    engine = ServingEngine(params, CFG, max_slots=3, max_seq=MAX_SEQ,
                           queue_limit=16, block_size=BLOCK,
                           prefill_chunk=CHUNK, prefix_cache=False,
                           attn_impl="jnp")
    state = engine.scheduler.state
    assert state.s is None and state.conv is None
    assert state.ssm.shape == (2, 3, 8, 16, 32)
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
    per_slot = kv_slots.state_bytes_per_slot(CFG)
    assert per_slot == 2 * (8 * 16 * 32 + 3 * 256) * 4
    assert summary["state_pool_bytes"] == 3 * per_slot
    assert summary["state_bytes_by_kind"] == {"hybrid": 3 * per_slot}
    assert "moe" not in summary
    series = {s["labels"]["kind"]: s["value"] for s in __import__(
        "trustworthy_dl_tpu.obs.registry", fromlist=["get_registry"]
    ).get_registry().snapshot()["metrics"]["tddl_serve_state_kind_bytes"][
        "series"]}
    assert series == {"hybrid": 3 * per_slot}


def test_a_slot_s_state_rows_are_zeroed_at_admission(params):
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=MAX_SEQ,
                           block_size=BLOCK, prefill_chunk=CHUNK,
                           prefix_cache=False, attn_impl="jnp")
    sched = engine.scheduler
    sched.state = sched.state._replace(
        ssm=jnp.ones_like(sched.state.ssm),
        ssm_conv=jnp.ones_like(sched.state.ssm_conv))
    engine.submit(ServeRequest(prompt=np.arange(5), max_new_tokens=2,
                               temperature=0.0))
    sched._zero_state(1)
    assert float(jnp.abs(sched.state.ssm[:, 1]).max()) == 0.0
    assert float(jnp.abs(sched.state.ssm_conv[:, 1]).max()) == 0.0
    assert float(sched.state.ssm[:, 0].min()) == 1.0


@pytest.mark.parametrize("refused", [
    {"prefix_cache": True}, {"spec_k": 2},
    {"kv_dtype": "int8", "kv_parity_check": False}, {"weight_dtype": "int8"},
    {"tp_size": 2}], ids=lambda r: next(iter(r)))
def test_what_the_state_rows_cannot_be_served_with_is_refused(params,
                                                               refused):
    kwargs = {"max_slots": 2, "max_seq": MAX_SEQ, "block_size": BLOCK,
              "prefill_chunk": CHUNK, "prefix_cache": False,
              "attn_impl": "jnp", **refused}
    with pytest.raises(ValueError) as raised:
        ServingEngine(params, CFG, **kwargs)
    if "prefix_cache" in refused or "spec_k" in refused:
        assert "state row" in str(raised.value)


def test_a_state_snapshot_for_migration_is_refused(params):
    engine, other = (ServingEngine(params, CFG, max_slots=2, max_seq=MAX_SEQ,
                                   block_size=BLOCK, prefill_chunk=CHUNK,
                                   prefix_cache=False, attn_impl="jnp")
                     for _ in range(2))
    engine.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=4,
                               temperature=0.0))
    engine.step()
    assert not migrate.can_migrate(engine, other)
    task = next(iter(engine.scheduler.tasks.values()))
    with pytest.raises(ValueError, match="snapshot"):
        engine.scheduler.export_migration(task)
