"""The layers the second served architecture brings, each against plain
mathematics at a small size on the CPU: the chunked delta rule against its
recurrence, the paged kernel at grouped heads against the gathered path, the
dropless held-experts layer against a loop over experts, and THE share test:
what every chip's share of an expert layer computes adds up to the whole
layer.  Float32 weights, so every tolerance is float32 rounding and a bf16
state or an fp8 product fails it."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.families import reference_solar_open2 as ref
from trustworthy_dl_tpu.models import kda, moe
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.ops import paged_attention as pa

# -- (b) the chunked form against the recurrence -------------------------------

#: Largest |chunked - recurrence| over the outputs and the final state, as a
#: share of the largest |value|: both are float32 sums of a few hundred
#: terms of either sign in another order, so 1e-5 is ~100 ulp; a bf16 state
#: reads 1e-3 or more.
KDA_TOL = 1e-5


def _kda_inputs(seed, heads, t, dk, dv, decay):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(heads, t, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(heads, t, dk)))
    if decay == "keys repeat":
        k[:, 1::2] = k[:, ::2]          # beta 2 on a repeated key: I - 2kk^T
    v = rng.normal(size=(heads, t, dv))
    g = {"near one": -rng.uniform(1e-5, 1e-3, (heads, t, dk)),
         "near zero": -rng.uniform(5.0, 30.0, (heads, t, dk)),
         "mixed": -np.exp(rng.uniform(np.log(1e-4), np.log(20.0),
                                      (heads, t, dk))),
         "keys repeat": -rng.uniform(1e-3, 0.1, (heads, t, dk))}[decay]
    beta = rng.uniform(0.0, 2.0, (heads, t))
    beta[:, ::5] = 2.0
    s0 = rng.normal(size=(heads, dk, dv))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, s0))


def _recurrence(q, k, v, g, beta, s, round_state=None):
    outs = []
    for i in range(q.shape[1]):
        o, s = kda.kda_step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        if round_state is not None:
            s = s.astype(round_state).astype(jnp.float32)
        outs.append(o)
    return jnp.stack(outs, axis=1), s


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("decay", ["near one", "near zero", "mixed",
                                   "keys repeat"])
@pytest.mark.parametrize("t,sub,block", [(48, 16, 4), (128, 64, 16),
                                         (8, 64, 16), (48, 64, 16)], ids=str)
def test_the_chunked_form_is_the_recurrence(decay, t, sub, block):
    args = _kda_inputs(3, 3, t, 16, 8, decay)
    want_o, want_s = _recurrence(*args)
    got_o, got_s = kda.kda_chunk(*args, sub_chunk=sub, block=block)
    assert np.isfinite(np.asarray(got_o)).all()
    assert _gap(got_o, want_o) < KDA_TOL
    assert _gap(got_s, want_s) < KDA_TOL


def _kda_system(c, decay):
    """The unit lower-triangular system of one sub-chunk of ``c`` positions
    and its right-hand side, as :func:`kda.kda_chunk` builds them."""
    q, k, v, g, beta, _ = _kda_inputs(7, 3, c, 16, 8, decay)
    cum = jnp.cumsum(g, axis=-2)
    a_kk = kda._pair_sums(k, k, cum, min(16, c), inclusive=False)
    system = jnp.eye(c, dtype=jnp.float32) + beta[..., None] * a_kk
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(cum), v], axis=-1)
    return system, rhs


@pytest.mark.parametrize("decay", ["near one", "near zero", "mixed",
                                   "keys repeat"])
@pytest.mark.parametrize("c", [8, 16, 48, 64])
def test_the_doubled_inverse_inverts_the_system(c, decay):
    """The blocked-doubling inverse is the system's inverse to float32
    rounding, at sizes that are and are not powers of two, and its product
    with the right-hand side is what a triangular solve gives."""
    system, rhs = _kda_system(c, decay)
    inv = kda.unit_lower_inverse(system)
    eye = jnp.eye(c, dtype=jnp.float32)
    assert _gap(jnp.matmul(system, inv, precision=kda.HIGHEST), eye) < 1e-5
    assert np.array_equal(np.triu(np.asarray(inv), 1), np.zeros_like(inv))
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    got = jnp.matmul(inv, rhs, precision=kda.HIGHEST)
    assert _gap(got, solved) < KDA_TOL


def test_a_position_with_beta_and_g_zero_leaves_the_state():
    q, k, v, g, beta, s0 = _kda_inputs(5, 2, 16, 8, 8, "mixed")
    live = jnp.arange(16) < 11
    g = jnp.where(live[None, :, None], g, 0.0)
    beta = jnp.where(live[None], beta, 0.0)
    _, s_all = kda.kda_chunk(q, k, v, g, beta, s0, sub_chunk=8, block=4)
    _, s_live = _recurrence(q[:, :11], k[:, :11], v[:, :11], g[:, :11],
                            beta[:, :11], s0)
    assert _gap(s_all, s_live) < KDA_TOL


def test_a_bf16_state_fails_the_tolerance():
    args = _kda_inputs(3, 3, 48, 16, 8, "mixed")
    want_o, want_s = _recurrence(*args)
    low_o, low_s = _recurrence(*args, round_state=jnp.bfloat16)
    assert _gap(low_o, want_o) > 10 * KDA_TOL
    assert _gap(low_s, want_s) > 10 * KDA_TOL


def test_the_convolution_carries_its_tail():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    zeros = jnp.zeros((2, 3, 6), jnp.float32)
    whole = kda.causal_conv(x, zeros, taps)
    first = kda.causal_conv(x[:, :7], zeros, taps)
    second = kda.causal_conv(x[:, 7:], x[:, 4:7], taps)
    assert np.allclose(jnp.concatenate([first, second], axis=1), whole,
                       atol=1e-6)
    assert np.allclose(whole[:, 0], taps[3] * x[:, 0], atol=1e-6)


# -- (c) the paged kernel at grouped heads -------------------------------------

#: Kernel against gathered path: the same float32 products, the softmax
#: accumulated block by block: a few ulp of values of order 1.
ATTN_TOL = 2e-6


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 2), (6, 6), (4, 1)])
@pytest.mark.parametrize("t", [1, 3, 24, 40])
def test_the_paged_kernel_at_grouped_heads_is_the_gathered_path(
        heads, kv_heads, t):
    rng = np.random.default_rng(heads * 100 + t)
    layers, blocks, bsz, dh = 2, 13, 8, 16
    pool_k, pool_v = (jnp.asarray(rng.normal(
        size=(layers, blocks, bsz, kv_heads * dh)), jnp.float32)
        for _ in range(2))
    table = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12],
                         [0] * 6], jnp.int32)
    start = jnp.asarray([48 - t, 5, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, heads, t, dh)), jnp.float32)
    want = pa.paged_attention_reference(q, pool_k, pool_v, table, start,
                                        layer=1)
    attend = pa.paged_prefill_attention if t > pa.QROWS \
        else pa.paged_attention
    got = attend(q, pool_k, pool_v, table, start, layer=1, interpret=True)
    assert float(jnp.max(jnp.abs(got - want)[:2])) < ATTN_TOL
    # Query head h reads K/V head h // (heads / kv_heads), and no other.
    rep = heads // kv_heads
    alone = pa.paged_attention_reference(
        q[:, rep:2 * rep] if kv_heads > 1 else q[:, :rep],
        *(p[..., dh:2 * dh] if kv_heads > 1 else p[..., :dh]
          for p in (pool_k, pool_v)), table, start, layer=1)
    assert float(jnp.max(jnp.abs(
        alone - (want[:, rep:2 * rep] if kv_heads > 1 else want[:, :rep]))
        [:2])) < ATTN_TOL


@pytest.mark.parametrize("heads,kv_heads,dh", [(8, 2, 64), (4, 1, 128),
                                               (6, 6, 64)])
def test_the_walk_at_grouped_heads_ends_where_the_row_does(heads, kv_heads,
                                                           dh):
    """The walk inside the step with the query heads of a K/V head as rows
    of one product (pools of whole 128-lane rows): a wave is the rule's, 64
    or 128 blocks of 8, the table two and a half waves; rows of length 0
    (all trash: one wave all the same), 1, exactly a wave, one position
    into the second, and the full table."""
    bsz = 8
    assert pa._copies_its_blocks(bsz, kv_heads * dh)
    wave = pa._step_shape("decode", heads=kv_heads, head_dim=dh,
                          block_size=bsz, kv_dtype="float32", t=1,
                          rep=heads // kv_heads)[2]
    assert wave in (64, 128)
    nbps = 5 * wave // 2
    lengths = [0, 1, wave * bsz, wave * bsz + 1, nbps * bsz]
    rng = np.random.default_rng(heads)
    pool_k, pool_v = (jnp.asarray(rng.normal(
        size=(2, 31, bsz, kv_heads * dh)), jnp.float32) for _ in range(2))
    table = np.asarray(1 + rng.integers(0, 30, size=(5, nbps)), np.int32)
    table[0] = 0
    table = jnp.asarray(table)
    start = jnp.asarray([max(n - 1, 0) for n in lengths], jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, heads, 1, dh)), jnp.float32)
    want = pa.paged_attention_reference(q, pool_k, pool_v, table, start,
                                        layer=1)
    got = pa.paged_attention(q, pool_k, pool_v, table, start, layer=1,
                             interpret=True)
    assert float(jnp.max(jnp.abs(got - want))) < 2 * ATTN_TOL
    assert [pa.walked_blocks("decode", n, heads, nbps, 1, dh, bsz, "float32",
                             kv_heads=kv_heads) for n in lengths] \
        == [wave, wave, wave, 2 * wave, 3 * wave]


def test_walked_blocks_at_the_long_document_cell_by_hand():
    """64 query heads over 8 K/V heads of 128, blocks of 64, 128 a row: a
    wave is 8 blocks.  A decode row of 4,096 positions holds 64 blocks and
    walks 8 waves = 64; one of 4,097 holds 65 and walks 9 waves = 72, as one
    of 4,316 (68 blocks) does.  A chunk of 1,024 from position 2,048 goes in
    four tiles of 256 queries whose walks end at blocks 35, 39, 43 and 47:
    5 + 5 + 6 + 6 waves = 176 blocks for the 48 that hold what the chunk
    sees (each tile walks its own causal window: an early tile ends a wave
    before the last)."""
    bf16 = jnp.bfloat16
    assert pa._step_shape("decode", heads=8, head_dim=128, block_size=64,
                          kv_dtype=bf16, t=1, rep=8) == (8, 8, 8)
    assert pa._step_shape("prefill", heads=8, head_dim=128, block_size=64,
                          kv_dtype=bf16, t=1024, rep=8) == (1, 256, 8)
    kw = dict(kv_heads=8)
    assert [pa.walked_blocks("decode", n, 64, 128, 1, 128, 64, bf16, **kw)
            for n in (4096, 4097, 4316, 0)] == [64, 72, 72, 8]
    assert pa.walked_blocks("prefill", (2048, 1024), 64, 128, 1024, 128, 64,
                            bf16, **kw) == (5 + 5 + 6 + 6) * 8


def test_grid_steps_counts_groups_of_kv_heads():
    """The eight positional arguments the benchmark's reader passes, and the
    K/V head count as a keyword that defaults to the query heads."""
    bf16 = jnp.bfloat16
    assert pa.grid_steps("decode", 24, 20, 64, 1, 64, 16, bf16) \
        == (24, 1, 1, 64)
    assert pa.grid_steps("decode", 24, 20, 64, 1, 64, 16, bf16,
                         kv_heads=20) == (24, 1, 1, 64)
    # 64 query heads over 8 K/V heads of 128, blocks of 64: decode holds all
    # eight K/V heads (8 x 8 query rows each) in one step; a chunk of 1,024
    # goes in tiles of 256 positions (2,048 rows a K/V head) a head.
    assert pa.grid_steps("decode", 64, 64, 128, 1, 128, 64, bf16,
                         kv_heads=8) == (64, 1, 1, 128)
    assert pa.grid_steps("prefill", 1, 64, 128, 1024, 128, 64, bf16,
                         kv_heads=8) == (1, 8, 4, 128)


# -- (d), (e) the expert layer ------------------------------------------------

#: Grouped products against a loop over experts, float32 weights: sums of
#: D = 32 and F = 16 terms in another order.  An fp8 product reads 1e-2.
MOE_TOL = 2e-6

SHAPE = {"n_experts": 16, "experts_per_tok": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 1.0}


def _expert_weights(seed, experts=16, d=32, f=16):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    return {"router": draw(d, experts), "router_bias": draw(experts) * 0.1,
            "w_gate_up": draw(experts, d, 2 * f),
            "w_down": draw(experts, f, d),
            "shared_gate_up": draw(d, 2 * f), "shared_down": draw(f, d)}


def _share(p, x, first, held, valid=None):
    """The program's routed part for experts [first, first + held)."""
    chosen, weights = moe.route_top_k(x, p["router"], p["router_bias"], 2)
    return moe.held_experts(x, chosen, weights,
                            p["w_gate_up"][first:first + held],
                            p["w_down"][first:first + held], first, valid)


def _reference_layer(p, x, first, held):
    shape = dict(SHAPE, first_expert=first, n_experts_held=held)
    part = dict(p, w_gate_up=p["w_gate_up"][first:first + held],
                w_down=p["w_down"][first:first + held])
    return ref.expert_layer(part, x, shape)


def test_the_shares_add_up_to_the_whole_layer():
    """THE share test: the 8 shares' routed parts (2 of 16 experts each)
    plus the shared expert counted ONCE are the uncut reference's layer."""
    p = _expert_weights(1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 32)),
                    jnp.float32)
    whole = _reference_layer(p, x, 0, 16)
    parts, pairs = zip(*(_share(p, x, first, 2) for first in range(0, 16, 2)))
    shared = L.silu_gated_mlp(p["shared_gate_up"], p["shared_down"], x)
    total = sum(parts) + shared
    assert float(jnp.max(jnp.abs(total - whole))) < MOE_TOL * float(
        jnp.max(jnp.abs(whole)))
    assert int(sum(jnp.sum(n) for n in pairs)) == 40 * 2   # every pair, once
    # ... and one share alone is the reference GIVEN the same share.
    one = _share(p, x, 6, 4)[0] + shared
    assert float(jnp.max(jnp.abs(one - _reference_layer(p, x, 6, 4)))) \
        < MOE_TOL * float(jnp.max(jnp.abs(whole)))


def test_no_token_is_dropped_whatever_the_load():
    """A router forced to send EVERY token to one held expert: the capacity
    path would drop most of them; here the expert takes all 64."""
    p = _expert_weights(3)
    p["router_bias"] = p["router_bias"].at[5].set(50.0)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(64, 32)),
                    jnp.float32)
    y, pairs = _share(p, x, 4, 4)
    assert int(pairs[1]) == 64 and int(jnp.sum(pairs)) <= 128
    shared = L.silu_gated_mlp(p["shared_gate_up"], p["shared_down"], x)
    want = _reference_layer(p, x, 4, 4)
    assert float(jnp.max(jnp.abs(y + shared - want))) < MOE_TOL * float(
        jnp.max(jnp.abs(want)))
    # Padding takes no expert's time and counts nowhere.
    valid = jnp.arange(64) < 10
    y_valid, pairs_valid = _share(p, x, 4, 4, valid)
    assert int(pairs_valid[1]) == 10
    assert float(jnp.max(jnp.abs(y_valid[:10] - y[:10]))) < 1e-6
    assert float(jnp.max(jnp.abs(y_valid[10:]))) == 0.0


def test_an_fp8_product_fails_the_tolerance():
    p = _expert_weights(1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 32)),
                    jnp.float32)
    shape = dict(SHAPE, first_expert=0, n_experts_held=16)
    whole = ref.expert_layer(p, x, shape)
    low = ref.expert_layer(p, x, shape, "fp8")
    assert float(jnp.max(jnp.abs(low - whole))) > 100 * MOE_TOL * float(
        jnp.max(jnp.abs(whole)))


def test_rmsnorm_and_the_gated_mlp_are_their_formulas():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=32), jnp.float32)
    want = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5) \
        * scale
    assert np.allclose(L.rmsnorm(scale, x, 1e-5), want, atol=1e-6)
    p = _expert_weights(7)
    h = x @ p["shared_gate_up"]
    want = (jax.nn.silu(h[:, :16]) * h[:, 16:]) @ p["shared_down"]
    assert np.allclose(L.silu_gated_mlp(p["shared_gate_up"],
                                        p["shared_down"], x), want, atol=1e-6)
