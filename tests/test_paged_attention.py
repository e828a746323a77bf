"""Pallas ragged paged-decode attention + in-kernel trust epilogue
(ops/paged_attention.py, wired through models/generate._paged_block and
the serve scheduler's attn_impl static).

Fast tier, ``pagedattn`` marker.  Interpret-mode kernel equality vs the
jnp gather path (fp32 AND int8 KV pools, ragged lengths, windows
crossing block boundaries, bit-identical pool writes), epilogue
entropy/margin equality vs the engine's existing reductions (margin
bit-exact, entropy f32-epsilon), the resolve/supports dispatch gate,
the compile-once pin under two waves of block churn with the compile
watcher attached (zero storms), bit-identical streams through
``ServingEngine`` (greedy + sampled, spec_k on and off) vs
``generate()``, the ``tddl_serve_attn_kernel{path=}`` gauge +
decode_tick_fraction summary surface, and same-flag-decisions on the
seeded poison drill with the epilogue in the loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import generate as gen
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.ops import paged_attention as pattn
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
from trustworthy_dl_tpu.serve.scheduler import _logit_signals

pytestmark = pytest.mark.pagedattn

# vocab_size continues the 97/101/103/107/113/127/139 process-global
# jit-cache isolation sequence: the paged program caches are
# process-global (scheduler._PROGRAMS), so a config identical to a
# sibling suite's would let that file pre-warm the programs this file's
# strict compile-once pins measure (and vice versa).  The attn_impl
# static separates kernel-on from kernel-off programs WITHIN this file.
CFG = gpt2.GPT2Config(vocab_size=157, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Kernel vs reference semantics (standalone, no transformer in the loop)
# --------------------------------------------------------------------------


def _pools(rng, layers, nb, h, bsz, dh, dtype):
    """Random stacked pools ``[L, NB, BLOCK, H·Dh]`` (K and V, every
    layer different) and, for int8, the scale planes ``[L, NB, BLOCK,
    H]`` as the ``k_scale``/``v_scale`` keywords."""
    shape = (layers, nb, bsz, h * dh)
    if jnp.dtype(dtype) == jnp.int8:
        k, v = (jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
                for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(0.01, 0.1, shape[:3] + (h,)),
                              jnp.float32) for _ in range(2))
        return k, v, dict(k_scale=ks, v_scale=vs)
    k, v = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))
    return k, v, {}



def test_kernel_matches_reference_fp32_ragged():
    """Interpret-mode kernel equality against the gather-semantics
    reference: ragged per-row lengths, causal windows crossing block
    boundaries, decode (T=1) through chunk-sized windows, scalar and
    vector ``start``."""
    rng = np.random.default_rng(0)
    nb, h, bsz, dh = 9, 3, 8, 16
    r, nbps = 4, 4
    pool_k, pool_v, _ = _pools(rng, 2, nb, h, bsz, dh, jnp.float32)
    table = jnp.asarray(rng.integers(0, nb, size=(r, nbps)), jnp.int32)
    # Ragged: row 0 empty history, row 3 nearly full; starts 5 and 13
    # put the causal window mid-block and across a block boundary.
    start = jnp.asarray([0, 5, 13, 30], jnp.int32)
    for t in (1, 3, 8):
        q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
        got = pattn.paged_attention(q, pool_k, pool_v, table, start,
                                    layer=1, interpret=True)
        ref = pattn.paged_attention_reference(q, pool_k, pool_v, table,
                                              start, layer=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # Scalar start (the chunked-prefill spelling, R=1).
    q = jnp.asarray(rng.normal(size=(1, h, 5, dh)), jnp.float32)
    got = pattn.paged_attention(q, pool_k, pool_v, table[:1],
                                jnp.asarray(8, jnp.int32), interpret=True)
    ref = pattn.paged_attention_reference(q, pool_k, pool_v, table[:1],
                                          jnp.asarray(8, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_matches_reference_int8_scales():
    """int8 KV streaming: the in-register dequant (K scale post-dot, V
    scale folded into the probabilities) equals the reference's
    gathered-view algebra."""
    rng = np.random.default_rng(1)
    nb, h, bsz, dh = 7, 2, 8, 8
    r, nbps = 3, 3
    pool_k, pool_v, scales = _pools(rng, 2, nb, h, bsz, dh, jnp.int8)
    table = jnp.asarray(rng.integers(0, nb, size=(r, nbps)), jnp.int32)
    start = jnp.asarray([0, 7, 17], jnp.int32)
    for t in (1, 4):
        q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
        got = pattn.paged_attention(q, pool_k, pool_v, table, start,
                                    layer=1, interpret=True, **scales)
        ref = pattn.paged_attention_reference(q, pool_k, pool_v, table,
                                              start, layer=1, **scales)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# What a grid step holds: the head group and the query tile, by shape
# --------------------------------------------------------------------------

# (heads, head_dim, block, pool dtype, the head group the rule must pick).
# The group is forced by SHAPE alone: small blocks take every head in one
# step; blocks of 1,024 to 4,096 positions leave VMEM for a proper divisor
# of the heads, which is a window of the pool's rows and so whole 128-lane
# columns (two heads of 64); larger ones still for one head, where a head
# is 128 lanes itself.  The first two are a small copy of the serving
# cell's geometry.
_GEOMETRIES = [
    (20, 64, 16, "bfloat16", 20), (20, 64, 16, "int8", 20),
    (4, 64, 8, "float32", 4), (3, 16, 8, "bfloat16", 3),
    (4, 16, 8, "int8", 4),
    (4, 64, 1024, "float32", 2), (4, 64, 2048, "bfloat16", 2),
    (4, 64, 4096, "int8", 2),
    (3, 128, 2048, "float32", 1), (2, 128, 4096, "bfloat16", 1),
    (2, 128, 8192, "int8", 1),
]


def _geometry_id(case):
    h, dh, bsz, dtype, group = case
    return f"h{h}-d{dh}-b{bsz}-{dtype}-g{group}"


@pytest.mark.parametrize("t", [1, 3, 64])
@pytest.mark.parametrize("case", _GEOMETRIES, ids=_geometry_id)
def test_grouped_step_matches_reference(case, t):
    """Both programs on the grouped step against the gather-semantics
    reference: every head in one step, a proper divisor of the heads and
    one head a step (each forced by the pool's shape and dtype alone),
    head widths 64 and 16, f32 / bf16 / int8 pools, decode (T = 1), the
    verify window (T = k + 1) and the chunk (T = 64, one query tile), on
    three rows: an empty history, a start that is block-aligned but (at
    the small blocks) not chunk-aligned, and a ragged one mid-block."""
    h, dh, bsz, dtype, group = case
    program = "prefill" if t > pattn.QROWS else "decode"
    aligned = 5 * bsz if bsz <= 16 else bsz
    start = jnp.asarray([0, aligned, aligned + 5], jnp.int32)
    r, nbps = 3, -(-(aligned + 5 + t) // bsz) + 1
    nb = r * nbps + 1
    assert pattn._step_shape(program, heads=h, head_dim=dh, block_size=bsz,
                             kv_dtype=dtype, t=t)[:2] == (
        group, -(-t // pattn.QROWS) * pattn.QROWS)
    # (The fourth number is the static bound of a row's walk: a loop inside
    # the step, or the grid's fourth dimension at a pool the step cannot
    # copy.)
    assert pattn.grid_steps(program, r, h, nbps, t, dh, bsz, dtype) == (
        r, h // group, 1, nbps)
    rng = np.random.default_rng(h * bsz + t)
    pool_k, pool_v, scales = _pools(rng, 2, nb, h, bsz, dh, dtype)
    table = jnp.asarray(1 + rng.permutation(r * nbps).reshape(r, nbps),
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
    attend = (pattn.paged_prefill_attention if program == "prefill"
              else pattn.paged_attention)
    got = attend(q, pool_k, pool_v, table, start, layer=1, interpret=True,
                 **scales)
    ref = pattn.paged_attention_reference(q, pool_k, pool_v, table, start,
                                          layer=1, **scales)
    tol = 5e-5 if dtype == "int8" else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)


# The stacked pool's layer operand: (heads, head_dim, block, pool dtype,
# group) — every head a step, and a window of two heads.
_LAYER_GEOMETRIES = [
    (4, 64, 16, "bfloat16", 4), (4, 64, 16, "int8", 4),
    (4, 64, 2048, "bfloat16", 2), (4, 64, 4096, "int8", 2),
]


@pytest.mark.parametrize("t", [1, 64], ids=["decode", "prefill"])
@pytest.mark.parametrize("case", _LAYER_GEOMETRIES, ids=_geometry_id)
def test_every_layer_of_a_stacked_pool(case, t):
    """Both programs read layer ``l`` of the stacked pool and nothing
    else: at EVERY layer index the kernel agrees with the reference at
    that layer, and disagrees with the reference at the next one (a layer
    operand that were dropped, or mapped to another axis, fails here)."""
    h, dh, bsz, dtype, group = case
    program = "prefill" if t > pattn.QROWS else "decode"
    layers, r, nbps = 3, 2, 2
    nb = r * nbps + 1
    assert pattn._step_shape(program, heads=h, head_dim=dh, block_size=bsz,
                             kv_dtype=dtype, t=t)[0] == group
    rng = np.random.default_rng(bsz + t)
    pool_k, pool_v, scales = _pools(rng, layers, nb, h, bsz, dh, dtype)
    table = jnp.asarray(1 + rng.permutation(r * nbps).reshape(r, nbps),
                        jnp.int32)
    start = jnp.asarray([0, bsz + 3], jnp.int32)
    q = jnp.asarray(rng.normal(size=(r, h, t, dh)), jnp.float32)
    attend = (pattn.paged_prefill_attention if program == "prefill"
              else pattn.paged_attention)
    tol = 5e-5 if dtype == "int8" else 2e-5
    refs = [np.asarray(pattn.paged_attention_reference(
        q, pool_k, pool_v, table, start, layer=l, **scales))
        for l in range(layers)]
    for l in range(layers):
        # A traced layer index, as the layer loop hands it over.
        got = np.asarray(jax.jit(
            lambda layer: attend(q, pool_k, pool_v, table, start,
                                 layer=layer, interpret=True, **scales)
        )(jnp.asarray(l, jnp.int32)))
        np.testing.assert_allclose(got, refs[l], rtol=tol, atol=tol)
        assert np.abs(got - refs[(l + 1) % layers]).max() > 1e-2


def test_a_head_group_is_a_window_of_the_lanes():
    """What the stacked pool's rows add to the rule: a group is whole
    128-lane columns or every head — at 64-wide heads never 1 or 5 — and
    a geometry whose every-head step overflows VMEM while no narrower
    window exists is refused, not served one head at a time."""
    assert pattn._head_groups(20, 64) == [20, 10, 4, 2]
    assert pattn._head_groups(12, 80) == [12]
    assert pattn._head_groups(12, 128) == [12, 6, 4, 3, 2, 1]
    assert pattn._head_groups(None, 64) == [2]
    for h, dh, bsz, dtype in ((2, 64, 4096, "bfloat16"),
                              (3, 16, 2048, "float32"),
                              (4, 16, 2048, "bfloat16")):
        assert not pattn.supports_paged_attention(
            head_dim=dh, block_size=bsz, kv_dtype=dtype, interpret=False,
            n_embd=h * dh)
    assert pattn.supports_paged_attention(
        head_dim=64, block_size=2048, kv_dtype="bfloat16", interpret=False,
        n_embd=4 * 64)


def test_chunk_in_query_tiles_matches_reference():
    """A chunk too long for one query tile beside blocks this large goes
    in tiles, each with its own causal bound: 72 rows walk as three tiles
    of 32 (the last one a quarter real), one head a step."""
    h, dh, bsz, t, nbps = 2, 128, 4032, 72, 2
    assert pattn._step_shape("prefill", heads=h, head_dim=dh,
                             block_size=bsz, kv_dtype="float32",
                             t=t) == (1, 32, 1)
    assert pattn.grid_steps("prefill", 1, h, nbps, t, dh, bsz,
                            "float32") == (1, h, 3, nbps)
    # The first tile's last query stands 9 positions short of block 1: its
    # walk ends a wave (of one block here) before the other two tiles'.
    assert pattn.walked_blocks("prefill", (bsz - 40, t), h, nbps, t, dh,
                               bsz, "float32") == 1 + 2 + 2
    rng = np.random.default_rng(72)
    pool_k, pool_v, _ = _pools(rng, 2, 3, h, bsz, dh, jnp.float32)
    table = jnp.asarray([[2, 1]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, h, t, dh)), jnp.float32)
    # The second tile's window crosses from block 0 into block 1.
    start = jnp.asarray(bsz - 40, jnp.int32)
    got = pattn.paged_prefill_attention(q, pool_k, pool_v, table, start,
                                        layer=1, interpret=True)
    ref = pattn.paged_attention_reference(q, pool_k, pool_v, table, start,
                                          layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# The walk inside the step: (pool dtype, block), rows of 2 x 64 = 128
# lanes.  A wave is WAVE_POSITIONS positions of blocks, whole packed tiles
# of their dtype or half of one (16 rows of int8, 8 of bfloat16: the
# blocks are laid end to end after the upcast), so a table of two and a
# half waves walks one, two or three.
_WALKS = [("float32", 8), ("bfloat16", 16), ("int8", 32), ("int8", 16),
          ("bfloat16", 8)]


@pytest.mark.parametrize("dtype,bsz", _WALKS, ids=lambda v: str(v))
def test_the_walk_ends_where_the_row_does(dtype, bsz):
    """The decode program over rows whose walks differ: a row that holds
    nothing (an all-trash table row, length 0: one wave all the same)
    beside live ones of length 1, of exactly one wave, of one position
    into the second wave, and of the full table (the last wave's tail
    clamped to the row's last block); then a chunk that starts in the
    first wave and ends in the second.  Against the gathered reference,
    and the blocks walked by hand."""
    h, dh = 2, 64
    wave = pattn.WAVE_POSITIONS // bsz
    nbps = 5 * wave // 2
    kw = dict(heads=h, head_dim=dh, block_size=bsz, kv_dtype=dtype)
    assert pattn._copies_its_blocks(bsz, h * dh)
    assert pattn._step_shape("decode", t=1, **kw) == (h, 8, wave)
    assert pattn._step_shape("prefill", t=64, **kw) == (h, 64, wave)
    full = nbps * bsz
    lengths = [0, 1, wave * bsz, wave * bsz + 1, full]
    rng = np.random.default_rng(bsz)
    nb = 2 * nbps + 1
    pool_k, pool_v, scales = _pools(rng, 2, nb, h, bsz, dh, dtype)
    table = np.asarray(1 + rng.integers(0, nb - 1, size=(5, nbps)), np.int32)
    table[0] = 0                        # the trash block, all along
    table = jnp.asarray(table)
    start = jnp.asarray([max(n - 1, 0) for n in lengths], jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, h, 1, dh)), jnp.float32)
    got = pattn.paged_attention(q, pool_k, pool_v, table, start, layer=1,
                                interpret=True, **scales)
    ref = pattn.paged_attention_reference(q, pool_k, pool_v, table, start,
                                          layer=1, **scales)
    tol = 5e-5 if dtype == "int8" else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)
    walked = [pattn.walked_blocks("decode", n, h, nbps, 1, dh, bsz, dtype)
              for n in lengths]
    assert walked == [wave, wave, wave, 2 * wave, 3 * wave]
    # the chunk: 64 rows from 40 short of the first wave's end
    pos = wave * bsz - 40
    q = jnp.asarray(rng.normal(size=(1, h, 64, dh)), jnp.float32)
    got = pattn.paged_prefill_attention(
        q, pool_k, pool_v, table[4:], jnp.asarray(pos, jnp.int32), layer=1,
        interpret=True, **scales)
    ref = pattn.paged_attention_reference(
        q, pool_k, pool_v, table[4:], jnp.asarray(pos, jnp.int32), layer=1,
        **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)
    assert pattn.walked_blocks("prefill", (pos, 64), h, nbps, 64, dh, bsz,
                               dtype) == 2 * wave
    assert pattn.walked_blocks("prefill", (0, 13), h, nbps, 64, dh, bsz,
                               dtype) == wave


def test_walked_blocks_at_the_serving_cell_by_hand():
    """GPT-2 large's pool (24 rows of 64 blocks of 16, 20 heads of 64,
    bfloat16): a wave is 16 blocks in the decode call and 8 beside the
    chunk's 64 query rows.  A decode row of 800 cached positions holds 50
    live blocks and walks 4 waves = the whole table's 64; one of 700 walks
    3 waves = 48 for its 44; a mid-prefill slot's trash row walks one wave,
    16 blocks for none.  A chunk of 64 rows from position 640 sees 704
    positions = 44 blocks and walks 6 waves of 8."""
    kw = (20, 64, 1, 64, 16, "bfloat16")
    assert pattn._step_shape("decode", heads=20, head_dim=64, block_size=16,
                             kv_dtype="bfloat16", t=1) == (20, 8, 16)
    assert pattn._step_shape("prefill", heads=20, head_dim=64,
                             block_size=16, kv_dtype="bfloat16",
                             t=64) == (20, 64, 8)
    assert [pattn.walked_blocks("decode", n, *kw)
            for n in (800, 700, 0, 1, 1024)] == [64, 48, 16, 16, 64]
    chunk = (20, 64, 64, 64, 16, "bfloat16")
    assert pattn.walked_blocks("prefill", (640, 64), *chunk) == 48
    assert pattn.walked_blocks("prefill", (0, 64), *chunk) == 8
    # a partial last chunk walks for the rows the program pads it to
    assert pattn.walked_blocks("prefill", (896, 5), *chunk) == 64
    # GPT-2 XL's rows (25 heads of 64 = 1,600 lanes, off the 128) are walked
    # by the grid, a block a step: a row's live blocks and not one more.
    xl = (25, 64, 1, 64, 16, "bfloat16")
    assert pattn._step_shape("decode", heads=25, head_dim=64, block_size=16,
                             kv_dtype="bfloat16", t=1)[2] == 1
    assert [pattn.walked_blocks("decode", n, *xl)
            for n in (800, 700, 0, 1, 1024)] == [50, 44, 1, 1, 64]


# Every attention geometry the other tests of the tier run or compile:
# this file's and test_serving_kernel_tier.py's pools, the engines' (4
# heads of 8, blocks of 8), test_chip_compile.py's and the serving cell's.
_RULE_GEOMETRIES = [c[:4] for c in _GEOMETRIES] + [
    (3, 16, 8, "float32"), (2, 8, 8, "int8"), (2, 16, 8, "float32"),
    (2, 16, 8, "int8"), (4, 8, 8, "float32"), (4, 8, 8, "int8"),
    (2, 128, 4032, "float32"),
    (12, 64, 16, "float32"), (12, 64, 16, "bfloat16"), (12, 64, 16, "int8"),
    (12, 64, 8, "bfloat16"), (12, 80, 12, "float32"),
    (12, 128, 32, "bfloat16"), (12, 128, 4096, "int8"),
    (12, 256, 1024, "float32"), (64, 128, 16, "bfloat16"),
    (20, 64, 16, "float32"),
]


@pytest.mark.parametrize("t", [1, 5, 13, 64, 72])
@pytest.mark.parametrize("case", _RULE_GEOMETRIES,
                         ids=lambda c: "h%d-d%d-b%d-%s" % c)
def test_rule_divides_heads_and_fits_budget(case, t):
    """The one rule: its group divides the heads and is a window of the
    pool's lanes, its tile is whole sublanes, what the step pins fits the budget the predicate asks
    about, no wider step of the same kind would, and the padded call the
    kernel sees picks the same step."""
    h, dh, bsz, dtype = case
    kw = dict(head_dim=dh, block_size=bsz, kv_dtype=dtype)
    assert pattn.supports_paged_attention(interpret=False, n_embd=h * dh,
                                          **kw)
    # (The decode program never tiles: the engine hands it a sublane of
    # query rows at most, models/generate._paged_block_kernel.)
    for program in ("decode", "prefill")[t > pattn.QROWS:]:
        group, tile, wave = pattn._step_shape(program, heads=h, t=t, **kw)
        t8 = -(-t // pattn.QROWS) * pattn.QROWS

        def pinned(g, qt, w=1):
            return pattn._pipelined_block_bytes(
                program, n_embd=h * dh, group=g, q_tile=qt, wave=w, **kw)

        # The wave: a power of two of blocks out of a pool the step can
        # copy (whatever the pool's dtype: the blocks are laid end to end
        # in float32), inside the budget and the cap; twice it is not, by
        # the pinned bytes or by what the kernel makes of a wave (its score
        # tile and f32 cuts: the other half of VMEM).
        in_step = pattn._copies_its_blocks(bsz, h * dh)
        assert wave & (wave - 1) == 0
        assert wave == 1 or in_step
        assert pinned(group, tile, wave) <= pattn.VMEM_BLOCK_BUDGET
        assert wave == 1 or wave * bsz <= pattn.WAVE_POSITIONS
        span = 2 * wave * bsz
        made = group * (pattn._tile_bytes(tile, span, jnp.float32)
                        + 2 * pattn._tile_bytes(span, dh, jnp.float32))
        assert (not in_step or span > pattn.WAVE_POSITIONS
                or pinned(group, tile, 2 * wave) > pattn.VMEM_BLOCK_BUDGET
                or made > pattn.VMEM_LIMIT_BYTES - pattn.VMEM_BLOCK_BUDGET)

        assert h % group == 0 and tile % pattn.QROWS == 0
        assert tile == t8 or (program == "prefill" and tile < t8)
        assert pinned(group, tile) <= pattn.VMEM_BLOCK_BUDGET
        groups = pattn._head_groups(h, dh)
        assert group in groups
        wider = [g for g in groups if g > group]
        assert all(pinned(g, tile) > pattn.VMEM_BLOCK_BUDGET for g in wider)
        if tile < t8:
            assert pinned(groups[-1], 2 * tile) > pattn.VMEM_BLOCK_BUDGET
        tiles = -(-t8 // tile)
        assert pattn.grid_steps(program, 7, h, 5, t, dh, bsz, dtype) == (
            7, h // group, tiles, 5)
        assert pattn._step_shape(program, heads=h, t=tiles * tile,
                                 **kw) == (group, tile, wave)


# --------------------------------------------------------------------------
# Kernel path vs jnp path through the REAL paged transformer stack
# --------------------------------------------------------------------------


# Two heads of 64: a pool of whole 128-lane rows, which the step walks
# itself where CFG's 32 lanes are walked by the grid.
WIDE = gpt2.GPT2Config(vocab_size=157, n_positions=64, n_layer=2, n_embd=128,
                       n_head=2, dtype=jnp.float32)


@pytest.mark.parametrize("cfg", [CFG, WIDE], ids=["grid-walk", "step-walk"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_paged_apply_kernel_vs_jnp_logits_and_pools(params, kv_dtype, cfg):
    """``_apply_with_cache_paged`` with attn_impl="interpret" vs "jnp"
    over identical pools: decode logits agree to f32 epsilon, verify-
    window (all_logits) logits agree, and the pool writes agree to the
    same epsilon (layer 0's writes are value-identical — same qkv, same
    scatter — and deeper layers inherit the upstream attention epsilon
    through the scan; on the int8 tier that epsilon can flip a rounding
    by at most one int8 step, the same numerics class the parity probe
    tolerates) — fp32 and int8 tiers, ragged lengths, a window crossing
    a block boundary."""
    from trustworthy_dl_tpu.serve.kv_slots import init_paged_pool

    assert pattn._copies_its_blocks(8, cfg.n_embd) == (cfg is WIDE)
    if cfg is WIDE:
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    bsz, num_blocks, r, nbps = 8, 12, 3, 4
    kv = init_paged_pool(cfg, num_blocks, bsz,
                         kv_dtype=jnp.int8 if kv_dtype == "int8"
                         else jnp.float32)
    # Seed the pool with content so history actually matters.
    if kv_dtype == "int8":
        k0 = jnp.asarray(rng.integers(-127, 128, size=kv.k.shape), jnp.int8)
        v0 = jnp.asarray(rng.integers(-127, 128, size=kv.v.shape), jnp.int8)
        ks0 = jnp.asarray(rng.uniform(0.005, 0.05, size=kv.k_scale.shape),
                          jnp.float32)
        pools = (k0, v0, ks0, ks0)
    else:
        k0 = jnp.asarray(rng.normal(size=kv.k.shape) * 0.3, jnp.float32)
        v0 = jnp.asarray(rng.normal(size=kv.v.shape) * 0.3, jnp.float32)
        pools = (k0, v0, None, None)
    # DISJOINT tables — the BlockAllocator's invariant: a row only ever
    # WRITES exclusively-owned blocks (shared prefix blocks are read-only
    # history).  The write-then-attend kernel path and the
    # gather-then-write jnp path agree exactly under that invariant; a
    # row reading another row's same-tick write block would be an
    # allocator bug, not an attention-path choice.  Ragged lengths: 1,
    # 11 (history crosses a block boundary) and 26.
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                        jnp.int32)
    lengths = jnp.asarray([1, 11, 26], jnp.int32)
    view = gen._decode_view(params, cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(r, 1)),
                         jnp.int32)
    outs = {}
    for impl in ("jnp", "interpret"):
        outs[impl] = gen._apply_with_cache_paged(
            view, tokens, *pools, table, lengths, cfg, attn_impl=impl)
    np.testing.assert_allclose(np.asarray(outs["jnp"][0]),
                               np.asarray(outs["interpret"][0]),
                               rtol=2e-4, atol=2e-4)
    for i in (1, 2, 3, 4):  # pool k, v, k_scale, v_scale
        if outs["jnp"][i] is None:
            assert outs["interpret"][i] is None
            continue
        a = np.asarray(outs["jnp"][i]).astype(np.float32)
        b = np.asarray(outs["interpret"][i]).astype(np.float32)
        if kv_dtype == "int8" and i in (1, 2):
            assert np.abs(a - b).max() <= 1          # one rounding step
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # Verify-window shape (the spec_verify program's read): T=4 starting
    # at the pre-draft lengths, all-position logits.
    tokens_w = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(r, 4)),
                           jnp.int32)
    outs_w = {}
    for impl in ("jnp", "interpret"):
        outs_w[impl] = gen._apply_with_cache_paged(
            view, tokens_w, *pools, table, lengths, cfg,
            all_logits=True, attn_impl=impl)
    np.testing.assert_allclose(np.asarray(outs_w["jnp"][0]),
                               np.asarray(outs_w["interpret"][0]),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# Trust epilogue
# --------------------------------------------------------------------------


def test_trust_epilogue_matches_engine_reductions():
    """The fused epilogue equals the engine's existing per-token
    reductions: margin BIT-exact (top-2 merge is max/min only, including
    duplicated maxima), entropy to f32 epsilon — over random,
    collapsed-distribution and near-tie logits at the serve vocab."""
    rng = np.random.default_rng(3)
    cases = [
        jnp.asarray(rng.normal(size=(5, CFG.vocab_size)) * 4, jnp.float32),
        # Collapse (one dominant logit — the backdoor signature).
        jnp.zeros((3, CFG.vocab_size), jnp.float32).at[:, 7].set(30.0),
        # Exact near-tie: duplicated maximum, margin must be exactly 0.
        jnp.zeros((2, CFG.vocab_size), jnp.float32)
        .at[:, 3].set(5.0).at[:, 100].set(5.0),
    ]
    for logits in cases:
        ent_k, mar_k = _logit_signals(logits, "interpret")
        ent_j, mar_j = _logit_signals(logits, "jnp")
        np.testing.assert_array_equal(np.asarray(mar_k), np.asarray(mar_j))
        np.testing.assert_allclose(np.asarray(ent_k), np.asarray(ent_j),
                                   rtol=1e-5, atol=1e-5)
    # And against the module's own reference spelling at an odd vocab.
    logits = jnp.asarray(rng.normal(size=(4, 50257)) * 3, jnp.float32)
    ent_k, mar_k = pattn.logit_trust_stats(logits, interpret=True)
    ent_r, mar_r = pattn.logit_trust_stats_reference(logits)
    np.testing.assert_array_equal(np.asarray(mar_k), np.asarray(mar_r))
    np.testing.assert_allclose(np.asarray(ent_k), np.asarray(ent_r),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# Dispatch gate
# --------------------------------------------------------------------------


def test_resolve_and_supports_gate(monkeypatch):
    """The shared-gate dispatch contract: "jnp" passes through; "auto"
    follows TDDL_PAGED_ATTN (default off-TPU = jnp fallback, the CPU
    container tier's green path); opt-in resolves to interpret off-TPU;
    explicit "pallas" on a non-TPU backend RAISES (the interpreter is
    not the kernel); a geometry the predicate refuses downgrades "auto"
    loudly and REJECTS an explicit ask."""
    monkeypatch.delenv("TDDL_PAGED_ATTN", raising=False)
    kw = dict(head_dim=64, block_size=16, kv_dtype=jnp.float32)
    assert pattn.resolve_attn_impl("jnp", **kw) == "jnp"
    # Default off-TPU: gate closed, jnp fallback stays the default.
    assert pattn.resolve_attn_impl("auto", **kw) == "jnp"
    monkeypatch.setenv("TDDL_PAGED_ATTN", "1")
    assert pattn.resolve_attn_impl("auto", **kw) == "interpret"
    monkeypatch.setenv("TDDL_PAGED_ATTN", "0")
    assert pattn.resolve_attn_impl("auto", **kw) == "jnp"
    with pytest.raises(ValueError, match="attn_impl"):
        pattn.resolve_attn_impl("mosaic", **kw)
    # Explicit "pallas" asked for COMPILED Mosaic by name — on this CPU
    # backend that must fail loudly, not silently serve the interpreter.
    with pytest.raises(ValueError, match="TPU backend"):
        pattn.resolve_attn_impl("pallas", **kw)
    # Compiled eligibility is the VMEM rule the TPU compiler enforces
    # (tests/test_chip_compile.py holds it to the compiler): blocks off
    # the dtype's sublane lower, blocks past the budget do not.
    for dtype, block in ((jnp.float32, 12), (jnp.bfloat16, 8),
                         (jnp.int8, 16)):
        assert pattn.supports_paged_attention(
            head_dim=64, block_size=block, kv_dtype=dtype, interpret=False)
    # A pool the step cannot copy out of HBM itself (a block off the 8
    # sublanes, rows off the 128 lanes: 25 heads of 64) is admitted all the
    # same: the grid walks it.
    for block, n_embd in ((12, None), (16, 25 * 64)):
        assert pattn.supports_paged_attention(
            head_dim=64, block_size=block, kv_dtype=jnp.float32,
            interpret=False, n_embd=n_embd)
    assert not pattn._copies_its_blocks(12, 1280)
    assert not pattn._copies_its_blocks(16, 25 * 64)
    assert pattn._copies_its_blocks(16, 1280)
    assert not pattn.supports_paged_attention(
        head_dim=512, block_size=4096, kv_dtype=jnp.float32,
        interpret=False)
    assert pattn.supports_paged_attention(
        head_dim=512, block_size=4096, kv_dtype=jnp.float32,
        interpret=True)
    # The int8 tier's scale blocks carry every head's plane.
    assert pattn.supports_paged_attention(
        head_dim=128, block_size=8192, kv_dtype=jnp.int8, interpret=False)
    assert not pattn.supports_paged_attention(
        head_dim=128, block_size=8192, kv_dtype=jnp.int8, interpret=False,
        n_embd=128 * 128)
    with pytest.raises(ValueError, match="cannot dispatch"):
        pattn.resolve_attn_impl("interpret", head_dim=0, block_size=8,
                                kv_dtype=jnp.float32)


# --------------------------------------------------------------------------
# Served streams: bit-identical vs generate(), compile-once, zero storms
# --------------------------------------------------------------------------


def _requests():
    rng = np.random.default_rng(7)
    reqs = []
    for _ in range(5):
        plen = int(rng.integers(3, 14))
        reqs.append(ServeRequest(
            prompt=rng.integers(0, CFG.vocab_size, plen).tolist(),
            max_new_tokens=int(rng.integers(2, 9))))
    reqs.append(ServeRequest(prompt=[2, 71, 8, 28], max_new_tokens=6,
                             temperature=0.8, rng=jax.random.PRNGKey(42)))
    return reqs


@pytest.mark.parametrize("spec_k", [0, 2])
def test_streams_bit_identical_vs_generate(params, spec_k):
    """THE acceptance pin: with the kernel in the loop (interpret mode —
    the same code path the TPU compiles) the engine serves greedy AND
    seeded-sampled streams bit-identical to ``generate()``, spec_k on
    and off, across chunked prefill, block churn and prefix sharing."""
    engine = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                           queue_limit=32, rng=jax.random.PRNGKey(5),
                           block_size=8, prefill_chunk=16, spec_k=spec_k,
                           attn_impl="interpret")
    assert engine.attn_kernel_path == "interpret"
    for req in _requests():
        engine.submit(req)
    results = engine.run_until_idle()
    assert all(r.status == "completed" for r in results.values())
    for rid, req in enumerate(_requests()):
        ref = generate(params, CFG,
                       jnp.asarray([list(req.prompt)], jnp.int32),
                       req.max_new_tokens, temperature=req.temperature,
                       rng=(req.rng if req.rng is not None
                            else jax.random.fold_in(jax.random.PRNGKey(5),
                                                    rid)))
        ref_tokens = np.asarray(ref)[0, len(req.prompt):].tolist()
        assert results[rid].tokens == ref_tokens, f"request {rid}"


def test_int8_kv_kernel_streams_match_jnp(params):
    """int8 KV pool with the kernel in the loop: streams equal the jnp
    gather path token for token (the in-register dequant is the same
    algebra; the attn_impl static keys separate compiled programs, so
    the two engines genuinely run different code)."""
    kwargs = dict(max_slots=2, max_seq=48, queue_limit=16, block_size=8,
                  kv_dtype="int8", kv_parity_check=False,
                  rng=jax.random.PRNGKey(5))
    outs = {}
    for impl in ("jnp", "interpret"):
        engine = ServingEngine(params, CFG, attn_impl=impl, **kwargs)
        for i in range(3):
            engine.submit(ServeRequest(prompt=[5, 17, 3, 2 + i],
                                       max_new_tokens=5))
        outs[impl] = {r: v.tokens
                      for r, v in engine.run_until_idle().items()}
    assert outs["jnp"] == outs["interpret"]


def test_compile_once_under_block_churn_zero_storms(params):
    """The compile-once pin with the kernel in the loop and the PR 10
    CompileWatcher attached: two waves of ragged requests (retirements
    free and re-map blocks between waves; a shared prefix exercises the
    radix cache) — the fused decode program compiles exactly once and
    the watcher records ZERO storms."""
    from trustworthy_dl_tpu.obs.compilewatch import (
        CompileRegistry,
        CompileWatcher,
    )

    registry = CompileRegistry().install()
    watcher = CompileWatcher(registry)
    try:
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                               block_size=8, prefill_chunk=8,
                               queue_limit=32, attn_impl="interpret",
                               compilewatch=watcher)
        before = engine.scheduler.decode_cache_size()
        rng = np.random.default_rng(3)
        shared = rng.integers(0, CFG.vocab_size, 9).tolist()
        served = 0
        for _wave in range(2):
            engine.submit(ServeRequest(prompt=shared, max_new_tokens=3))
            for _ in range(3):
                plen = int(rng.integers(3, 12))
                engine.submit(ServeRequest(
                    prompt=rng.integers(0, CFG.vocab_size, plen).tolist(),
                    max_new_tokens=int(rng.integers(2, 6))))
            results = engine.run_until_idle()
            served += len(engine.drain_results())
        assert served == 8
        assert all(r.status == "completed" for r in results.values())
        assert engine.scheduler.decode_cache_size() - before == 1
        assert watcher.storm_total == 0
    finally:
        registry.uninstall()


# --------------------------------------------------------------------------
# Obs surface + the poison drill
# --------------------------------------------------------------------------


def test_attn_gauge_and_summary_surface(params):
    """Every serve snapshot names the active path of EVERY program in
    the serving-kernel tier: the ``tddl_serve_attn_kernel{path=,
    program=}`` gauge sets 1 on exactly the resolved path per program
    (decode / prefill / verify / adapter), and metrics_summary carries
    decode_tick_fraction + prefill_chunk_fraction +
    spec_verify_fraction + the path map (what the perf sentinel
    bands)."""
    for impl, expect in (("interpret", "interpret"), ("jnp", "jnp")):
        registry = MetricsRegistry()
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                               block_size=8, registry=registry,
                               attn_impl=impl)
        engine.submit(ServeRequest(prompt=[3, 1, 4], max_new_tokens=3))
        engine.run_until_idle()
        paths = engine.attn_kernel_paths
        assert paths["decode"] == expect
        assert paths["prefill"] == expect
        assert paths["verify"] == expect
        # No adapter pool configured: the adapter program has no work,
        # its path stays the structural-absence "jnp".
        assert paths["adapter"] == "jnp"
        gauge = registry.get("tddl_serve_attn_kernel")
        for program in pattn.PAGED_PROGRAMS:
            for path in ("pallas", "interpret", "jnp"):
                want = 1.0 if path == paths[program] else 0.0
                assert gauge.value(path=path, program=program) == want, \
                    (impl, program, path)
        summary = engine.metrics_summary()
        assert summary["attn_kernel_path"] == expect
        assert summary["attn_kernel_paths"] == paths
        assert 0.0 < summary["decode_tick_fraction"] <= 1.0
        assert 0.0 < summary["prefill_chunk_fraction"] <= 1.0
        assert summary["spec_verify_fraction"] == 0.0  # spec_k == 0


def test_config_knob_validation_and_threading(params, monkeypatch):
    """ServeConfig.attn_impl fails loudly where the operator typed it
    and threads through from_config to the resolved scheduler path."""
    from trustworthy_dl_tpu.core.config import ServeConfig

    with pytest.raises(ValueError, match="attn_impl"):
        ServeConfig(attn_impl="mosaic")
    engine = ServingEngine.from_config(
        params, CFG, ServeConfig(max_slots=2, max_seq=32, block_size=8,
                                 attn_impl="interpret"))
    assert engine.attn_kernel_path == "interpret"
    off = ServingEngine.from_config(
        params, CFG, ServeConfig(max_slots=2, max_seq=32, block_size=8))
    # Default "auto" resolves to the jnp fallback on the CPU tier (gate
    # closed) — the container default stays green and kernel-free.
    assert off.attn_kernel_path == "jnp"
    # A forced kernel path that cannot dispatch fails loudly at the
    # engine: compiled Mosaic on this CPU backend, and any kernel path on
    # a geometry the eligibility predicate refuses.
    with pytest.raises(ValueError, match="TPU backend"):
        ServingEngine(params, CFG, max_slots=2, max_seq=32,
                      attn_impl="pallas")
    monkeypatch.setattr(pattn, "supports_paged_attention",
                        lambda **kw: False)
    with pytest.raises(ValueError, match="cannot dispatch"):
        ServingEngine(params, CFG, max_slots=2, max_seq=32,
                      attn_impl="interpret")


def test_poison_drill_same_flag_decisions(params):
    """The seeded SERVE_POISON drill with the epilogue in the loop: the
    kernel-path engine flags the SAME request and quarantines the same
    number of slots as the jnp-path engine — monitor decisions ride the
    epilogue's entropy/margin without drift."""
    from trustworthy_dl_tpu.chaos import FaultEvent, FaultInjector, \
        FaultKind, FaultPlan
    from trustworthy_dl_tpu.serve.engine import OutputMonitor

    verdicts = {}
    for impl in ("interpret", "jnp"):
        plan = FaultPlan.scripted([
            FaultEvent(step=4, kind=FaultKind.SERVE_POISON),
        ])
        # z_threshold 50: this vocab's natural margin variation reaches
        # z~6 at warmup 3, while the poison overwrite lands z > 10^4 —
        # the drill isolates the poison path, and the assertion below is
        # the cross-impl one that matters: SAME decisions on both paths.
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                               block_size=8, attn_impl=impl,
                               monitor=OutputMonitor(warmup=3,
                                                     z_threshold=50.0),
                               chaos=FaultInjector(plan))
        rng = np.random.default_rng(0)
        for _ in range(5):   # ids 0..4; id 4 is the poisoned one
            plen = int(rng.integers(3, 10))
            engine.submit(ServeRequest(
                prompt=rng.integers(0, CFG.vocab_size, plen).tolist(),
                max_new_tokens=int(rng.integers(2, 6))))
        results = engine.run_until_idle()
        verdicts[impl] = {rid: r.flagged for rid, r in results.items()}
        assert results[4].flagged and not results[3].flagged
        assert len(engine.quarantined_slots) == 1
    assert verdicts["interpret"] == verdicts["jnp"]
