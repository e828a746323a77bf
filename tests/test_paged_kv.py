"""Paged KV pool (trustworthy_dl_tpu/serve/kv_slots.py + the paged
scheduler/engine path): block-table KV with prefix sharing and chunked
prefill — occupancy bounded by tokens, not requests.

Fast tier, ``paged`` marker.  Host contracts: block alloc/free/COW
refcount lifecycle, quarantine-of-a-slot releases only UNSHARED blocks,
out-of-blocks backpressure (and prefix-cache eviction under admission
pressure), radix insert/lookup/LRU-eviction, pool-sizing math, and the
refusal of every way the removed stripe pool could be asked for.  The
compile-once
cell jits the tiny 2-layer GPT-2 (seconds, the test_quant pattern) and
pins that block-table churn never recompiles the fused decode step.

Slow tier: THE smoke — heterogeneous requests with a shared multi-block
prefix through the paged ``ServingEngine``, streams bit-identical to
batch ``generate()``, prefix hits > 0."""

import dataclasses
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.core.config import ServeConfig
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.serve import (
    BlockAllocator,
    PagedBatchingScheduler,
    PrefixCache,
    ServeRequest,
    ServingEngine,
    init_paged_pool,
    kv_bytes_per_token,
    paged_pool_blocks,
)
from trustworthy_dl_tpu.serve.kv_slots import TRASH_BLOCK, blocks_for_span
from trustworthy_dl_tpu.serve.scheduler import SlotTask, request_key_stream

pytestmark = pytest.mark.paged

# vocab_size deliberately differs from tests/test_serve.py's 97 and
# tests/test_quant.py's 101: the prefill/decode jit caches are
# process-global (scheduler._PROGRAMS), so an identical config would let
# another file's run pre-warm the programs this file's strict
# compile-once pin measures (and vice versa).
CFG = gpt2.GPT2Config(vocab_size=103, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


def _task(rid, prompt, max_new, temperature=0.0):
    return SlotTask(
        request_id=rid, prompt=np.asarray(prompt, np.int32),
        max_new_tokens=max_new, temperature=temperature,
        keys=request_key_stream(jax.random.PRNGKey(100 + rid), max_new),
    )


# --------------------------------------------------------------------------
# Fast tier: host-side contracts (no device program runs)
# --------------------------------------------------------------------------


def test_block_allocator_cow_refcount_lifecycle():
    alloc = BlockAllocator(4)
    got = alloc.alloc(2)
    assert len(got) == 2 and alloc.free_count == 2
    # Physical id 0 is the reserved trash block — never handed out.
    assert TRASH_BLOCK not in got
    assert all(alloc.refcount(b) == 1 for b in got)
    assert alloc.alloc(3) is None          # backpressure, not an error
    assert alloc.alloc(0) == []
    # COW sharing: a second holder increfs; releases peel one ref each.
    a, b = got
    alloc.incref(a)
    assert alloc.refcount(a) == 2
    assert alloc.release(a) == "shared"    # one holder remains
    assert alloc.release(a) == "freed"
    assert alloc.release(b) == "freed"
    assert alloc.free_count == 4 and alloc.in_use == 0
    with pytest.raises(ValueError):
        alloc.release(a)                   # double free
    with pytest.raises(ValueError):
        alloc.incref(a)                    # incref of unallocated block


def test_block_quarantine_spares_shared_blocks():
    alloc = BlockAllocator(4)
    shared, private = alloc.alloc(2)
    alloc.incref(shared)                   # e.g. the prefix cache holds it
    # Quarantine releases: a still-shared block merely decrefs, only the
    # block whose LAST holder was the flagged request leaves the pool.
    assert alloc.release(shared, quarantine=True) == "shared"
    assert alloc.release(private, quarantine=True) == "quarantined"
    assert alloc.quarantined == {private}
    assert alloc.free_count == 2           # private is NOT free
    assert alloc.alloc(3) is None          # and cannot be re-handed out
    alloc.unquarantine(private)
    assert alloc.free_count == 3 and alloc.quarantined == set()


def test_scheduler_quarantine_impounds_only_private_blocks(params):
    """Admission, sharing and quarantine-retirement are pure host work —
    quarantining a slot impounds the request's PRIVATE blocks while a
    prefix other holders share stays resident; release_quarantine returns
    the impounded blocks with the decode row."""
    sched = PagedBatchingScheduler(params, CFG, max_slots=3, max_seq=16,
                                   block_size=4, num_blocks=8)
    prompt = list(range(1, 13))            # 12 tokens = 3 full blocks
    a = _task(0, prompt, 4)
    assert sched.admit(a)                  # 16 tokens total -> 4 blocks
    assert sched.blocks.free_count == 4
    # Publish A's full prompt blocks (what finishing its prefill does).
    sched.prefix.insert(prompt, sched.tables[a.slot][:3])
    b = _task(1, prompt, 4)
    assert sched.admit(b)                  # shares 2 blocks, allocs 2
    shared = sched.tables[b.slot][:2]
    private = sched.tables[b.slot][2:]
    assert shared == sched.tables[a.slot][:2]
    assert sched.prefix_hits == 1
    assert sched.prefix_tokens_reused == 8
    assert sched.blocks.free_count == 2

    sched.retire(b, quarantine=True)
    assert b.slot not in sched.tasks
    # Shared prefix blocks survive (A + the cache still hold them);
    # only B's private blocks are impounded with the row.
    assert sched.blocks.quarantined == set(private)
    assert all(sched.blocks.refcount(blk) >= 2 for blk in shared)
    assert sched.blocks.free_count == 2    # impounded, not freed
    assert sched.allocator.capacity == 2

    sched.release_quarantine(b.slot)
    assert sched.blocks.quarantined == set()
    assert sched.blocks.free_count == 4
    assert sched.allocator.capacity == 3


def test_out_of_blocks_backpressure_leaks_nothing(params):
    sched = PagedBatchingScheduler(params, CFG, max_slots=4, max_seq=16,
                                   block_size=4, num_blocks=6)
    a = _task(0, list(range(8)), 4)        # 12 tokens -> 3 blocks
    b = _task(1, list(range(8)), 4)
    assert sched.admit(a) and sched.admit(b)
    assert sched.blocks.free_count == 0
    c = _task(2, list(range(8)), 4)
    assert not sched.admit(c)              # out of blocks: backpressure
    assert c.slot == -1                    # task untouched
    assert sched.allocator.free_count == 2  # claimed row was returned
    assert sched.blocks.in_use == 6        # nothing leaked either way
    sched.retire(a)                        # frees 3 blocks
    assert sched.admit(c)
    # Oversized requests stay a loud error, not backpressure.
    with pytest.raises(ValueError, match="exceeds max_seq"):
        sched.admit(_task(3, list(range(14)), 4))


def test_spec_claims_span_block_boundary_and_rollback():
    """Speculative-claim COW edge case 1 (rejected draft tokens
    spanning a block boundary): the claim set covers every DISTINCT
    block the draft window touches — the partially-filled current block
    and the next one — excluding trash padding and positions past the
    table; rollback (release_speculative) restores every refcount, and
    releasing a claim that was never taken stays a loud double-free."""
    table = [3, 7, 5]
    # Window [6, 11) with block_size 4 crosses the 7→5 boundary.
    assert blocks_for_span(table, 4, 6, 11) == [7, 5]
    assert blocks_for_span(table, 4, 10, 14) == [5]   # past table: trash
    assert blocks_for_span(table, 4, 12, 15) == []    # fully past
    assert blocks_for_span([TRASH_BLOCK, 7], 4, 0, 8) == [7]
    alloc = BlockAllocator(8)
    a, b = alloc.alloc(2)
    claimed = [a, b]
    alloc.claim_speculative(claimed)
    assert alloc.refcount(a) == 2 and alloc.refcount(b) == 2
    alloc.release_speculative(claimed)                # THE rollback
    assert alloc.refcount(a) == 1 and alloc.refcount(b) == 1
    assert alloc.free_count == 6                      # nothing freed
    alloc.release(a)
    with pytest.raises(ValueError):
        alloc.release(a)                              # still loud


def test_spec_rollback_spares_published_prefix_block():
    """Edge case 2 (rollback of a block the prefix cache just
    published): a draft window overlapping a cache-published block only
    ever drops ITS OWN claim — the cache's reference and the owning
    table's reference survive, and the prefix stays servable."""
    blocks = BlockAllocator(8)
    ids = blocks.alloc(2)
    cache = PrefixCache(4, blocks)
    tokens = list(range(60, 68))
    cache.insert(tokens, ids)                 # publish: rc 2 each
    blocks.claim_speculative([ids[1]])        # draft window touches it
    assert blocks.refcount(ids[1]) == 3
    blocks.release_speculative([ids[1]])      # reject: refcount decrement
    assert blocks.refcount(ids[1]) == 2       # table + cache intact
    held = cache.lookup(tokens, 1)            # prefix still served
    assert held == ids[:1]
    blocks.release(held[0])


def test_quarantine_retire_purges_slot_with_unverified_draft_claims(params):
    """Edge case 3 (quarantine-at-retire with un-verified draft
    blocks): a flagged slot retiring while speculative claims are still
    outstanding — the abort path — must unwind the claims FIRST, or the
    table release would see the claimed block as 'shared' and FREE the
    suspect KV back into the pool instead of impounding it."""
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=16,
                                   block_size=4, num_blocks=8,
                                   prefix_cache=False)
    t = _task(0, [1, 2, 3, 4, 5, 6], 8)       # 14 tokens -> 4 blocks
    assert sched.admit(t)
    table = list(sched.tables[t.slot])
    # Simulate a tick aborted between claim and release: the draft
    # window's blocks carry live speculative refs at retire time.
    claimed = blocks_for_span(table, 4, 6, 9)
    sched.blocks.claim_speculative(claimed)
    sched._spec_claims[t.slot] = claimed
    sched.retire(t, quarantine=True)
    # Every block impounded — the claimed ones included — none freed.
    assert sched.blocks.quarantined == set(table)
    assert sched.blocks.free_count == 4
    assert not sched._spec_claims
    assert all(sched.blocks.refcount(b) == 0 for b in table)
    sched.release_quarantine(t.slot)
    assert sched.blocks.free_count == 8 and sched.blocks.in_use == 0


def test_prefix_cache_insert_lookup_refcounts():
    blocks = BlockAllocator(8)
    ids = blocks.alloc(3)
    cache = PrefixCache(4, blocks)
    tokens = list(range(100, 112))         # 12 tokens = 3 full blocks
    assert cache.insert(tokens, ids) == ids  # cache increfs each -> rc 2
    assert cache.insert(tokens, ids) == []   # refresh, never duplicate
    assert len(cache) == 3
    # Lookup increfs every matched block on behalf of the caller.
    assert cache.lookup(tokens, 2) == ids[:2]
    assert blocks.refcount(ids[0]) == 3
    assert blocks.refcount(ids[2]) == 2    # beyond max_blocks: untouched
    assert cache.lookup([7, 7, 7, 7, 7], 2) == []
    # A diverging tail still reuses the matching full-block prefix.
    assert cache.lookup(tokens[:8] + [999] * 4, 3) == ids[:2]


def test_prefix_cache_eviction_lru_skips_live_blocks():
    blocks = BlockAllocator(8)
    ids = blocks.alloc(3)
    cache = PrefixCache(4, blocks)
    tokens = list(range(100, 112))
    cache.insert(tokens, ids)
    hold = cache.lookup(tokens, 2)         # a "live request" shares 2
    for b in ids:
        blocks.release(b)                  # the owning request retires
    # Only the leaf with no live holder (ids[2]) may be evicted; the
    # shared blocks are pinned by the lookup's refs, the interior nodes
    # by their cached extensions.
    assert cache.evict(3) == 1
    assert blocks.refcount(ids[2]) == 0 and len(cache) == 2
    for b in hold:
        blocks.release(b)                  # live holders retire
    assert cache.evict(8) == 2             # leaf-first unwinds the chain
    assert len(cache) == 0 and blocks.free_count == 8
    # LRU order: the least recently touched single-block prefix goes
    # first.
    a = blocks.alloc(1)
    b = blocks.alloc(1)
    lru = PrefixCache(2, blocks)
    lru.insert([1, 2], a)
    lru.insert([3, 4], b)
    blocks.release(a[0])
    blocks.release(b[0])                   # cache is the sole holder
    for blk in lru.lookup([1, 2], 1):      # touch [1, 2] -> [3, 4] is LRU
        blocks.release(blk)
    assert lru.evict(1) == 1
    assert blocks.refcount(b[0]) == 0 and len(lru) == 1
    assert lru.lookup([1, 2], 1) != []


def test_quarantine_purges_published_prefix_blocks(params):
    """A flagged request's own PUBLISHED prompt blocks leave the prefix
    cache and are impounded with its row — without the purge their cache
    reference keeps them 'shared' at quarantine-retire, and a later
    same-prefix request would decode straight off suspect KV with no
    prefill."""
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=16,
                                   block_size=4, num_blocks=8)
    prompt = list(range(1, 13))            # 3 full blocks
    a = _task(0, prompt, 4)
    assert sched.admit(a)                  # 4 blocks total
    # What _record_prefill does at prefill completion: publish and
    # remember the publication.
    sched._published[a.slot] = sched.prefix.insert(
        prompt, sched.tables[a.slot][:3])
    table = list(sched.tables[a.slot])
    sched.retire(a, quarantine=True)
    # ALL of A's blocks are impounded — published prompt blocks
    # included — and its cache entries are gone.
    assert sched.blocks.quarantined == set(table)
    assert len(sched.prefix) == 0
    b = _task(1, prompt, 4)
    assert sched.admit(b)                  # fresh blocks, full prefill
    assert sched.prefix_hits == 0          # nothing suspect was reused
    assert not (set(sched.tables[b.slot]) & set(table))


def test_prefix_purge_cascades_to_extension_nodes():
    """Purging a prefix node also drops the cached extensions hanging
    off it (unreachable once the parent is gone), releasing the cache's
    reference on each — no orphaned nodes leaking block refs."""
    blocks = BlockAllocator(4)
    base = blocks.alloc(2)                 # published by request X
    ext = blocks.alloc(1)                  # published by request Y
    cache = PrefixCache(4, blocks)
    tokens = list(range(200, 212))
    assert cache.insert(tokens[:8], base) == base
    assert cache.insert(tokens, base + ext) == ext  # child of base[1]
    assert len(cache) == 3
    assert cache.purge(set(base)) == 3     # both + the cascaded child
    assert len(cache) == 0
    assert blocks.refcount(base[0]) == 1   # only X's table ref remains
    assert blocks.refcount(ext[0]) == 1    # cascade released Y's cache ref


def test_admission_evicts_prefix_cache_under_pressure(params):
    """A full pool with cache-only blocks evicts the prefix cache to
    admit new work — cached prefixes are a best-effort accelerant, never
    a capacity reservation."""
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=8,
                                   block_size=4, num_blocks=2)
    ids = sched.blocks.alloc(2)
    sched.prefix.insert(list(range(50, 58)), ids)
    for b in ids:
        sched.blocks.release(b)            # cache is the sole holder
    assert sched.blocks.free_count == 0
    t = _task(0, [1, 2, 3, 4], 4)          # 8 tokens -> needs 2 blocks
    assert sched.admit(t)                  # evicted its way in
    assert len(sched.prefix) == 0
    assert sched.blocks.in_use == 2


def test_pool_sizing_helpers():
    """kv_bytes_per_token is the budgeting primitive; it and the block
    sizing agree with the pools they describe (trash block included —
    honest HBM math)."""
    dh = CFG.n_embd // CFG.n_head
    heads = CFG.n_layer * CFG.n_head
    assert kv_bytes_per_token(CFG) == 2 * heads * dh * 4        # f32
    assert kv_bytes_per_token(CFG, jnp.int8) == 2 * heads * (dh + 4)
    # One full 48-position sequence: 3 blocks + the trash block.
    assert (init_paged_pool(CFG, 3, 16).pool_bytes
            == (48 + 16) * kv_bytes_per_token(CFG))
    # A budget of exactly N blocks' bytes buys N-1 usable (+1 trash).
    bpt = kv_bytes_per_token(CFG)
    assert paged_pool_blocks(CFG, 6 * 16 * bpt, 16) == 5
    pool = init_paged_pool(CFG, 5, 16)
    assert pool.num_blocks == 5 and pool.block_size == 16
    assert pool.pool_bytes == 6 * 16 * bpt  # trash block counted
    assert pool.pool_bytes <= 6 * 16 * bpt  # fits the budget it was
    # int8 pool pages values AND per-(head, position) scales identically,
    # so the quant capacity win compounds with paging.
    q = init_paged_pool(CFG, 5, 16, kv_dtype=jnp.int8)
    assert q.quantized
    assert q.pool_bytes == 6 * 16 * kv_bytes_per_token(CFG, jnp.int8)
    assert q.k.shape == (CFG.n_layer, 6, 16, CFG.n_embd)
    assert q.k_scale.shape == (CFG.n_layer, 6, 16, CFG.n_head)


@pytest.mark.parametrize("attn_impl", ["jnp", "interpret"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_a_serving_program_writes_its_rows_and_nothing_else(
        params, program, kv_dtype, attn_impl):
    """The pool is carried through the layer loop and written in place:
    after ONE call of a serving program every row of every pool array
    outside the R·T positions the call wrote — other layers' rows do not
    exist, each layer writes its own; other blocks; other offsets of the
    written blocks; the trash block aside — is bit-equal to before, and
    every written row of every layer is new."""
    from trustworthy_dl_tpu.models import generate as gen
    from trustworthy_dl_tpu.serve import scheduler as sch
    from trustworthy_dl_tpu.serve.kv_slots import init_paged_pool

    rng = np.random.default_rng(11)
    bsz, nb, nbps = 8, 12, 4
    kv = init_paged_pool(CFG, nb, bsz, kv_dtype=jnp.dtype(kv_dtype))

    def fill(a):
        if a is None:
            return None
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.01, 0.5, a.shape), a.dtype)

    before = tuple(map(fill, (kv.k, kv.v, kv.k_scale, kv.v_scale)))
    view = gen._decode_view(params, CFG)
    key = jax.random.PRNGKey(3)
    if program == "chunk":
        # Two slots' chunks and a padding row in one call: 16 positions
        # from the block-aligned 8 (blocks 6 and 7 whole), 16 from 0
        # (blocks 9 and 10 whole), and a row whose table is all trash.
        table = jnp.asarray([[5, 6, 7, 8], [9, 10, 11, 12], [0, 0, 0, 0]],
                            jnp.int32)
        tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (3, 16)),
                             jnp.int32).at[2].set(0)
        out = sch._paged_chunk_impl(
            CFG, *before, view, tokens, table,
            jnp.asarray([8, 0, 0], jnp.int32),
            jnp.asarray([15, 15, 0], jnp.int32), jnp.stack([key] * 3),
            jnp.ones(3), jnp.ones(3, bool), attn_impl=attn_impl)
        after = out[:4]
        written = [(b, o) for b in (6, 7, 9, 10) for o in range(bsz)]
    else:
        # Three live rows at ragged lengths and an idle row (all trash).
        table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12],
                             [0, 0, 0, 0]], jnp.int32)
        lengths = jnp.asarray([1, 11, 26, 0], jnp.int32)
        tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, 4), jnp.int32)
        out = sch._paged_decode_impl(
            CFG, *before, view, tokens, table, lengths,
            jnp.stack([key] * 4), jnp.ones(4), jnp.ones(4, bool),
            attn_impl=attn_impl)
        after = out[1:]
        written = [(1, 1), (6, 3), (12, 2)]
    for was, now in zip(before, after):
        if was is None:
            assert now is None
            continue
        assert now.shape == was.shape and now.dtype == was.dtype
        was, now = np.asarray(was), np.asarray(now)
        untouched = np.ones(was.shape[:3], bool)
        untouched[:, TRASH_BLOCK] = False
        for block, offset in written:
            untouched[:, block, offset] = False
            # Every layer wrote its own row there.
            assert (was[:, block, offset] != now[:, block, offset]) \
                .any(axis=-1).all()
        assert np.array_equal(was[untouched], now[untouched])


def test_int8_kv_defaults_to_full_prompt_prefill(params):
    """Under int8 KV the default prefill chunk is the WHOLE prompt: a
    chunked continuation would attend to the previous chunk's
    already-quantized blocks, while the whole-prompt program prefills
    through a full-precision local cache — the first token's parity with
    generate() holds on the one-chunk path.  An explicit chunk opts back into chunking."""
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=32,
                                   block_size=8, kv_dtype="int8")
    assert sched.chunk == 32
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=32,
                                   block_size=8, kv_dtype="int8",
                                   prefill_chunk=8)
    assert sched.chunk == 8
    # Model-dtype pools keep the bounded auto chunk (min(64, max_seq)
    # rounded to a block multiple — 32 for this tiny geometry).
    sched = PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=32,
                                   block_size=8)
    assert sched.chunk == 32


def _serve_config_of_the_benchmark():
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmark"
            / "configs" / "gpt2-large-774m.json")
    return json.loads(path.read_text())["deployment"]["serve_config"]


def _cli_legacy_stripe(_params):
    from trustworthy_dl_tpu import cli

    cli.build_serve_parser().parse_args(["--legacy-stripe"])


# Every way the removed stripe pool could be asked for: the error, what
# its message names (argparse exits 2 and has none), and the ask.
_STRIPE_ASKS = {
    "config-paged-false": (
        TypeError, "paged",
        lambda params: ServeConfig(paged=False)),
    "engine-paged-kwarg": (
        TypeError, "paged",
        lambda params: ServingEngine(params, CFG, max_seq=32, paged=False)),
    "engine-buckets-kwarg": (
        TypeError, "buckets",
        lambda params: ServingEngine(params, CFG, max_seq=32,
                                     buckets=(16,))),
    "scheduler-buckets-kwarg": (
        TypeError, "buckets",
        lambda params: PagedBatchingScheduler(
            params, CFG, max_slots=2, max_seq=32, block_size=8,
            buckets=(16,))),
    "cli-legacy-stripe": (SystemExit, None, _cli_legacy_stripe),
}


@pytest.mark.parametrize("case",
                         [*_STRIPE_ASKS, "benchmark-config-constructs"])
def test_one_kv_pool_no_switch(params, case):
    """The serving stack has ONE KV pool since PR 33: every way the
    removed stripe pool could be asked for is refused loudly, where the
    operator typed it, ``paged`` is no key of ``ServeConfig`` at all,
    and the benchmark's serving configuration constructs the paged
    scheduler."""
    if case in _STRIPE_ASKS:
        error, match, ask = _STRIPE_ASKS[case]
        with pytest.raises(error, match=match) as refusal:
            ask(params)
        if error is SystemExit:
            assert refusal.value.code == 2
        return
    with pytest.raises(TypeError, match="paged"):
        ServeConfig(paged=True)
    config = ServeConfig(**_serve_config_of_the_benchmark())
    assert config.max_slots == 24 and config.max_seq == 1024
    # The file's every other key, at this file's tiny geometry.
    engine = ServingEngine.from_config(
        params, CFG, dataclasses.replace(config, max_slots=2, max_seq=32))
    assert isinstance(engine.scheduler, PagedBatchingScheduler)
    assert not hasattr(engine, "paged")


def test_engine_validates_geometry_and_routes_config(params):
    """Engines built without a config hit the same loud geometry check
    as ``ServeConfig`` (bad geometry fails at construction, where the
    operator typed it), and from_config threads every paged knob
    through."""
    with pytest.raises(ValueError, match="multiple of block_size"):
        ServingEngine(params, CFG, max_seq=40, block_size=16)
    with pytest.raises(ValueError, match="multiple of block_size"):
        ServeConfig(max_seq=40, block_size=16)
    with pytest.raises(ValueError, match="num_blocks"):
        ServeConfig(max_seq=64, block_size=16, num_blocks=2)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeConfig(max_seq=64, block_size=16, prefill_chunk=24)
    # The paged scheduler enforces the model's position-table depth — a
    # too-deep max_seq would otherwise silently gather clamped position
    # embeddings.
    with pytest.raises(ValueError, match="position table"):
        ServingEngine(params, CFG, max_seq=128, block_size=16)
    with pytest.raises(ValueError, match="position table"):
        PagedBatchingScheduler(params, CFG, max_slots=2, max_seq=128)
    cfg = ServeConfig(max_slots=2, max_seq=32, block_size=8,
                      num_blocks=10, prefix_cache=False, prefill_chunk=16)
    engine = ServingEngine.from_config(params, CFG, cfg)
    sched = engine.scheduler
    assert isinstance(sched, PagedBatchingScheduler)
    assert sched.block_size == 8 and sched.num_blocks == 10
    assert sched.prefix is None and sched.chunk == 16
    # Default pool sizing: every slot can hold a full max_seq sequence.
    default = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                            block_size=8)
    assert default.scheduler.num_blocks == 2 * (32 // 8)


def test_compile_once_under_block_table_churn(params):
    """THE pin: block tables are traced VALUES — admissions, retirements,
    block reuse, prefix hits and chunked prefill across two heterogeneous
    waves never recompile the fused paged decode step."""
    registry = MetricsRegistry()
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           block_size=8, prefill_chunk=8, queue_limit=32,
                           registry=registry)
    before = engine.scheduler.decode_cache_size()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, CFG.vocab_size, 9).tolist()  # > one block
    waves = 0
    for wave in range(2):                  # second wave reuses freed blocks
        for i in range(4):
            plen = int(rng.integers(3, 13))  # crosses the 8-pos chunk
            prompt = (shared + [int(i)] if i % 2 == 0
                      else rng.integers(0, CFG.vocab_size, plen).tolist())
            rid = engine.submit(ServeRequest(
                prompt=prompt, max_new_tokens=int(rng.integers(1, 5))))
            assert rid is not None
            waves += 1
    results = engine.run_until_idle()
    assert len(results) == waves
    assert all(r.status == "completed" for r in results.values())
    assert engine.scheduler.decode_cache_size() - before == 1
    # The shared prompt actually exercised the radix cache, and the
    # paged gauges ride the registry snapshot (obs satellite).
    summary = engine.metrics_summary()
    assert summary["prefix_hits"] >= 1
    assert summary["prefix_hit_rate"] > 0
    snap = registry.snapshot()["metrics"]
    assert "tddl_serve_blocks_in_use" in snap
    assert "tddl_serve_tokens_in_flight" in snap
    assert registry.get("tddl_serve_prefix_hits_total").value() == float(
        summary["prefix_hits"]
    )


def test_quarantined_blocks_starving_pool_sheds_queue(params):
    """Liveness under block starvation: a flagged request's impounded
    blocks can starve the pool while decode rows remain free — the
    engine must shed the unservable queue as no_capacity, not spin to
    the iteration bound, and release_quarantine must restore service."""

    class FlagAll:
        def observe(self, entropies, margins):
            return True, 99.0

    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           block_size=8, prefill_chunk=8, num_blocks=4,
                           prefix_cache=False, monitor=FlagAll())
    rid_a = engine.submit(ServeRequest(prompt=list(range(1, 17)),
                                       max_new_tokens=16))  # all 4 blocks
    rid_b = engine.submit(ServeRequest(prompt=[1, 2, 3, 4],
                                       max_new_tokens=4))   # needs 2
    results = engine.run_until_idle()
    assert results[rid_a].flagged
    assert engine.scheduler.blocks.quarantined != set()
    # One decode row is still free — the old all-rows-quarantined guard
    # would not have tripped; the block pool is what starved.
    assert engine.scheduler.allocator.free_count >= 1
    assert results[rid_b].status == "no_capacity"
    engine.monitor = None
    for slot in list(engine.quarantined_slots):
        engine.release_quarantine(slot)
    rid = engine.submit(ServeRequest(prompt=[5, 6, 7], max_new_tokens=2))
    assert engine.run_until_idle()[rid].status == "completed"


def test_mid_prefill_deadline_expiry_releases_blocks(params):
    """A deadline that passes while a long prompt is mid-chunked-prefill
    retires the request (empty output) instead of burning the remaining
    chunk programs; its row and every claimed block come back."""
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           block_size=8, prefill_chunk=8)
    req = ServeRequest(prompt=list(range(1, 25)), max_new_tokens=4,
                       deadline_s=30.0)
    rid = engine.submit(req)
    engine.step()                      # admit + first chunk only
    assert rid in engine._inflight and rid not in engine.results
    req.deadline_s = -1.0              # force expiry mid-prefill
    engine.step()
    res = engine.results[rid]
    assert res.status == "deadline_exceeded"
    assert res.tokens == [] and res.ttft_s is None
    assert engine.scheduler.allocator.free_count == 2
    assert engine.scheduler.blocks.in_use == 0  # nothing was published
    assert not engine._inflight


# --------------------------------------------------------------------------
# Slow tier: the parity smoke
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_paged_smoke_bit_identical_to_generate(params):
    """THE acceptance smoke: heterogeneous requests — several sharing a
    multi-block prompt prefix, prompts longer than the prefill chunk, a
    temperature-sampled stream — through the paged engine (3 decode rows,
    chunked prefill interleaved with decode).  Every request's tokens
    must be BIT-IDENTICAL to batch generate(); the paged run must
    actually share (prefix hits > 0) and compile its decode step exactly
    once."""
    rng = np.random.default_rng(11)
    common = rng.integers(0, CFG.vocab_size, 20).tolist()  # 2 full blocks
    sample_key = jax.random.PRNGKey(42)

    def build_requests():
        reqs = [ServeRequest(prompt=common + [5], max_new_tokens=2)]
        for i in range(4):                 # heterogeneous fillers
            plen = 3 + 4 * i               # 3, 7, 11, 15: spans chunks
            reqs.append(ServeRequest(
                prompt=[(7 * i + j) % CFG.vocab_size for j in range(plen)],
                max_new_tokens=3 + i))
        # Same-prefix requests queued BEHIND the fillers: they admit
        # after the first common prompt's prefill published its blocks.
        reqs.append(ServeRequest(prompt=common + [9, 9], max_new_tokens=4))
        reqs.append(ServeRequest(prompt=common + [3, 1, 4],
                                 max_new_tokens=3))
        reqs.append(ServeRequest(prompt=[2, 71, 8, 28], max_new_tokens=6,
                                 temperature=0.8, rng=sample_key))
        return reqs

    engine = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                           queue_limit=32, rng=jax.random.PRNGKey(5),
                           block_size=8, prefill_chunk=16)
    before = engine.scheduler.decode_cache_size()
    for req in build_requests():
        engine.submit(req)
    results = engine.run_until_idle()
    assert len(results) == 8
    assert all(r.status == "completed" for r in results.values())
    assert engine.scheduler.decode_cache_size() - before == 1
    outputs = {rid: r.tokens for rid, r in results.items()}

    # Bit-identical to batch generate() under the same keys.
    for rid, req in enumerate(build_requests()):
        ref = generate(params, CFG,
                       jnp.asarray([list(req.prompt)], jnp.int32),
                       req.max_new_tokens, temperature=req.temperature,
                       rng=(req.rng if req.rng is not None
                            else jax.random.fold_in(jax.random.PRNGKey(5),
                                                    rid)))
        ref_tokens = np.asarray(ref)[0, len(req.prompt):].tolist()
        assert outputs[rid] == ref_tokens, f"request {rid}"

    # The sharing was real: later common-prefix admissions reused cached
    # blocks and prefilled only their suffix.
    summary = engine.metrics_summary()
    assert summary["prefix_hits"] >= 2
    assert summary["prefix_tokens_reused"] >= 2 * 2 * 8
    assert summary["prefix_hit_rate"] > 0
    # After the drain only the radix cache still references blocks.
    sched = engine.scheduler
    assert sched.blocks.in_use == len(sched.prefix)
    assert summary["peak_tokens_in_flight"] > 0


@pytest.mark.adversary
def test_vote_replay_publish_prefix_false_leaves_cache_untouched(params):
    """Adversarial-serving satellite (replay-path honesty): a verdict-
    vote REPLAY (``ServeRequest.publish_prefix=False``) may READ the
    prefix cache but never publishes its own prompt blocks — the cache
    and its block references are exactly as the replay found them, so
    audit traffic can never pin pool blocks or seed later requests from
    a replay's prefill."""
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

    eng = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                        block_size=4)
    sched = eng.scheduler
    prompt = list(range(2, 14))                 # 3 full blocks
    eng.submit(ServeRequest(prompt=prompt, max_new_tokens=3,
                            publish_prefix=False))
    eng.run_until_idle()
    assert len(sched.prefix) == 0               # nothing cached
    assert sched.blocks.free_count == sched.blocks.num_blocks
    # A second audit replay of the same prompt: still a cache miss.
    eng.submit(ServeRequest(prompt=prompt, max_new_tokens=3,
                            publish_prefix=False))
    eng.run_until_idle()
    assert sched.prefix_hits == 0
    # A NORMAL request publishes as always...
    eng.submit(ServeRequest(prompt=prompt, max_new_tokens=3))
    eng.run_until_idle()
    assert len(sched.prefix) == 3
    # ...and a replay may read it without perturbing it.
    eng.submit(ServeRequest(prompt=prompt, max_new_tokens=3,
                            publish_prefix=False))
    eng.run_until_idle()
    assert sched.prefix_hits == 1
    assert len(sched.prefix) == 3
