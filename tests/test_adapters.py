"""Per-tenant paged adapter tier (serve/adapters.py wired through
scheduler/engine/fleet/chaos).

Fast tier: host contracts — config validation, the page-row spelling,
pool lifecycle (LRU eviction skips live refs, quarantine impounds
deferred), deterministic materialisation/quantisation, Zipf assignment
determinism and base-traffic invariance, per-adapter QoS throttling.
Slow tier: the compile-sensitive and numeric acceptance claims —
adapter-off AND zero-page streams bit-identical to generate(),
adapter-carrying streams diverge yet replicate deterministically,
two-wave adapter churn with ZERO recompiles, and THE ADAPTER_POISON
drill: the fleet quarantines the ADAPTER (replicas stay healthy, slot
evidence transferred back) with counts matching ``predict_fleet()``
exactly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.chaos import (
    FaultEvent,
    FaultInjector,
    FaultKind,
    FaultPlan,
)
from trustworthy_dl_tpu.core.config import validate_adapters
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.serve import (
    FleetConfig,
    ServeRequest,
    ServingEngine,
    ServingFleet,
    WorkloadConfig,
    generate_workload,
)
from trustworthy_dl_tpu.serve.adapters import (
    ZERO_PAGE,
    AdapterPool,
    adapter_page_row,
    adapter_pool_bytes,
    materialize_adapter,
    quantize_adapter,
)
from trustworthy_dl_tpu.serve.control import TenantQuotaConfig
from trustworthy_dl_tpu.serve.workload import zipf_adapter_assignments

pytestmark = pytest.mark.adapters

# Unique decode geometry for this file (vocab 109): the process-global
# jit cache must never hand another serve-test file's compiled program
# to this one's compile-sensitive assertions (test_serve/test_quant/
# test_paged_kv/test_fleet document the same split: 97/101/103/107).
CFG = gpt2.GPT2Config(vocab_size=109, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Fast tier: host-side contracts
# --------------------------------------------------------------------------


def test_validate_adapters_contract():
    validate_adapters(0, None, "fp4", 2)      # disabled: no demands
    validate_adapters(4, 4, "int8", 0)        # the int8 tier
    with pytest.raises(ValueError):
        validate_adapters(-1, None, "model", 0)
    with pytest.raises(ValueError):
        validate_adapters(4, 4, "model", 2)   # speculative decode
    with pytest.raises(ValueError):
        validate_adapters(4, 4, "fp4", 0)     # unknown tier
    with pytest.raises(ValueError):
        validate_adapters(4, 0, "model", 0)   # zero usable pages


def test_adapter_page_row_is_the_one_spelling():
    row = adapter_page_row({1: 3, 2: 1}, 4)
    assert row.dtype == np.int32
    assert row.tolist() == [ZERO_PAGE, 3, 1, ZERO_PAGE]
    assert adapter_page_row({}, 2).tolist() == [ZERO_PAGE, ZERO_PAGE]


def test_pool_bytes_int8_tier_is_smaller():
    f32 = adapter_pool_bytes(CFG, 4, 8, "model")
    i8 = adapter_pool_bytes(CFG, 4, 8, "int8")
    assert i8 < f32 / 3        # ~4x minus the f32 scale sidecars


def test_pool_lifecycle_lru_eviction_skips_live_refs():
    pool = AdapterPool(CFG, rank=2, pages=2)
    pa, pb = pool.acquire("A"), pool.acquire("B")
    assert pa != pb and ZERO_PAGE not in (pa, pb)
    # Both pages carry an in-flight request: eviction must refuse.
    assert pool.acquire("C") is None
    pool.release("A")                      # A cold (residency ref only)
    pc = pool.acquire("C")                 # LRU-evicts exactly A
    assert pc == pa
    m = pool.metrics()
    # 4 misses: A, B, the REFUSED C (backpressure is a miss), C again.
    assert m["evictions"] == 1 and m["uploads"] == 3 and m["misses"] == 4
    assert "A" not in pool.resident
    assert pool.acquire("B") == pb         # resident: a hit, no upload
    assert pool.metrics()["hits"] == 1
    assert pool.metrics()["uploads"] == 3


def test_pool_quarantine_impounds_deferred_and_readmits():
    pool = AdapterPool(CFG, rank=2, pages=2)
    pool.acquire("A")
    pool.quarantine("A")                   # live request: impound defers
    assert pool.acquire("A") is None       # but resolution refuses NOW
    assert "A" in pool.resident
    pool.release("A")                      # last ref drains -> impounded
    assert "A" not in pool.resident
    assert pool.pages_in_use == 1          # impounded still counts
    assert pool.acquire("B") is not None
    assert pool.acquire("C") is None       # impound shrank the pool
    pool.unquarantine("A")                 # page returns to the free list
    assert pool.acquire("A") is not None   # fresh upload on readmission
    assert pool.metrics()["uploads"] == 3


def test_materialize_deterministic_and_quantize_bounds():
    a1, b1 = materialize_adapter("tenant-x", CFG, 4)
    a2, b2 = materialize_adapter("tenant-x", CFG, 4)
    np.testing.assert_array_equal(a1, a2)  # replica-exact by id alone
    np.testing.assert_array_equal(b1, b2)
    a3, _ = materialize_adapter("tenant-y", CFG, 4)
    assert not np.array_equal(a1, a3)
    a_q, a_s, b_q, b_s = quantize_adapter(a1, b1)
    assert a_q.dtype == np.int8 and b_q.dtype == np.int8
    assert np.all(a_s > 0) and np.all(b_s > 0)
    deq = a_q.astype(np.float32) * a_s[:, :, None, None]
    assert float(np.max(np.abs(deq - a1))) <= float(np.max(a_s)) * 0.5 + 1e-6


def test_zipf_assignments_deterministic_and_never_perturb_base_traffic():
    names = [f"t{i}" for i in range(20)]
    m1 = zipf_adapter_assignments(names, 5, seed=3)
    assert m1 == zipf_adapter_assignments(names, 5, seed=3)
    assert set(m1) == set(names)
    assert all(v.startswith("adapter-") for v in m1.values())
    assert zipf_adapter_assignments(names, 0) == {}
    # Adding adapters to a workload config must not move a single
    # arrival/prompt/tenant draw of the base traffic.
    base = generate_workload(WorkloadConfig(seed=1, num_requests=12),
                             vocab_size=CFG.vocab_size, max_seq=48)
    adapted = generate_workload(
        WorkloadConfig(seed=1, num_requests=12, num_adapters=4),
        vocab_size=CFG.vocab_size, max_seq=48)
    key = [(i.t_arrive, i.prompt, i.tenant, i.max_new_tokens)
           for i in base]
    assert key == [(i.t_arrive, i.prompt, i.tenant, i.max_new_tokens)
                   for i in adapted]
    assert all(i.adapter is None for i in base)
    assert all(i.adapter is not None for i in adapted)


def test_adapter_quota_throttles_and_refunds_tenant_spend(params):
    """Two tenants share one hot adapter: the second submission trips
    the ADAPTER bucket (not the tenant's), loudly, and the refused
    tenant's own budget is refunded in full."""
    fleet = ServingFleet(
        params, CFG,
        fleet_config=FleetConfig(
            num_replicas=1,
            tenant_quota=TenantQuotaConfig(capacity_tokens=100.0),
            adapter_quota=TenantQuotaConfig(capacity_tokens=10.0),
        ),
        max_slots=2, max_seq=48, queue_limit=8,
        block_size=8, num_blocks=16,
        adapter_rank=2, adapter_pool_pages=2,
        adapter_map={"t1": "ad-hot", "t2": "ad-hot"},
    )
    ok = fleet.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=5,
                                   tenant="t1"))          # cost 8 <= 10
    assert ok is not None
    refused = fleet.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=5,
                                        tenant="t2"))     # bucket has 2
    assert refused is None
    assert fleet.counters["adapter_throttles"] == 1
    assert fleet.counters["throttles"] == 0               # tenant plane clean
    # The refused tenant's own bucket was refunded to capacity...
    lvl, _ = fleet._buckets._b["t2"]
    assert lvl == 100.0
    # ...and an unadapted tenant is untouched by the adapter plane.
    assert fleet.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=5,
                                     tenant="t3")) is not None


# --------------------------------------------------------------------------
# Slow tier: numeric + compile-once + THE drill
# --------------------------------------------------------------------------


def _drain(engine, reqs):
    fids = [engine.submit(r) for r in reqs]
    assert all(f is not None for f in fids)
    results = engine.run_until_idle()
    return [results[f].tokens for f in fids]


def _mixed_requests(tenant=None):
    """Greedy + sampled requests with fixed shapes (shared by every
    parity arm, so all arms replay identical traffic)."""
    rng = np.random.default_rng(11)
    out = []
    for i in range(4):
        prompt = rng.integers(0, CFG.vocab_size, 6).tolist()
        if i % 2 == 0:
            out.append(ServeRequest(prompt=prompt, max_new_tokens=5,
                                    temperature=0.0, tenant=tenant))
        else:
            out.append(ServeRequest(prompt=prompt, max_new_tokens=5,
                                    temperature=0.8,
                                    rng=jax.random.PRNGKey(100 + i),
                                    tenant=tenant))
    return out


@pytest.mark.slow
def test_adapter_off_and_zero_page_streams_bit_identical(params):
    """Adapter-off (rank 0: structural absence) AND adapter-capable-but
    -unused (rank > 0, every slot on the zero page) streams are
    bit-identical to generate() — greedy and sampled; the int8-KV tier
    pins rank 0 vs zero-page against each other."""
    refs = []
    for r in _mixed_requests():
        ref = generate(params, CFG,
                       jnp.asarray([list(r.prompt)], jnp.int32),
                       r.max_new_tokens, temperature=r.temperature,
                       rng=r.rng)
        refs.append(np.asarray(ref)[0, len(r.prompt):].tolist())

    arms = {
        "rank0": dict(block_size=8, num_blocks=24),
        "zero-page": dict(block_size=8, num_blocks=24,
                          adapter_rank=2, adapter_pool_pages=2),
    }
    for label, kw in arms.items():
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                               queue_limit=8, **kw)
        assert _drain(engine, _mixed_requests()) == refs, label

    i8 = []
    for kw in (dict(), dict(adapter_rank=2, adapter_pool_pages=2)):
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                               queue_limit=8, block_size=8,
                               num_blocks=24, kv_dtype="int8", **kw)
        i8.append(_drain(engine, _mixed_requests()))
    assert i8[0] == i8[1]      # int8 KV: rank 0 == zero page, stream-exact


@pytest.mark.slow
def test_adapter_streams_diverge_and_replicate_deterministically(params):
    """An adapter-carrying tenant's stream really differs from the base
    model's, and a second engine (a fleet replica) reproduces it
    bit-for-bit from the adapter id alone."""
    prompt = [5, 17, 3, 88, 41, 2]
    ref = np.asarray(generate(params, CFG,
                              jnp.asarray([prompt], jnp.int32), 8,
                              temperature=0.0))[0, 6:].tolist()

    def run_replica():
        engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                               queue_limit=4, block_size=8,
                               num_blocks=24, adapter_rank=4,
                               adapter_pool_pages=2,
                               adapter_map={"tx": "ad-x"})
        # The tiny random-init model's argmax gaps dwarf the default
        # init scale; bump it (BEFORE first acquire — uploads are lazy)
        # so the delta visibly moves the greedy stream.
        engine.adapter_pool.init_scale = 0.5
        rid = engine.submit(ServeRequest(prompt=prompt, max_new_tokens=8,
                                         tenant="tx"))
        result = engine.run_until_idle()[rid]
        assert result.status == "completed"
        assert result.adapter == "ad-x"
        return result.tokens

    tokens_a = run_replica()
    assert tokens_a != ref                 # the adapter is really applied
    assert tokens_a == run_replica()       # replica-deterministic


@pytest.mark.slow
def test_two_wave_adapter_churn_never_recompiles(params):
    """Acceptance pin: a second wave of NEVER-SEEN adapters (misses,
    uploads, LRU evictions, different tenant mix) executes zero XLA
    compilations — residency churn is buffer updates under a traced
    page table, exactly the KV block-table discipline."""
    from trustworthy_dl_tpu.obs.compilewatch import CompileRegistry

    adapter_map = {f"t{i}": f"ad{i}" for i in range(6)}
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                           queue_limit=16, block_size=8,
                           num_blocks=24, adapter_rank=2,
                           adapter_pool_pages=2, adapter_map=adapter_map)

    def wave(tenants):
        rng = np.random.default_rng(7)
        reqs = []
        for i, tenant in enumerate(tenants):
            prompt = rng.integers(0, CFG.vocab_size, 5).tolist()
            reqs.append(ServeRequest(prompt=prompt, max_new_tokens=4,
                                     temperature=0.0, tenant=tenant))
        for r in reqs:
            assert engine.submit(r) is not None
        return engine.run_until_idle()

    # Wave 1 (warmup): 3 adapters through 2 pages already evicts.
    wave(["t0", "t1", "t2", "t0"])
    ev1 = engine.adapter_pool.evictions
    assert ev1 >= 1

    reg = CompileRegistry().install()
    try:
        results = wave(["t3", "t4", "t5", "t3", "t1"])
    finally:
        reg.uninstall()
    assert all(r.status == "completed" for r in results.values())
    assert engine.adapter_pool.evictions > ev1   # churn really happened
    assert reg.total == 0, reg.summary()         # and compiled NOTHING


class PoisonSignatureMonitor:
    """Deterministic stand-in (tests/test_fleet.py): flags exactly the
    chaos poison signature — margin >> any real logit margin — so the
    drill pins the fleet's RESPONSE to flags, independent of how many
    requests a rolling z-score baseline has absorbed."""

    def observe(self, entropies, margins):
        poisoned = float(np.mean(margins)) > 100.0
        return poisoned, (99.0 if poisoned else 0.0)


@pytest.mark.slow
def test_adapter_poison_drill_quarantines_adapter_not_replica(params):
    """THE acceptance drill: a scripted ADAPTER_POISON corrupts every
    stream served THROUGH one adapter, on whichever replica hosts it.
    The fleet's per-adapter flag window convicts the ADAPTER fleet-wide
    — both replicas stay healthy, impounded slot evidence transfers
    back on conviction — with counts matching ``predict_fleet()``
    exactly; heal + release readmits the adapter cleanly."""
    plan = FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.ADAPTER_POISON, tenant="ad-ev"),
    ])
    inj = FaultInjector(plan)
    fleet = ServingFleet(
        params, CFG,
        fleet_config=FleetConfig(
            num_replicas=2, flag_min_count=2,
            quarantine_cooloff_ticks=10_000,
        ),
        chaos=inj,
        max_slots=2, max_seq=48, queue_limit=32,
        block_size=8, num_blocks=32,
        adapter_rank=4, adapter_pool_pages=4,
        adapter_map={"t-evil": "ad-ev", "t-good": "ad-ok"},
        monitor=PoisonSignatureMonitor(),
    )
    rng = np.random.default_rng(3)
    good_fids = []
    for i in range(8):
        tenant = "t-evil" if i % 2 == 0 else "t-good"
        prompt = rng.integers(0, CFG.vocab_size, 5).tolist()
        fid = fleet.submit(ServeRequest(prompt=prompt, max_new_tokens=4,
                                        tenant=tenant))
        assert fid is not None
        if tenant == "t-good":
            good_fids.append(fid)
    results = fleet.run_until_idle(max_ticks=2000)

    # Exactly the plan-predicted counts: the quarantine lands on the
    # ARTIFACT, never the replicas.
    predicted = plan.predict_fleet()
    observed = {k: fleet.counters[k] for k in predicted}
    assert observed == predicted, (observed, predicted)
    assert fleet.quarantined_adapters == {"ad-ev"}
    assert fleet.states() == {0: "healthy", 1: "healthy"}
    assert inj.counts() == {"adapter_poison": 1}

    # Evidence transfer: conviction released every slot the flagged
    # retirements impounded — full capacity, zero quarantined slots.
    for rep in fleet.replicas:
        assert rep.engine.quarantined_slots == set()
        assert rep.engine.in_service_capacity == 2

    # The co-resident tenant was never collateral damage.
    for fid in good_fids:
        assert results[fid].status == "completed"
        assert not results[fid].flagged

    # Standing verdict refuses new traffic for the adapter only...
    assert fleet.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=2,
                                     tenant="t-evil")) is None
    ok = fleet.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=2,
                                   tenant="t-good"))
    assert ok is not None
    fleet.run_until_idle(max_ticks=2000)

    # ...and heal + release readmits it cleanly (no second conviction).
    inj.heal_adapter("ad-ev")
    fleet.release_adapter_quarantine("ad-ev")
    fid = fleet.submit(ServeRequest(prompt=[4, 5, 6], max_new_tokens=3,
                                    tenant="t-evil"))
    assert fid is not None
    readmitted = fleet.run_until_idle(max_ticks=2000)
    assert readmitted[fid].status == "completed"
    assert not readmitted[fid].flagged
    assert fleet.counters["adapter_quarantines"] == 1
    assert fleet.quarantined_adapters == set()
