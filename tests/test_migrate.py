"""Live KV block-table migration tier (serve/migrate.py wired through
engine/scheduler/fleet).

What this file pins, in three rings:

* **Protocol cells** — the two-phase claim/copy/commit/release hand-off
  at the engine pair level: bit-identical migrated streams (greedy AND
  sampled — the rng key-stream position travels), destination-refusal
  unwind that leaves BOTH replicas byte-untouched, quarantined-source
  impound (blocks leave the request but never re-enter the suspect's
  free list), adapter-page re-acquire on the destination, speculative
  claims unwound before the snapshot travels.
* **Capability gate** — :func:`can_migrate` is structural:
  self-migration, geometry/dtype/quantization mismatches and fakes
  all fall back to the pre-existing cancel-and-recompute path.
* **Fleet drills** — a REPLICA_PREEMPT mid-decode drill whose
  migration/preempt counters match ``predict_fleet()`` EXACTLY, with
  zero lost accepted requests, streams bit-identical to ``generate()``,
  the attribution ledger reconciling across BOTH replicas' journals,
  and zero compile storms; plus the disaggregated prefill/decode-pool
  hand-off where every request migrates exactly once at its first
  decode token.

Fresh vocab prime (167) so cached jit programs never alias another
test module's.  Run alone: ``pytest -m migrate``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.serve import (FleetConfig, ServeRequest,
                                      ServingEngine, ServingFleet)
from trustworthy_dl_tpu.serve.migrate import can_migrate, migrate_request

pytestmark = pytest.mark.migrate

CFG = gpt2.GPT2Config(vocab_size=167, n_positions=64, n_layer=2,
                      n_embd=32, n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


def _ref(params, prompt, new, temperature=0.0, rng=None):
    out = generate(params, CFG, jnp.asarray([prompt], jnp.int32), new,
                   temperature=temperature, rng=rng)
    return np.asarray(out)[0, len(prompt):].tolist()


def _paged(params, **kw):
    return ServingEngine(params, CFG, max_slots=2, max_seq=48,
                         queue_limit=4, block_size=8,
                         num_blocks=24, **kw)


def _decode_until(engine, rid, n_tokens):
    """Tick the source until the request has emitted ``n_tokens`` —
    i.e. it is mid-decode, the exact state a migration snapshots."""
    for _ in range(64):
        pair = engine._inflight.get(rid)
        if pair is not None and len(pair[0].emitted) >= n_tokens:
            return
        engine.step()
    raise AssertionError(f"request {rid} never reached "
                         f"{n_tokens} decoded tokens")


# ---------------------------------------------------------------------------
# capability gate — structural, host-only
# ---------------------------------------------------------------------------

def test_can_migrate_structural_gate(params):
    """The gate admits only paged↔paged pairs with identical pool
    geometry/dtype/quantization and the export/adopt surface on both
    ends; everything else (self, fakes, mismatched tiers) falls
    back to cancel-and-recompute instead of corrupting a copy."""
    a, b = _paged(params), _paged(params)
    assert can_migrate(a, b) and can_migrate(b, a)
    # Self-migration is a no-op by definition, not a copy.
    assert not can_migrate(a, a)
    # Pool-geometry mismatch: a block copy would be silent corruption.
    small = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                          queue_limit=4, block_size=8,
                          num_blocks=12)
    assert not can_migrate(a, small)
    # Quantization-tier mismatch: f32 → int8 would be a silent dequant.
    i8 = _paged(params, kv_dtype="int8")
    assert not can_migrate(a, i8)
    assert not can_migrate(i8, a)
    # int8 → int8 with matching geometry is fine (scales ride along).
    assert can_migrate(i8, _paged(params, kv_dtype="int8"))
    # Fakes (fleet unit tests) expose no export/adopt surface.
    assert not can_migrate(object(), b)
    assert not can_migrate(a, object())
    # Unknown ids refuse read-only, nothing touched.
    assert a.export_request(999) is None


# ---------------------------------------------------------------------------
# two-phase protocol — engine pairs
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_migrated_stream_bit_identical_greedy_and_sampled(params):
    """The migrated continuation is byte-for-byte the unmigrated
    stream, greedy AND sampled: nothing numeric is recomputed, the
    key-stream index travels as ``len(emitted)``, and the streaming
    callback sees every token exactly once across the hand-off."""
    prompt, new = [5, 17, 3, 88, 41, 2], 8
    key = jax.random.PRNGKey(3)
    for temp, rng in ((0.0, None), (0.8, key)):
        src, dst = _paged(params), _paged(params)
        streamed = []
        rid = src.submit(ServeRequest(
            prompt=prompt, max_new_tokens=new, temperature=temp,
            rng=rng, on_token=lambda r, t: streamed.append(t)))
        _decode_until(src, rid, 3)
        moved = migrate_request(
            src, dst, rid, on_token=lambda r, t: streamed.append(t))
        assert moved is not None and moved["blocks"] >= 1
        assert rid not in src._inflight          # source attempt closed
        out = dst.run_until_idle()[moved["local_id"]]
        want = _ref(params, prompt, new, temperature=temp, rng=rng)
        assert out.status == "completed"
        assert out.tokens == want, f"temp={temp} stream diverged"
        assert streamed == want                  # no dup, no gap


@pytest.mark.slow
def test_destination_refusal_leaves_source_untouched(params):
    """CLAIM is the normal admission path: a destination with no free
    decode row refuses, ``migrate_request`` returns None, and BOTH
    replicas are exactly as they were — the source then finishes the
    request itself, stream-exact."""
    prompt, new = [5, 17, 3], 6
    src = ServingEngine(params, CFG, max_slots=1, max_seq=64,
                        queue_limit=8, block_size=8,
                        num_blocks=24)
    dst = ServingEngine(params, CFG, max_slots=1, max_seq=64,
                        queue_limit=8, block_size=8,
                        num_blocks=24)
    # A live blocker pins the destination's only slot.
    dst.submit(ServeRequest(prompt=list(range(1, 40)),
                            max_new_tokens=20))
    for _ in range(3):
        dst.step()
    rid = src.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
    _decode_until(src, rid, 2)
    src_free = src.scheduler.blocks.free_count
    dst_free = dst.scheduler.blocks.free_count
    assert migrate_request(src, dst, rid) is None
    # Two-phase unwind: refusal claimed nothing and released nothing.
    assert rid in src._inflight
    assert src.scheduler.blocks.free_count == src_free
    assert dst.scheduler.blocks.free_count == dst_free
    out = src.run_until_idle()[rid]
    assert out.status == "completed"
    assert out.tokens == _ref(params, prompt, new)


@pytest.mark.slow
def test_quarantined_source_impounds_blocks(params):
    """Migrating OFF a quarantined replica impounds the source blocks
    instead of freeing them: the request travels, but the suspect's
    bytes never silently re-enter its own free list."""
    prompt, new = [5, 17, 3, 88, 41, 2], 10
    src, dst = _paged(params), _paged(params)
    rid = src.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
    _decode_until(src, rid, 3)
    snap_ids = list(src.export_request(rid)["block_ids"])
    free_before = src.scheduler.blocks.free_count
    moved = migrate_request(src, dst, rid, quarantine_src=True)
    assert moved is not None
    assert set(snap_ids) <= set(src.scheduler.blocks.quarantined)
    assert src.scheduler.blocks.free_count == free_before  # impounded
    out = dst.run_until_idle()[moved["local_id"]]
    assert out.tokens == _ref(params, prompt, new)


@pytest.mark.slow
def test_adapter_page_reacquired_on_destination(params):
    """An adapter-carrying request re-acquires its tenant's page
    through the destination's NORMAL adapter pool during CLAIM, and
    the migrated stream still matches the unmigrated adapter stream
    bit-for-bit (the delta applies identically on both replicas)."""
    prompt, new = [5, 17, 3, 88, 41, 2], 8

    def eng():
        e = _paged(params, adapter_rank=4, adapter_pool_pages=2,
                   adapter_map={"tx": "ad-x"})
        e.adapter_pool.init_scale = 0.5   # non-zero delta, pre-acquire
        return e

    ref_e = eng()
    rid = ref_e.submit(ServeRequest(prompt=prompt, max_new_tokens=new,
                                    tenant="tx"))
    want = ref_e.run_until_idle()[rid].tokens
    # The adapter really changes the stream, or this cell proves nothing.
    assert want != _ref(params, prompt, new)

    src, dst = eng(), eng()
    rid = src.submit(ServeRequest(prompt=prompt, max_new_tokens=new,
                                  tenant="tx"))
    _decode_until(src, rid, 3)
    moved = migrate_request(src, dst, rid)
    assert moved is not None
    out = dst.run_until_idle()[moved["local_id"]]
    assert out.adapter == "ad-x"
    assert out.tokens == want
    assert "ad-x" in dst.adapter_pool.resident   # page lives on dst now


@pytest.mark.slow
def test_spec_claims_unwound_before_migration(params):
    """A speculative source unwinds its outstanding draft claims
    BEFORE the snapshot travels: no un-verified draft KV migrates, the
    source pool fully restores, and the continuation (also spec-on at
    the destination) still equals plain ``generate()``."""
    prompt, new = [5, 17, 3, 88, 41, 2], 16

    def se():
        return _paged(params, spec_k=2)

    want = _ref(params, prompt, new)
    src, dst = se(), se()
    free0 = src.scheduler.blocks.free_count
    rid = src.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
    _decode_until(src, rid, 2)
    moved = migrate_request(src, dst, rid)
    assert moved is not None
    # Spec claims aborted + table released: every source block is back.
    assert src.scheduler.blocks.free_count == free0
    out = dst.run_until_idle()[moved["local_id"]]
    assert out.tokens == want


# ---------------------------------------------------------------------------
# fleet drills
# ---------------------------------------------------------------------------

class RecordingTrace:
    def __init__(self):
        self.events = []

    def emit(self, type, **data):
        self.events.append({"type": getattr(type, "value", type), **data})

    def of(self, type):
        return [e for e in self.events if e["type"] == type]


@pytest.mark.slow
@pytest.mark.forensics
def test_fleet_preempt_drill_matches_predict_and_reference_streams(
        params, tmp_path):
    """REPLICA_PREEMPT mid-decode: every in-flight request on the
    preempted replica moves as a block copy (not a replay), the
    migration/preempt/fail-over counters match ``predict_fleet()``
    EXACTLY, zero accepted requests are lost, every stream is
    bit-identical to ``generate()``, the ledger reconciles the
    migrated records across BOTH replicas' journals, and the drill
    compiles zero new decode programs.

    Re-run with forensics attached (PR 18): the preemption assembles
    one ``replica_preempt`` incident whose kv_migration action count
    reconciles EXACTLY with ``predict_fleet()`` and whose blast radius
    names the migrated requests via their ``migrated_from``
    provenance."""
    from trustworthy_dl_tpu.chaos import (FaultEvent, FaultInjector,
                                          FaultKind, FaultPlan)
    from trustworthy_dl_tpu.obs.attribution import AttributionLedger
    from trustworthy_dl_tpu.obs.compilewatch import (CompileRegistry,
                                                     CompileWatcher)
    from trustworthy_dl_tpu.obs.forensics import (IncidentAssembler,
                                                  load_incidents)

    plan = FaultPlan.scripted([
        FaultEvent(step=3, kind=FaultKind.REPLICA_PREEMPT, target=0),
    ])
    ledger = AttributionLedger(None)
    trace = RecordingTrace()
    forensics = IncidentAssembler(str(tmp_path), trace=trace,
                                  ledger=ledger)
    compiles = CompileRegistry().install()
    try:
        watcher = CompileWatcher(compiles)
        fleet = ServingFleet(
            params, CFG,
            fleet_config=FleetConfig(num_replicas=3, max_retries=6,
                                     heartbeat_miss_limit=3,
                                     restart_ticks=2,
                                     drain_grace_ticks=4),
            chaos=FaultInjector(plan), ledger=ledger,
            max_slots=2, max_seq=48, queue_limit=32,
            compilewatch=watcher, forensics=forensics,
        )
        fleet.trace = trace
        # 4 requests over 3 replicas × 2 slots: the round-robin router
        # gives replica 0 two of them, and the other replicas keep a
        # free slot each — so both preempted requests CAN land.
        rng = np.random.default_rng(7)
        reqs = []
        for _ in range(4):
            plen = int(rng.integers(3, 8))
            new = int(rng.integers(8, 12))
            prompt = rng.integers(0, CFG.vocab_size, plen).tolist()
            reqs.append((prompt, new))
            fleet.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
        results = fleet.run_until_idle(max_ticks=2000)

        # Zero lost accepted requests, all streams reference-exact.
        assert sorted(results) == list(range(4))
        assert all(r.status == "completed" for r in results.values())
        for fid, (prompt, new) in enumerate(reqs):
            assert results[fid].tokens == _ref(params, prompt, new), (
                f"request {fid} stream diverged across migration")

        # Chaos-plan arithmetic, not observation: the drill's counters
        # are pinned to the plan's own prediction.
        predicted = plan.predict_fleet(preempt_inflight=2)
        observed = {k: fleet.counters[k] for k in predicted}
        assert observed == predicted, (observed, predicted)
        assert fleet.counters["migrations"] == 2
        assert fleet.counters["failover_episodes"] == 0  # no replays

        # The hand-offs surfaced as typed events with the physical
        # copy size — observability is part of the contract.
        migs = trace.of("kv_migration")
        assert len(migs) == 2
        assert all(e["from_replica"] == 0 and e["reason"] == "preempt"
                   and e["blocks"] >= 1 for e in migs)

        # One record per migrated request spans BOTH journals: the
        # destination attempt carries ``migrated_from`` with the
        # source's replica:gen journal key and block provenance, and
        # verification reconciles it without flagging the release.
        ok, problems = fleet.verify_attribution()
        assert ok, problems
        spanning = [r for r in ledger.records()
                    if r.get("admitted") and r.get("attempts")
                    and any(a.get("migrated_from") for a in r["attempts"])]
        assert len(spanning) == 2
        for rec in spanning:
            mf = next(a["migrated_from"] for a in rec["attempts"]
                      if a.get("migrated_from"))
            assert mf["replica"] == 0 and mf["journal"] == "0:0"
            assert len(mf["block_ids"]) >= 1

        # The block copy never compiled a fresh decode program.
        assert watcher.storm_total == 0

        # -- forensics: the preemption's incident report -------------------
        assert forensics.counts_by_reason() == {
            "replica_preempt": predicted["preempts"]}
        incidents = load_incidents(str(tmp_path))
        assert len(incidents) == 1
        inc = incidents[0]
        assert inc["reason"] == "replica_preempt"
        assert inc["suspect_replicas"] == [0]
        assert inc["suspect_journals"] == ["0:0"]
        # Trigger = the preempted replica's restart transition; the
        # kv_migration actions reconcile EXACTLY with predict_fleet().
        trig = inc["trigger"]
        assert trig["type"] == "replica_transition"
        assert trig["replica"] == 0 and trig["reason"] == "preempt"
        inc_migs = [e for e in inc["actions"]
                    if e["type"] == "kv_migration"]
        assert len(inc_migs) == predicted["migrations"] == 2
        # Counters snapshot at assembly carried the full episode.
        assert inc["counters"]["preempts"] == predicted["preempts"]
        assert inc["counters"]["migrations"] == predicted["migrations"]
        # Blast radius: the requests still in flight at assembly time
        # are visible through their provisional closed-attempt history
        # — the two migrated streams' source placements on the
        # preempted generation — and they are EXACTLY the spanning
        # records the ledger later reconciled across both journals.
        assert inc["blast_radius"]["requests"] == sorted(
            r["request_id"] for r in spanning)
        for rid in inc["blast_radius"]["requests"]:
            hows = inc["blast_radius"]["via"][str(rid)]
            assert any(h.get("journal") == "0:0" for h in hows)
        assert "0:0" in inc["blast_radius"]["suspect_blocks"]
    finally:
        compiles.uninstall()


@pytest.mark.slow
def test_disaggregated_pools_hand_off_every_request_once(params):
    """``pool_roles`` splits the fleet into prefill and decode
    specialists: every request prefills on the prefill replica,
    migrates exactly once at its first decode token (reason
    ``disagg``), and the stream is still bit-identical — the hand-off
    is invisible to the caller."""
    from trustworthy_dl_tpu.obs.attribution import AttributionLedger

    trace = RecordingTrace()
    fleet = ServingFleet(
        params, CFG,
        fleet_config=FleetConfig(
            num_replicas=3, pool_roles=("prefill", "decode", "decode")),
        ledger=AttributionLedger(None),
        max_slots=2, max_seq=48, queue_limit=32,
    )
    fleet.trace = trace
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(5):
        plen = int(rng.integers(3, 8))
        new = int(rng.integers(6, 10))
        prompt = rng.integers(0, CFG.vocab_size, plen).tolist()
        reqs.append((prompt, new))
        fleet.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
    results = fleet.run_until_idle(max_ticks=2000)

    assert sorted(results) == list(range(5))
    assert all(r.status == "completed" for r in results.values())
    for fid, (prompt, new) in enumerate(reqs):
        assert results[fid].tokens == _ref(params, prompt, new), (
            f"request {fid} stream diverged across the pool hand-off")
    # One hand-off per request, all off the prefill specialist.
    assert fleet.counters["migrations"] == 5
    migs = trace.of("kv_migration")
    assert len(migs) == 5
    assert all(e["reason"] == "disagg" and e["from_replica"] == 0
               for e in migs)
    # The role gauge never conflates the pools.
    ok, problems = fleet.verify_attribution()
    assert ok, problems
