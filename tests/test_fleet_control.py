"""Fleet control plane (serve/control.py wired into serve/fleet.py).

Fast tier: host contracts — config validation, token-bucket refill
determinism, deficit-round-robin fairness, autoscaler hysteresis, the
predictive diurnal arm, TENANT_FLOOD throttling, the scale-up → drain →
RETIRED → revive cycle and lowest-class-first shedding — all through
the FakeEngine seam (nothing jits).  Slow tier: THE acceptance drill —
diurnal-burst background traffic + a TENANT_FLOOD against a real 2→3
fleet, with scale-up/scale-down/throttle counters matching
``FaultPlan.predict_fleet()`` exactly, scale-down losing zero accepted
work (streams bit-identical to ``generate()``), the flooding tenant
throttled while the higher classes hold their latency targets.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_fleet import FakeEngine, RecordingTrace

from trustworthy_dl_tpu.chaos import (
    FaultEvent,
    FaultInjector,
    FaultKind,
    FaultPlan,
)
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.attribution import AttributionLedger
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.serve import (
    DEFAULT_SLO_CLASSES,
    AutoscalerConfig,
    FleetConfig,
    PredictiveArmConfig,
    ReplicaState,
    SLOClass,
    ServeRequest,
    ServingEngine,
    ServingFleet,
    TenantQuotaConfig,
    WorkloadConfig,
    drive_closed_loop,
    generate_workload,
)
from trustworthy_dl_tpu.serve.control import (
    Autoscaler,
    ClassLatencyTracker,
    ClassQueues,
    ScaleSignals,
    TenantBuckets,
    autoscale_pressure,
    class_for_priority,
    diurnal_rate,
    predicted_replicas,
)

pytestmark = [pytest.mark.fleet, pytest.mark.fleetctl]

# Unique decode geometry for this file (vocab 139) — continues the
# 97/101/103/107/113/127/131/157 process-global jit-cache isolation
# sequence documented in test_fleet.py.
CFG = gpt2.GPT2Config(vocab_size=139, n_positions=64, n_layer=2,
                      n_embd=32, n_head=4, dtype=jnp.float32)


def ctl_fleet(num_replicas=2, chaos=None, ledger=None, registry=None,
              trace=None, **cfg_kwargs):
    """FakeEngine fleet with control-plane config passed through."""
    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(num_replicas=num_replicas, **cfg_kwargs),
        chaos=chaos, ledger=ledger, engine_factory=factory,
        registry=registry or MetricsRegistry(), trace=trace,
    )
    return fleet, fakes


def complete_all(fakes):
    for fake in list(fakes.values()):
        for rid in list(fake.inflight):
            fake.complete(rid)


# --------------------------------------------------------------------------
# Fast tier: control primitives
# --------------------------------------------------------------------------


def test_control_config_validation_and_class_mapping():
    with pytest.raises(ValueError):
        SLOClass("", priority=0)
    with pytest.raises(ValueError):
        SLOClass("x", priority=0, weight=0.0)
    with pytest.raises(ValueError):
        SLOClass("x", priority=0, ttft_target_s=-1.0)
    with pytest.raises(ValueError):
        TenantQuotaConfig(capacity_tokens=0)
    with pytest.raises(ValueError):
        TenantQuotaConfig(capacity_tokens=10, refill_per_tick=-1)
    with pytest.raises(ValueError, match="per_tenant"):
        TenantQuotaConfig(capacity_tokens=10,
                          per_tenant={"t": (0, 0.0)})
    # Hysteresis band is mandatory: down thresholds strictly below up.
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscalerConfig(scale_up_queue_per_replica=2.0,
                         scale_down_queue_per_replica=2.0)
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscalerConfig(scale_up_occupancy=0.5,
                         scale_down_occupancy=0.5)
    with pytest.raises(ValueError):
        AutoscalerConfig(min_replicas=3, max_replicas=2)
    # The fleet refuses to START outside its own autoscale bounds.
    with pytest.raises(ValueError, match="autoscale bounds"):
        FleetConfig(num_replicas=1,
                    autoscale=AutoscalerConfig(min_replicas=2,
                                               max_replicas=4))
    # Priority -> class: highest class at or below the priority;
    # off-ladder priorities clamp to the nearest rung.
    assert class_for_priority(DEFAULT_SLO_CLASSES, 0).name == "batch"
    assert class_for_priority(DEFAULT_SLO_CLASSES, 1).name == "standard"
    assert class_for_priority(DEFAULT_SLO_CLASSES, 2).name == "premium"
    assert class_for_priority(DEFAULT_SLO_CLASSES, 7).name == "premium"
    assert class_for_priority(DEFAULT_SLO_CLASSES, -3).name == "batch"


def test_token_bucket_refill_is_tick_deterministic():
    cfg = TenantQuotaConfig(capacity_tokens=40, refill_per_tick=2.0,
                            per_tenant={"vip": (100, 10.0)})
    b = TenantBuckets(cfg)
    assert b.try_spend("t", 30, 0)          # 40 -> 10
    assert not b.try_spend("t", 30, 0)      # 10 < 30
    assert b.try_spend("t", 30, 10)         # +2*10 -> 30, spends all
    assert b.level("t", 10) == 0.0
    assert b.level("t", 30) == 40.0         # refill caps at capacity
    # Per-tenant overrides get their own limits.
    assert b.try_spend("vip", 90, 0)
    assert b.try_spend("vip", 90, 9)        # 10 + 9*10 = 100 >= 90
    # Tenants are independent: vip spending never drains t.
    assert b.level("t", 30) == 40.0


def test_drr_dequeue_is_token_weighted_and_skips_stale():
    classes = (SLOClass("small", priority=0, weight=1.0),
               SLOClass("big", priority=1, weight=3.0))
    cq = ClassQueues(classes, quantum_tokens=8, per_class_limit=8)
    for i in range(8):
        assert cq.push("small", i, 8)
    assert not cq.push("small", 99, 8)      # per-class bound
    for i in range(100, 108):
        assert cq.push("big", i, 8)
    dead = {2, 103}
    taken = cq.take(8, lambda fid: fid not in dead)
    by_class = {"small": 0, "big": 0}
    for name, fid, _cost in taken:
        assert fid not in dead              # stale entries skipped
        by_class[name] += 1
    # Weight 3:1 in tokens (equal costs -> requests): the heavy class
    # releases about three for each light one inside the batch.
    assert by_class["big"] >= 2 * by_class["small"] > 0
    # Shed candidate: NEWEST entry of the LOWEST class.
    name, fid = cq.shed_candidate(lambda fid: fid not in dead)
    assert name == "small" and fid == 7


def test_autoscaler_hysteresis_cooldown_and_bounds():
    cfg = AutoscalerConfig(min_replicas=1, max_replicas=3,
                           scale_up_queue_per_replica=4.0,
                           scale_down_queue_per_replica=1.0,
                           scale_up_occupancy=0.9,
                           scale_down_occupancy=0.3,
                           scale_up_cooldown_ticks=5,
                           scale_down_cooldown_ticks=5,
                           scale_down_idle_ticks=3)

    def sig(tick, n, q, occ=0.0, **kw):
        return ScaleSignals(tick=tick, in_service=n,
                            queue_per_replica=q, occupancy=occ, **kw)

    # The pure predicate: band between the thresholds is dead.
    assert autoscale_pressure(cfg, sig(0, 1, 5.0)) == 1
    assert autoscale_pressure(cfg, sig(0, 1, 2.0)) == 0
    assert autoscale_pressure(cfg, sig(0, 1, 0.5)) == -1
    assert autoscale_pressure(cfg, sig(0, 1, 0.5, occ=0.95)) == 1
    assert autoscale_pressure(cfg, sig(0, 1, 0.5, slo_burning=True)) == 1
    # Predictive demand trumps current quiet.
    assert autoscale_pressure(cfg, sig(0, 1, 0.5,
                                       predicted_replicas=2)) == 1
    a = Autoscaler(cfg)
    assert a.observe(sig(1, 1, 8.0)) == 1      # up
    assert a.observe(sig(2, 2, 8.0)) == 0      # cooldown blocks
    assert a.observe(sig(6, 2, 8.0)) == 1      # cooldown over
    assert a.observe(sig(11, 3, 8.0)) == 0     # at max: bounded
    # Scale-down needs a SUSTAINED idle streak, and one busy tick
    # resets it.
    assert a.observe(sig(12, 3, 0.0)) == 0
    assert a.observe(sig(13, 3, 0.0)) == 0
    assert a.observe(sig(14, 3, 8.0)) == 0     # streak broken (at max)
    assert a.observe(sig(15, 3, 0.0)) == 0
    assert a.observe(sig(16, 3, 0.0)) == 0
    assert a.observe(sig(17, 3, 0.0)) == -1    # 3 consecutive idle
    assert a.observe(sig(18, 2, 0.0)) == 0     # down cooldown
    assert a.decisions == {"up": 2, "down": 1}


def test_predictive_arm_matches_workload_envelope_and_leads_it():
    wl = WorkloadConfig(seed=3, num_requests=8, mean_rps=16.0,
                        burstiness=0.6, burst_period_s=4.0)
    # ONE spelling: the control-plane envelope is the generator's.
    import math
    for t in (0.0, 0.7, 1.3, 2.9):
        expected = wl.mean_rps * (1.0 + wl.burstiness * math.sin(
            2.0 * math.pi * t / wl.burst_period_s))
        expected = max(expected, wl.mean_rps * (1.0 - wl.burstiness),
                       1e-6)
        assert diurnal_rate(wl.mean_rps, wl.burstiness,
                            wl.burst_period_s, t) == \
            pytest.approx(expected)
    # With lead_s = a quarter period, the arm demands burst capacity
    # while the rate is still at the mean — it anticipates, a reactive
    # reading of the same tick does not.
    pred = PredictiveArmConfig(mean_rps=16.0, burstiness=0.6,
                               burst_period_s=4.0, per_replica_rps=8.0,
                               lead_s=1.0, tick_duration_s=0.05)
    reactive = PredictiveArmConfig(mean_rps=16.0, burstiness=0.6,
                                   burst_period_s=4.0,
                                   per_replica_rps=8.0, lead_s=0.0,
                                   tick_duration_s=0.05)
    # tick 0: rate(0) = 16 -> 2 replicas reactive; rate(1.0s) = peak
    # 25.6 -> 4 replicas predictive.
    assert predicted_replicas(reactive, 0) == 2
    assert predicted_replicas(pred, 0) == 4
    # Deterministic: same tick, same answer.
    assert predicted_replicas(pred, 0) == predicted_replicas(pred, 0)
    with pytest.raises(ValueError):
        PredictiveArmConfig(mean_rps=0.0, burstiness=0.5,
                            burst_period_s=1.0, per_replica_rps=1.0)


def test_predict_fleet_flood_and_scale_arithmetic():
    plan = FaultPlan.scripted([
        FaultEvent(step=5, kind=FaultKind.TENANT_FLOOD, severity=12,
                   tenant="flood"),
        FaultEvent(step=400, kind=FaultKind.TENANT_FLOOD, severity=3,
                   tenant="flood"),
    ])
    blind = plan.predict_fleet()
    assert blind["tenant_floods"] == 2
    assert blind["throttles"] == 0              # no quota: all admitted
    assert blind["scale_ups"] == blind["scale_downs"] == 0
    # Bucket 40, request cost 8 -> 5 admitted per isolated event.
    pinned = plan.predict_fleet(autoscale=True, quota_tokens=40,
                                flood_request_tokens=8)
    assert pinned["throttles"] == (12 - 5) + 0  # second flood fits
    assert pinned["scale_ups"] == pinned["scale_downs"] == 2
    assert pinned["drains"] == 2                # scale-downs ARE drains
    with pytest.raises(ValueError, match="flood_request_tokens"):
        plan.predict_fleet(quota_tokens=40)


# --------------------------------------------------------------------------
# Fast tier: fleet wiring through the FakeEngine seam
# --------------------------------------------------------------------------


def test_tenant_flood_throttles_itself_not_the_fleet():
    """The flooding tenant's own bucket refuses its overflow — loudly
    (typed events + the tenant-labelled counter) — while other tenants'
    traffic admits untouched and the admitted flood work completes."""
    reg = MetricsRegistry()
    trace = RecordingTrace()
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=3, kind=FaultKind.TENANT_FLOOD, severity=10,
                   tenant="flood"),
    ]))
    fleet, fakes = ctl_fleet(
        num_replicas=2, chaos=inj, registry=reg, trace=trace,
        slo_classes=DEFAULT_SLO_CLASSES,
        tenant_quota=TenantQuotaConfig(
            capacity_tokens=10_000, refill_per_tick=0.0,
            per_tenant={"flood": (24, 0.0)}),
    )
    ok = [fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                    tenant="acme", priority=2))
          for _ in range(2)]
    assert all(fid is not None for fid in ok)
    fleet.step()
    fleet.step()
    fleet.step()                    # tick 3: flood fires
    # 10 requests x 8 tokens against a 24-token bucket: 3 admitted.
    assert fleet.counters["tenant_floods"] == 1
    assert fleet.counters["throttles"] == 7
    throttle_events = trace.of("tenant_throttle")
    assert len(throttle_events) == 7
    assert all(e["tenant"] == "flood" and e["tokens"] == 8
               for e in throttle_events)
    assert reg.get("tddl_fleet_tenant_throttled_total").value(
        tenant="flood") == 7.0
    # The other tenant was never throttled and everything admitted
    # completes — the flood backpressured ITSELF, not the fleet.
    for _ in range(6):
        complete_all(fakes)
        fleet.step()
    assert not fleet.busy
    statuses = [r.status for r in fleet.results.values()]
    assert statuses.count("completed") == 2 + 3
    by_tenant = {}
    for r in fleet.results.values():
        by_tenant.setdefault(r.tenant, []).append(r.status)
    assert by_tenant["acme"] == ["completed", "completed"]
    assert by_tenant["flood"] == ["completed"] * 3
    # Flood requests ride the lowest class.
    assert all(r.slo_class == "batch" for r in fleet.results.values()
               if r.tenant == "flood")


def test_autoscaler_scale_up_drain_retire_revive_cycle():
    """Queue pressure scales up (new replica warms through RESTARTING),
    idle drains the newest replica into RETIRED (journal retained,
    gauge shows the state), and fresh pressure REVIVES the retired
    index as a new generation — the full breathing cycle, with
    fleet_scale events naming both counts."""
    reg = MetricsRegistry()
    trace = RecordingTrace()
    fleet, fakes = ctl_fleet(
        num_replicas=2, registry=reg, trace=trace, restart_ticks=1,
        autoscale=AutoscalerConfig(
            min_replicas=2, max_replicas=3,
            scale_up_queue_per_replica=3.0,
            scale_down_queue_per_replica=0.4,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=2, scale_down_cooldown_ticks=2,
            scale_down_idle_ticks=2),
    )
    fids = [fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
            for _ in range(8)]
    fleet.step()
    assert fleet.counters["scale_ups"] == 1
    assert len(fleet.replicas) == 3
    assert fleet.replicas[2].state is ReplicaState.RESTARTING
    for _ in range(8):
        complete_all(fakes)
        fleet.step()
    assert fleet.counters["scale_downs"] == 1
    assert fleet.replicas[2].state is ReplicaState.RETIRED
    assert fleet.replicas[2].engine is None
    assert "2:0" in fleet.journals          # post-mortem journal kept
    assert all(fleet.results[f].status == "completed" for f in fids)
    assert reg.get("tddl_fleet_replicas").value(state="retired") == 1.0
    scales = [(e["direction"], e["from_replicas"], e["to_replicas"])
              for e in trace.of("fleet_scale")]
    assert scales == [("up", 2, 3), ("down", 3, 2)]
    # Replica-count trace recorded the breath.
    assert [n for _, n in fleet.replica_trace] == [2, 3, 2]
    # Fresh pressure revives index 2 as generation 1.
    fids2 = [fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
             for _ in range(8)]
    fleet.step()
    assert fleet.counters["scale_ups"] == 2
    assert fleet.replicas[2].gen == 1
    assert "2:1" in fleet.journals
    for _ in range(8):
        complete_all(fakes)
        fleet.step()
    assert all(fleet.results[f].status == "completed" for f in fids2)


def test_scale_down_drain_lets_inflight_run_out_never_migrates():
    """A scale-down drain is exempt from the grace-deadline forced
    migration: in-flight work finishes ON the draining replica (its
    stream is the canonical result), and only then does the replica
    retire."""
    fleet, fakes = ctl_fleet(
        num_replicas=3, drain_grace_ticks=1,
        autoscale=AutoscalerConfig(
            min_replicas=2, max_replicas=3,
            scale_up_queue_per_replica=50.0,
            scale_down_queue_per_replica=2.0,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
            scale_down_idle_ticks=2),
    )
    # One in-flight request per replica: loads tie, the NEWEST index
    # (replica 2) is the victim; queue/replica = 1 <= 2 reads as idle.
    fids = [fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
            for _ in range(3)]
    victim_fid = next(f for f in fids
                      if 2 in fleet.requests[f].live)
    fleet.step()
    fleet.step()
    assert fleet.counters["scale_downs"] == 1
    rep = fleet.replicas[2]
    assert rep.state is ReplicaState.DRAINING
    # Past drain_grace_ticks=1 the in-flight request is STILL on the
    # draining replica — scale-down never force-migrates.
    for _ in range(4):
        fleet.step()
    assert rep.state is ReplicaState.DRAINING
    assert fleet.requests[victim_fid].live.keys() == {2}
    assert fleet.counters["failovers"] == 0
    # It finishes where it ran; only then does the replica retire.
    fakes[2].complete(fleet.requests[victim_fid].live[2].local_id,
                      tokens=(9, 9))
    fleet.step()
    fleet.step()
    assert fleet.results[victim_fid].status == "completed"
    assert fleet.results[victim_fid].replica == 2
    assert rep.state is ReplicaState.RETIRED


def test_class_breach_sheds_lowest_class_first():
    """Under a per-class latency breach with the backlog over capacity,
    the fleet sheds the NEWEST entry of the LOWEST class — premium
    survives a breach that batch pays for (replacing the raw
    lowest-priority shed)."""
    classes = (SLOClass("batch", priority=0, weight=1.0),
               SLOClass("premium", priority=2, weight=4.0,
                        ttft_target_s=0.001))
    fleet, fakes = ctl_fleet(num_replicas=2, slo_classes=classes,
                             class_latency_min_count=2)
    for fake in fakes.values():
        fake.queue_limit = 0            # zero free capacity: all queue
    batch = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1,
                                       priority=0)) for _ in range(3)]
    prem = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1,
                                      priority=2)) for _ in range(2)]
    # Breach premium's TTFT target (slow observations past min_count).
    fleet._class_latency.observe("premium", ttft_s=1.0)
    fleet._class_latency.observe("premium", ttft_s=1.0)
    assert fleet._class_latency.breached("premium")
    fleet.step()
    fleet.step()
    shed = [fid for fid, r in fleet.results.items()
            if r.status == "shed_slo"]
    assert len(shed) == 2               # one per tick — bounded shed
    assert set(shed) <= set(batch)      # ONLY the lowest class paid
    assert all(fleet.requests.get(f) is not None for f in prem)
    summary = fleet.metrics_summary()
    assert summary["per_class"]["batch"]["shed"] == 2
    assert summary["per_class"]["premium"]["shed"] == 0
    assert summary["per_class"]["premium"]["breached"] is True


def test_tenant_identity_threads_to_fleet_ledger_and_results():
    ledger = AttributionLedger(None)
    fleet, fakes = ctl_fleet(num_replicas=2, ledger=ledger,
                             slo_classes=DEFAULT_SLO_CLASSES)
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                    tenant="acme", priority=1))
    fleet.step()
    fakes_with = [i for i, f in fakes.items() if f.load]
    rep = fakes_with[0]
    fakes[rep].complete(fleet.requests[fid].live[rep].local_id)
    fleet.step()
    res = fleet.results[fid]
    assert res.tenant == "acme" and res.slo_class == "standard"
    record = [r for r in ledger.records() if r.get("admitted")][0]
    assert record["tenant"] == "acme"
    assert record["slo_class"] == "standard"


def test_tenant_rides_engine_ledger_and_request_span():
    """Engine-side satellite: a standalone ServingEngine stamps the
    request's tenant into its attribution record AND the serve.request
    span attrs (before this PR the workload generator drew tenants and
    the serving path forgot them at submit)."""
    from trustworthy_dl_tpu.obs.spans import SpanTracker

    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    ledger = AttributionLedger(None)
    spans = SpanTracker()
    engine = ServingEngine(params, CFG, max_slots=1, max_seq=32,
                           ledger=ledger, spans=spans)
    rid = engine.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=2,
                                     tenant="acme"))
    engine.run_until_idle()
    record = [r for r in ledger.records()
              if r["request_id"] == rid][0]
    assert record["tenant"] == "acme"
    root = [s for s in spans.closed_spans()
            if s.name == "serve.request"][0]
    assert root.attrs["tenant"] == "acme"
    # Queue-side sheds carry it too (unadmitted record path).
    rid2 = engine.submit(ServeRequest(prompt=[1], max_new_tokens=1,
                                      tenant="acme", deadline_s=0.0))
    import time

    time.sleep(0.01)
    engine.run_until_idle()
    rec2 = [r for r in ledger.records() if r["request_id"] == rid2][0]
    assert rec2["admitted"] is False and rec2["tenant"] == "acme"


def test_closed_loop_driver_holds_inflight_and_drains():
    """The extracted PR 12 closed-loop bounded-queue driver
    (serve/workload.py): holds the in-flight target, accepts every
    submission eventually, and drains — one spelling shared by the
    drills."""
    fleet, fakes = ctl_fleet(num_replicas=2)
    items = generate_workload(
        WorkloadConfig(seed=1, num_requests=12, mean_rps=1000.0),
        97, 48)
    peak = {"open": 0}

    class AutoComplete:
        busy = property(lambda self: fleet.busy)
        open_requests = property(lambda self: fleet.open_requests)

        def submit(self, request):
            return fleet.submit(request)

        def step(self):
            peak["open"] = max(peak["open"], fleet.open_requests)
            complete_all(fakes)
            return fleet.step()

    accepted = drive_closed_loop(
        AutoComplete(), items,
        lambda item: ServeRequest(prompt=list(item.prompt),
                                  max_new_tokens=1,
                                  tenant=item.tenant),
        inflight_target=4)
    assert accepted == 12
    assert peak["open"] <= 4                # the bound held
    assert sorted(fleet.results) == list(range(12))
    assert all(r.status == "completed" for r in fleet.results.values())


def test_scale_down_bounds_exclude_replicas_already_leaving():
    """Review regression: a replica draining toward RETIRED is LEAVING
    capacity — while its (long) drain is open, the min_replicas bound
    must count it as gone, or one scale-down per cool-down walks the
    fleet below the floor (to zero in the worst case)."""
    fleet, fakes = ctl_fleet(
        num_replicas=3,
        autoscale=AutoscalerConfig(
            min_replicas=2, max_replicas=3,
            scale_up_queue_per_replica=50.0,
            scale_down_queue_per_replica=2.0,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
            scale_down_idle_ticks=1),
    )
    # One in-flight request pins replica 2's drain open for many ticks.
    fids = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
            for _ in range(3)]
    fleet.step()
    fleet.step()
    assert fleet.counters["scale_downs"] == 1
    victim = next(r for r in fleet.replicas if r.retire_pending)
    # The drain stays open (its request never completes) while every
    # idle tick re-runs the controller: staying == min, so NO second
    # down — the fleet never commits to dropping below the floor.
    for _ in range(12):
        fleet.step()
    assert fleet.counters["scale_downs"] == 1
    assert victim.state is ReplicaState.DRAINING
    staying = [r for r in fleet.replicas if not r.retire_pending]
    assert len(staying) == 2


def test_stalled_scale_in_drain_fails_over_instead_of_stranding():
    """Review regression: a scale-in drain lets in-flight RUN OUT — but
    only while the engine keeps ticking.  A replica that stops making
    progress mid-retire-drain falls back to the force-migration after
    heartbeat_miss_limit silent ticks, so accepted work never leaves
    with the capacity."""
    fleet, fakes = ctl_fleet(
        num_replicas=3, heartbeat_miss_limit=3, backoff_base_ticks=0,
        autoscale=AutoscalerConfig(
            min_replicas=2, max_replicas=3,
            scale_up_queue_per_replica=50.0,
            scale_down_queue_per_replica=2.0,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
            scale_down_idle_ticks=2),
    )
    fids = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
            for _ in range(3)]
    victim_fid = next(f for f in fids if 2 in fleet.requests[f].live)
    fleet.step()
    fleet.step()
    assert fleet.replicas[2].retire_pending
    # Wedge the draining replica: it stops stepping entirely.
    fleet.replicas[2].stalled_until = 10 ** 9
    for _ in range(8):
        fleet.step()
    # The stranded request failed over and the replica still retired.
    rec = fleet.requests.get(victim_fid)
    assert rec is None or 2 not in rec.live
    assert fleet.counters["failovers"] >= 1
    assert fleet.replicas[2].state is ReplicaState.RETIRED
    # Completing the moved attempt finishes the request elsewhere.
    for _ in range(6):
        complete_all(fakes)
        fleet.step()
    assert fleet.results[victim_fid].status == "completed"
    assert fleet.results[victim_fid].replica != 2


def test_closed_loop_driver_drops_permanently_refused_head():
    """Review regression: a head item nothing will ever admit (cost
    above its tenant's bucket, zero refill) is DROPPED after
    max_refused_ticks instead of head-of-line-blocking every item
    behind it to the max_ticks crash."""
    fleet, fakes = ctl_fleet(
        num_replicas=2,
        tenant_quota=TenantQuotaConfig(capacity_tokens=4.0,
                                       refill_per_tick=0.0))

    class AutoComplete:
        busy = property(lambda self: fleet.busy)
        open_requests = property(lambda self: fleet.open_requests)

        def submit(self, request):
            return fleet.submit(request)

        def step(self):
            complete_all(fakes)
            return fleet.step()

    items = generate_workload(
        WorkloadConfig(seed=2, num_requests=3, mean_rps=1000.0), 97, 48)

    def make(item):
        # The FIRST item costs more than any bucket ever holds; the
        # rest are cheap and ride a different tenant.
        if item is items[0]:
            return ServeRequest(prompt=[1] * 10, max_new_tokens=2,
                                tenant="hog")
        return ServeRequest(prompt=[1], max_new_tokens=1, tenant="ok")

    accepted = drive_closed_loop(AutoComplete(), items, make,
                                 inflight_target=2, max_ticks=500,
                                 max_refused_ticks=10)
    assert accepted == 2                    # the hog head was dropped
    assert fleet.counters["throttles"] >= 10
    assert all(r.status == "completed" for r in fleet.results.values())


def test_rejected_submission_refunds_the_tenant_bucket():
    """Review regression: a submission the fleet REJECTS after the
    quota check passed (class queue full) does no work, so it must not
    drain the tenant's budget — a rejected burst would otherwise
    throttle the tenant's next legitimate requests."""
    fleet, fakes = ctl_fleet(
        num_replicas=2, slo_classes=DEFAULT_SLO_CLASSES,
        class_queue_limit=2,
        tenant_quota=TenantQuotaConfig(capacity_tokens=20.0,
                                       refill_per_tick=0.0))
    # Cost 2 each (prompt 1 + new 1): 2 queue, the 3rd is REJECTED by
    # the class-queue bound — and refunded.
    fids = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1,
                                      tenant="acme"))
            for _ in range(3)]
    assert fids[2] is None and fleet.rejected == 1
    assert fleet.counters["throttles"] == 0
    assert fleet._buckets.level("acme", fleet.tick) == 20.0 - 2 * 2
    # The budget the rejection did NOT burn still admits real work.
    for _ in range(3):
        complete_all(fakes)
        fleet.step()
    assert fleet.submit(ServeRequest(prompt=[1] * 15, max_new_tokens=1,
                                     tenant="acme")) is not None


def test_unserved_death_refunds_the_tenant_bucket_once():
    """Drain→resubmit reconciliation: the bucket spend lands ONCE at
    submit() and rides through every migrate/resubmit hop un-recharged,
    so a request that dies UNSERVED (retry budget exhausted after a
    crash) must hand that one spend back — exactly once, and never for
    work that actually served."""
    plan = FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_CRASH, target=0),
    ])
    fleet, fakes = ctl_fleet(
        num_replicas=1, chaos=FaultInjector(plan), max_retries=0,
        tenant_quota=TenantQuotaConfig(capacity_tokens=20.0,
                                       refill_per_tick=0.0))
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                    tenant="acme"))
    assert fid is not None
    assert fleet._buckets.level("acme", fleet.tick) == 20.0 - 4
    for _ in range(4):
        fleet.step()
    assert fleet.results[fid].status == "failover_exhausted"
    # The unserved death refunded the submit-time spend — once: extra
    # ticks over the done record never refund again (zero refill, so
    # any drift above capacity-minus-spends would be a double refund).
    assert fleet._buckets.level("acme", fleet.tick) == 20.0
    for _ in range(3):
        fleet.step()
    assert fleet._buckets.level("acme", fleet.tick) == 20.0
    # A request that SERVES keeps its spend spent.
    fleet2, fakes2 = ctl_fleet(
        num_replicas=1,
        tenant_quota=TenantQuotaConfig(capacity_tokens=20.0,
                                       refill_per_tick=0.0))
    fid2 = fleet2.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                      tenant="acme"))
    for _ in range(3):
        complete_all(fakes2)
        fleet2.step()
    assert fleet2.results[fid2].status == "completed"
    assert fleet2._buckets.level("acme", fleet2.tick) == 20.0 - 4


def test_dispatch_failure_requeues_the_whole_remaining_batch():
    """Review regression: when an engine refuses a submission mid-
    dispatch-batch, EVERY not-yet-placed entry returns to its class
    queue — dropping the tail would orphan requests with no live
    attempt, no retry and no queue entry, wedging ``busy`` forever."""

    class RefusingEngine(FakeEngine):
        refusing = True

        def submit(self, request):
            if self.refusing:
                return None         # refuses despite free queue space
            return super().submit(request)

    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = RefusingEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(num_replicas=2,
                                 slo_classes=DEFAULT_SLO_CLASSES),
        engine_factory=factory, registry=MetricsRegistry(),
    )
    fids = [fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
            for _ in range(4)]
    fleet.step()
    # Nothing placed, nothing lost: all four still queued.
    assert fleet._classq.depth() == 4
    assert all(not fleet.requests[f].live for f in fids)
    for fake in fakes.values():
        fake.refusing = False
    for _ in range(4):
        complete_all(fakes)
        fleet.step()
    assert all(fleet.results[f].status == "completed" for f in fids)


def test_no_candidate_scale_down_is_not_consumed():
    """Review regression: a scale-down DECISION while nothing can
    safely drain (everything restarting/quarantined mid-chaos) must
    not arm the cool-down and reset the idle streak — the controller
    waits, then acts the moment a candidate exists."""
    fleet, fakes = ctl_fleet(
        num_replicas=3, restart_ticks=10 ** 6,
        autoscale=AutoscalerConfig(
            min_replicas=2, max_replicas=3,
            scale_up_queue_per_replica=50.0,
            scale_down_queue_per_replica=2.0,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=1,
            scale_down_cooldown_ticks=10 ** 6,  # ONE down ever
            scale_down_idle_ticks=2),
    )
    # No admitting replica: idle pressure accumulates but the decision
    # is never consumed by a no-op.
    for rep in fleet.replicas:
        rep.state = ReplicaState.RESTARTING
        rep.warm_until = 6
    for _ in range(4):
        fleet.step()
    assert fleet.counters["scale_downs"] == 0
    assert fleet.autoscaler.decisions["down"] == 0
    # Replicas return at tick 6; the ONE allowed down (cooldown 1e6 —
    # an earlier consumed no-op would have burned it) fires promptly.
    for _ in range(8):
        fleet.step()
    assert fleet.counters["scale_downs"] == 1
    assert fleet.autoscaler.decisions["down"] == 1
    assert fleet.counters["scale_downs"] == \
        fleet.autoscaler.decisions["down"]


def test_quarantined_replicas_do_not_dilute_the_scale_signal():
    """Review regression: a quarantined replica serves nothing for an
    indefinite cool-off — counting it in queue-per-replica (and against
    max_replicas) would hold the autoscaler back exactly when chaos
    removed the capacity."""
    fleet, fakes = ctl_fleet(
        num_replicas=3, restart_ticks=1,
        autoscale=AutoscalerConfig(
            min_replicas=1, max_replicas=3,
            scale_up_queue_per_replica=5.0,
            scale_down_queue_per_replica=0.4,
            scale_up_occupancy=1.1, scale_down_occupancy=1.0,
            scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
            scale_down_idle_ticks=10 ** 6),
    )
    fleet.replicas[1].state = ReplicaState.QUARANTINED
    fleet.replicas[2].state = ReplicaState.QUARANTINED
    for rep in fleet.replicas[1:]:
        rep.cooloff_until = 10 ** 6
    # 6 requests on the ONE live replica: 6/1 = 6 >= 5 trips the up —
    # diluted over all three (6/3 = 2) it would not, and the max bound
    # must not count the quarantined pair either.
    for _ in range(6):
        fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    fleet.step()
    assert fleet.counters["scale_ups"] == 1
    assert len(fleet.replicas) == 4        # live capacity ADDED at max


# --------------------------------------------------------------------------
# Slow tier: THE drill — diurnal burst + TENANT_FLOOD vs a real 2→3 fleet
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_flood_autoscale_drill_matches_predict_and_reference_streams():
    """THE acceptance drill: seeded diurnal-burst background traffic
    (closed-loop, tick-deterministic) + a TENANT_FLOOD against a real
    fleet with the full control plane on.  Pinned: scale-up/scale-down/
    throttle/flood counters == ``predict_fleet(autoscale=True,
    quota_tokens=, flood_request_tokens=)`` EXACTLY; the scale-down
    loses zero accepted requests and every completed stream — including
    those served by the scaled-up replica before it drained — is
    bit-identical to single-engine ``generate()``; the flooding tenant
    is throttled while the higher classes hold their latency targets;
    attribution reconciles across the RETIRED replica's journal."""
    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    plan = FaultPlan.scripted([
        FaultEvent(step=8, kind=FaultKind.TENANT_FLOOD, severity=12,
                   tenant="flood"),
    ])
    inj = FaultInjector(plan)
    ledger = AttributionLedger(None)
    trace = RecordingTrace()
    # Generous latency targets: the "higher classes hold their targets"
    # assertion must pin the CONTROL behaviour, not this container's
    # wall clock.
    classes = (SLOClass("batch", priority=0, weight=1.0),
               SLOClass("standard", priority=1, weight=2.0,
                        ttft_target_s=60.0, itl_target_s=10.0),
               SLOClass("premium", priority=2, weight=4.0,
                        ttft_target_s=60.0, itl_target_s=10.0))
    fleet = ServingFleet(
        params, CFG,
        fleet_config=FleetConfig(
            num_replicas=2, max_retries=6, restart_ticks=2,
            quarantine_cooloff_ticks=10_000,
            slo_classes=classes,
            tenant_quota=TenantQuotaConfig(
                capacity_tokens=100_000, refill_per_tick=0.0,
                # The flood tenant's own bucket: 40 tokens at 8 per
                # flood request -> 5 admitted, 7 throttled of 12.
                per_tenant={"flood": (40.0, 0.0)}),
            autoscale=AutoscalerConfig(
                min_replicas=2, max_replicas=3,
                # Queue is the ONLY drill trigger: occupancy/latency
                # arms neutralised so the pinned counts depend on the
                # deterministic tick-driven queue alone.
                scale_up_queue_per_replica=6.0,
                scale_down_queue_per_replica=0.5,
                scale_up_occupancy=1.1, scale_down_occupancy=1.0,
                scale_up_cooldown_ticks=200,
                scale_down_cooldown_ticks=8,
                scale_down_idle_ticks=6),
        ),
        chaos=inj, ledger=ledger,
        max_slots=4, max_seq=48, queue_limit=32,
        # The drill pins CONTROL arithmetic: the output monitor's
        # (deterministic but hard-to-predict) flags must not add
        # un-planned drains to the counter comparison.
        enable_monitor=False,
    )
    fleet.trace = trace

    # Seeded diurnal background traffic, driven CLOSED-loop so the
    # queue the autoscaler reads is a function of ticks, not of this
    # machine's service rate.
    items = generate_workload(
        WorkloadConfig(seed=5, num_requests=20, mean_rps=16.0,
                       burstiness=0.6, prompt_median=6, output_median=5,
                       max_output=8),
        CFG.vocab_size, 48)
    reqs = {}
    pending = list(items)
    ticks = 0
    while pending or fleet.busy:
        while pending and fleet.open_requests < 10:
            item = pending[0]
            fid = fleet.submit(ServeRequest(
                prompt=list(item.prompt),
                max_new_tokens=item.max_new_tokens,
                temperature=0.0, priority=item.priority,
                tenant=item.tenant,
            ))
            if fid is None:
                break
            pending.pop(0)
            reqs[fid] = (list(item.prompt), item.max_new_tokens)
        fleet.step()
        ticks += 1
        assert ticks < 4000, "drill did not drain"
    # Idle breaths: let the trailing scale-down land.
    for _ in range(24):
        fleet.step()

    # THE pinned counters: control decisions == the plan's arithmetic.
    predicted = plan.predict_fleet(autoscale=True, quota_tokens=40,
                                   flood_request_tokens=8)
    observed = {k: fleet.counters[k] for k in predicted}
    assert observed == predicted, (observed, predicted)
    assert fleet.counters["scale_ups"] == 1
    assert fleet.counters["scale_downs"] == 1
    assert fleet.counters["throttles"] == 7

    # The breath is visible: up to 3, back to 2, replica 2 RETIRED
    # with its journal retained.
    scales = [(e["direction"], e["from_replicas"], e["to_replicas"])
              for e in trace.of("fleet_scale")]
    assert scales == [("up", 2, 3), ("down", 3, 2)]
    retired = [r for r in fleet.replicas
               if r.state is ReplicaState.RETIRED]
    assert len(retired) == 1                # breathed back to the floor
    assert f"{retired[0].index}:0" in fleet.journals
    throttled = trace.of("tenant_throttle")
    assert len(throttled) == 7
    assert all(e["tenant"] == "flood" for e in throttled)

    # Zero lost accepted work: every background request AND every
    # admitted flood request completed...
    results = fleet.results
    flood_fids = [fid for fid, r in results.items()
                  if r.tenant == "flood"]
    assert len(flood_fids) == 5             # 12 - 7 throttled
    assert sorted(results) == sorted(list(reqs) + flood_fids)
    assert all(r.status == "completed" for r in results.values())
    # ...and every stream is bit-identical to generate() — including
    # whatever the scaled-up replica served before it drained out.
    flood_prompt = [0] * fleet.config.flood_prompt_len
    flood_ref = np.asarray(generate(
        params, CFG, jnp.asarray([flood_prompt], jnp.int32),
        fleet.config.flood_new_tokens, temperature=0.0,
    ))[0, len(flood_prompt):].tolist()
    served_by_new_replica = 0
    for fid, res in results.items():
        if fid in reqs:
            prompt, new = reqs[fid]
            ref = np.asarray(generate(
                params, CFG, jnp.asarray([prompt], jnp.int32), new,
                temperature=0.0,
            ))[0, len(prompt):].tolist()
        else:
            ref = flood_ref
        assert res.tokens == ref, f"request {fid}"
        if res.replica == 2:
            served_by_new_replica += 1
    assert served_by_new_replica >= 1       # the extra capacity WORKED

    # The flooding tenant was throttled while the higher classes held
    # their (tracked) targets.
    summary = fleet.metrics_summary()
    per_class = summary["per_class"]
    assert per_class["standard"]["breached"] is False
    assert per_class["premium"]["breached"] is False
    assert per_class["batch"]["completed"] >= 5   # flood class served
    assert sum(c["completed"] for c in per_class.values()) == \
        len(results)

    # Attribution reconciles across every generation — including the
    # retired replica's journal.
    ok, problems = fleet.verify_attribution()
    assert ok, problems
    admitted = [r for r in ledger.records() if r.get("admitted")]
    assert sorted(r["request_id"] for r in admitted) == sorted(results)
    assert {r["tenant"] for r in admitted} >= {"flood"}
    assert all(r.get("slo_class") for r in admitted)
