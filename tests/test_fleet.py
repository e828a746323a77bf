"""Trust-aware serving fleet (serve/fleet.py + serve/workload.py).

Fast tier: host contracts through a FakeEngine seam (state machine
transitions, backoff schedule, hedge dedup-at-retire, drain blocks
admission, replica-addressed chaos, workload generator determinism) —
nothing jits a model.  Slow tier: THE seeded drill — REPLICA_CRASH +
REPLICA_POISON + REPLICA_STALL in one plan over real engines, asserting
the ``FaultPlan.predict_fleet()``-pinned failover/drain/quarantine
counts, zero lost accepted requests, and every surviving stream
bit-identical to a single-engine ``generate()`` reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.chaos import (
    AdaptivePoisonAttacker,
    AdversaryConfig,
    FaultEvent,
    FaultInjector,
    FaultKind,
    FaultPlan,
    MarginSignatureMonitor,
    predict_attacker_trajectory,
)
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.attribution import AttributionLedger
from trustworthy_dl_tpu.serve import (
    FleetConfig,
    ReplicaState,
    ServeRequest,
    ServeResult,
    ServingFleet,
    Tenant,
    WorkloadConfig,
    backoff_ticks,
    generate_workload,
)

pytestmark = pytest.mark.fleet

# Unique decode geometry for this file (vocab 107): the process-global
# jit cache must never hand another serve-test file's compiled program
# to this one's compile-sensitive assertions (test_quant/test_paged_kv
# document the same split: 97/101/103).
CFG = gpt2.GPT2Config(vocab_size=107, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


class FakeEngine:
    """Minimal host-only stand-in honouring the fleet's engine surface:
    submit/step/cancel, queued/inflight ids, retire_hook.  ``step()``
    admits the queue; tests finish requests explicitly via
    ``complete()``."""

    def __init__(self, index, **kwargs):
        self.index = index
        self.replica_id = kwargs.get("replica_id")
        self.retire_hook = kwargs.get("retire_hook")
        self.slo = kwargs.get("slo")
        self.anomaly = kwargs.get("anomaly")
        self.chaos = kwargs.get("chaos")
        self.queue_limit = kwargs.get("queue_limit", 64)
        self.kv_dtype = "model"
        self.weight_dtype = "model"
        self.kv_fallback_reason = None
        self._next = 0
        self.queue = {}
        self.inflight = {}
        self.steps = 0

    def submit(self, request):
        if len(self.queue) >= self.queue_limit:
            return None
        rid = self._next
        self._next += 1
        self.queue[rid] = request
        return rid

    def step(self):
        self.inflight.update(self.queue)
        self.queue.clear()
        self.steps += 1
        return 0

    def cancel(self, rid, status="cancelled"):
        req = self.queue.pop(rid, None) or self.inflight.pop(rid, None)
        if req is None:
            return False
        self.retire_hook(ServeResult(request_id=rid, tokens=[],
                                     status=status, ttft_s=None, itl_s=[]),
                         None)
        return True

    def complete(self, rid, tokens=(1, 2), status="completed",
                 flagged=False):
        if self.inflight.pop(rid, None) is None:
            del self.queue[rid]
        self.retire_hook(
            ServeResult(request_id=rid, tokens=list(tokens), status=status,
                        ttft_s=0.01, itl_s=[], flagged=flagged),
            {"layout": "stripe", "slot": 0, "block_ids": [],
             "prefix_block_ids": [], "prefix_publishers": {}},
        )

    @property
    def queued_ids(self):
        return list(self.queue)

    @property
    def inflight_ids(self):
        return list(self.inflight)

    @property
    def load(self):
        return len(self.queue) + len(self.inflight)


def fake_fleet(num_replicas=2, chaos=None, ledger=None, **cfg_kwargs):
    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(num_replicas=num_replicas, **cfg_kwargs),
        chaos=chaos, ledger=ledger, engine_factory=factory,
    )
    return fleet, fakes


# --------------------------------------------------------------------------
# Fast tier: host contracts
# --------------------------------------------------------------------------


def test_fleet_config_validation_and_backoff_schedule():
    with pytest.raises(ValueError):
        FleetConfig(num_replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(flag_rate_quarantine=0.0)
    with pytest.raises(ValueError):
        FleetConfig(flag_min_count=8, flag_window=4)
    with pytest.raises(ValueError):
        FleetConfig(backoff_mult=0.5)
    cfg = FleetConfig(backoff_base_ticks=2, backoff_mult=2.0)
    assert [backoff_ticks(cfg, a) for a in (1, 2, 3, 4)] == [2, 4, 8, 16]
    with pytest.raises(ValueError):
        backoff_ticks(cfg, 0)


def test_stall_heartbeat_drives_degrade_drain_failover_readmit():
    """A wedged replica walks the ladder off missed-tick heartbeats
    alone: healthy -> degraded -> draining (in-flight failed over) ->
    restarting -> healthy; its request completes on the other replica
    and the drill counters record exactly one drain + one episode."""
    class RecordingTrace:
        def __init__(self):
            self.events = []

        def emit(self, type, **data):
            self.events.append({"type": getattr(type, "value", type),
                                **data})

    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=2, kind=FaultKind.REPLICA_STALL, target=0,
                   severity=12),
    ]))
    trace = RecordingTrace()
    fleet, fakes = fake_fleet(chaos=inj, heartbeat_miss_degraded=2,
                              heartbeat_miss_limit=4, restart_ticks=1,
                              backoff_base_ticks=0)
    fleet.trace = trace
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
    assert fleet.requests[fid].live.keys() == {0}   # least-index wins
    for _ in range(8):
        fleet.step()
    # The full ladder, in order, as typed replica_transition events
    # (one engine tick can walk several rungs — the trace is the record).
    ladder = [(e["from_state"], e["to_state"]) for e in trace.events
              if e["type"] == "replica_transition" and e["replica"] == 0]
    assert ladder[:3] == [("healthy", "degraded"),
                          ("degraded", "draining"),
                          ("draining", "restarting")]
    assert fleet.counters["drains"] == 1
    assert fleet.counters["failover_episodes"] == 1
    assert fleet.counters["failovers"] == 1
    # The request moved to replica 1 and completes there.
    attempt = fleet.requests[fid].live
    assert attempt.keys() == {1}
    fakes[1].complete(attempt[1].local_id, tokens=(7, 8))
    fleet.step()
    assert fleet.results[fid].status == "completed"
    assert fleet.results[fid].replica == 1
    assert fleet.results[fid].tokens == [7, 8]
    # Stall over + warmup -> the replica re-enters service.
    for _ in range(12):
        fleet.step()
    assert fleet.replicas[0].state is ReplicaState.HEALTHY


def test_hedge_dedup_exactly_one_canonical_stream():
    """Near-deadline hedging: the duplicate launches on a second
    replica, the FIRST completed attempt wins, the loser is cancelled
    and ledgered ``admitted: false, status: hedge_lost`` — exactly one
    admitted record per fleet request id."""
    ledger = AttributionLedger(None)
    fleet, fakes = fake_fleet(ledger=ledger, hedge_deadline_s=60.0)
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                    deadline_s=30.0))
    fleet.step()    # remaining 30 < 60: hedge fires
    rec = fleet.requests[fid]
    assert set(rec.live) == {0, 1}
    assert fleet.counters["hedges"] == 1
    # The HEDGE (replica 1) completes first -> canonical; primary loses.
    fakes[1].complete(rec.live[1].local_id, tokens=(5, 6))
    fleet.step()
    assert fleet.results[fid].status == "completed"
    assert fleet.results[fid].replica == 1
    assert fleet.results[fid].tokens == [5, 6]
    assert fleet.counters["hedge_lost"] == 1
    records = ledger.records()
    admitted = [r for r in records if r.get("admitted")]
    losers = [r for r in records if not r.get("admitted")]
    assert len(admitted) == 1 and admitted[0]["request_id"] == fid
    assert len(losers) == 1 and losers[0]["status"] == "hedge_lost"
    assert losers[0]["replica"] == 0
    assert not fleet.busy


def test_draining_replica_blocks_admission_until_capacity_returns():
    fleet, fakes = fake_fleet(num_replicas=2)
    fleet.replicas[0].state = ReplicaState.DRAINING
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    assert fleet.requests[fid].live.keys() == {1}   # routed around drain
    fleet.replicas[1].state = ReplicaState.DRAINING
    parked = fleet.submit(ServeRequest(prompt=[2], max_new_tokens=1))
    rec = fleet.requests[parked]
    assert not rec.live and rec.retry_due is not None   # accepted, parked
    fleet.replicas[0].state = ReplicaState.HEALTHY
    fleet.step()
    assert rec.live.keys() == {0}                  # resubmitted on revival


def test_fleet_backpressure_when_every_admitting_queue_is_full():
    fleet, fakes = fake_fleet(num_replicas=2, )
    for f in fakes.values():
        f.queue_limit = 1
    a = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    b = fleet.submit(ServeRequest(prompt=[2], max_new_tokens=1))
    assert a is not None and b is not None
    shed = fleet.submit(ServeRequest(prompt=[3], max_new_tokens=1))
    assert shed is None                             # true backpressure
    assert fleet.rejected == 1


def test_crash_fails_over_and_restarts_with_retained_journal():
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=2, kind=FaultKind.REPLICA_CRASH, target=0),
    ]))
    fleet, fakes = fake_fleet(chaos=inj, restart_ticks=2,
                              backoff_base_ticks=0)
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
    assert fleet.requests[fid].live.keys() == {0}
    fleet.step()            # tick 1
    fleet.step()            # tick 2: crash fires
    assert fleet.replicas[0].engine is None
    assert fleet.replicas[0].state is ReplicaState.RESTARTING
    assert fleet.counters["crashes"] == 1
    assert fleet.counters["failover_episodes"] == 1
    rec = fleet.requests[fid]
    assert rec.closed and rec.closed[0]["outcome"] == "crashed"
    fleet.step()
    assert rec.live.keys() == {1}                  # failed over
    for _ in range(3):
        fleet.step()
    assert fleet.replicas[0].state is ReplicaState.HEALTHY
    assert fleet.replicas[0].engine is not None
    assert fleet.replicas[0].gen == 1              # new generation
    assert fleet.counters["restarts"] == 1
    assert "0:0" in fleet.journals and "0:1" in fleet.journals
    fakes[1].complete(rec.live[1].local_id)
    fleet.step()
    assert fleet.results[fid].status == "completed"


def test_retry_exhaustion_is_an_explicit_terminal_never_silent():
    """A request whose every attempt is shed finalizes
    ``failover_exhausted`` after max_retries resubmissions — an
    accepted request always retires with an explicit status."""

    fleet, fakes = fake_fleet(num_replicas=2, max_retries=2,
                              backoff_base_ticks=0)
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    for _ in range(10):
        if fleet.requests.get(fid) is None:
            break
        rec = fleet.requests[fid]
        for rep_idx, att in list(rec.live.items()):
            fakes[rep_idx].queue.pop(att.local_id, None)
            fakes[rep_idx].inflight.pop(att.local_id, None)
            fakes[rep_idx].retire_hook(
                ServeResult(request_id=att.local_id, tokens=[],
                            status="no_capacity", ttft_s=None, itl_s=[]),
                None)
        fleet.step()
    res = fleet.results[fid]
    assert res.status == "failover_exhausted"
    assert res.attempts == 3                        # 1 + max_retries
    assert fleet.counters["failovers"] == 2


def test_replica_addressed_serve_poison_never_crosses_replicas():
    """Satellite regression: request ids are replica-LOCAL in a fleet —
    a SERVE_POISON aimed at replica 1's request 3 must never fire on
    replica 0's request 3 (same id, different namespace)."""

    class Task:
        def __init__(self):
            self.request_id = 3
            self.entropies = [3.0, 3.1]
            self.margins = [0.5, 0.4]

    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=3, kind=FaultKind.SERVE_POISON, target=1),
    ]))
    on_zero = Task()
    inj.on_serve_retire(on_zero, replica=0)        # wrong replica
    assert on_zero.margins == [0.5, 0.4]           # untouched
    assert not inj.fired
    standalone = Task()
    inj.on_serve_retire(standalone)                # no replica at all
    assert standalone.margins == [0.5, 0.4]
    on_one = Task()
    inj.on_serve_retire(on_one, replica=1)         # the addressed target
    assert on_one.margins[0] > 100.0               # poisoned
    assert len(inj.fired) == 1
    # Fire-once: a second retire with the same local id stays clean.
    again = Task()
    inj.on_serve_retire(again, replica=1)
    assert again.margins == [0.5, 0.4]


def test_replica_poison_persists_until_healed():
    class Task:
        def __init__(self, rid):
            self.request_id = rid
            self.entropies = [3.0]
            self.margins = [0.5]

    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_POISON, target=2),
    ]))
    assert [e.kind for e in inj.on_fleet_tick(1)] \
        == [FaultKind.REPLICA_POISON]
    assert inj.on_fleet_tick(2) == []              # fire-once event
    for rid in (0, 1):                             # ...persistent effect
        t = Task(rid)
        inj.on_serve_retire(t, replica=2)
        assert t.margins[0] > 100.0
    clean = Task(2)
    inj.on_serve_retire(clean, replica=1)          # other replicas clean
    assert clean.margins == [0.5]
    inj.heal_replica(2)
    healed = Task(3)
    inj.on_serve_retire(healed, replica=2)
    assert healed.margins == [0.5]


def test_predict_fleet_counts_and_generate_targets():
    plan = FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_POISON, target=2),
        FaultEvent(step=3, kind=FaultKind.REPLICA_CRASH, target=0),
        FaultEvent(step=5, kind=FaultKind.REPLICA_STALL, target=1),
        FaultEvent(step=7, kind=FaultKind.REPLICA_SLOWSTART, target=1),
    ])
    assert plan.predict_fleet() == {
        "crashes": 1, "restarts": 1, "stalls": 1, "poisons": 1,
        "adaptive_poisons": 0, "slowstarts": 1, "failover_episodes": 2,
        "suspicions": 1, "votes": 0, "outvotes": 0, "drains": 2,
        "quarantines": 1,
        "tenant_floods": 0, "throttles": 0,
        "scale_ups": 0, "scale_downs": 0,
        "adapter_poisons": 0, "adapter_quarantines": 0,
        "adapter_throttles": 0,
        "preempts": 0,
    }
    # Seeded generation draws replica targets for fleet kinds...
    gen_plan = FaultPlan.generate(7, 50, {FaultKind.REPLICA_CRASH: 0.1},
                                  num_replicas=3)
    assert gen_plan.events, "expected some crashes at rate 0.1 over 50"
    assert all(0 <= e.target < 3 for e in gen_plan.events)
    assert FaultPlan.generate(
        7, 50, {FaultKind.REPLICA_CRASH: 0.1}, num_replicas=3,
    ).events == gen_plan.events                    # reproducible
    # ...and refuses fleet rates without a replica count.
    with pytest.raises(ValueError, match="num_replicas"):
        FaultPlan.generate(0, 10, {FaultKind.REPLICA_STALL: 0.5})


def test_workload_generator_is_seeded_bursty_and_skewed():
    cfg = WorkloadConfig(seed=3, num_requests=256, mean_rps=32.0,
                         burstiness=0.8)
    a = generate_workload(cfg, vocab_size=97, max_seq=64)
    b = generate_workload(cfg, vocab_size=97, max_seq=64)
    assert a == b                                  # reproducible
    assert len(a) == 256
    for item in a:
        assert len(item.prompt) + item.max_new_tokens <= 64
        assert all(0 <= t < 97 for t in item.prompt)
        assert item.max_new_tokens >= 1
    # Tenant skew: the heavy tenant dominates, every class shows up.
    names = [i.tenant for i in a]
    assert names.count("bulk") > names.count("interactive") \
        > names.count("premium") > 0
    prios = {i.tenant: i.priority for i in a}
    assert prios["premium"] > prios["bulk"]
    # Heavy tail: max prompt length far above the median.
    plens = sorted(len(i.prompt) for i in a)
    assert plens[-1] >= 3 * plens[len(plens) // 2]
    # Burstiness: inter-arrival gaps swing well beyond Poisson jitter —
    # the shortest-gap decile packs much tighter than the longest.
    gaps = np.diff([i.t_arrive for i in a])
    assert np.quantile(gaps, 0.9) > 4 * max(np.quantile(gaps, 0.1), 1e-9)
    with pytest.raises(ValueError):
        WorkloadConfig(burstiness=1.0)
    with pytest.raises(ValueError):
        WorkloadConfig(tenants=())


def test_slowstart_pauses_admissions_without_failover():
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_SLOWSTART, target=0,
                   severity=5),
    ]))
    fleet, fakes = fake_fleet(chaos=inj)
    fleet.step()
    assert fleet.replicas[0].state is ReplicaState.RESTARTING
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    assert fleet.requests[fid].live.keys() == {1}  # warmup excluded
    assert fleet.counters["slowstarts"] == 1
    assert fleet.counters["failover_episodes"] == 0
    for _ in range(7):
        fleet.step()
    assert fleet.replicas[0].state is ReplicaState.HEALTHY


def test_invalid_submit_raises_without_orphaning_a_record():
    """Review regression: an impossible request must fail AT submit with
    the engine's own semantics and leave NO registered record behind —
    an orphan (no live attempt, no retry, done=False) would keep
    ``busy`` True forever and spin run_until_idle to its tick bound."""
    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    fleet = ServingFleet(params, CFG, num_replicas=2, max_slots=2,
                         max_seq=32, queue_limit=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=0))
    with pytest.raises(ValueError, match="empty prompt"):
        fleet.submit(ServeRequest(prompt=[], max_new_tokens=1))
    with pytest.raises(ValueError, match="max_seq"):
        fleet.submit(ServeRequest(prompt=[1] * 30, max_new_tokens=10))
    assert not fleet.requests and not fleet.busy   # nothing orphaned
    assert fleet.run_until_idle(max_ticks=2) == {}


def test_queue_expiry_does_not_dilute_the_flag_rate_window():
    """Review regression: a queue-side deadline expiry (placement None —
    it never held a slot, the monitor never ran) must NOT feed the
    replica's flag-rate window; otherwise tight-deadline sheds dilute
    the rate and a poisoned replica hides below the quarantine
    threshold."""
    fleet, fakes = fake_fleet(num_replicas=2)
    fleet._on_terminal(0, ServeResult(request_id=99, tokens=[],
                                      status="deadline_exceeded",
                                      ttft_s=None, itl_s=[]), None)
    assert len(fleet.replicas[0].flags) == 0       # unknown id: no-op
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    rec = fleet.requests[fid]
    local = rec.live[0].local_id
    # Queue-side expiry: placement None -> window untouched.
    fakes[0].queue.pop(local, None)
    fakes[0].inflight.pop(local, None)
    fakes[0].retire_hook(ServeResult(request_id=local, tokens=[],
                                     status="deadline_exceeded",
                                     ttft_s=None, itl_s=[]), None)
    fleet.step()
    assert len(fleet.replicas[0].flags) == 0
    # Slot-side retirement: placement present -> window fed.
    fid2 = fleet.submit(ServeRequest(prompt=[2], max_new_tokens=1))
    fakes[0].complete(fleet.requests[fid2].live[0].local_id,
                      flagged=True)
    fleet.step()
    assert list(fleet.replicas[0].flags) == [1]


def test_chaos_on_quarantined_replica_never_launders_trust_state():
    """Review regression: a CRASH or SLOWSTART landing on a QUARANTINED
    replica must not cancel its cool-off or readmit it without a probe
    — dying is not an exit from the trust ladder."""
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=3, kind=FaultKind.REPLICA_CRASH, target=0),
        FaultEvent(step=4, kind=FaultKind.REPLICA_SLOWSTART, target=0,
                   severity=1),
    ]))
    fleet, fakes = fake_fleet(chaos=inj, quarantine_cooloff_ticks=1000)
    rep = fleet.replicas[0]
    rep.state = ReplicaState.QUARANTINED
    rep.cooloff_until = 1000
    for _ in range(6):
        fleet.step()
    assert rep.state is ReplicaState.QUARANTINED   # ladder intact
    assert rep.cooloff_until == 1000               # cool-off untouched
    assert rep.engine is None                      # crash still landed
    assert fleet.counters["crashes"] == 1
    assert fleet.counters["failover_episodes"] == 0  # held no work


def test_failover_emits_one_event_with_the_replica_it_left():
    """Review regression: exactly ONE fleet_failover trace event per
    failover, naming the replica the request actually left — so
    event-count-vs-counter reconciliation holds and forensics don't
    misattribute the failing replica."""

    class RecordingTrace:
        def __init__(self):
            self.events = []

        def emit(self, type, **data):
            self.events.append({"type": getattr(type, "value", type),
                                **data})

    trace = RecordingTrace()
    fleet, fakes = fake_fleet(num_replicas=3, backoff_base_ticks=0,
                              max_retries=4)
    fleet.trace = trace
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    first = fleet.requests[fid].live
    assert set(first) == {0}

    def shed_current():
        rec = fleet.requests[fid]
        for rep_idx, att in list(rec.live.items()):
            fakes[rep_idx].queue.pop(att.local_id, None)
            fakes[rep_idx].inflight.pop(att.local_id, None)
            fakes[rep_idx].retire_hook(
                ServeResult(request_id=att.local_id, tokens=[],
                            status="no_capacity", ttft_s=None, itl_s=[]),
                None)
            return rep_idx

    left_a = shed_current()
    fleet.step()
    left_b = shed_current()
    fleet.step()
    failovers = [e for e in trace.events if e["type"] == "fleet_failover"]
    assert len(failovers) == fleet.counters["failovers"] == 2
    assert [e["from_replica"] for e in failovers] == [left_a, left_b]


def test_engine_trace_events_carry_replica_in_fleet_mode():
    """Review regression: replica-local request ids are ambiguous on a
    shared TraceBus — every engine lifecycle event must carry the
    replica index when the engine runs inside a fleet (standalone
    engines stay untagged)."""

    class RecordingTrace:
        def __init__(self):
            self.events = []

        def emit(self, type, **data):
            self.events.append({"type": getattr(type, "value", type),
                                **data})

    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    trace = RecordingTrace()
    from trustworthy_dl_tpu.serve import ServingEngine

    tagged = ServingEngine(params, CFG, max_slots=1, max_seq=32,
                           trace=trace, replica_id=1)
    tagged.submit(ServeRequest(prompt=[1, 2], max_new_tokens=1))
    assert trace.events and all(e.get("replica") == 1
                                for e in trace.events)
    plain = ServingEngine(params, CFG, max_slots=1, max_seq=32,
                          trace=trace)
    plain.submit(ServeRequest(prompt=[1, 2], max_new_tokens=1))
    assert "replica" not in trace.events[-1]


def test_replay_workload_drives_any_serving_surface():
    """The shared open-loop driver (the CLI uses this one spelling):
    submits each arrival on time, steps while busy, returns accepted."""
    fleet, fakes = fake_fleet(num_replicas=2)
    items = generate_workload(WorkloadConfig(seed=1, num_requests=4,
                                             mean_rps=10_000.0), 97, 48)

    class AutoComplete:
        """Wrap the fleet so every admitted attempt finishes next tick
        (FakeEngines never finish on their own)."""

        busy = property(lambda self: fleet.busy)

        def submit(self, request):
            return fleet.submit(request)

        def step(self):
            for fake in fakes.values():
                for rid in list(fake.inflight):
                    fake.complete(rid)
            return fleet.step()

    from trustworthy_dl_tpu.serve import replay_workload

    accepted = replay_workload(AutoComplete(), items, lambda item:
                               ServeRequest(prompt=list(item.prompt),
                                            max_new_tokens=1))
    assert accepted == 4
    assert sorted(fleet.results) == list(range(4))
    assert all(r.status == "completed" for r in fleet.results.values())


@pytest.mark.fleetctl
def test_production_scale_drill_bounded_per_tick_work():
    """Production-shape scalability drill (50x the PR 8 slow drill's 12
    requests) through the host-only FakeEngine seam, with the FULL
    control plane on — SLO classes + DRR dispatch, tenant token
    buckets, and the autoscaler: 600 requests drain with every one
    accounted, while the fleet's live working set stays bounded by the
    closed-loop in-flight target — router/scheduler/admission are
    O(small) per tick, not O(requests ever submitted)."""
    from trustworthy_dl_tpu.serve import (
        DEFAULT_SLO_CLASSES,
        AutoscalerConfig,
        TenantQuotaConfig,
        WorkloadConfig,
        drive_closed_loop,
        generate_workload,
    )

    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=2,
            slo_classes=DEFAULT_SLO_CLASSES,
            tenant_quota=TenantQuotaConfig(capacity_tokens=100_000,
                                           refill_per_tick=50.0),
            autoscale=AutoscalerConfig(
                min_replicas=2, max_replicas=4,
                scale_up_queue_per_replica=24.0,
                scale_down_queue_per_replica=1.0,
                scale_up_occupancy=1.1, scale_down_occupancy=1.0,
                scale_up_cooldown_ticks=4,
                scale_down_cooldown_ticks=8,
                scale_down_idle_ticks=4),
        ),
        engine_factory=factory,
    )
    items = generate_workload(
        WorkloadConfig(seed=11, num_requests=600, mean_rps=10_000.0),
        97, 64)
    inflight_target = 32
    peaks = {"open": 0, "requests": 0}

    class AutoComplete:
        """FakeEngines never finish on their own: complete every
        admitted attempt each tick, recording the live-set peaks."""

        busy = property(lambda self: fleet.busy)
        open_requests = property(lambda self: fleet.open_requests)

        def submit(self, request):
            return fleet.submit(request)

        def step(self):
            peaks["open"] = max(peaks["open"], fleet.open_requests)
            peaks["requests"] = max(peaks["requests"],
                                    len(fleet.requests))
            for fake in list(fakes.values()):
                for rid in list(fake.inflight):
                    fake.complete(rid)
            return fleet.step()

    accepted = drive_closed_loop(
        AutoComplete(), items,
        lambda item: ServeRequest(prompt=list(item.prompt),
                                  max_new_tokens=item.max_new_tokens,
                                  priority=item.priority,
                                  tenant=item.tenant),
        inflight_target)
    # Every request accounted — accepted ones completed, the rest were
    # loudly throttled/rejected (counters, never silence).
    assert accepted + fleet.counters["throttles"] + fleet.rejected \
        == 600
    statuses = [r.status for r in fleet.results.values()]
    assert statuses.count("completed") == accepted
    assert accepted >= 550                 # the quota is generous here
    # Bounded per-tick work: the live working set tracked the closed
    # loop's in-flight target, not the 600-request history (small slack
    # for settled-but-unpruned records inside one tick).
    assert peaks["open"] <= inflight_target
    assert peaks["requests"] <= inflight_target + 16
    # The control plane actually engaged at scale.
    summary = fleet.metrics_summary()
    assert sum(c["completed"] for c in summary["per_class"].values()) \
        == accepted
    assert all(c["completed"] > 0 for c in summary["per_class"].values())
    assert not fleet.busy


# --------------------------------------------------------------------------
# Adversarial tier: suspicion below the threshold + verdict voting
# --------------------------------------------------------------------------


class RecordingTrace:
    def __init__(self):
        self.events = []

    def emit(self, type, **data):
        self.events.append({"type": getattr(type, "value", type), **data})

    def of(self, type):
        return [e for e in self.events if e["type"] == type]


def _complete_ballots(fleet, fakes, vote_target, tokens):
    """Finish every outstanding vote-replay ballot with ``tokens``
    (per-voter dict or one tuple for all) and settle the tick."""
    for (voter, local), vote in list(fleet._vote_ballots.items()):
        if vote.target != vote_target:
            continue
        toks = tokens[voter] if isinstance(tokens, dict) else tokens
        fakes[voter].complete(local, tokens=toks)
    fleet.step()


@pytest.mark.adversary
def test_adversary_config_validation_and_pinned_controller():
    with pytest.raises(ValueError, match="mode"):
        AdversaryConfig(target=0, mode="nope")
    with pytest.raises(ValueError, match="min_strength"):
        AdversaryConfig(target=0, min_strength=0.9, max_strength=0.5)
    with pytest.raises(ValueError, match="corrupt_fraction"):
        AdversaryConfig(target=0, corrupt_fraction=0.0)
    cfg = AdversaryConfig(target=1, initial_strength=0.3, step_up=0.1,
                          backoff=0.5, min_strength=0.05,
                          flag_rate_quarantine=0.25, safety_margin=0.05)
    attacker = AdaptivePoisonAttacker(cfg)
    attacker.activate()
    # Live controller == predictor, observation for observation: the
    # trajectory is pinned exactly (climb while comfortable, hold in
    # the band, multiplicative backoff near the threshold).
    flags = [False, False, True, False, False, False]
    window = []
    for f in flags:
        window.append(1 if f else 0)
        attacker.observe(f, sum(window[-8:]) / len(window[-8:]))
    assert attacker.strength_history == \
        predict_attacker_trajectory(cfg, flags, flag_window=8)
    assert attacker.strength_history[:4] == [0.3, 0.4, 0.5, 0.25]


@pytest.mark.adversary
def test_adaptive_poison_requires_an_attached_adversary():
    """Loud contract: an adaptive event with no (or a mis-targeted)
    adversary must raise at fire time, not silently no-op the drill."""
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_ADAPTIVE_POISON,
                   target=2),
    ]))
    with pytest.raises(ValueError, match="no adversary"):
        inj.on_fleet_tick(1)
    wrong = FaultInjector(
        FaultPlan.scripted([FaultEvent(
            step=1, kind=FaultKind.REPLICA_ADAPTIVE_POISON, target=2)]),
        adversary=AdaptivePoisonAttacker(AdversaryConfig(target=0)),
    )
    with pytest.raises(ValueError, match="configured for replica"):
        wrong.on_fleet_tick(1)


@pytest.mark.adversary
def test_predict_fleet_vote_extension_and_validity_bound():
    plan = FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_ADAPTIVE_POISON,
                   target=2),
    ])
    blind = plan.predict_fleet()            # voting off: the blind spot
    assert blind["adaptive_poisons"] == 1
    assert blind["suspicions"] == 1
    assert blind["quarantines"] == blind["drains"] == blind["votes"] == 0
    caught = plan.predict_fleet(vote_k=2, vote_outvote_limit=3)
    assert caught["votes"] == caught["outvotes"] == 3
    assert caught["drains"] == caught["quarantines"] == 1
    # A lone voter can never outvote: vote counts are traffic-bound.
    with pytest.raises(ValueError, match="vote_k=1"):
        plan.predict_fleet(vote_k=1)
    # Satellite: the cool-off validity bound is LOUD — a horizon that
    # crosses a quarantined replica's cool-off expiry raises instead of
    # silently predicting counts the readmission-probe churn falsifies.
    with pytest.raises(ValueError, match="validity bound"):
        plan.predict_fleet(vote_k=2, horizon=500, cooloff_ticks=100)
    assert plan.predict_fleet(vote_k=2, horizon=500,
                              cooloff_ticks=10_000)["quarantines"] == 1


@pytest.mark.adversary
def test_suspicion_tier_works_with_voting_disabled():
    """Satellite: a sustained-but-sub-threshold flag rate emits
    fleet_suspicion and the tddl_fleet_suspicion{replica=} gauge even
    at vote_k=0 — the blind spot is at least VISIBLE without voting."""
    from trustworthy_dl_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    trace = RecordingTrace()
    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=2, flag_window=16, flag_min_count=8,
            suspicion_threshold=0.1, suspicion_min_flags=2),
        engine_factory=factory, registry=reg, trace=trace,
    )
    # Two flagged retirements among clean ones: rate 2/5 but
    # flag_min_count=8 keeps the ladder silent — suspicion still opens.
    # (observe_retirement is the documented slot-side feed point.)
    for flagged in (True, False, True, False, False):
        fleet.observe_retirement(0, flagged)
    fleet.step()
    rep = fleet.replicas[0]
    assert rep.state in (ReplicaState.HEALTHY, ReplicaState.DEGRADED)
    assert fleet.counters["suspicions"] == 1
    assert fleet.counters["votes"] == 0          # K=0: no audits
    episodes = trace.of("fleet_suspicion")
    assert len(episodes) == 1 and episodes[0]["replica"] == 0
    assert episodes[0]["reason"] == "flag_rate"
    assert reg.get("tddl_fleet_suspicion").value(replica="0") \
        == pytest.approx(rep.suspicion)
    assert reg.get("tddl_fleet_suspicions_total").value() == 1.0
    # Hysteresis: the episode closes only once the EWMA decays well
    # under the threshold — and a fresh crossing is a NEW episode.
    for _ in range(12):
        fleet.observe_retirement(0, False)
    assert not rep.suspicion_episode
    # Verify-drive regression: an OUTVOTE on record pins the episode
    # open through the decay — a replica a verdict already went
    # against cannot wait out the EWMA and escape its deciding vote.
    fleet.observe_retirement(0, True)
    fleet.observe_retirement(0, True)
    assert rep.suspicion_episode
    rep.outvotes = 1
    for _ in range(20):
        fleet.observe_retirement(0, False)
    assert rep.suspicion < 0.05 and rep.suspicion_episode


@pytest.mark.adversary
def test_suspicion_vote_outvote_walks_the_quarantine_ladder():
    """The tentpole handoff: sub-threshold flags -> suspicion episode ->
    verdict votes (replayed on K other replicas) -> outvoted twice ->
    the SAME drain -> quarantine ladder the flag-rate trip uses; votes
    and outvotes land in the drill counters and the outcome-labelled
    tddl_fleet_votes_total."""
    from trustworthy_dl_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    trace = RecordingTrace()
    ledger = AttributionLedger(None)
    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=3, flag_window=16, flag_min_count=8,
            suspicion_threshold=0.1, suspicion_min_flags=2,
            vote_k=2, vote_outvote_limit=2, drain_grace_ticks=2),
        engine_factory=factory, registry=reg, trace=trace, ledger=ledger,
    )

    # Submit 9 requests up front: least-loaded routing spreads them 3
    # per replica — the suspect keeps serving from its admitted backlog
    # even after its first flag degrades it (the router only steers NEW
    # work away from a degraded replica).
    fids = [fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
            for _ in range(9)]
    on_zero = [fid for fid in fids if 0 in fleet.requests[fid].live]
    assert len(on_zero) == 3

    def finish_on_zero(fid, tokens, flagged):
        fakes[0].complete(fleet.requests[fid].live[0].local_id,
                          tokens=tokens, flagged=flagged)
        fleet.step()

    finish_on_zero(on_zero[0], (1, 2), True)
    assert not fleet._vote_ballots          # 1 flag: not yet suspected
    fid2 = on_zero[1]
    finish_on_zero(fid2, (3, 4), True)      # 2nd flag: suspected + vote
    assert fleet.counters["suspicions"] == 1
    assert fleet.counters["votes"] == 1
    ballots = {k for k, v in fleet._vote_ballots.items()
               if v.fid == fid2}
    assert {k[0] for k in ballots} == {1, 2}
    # The replays are audits: no user stream, no prefix publication.
    for (voter, local) in ballots:
        replay = (fakes[voter].queue.get(local)
                  or fakes[voter].inflight.get(local))
        assert replay.publish_prefix is False
        assert replay.on_token is None
    # Both replays agree with each other, against the original: OUTVOTED.
    _complete_ballots(fleet, fakes, 0, (9, 9))
    assert fleet.counters["outvotes"] == 1
    assert fleet.replicas[0].state in (ReplicaState.HEALTHY,
                                       ReplicaState.DEGRADED)
    fid3 = on_zero[2]
    finish_on_zero(fid3, (5, 6), False)     # still suspected: next vote
    assert fleet.counters["votes"] == 2
    _complete_ballots(fleet, fakes, 0, (8, 8))
    assert fleet.counters["outvotes"] == 2  # limit hit -> trust drain
    for _ in range(4):
        fleet.step()
    assert fleet.replicas[0].state is ReplicaState.QUARANTINED
    assert fleet.counters["drains"] == 1
    assert fleet.counters["quarantines"] == 1
    reasons = [(e["to_state"], e["reason"])
               for e in trace.of("replica_transition")
               if e["replica"] == 0]
    assert ("draining", "verdict_outvoted") in reasons
    votes = trace.of("verdict_vote")
    assert [v["outcome"] for v in votes] == ["outvoted", "outvoted"]
    assert votes[0]["request_id"] == fid2
    assert votes[1]["request_id"] == fid3
    assert reg.get("tddl_fleet_votes_total").value(outcome="outvoted") \
        == 2.0
    # Replay-path honesty: every ballot is an admitted:false
    # vote_replay record; exactly ONE admitted record per fleet id.
    records = ledger.records()
    replays = [r for r in records if r.get("status") == "vote_replay"]
    assert len(replays) == 4 and not any(r["admitted"] for r in replays)
    assert all(r["vote_target"] == 0 for r in replays)
    admitted = [r for r in records if r.get("admitted")]
    assert sorted(r["request_id"] for r in admitted) == \
        sorted({r["request_id"] for r in admitted})


@pytest.mark.adversary
def test_lone_faulty_voter_never_quarantines_a_clean_replica():
    """Safety contract: outvoting needs TWO agreeing dissenting ballots
    — a single lying voter cannot frame a clean replica (it only earns
    ITSELF suspicion), and at vote_k=1 no outvote is possible at all."""
    trace = RecordingTrace()
    fleet, fakes = fake_fleet(num_replicas=3, vote_k=2,
                              vote_outvote_limit=1, flag_min_count=8)
    fleet.trace = trace
    fleet.note_suspicion(0, "attribution")   # irregularity boost
    assert fleet.counters["suspicions"] == 1
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=2))
    fakes[0].complete(fleet.requests[fid].live[0].local_id,
                      tokens=(1, 2))
    fleet.step()
    assert fleet.counters["votes"] == 1
    # Voter 1 tells the truth (matches the original); voter 2 lies.
    _complete_ballots(fleet, fakes, 0, {1: (1, 2), 2: (7, 7)})
    for _ in range(3):
        fleet.step()
    assert fleet.counters["outvotes"] == 0
    assert fleet.replicas[0].state is not ReplicaState.QUARANTINED
    assert trace.of("verdict_vote")[0]["outcome"] == "confirmed"
    # ...and the LIAR is now the suspect (vote_dissent suspicion).
    assert fleet.replicas[2].suspicion > 0.0
    assert any(e["replica"] == 2 and e["reason"] == "vote_dissent"
               for e in trace.of("fleet_suspicion"))

    # vote_k=1: a lone voter's dissent is never conclusive.
    fleet2, fakes2 = fake_fleet(num_replicas=2, vote_k=1,
                                vote_outvote_limit=1, flag_min_count=8)
    fleet2.note_suspicion(0, "attribution")
    fid = fleet2.submit(ServeRequest(prompt=[1], max_new_tokens=2))
    fakes2[0].complete(fleet2.requests[fid].live[0].local_id,
                       tokens=(1, 2))
    fleet2.step()
    assert fleet2.counters["votes"] == 1
    _complete_ballots(fleet2, fakes2, 0, (9, 9))
    for _ in range(3):
        fleet2.step()
    assert fleet2.counters["outvotes"] == 0
    assert fleet2.replicas[0].state is not ReplicaState.QUARANTINED


@pytest.mark.adversary
def test_vote_dedup_with_hedged_retries():
    """One vote per fleet request id even when hedging doubled the
    attempts: only the WINNER's completion can trigger the audit, the
    hedge loser is never mistaken for a ballot, and the
    one-admitted-record invariant survives votes + hedges together."""
    ledger = AttributionLedger(None)
    fleet, fakes = fake_fleet(num_replicas=3, ledger=ledger,
                              hedge_deadline_s=60.0, vote_k=2,
                              vote_outvote_limit=5, flag_min_count=8)
    fleet.note_suspicion(1, "attribution")   # replica 1 is the suspect
    fid = fleet.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                    deadline_s=30.0))
    fleet.step()                             # hedge fires -> {0, 1}
    rec = fleet.requests[fid]
    assert set(rec.live) == {0, 1}
    # The hedge on the SUSPECTED replica completes first and wins.
    fakes[1].complete(rec.live[1].local_id, tokens=(5, 6))
    fleet.step()
    assert fleet.results[fid].replica == 1
    assert fleet.counters["votes"] == 1      # exactly one audit
    assert fleet.counters["hedge_lost"] == 1
    ballots = {k for k, v in fleet._vote_ballots.items() if v.fid == fid}
    assert {k[0] for k in ballots} == {0, 2}  # loser replica CAN vote
    _complete_ballots(fleet, fakes, 1, (5, 6))
    for _ in range(2):
        fleet.step()
    assert fleet.counters["votes"] == 1
    assert fleet.counters["outvotes"] == 0   # replays agreed: confirmed
    records = ledger.records()
    admitted = [r for r in records if r.get("admitted")]
    assert len(admitted) == 1 and admitted[0]["request_id"] == fid
    assert sorted(r["status"] for r in records if not r.get("admitted")) \
        == ["hedge_lost", "vote_replay", "vote_replay"]
    assert not fleet.busy


@pytest.mark.adversary
def test_crash_of_vote_target_abandons_the_stale_vote():
    """Review regression: a vote whose TARGET generation dies (crash →
    rebuild, which resets ``vote_open``) is abandoned — ballots
    cancelled, no outcome, no counters — so a stale verdict can never
    convict the successor generation, and the rebuilt replica cannot
    end up with two concurrent votes."""
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=2, kind=FaultKind.REPLICA_CRASH, target=0),
    ]))
    fleet, fakes = fake_fleet(num_replicas=3, chaos=inj, vote_k=2,
                              flag_min_count=8, restart_ticks=1,
                              backoff_base_ticks=0)
    fleet.note_suspicion(0, "attribution")
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    fakes[0].complete(fleet.requests[fid].live[0].local_id)
    fleet.step()                    # tick 1: vote launches on {1, 2}
    assert fleet.counters["votes"] == 1 and fleet._vote_ballots
    fleet.step()                    # tick 2: the TARGET crashes
    assert not fleet._vote_ballots  # stale vote abandoned outright
    assert fleet.counters["outvotes"] == 0
    assert not fleet.busy
    # The voters' replay slots were reclaimed, not left serving a
    # stream nobody will ever score.
    assert fakes[1].load == 0 and fakes[2].load == 0


@pytest.mark.adversary
def test_voter_crash_mid_vote_abstains_instead_of_wedging():
    """A ballot on a crashed replica abstains; the vote still resolves
    (inconclusively here) and ``busy`` clears — outstanding votes keep
    the loop live but never wedge it."""
    inj = FaultInjector(FaultPlan.scripted([
        FaultEvent(step=3, kind=FaultKind.REPLICA_CRASH, target=1),
        FaultEvent(step=3, kind=FaultKind.REPLICA_CRASH, target=2),
    ]))
    fleet, fakes = fake_fleet(num_replicas=3, chaos=inj, vote_k=2,
                              flag_min_count=8, restart_ticks=2)
    fleet.note_suspicion(0, "attribution")
    fid = fleet.submit(ServeRequest(prompt=[1], max_new_tokens=1))
    fakes[0].complete(fleet.requests[fid].live[0].local_id)
    fleet.step()                             # tick 1: vote launches
    assert fleet.counters["votes"] == 1 and fleet.busy
    fleet.step()                             # tick 2
    fleet.step()                             # tick 3: both voters crash
    assert not fleet._vote_ballots
    assert not fleet.busy
    assert fleet.counters["outvotes"] == 0
    assert fleet.replicas[0].state is not ReplicaState.QUARANTINED


# --------------------------------------------------------------------------
# Slow tier: THE seeded drill over real engines
# --------------------------------------------------------------------------


class PoisonSignatureMonitor:
    """Deterministic stand-in for the drill: flags exactly the chaos
    poison signature (margin >> any real logit margin).  The z-score
    monitor's statistics are covered by test_serve/test_chaos; the
    drill pins the FLEET's response to flags, which must not depend on
    how many requests the rolling baseline has absorbed."""

    def observe(self, entropies, margins):
        poisoned = float(np.mean(margins)) > 100.0
        return poisoned, (99.0 if poisoned else 0.0)


@pytest.mark.slow
@pytest.mark.forensics
def test_fleet_chaos_drill_matches_predict_and_reference_streams(tmp_path):
    """THE acceptance drill: REPLICA_POISON + REPLICA_CRASH +
    REPLICA_STALL in one seeded plan over 3 real engines.  Recovery
    counts match ``predict_fleet()`` exactly, every accepted request
    retires with an explicit status (zero silently dropped), all
    surviving streams are bit-identical to single-engine generate(),
    and the fleet attribution ledger reconciles against every replica
    generation's block journal — including records whose attempts span
    two replicas' allocators.

    Re-run with forensics attached (PR 18): the poison's quarantine
    assembles exactly one ``replica_quarantine`` incident whose trigger
    is the quarantine transition, whose action counts reconcile with
    ``predict_fleet()``, and whose blast radius names EXACTLY the
    requests whose ledger attempts touched the poisoned generation's
    blocks — no over-, no under-attribution."""
    from trustworthy_dl_tpu.obs.forensics import IncidentAssembler, \
        load_incidents
    from trustworthy_dl_tpu.obs.verdicts import VerdictStore

    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    plan = FaultPlan.scripted([
        FaultEvent(step=1, kind=FaultKind.REPLICA_POISON, target=2),
        FaultEvent(step=3, kind=FaultKind.REPLICA_CRASH, target=0),
        FaultEvent(step=6, kind=FaultKind.REPLICA_STALL, target=1,
                   severity=10),
    ])
    inj = FaultInjector(plan)
    ledger = AttributionLedger(None)
    trace = RecordingTrace()
    verdicts = VerdictStore(str(tmp_path / "VERDICTS.jsonl"))
    forensics = IncidentAssembler(str(tmp_path), trace=trace,
                                  ledger=ledger, verdicts=verdicts)
    fleet = ServingFleet(
        params, CFG,
        fleet_config=FleetConfig(
            num_replicas=3, max_retries=6, heartbeat_miss_limit=3,
            restart_ticks=2, drain_grace_ticks=4,
            quarantine_cooloff_ticks=10_000,   # stays out for the drill
        ),
        chaos=inj, ledger=ledger,
        max_slots=2, max_seq=48, queue_limit=32,
        monitor=PoisonSignatureMonitor(),
        forensics=forensics,
    )
    fleet.trace = trace
    rng = np.random.default_rng(1)
    reqs = []
    for _ in range(12):
        plen = int(rng.integers(3, 10))
        new = int(rng.integers(4, 10))
        prompt = rng.integers(0, CFG.vocab_size, plen).tolist()
        reqs.append((prompt, new))
        fleet.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
    results = fleet.run_until_idle(max_ticks=2000)

    # Exactly the plan-predicted recovery counts.
    predicted = plan.predict_fleet()
    observed = {k: fleet.counters[k] for k in predicted}
    assert observed == predicted, (observed, predicted)

    # Zero lost accepted requests: every one retires explicitly...
    assert sorted(results) == list(range(12))
    assert all(r.status == "completed" for r in results.values())
    # ...and every survivor is bit-identical to the reference.
    for fid, (prompt, new) in enumerate(reqs):
        ref = np.asarray(generate(
            params, CFG, jnp.asarray([prompt], jnp.int32), new,
            temperature=0.0,
        ))[0, len(prompt):].tolist()
        assert results[fid].tokens == ref, f"request {fid}"

    # The poisoned replica ends quarantined; the others recovered.
    assert fleet.states() == {0: "healthy", 1: "healthy",
                              2: "quarantined"}
    # Chaos fired exactly the plan.
    assert inj.counts() == {"replica_poison": 1, "replica_crash": 1,
                            "replica_stall": 1}

    # Attribution: reconciles across ALL replica generations, with at
    # least one record whose attempts span two different journals (a
    # failed-over request) — the one-record/two-journals contract.
    ok, problems = fleet.verify_attribution()
    assert ok, problems
    records = ledger.records()
    admitted = [r for r in records if r.get("admitted")]
    assert sorted(r["request_id"] for r in admitted) == list(range(12))
    spanning = [r for r in admitted if r.get("attempts")
                and len({a["journal"] for a in r["attempts"]}) > 1]
    assert spanning, "no record spans two replicas' journals"
    # The crash retained its generation's journal alongside the new one.
    assert "0:0" in fleet.journals and "0:1" in fleet.journals

    # -- forensics: the quarantine episode's incident report ---------------
    # Exactly one replica_quarantine incident — one per predicted
    # quarantine — written next to where the flight dump would land.
    counts = forensics.counts_by_reason()
    assert counts.get("replica_quarantine") == predicted["quarantines"]
    incidents = load_incidents(str(tmp_path))
    quar = [i for i in incidents if i["reason"] == "replica_quarantine"]
    assert len(quar) == predicted["quarantines"] == 1
    inc = quar[0]
    assert inc["schema_version"] == 1
    assert inc["suspect_replicas"] == [2]
    assert inc["suspect_journals"] == ["2:0"]
    # Trigger = the quarantine transition itself, with its trace seq.
    trig = inc["trigger"]
    assert trig["type"] == "replica_transition"
    assert trig["replica"] == 2 and trig["to_state"] == "quarantined"
    assert not trig.get("synthetic") and trig["seq"] is not None
    # Every contributing signal precedes the trigger and names the
    # suspect; the action count reconciles with predict_fleet(): the
    # suspect's quarantine transition appears exactly once.
    assert all(e["seq"] <= trig["seq"] for e in inc["contributing"])
    q_actions = [e for e in inc["actions"]
                 if e["type"] == "replica_transition"
                 and e["to_state"] == "quarantined"]
    assert len(q_actions) == predicted["quarantines"]
    # The counters snapshot at assembly already carried the quarantine.
    assert inc["counters"]["quarantines"] == predicted["quarantines"]
    assert inc["counters"]["poisons"] == predicted["poisons"]

    # Blast radius: EXACTLY the requests whose ledger attempts touched
    # the poisoned generation's blocks (directly or as migrated_from
    # provenance) — recomputed here by an independent walk.
    touched = set()
    for rec in admitted:
        for att in rec.get("attempts") or []:
            placed = bool(att.get("block_ids")) or (
                att.get("layout") == "stripe"
                and att.get("slot", -1) >= 0)
            if att.get("journal") == "2:0" and placed:
                touched.add(rec["request_id"])
            if (att.get("migrated_from") or {}).get("journal") == "2:0":
                touched.add(rec["request_id"])
    assert touched, "drill routed nothing through the poisoned replica"
    assert inc["blast_radius"]["requests"] == sorted(touched)
    assert set(inc["blast_radius"]["suspect_blocks"]) == {"2:0"}

    # The durable verdict history recorded the episode end-to-end:
    # suspicion opened, quarantine verdict, incident row — and the
    # priors aggregation pins replica 2 as the suspect.
    priors = verdicts.priors()
    rep2 = priors["replicas"]["2"]
    assert rep2["counts"].get("quarantine:quarantined") == 1
    assert inc["incident_id"] in rep2["incidents"]


@pytest.mark.slow
@pytest.mark.adversary
def test_adaptive_subthreshold_attacker_caught_by_verdict_voting():
    """THE adversarial acceptance drill: a seeded adaptive attacker
    corrupts replica 2's served streams while its controller holds the
    replica's public flag rate BELOW ``flag_rate_quarantine`` — the
    PR 8 ladder never trips (no flag-rate drain, no slot exhaustion) —
    yet verdict voting outvotes the corrupted streams twice and sends
    the replica down the same drain -> quarantine ladder.  Pinned:
    recovery counters == ``predict_fleet(vote_k=2)`` exactly (under its
    validity bound), the attacker's full strength trajectory ==
    ``predict_attacker_trajectory`` over the recorded flags, zero
    clean-replica quarantines, unaffected streams bit-identical to
    ``generate()``, corrupted streams provably corrupted, attribution
    reconciliation clean across the vote replays, and zero compile
    storms under the CompileWatcher."""
    from collections import deque

    from trustworthy_dl_tpu.obs.compilewatch import (
        CompileRegistry,
        CompileWatcher,
    )

    params = gpt2.init_params(jax.random.PRNGKey(0), CFG)
    adv_cfg = AdversaryConfig(
        target=2, seed=5,
        flag_rate_quarantine=0.25, safety_margin=0.08,
        initial_strength=0.3, step_up=0.1, backoff=0.5,
        min_strength=0.05, max_strength=1.0,
        signal_scale=40.0, signal_jitter=0.0,
        vocab_size=CFG.vocab_size,
    )
    attacker = AdaptivePoisonAttacker(adv_cfg)
    plan = FaultPlan.scripted([FaultEvent(
        step=1, kind=FaultKind.REPLICA_ADAPTIVE_POISON, target=2,
    )])
    inj = FaultInjector(plan, adversary=attacker)
    ledger = AttributionLedger(None)
    trace = RecordingTrace()
    compiles = CompileRegistry().install()
    try:
        watcher = CompileWatcher(compiles)
        fleet = ServingFleet(
            params, CFG,
            fleet_config=FleetConfig(
                num_replicas=3, max_retries=6,
                flag_window=16, flag_min_count=4,
                flag_rate_quarantine=0.25,
                suspicion_threshold=0.1, suspicion_min_flags=2,
                vote_k=2, vote_outvote_limit=2,
                quarantine_cooloff_ticks=10_000,  # past the horizon
            ),
            chaos=inj, ledger=ledger,
            # 6 slots/replica: per-slot quarantine exhaustion would need
            # 6 flags — the attacker's controller never banks that many
            # in-window, so the ONLY way it falls is the vote verdict.
            # queue_limit 4 keeps per-engine queues BOUNDED: once the
            # healthy replicas backpressure, the router walks to the
            # degraded suspect — which therefore keeps serving (and
            # keeps being auditable) exactly like a loaded production
            # fleet, instead of starving behind the healthy-first sort.
            max_slots=6, max_seq=48, queue_limit=4,
            # Margin-signature monitor: flags are a deterministic
            # function of attacker strength (jitter 0), so the recorded
            # flag sequence replays the controller exactly.
            monitor=MarginSignatureMonitor(20.0),
            compilewatch=watcher,
        )
        fleet.trace = trace
        rng = np.random.default_rng(1)
        prepared = deque()
        for _ in range(150):
            plen = int(rng.integers(3, 10))
            new = int(rng.integers(4, 10))
            prepared.append(
                (rng.integers(0, CFG.vocab_size, plen).tolist(),
                 int(new)))
        reqs = {}
        # Closed-loop seeded traffic: hold ~30 requests in flight —
        # above the two healthy replicas' bounded capacity, so the
        # suspect keeps receiving work — until the verdict lands (or
        # the prepared stream runs out, failing the quarantine
        # assertions below loudly).  Backpressured submissions retry
        # on later ticks.
        for _ in range(4000):
            if fleet.replicas[2].state is ReplicaState.QUARANTINED:
                break
            while prepared and sum(
                    1 for r in fleet.requests.values()
                    if not r.done) < 30:
                prompt, new = prepared.popleft()
                fid = fleet.submit(ServeRequest(prompt=prompt,
                                                max_new_tokens=new))
                if fid is None:
                    prepared.appendleft((prompt, new))
                    break
                reqs[fid] = (prompt, new)
            fleet.step()
        results = fleet.run_until_idle(max_ticks=4000)

        # THE headline: the ladder alone never saw it...
        ladder_reasons = {e["reason"]
                          for e in trace.of("replica_transition")
                          if e["to_state"] == "draining"}
        assert "monitor_flag_rate" not in ladder_reasons
        assert "slot_quarantine_exhausted" not in ladder_reasons
        assert fleet.replicas[2].flag_rate < 0.25  # sub-threshold, held
        # ...voting caught it.
        assert ladder_reasons == {"verdict_outvoted"}
        assert fleet.states() == {0: "healthy", 1: "healthy",
                                  2: "quarantined"}

        # Counters == the extended predict_fleet, under its (enforced)
        # cool-off validity bound.
        predicted = plan.predict_fleet(vote_k=2, vote_outvote_limit=2,
                                       horizon=fleet.tick,
                                       cooloff_ticks=10_000)
        observed = {k: fleet.counters[k] for k in predicted}
        assert observed == predicted, (observed, predicted)

        # The attacker's trajectory is pinned: live controller ==
        # predictor replayed over the recorded flag observations, and
        # the final strength matches.
        flags = [f for f, _ in attacker.flag_observations]
        assert sum(flags) >= 2          # it DID flag — just sustained
        predicted_traj = predict_attacker_trajectory(adv_cfg, flags,
                                                     flag_window=16)
        assert attacker.strength_history == predicted_traj
        assert attacker.strength == predicted_traj[-1]

        # Every accepted request retired explicitly and completed.
        assert sorted(results) == sorted(reqs)
        assert all(r.status == "completed" for r in results.values())
        # Streams of UNAFFECTED requests are bit-identical to
        # generate(); every stream served by the compromised replica is
        # provably corrupted (the attack has a payload, not just
        # signals).
        corrupted = clean = 0
        for fid, (prompt, new) in reqs.items():
            ref = np.asarray(generate(
                params, CFG, jnp.asarray([prompt], jnp.int32), new,
                temperature=0.0,
            ))[0, len(prompt):].tolist()
            if results[fid].replica == 2:
                assert results[fid].tokens != ref, f"request {fid}"
                corrupted += 1
            else:
                assert results[fid].tokens == ref, f"request {fid}"
                clean += 1
        assert corrupted >= 2 and clean >= 2

        # Replay-path honesty: ballots are admitted:false vote_replay
        # records (2 per vote), exactly one admitted record per id, and
        # the ledger reconciles against every replica's block journal.
        records = ledger.records()
        replays = [r for r in records if r.get("status") == "vote_replay"]
        assert len(replays) == 2 * fleet.counters["votes"]
        assert not any(r["admitted"] for r in replays)
        admitted = [r for r in records if r.get("admitted")]
        assert sorted(r["request_id"] for r in admitted) == sorted(reqs)
        ok, problems = fleet.verify_attribution()
        assert ok, problems

        # Suspicion surfaced as a typed episode, and the verdict votes
        # as outcome-labelled events.
        assert [e["replica"] for e in trace.of("fleet_suspicion")
                if e["reason"] == "flag_rate"] == [2]
        outvoted = [e for e in trace.of("verdict_vote")
                    if e["outcome"] == "outvoted"]
        assert len(outvoted) == 2

        # Zero storms: block churn, vote replays and the quarantine
        # never recompiled a decode program.
        assert watcher.storm_total == 0
    finally:
        compiles.uninstall()
