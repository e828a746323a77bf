"""Trust-aware serving fleet: N engine replicas behind one ``submit()``.

One ``ServingEngine`` is one failure domain — a wedged, preempted or
poisoned replica takes "heavy traffic from millions of users" down with
it.  ``ServingFleet`` is the robustness layer ROADMAP item 4 calls for,
reusing the training trust stack at REPLICA granularity:

* **Replica lifecycle state machine** — ``healthy → degraded →
  draining → quarantined → restarting`` — driven by the obs signals the
  engines already produce (anomaly-watcher episodes, SLO burn, output-
  monitor flag rate, missed-tick heartbeat), not new instrumentation.
  A replica whose monitor flag-rate crosses the quarantine threshold is
  DRAINED (no new admissions; existing slots run out or migrate) and
  QUARANTINED with a cool-off readmission probe — mirroring the
  training-side ``elastic/`` evict → probation → readmit ladder, where
  re-entry is earned by clean behaviour, not granted by time alone
  (a still-poisoned replica re-flags during its probe and goes straight
  back, with a doubled cool-off).
* **Request fail-over** — a request on a crashed/stalled/draining
  replica is resubmitted to a healthy one with bounded retries and
  exponential backoff, inheriting its ORIGINAL submission age
  (``ServeRequest.first_submit_id``) so sustained pressure cannot
  starve retries via the shed tie-break.  Requests near their deadline
  can launch a **hedged duplicate** on a second replica; dedup-at-
  retire keeps exactly ONE canonical stream per fleet request id — the
  first completed attempt wins, losers are cancelled and recorded
  ``admitted: false, status: "hedge_lost"``.
* **Fleet chaos** — the seeded ``chaos.FaultPlan`` REPLICA_* kinds
  (crash / stall / poison / slow-start) drive drills whose exact
  fail-over/drain/quarantine counts are pinned by
  ``FaultPlan.predict_fleet()``; every attempt is replayed with the
  request's own rng key, so a survivor's stream is bit-identical to a
  single-engine ``generate()`` run no matter how many replicas it
  crossed.

Time: the fleet is a synchronous tick loop (``step()`` = one fleet
tick: chaos hooks → step each live replica → process retirements →
supervise lifecycles → retries/hedges).  Backoff, heartbeats, drains,
cool-offs and restarts are all measured in TICKS so drills are
deterministic; request deadlines stay wall-clock (they are the user's
contract, not the scheduler's).

Attribution: each engine runs ledger-less; the FLEET writes one
canonical record per request at final retirement, carrying an
``attempts`` list (placement + journal key per attempt) so one record's
blocks can span two replicas' allocators and still reconcile —
``verify_attribution`` checks each attempt against its replica
GENERATION's lifecycle journal (``self.journals``; a crashed replica's
journal is retained like a flight recording, its successor's fresh pool
is a new generation).

Streaming caveat: ``on_token`` fires for the PRIMARY attempt's tokens
as they are produced — after a fail-over the new attempt re-streams
from token 0, and a winning hedge's tokens may never have streamed
(at-least-once streaming; exactly-once is the retired result/record).

Adversarial tier (README §Fleet/"Adversarial scenarios"): below the
flag-rate quarantine threshold sits a **suspicion** tier — an
EWMA-smoothed score over monitor verdicts (plus anomaly-watcher
episodes and explicit :meth:`ServingFleet.note_suspicion` boosts for
attribution irregularities) that emits ``fleet_suspicion`` events and
the ``tddl_fleet_suspicion{replica=}`` gauge even with voting disabled.
With ``FleetConfig.vote_k >= 1``, a completed request retiring on a
suspected (but sub-threshold) replica triggers a **cross-replica
verdict vote**: the request is replayed on K other replicas (replay is
bit-identical by construction — every attempt reuses the request's own
rng key) and the streams are majority-voted token-for-token via the
attribution ``token_hash``, without retaining full streams.  A replica
whose stream is outvoted (a >= 2-strong majority of replays agree with
each other AND against it) ``vote_outvote_limit`` times enters the
existing drain → quarantine ladder — an adaptive attacker holding its
flag rate just under ``flag_rate_quarantine`` is caught by
*disagreement* instead of flag rate.  A lone faulty voter can never
quarantine a clean replica: outvoting requires two agreeing dissenting
ballots, so a single lying replay only earns ITSELF suspicion.  Vote
replays never stream to the user, never publish their prompt blocks to
the replica's PrefixCache (``publish_prefix=False``), and are ledgered
``admitted: false, status: "vote_replay"`` — exactly one admitted
record per fleet id still holds.

Control plane (README §Fleet/"Control plane", serve/control.py): the
closed loop ROADMAP item 4 calls for, every piece opt-in via
``FleetConfig`` so the PR 8 fleet is unchanged by default.  (1) An
**autoscaler** drives the replica count between ``min_replicas`` and
``max_replicas`` from queue depth per replica, pool occupancy, the
fleet-wide ITL p99 and SLO burn — the FLEET aggregates, not the
last-writer per-engine gauges — plus a predictive arm that anticipates
the workload generator's seeded diurnal envelope.  Hysteresis is a
threshold band + per-direction cool-downs + a sustained-idle streak;
scale-up builds a replica through the existing HBM headroom gate and
warms through RESTARTING; scale-down always DRAINS (queue migrates,
in-flight runs out — never force-migrated, never killed) into the new
RETIRED state, whose journal is retained and whose index the next
scale-up revives as a fresh generation.  (2) **Per-tenant token-bucket
admission**: a submission costs prompt + max_new tokens against its
tenant's bucket (refilled per TICK — deterministic drills); a flooding
tenant throttles ITSELF, loudly (``tenant_throttle`` events +
``tddl_fleet_tenant_throttled_total{tenant=}``), while untagged
traffic is exempt.  (3) **SLO-class weighted-fair scheduling**:
submissions queue at the fleet in per-class deficit-round-robin queues
(token-cost fairness) and dispatch to engines each tick; under a
per-class TTFT/ITL breach the LOWEST class sheds first — replacing the
raw lowest-priority shed.  Overload is drillable like crash or poison:
``FaultKind.TENANT_FLOOD`` bursts a tenant through the real admission
path, and ``FaultPlan.predict_fleet(autoscale=, quota_tokens=,
flood_request_tokens=)`` pins the exact throttle and scale-up/-down
counts.
"""

from __future__ import annotations

import dataclasses
import enum
import logging
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Set, Tuple

import jax

from trustworthy_dl_tpu.obs import attribution
from trustworthy_dl_tpu.obs.events import EventType
from trustworthy_dl_tpu.obs.registry import get_registry
from trustworthy_dl_tpu.serve.engine import ServeRequest, ServeResult, \
    ServingEngine

logger = logging.getLogger(__name__)


class ReplicaState(str, enum.Enum):
    """The replica lifecycle ladder (README §Fleet carries the
    transition table)."""

    HEALTHY = "healthy"          # admitting + serving
    DEGRADED = "degraded"        # admitting, under suspicion
    DRAINING = "draining"        # no admissions; slots run out or migrate
    QUARANTINED = "quarantined"  # out of service, cool-off running
    RESTARTING = "restarting"    # warming up (restart/probe/slow-start)
    RETIRED = "retired"          # scaled in (autoscaler); pool released,
    #                              journal retained, index reusable


#: States the router may place new work on.
ADMITTING = (ReplicaState.HEALTHY, ReplicaState.DEGRADED)

#: Statuses that end a fleet request (everything else is an attempt
#: outcome the fleet recovers from).
TERMINAL_STATUSES = ("completed", "deadline_exceeded", "shed_slo",
                     "no_capacity", "failover_exhausted")


@dataclasses.dataclass
class FleetConfig:
    """Host-side fleet knobs.  Tick-denominated fields follow the fleet
    clock (one ``step()`` = one tick), never wall time — drills must be
    seed-deterministic."""

    num_replicas: int = 2
    # -- trust: output-monitor flag rate over a sliding retirement window
    flag_window: int = 16          # retirements per replica remembered
    flag_min_count: int = 2        # flags before the rate can trip
    flag_rate_quarantine: float = 0.25  # drain+quarantine at/above this
    # -- heartbeat (missed fleet ticks without replica progress)
    heartbeat_miss_degraded: int = 2
    heartbeat_miss_limit: int = 4  # drain + fail-over at/above this
    # -- fail-over
    max_retries: int = 3           # resubmissions per request (all causes)
    backoff_base_ticks: int = 1    # retry n waits base * mult**(n-1)
    backoff_mult: float = 2.0
    # -- hedging (None = off): duplicate a request still unfinished when
    # its remaining deadline drops below this
    hedge_deadline_s: Optional[float] = None
    # -- lifecycle timing (ticks)
    restart_ticks: int = 2         # warmup after restart / probe re-entry
    quarantine_cooloff_ticks: int = 32  # first cool-off (doubles each trip)
    drain_grace_ticks: int = 8     # in-flight allowed this long to run out
    # -- per-replica watcher attachment (SLO/anomaly watchers as extra
    # degraded-signals; host-only, no registry gauges per replica)
    attach_watchers: bool = False
    # -- suspicion tier BELOW the quarantine threshold: an EWMA over
    # monitor verdicts (1 = flagged) per slot-side retirement.  A
    # replica is SUSPECTED once the score crosses suspicion_threshold
    # and it has accumulated suspicion_min_flags lifetime flags this
    # generation (or an explicit note_suspicion boost) — sustained
    # sub-threshold flagging, not one unlucky request.  Suspicion emits
    # fleet_suspicion + the tddl_fleet_suspicion{replica=} gauge even
    # with voting off.
    suspicion_ewma_alpha: float = 0.2
    suspicion_threshold: float = 0.1
    suspicion_min_flags: int = 2
    # -- cross-replica verdict voting (0 = off): replay a suspected
    # replica's completed requests on vote_k other replicas and
    # majority-vote the streams token-for-token by token_hash.  One
    # vote in flight per suspect, launched quorum-or-nothing;
    # vote_outvote_limit outvotes send the replica down the drain ->
    # quarantine ladder.  vote_k >= 2 is needed for any verdict (a
    # lone ballot can never form a majority, so clean replicas are
    # safe from a single faulty voter by construction; vote_k == 1
    # votes resolve "inconclusive").
    vote_k: int = 0
    vote_outvote_limit: int = 2
    # -- control plane (serve/control.py; ALL opt-in — the defaults
    # leave the PR 8 fleet byte-for-byte unchanged) --
    #: SLO classes (tuple of control.SLOClass): submissions queue at the
    #: FLEET in per-class deficit-round-robin queues and dispatch to
    #: engines by token-weighted fairness; under a per-class latency
    #: breach the LOWEST class sheds first.  None = legacy direct
    #: submit (requests go straight to a replica).
    slo_classes: Optional[Tuple[Any, ...]] = None
    class_queue_limit: int = 256       # per-class fleet queue bound
    drr_quantum_tokens: int = 32       # DRR quantum (tokens per round)
    class_latency_min_count: int = 8   # observations before a breach
    #: Per-tenant token-bucket admission (control.TenantQuotaConfig):
    #: a submission costs prompt + max_new tokens against its tenant's
    #: bucket; over-budget submissions are throttled loudly.  None =
    #: no quotas.  Requests with tenant=None bypass quota (untagged
    #: traffic is the operator's own).
    tenant_quota: Optional[Any] = None
    #: Per-ADAPTER token-bucket admission (control.TenantQuotaConfig,
    #: keyed by adapter id): one tenant's fine-tune must not starve the
    #: base-model traffic or another tenant's adapter — a submission
    #: resolving to an adapter spends against BOTH its tenant bucket and
    #: its adapter bucket.  None = no adapter quotas.  Requests that
    #: resolve to no adapter bypass this bucket entirely.
    adapter_quota: Optional[Any] = None
    #: Autoscaler (control.AutoscalerConfig): drives the replica count
    #: between min/max from queue depth, occupancy, ITL-p99, SLO burn
    #: and the predictive arm, with hysteresis + cool-downs.  Scale-up
    #: builds a replica through the existing HBM headroom gate;
    #: scale-down always DRAINS (queue migrates, in-flight runs out).
    #: None = static fleet.
    autoscale: Optional[Any] = None
    #: TENANT_FLOOD request shape: each flood submission is
    #: prompt [0] * flood_prompt_len, max_new = flood_new_tokens, so a
    #: flood request costs flood_prompt_len + flood_new_tokens bucket
    #: tokens (predict_fleet's flood_request_tokens).
    flood_prompt_len: int = 4
    flood_new_tokens: int = 4
    #: Disaggregated prefill/decode pools (None = unified fleet,
    #: byte-identical to the defaults): one role per INITIAL replica
    #: index, each "prefill" or "decode", at least one of each.  New
    #: submissions route to prefill-specialist replicas; once a request
    #: emits its first decode token the per-tick rebalance sweep moves
    #: it to a decode-specialist as a LIVE block-table migration
    #: (serve/migrate.py) — prefill capacity is never held hostage by
    #: long decodes, and the autoscaler (when configured) scales each
    #: pool INDEPENDENTLY from its own pool-local signals.
    pool_roles: Optional[Tuple[str, ...]] = None
    #: Operator escape hatch: ``False``
    #: restores the pre-migration arcs everywhere — drains run out or
    #: replay, preemptions replay, disaggregated rebalance is inert —
    #: without touching any other knob.
    live_migration: bool = True
    #: Tensor-parallel width of every INITIAL replica: each engine owns
    #: a tp_size-device submesh over the 'model' axis and its weights
    #: carry the registry-declared TP layout (core/sharding.py), so the
    #: fleet's capacity is a replicas × model-shards grid.  1 (default)
    #: is the single-chip fleet, byte-for-byte.
    tp_size: int = 1
    #: Scale-UP headroom: the autoscaler may grow a replica's TP group
    #: up to this width (control.choose_scale_action — occupancy-driven
    #: pressure doubles the group; queue-driven pressure adds replicas).
    #: 0 (default) pins tp_max = tp_size: no scale-up dimension, the
    #: pre-TP autoscaler byte-for-byte.
    tp_max: int = 0

    def __post_init__(self) -> None:
        if self.tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {self.tp_size}")
        if self.tp_max and self.tp_max < self.tp_size:
            raise ValueError(
                f"tp_max={self.tp_max} must be 0 (= tp_size) or >= "
                f"tp_size={self.tp_size}")
        if self.num_replicas < 1:
            raise ValueError("num_replicas must be >= 1")
        if not 0.0 < self.flag_rate_quarantine <= 1.0:
            raise ValueError("flag_rate_quarantine must be in (0, 1]")
        if self.flag_min_count < 1 or self.flag_window < self.flag_min_count:
            raise ValueError("need 1 <= flag_min_count <= flag_window")
        if self.heartbeat_miss_limit < self.heartbeat_miss_degraded:
            raise ValueError("heartbeat_miss_limit must be >= "
                             "heartbeat_miss_degraded")
        if self.max_retries < 0 or self.backoff_base_ticks < 0:
            raise ValueError("max_retries/backoff_base_ticks must be >= 0")
        if self.backoff_mult < 1.0:
            raise ValueError("backoff_mult must be >= 1")
        if not 0.0 < self.suspicion_ewma_alpha <= 1.0:
            raise ValueError("suspicion_ewma_alpha must be in (0, 1]")
        if not 0.0 < self.suspicion_threshold < 1.0:
            raise ValueError("suspicion_threshold must be in (0, 1)")
        if self.suspicion_min_flags < 1:
            raise ValueError("suspicion_min_flags must be >= 1")
        if self.vote_k < 0 or self.vote_outvote_limit < 1:
            raise ValueError("vote_k must be >= 0 and "
                             "vote_outvote_limit >= 1")
        if self.class_queue_limit < 1 or self.drr_quantum_tokens < 1 \
                or self.class_latency_min_count < 1:
            raise ValueError("class_queue_limit, drr_quantum_tokens and "
                             "class_latency_min_count must be >= 1")
        if self.flood_prompt_len < 1 or self.flood_new_tokens < 1:
            raise ValueError("flood_prompt_len and flood_new_tokens "
                             "must be >= 1")
        if self.autoscale is not None and not (
                self.autoscale.min_replicas <= self.num_replicas
                <= self.autoscale.max_replicas):
            raise ValueError(
                f"num_replicas={self.num_replicas} must start inside "
                f"the autoscale bounds [{self.autoscale.min_replicas}, "
                f"{self.autoscale.max_replicas}]")
        if self.pool_roles is not None:
            roles = tuple(self.pool_roles)
            if len(roles) != self.num_replicas:
                raise ValueError(
                    f"pool_roles needs one role per replica: got "
                    f"{len(roles)} for num_replicas={self.num_replicas}")
            bad = sorted(set(roles) - {"prefill", "decode"})
            if bad:
                raise ValueError(f"pool_roles must be 'prefill' or "
                                 f"'decode', got {bad}")
            if not ({"prefill", "decode"} <= set(roles)):
                raise ValueError("pool_roles needs at least one prefill "
                                 "AND one decode replica")


def backoff_ticks(cfg: FleetConfig, attempt: int) -> int:
    """Ticks resubmission number ``attempt`` (1-based) waits:
    ``base * mult**(attempt-1)``, floored at the base."""
    if attempt < 1:
        raise ValueError("attempt is 1-based")
    return int(cfg.backoff_base_ticks * cfg.backoff_mult ** (attempt - 1))


@dataclasses.dataclass
class FleetResult:
    """Terminal record of one fleet request (the canonical stream)."""

    request_id: int                # fleet id
    tokens: List[int]
    status: str                    # see TERMINAL_STATUSES
    replica: Optional[int]         # replica that produced the stream
    attempts: int                  # submissions it took (1 = no fail-over)
    ttft_s: Optional[float]        # FIRST fleet submit -> first token
    flagged: bool = False
    monitor_z: float = 0.0
    tenant: Optional[str] = None   # end-to-end tenant identity
    slo_class: Optional[str] = None  # class it was scheduled under
    adapter: Optional[str] = None  # adapter the stream was served under


@dataclasses.dataclass
class _Attempt:
    replica: int
    gen: int
    local_id: int
    submit_t: float
    span: Optional[int] = None     # fleet.attempt span id
    loser: bool = False            # cancelled as hedge/dedup loser


@dataclasses.dataclass
class _Vote:
    """One in-flight cross-replica verdict vote (one per suspect at a
    time).  ``ballots`` maps voter replica -> replay token_hash
    (None = abstained: the replay failed, was cancelled, or its replica
    crashed); the vote resolves once ``pending`` empties."""

    fid: int
    target: int                    # the suspected replica under audit
    original_hash: str             # the canonical stream's token_hash
    ballots: Dict[int, Optional[str]] = dataclasses.field(
        default_factory=dict)
    pending: Set[int] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class _FleetRequest:
    fid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    priority: int
    rng: Any                       # resolved key — EVERY attempt reuses it
    on_token: Optional[Callable[[int, int], None]]
    deadline_at: Optional[float]   # absolute perf_counter deadline
    submit_t: float = 0.0
    live: Dict[int, _Attempt] = dataclasses.field(default_factory=dict)
    closed: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    submissions: int = 0
    retry_due: Optional[int] = None   # tick a pending resubmit is due
    excluded: Set[int] = dataclasses.field(default_factory=set)
    hedged: bool = False
    done: bool = False
    span_root: Optional[int] = None
    tenant: Optional[str] = None
    slo_class: Optional[str] = None
    adapter: Optional[str] = None  # fleet-resolved adapter id (explicit
    #                              # request.adapter, else adapter_map)
    cost: int = 0                  # prompt + max_new (bucket/DRR tokens)


class _Replica:
    """One replica's supervision state (host-only)."""

    def __init__(self, index: int, engine: Any, flag_window: int):
        self.index = index
        self.engine = engine
        self.gen = 0
        self.role = "mixed"         # pool role; "mixed" = unified fleet
        self.tp = 1                 # tensor-parallel group width
        self.state = ReplicaState.HEALTHY
        self.last_progress_tick = 0
        self.stalled_until = -1     # chaos wedge: step() suspended until
        self.warm_until = -1        # RESTARTING exits at this tick
        self.cooloff_until = -1     # QUARANTINED exits at this tick
        self.cooloff_ticks = 0      # current cool-off length (doubles)
        self.drain_deadline = -1
        self.quarantine_pending = False
        self.retire_pending = False  # scale-down drain: retire at empty
        self.reason = ""
        self.flags: Deque[int] = deque(maxlen=flag_window)
        # -- suspicion tier (EWMA over verdicts + explicit boosts) --
        self.suspicion = 0.0
        self.total_flags = 0        # lifetime flags this generation
        self.suspicion_noted = False  # note_suspicion() boost received
        self.suspicion_episode = False  # currently suspected (hysteresis)
        # -- verdict voting --
        self.outvotes = 0
        self.vote_open = False      # one vote in flight per suspect

    def reset_trust_window(self) -> None:
        """Fresh trust evidence for a fresh generation (rebuild /
        readmission probe): the window, the suspicion score and the
        outvote tally all start over — re-conviction must come from new
        behaviour, not stale history."""
        self.flags.clear()
        self.suspicion = 0.0
        self.total_flags = 0
        self.suspicion_noted = False
        self.suspicion_episode = False
        self.outvotes = 0
        self.vote_open = False

    @property
    def journal_key(self) -> str:
        return f"{self.index}:{self.gen}"

    @property
    def flag_count(self) -> int:
        return sum(self.flags)

    @property
    def flag_rate(self) -> float:
        return self.flag_count / len(self.flags) if self.flags else 0.0

    def ladder_tripped(self, cfg: "FleetConfig") -> bool:
        """ONE spelling of the flag-rate trip predicate (shared by the
        supervision pass and the vote tier's ladder-ownership guard)."""
        return (self.flag_count >= cfg.flag_min_count
                and self.flag_rate >= cfg.flag_rate_quarantine)


class ServingFleet:
    """N ``ServingEngine`` replicas behind one ``submit()`` surface with
    replica supervision, fail-over and trust-aware routing (module
    docstring).  ``engine_kwargs`` pass through to every engine build
    (max_slots, max_seq, kv_dtype, paged geometry, ...); ``chaos`` is a
    ``chaos.FaultInjector`` whose REPLICA_* events this loop executes.
    ``engine_factory(replica_index, **kwargs)`` is the test seam — it
    must honour the ``replica_id``/``retire_hook``/``monitor`` kwargs
    the fleet threads through."""

    def __init__(self, params: Any = None, cfg: Any = None, *,
                 fleet_config: Optional[FleetConfig] = None,
                 num_replicas: Optional[int] = None,
                 chaos: Any = None, trace: Any = None, registry: Any = None,
                 spans: Any = None, ledger: Any = None,
                 rng: Optional[jax.Array] = None,
                 engine_factory: Optional[Callable[..., Any]] = None,
                 slo_rules: Any = None,
                 forensics: Any = None,
                 **engine_kwargs: Any):
        self.config = fleet_config or FleetConfig(
            num_replicas=num_replicas or 2)
        if num_replicas is not None:
            self.config = dataclasses.replace(self.config,
                                              num_replicas=num_replicas)
        self.chaos = chaos
        self.trace = trace
        self.spans = spans
        self.ledger = ledger
        # Forensics (obs/forensics.py): quarantines, adapter impounds,
        # preemptions and full-walk migration refusals each assemble an
        # incident; the assembler's VerdictStore (when it has one) gets
        # the durable suspicion/vote/quarantine history rows.
        self.forensics = forensics
        self.verdicts = getattr(forensics, "verdicts", None) \
            if forensics is not None else None
        #: Per-destination refusals of the LAST failed _live_migrate
        #: walk (diagnostics + the migration_refused incident payload).
        self._last_migration_refusals: List[Dict[str, Any]] = []
        self._params = params
        self._cfg = cfg
        self._engine_kwargs = dict(engine_kwargs)
        # Tensor-parallel replica width: FleetConfig.tp_size governs;
        # a tp_size riding engine_kwargs (from_config passes the
        # ServeConfig knob through) seeds it when the fleet config
        # leaves the default.  Per-replica widths can then diverge via
        # scale-UP, so the knob is popped here and threaded per build.
        self._base_tp = max(
            int(self._engine_kwargs.pop("tp_size", 1) or 1),
            self.config.tp_size)
        # Per-replica SLO rules (None + attach_watchers=False = no
        # watchers).  Watchers are built per REPLICA, not per fleet —
        # a breach is a replica-local signal (one slow replica must not
        # shed the whole fleet's admissions) and feeds that replica's
        # ``watcher_bad`` degraded signal.
        self._slo_rules = slo_rules
        self._factory = engine_factory or self._default_factory
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        if registry is None:
            registry = get_registry()
        self.registry = registry
        self._replicas_gauge = registry.gauge(
            "tddl_fleet_replicas", "Replicas per lifecycle state",
            labels=("state",),
        )
        self._failover_counter = registry.counter(
            "tddl_fleet_failovers_total",
            "Requests resubmitted after a replica failure/drain",
        )
        self._hedge_counter = registry.counter(
            "tddl_fleet_hedges_total",
            "Hedged duplicates launched for deadline-pressed requests",
        )
        self._transition_counter = registry.counter(
            "tddl_fleet_transitions_total",
            "Replica lifecycle transitions, by destination state",
            labels=("to_state",),
        )
        # Adversarial tier: the sub-threshold suspicion score per
        # replica (an adversary holding its flag rate under the
        # quarantine threshold still moves THIS gauge), suspicion
        # episodes, and verdict votes by outcome.
        self._suspicion_gauge = registry.gauge(
            "tddl_fleet_suspicion",
            "EWMA suspicion score per replica (sub-threshold tier)",
            labels=("replica",),
        )
        self._suspicion_counter = registry.counter(
            "tddl_fleet_suspicions_total",
            "Suspicion episodes opened (score crossed the threshold)",
        )
        self._vote_counter = registry.counter(
            "tddl_fleet_votes_total",
            "Cross-replica verdict votes resolved, by outcome",
            labels=("outcome",),
        )
        # Fleet-wide occupancy aggregates, refreshed every tick.  The
        # ENGINE serve gauges (tddl_serve_blocks_in_use, ...) carry a
        # ``replica=`` label in fleet mode (the fleet threads
        # replica_id into every engine build), so per-replica
        # occupancy/blocks/tokens are individually readable; THESE
        # aggregates remain the deployment-level sums an autoscaler
        # reads without summing label sets itself.
        self._tif_gauge = registry.gauge(
            "tddl_fleet_tokens_in_flight",
            "Cached tokens backing live sequences, summed over replicas",
        )
        self._queue_gauge = registry.gauge(
            "tddl_fleet_queue_depth",
            "Queued + in-flight requests, summed over live replicas",
        )
        # Control plane (serve/control.py): throttles by tenant, scale
        # events by direction, per-class fleet-queue depth.
        self._throttle_counter = registry.counter(
            "tddl_fleet_tenant_throttled_total",
            "Submissions throttled by the per-tenant token bucket",
            labels=("tenant",),
        )
        self._adapter_throttle_counter = registry.counter(
            "tddl_fleet_adapter_throttled_total",
            "Submissions throttled by the per-adapter token bucket",
            labels=("adapter",),
        )
        self._scale_counter = registry.counter(
            "tddl_fleet_scale_events_total",
            "Autoscaler replica-count changes, by direction",
            labels=("direction",),
        )
        # Live migration tier (serve/migrate.py): in-flight requests
        # moved between replicas as block copies, by the capacity-loss
        # reason that moved them; replicas per pool role when the
        # disaggregated prefill/decode split is on.
        self._migration_counter = registry.counter(
            "tddl_fleet_migrations_total",
            "In-flight requests live-migrated as KV block copies",
            labels=("reason",),
        )
        self._pool_gauge = registry.gauge(
            "tddl_fleet_pool_replicas",
            "In-service replicas per disaggregated pool role",
            labels=("role",),
        )
        self._chips_gauge = registry.gauge(
            "tddl_fleet_chips",
            "Devices occupied: in-service replicas weighted by their "
            "tensor-parallel group width",
        )
        self._classq_gauge = registry.gauge(
            "tddl_fleet_class_queue_depth",
            "Fleet admission-queue depth, by SLO class",
            labels=("slo_class",),
        )
        self.tick = 0
        self._next_fid = 0
        self.rejected = 0
        self._max_seq: Optional[int] = None
        self.requests: Dict[int, _FleetRequest] = {}
        # Fid whose terminal is mid-processing: an adapter conviction
        # fired from inside its own retirement must not usurp it.
        self._terminal_fid: Optional[int] = None
        self.results: Dict[int, FleetResult] = {}
        self._local2fleet: Dict[Tuple[int, int], int] = {}
        self._terminal: Deque[Tuple[int, ServeResult, Optional[dict]]] = \
            deque()
        #: journal key ("replica:gen") -> BlockAllocator — RETAINED
        #: across restarts so records naming a dead generation's blocks
        #: still reconcile (the post-mortem journal, not the live pool).
        #: RETIRED (scaled-in) generations keep theirs the same way.
        self.journals: Dict[str, Any] = {}
        # Drill-facing recovery counters (diffed against predict_fleet).
        self.counters: Dict[str, int] = {
            "crashes": 0, "restarts": 0, "stalls": 0, "poisons": 0,
            "adaptive_poisons": 0, "slowstarts": 0,
            "failover_episodes": 0, "drains": 0,
            "quarantines": 0, "readmissions": 0, "failovers": 0,
            "hedges": 0, "hedge_lost": 0,
            "suspicions": 0, "votes": 0, "outvotes": 0,
            "tenant_floods": 0, "throttles": 0,
            "scale_ups": 0, "scale_downs": 0, "tp_scale_ups": 0,
            "adapter_poisons": 0, "adapter_quarantines": 0,
            "adapter_throttles": 0,
            "preempts": 0, "migrations": 0,
        }
        # Verdict-vote working state: (voter replica, engine-local id)
        # -> the vote its replay ballots into.  Vote replays never enter
        # _local2fleet — they are audits, not fleet requests.
        self._vote_ballots: Dict[Tuple[int, int], _Vote] = {}
        # Deferred drain resubmissions; normally armed inside
        # _supervise, but a vote-triggered drain can queue moves from
        # terminal processing too, so the list outlives one pass.
        self._drain_moves: List[Tuple[int, int, str]] = []
        # -- control plane (serve/control.py; every piece opt-in) --
        from trustworthy_dl_tpu.serve.control import (
            Autoscaler,
            ClassLatencyTracker,
            ClassQueues,
            TenantBuckets,
            class_for_priority,
        )

        self._class_for_priority = class_for_priority
        cfg = self.config
        self._classes = tuple(cfg.slo_classes) if cfg.slo_classes else None
        self._classq = None
        self._class_latency = None
        self._class_stats: Dict[str, Dict[str, int]] = {}
        if self._classes:
            self._classq = ClassQueues(
                self._classes, quantum_tokens=cfg.drr_quantum_tokens,
                per_class_limit=cfg.class_queue_limit)
            self._class_latency = ClassLatencyTracker(
                self._classes, min_count=cfg.class_latency_min_count)
            self._class_stats = {
                c.name: {"completed": 0, "tokens": 0, "shed": 0}
                for c in self._classes}
        self._buckets = (TenantBuckets(cfg.tenant_quota)
                         if cfg.tenant_quota is not None else None)
        # -- adapter trust plane (serve/adapters.py) --
        # The SAME TenantBuckets machinery, keyed by ADAPTER id: QoS
        # follows the artifact being served, not just who asked.
        self._adapter_buckets = (TenantBuckets(cfg.adapter_quota)
                                 if cfg.adapter_quota is not None else None)
        #: Fleet-resolved tenant -> adapter assignments, mirroring the
        #: engines' own map (engine_kwargs["adapter_map"]) so submit()
        #: can police quarantines/quotas BEFORE picking a replica.
        self._adapter_map: Dict[str, str] = dict(
            engine_kwargs.get("adapter_map") or {})
        #: Fleet-wide per-ADAPTER flag-rate windows.  An adapter is one
        #: artifact resident on MANY replicas: its evidence pools
        #: fleet-wide (same window/thresholds as the replica ladder) and
        #: a trip quarantines the ADAPTER everywhere while the replicas
        #: that served it stay HEALTHY — trust follows attribution.
        self._adapter_flags: Dict[str, Deque[int]] = {}
        self.quarantined_adapters: Set[str] = set()
        #: Engine-side slot impounds whose flags were ADAPTER-attributed
        #: (adapter -> [(replica, gen, slot)]).  The engine impounds the
        #: slot at retire time without knowing fleet policy; once the
        #: fleet convicts the ADAPTER the evidence transfers to the
        #: artifact and the slots release — otherwise a poisoned adapter
        #: would exhaust a healthy replica's capacity and drag it down
        #: the drain ladder by attrition.
        self._adapter_impounds: Dict[str, List[Tuple[int, int, int]]] = {}
        self.autoscaler = (Autoscaler(cfg.autoscale)
                           if cfg.autoscale is not None else None)
        # -- disaggregated prefill/decode pools (opt-in) --
        self._roles_active = cfg.pool_roles is not None
        #: role -> Autoscaler: each pool's hysteresis/cool-down state is
        #: its own — a decode-pool scale-up must not eat the prefill
        #: pool's cool-down (and vice versa).  The shared AutoscalerConfig
        #: bounds apply PER POOL when roles are active.
        self._pool_scalers: Dict[str, Any] = {}
        if self._roles_active and cfg.autoscale is not None:
            self._pool_scalers = {
                role: Autoscaler(cfg.autoscale)
                for role in ("prefill", "decode")}
        # Fleet-wide completed-request ITL sketch: the autoscaler's
        # latency signal (per-class sketches serve the shed predicate).
        from trustworthy_dl_tpu.obs.slo import StreamingPercentiles

        self._itl_est = StreamingPercentiles()
        #: (tick, in-service replicas) on every change — the fleet's
        #: replica-count trace.  Bounded: a pathological flap cannot
        #: grow host memory without bound.
        self.replica_trace: List[Tuple[int, int]] = []
        self.replicas: List[_Replica] = []
        for i in range(self.config.num_replicas):
            self.replicas.append(self._build_replica(i))
        self._note_replica_trace()
        self._set_state_gauge()

    @classmethod
    def from_config(cls, params: Any, cfg: Any, serve_config: Any,
                    **kwargs: Any) -> "ServingFleet":
        """Build a fleet whose replicas all use a validated
        ``core.config.ServeConfig`` — ONE source of truth for the
        serving knobs, exactly like ``ServingEngine.from_config``
        (``kwargs`` pass through for the fleet surfaces: fleet_config,
        chaos, trace, ledger, ... and any extra engine kwargs)."""
        return cls(
            params, cfg,
            max_slots=serve_config.max_slots,
            max_seq=serve_config.max_seq,
            queue_limit=serve_config.queue_limit,
            kv_dtype=serve_config.kv_dtype,
            weight_dtype=serve_config.weight_dtype,
            block_size=serve_config.block_size,
            num_blocks=serve_config.num_blocks,
            prefix_cache=serve_config.prefix_cache,
            prefill_chunk=serve_config.prefill_chunk,
            # Speculative decoding inherits across replica RESTARTS too:
            # spec_k rides engine_kwargs, so the cool-off probe's
            # rebuilt engine drafts exactly like the one it replaces.
            spec_k=serve_config.spec_k,
            # Adapter knobs ride engine_kwargs the same way: a replica
            # rebuilt after a crash re-creates its pool with the exact
            # geometry (and deterministic weights) of the one it lost.
            adapter_rank=serve_config.adapter_rank,
            adapter_pool_pages=serve_config.adapter_pool_pages,
            adapter_dtype=serve_config.adapter_dtype,
            # TP width rides engine_kwargs too; the fleet pops it into
            # its per-replica width bookkeeping (scale-UP can diverge
            # individual replicas from this base).
            tp_size=serve_config.tp_size,
            **kwargs,
        )

    # -- replica construction ---------------------------------------------

    def _default_factory(self, index: int, **kwargs: Any) -> Any:
        return ServingEngine(self._params, self._cfg, **kwargs)

    def _tp_devices(self, index: int, tp: int) -> Optional[List[Any]]:
        """Carve replica ``index``'s TP device slice: contiguous groups
        of ``tp`` local devices when the host has enough for disjoint
        slices, else None (the engine defaults to the first ``tp``
        devices — simulation aliasing on small hosts; real deployments
        size the host to replicas × tp chips)."""
        devices = jax.devices()
        lo, hi = index * tp, (index + 1) * tp
        if hi <= len(devices):
            return list(devices[lo:hi])
        return None

    def _engine_build_kwargs(self, index: int,
                             tp: Optional[int] = None) -> Dict[str, Any]:
        kwargs = dict(self._engine_kwargs)
        tp = tp or self._base_tp
        if tp > 1:
            kwargs["tp_size"] = tp
            kwargs["tp_devices"] = self._tp_devices(index, tp)
        kwargs.setdefault("rng", jax.random.fold_in(self._rng, index))
        kwargs["replica_id"] = index
        kwargs["chaos"] = self.chaos
        kwargs["trace"] = self.trace
        kwargs["spans"] = self.spans
        kwargs["registry"] = self.registry
        kwargs["retire_hook"] = \
            lambda result, placement, _i=index: \
            self._terminal.append((_i, result, placement))
        if self.config.attach_watchers or self._slo_rules is not None:
            from trustworthy_dl_tpu.obs.anomaly import AnomalyWatcher
            from trustworthy_dl_tpu.obs.slo import SLOWatcher, \
                default_serve_rules

            # Host-only per-replica watchers (no registry: N replicas
            # would fight over one un-labelled gauge set).
            kwargs.setdefault("slo", SLOWatcher(
                self._slo_rules if self._slo_rules is not None
                else default_serve_rules()))
            kwargs.setdefault("anomaly", AnomalyWatcher())
        return kwargs

    def _build_replica(self, index: int,
                       prev: Optional[_Replica] = None,
                       role: Optional[str] = None,
                       tp: Optional[int] = None) -> _Replica:
        # TP width is sticky like the role: a rebuild/restart keeps the
        # width it had; only an explicit scale-UP changes it.
        if tp is None:
            tp = prev.tp if prev is not None and prev.tp > 1 \
                else self._base_tp
        engine = self._factory(index,
                               **self._engine_build_kwargs(index, tp))
        rep = prev if prev is not None else _Replica(
            index, engine, self.config.flag_window)
        rep.engine = engine
        rep.tp = tp
        # Pool role is a property of the INDEX (initial assignment) or
        # of the scale-up that created the replica — a rebuild/restart
        # keeps the role it had; chaos must not reshuffle the pools.
        if role is not None:
            rep.role = role
        elif prev is None and self._roles_active \
                and index < len(self.config.pool_roles):
            rep.role = self.config.pool_roles[index]
        rep.reset_trust_window()
        # A rebuilt replica must inherit the fleet's standing adapter
        # verdicts: the quarantine is against the ARTIFACT, and a crash
        # restart must not reopen a door the fleet already closed.
        for name in self.quarantined_adapters:
            if hasattr(engine, "quarantine_adapter"):
                engine.quarantine_adapter(name)
        self.journals[rep.journal_key] = self._engine_journal(engine)
        # Geometry limits for submit-time validation, captured ONCE so
        # impossible requests fail in submit() even when every engine is
        # momentarily down mid-chaos (all replicas share one geometry).
        sched = getattr(engine, "scheduler", None)
        if sched is not None and self._max_seq is None:
            self._max_seq = sched.max_seq
        return rep

    @staticmethod
    def _engine_journal(engine: Any) -> Any:
        return getattr(getattr(engine, "scheduler", None), "blocks", None)

    # -- submission --------------------------------------------------------

    def submit(self, request: ServeRequest) -> Optional[int]:
        """Enqueue one request; returns its FLEET id (engine-local ids
        are namespaced per replica and never surface).  Returns None —
        backpressure, exactly like the engine — when every admitting
        replica rejected it (queues full).  A transiently replica-less
        fleet (everything draining/restarting mid-chaos) instead PARKS
        the accepted request and resubmits as capacity returns: an
        accepted request is never silently dropped."""
        now = time.perf_counter()
        # Fail impossible requests HERE, with the engine's own submit
        # semantics — a parked request must never explode inside the
        # tick loop, and the record below must never be registered for
        # a request no replica could ever serve (an orphan would keep
        # ``busy`` True forever).
        prompt_len = len(list(request.prompt))
        if prompt_len == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self._max_seq is not None:
            total = prompt_len + int(request.max_new_tokens)
            if total > self._max_seq:
                raise ValueError(
                    f"prompt+new = {total} exceeds max_seq="
                    f"{self._max_seq}")
        cost = prompt_len + int(request.max_new_tokens)
        tenant = request.tenant
        # Resolve the adapter at the FLEET boundary (explicit wins, else
        # the tenant map), mirroring the engine's own resolution, so the
        # quarantine/quota verdicts land before any replica is picked.
        adapter = getattr(request, "adapter", None)
        if adapter is None and tenant is not None:
            adapter = self._adapter_map.get(tenant)
        if adapter is not None and adapter in self.quarantined_adapters:
            # Fleet-wide adapter quarantine: the refusal is loud and
            # replica-independent — every replica would refuse it too.
            logger.warning(
                "fleet: adapter %r is quarantined fleet-wide; "
                "submission for tenant %r refused", adapter, tenant)
            return None
        # Per-tenant token-bucket admission: the flooding tenant's own
        # bucket refuses the submission — loudly — before any fleet
        # state is touched.  Untagged traffic (tenant None) bypasses
        # quota: it is the operator's own.
        if self._buckets is not None and tenant is not None:
            if not self._buckets.try_spend(tenant, cost, self.tick):
                self.counters["throttles"] += 1
                self._throttle_counter.inc(tenant=tenant)
                level = self._buckets.level(tenant, self.tick)
                logger.warning(
                    "fleet: tenant %r throttled (%d tokens, bucket at "
                    "%.1f)", tenant, cost, level)
                if self.trace is not None:
                    self.trace.emit(EventType.TENANT_THROTTLE,
                                    tenant=tenant, tokens=cost,
                                    bucket_level=round(level, 2),
                                    tick=self.tick)
                return None
        # Per-ADAPTER bucket SECOND: a refusal here must hand back the
        # tenant spend above (a throttled submission does no work).
        if self._adapter_buckets is not None and adapter is not None:
            if not self._adapter_buckets.try_spend(adapter, cost,
                                                   self.tick):
                if self._buckets is not None and tenant is not None:
                    self._buckets.refund(tenant, cost, self.tick)
                self.counters["adapter_throttles"] += 1
                self._adapter_throttle_counter.inc(adapter=adapter)
                level = self._adapter_buckets.level(adapter, self.tick)
                logger.warning(
                    "fleet: adapter %r throttled (%d tokens, bucket at "
                    "%.1f)", adapter, cost, level)
                if self.trace is not None:
                    self.trace.emit(EventType.TENANT_THROTTLE,
                                    tenant=tenant, adapter=adapter,
                                    tokens=cost,
                                    bucket_level=round(level, 2),
                                    tick=self.tick)
                return None
        fid = self._next_fid
        self._next_fid += 1
        rng = request.rng
        if rng is None:
            # Resolved ONCE per fleet request: every attempt replays the
            # same key stream, so the stream is replica-independent.
            rng = jax.random.fold_in(self._rng, fid)
        rec = _FleetRequest(
            fid=fid, prompt=list(request.prompt),
            max_new_tokens=int(request.max_new_tokens),
            temperature=float(request.temperature), eos_id=request.eos_id,
            priority=int(request.priority), rng=rng,
            on_token=request.on_token,
            deadline_at=(now + request.deadline_s
                         if request.deadline_s is not None else None),
            submit_t=now,
            tenant=tenant, adapter=adapter, cost=cost,
        )
        if self._classes:
            rec.slo_class = self._class_for_priority(
                self._classes, rec.priority).name
        if self.spans is not None:
            rec.span_root = self.spans.start(
                "fleet.request", kind="serve", request_id=fid,
                prompt_len=len(rec.prompt),
                max_new_tokens=rec.max_new_tokens,
                tenant=tenant, slo_class=rec.slo_class)
        self.requests[fid] = rec
        if self._classq is not None:
            # Class-scheduled admission: the request queues at the
            # FLEET and the deficit-round-robin dispatcher places it —
            # token-weighted fairness across classes, not arrival order.
            if not self._classq.push(rec.slo_class, fid, cost):
                del self.requests[fid]
                self.rejected += 1
                self._refund_bucket(rec)
                if self.spans is not None and rec.span_root is not None:
                    self.spans.end(rec.span_root, status="rejected")
                return None
            return fid
        try:
            outcome = self._try_submit(rec)
        except Exception:
            # Never leave an orphaned record behind an engine-side
            # raise: unwind so ``busy`` reflects only servable work.
            del self.requests[fid]
            self._refund_bucket(rec)
            if self.spans is not None and rec.span_root is not None:
                self.spans.end(rec.span_root, status="error")
            raise
        if outcome == "full":
            # Real backpressure: admitting replicas exist and ALL shed.
            del self.requests[fid]
            self.rejected += 1
            self._refund_bucket(rec)
            if self.spans is not None and rec.span_root is not None:
                self.spans.end(rec.span_root, status="rejected")
            return None
        if outcome == "none_admitting":
            # Transient chaos hole: park; the tick loop resubmits.
            rec.retry_due = self.tick
        return fid

    def _refund_bucket(self, rec: _FleetRequest) -> None:
        """Return a bucket spend for a submission the fleet REJECTED
        after the quota check passed — a rejection does no work, so it
        must not drain the tenant's budget."""
        if self._buckets is not None and rec.tenant is not None:
            self._buckets.refund(rec.tenant, rec.cost, self.tick)
        if self._adapter_buckets is not None and rec.adapter is not None:
            self._adapter_buckets.refund(rec.adapter, rec.cost, self.tick)

    def _pick_replicas(self, rec: _FleetRequest,
                       exclude: Set[int] = frozenset()) -> List[_Replica]:
        """Trust-aware routing order: admitting replicas only (healthy
        before degraded), least-loaded first.  ``exclude`` avoids
        replicas that already failed this request (ignored when it
        would leave no candidates — availability beats affinity; a
        replica already running an attempt of this request is never a
        candidate)."""
        live_on = set(rec.live)
        avoid = set(exclude) | rec.excluded | live_on
        candidates = [r for r in self.replicas
                      if r.state in ADMITTING and r.engine is not None]
        picked = [r for r in candidates if r.index not in avoid]
        if not picked:
            picked = [r for r in candidates if r.index not in live_on]
        # Disaggregated pools: submissions (and resubmissions — every
        # resubmission replays from the prompt) PREFER prefill
        # specialists; decode replicas stay in the order as a fallback
        # because availability beats specialization.
        roles = self._roles_active
        return sorted(picked,
                      key=lambda r: (roles and r.role == "decode",
                                     r.state is not ReplicaState.HEALTHY,
                                     r.engine.load, r.index))

    def _try_submit(self, rec: _FleetRequest,
                    exclude: Set[int] = frozenset()) -> str:
        """Returns ``"submitted"``, ``"full"`` (admitting replicas
        existed but EVERY one's queue shed the request — backpressure)
        or ``"none_admitting"`` (no replica can take work right now)."""
        reps = self._pick_replicas(rec, exclude)
        if not reps:
            return "none_admitting"
        for rep in reps:
            if self._submit_to(rec, rep):
                return "submitted"
        return "full"

    def _submit_to(self, rec: _FleetRequest, rep: _Replica) -> bool:
        now = time.perf_counter()
        deadline_s = None
        if rec.deadline_at is not None:
            deadline_s = max(rec.deadline_at - now, 0.0)
        span = None
        if self.spans is not None:
            span = self.spans.start(
                "fleet.attempt", kind="serve", parent_id=rec.span_root,
                request_id=rec.fid, replica=rep.index,
                attempt=rec.submissions + 1)
        local = rep.engine.submit(ServeRequest(
            prompt=rec.prompt, max_new_tokens=rec.max_new_tokens,
            temperature=rec.temperature, eos_id=rec.eos_id,
            deadline_s=deadline_s, rng=rec.rng,
            on_token=self._token_forwarder(rec, rep.index),
            priority=rec.priority, first_submit_id=rec.fid,
            span_parent=span, tenant=rec.tenant, adapter=rec.adapter,
        ))
        if local is None:
            if span is not None:
                self.spans.end(span, outcome="queue_full")
            return False
        rec.submissions += 1
        rec.retry_due = None
        rec.live[rep.index] = _Attempt(
            replica=rep.index, gen=rep.gen, local_id=local,
            submit_t=now, span=span,
        )
        self._local2fleet[(rep.index, local)] = rec.fid
        return True

    def _token_forwarder(self, rec: _FleetRequest, replica: int
                         ) -> Optional[Callable[[int, int], None]]:
        if rec.on_token is None:
            return None

        def forward(_local_rid: int, token: int) -> None:
            # Primary-attempt streaming: the earliest-submitted live
            # attempt owns the stream (hedges stream only if promoted
            # by the primary's failure) — and nothing streams after the
            # record closed.
            att = rec.live.get(replica)
            if rec.done or att is None or att.loser:
                return
            primary = min(rec.live.values(), key=lambda a: a.submit_t)
            if primary.replica == replica:
                rec.on_token(rec.fid, token)

        return forward

    # -- the fleet tick ----------------------------------------------------

    def step(self) -> int:
        """One fleet tick: chaos → step live replicas → process
        retirements → supervise lifecycles → due retries + hedges.
        Returns tokens emitted across the fleet this tick."""
        self.tick += 1
        self._apply_chaos()
        self._dispatch_classes()
        emitted = 0
        for rep in self.replicas:
            if rep.engine is None or rep.state is ReplicaState.QUARANTINED:
                continue
            if self.tick < rep.stalled_until:
                continue  # chaos wedge: no progress, heartbeat will see
            emitted += rep.engine.step()
            rep.last_progress_tick = self.tick
        self._rebalance_pools()
        self._process_terminals()
        self._supervise()
        self._autoscale()
        self._run_retries_and_hedges()
        self._set_state_gauge()
        # Done records with every attempt settled leave the working set
        # (their FleetResult stays in ``results`` until drained) — the
        # tick loop stays O(live), not O(history).
        for fid in [f for f, r in self.requests.items()
                    if r.done and not r.live]:
            del self.requests[fid]
        return emitted

    def run_until_idle(self, max_ticks: int = 100_000
                       ) -> Dict[int, FleetResult]:
        """Drive ``step()`` until every submitted request is terminal
        AND every verdict-vote ballot has resolved (or ``max_ticks``
        trips — the liveness backstop)."""
        ticks = 0
        while self.busy:
            self.step()
            ticks += 1
            if ticks >= max_ticks:
                raise RuntimeError(
                    f"fleet did not drain in {max_ticks} ticks "
                    f"(states: {[r.state.value for r in self.replicas]})"
                )
        return self.results

    # -- chaos mechanics ---------------------------------------------------

    def _apply_chaos(self) -> None:
        if self.chaos is None or not hasattr(self.chaos, "on_fleet_tick"):
            return
        from trustworthy_dl_tpu.chaos.plan import FaultKind

        for event in self.chaos.on_fleet_tick(self.tick):
            if event.kind is FaultKind.TENANT_FLOOD:
                self.counters["tenant_floods"] += 1
                self._run_flood(event)
                continue
            if event.kind is FaultKind.ADAPTER_POISON:
                # The injector keeps the persistent per-adapter signal
                # overwrite (the adapter id rides the event's ``tenant``
                # field — there is no replica target: a poisoned
                # artifact is everywhere its page is resident).  The
                # per-adapter flag ladder does the catching.
                self.counters["adapter_poisons"] += 1
                continue
            target = event.target
            if not 0 <= target < len(self.replicas):
                logger.warning("chaos: fleet event %s targets unknown "
                               "replica %d", event.kind.value, target)
                continue
            rep = self.replicas[target]
            if event.kind is FaultKind.REPLICA_CRASH:
                self._crash_replica(rep)
            elif event.kind is FaultKind.REPLICA_STALL:
                self.counters["stalls"] += 1
                rep.stalled_until = self.tick + max(int(event.severity), 1)
            elif event.kind is FaultKind.REPLICA_POISON:
                # The injector keeps the persistent signal overwrite;
                # the monitor flag-rate ladder does the rest.
                self.counters["poisons"] += 1
            elif event.kind is FaultKind.REPLICA_ADAPTIVE_POISON:
                # The injector's attached adversary owns the corruption
                # and its strength controller; the suspicion tier +
                # verdict voting do the catching (the flag-rate ladder
                # never trips by the attacker's design).
                self.counters["adaptive_poisons"] += 1
            elif event.kind is FaultKind.REPLICA_SLOWSTART:
                # Warm-up only makes sense for a replica IN service: a
                # quarantined/draining replica must keep its ladder
                # state (a slow-start must never cancel a pending
                # quarantine or skip a cool-off); an already-restarting
                # one just warms longer.
                self.counters["slowstarts"] += 1
                warm = self.tick + max(int(event.severity), 1)
                if rep.state in ADMITTING:
                    rep.warm_until = warm
                    self._transition(rep, ReplicaState.RESTARTING,
                                     "slowstart")
                elif rep.state is ReplicaState.RESTARTING:
                    rep.warm_until = max(rep.warm_until, warm)
                else:
                    logger.warning(
                        "chaos: slowstart on replica %d ignored in "
                        "state %s (ladder state preserved)",
                        rep.index, rep.state.value)
            elif event.kind is FaultKind.REPLICA_PREEMPT:
                self._preempt_replica(rep)

    def _crash_replica(self, rep: _Replica) -> None:
        """Kill the engine outright: every fleet request it held fails
        over (ONE episode), the replica restarts after
        ``restart_ticks``.  The dead generation's allocator journal
        stays in ``self.journals`` — its blocks must keep reconciling.
        A crash must never LAUNDER trust state: a quarantined replica
        stays quarantined (the cool-off probe path rebuilds the engine
        when it fires), and a trust-drain in progress completes as a
        quarantine — dying mid-drain is not an exit from the ladder."""
        if rep.state is ReplicaState.RETIRED:
            # Scaled-in replica: no engine exists to crash — the event
            # is a no-op (and must not resurrect retired capacity).
            logger.warning("chaos: crash on retired replica %d ignored",
                           rep.index)
            return
        self.counters["crashes"] += 1
        if rep.state is ReplicaState.QUARANTINED:
            rep.engine = None   # probe exit rebuilds; cool-off intact
            return
        self.counters["failover_episodes"] += 1
        victims = [(key, fid) for key, fid in self._local2fleet.items()
                   if key[0] == rep.index]
        for (replica, local), fid in victims:
            del self._local2fleet[(replica, local)]
            rec = self.requests[fid]
            att = rec.live.pop(replica, None)
            if att is not None:
                self._close_attempt_span(att, "crashed")
                rec.closed.append({
                    "replica": replica, "gen": att.gen,
                    "journal": f"{replica}:{att.gen}", "outcome": "crashed",
                    "layout": None, "slot": -1, "block_ids": [],
                    "prefix_block_ids": [], "prefix_publishers": {},
                })
            self._schedule_failover(rec, from_replica=rep.index,
                                    reason="crash")
        # Vote ballots the dead engine held abstain (the vote must not
        # wait forever on a replica that no longer exists)...
        for key in [k for k in self._vote_ballots if k[0] == rep.index]:
            vote = self._vote_ballots.pop(key)
            vote.pending.discard(rep.index)
            vote.ballots[rep.index] = None
            if not vote.pending:
                self._resolve_vote(vote)
        # ...and votes TARGETING the dead replica are abandoned: the
        # generation (and the stream under audit) is gone, so a stale
        # verdict must never convict the successor — nor leak a second
        # concurrent vote once the rebuild resets ``vote_open``.
        self._abandon_votes_targeting(rep.index)
        rep.engine = None
        # A crash voids a pending scale-in: the capacity decision is
        # re-made by the autoscaler against post-crash reality, not
        # carried as a stale flag into an unrelated future drain.
        rep.retire_pending = False
        if rep.quarantine_pending:
            # The suspect replica died mid-drain: impound it — the
            # quarantine the flag-rate earned still happens, cool-off
            # ladder intact (no crash-as-quarantine-escape).
            rep.quarantine_pending = False
            rep.cooloff_ticks = max(rep.cooloff_ticks * 2,
                                    self.config.quarantine_cooloff_ticks)
            rep.cooloff_until = self.tick + rep.cooloff_ticks
            self._transition(rep, ReplicaState.QUARANTINED, "crash")
        else:
            rep.warm_until = self.tick + self.config.restart_ticks
            self._transition(rep, ReplicaState.RESTARTING, "crash")

    def _preempt_replica(self, rep: _Replica) -> None:
        """Preemptible capacity loss WITH notice — the serving twin of
        the training-side PREEMPT.  Unlike a crash the fleet gets to
        move the replica's state before the instance disappears: the
        queue re-queues elsewhere (no device state to move) and every
        in-flight request LIVE-migrates as a KV block copy
        (serve/migrate.py); only what cannot move (no capacity, no
        migration surface) falls back to the replay fail-over.  A
        preemption that migrates everything is therefore NOT a failover
        episode and NOT a drain — the capacity leaves, the work does
        not — and the replica warms back through RESTARTING exactly
        like a crash restart (``predict_fleet``: 1 preempt +
        1 restart)."""
        if rep.state is ReplicaState.RETIRED or rep.engine is None:
            logger.warning("chaos: preempt on replica %d ignored "
                           "(no engine)", rep.index)
            return
        self.counters["preempts"] += 1
        if rep.state is ReplicaState.QUARANTINED:
            # Quarantined = already drained empty: nothing to move, and
            # preemption must not launder the cool-off (crash parity).
            rep.engine = None
            self._forensic_incident("replica_preempt", rep=rep,
                                    trigger_type="replica_transition")
            return
        self._migrate(rep, rep.engine.queued_ids,
                      status="migrated", reason="preempt")
        for local in list(rep.engine.inflight_ids):
            fid = self._local2fleet.get((rep.index, local))
            if fid is None or not self._live_migrate(rep, fid, "preempt"):
                self._migrate(rep, [local],
                              status="failover", reason="preempt")
        # Settle the cancels NOW: ballots seated here abstain, and the
        # moved attempts close before the engine is torn down.
        self._process_terminals()
        self._abandon_votes_targeting(rep.index)
        rep.retire_pending = False
        if rep.quarantine_pending:
            # Preempted mid-trust-drain: impound — same
            # no-escape-from-the-ladder rule as a crash.
            rep.quarantine_pending = False
            rep.cooloff_ticks = max(rep.cooloff_ticks * 2,
                                    self.config.quarantine_cooloff_ticks)
            rep.cooloff_until = self.tick + rep.cooloff_ticks
            rep.engine = None
            self._transition(rep, ReplicaState.QUARANTINED, "preempt")
            self._forensic_incident("replica_preempt", rep=rep,
                                    trigger_type="replica_transition")
            return
        rep.engine = None
        rep.warm_until = self.tick + self.config.restart_ticks
        self._transition(rep, ReplicaState.RESTARTING, "preempt")
        # Assembled AFTER the transition so the incident's counters
        # snapshot carries the full episode (preempt + migrations) and
        # its actions include every kv_migration just emitted.
        self._forensic_incident("replica_preempt", rep=rep,
                                trigger_type="replica_transition")

    # -- control plane: floods, class dispatch, autoscaling ----------------

    def _run_flood(self, event: Any) -> None:
        """Execute a TENANT_FLOOD: burst ``severity`` requests from the
        flooding tenant through the NORMAL admission path in one tick —
        the token bucket throttles what the tenant cannot pay for, the
        class queues schedule the rest, and the admitted burst drives
        the autoscaler like any real overload.  Admitted flood requests
        are accepted work: they serve to completion like any other."""
        n = max(int(event.severity), 1)
        tenant = event.tenant or "flood"
        cfgc = self.config
        admitted = 0
        for _ in range(n):
            fid = self.submit(ServeRequest(
                prompt=[0] * cfgc.flood_prompt_len,
                max_new_tokens=cfgc.flood_new_tokens,
                temperature=0.0, tenant=tenant, priority=0,
            ))
            if fid is not None:
                admitted += 1
        logger.warning("fleet: tenant flood from %r — %d/%d admitted at "
                       "tick %d", tenant, admitted, n, self.tick)

    def _classq_alive(self, fid: int) -> bool:
        rec = self.requests.get(fid)
        return rec is not None and not rec.done and not rec.live \
            and rec.retry_due is None

    def _free_engine_queue_slots(self) -> int:
        free = 0
        for rep in self.replicas:
            if rep.state in ADMITTING and rep.engine is not None:
                free += max(int(rep.engine.queue_limit)
                            - len(rep.engine.queued_ids), 0)
        return free

    def _dispatch_classes(self) -> None:
        """One dispatch pass per tick (no-op without SLO classes): shed
        the lowest class first while any class's latency target is
        breached and the backlog exceeds free capacity — replacing the
        raw lowest-priority shed — then release queued requests to the
        engines by token-cost deficit round robin."""
        if self._classq is None:
            return
        free = self._free_engine_queue_slots()
        if (self._class_latency.any_breached()
                and self._classq.depth() > free):
            # At most one shed per tick (pressure is re-evaluated every
            # tick), from the NEWEST entry of the LOWEST class.
            cand = self._classq.shed_candidate(self._classq_alive)
            if cand is not None:
                name, fid = cand
                rec = self.requests.get(fid)
                if rec is not None and not rec.done:
                    self._class_stats[name]["shed"] += 1
                    self._finalize_unserved(rec, "shed_slo")
        batch = self._classq.take(free, self._classq_alive)
        for i, (name, fid, cost) in enumerate(batch):
            rec = self.requests.get(fid)
            if rec is None or rec.done:
                continue
            try:
                outcome = self._try_submit(rec)
            except BaseException:
                # An engine-side RAISE mid-batch must not orphan the
                # already-dequeued tail either: re-queue everything
                # from this entry on (the raising entry keeps its
                # record and stays queued), then let the caller see
                # the error.
                for name2, fid2, cost2 in reversed(batch[i:]):
                    self._classq.push_front(name2, fid2, cost2)
                raise
            if outcome != "submitted":
                # Engine backpressure mid-batch: EVERY not-yet-placed
                # entry goes back (reversed push_front restores order)
                # — dropping the tail would orphan requests with no
                # live attempt, no retry and no queue entry, wedging
                # ``busy`` forever.
                for name2, fid2, cost2 in reversed(batch[i:]):
                    self._classq.push_front(name2, fid2, cost2)
                break

    def _rebalance_pools(self) -> None:
        """Disaggregated-pool sweep (no-op without ``pool_roles``): a
        request that just produced its first decode token on a
        prefill-specialist replica moves to a decode specialist as a
        live block copy — the hand-off the split exists for.  A refusal
        (full decode pool) leaves it decoding where it is; the sweep
        retries next tick, because availability beats specialization."""
        if not self._roles_active:
            return
        moved = 0
        for rep in self.replicas:
            if (rep.role != "prefill" or rep.engine is None
                    or rep.state not in ADMITTING):
                continue
            for local in list(getattr(rep.engine, "decode_ready_ids",
                                      ())):
                fid = self._local2fleet.get((rep.index, local))
                if fid is None:
                    continue  # vote replay: audits never rebalance
                if self._live_migrate(rep, fid, "disagg"):
                    moved += 1
        if moved and self.trace is not None:
            self.trace.emit(
                EventType.POOL_REBALANCE, role="prefill", moved=moved,
                replicas=sum(1 for r in self.replicas
                             if r.role == "decode"
                             and r.engine is not None))

    def _in_service(self) -> List[_Replica]:
        """Replicas that exist as capacity (everything but RETIRED) —
        the count the autoscaler's [min, max] bounds govern."""
        return [r for r in self.replicas
                if r.state is not ReplicaState.RETIRED]

    def _note_replica_trace(self) -> None:
        n = len(self._in_service())
        if len(self.replica_trace) < 4096 and (
                not self.replica_trace
                or self.replica_trace[-1][1] != n):
            self.replica_trace.append((self.tick, n))

    def _autoscale(self) -> None:
        """One control decision per tick (no-op without an autoscaler):
        gather the tick's signals, run the shared pure predicate
        through the hysteresis state, and execute at most one scale
        action."""
        if self.autoscaler is None:
            return
        if self._pool_scalers:
            # Disaggregated pools scale INDEPENDENTLY: each pool reads
            # only its own replicas' signals and holds its own
            # hysteresis/cool-down state, so decode-pool pressure (long
            # generations) grows decode capacity without touching the
            # prefill pool and vice versa.  The [min, max] bounds apply
            # per pool.
            for role in ("prefill", "decode"):
                sig = self._scale_signals(role)
                decision = self._pool_scalers[role].observe(sig)
                if decision > 0:
                    self._scale_up(sig, role=role)
                elif decision < 0:
                    self._scale_down(sig, role=role)
            return
        sig = self._scale_signals(None)
        decision = self.autoscaler.observe(sig)
        if decision > 0:
            self._scale_up(sig)
        elif decision < 0:
            self._scale_down(sig)

    def _scale_signals(self, role: Optional[str]) -> Any:
        """One tick's autoscaler inputs, fleet-wide (``role=None``) or
        restricted to one disaggregated pool."""
        from trustworthy_dl_tpu.serve.control import ScaleSignals, \
            predicted_replicas

        # Capacity-planning view: a replica already draining toward
        # RETIRED is LEAVING — counting it against the [min, max]
        # bounds would let repeated scale-downs (one per cool-down,
        # while a long drain holds the count up) walk the fleet below
        # min_replicas, to zero in the worst case.  Excluding it also
        # lets a scale-up REPLACE leaving capacity under fresh load.
        # QUARANTINED replicas are excluded the same way: they serve
        # nothing for an indefinite cool-off, so counting them would
        # BOTH dilute queue-per-replica (12 requests on the one live
        # engine of a 3-replica fleet reading as 4/replica) AND block
        # scale-ups at the max bound exactly when chaos removed the
        # capacity.  RESTARTING stays counted — it is warming capacity,
        # and forgetting it would re-fire a scale-up every tick of the
        # warmup.
        staying = [r for r in self._in_service()
                   if r.state is not ReplicaState.QUARANTINED
                   and not (r.state is ReplicaState.DRAINING
                            and r.retire_pending)
                   and (role is None or r.role == role)]
        engines = [r.engine for r in staying if r.engine is not None]
        queue = sum(e.load for e in engines)
        if self._classq is not None and role in (None, "prefill"):
            # Class-queued work dispatches to the PREFILL pool when the
            # split is on (routing prefers prefill specialists), so the
            # backlog is that pool's pressure, counted once.
            queue += self._classq.depth()
        occ = 0.0
        pools = [getattr(e, "scheduler", None) for e in engines]
        pools = [s for s in pools if s is not None]
        if pools:
            occ = sum(s.occupancy for s in pools) / len(pools)
        burning = any(
            getattr(e, "slo", None) is not None and e.slo.breached
            for e in engines)
        itl = (self._itl_est.quantile(0.99)
               if self._itl_est.count else None)
        cfg = self.autoscaler.cfg
        # The predictive arm models FLEET-wide demand.  A pool scaler
        # may consume it only when the config DECLARES that pool's
        # demand share (PredictiveArmConfig.role_share) — the shares
        # partition the envelope, so per-pool predictions cannot
        # jointly exceed the fleet-wide ask (the double-provisioning
        # hazard that used to force pool mode to run reactive-only).
        pred = None
        if cfg.predictive is not None:
            if role is None:
                pred = predicted_replicas(cfg.predictive, self.tick)
            elif role in dict(cfg.predictive.role_share or ()):
                pred = predicted_replicas(cfg.predictive, self.tick,
                                          role=role)
        return ScaleSignals(
            tick=self.tick, in_service=len(staying),
            queue_per_replica=queue / max(len(staying), 1),
            occupancy=occ, itl_p99=itl, slo_burning=burning,
            predicted_replicas=pred,
            down_candidates=any(r.state in ADMITTING
                                and r.engine is not None
                                and (role is None or r.role == role)
                                for r in self.replicas),
        )

    def _emit_scale(self, direction: str, frm: int, to: int,
                    reason: str) -> None:
        self.counters[f"scale_{direction}s"] += 1
        self._scale_counter.inc(direction=direction)
        self._note_replica_trace()
        if self.trace is not None:
            self.trace.emit(EventType.FLEET_SCALE, direction=direction,
                            from_replicas=frm, to_replicas=to,
                            reason=reason, tick=self.tick)

    def _scale_up(self, sig: Any, role: Optional[str] = None) -> None:
        """Add capacity: revive a RETIRED index (fresh generation —
        journals retained) or append a new replica.  Either way the
        engine build goes through the existing HBM headroom gate
        (``hbm`` rides engine_kwargs), and the replica warms up through
        RESTARTING like any rebuild — scale-up is never instant
        admission.  ``role`` pins the new capacity to one disaggregated
        pool: the revived/appended replica joins THAT pool (a decode
        scale-up must never come back as a prefill specialist).

        With TP headroom configured (``tp_max > tp_size``) the pure
        shape predicate (control.choose_scale_action) picks scale-OUT
        (another replica of the current width) vs scale-UP (the new
        capacity arrives with a DOUBLED TP group): occupancy pressure
        with a quiet queue means per-replica HBM is the bottleneck and
        a wider shard group buys pool blocks, while queue pressure
        means aggregate service rate is — more engines beat bigger
        ones.  Existing replicas are never rebuilt in place (that would
        kill their in-flight work); the fleet upgrades through churn."""
        from trustworthy_dl_tpu.serve.control import choose_scale_action

        frm = len(self._in_service())
        cfgc = self.config
        cur_tp = max((r.tp for r in self._in_service()
                      if r.engine is not None), default=self._base_tp)
        tp_max = cfgc.tp_max or max(cfgc.tp_size, self._base_tp)
        action = choose_scale_action(self.autoscaler.cfg, sig,
                                     cur_tp, tp_max)
        tp_new = min(cur_tp * 2, tp_max) if action == "up" else None
        rep = next((r for r in self.replicas
                    if r.state is ReplicaState.RETIRED
                    and (role is None or r.role == role)), None)
        if rep is None and role is not None:
            # No retired index from this pool — a retired replica from
            # the OTHER pool is still cheaper than a fresh index (its
            # journal survives); it changes pools on revival.
            rep = next((r for r in self.replicas
                        if r.state is ReplicaState.RETIRED), None)
        if rep is not None:
            rep.gen += 1
            self._build_replica(rep.index, prev=rep, role=role, tp=tp_new)
        else:
            rep = self._build_replica(len(self.replicas), role=role,
                                      tp=tp_new)
            self.replicas.append(rep)
        rep.warm_until = self.tick + cfgc.restart_ticks
        rep.last_progress_tick = self.tick
        self._transition(rep, ReplicaState.RESTARTING, "scale_up")
        if action == "up":
            self.counters["tp_scale_ups"] += 1
        logger.warning("fleet: scale-%s -> replica %d tp=%d "
                       "(queue/replica %.1f, occupancy %.2f)", action,
                       rep.index, rep.tp,
                       sig.queue_per_replica, sig.occupancy)
        self._emit_scale("up", frm, len(self._in_service()), "scale_up")

    def _scale_down(self, sig: Any, role: Optional[str] = None) -> None:
        """Shed capacity WITHOUT shedding work: pick the least-loaded
        admitting replica (ties: newest index), migrate its queue now,
        and let in-flight run out — a scale-down drain never
        force-migrates at the grace deadline and never kills accepted
        requests.  The drain completes into RETIRED: pool released,
        journal retained, index reusable by the next scale-up.
        ``role`` restricts the pick to one disaggregated pool so the
        decode scaler can never drain a prefill specialist."""
        cands = [r for r in self.replicas
                 if r.state in ADMITTING and r.engine is not None
                 and (role is None or r.role == role)]
        if not cands:
            return  # nothing safely removable this tick
        frm = len(self._in_service())
        rep = min(cands, key=lambda r: (r.engine.load, -r.index))
        rep.retire_pending = True
        rep.quarantine_pending = False
        self._transition(rep, ReplicaState.DRAINING, "scale_down")
        self._migrate(rep, rep.engine.queued_ids,
                      status="migrated", reason="scale_down")
        # In-flight moves immediately as live block copies (the retiring
        # pool's capacity frees NOW, not after the longest decode); what
        # cannot move keeps the pre-existing run-out — a scale-in drain
        # still never kills accepted work.
        for local in list(rep.engine.inflight_ids):
            fid = self._local2fleet.get((rep.index, local))
            if fid is not None:
                self._live_migrate(rep, fid, "scale_down")
        logger.warning("fleet: scale-down draining replica %d "
                       "(queue/replica %.1f, occupancy %.2f)",
                       rep.index, sig.queue_per_replica, sig.occupancy)
        self._emit_scale("down", frm, frm - 1, "scale_down")

    # -- terminal processing -----------------------------------------------

    def _process_terminals(self) -> None:
        while self._terminal:
            replica, result, placement = self._terminal.popleft()
            self._on_terminal(replica, result, placement)

    def _attempt_record(self, att: _Attempt, result: ServeResult,
                        placement: Optional[dict], outcome: str
                        ) -> Dict[str, Any]:
        placement = placement or {"layout": None, "slot": -1,
                                  "block_ids": [], "prefix_block_ids": [],
                                  "prefix_publishers": {}}
        return {"replica": att.replica, "gen": att.gen,
                "journal": f"{att.replica}:{att.gen}",
                "local_id": att.local_id, "outcome": outcome,
                **placement}

    def _on_terminal(self, replica: int, result: ServeResult,
                     placement: Optional[dict]) -> None:
        vote = self._vote_ballots.pop((replica, result.request_id), None)
        if vote is not None:
            # A verdict-vote replay, not a fleet request: record the
            # ballot (abstain unless it completed) and resolve once the
            # last voter reports.  Replays never feed the voter's flag
            # window — they are audit traffic, and a poisoned VOTER is
            # caught by its dissent, not by double-scoring.
            self._on_vote_ballot(vote, replica, result)
            return
        fid = self._local2fleet.pop((replica, result.request_id), None)
        if fid is None:
            return  # already accounted (crash bookkeeping ran first)
        rec = self.requests.get(fid)
        if rec is None:
            return
        att = rec.live.pop(replica, None)
        if att is None:
            return
        status = result.status
        if (status in ("completed", "deadline_exceeded")
                and placement is not None):
            # The monitor scored this retirement (it held a slot — a
            # queue-side deadline expiry has placement None and never
            # ran, so feeding it would dilute the flag rate and let a
            # poisoned replica hide behind tight-deadline sheds).
            adapter = getattr(result, "adapter", None)
            if adapter is not None:
                # Adapter-attributed stream: the flag indicts the
                # ARTIFACT, not the replica that hosted it — the verdict
                # pools into the fleet-wide per-adapter window and the
                # replica's own window records a clean retirement (its
                # base-model behaviour is not in evidence here).
                if result.flagged:
                    self._note_adapter_impound(adapter, replica, placement)
                # This observation may CONVICT the adapter, and the
                # conviction sweep fails every open request riding it —
                # but this fid's real result is in hand, mid-flight:
                # mark it so the sweep leaves it to finalize below.
                self._terminal_fid = fid
                try:
                    self._observe_adapter_retirement(adapter,
                                                     result.flagged)
                finally:
                    self._terminal_fid = None
                self.observe_retirement(replica, False)
            else:
                self.observe_retirement(replica, result.flagged)
        if att.loser or (rec.done and status != "hedge_lost"):
            # A dedup loser we cancelled — or the race variant: both
            # attempts completed inside one tick and this one lost.
            status = "hedge_lost"
        self._close_attempt_span(att, status)
        rec.closed.append(self._attempt_record(att, result, placement,
                                               status))
        if status == "hedge_lost":
            self.counters["hedge_lost"] += 1
            self._ledger_loser(rec, att)
            return
        if status == "completed":
            self._finalize(rec, result, att)
            return
        if status == "deadline_exceeded":
            # Absolute deadline: every sibling attempt is as dead.
            self._cancel_siblings(rec, status="hedge_lost")
            self._finalize(rec, result, att)
            return
        if status in ("migrated", "failover"):
            # We cancelled it ourselves to move it; the resubmission is
            # already scheduled by the drain/crash path.
            return
        if status in ("no_capacity", "shed_slo"):
            # Engine-side shed: retry elsewhere while budget remains.
            self._schedule_failover(rec, from_replica=replica,
                                    reason=status)
            return
        # Unknown terminal: finalize loudly rather than lose the request.
        logger.warning("fleet: request %d terminal status %r taken as "
                       "final", fid, status)
        self._finalize(rec, result, att)

    def _cancel_siblings(self, rec: _FleetRequest, status: str) -> None:
        for replica, att in list(rec.live.items()):
            rep = self.replicas[replica]
            att.loser = True
            if rep.engine is not None:
                rep.engine.cancel(att.local_id, status=status)

    def _schedule_failover(self, rec: _FleetRequest, from_replica: int,
                           reason: str) -> None:
        if rec.done or rec.live or rec.retry_due is not None:
            return
        now = time.perf_counter()
        if rec.deadline_at is not None and now > rec.deadline_at:
            self._finalize_unserved(rec, "deadline_exceeded")
            return
        if rec.submissions > self.config.max_retries:
            self._finalize_unserved(rec, "failover_exhausted")
            return
        rec.excluded.add(from_replica)
        rec.retry_due = self.tick + backoff_ticks(self.config,
                                                  max(rec.submissions, 1))
        self.counters["failovers"] += 1
        self._failover_counter.inc()
        if self.trace is not None:
            self.trace.emit(EventType.FLEET_FAILOVER, request_id=rec.fid,
                            from_replica=from_replica, to_replica=None,
                            attempt=rec.submissions + 1, reason=reason,
                            due_tick=rec.retry_due)

    # -- finalization ------------------------------------------------------

    def _finalize(self, rec: _FleetRequest, result: ServeResult,
                  att: _Attempt) -> None:
        if rec.done:
            return
        rec.done = True
        rec.retry_due = None
        self._cancel_siblings(rec, status="hedge_lost")
        ttft = None
        if result.ttft_s is not None:
            ttft = (att.submit_t - rec.submit_t) + result.ttft_s
        self.results[rec.fid] = FleetResult(
            request_id=rec.fid, tokens=list(result.tokens),
            status=result.status, replica=att.replica,
            attempts=rec.submissions, ttft_s=ttft,
            flagged=result.flagged, monitor_z=result.monitor_z,
            tenant=rec.tenant, slo_class=rec.slo_class,
            adapter=rec.adapter,
        )
        if result.status == "completed":
            for dt in result.itl_s:
                self._itl_est.observe(dt)
            if rec.slo_class is not None:
                stats = self._class_stats[rec.slo_class]
                stats["completed"] += 1
                stats["tokens"] += len(result.tokens)
                self._class_latency.observe(rec.slo_class, ttft_s=ttft,
                                            itl_s=result.itl_s)
        self._ledger_canonical(rec, result, att, ttft)
        if self.spans is not None and rec.span_root is not None:
            self.spans.end(rec.span_root, status=result.status,
                           replica=att.replica, attempts=rec.submissions,
                           tokens=len(result.tokens))
        self._maybe_vote(rec, result, att)

    def _finalize_unserved(self, rec: _FleetRequest, status: str) -> None:
        """Terminal without a serving attempt left: deadline ran out
        between attempts, retry budget exhausted, or fleet-wide
        starvation.  NEVER silent: the request gets a result, a ledger
        record and a closed span like every other."""
        if rec.done:
            return
        rec.done = True
        rec.retry_due = None
        # Token-bucket reconciliation: the spend landed ONCE at submit()
        # and rode through every drain→migrate→resubmit hop without a
        # re-charge; a request that dies UNSERVED (deadline between
        # attempts, retry budget, starvation) produced no tokens, so the
        # tenant gets that one spend back — never refunded twice
        # (rec.done guards above) and never refunded for served work.
        self._refund_bucket(rec)
        self._cancel_siblings(rec, status="hedge_lost")
        self.results[rec.fid] = FleetResult(
            request_id=rec.fid, tokens=[], status=status, replica=None,
            attempts=rec.submissions, ttft_s=None,
            tenant=rec.tenant, slo_class=rec.slo_class,
            adapter=rec.adapter,
        )
        if self.ledger is not None:
            self.ledger.append({
                "request_id": rec.fid, "status": status,
                "admitted": bool(rec.closed),
                "replica": None, "attempts": list(rec.closed),
                "flagged": False, "monitor_z": 0.0, "tokens": 0,
                "token_hash": attribution.token_hash([]),
                "ttft_s": None, "submissions": rec.submissions,
                "tenant": rec.tenant, "slo_class": rec.slo_class,
            })
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_RETIRE, request_id=rec.fid,
                            status=status, tokens=0, fleet=True)
        if self.spans is not None and rec.span_root is not None:
            self.spans.end(rec.span_root, status=status,
                           attempts=rec.submissions)

    def _ledger_canonical(self, rec: _FleetRequest, result: ServeResult,
                          att: _Attempt, ttft: Optional[float]) -> None:
        if self.ledger is None:
            return
        winner = rec.closed[-1] if rec.closed else {}
        engine = self.replicas[att.replica].engine
        self.ledger.append({
            "request_id": rec.fid, "status": result.status,
            "admitted": True, "replica": att.replica,
            "journal": f"{att.replica}:{att.gen}",
            "layout": winner.get("layout"), "slot": winner.get("slot", -1),
            "block_ids": list(winner.get("block_ids") or []),
            "prefix_block_ids": list(winner.get("prefix_block_ids") or []),
            "prefix_publishers": dict(winner.get("prefix_publishers") or {}),
            "attempts": list(rec.closed),
            "kv_dtype": getattr(engine, "kv_dtype", None),
            "weight_dtype": getattr(engine, "weight_dtype", None),
            "kv_fallback_reason": getattr(engine, "kv_fallback_reason",
                                          None),
            "flagged": bool(result.flagged),
            "monitor_z": float(result.monitor_z),
            "tokens": len(result.tokens),
            "token_hash": attribution.token_hash(result.tokens),
            "ttft_s": ttft, "submissions": rec.submissions,
            "tenant": rec.tenant, "slo_class": rec.slo_class,
            "adapter": rec.adapter,
            "adapter_page": winner.get("adapter_page", 0),
        })

    def _ledger_loser(self, rec: _FleetRequest, att: _Attempt) -> None:
        if self.ledger is None:
            return
        self.ledger.append({
            "request_id": rec.fid, "status": "hedge_lost",
            "admitted": False, "replica": att.replica,
            "journal": f"{att.replica}:{att.gen}",
            "tokens": 0, "token_hash": attribution.token_hash([]),
        })

    def _close_attempt_span(self, att: _Attempt, outcome: str) -> None:
        if self.spans is not None and att.span is not None:
            self.spans.end(att.span, outcome=outcome)

    # -- supervision -------------------------------------------------------

    def _forensic_incident(self, reason: str, *,
                           rep: Optional[_Replica] = None,
                           adapter: Optional[str] = None,
                           tenant: Optional[str] = None,
                           trigger_type: Optional[str] = None,
                           refusals: Optional[List[Dict[str, Any]]] = None,
                           extra: Optional[Dict[str, Any]] = None) -> None:
        """Assemble one forensic incident for a fleet episode (no-op
        without an attached assembler).  The counters snapshot is taken
        HERE — after every counter the episode bumped — so drill
        assertions can reconcile the incident against
        ``predict_fleet()`` exactly."""
        if self.forensics is None:
            return
        records = list(self.ledger.records()) \
            if self.ledger is not None else []
        # Ledger records land at RETIREMENT — a mid-episode blast
        # radius must also see the requests still in flight (a
        # preemption's migrated streams, a drain's survivors), so open
        # requests contribute a provisional record built from their
        # closed-attempt history.  The journal/block placements in
        # ``rec.closed`` are the same dicts the final ledger record
        # will carry.
        for fid, rec in self.requests.items():
            if not rec.done and rec.closed:
                records.append({"request_id": fid, "admitted": True,
                                "status": "in_flight",
                                "attempts": list(rec.closed),
                                "provisional": True})
        self.forensics.assemble(
            reason, tick=self.tick,
            suspects=[rep.index] if rep is not None else None,
            suspect_journals=[rep.journal_key] if rep is not None else (),
            adapter=adapter, tenant=tenant, trigger_type=trigger_type,
            counters=dict(self.counters),
            records=records,
            refusals=refusals, extra=extra,
        )

    def _transition(self, rep: _Replica, to: ReplicaState,
                    reason: str) -> None:
        if rep.state is to:
            return
        frm = rep.state
        rep.state = to
        rep.reason = reason
        self._transition_counter.inc(to_state=to.value)
        if to is ReplicaState.DRAINING:
            self.counters["drains"] += 1
            rep.drain_deadline = self.tick + self.config.drain_grace_ticks
        elif to is ReplicaState.QUARANTINED:
            self.counters["quarantines"] += 1
        logger.warning("fleet: replica %d %s -> %s (%s)", rep.index,
                       frm.value, to.value, reason)
        if self.trace is not None:
            self.trace.emit(EventType.REPLICA_TRANSITION,
                            replica=rep.index, from_state=frm.value,
                            to_state=to.value, reason=reason,
                            tick=self.tick)
        if to is ReplicaState.QUARANTINED:
            # The quarantine is the flight-dump-grade verdict: durable
            # history row + full forensic incident (trigger = the
            # transition just emitted; blast radius = every request
            # that decoded off this generation's blocks).
            if self.verdicts is not None:
                self.verdicts.append("quarantine", "quarantined",
                                     replica=rep.index, reason=reason,
                                     tick=self.tick)
            self._forensic_incident("replica_quarantine", rep=rep,
                                    trigger_type="replica_transition",
                                    extra={"transition_reason": reason})

    def _migrate(self, rep: _Replica, ids: List[int], status: str,
                 reason: str) -> None:
        """Cancel the given local requests on ``rep`` and schedule their
        resubmission elsewhere (the cancel's retire_hook lands them in
        the terminal queue; the 'migrated'/'failover' status routes them
        back through ``_schedule_failover``)."""
        for local in ids:
            fid = self._local2fleet.get((rep.index, local))
            rep.engine.cancel(local, status=status)
            if fid is None:
                continue
            rec = self.requests.get(fid)
            if rec is not None and not rec.done:
                # The cancel fired the hook synchronously; the terminal
                # record is queued.  Schedule the move NOW so the
                # resubmission carries the drain reason.
                self._drain_moves.append((fid, rep.index, reason))

    def _live_migrate(self, rep: _Replica, fid: int, reason: str) -> bool:
        """Move fleet request ``fid`` off ``rep`` as a LIVE KV
        block-table migration (serve/migrate.py): the destination's
        admission rides the normal allocator path, the fleet re-points
        its attempt table in the commit hook BEFORE the source attempt
        closes, and the source's blocks release — or impound, when the
        source is bound for quarantine — only after that.  Returns False
        (source untouched, caller falls back to the replay path or the
        drain grace window) when no destination can take the copy:
        structural gate failure, full pools, or a mid-prefill request
        with nothing migratable yet."""
        from trustworthy_dl_tpu.serve.migrate import can_migrate, \
            migrate_request

        if not self.config.live_migration:
            return False
        rec = self.requests.get(fid)
        if rec is None or rec.done:
            return False
        att = rec.live.get(rep.index)
        if att is None:
            return False
        cands = [r for r in self.replicas
                 if r.index != rep.index and r.state in ADMITTING
                 and r.engine is not None and r.index not in rec.live]
        if self._roles_active:
            decode = [r for r in cands if r.role == "decode"]
            if decode:
                cands = decode
        cands.sort(key=lambda r: (r.state is not ReplicaState.HEALTHY,
                                  r.engine.load, r.index))
        refusals: List[Dict[str, Any]] = []
        for dst in cands:
            if not can_migrate(rep.engine, dst.engine):
                refusals.append({"replica": dst.index,
                                 "reason": "structural_gate"})
                continue

            def commit(new_local: int, _dst: _Replica = dst) -> None:
                # The destination attempt inherits the SOURCE attempt's
                # submit_t: the fleet's TTFT math must read the stream
                # as one request, not restart the clock mid-flight.
                rec.live[_dst.index] = _Attempt(
                    replica=_dst.index, gen=_dst.gen,
                    local_id=new_local, submit_t=att.submit_t)
                self._local2fleet[(_dst.index, new_local)] = rec.fid

            moved = migrate_request(
                rep.engine, dst.engine, att.local_id,
                quarantine_src=rep.quarantine_pending,
                on_token=self._token_forwarder(rec, dst.index),
                src_journal=f"{rep.index}:{att.gen}",
                on_commit=commit,
                on_refuse=lambda why, _d=dst: refusals.append(
                    {"replica": _d.index, "reason": why}),
            )
            if moved is None:
                continue
            self.counters["migrations"] += 1
            self._migration_counter.inc(reason=reason)
            if self.trace is not None:
                self.trace.emit(EventType.KV_MIGRATION, request_id=fid,
                                from_replica=rep.index,
                                to_replica=dst.index,
                                blocks=moved["blocks"], reason=reason)
            # Settle the source cancel NOW: until its terminal record
            # pops the source attempt from rec.live, both attempts
            # share a submit_t and the streaming tie-break would
            # suppress the destination's next token.
            self._process_terminals()
            return True
        # Full walk refused: every ranked destination either failed the
        # structural gate or refused the claim (or the source had
        # nothing migratable).  The caller falls back to replay; the
        # incident records WHO refused and WHY, per destination.
        self._last_migration_refusals = refusals
        if refusals:
            self._forensic_incident(
                "migration_refused", rep=rep, refusals=refusals,
                trigger_type="replica_transition",
                extra={"request_id": fid, "migrate_reason": reason})
        return False

    def _start_trust_drain(self, rep: _Replica, reason: str) -> None:
        """ONE spelling of the trust-driven drain entry (flag-rate trip
        AND verdict outvote): transition, arm the quarantine, migrate
        the queue now — and move in-flight work IMMEDIATELY as live
        block copies with the source blocks impounded (the suspect's
        bytes leave its pool with the evidence held, instead of the
        suspect serving user tokens for a whole grace window).  What
        cannot move keeps the pre-existing grace-window run-out."""
        self._transition(rep, ReplicaState.DRAINING, reason)
        rep.quarantine_pending = True
        self._migrate(rep, rep.engine.queued_ids,
                      status="migrated", reason="drain")
        for local in list(rep.engine.inflight_ids):
            fid = self._local2fleet.get((rep.index, local))
            if fid is not None:
                self._live_migrate(rep, fid, "drain")

    def _supervise(self) -> None:
        cfg = self.config
        # NOTE: _drain_moves is NOT reset here — a vote-triggered drain
        # queues moves from terminal processing before this pass runs.
        for rep in self.replicas:
            if rep.state is ReplicaState.RETIRED:
                continue  # scaled in: no engine, no signals, no ladder
            if rep.state is ReplicaState.RESTARTING:
                if self.tick >= rep.warm_until:
                    if rep.engine is None:
                        rep.gen += 1
                        self._build_replica(rep.index, prev=rep)
                        self.counters["restarts"] += 1
                    # Fresh heartbeat epoch: the warmup gap must not
                    # read as missed ticks the instant service resumes.
                    rep.last_progress_tick = self.tick
                    self._transition(rep, ReplicaState.HEALTHY,
                                     "warmup_complete")
                continue
            if rep.state is ReplicaState.QUARANTINED:
                if self.tick >= rep.cooloff_until:
                    # Cool-off over: readmission PROBE — the replica
                    # re-enters through RESTARTING and must serve clean;
                    # a still-poisoned replica re-flags and goes back
                    # with a doubled cool-off.
                    self.counters["readmissions"] += 1
                    if self.verdicts is not None:
                        self.verdicts.append(
                            "quarantine", "readmitted",
                            replica=rep.index,
                            reason="readmission_probe", tick=self.tick)
                    # Any vote straggler from the PRE-quarantine
                    # generation dies with the evidence window: the
                    # probe must be judged on fresh behaviour only.
                    self._abandon_votes_targeting(rep.index)
                    rep.reset_trust_window()
                    rep.warm_until = self.tick + cfg.restart_ticks
                    self._transition(rep, ReplicaState.RESTARTING,
                                     "readmission_probe")
                continue
            if rep.engine is None:
                continue
            missed = self.tick - rep.last_progress_tick
            trip = rep.ladder_tripped(cfg)
            watcher_bad = (
                (rep.engine.slo is not None and rep.engine.slo.breached)
                or (rep.engine.anomaly is not None
                    and rep.engine.anomaly.any_active))
            if watcher_bad and rep.state in (ReplicaState.HEALTHY,
                                             ReplicaState.DEGRADED):
                # Anomaly/SLO-watcher episodes feed the suspicion tier
                # too: a replica can be suspected (and vote-audited)
                # without a single monitor flag.
                self.note_suspicion(rep.index, "watcher")
            if rep.state in (ReplicaState.HEALTHY, ReplicaState.DEGRADED):
                if trip:
                    self._start_trust_drain(rep, "monitor_flag_rate")
                elif (getattr(rep.engine, "in_service_capacity", None)
                        == 0 and rep.engine.load):
                    # Every slot impounded by per-request monitor
                    # quarantines: the replica cannot serve its queue
                    # and the flag evidence is already decisive at
                    # engine granularity.  Without this a SUB-threshold
                    # attacker (window rate below the ladder trip, but
                    # flags trickling in) starves its replica's queue
                    # forever — the fleet drives engine.step() directly
                    # and never hits the engine's own run_until_idle
                    # starvation shed.
                    self._start_trust_drain(rep,
                                            "slot_quarantine_exhausted")
                elif missed >= cfg.heartbeat_miss_limit:
                    self._transition(rep, ReplicaState.DRAINING,
                                     "heartbeat")
                    rep.quarantine_pending = False
                    self.counters["failover_episodes"] += 1
                    # No progress = nothing to wait for: migrate queue
                    # AND in-flight immediately.  A wedged engine's
                    # pool is still readable, so in-flight state moves
                    # as a live block copy — every accepted token
                    # travels — and only what cannot move replays.
                    self._migrate(rep, rep.engine.queued_ids,
                                  status="migrated", reason="drain")
                    for local in list(rep.engine.inflight_ids):
                        fid = self._local2fleet.get((rep.index, local))
                        if fid is None or not self._live_migrate(
                                rep, fid, "heartbeat"):
                            self._migrate(rep, [local],
                                          status="failover",
                                          reason="heartbeat")
                elif rep.state is ReplicaState.HEALTHY and (
                        rep.flag_count >= 1
                        or missed >= cfg.heartbeat_miss_degraded
                        or watcher_bad):
                    self._transition(rep, ReplicaState.DEGRADED,
                                     "early_warning")
                elif rep.state is ReplicaState.DEGRADED and (
                        rep.flag_count == 0
                        and missed < cfg.heartbeat_miss_degraded
                        and not watcher_bad):
                    self._transition(rep, ReplicaState.HEALTHY,
                                     "recovered")
            if rep.state is ReplicaState.DRAINING:
                # Scale-down drains are exempt from the grace-deadline
                # force-migration — a scale-in drain's in-flight work
                # RUNS OUT where it is, bounded by max_new_tokens.  But
                # that bound assumes the engine keeps TICKING: a
                # replica that stops making progress mid-retire-drain
                # (chaos stall, wedge) would strand its in-flight work
                # forever, so a stalled retire-drain falls back to the
                # force-migration after heartbeat_miss_limit silent
                # ticks — the capacity was leaving anyway, the work
                # must not leave with it.
                stalled_retire = (
                    rep.retire_pending and rep.engine.load
                    and self.tick - rep.last_progress_tick
                    >= cfg.heartbeat_miss_limit)
                if stalled_retire or (
                        not rep.retire_pending and rep.engine.load
                        and self.tick >= rep.drain_deadline):
                    why = ("scale_down_stall" if stalled_retire
                           else "drain_grace")
                    self._migrate(rep, rep.engine.queued_ids,
                                  status="migrated", reason="drain")
                    for local in list(rep.engine.inflight_ids):
                        fid = self._local2fleet.get((rep.index, local))
                        if fid is None or not self._live_migrate(
                                rep, fid, why):
                            self._migrate(rep, [local],
                                          status="failover", reason=why)
                if rep.engine.load == 0:
                    if rep.retire_pending:
                        # Scale-in complete: release the pool, keep the
                        # journal (records naming its blocks must still
                        # reconcile), leave the index reusable.
                        rep.retire_pending = False
                        rep.engine = None
                        self._transition(rep, ReplicaState.RETIRED,
                                         "scale_down_complete")
                        self._note_replica_trace()
                    elif rep.quarantine_pending:
                        rep.quarantine_pending = False
                        rep.cooloff_ticks = max(
                            rep.cooloff_ticks * 2,
                            cfg.quarantine_cooloff_ticks)
                        rep.cooloff_until = self.tick + rep.cooloff_ticks
                        self._transition(rep, ReplicaState.QUARANTINED,
                                         rep.reason)
                    else:
                        rep.warm_until = max(rep.stalled_until,
                                             self.tick + cfg.restart_ticks)
                        self._transition(rep, ReplicaState.RESTARTING,
                                         "drain_complete")
        # Cancel hooks queued terminal records; drain them, then arm the
        # scheduled moves (the terminal handler skips migrated/failover
        # statuses precisely so this path owns their resubmission).
        self._process_terminals()
        for fid, from_replica, reason in self._drain_moves:
            rec = self.requests.get(fid)
            if rec is not None and not rec.done:
                self._schedule_failover(rec, from_replica, reason)
        self._drain_moves = []

    def observe_retirement(self, replica: int, flagged: bool) -> None:
        """Feed one retirement's monitor verdict into the replica's
        flag-rate window AND the EWMA suspicion score (called from the
        terminal processing path).  The post-observation flag rate is
        public (gauges) — it is also what an adaptive adversary steers
        by, so the chaos feedback hook gets exactly the same number."""
        if not 0 <= replica < len(self.replicas):
            return
        rep = self.replicas[replica]
        rep.flags.append(1 if flagged else 0)
        if flagged:
            rep.total_flags += 1
        a = self.config.suspicion_ewma_alpha
        rep.suspicion = (1.0 - a) * rep.suspicion + a * (
            1.0 if flagged else 0.0)
        self._suspicion_gauge.set(rep.suspicion, replica=str(rep.index))
        self._update_suspicion_episode(rep, reason="flag_rate")
        if self.chaos is not None and hasattr(self.chaos,
                                              "on_flag_observed"):
            self.chaos.on_flag_observed(replica, flagged, rep.flag_rate)

    # -- adapter trust plane ----------------------------------------------

    def _observe_adapter_retirement(self, adapter: str,
                                    flagged: bool) -> None:
        """Feed one adapter-attributed retirement's monitor verdict into
        the ADAPTER's fleet-wide flag window.  Same window length and
        trip predicate as the replica ladder (flag_min_count /
        flag_rate_quarantine over flag_window) — but the evidence pools
        across every replica serving the adapter, and the trip
        quarantines the adapter EVERYWHERE in one step."""
        cfg = self.config
        win = self._adapter_flags.get(adapter)
        if win is None:
            win = self._adapter_flags[adapter] = deque(
                maxlen=cfg.flag_window)
        win.append(1 if flagged else 0)
        if adapter in self.quarantined_adapters:
            return  # already impounded; late stragglers add no verdict
        count = sum(win)
        rate = count / len(win)
        if count >= cfg.flag_min_count and rate >= cfg.flag_rate_quarantine:
            self._quarantine_adapter(adapter, "monitor_flag_rate", rate)

    def _note_adapter_impound(self, adapter: str, replica: int,
                              placement: Optional[dict]) -> None:
        """Remember an engine-side slot impound whose flag was
        ADAPTER-attributed.  The engine quarantines the slot at retire
        time (defence in depth — it cannot know fleet policy); once the
        fleet convicts the adapter the evidence belongs to the artifact
        and the slot is released (an already-convicted adapter's
        straggler releases immediately)."""
        slot = (placement or {}).get("slot", -1)
        if slot is None or slot < 0:
            return
        rep = self.replicas[replica]
        if adapter in self.quarantined_adapters:
            self._release_impound(rep, rep.gen, int(slot))
        else:
            self._adapter_impounds.setdefault(adapter, []).append(
                (replica, rep.gen, int(slot)))

    def _release_impound(self, rep: "_Replica", gen: int,
                         slot: int) -> None:
        if (rep.engine is not None and rep.gen == gen
                and slot in rep.engine.quarantined_slots):
            rep.engine.release_quarantine(slot)

    def _quarantine_adapter(self, adapter: str, reason: str,
                            flag_rate: float = 0.0) -> None:
        """Fleet-wide adapter quarantine: refuse new submissions naming
        the adapter, impound its pool page on EVERY replica (in-flight
        requests finish; the page frees at the last release), emit the
        typed event, bump the drill counter.  Replicas stay in service —
        the artifact is the convict, not the host."""
        if adapter in self.quarantined_adapters:
            return
        self.quarantined_adapters.add(adapter)
        self.counters["adapter_quarantines"] += 1
        for rep in self.replicas:
            if rep.engine is not None and hasattr(rep.engine,
                                                  "quarantine_adapter"):
                rep.engine.quarantine_adapter(adapter)
        # Conviction transfers the evidence: the slots the engines
        # impounded for THIS adapter's flags go back in service (the
        # replicas were never the suspects).
        for replica, gen, slot in self._adapter_impounds.pop(adapter, []):
            self._release_impound(self.replicas[replica], gen, slot)
        # The verdict is fleet-wide and permanent until an operator
        # readmits: every open request riding the adapter would sit in
        # an engine queue forever (admission refuses a quarantined
        # page's resolution) or keep streaming through the convicted
        # artifact.  Fail them NOW, loudly, with their own terminal
        # status — the fleet owns the verdict, so the fleet retires
        # them.
        for rec in list(self.requests.values()):
            if (rec.adapter == adapter and not rec.done
                    and rec.fid != self._terminal_fid):
                self._finalize_unserved(rec, "adapter_quarantined")
        logger.warning("fleet: adapter %r QUARANTINED fleet-wide "
                       "(%s, flag rate %.3f)", adapter, reason, flag_rate)
        if self.trace is not None:
            self.trace.emit(EventType.ADAPTER_QUARANTINE, adapter=adapter,
                            reason=reason,
                            flag_rate=round(flag_rate, 4),
                            tick=self.tick)
        if self.verdicts is not None:
            self.verdicts.append("adapter_quarantine", "quarantined",
                                 adapter=adapter, reason=reason,
                                 tick=self.tick)
        # The blast radius is adapter-keyed: every request that decoded
        # through the convicted artifact's page, on any replica.
        self._forensic_incident("adapter_quarantine", adapter=adapter,
                                trigger_type="adapter_quarantine",
                                extra={"flag_rate": round(flag_rate, 4)})

    def release_adapter_quarantine(self, adapter: str) -> None:
        """Operator-driven readmission of a quarantined adapter: clears
        the fleet verdict AND the stale evidence window (re-conviction
        must come from fresh behaviour), and lifts the refusal on every
        live replica."""
        self.quarantined_adapters.discard(adapter)
        self._adapter_flags.pop(adapter, None)
        for rep in self.replicas:
            if rep.engine is not None and hasattr(rep.engine,
                                                  "unquarantine_adapter"):
                rep.engine.unquarantine_adapter(adapter)

    def adapter_flag_rate(self, adapter: str) -> float:
        win = self._adapter_flags.get(adapter)
        return sum(win) / len(win) if win else 0.0

    def note_suspicion(self, replica: int, reason: str,
                       weight: float = 1.0) -> None:
        """Raise a replica's suspicion from a NON-flag signal — an
        anomaly-watcher episode (wired in ``_supervise``) or an
        attribution irregularity a reconciliation job attributes to the
        replica.  Folded into the same EWMA the flag verdicts feed, and
        marks the replica eligible for suspicion without
        ``suspicion_min_flags`` flag evidence."""
        if not 0 <= replica < len(self.replicas):
            return
        rep = self.replicas[replica]
        a = self.config.suspicion_ewma_alpha
        rep.suspicion = min(1.0,
                            (1.0 - a) * rep.suspicion + a * float(weight))
        rep.suspicion_noted = True
        self._suspicion_gauge.set(rep.suspicion, replica=str(rep.index))
        self._update_suspicion_episode(rep, reason=reason)

    def _update_suspicion_episode(self, rep: _Replica,
                                  reason: str) -> None:
        cfg = self.config
        suspected = (rep.suspicion >= cfg.suspicion_threshold
                     and (rep.total_flags >= cfg.suspicion_min_flags
                          or rep.suspicion_noted))
        if suspected and not rep.suspicion_episode:
            rep.suspicion_episode = True
            self.counters["suspicions"] += 1
            self._suspicion_counter.inc()
            logger.warning("fleet: replica %d SUSPECTED (score %.3f, "
                           "flag rate %.3f, %s)", rep.index,
                           rep.suspicion, rep.flag_rate, reason)
            if self.trace is not None:
                self.trace.emit(EventType.FLEET_SUSPICION,
                                replica=rep.index,
                                score=round(rep.suspicion, 4),
                                reason=reason,
                                flag_rate=round(rep.flag_rate, 4),
                                tick=self.tick)
            if self.verdicts is not None:
                self.verdicts.append("suspicion", "opened",
                                     replica=rep.index, reason=reason,
                                     tick=self.tick)
        elif (rep.suspicion_episode
              and rep.suspicion < cfg.suspicion_threshold / 2.0
              and rep.outvotes == 0):
            # Hysteresis: the episode closes only once the score decays
            # well below the threshold, so a borderline replica doesn't
            # open a fresh episode (and counter tick) per retirement.
            # An outvote on record PINS the episode open: a replica a
            # verdict has already gone against must stay under audit
            # until the ladder resolves (or a fresh generation resets
            # it) — otherwise an attacker could take one outvote, go
            # signal-quiet while still corrupting tokens, wait out the
            # EWMA decay, and never face the deciding vote.
            rep.suspicion_episode = False
            if self.verdicts is not None:
                self.verdicts.append("suspicion", "closed",
                                     replica=rep.index, reason=reason,
                                     tick=self.tick)

    # -- cross-replica verdict voting --------------------------------------

    def _maybe_vote(self, rec: _FleetRequest, result: ServeResult,
                    att: _Attempt) -> None:
        """Launch a verdict vote for a completed request that retired on
        a SUSPECTED (but still admitting — i.e. sub-threshold) replica:
        replay it on up to ``vote_k`` other admitting replicas with the
        request's own rng key.  One vote in flight per suspect keeps
        audit cost bounded and drill counts exact."""
        cfg = self.config
        if cfg.vote_k < 1 or result.status != "completed":
            return
        rep = self.replicas[att.replica]
        if (not rep.suspicion_episode or rep.vote_open
                or rep.state not in ADMITTING or rep.engine is None):
            return
        if rep.ladder_tripped(cfg):
            return  # the flag-rate ladder owns it this tick
        voters = sorted(
            (r for r in self.replicas
             if r.index != rep.index and r.state in ADMITTING
             and r.engine is not None),
            key=lambda r: (r.engine.load, r.index),
        )[:cfg.vote_k]
        if not voters:
            return
        accepted: List[Tuple[_Replica, int]] = []
        for voter in voters:
            local = voter.engine.submit(ServeRequest(
                prompt=rec.prompt, max_new_tokens=rec.max_new_tokens,
                temperature=rec.temperature, eos_id=rec.eos_id,
                rng=rec.rng, priority=rec.priority, tenant=rec.tenant,
                # Audit semantics: no user stream, no deadline, and the
                # replay's prompt blocks never enter the PrefixCache.
                publish_prefix=False,
            ))
            if local is not None:
                accepted.append((voter, local))
        if len(accepted) < min(cfg.vote_k, 2):
            # Quorum-or-nothing launch: a vote that cannot seat at
            # least two ballots (one at vote_k=1) could never convict
            # and would punish whoever dissented alone — abandon the
            # partial launch (backpressure) and retry at the suspect's
            # next retirement.
            for voter, local in accepted:
                voter.engine.cancel(local, status="vote_abandoned")
            return
        vote = _Vote(fid=rec.fid, target=rep.index,
                     original_hash=attribution.token_hash(result.tokens))
        for voter, local in accepted:
            vote.pending.add(voter.index)
            self._vote_ballots[(voter.index, local)] = vote
        rep.vote_open = True
        self.counters["votes"] += 1

    def _abandon_votes_targeting(self, index: int) -> None:
        """Drop every outstanding verdict vote whose TARGET generation
        is being torn down (crash rebuild, readmission probe): cancel
        the replay ballots and forget the vote — no counters, no
        outcome.  Without this, ``reset_trust_window`` clearing
        ``vote_open`` would let a fresh generation open a SECOND
        concurrent vote while the stale one still resolves against
        evidence from a pool that no longer exists."""
        stale = [(key, vote) for key, vote in self._vote_ballots.items()
                 if vote.target == index]
        for (voter, local), _vote in stale:
            self._vote_ballots.pop((voter, local), None)
            rep = self.replicas[voter]
            if rep.engine is not None:
                rep.engine.cancel(local, status="vote_abandoned")

    def _on_vote_ballot(self, vote: _Vote, replica: int,
                        result: ServeResult) -> None:
        vote.pending.discard(replica)
        completed = result.status == "completed"
        replay_hash = attribution.token_hash(result.tokens)
        vote.ballots[replica] = replay_hash if completed else None
        if self.ledger is not None:
            # The replay is evidence, not service: admitted False keeps
            # the one-admitted-record-per-fleet-id invariant, and the
            # hash is all the vote retains of the stream.
            self.ledger.append({
                "request_id": vote.fid, "status": "vote_replay",
                "admitted": False, "replica": replica,
                "vote_target": vote.target,
                "tokens": len(result.tokens),
                "token_hash": replay_hash,
            })
        if not vote.pending:
            self._resolve_vote(vote)

    def _resolve_vote(self, vote: _Vote) -> None:
        """Majority-vote the streams token-for-token (by token_hash —
        exact equality, no retained streams).  Outvoted = a dissenting
        hash shared by >= 2 replays that also outnumbers the agreeing
        ballots: a clean original beats any LONE faulty voter by
        construction, and split dissent convicts nobody."""
        cfg = self.config
        rep = self.replicas[vote.target]
        rep.vote_open = False
        counted = {r: h for r, h in vote.ballots.items() if h is not None}
        agree = [r for r, h in counted.items()
                 if h == vote.original_hash]
        dissent_by_hash: Dict[str, List[int]] = {}
        for r, h in counted.items():
            if h != vote.original_hash:
                dissent_by_hash.setdefault(h, []).append(r)
        top_dissent: List[int] = max(dissent_by_hash.values(),
                                     key=len, default=[])
        if len(counted) < 2:
            # Below quorum (abstentions shrank the ballot set): nobody
            # is convicted and nobody is suspected — one surviving
            # voter's word alone is evidence of nothing.
            outcome = "inconclusive"
        elif len(top_dissent) >= 2 and len(top_dissent) > len(agree):
            outcome = "outvoted"
            self.counters["outvotes"] += 1
            rep.outvotes += 1
            self.note_suspicion(vote.target, "outvoted")
            if (rep.outvotes >= cfg.vote_outvote_limit
                    and rep.state in ADMITTING and rep.engine is not None):
                # The suspect lost its Mth vote: same drain → quarantine
                # ladder the flag-rate trip takes — disagreement is the
                # verdict the sub-threshold attacker cannot tune away.
                self._start_trust_drain(rep, "verdict_outvoted")
        else:
            outcome = "confirmed"
            for h, voters in dissent_by_hash.items():
                for voter in voters:
                    # A minority dissenter disagreed with a confirmed
                    # stream: that VOTER is now suspect (symmetric
                    # catch for a lying replay replica).
                    self.note_suspicion(voter, "vote_dissent")
        self._vote_counter.inc(outcome=outcome)
        logger.warning("fleet: verdict vote on request %d (replica %d): "
                       "%s (agree %d, dissent %d)", vote.fid, vote.target,
                       outcome, len(agree), len(top_dissent))
        if self.trace is not None:
            self.trace.emit(EventType.VERDICT_VOTE, request_id=vote.fid,
                            replica=vote.target, outcome=outcome,
                            agree=len(agree), dissent=len(top_dissent),
                            outvotes=rep.outvotes, tick=self.tick)
        if self.verdicts is not None:
            self.verdicts.append("vote", outcome, replica=vote.target,
                                 request_id=vote.fid, tick=self.tick)

    # -- retries + hedges --------------------------------------------------

    def _run_retries_and_hedges(self) -> None:
        now = time.perf_counter()
        for rec in list(self.requests.values()):
            if rec.done:
                continue
            if (rec.deadline_at is not None and now > rec.deadline_at
                    and not rec.live):
                self._finalize_unserved(rec, "deadline_exceeded")
                continue
            if rec.retry_due is not None and self.tick >= rec.retry_due:
                # ONE FLEET_FAILOVER event per failover — emitted by
                # _schedule_failover with the replica the request
                # actually left; the destination rides the new
                # fleet.attempt span.  (A second emit here would double
                # the event-vs-counter reconciliation.)
                self._try_submit(rec, exclude=rec.excluded)
                # On failure: stay parked; deadline/liveness guards
                # bound it.
                continue
            if (self.config.hedge_deadline_s is not None
                    and rec.deadline_at is not None and not rec.hedged
                    and len(rec.live) == 1
                    and len(self.replicas) > 1
                    and rec.deadline_at - now
                    < self.config.hedge_deadline_s):
                primary = next(iter(rec.live.values()))
                if self._try_submit(rec,
                                    exclude={primary.replica}
                                    | rec.excluded) == "submitted":
                    rec.hedged = True
                    self.counters["hedges"] += 1
                    self._hedge_counter.inc()
                    if self.trace is not None:
                        att = max(rec.live.values(),
                                  key=lambda a: a.submit_t)
                        self.trace.emit(EventType.FLEET_HEDGE,
                                        request_id=rec.fid,
                                        replica=att.replica,
                                        primary=primary.replica)
        # Cancels issued while finalizing (hedge losers) queued terminal
        # records — settle them inside the same tick so a pruned record
        # is never looked up by a straggler.
        self._process_terminals()

    # -- reporting ---------------------------------------------------------

    def _set_state_gauge(self) -> None:
        by_state = {s: 0 for s in ReplicaState}
        tif = 0
        load = 0
        for rep in self.replicas:
            by_state[rep.state] += 1
            self._suspicion_gauge.set(rep.suspicion,
                                      replica=str(rep.index))
            if rep.engine is not None:
                load += rep.engine.load
                sched = getattr(rep.engine, "scheduler", None)
                if sched is not None:
                    tif += sched.tokens_in_flight
        for state, n in by_state.items():
            self._replicas_gauge.set(float(n), state=state.value)
        if self._roles_active:
            for role in ("prefill", "decode"):
                n = sum(1 for r in self.replicas if r.role == role
                        and r.state is not ReplicaState.RETIRED)
                self._pool_gauge.set(float(n), role=role)
        self._tif_gauge.set(float(tif))
        self._queue_gauge.set(float(load))
        self._chips_gauge.set(float(self.chips_in_service()))
        if self._classq is not None:
            for name, depth in self._classq.depth_by_class().items():
                self._classq_gauge.set(float(depth), slo_class=name)

    def chips_in_service(self) -> int:
        """Devices the fleet occupies: the replicas × model-shards grid
        summed (each replica counts its TP group width) — the capacity
        dimension a scale-OUT and a scale-UP both grow, each along its
        own axis."""
        return sum(r.tp for r in self._in_service())

    @property
    def open_requests(self) -> int:
        """Accepted-but-unfinished fleet requests (class-queued, live
        or between retries) — the closed-loop driver's in-flight
        count."""
        return sum(1 for r in self.requests.values() if not r.done)

    @property
    def busy(self) -> bool:
        # Outstanding vote ballots keep the loop live: a vote's replays
        # must resolve (and their quarantine verdict land) even after
        # the last user request retired.
        return (any(not r.done for r in self.requests.values())
                or bool(self._vote_ballots))

    def drain_results(self) -> Dict[int, FleetResult]:
        """Return finished results and clear them — the bounded-memory
        retrieval API for long-lived fleet loops (engine parity)."""
        out = self.results
        self.results = {}
        return out

    def states(self) -> Dict[int, str]:
        return {r.index: r.state.value for r in self.replicas}

    def verify_attribution(self) -> Tuple[bool, List[str]]:
        """Reconcile the fleet ledger against every replica
        GENERATION's allocator journal (retained across restarts)."""
        if self.ledger is None:
            raise ValueError("fleet has no attribution ledger attached")
        return attribution.verify_attribution(self.ledger.records(),
                                              self.journals)

    def metrics_summary(self) -> Dict[str, Any]:
        """Fleet rollup: terminal statuses, recovery counters, replica
        states, canonical-stream goodput."""
        statuses: Dict[str, int] = {}
        tokens = 0
        for res in self.results.values():
            statuses[res.status] = statuses.get(res.status, 0) + 1
            if res.status == "completed":
                tokens += len(res.tokens)
        out = {
            "requests": len(self.requests),
            "statuses": statuses,
            "completed_tokens": tokens,
            "replica_states": self.states(),
            "replica_suspicion": {r.index: round(r.suspicion, 4)
                                  for r in self.replicas},
            "ticks": self.tick,
            **{f"fleet_{k}": v for k, v in self.counters.items()},
        }
        slo_active = {
            rep.index: rep.engine.slo.active
            for rep in self.replicas
            if rep.engine is not None
            and getattr(rep.engine, "slo", None) is not None
        }
        if slo_active:
            out["replica_slo_active"] = slo_active
        if self._classes:
            out["per_class"] = {
                c.name: {**self._class_stats[c.name],
                         **self._class_latency.summary(c.name)}
                for c in self._classes
            }
            out["class_queue_depth"] = self._classq.depth_by_class()
        if self.autoscaler is not None:
            out["replicas_in_service"] = len(self._in_service())
            out["replica_trace"] = list(self.replica_trace)
        if self._adapter_flags or self.quarantined_adapters:
            out["adapter_flag_rates"] = {
                name: round(self.adapter_flag_rate(name), 4)
                for name in sorted(self._adapter_flags)}
            out["quarantined_adapters"] = sorted(self.quarantined_adapters)
        return out
