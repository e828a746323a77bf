"""The repo-specific contract tables the rules consult.

Each table is distilled from a shipped bug or an explicitly documented
module contract — when a module gains or sheds a contract (e.g. a new
host-only CLI, a new tick-deterministic controller), THIS file is the
one place to update; the rules read it through :class:`~.engine.
LintConfig`, so tests can substitute synthetic tables for fixtures.

Paths are repo-relative posix strings matched with :func:`fnmatch.
fnmatch` (``*`` crosses ``/`` — ``trustworthy_dl_tpu/obs/*.py`` covers
the whole subtree).
"""

from __future__ import annotations

#: Modules whose decisions must be reproducible from (seed, tick) alone
#: so chaos/fleet drills can pin exact counts (``FaultPlan.predict*``,
#: ``autoscale_pressure``): no wall clocks, no unseeded RNGs, no
#: cross-process-nondeterministic set iteration.  serve/control.py and
#: chaos/plan.py document this contract in their module docstrings;
#: chaos/adversary.py's controller is ONE pure function shared with
#: ``predict_attacker_trajectory``; obs/sentinel.py verdicts must not
#: depend on when the comparison runs.
DETERMINISTIC_MODULES = (
    "trustworthy_dl_tpu/serve/control.py",
    "trustworthy_dl_tpu/chaos/plan.py",
    "trustworthy_dl_tpu/chaos/adversary.py",
    "trustworthy_dl_tpu/obs/sentinel.py",
)

#: Modules documented host-only / jax-free: the obs CLI path must work
#: on a machine with a broken accelerator backend, the sentinel diffs
#: artifacts offline, the control plane runs inside the fleet tick, and
#: the linter lints itself.  A module-level import chain from any of
#: these that reaches ``jax``/``jaxlib`` is a contract break even when
#: the jax name is never used (importing it initialises the backend).
HOST_ONLY_MODULES = (
    "trustworthy_dl_tpu/obs/sentinel.py",
    "trustworthy_dl_tpu/obs/events.py",
    "trustworthy_dl_tpu/obs/meta.py",
    "trustworthy_dl_tpu/obs/recorder.py",
    "trustworthy_dl_tpu/obs/registry.py",
    "trustworthy_dl_tpu/obs/forensics.py",
    "trustworthy_dl_tpu/obs/verdicts.py",
    "trustworthy_dl_tpu/serve/control.py",
    "trustworthy_dl_tpu/cli.py",
    "trustworthy_dl_tpu/utils/io.py",
    "trustworthy_dl_tpu/analysis/*.py",
)

#: External top-level module names whose import breaks host-only purity.
DEVICE_RUNTIME_MODULES = frozenset({"jax", "jaxlib"})

#: Modules whose loops are serving/training hot paths: a ``jnp.array``
#: LITERAL built per iteration is a fresh device constant (and, closed
#: over a varying Python scalar, a fresh jit cache key — the PR 10
#: threshold-pushback storm pattern).
HOT_LOOP_MODULES = (
    "trustworthy_dl_tpu/serve/scheduler.py",
    "trustworthy_dl_tpu/serve/engine.py",
    "trustworthy_dl_tpu/engine/step.py",
    "trustworthy_dl_tpu/engine/trainer.py",
    # Holds the serving programs' layer loop, which CARRIES the paged KV
    # pool [L, NB, BLOCK, H·Dh] — the one shape on which the in-place row
    # write, the kernels' block and the resting layout agree
    # (tests/test_chip_compile.py pins that no program copies it).
    "trustworthy_dl_tpu/models/generate.py",
    # The paged-attention kernel module runs INSIDE every paged decode
    # program (its wrapper traces per layer per tick) — a per-call
    # device constant here is a per-tick constant upload.
    "trustworthy_dl_tpu/ops/paged_attention.py",
)

#: module -> function names forming the latency-critical dispatch paths
#: where an accidental device->host pull (``np.asarray``/``float``/
#: ``.item()`` on a traced value) serialises the pipeline.  The ONE
#: intentional pull per tick is inline-suppressed at the site.
HOST_SYNC_SCOPES = {
    "trustworthy_dl_tpu/serve/scheduler.py": (
        "decode_tick", "_spec_tick", "_dispatch_prefill", "_prefill_call",
        "_dispatch_whole_prompt", "_chunk_args", "_dispatch_chunk",
        "_record_prefill", "_dispatch_decode", "_record_decode", "admit",
    ),
    "trustworthy_dl_tpu/engine/trainer.py": ("train_epoch",),
    # The kernel dispatch wrappers trace inside jitted serve programs:
    # any host pull of a traced value here would serialise every decode
    # tick (there is no intentional pull — these scopes allow zero).
    "trustworthy_dl_tpu/ops/paged_attention.py": (
        "paged_attention", "paged_prefill_attention", "_attend",
        "fused_verify_tail", "adapter_delta", "logit_trust_stats",
    ),
}

#: Modules that write persistent artifacts (checkpoints, ledgers,
#: reports, experiment results): ``open(path, "w")`` without a
#: tmp-then-``os.replace`` swap in the same function truncates the old
#: artifact before the new one is durable (the PR 2 topology-sidecar
#: bug class).
ARTIFACT_MODULES = (
    "trustworthy_dl_tpu/obs/*.py",
    "trustworthy_dl_tpu/experiments/*.py",
    "trustworthy_dl_tpu/engine/checkpoint.py",
    "trustworthy_dl_tpu/trust/manager.py",
    "trustworthy_dl_tpu/detect/detector.py",
    "trustworthy_dl_tpu/serve/*.py",
    "trustworthy_dl_tpu/utils/*.py",
)

#: Modules whose JSON artifacts must carry the run_metadata stamp
#: (VERDICT weak #5: numbers published without the platform that
#: produced them).  Mirrors tests/test_obs.py's standing contract test.
STAMPED_ARTIFACT_MODULES = (
    "trustworthy_dl_tpu/experiments/*.py",
)

#: Recovery/supervision paths where a bare ``except:`` can swallow
#: KeyboardInterrupt/SystemExit and wedge the very ladder that exists
#: to recover (supervisor retries, fleet drains, chaos unwinds,
#: checkpoint commit).
RECOVERY_MODULES = (
    "trustworthy_dl_tpu/engine/supervisor.py",
    "trustworthy_dl_tpu/engine/checkpoint.py",
    "trustworthy_dl_tpu/serve/fleet.py",
    "trustworthy_dl_tpu/serve/engine.py",
    "trustworthy_dl_tpu/chaos/*.py",
)

#: Function-name patterns (fnmatch) of the pure prediction functions
#: drills pin against: ``FaultPlan.predict*``,
#: ``predict_attacker_trajectory``, ``autoscale_pressure``,
#: ``diurnal_rate``/``predicted_replicas``.  Pure means: output from
#: arguments only — reading module-global MUTABLE state (or declaring
#: ``global``) makes the pin silently dependent on call history.
PREDICT_FUNCTION_PATTERNS = (
    "predict_*",
    "autoscale_pressure",
    "diurnal_rate",
    "predicted_replicas",
)

#: The label-name vocabulary dashboards key on.  A label outside this
#: set is either a typo (``tenent``) or a new dimension that must be
#: added HERE (and to the dashboards) deliberately, not slipped in.
KNOWN_METRIC_LABELS = frozenset({
    "action", "adapter", "device", "direction", "dtype", "expert", "kind",
    "metric", "node", "outcome", "path", "phase", "program", "reason", "replica",
    "role", "scope", "signal", "slo", "slo_class", "stage", "state",
    "status", "tenant", "to_state", "type",
})

#: Metric-name prefix every registered literal must carry (the
#: Prometheus surface's naming promise).
METRIC_PREFIX = "tddl_"

#: The flight-dump / incident reason vocabulary.  Incident artifacts
#: pair with their flight dump and their trigger events BY reason
#: string — a typo'd reason silently orphans the incident from its
#: trigger (the timeline renders empty) — so every literal ``reason``
#: passed to ``dump_flight``/``recorder.dump``/``assemble`` must come
#: from this registered set.  New episode classes add their reason HERE
#: first (and to the README catalog), not inline.
ARTIFACT_REASONS = frozenset({
    # training supervisor ladder (engine/supervisor.py)
    "guard_trip", "rollback", "preemption",
    # watcher-driven dumps (obs/slo.py, anomaly.py, compilewatch.py)
    "slo_breach", "anomaly", "compile_storm",
    # fleet forensic episodes (serve/fleet.py)
    "replica_quarantine", "replica_preempt", "adapter_quarantine",
    "migration_refused",
    # operator-initiated artifacts (examples, tests, CLI)
    "drill", "manual",
})

#: The adapter-resource locality contract (PR 16): the per-slot adapter
#: page-table row and the pool's PartitionSpecs each have exactly ONE
#: spelling, in serve/adapters.py — the compile-once pin of the paged
#: decode/prefill programs keys on that table's shape and the pool's
#: sharding, so a second spelling elsewhere is a fork of the pin, not a
#: convenience.  A definition of either name, or an adapter-targeted
#: ``PartitionSpec(...)`` construction, outside the home module is a
#: finding.
ADAPTER_HOME_MODULE = "trustworthy_dl_tpu/serve/adapters.py"
ADAPTER_LOCALITY_NAMES = ("adapter_page_row", "adapter_partition_specs")

#: The sharding-registry locality contract (PR 19): EVERY
#: ``PartitionSpec(...)`` in the package resolves through the
#: logical-axis rule table in core/sharding.py — the one place the
#: logical->mesh axis mapping is spelled.  A PartitionSpec constructed
#: anywhere else (including under a ``... as P`` alias) bypasses the
#: registry: it hard-codes a mesh-axis name that the rule table can no
#: longer retarget, and it forks the layout the compile-once pins and
#: the elastic migrations key on.  Modules with a sanctioned reason to
#: spell specs directly are whitelisted HERE, deliberately.
SHARDING_HOME_MODULE = "trustworthy_dl_tpu/core/sharding.py"
SHARDING_SPEC_WHITELIST = (
    # The adapter pool's home module: its spec spellings are already
    # governed (and scoped) by the adapter-locality rule above.
    ADAPTER_HOME_MODULE,
)

#: Default committed baseline of grandfathered findings (repo-relative).
DEFAULT_BASELINE = "tddl_lint_baseline.json"


def event_type_members():
    """Names of the ``EventType`` enum — imported lazily from the
    (host-only) events module so contract tables stay import-light."""
    from trustworthy_dl_tpu.obs.events import EventType

    return frozenset(EventType.__members__)
