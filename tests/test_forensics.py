"""Incident forensics engine (obs/forensics.py + obs/verdicts.py):
causal-timeline assembly, blast-radius attribution, the durable
VerdictStore, and the offline ``trustworthy-dl-obs incident`` CLI.

Everything here except the serve-CLI integration drill is host-only and
fast: the assembler and store are pure artifact plumbing by contract
(``analysis/contracts.py`` HOST_ONLY_MODULES), so these tests pin exact
sets against hand-built ledgers and traces.  The fleet/preempt drills
that reconcile live assembly with ``predict_fleet()`` ride inside
tests/test_fleet.py and tests/test_migrate.py next to the drills they
extend.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from trustworthy_dl_tpu.obs.forensics import (
    ACTION_EVENTS,
    INCIDENT_SCHEMA_VERSION,
    IncidentAssembler,
    SIGNAL_EVENTS,
    blast_radius,
    find_incident,
    load_incidents,
    render_blast,
    render_incident,
)
from trustworthy_dl_tpu.obs.verdicts import VERDICT_OUTCOMES, VerdictStore

pytestmark = pytest.mark.forensics

REPO = Path(__file__).resolve().parent.parent


class RecordingTrace:
    def __init__(self):
        self.events = []

    def emit(self, type, **data):
        self.events.append({"type": getattr(type, "value", type), **data})


# ---------------------------------------------------------------------------
# VerdictStore: the PerfLedger pattern, verbatim
# ---------------------------------------------------------------------------


def test_verdict_store_round_trip_and_stamping(tmp_path):
    store = VerdictStore(str(tmp_path / "VERDICTS.jsonl"))
    entry = store.append("vote", "outvoted", replica=2, request_id=7,
                         reason="verdict_outvoted", tick=9)
    assert entry["kind"] == "vote" and entry["replica"] == 2
    store.append("quarantine", "quarantined", replica=2, tick=11)
    store.append("adapter_quarantine", "quarantined",
                 adapter="tenant-a", tenant="a")
    rows = store.read()
    assert [r["kind"] for r in rows] == ["vote", "quarantine",
                                        "adapter_quarantine"]
    # Every row is run_metadata-stamped — cross-run aggregation needs
    # to know which platform produced each verdict.
    assert all(r["run_metadata"] for r in rows)
    assert all(r["t"] > 0 for r in rows)
    # A second store over the same file ACCUMULATES (cross-run).
    again = VerdictStore(str(tmp_path / "VERDICTS.jsonl"))
    again.append("suspicion", "opened", replica=0)
    assert len(again.read()) == 4


def test_verdict_store_outcome_vocabulary_is_closed(tmp_path):
    store = VerdictStore(str(tmp_path / "v.jsonl"))
    with pytest.raises(ValueError, match="unknown verdict outcome"):
        store.append("vote", "maybe", replica=0)
    # The vocabulary is exactly the counter's label set.
    assert set(VERDICT_OUTCOMES) == {
        "opened", "closed", "confirmed", "outvoted", "inconclusive",
        "quarantined", "readmitted", "recorded"}
    with pytest.raises(ValueError):
        VerdictStore(str(tmp_path / "w.jsonl"), keep=0)


def test_verdict_store_keep_trims_and_tolerates_torn_lines(tmp_path):
    path = tmp_path / "v.jsonl"
    store = VerdictStore(str(path), keep=5)
    for i in range(8):
        store.append("vote", "confirmed", replica=i)
    rows = store.read()
    assert len(rows) == 5                       # file itself is bounded
    assert [r["replica"] for r in rows] == [3, 4, 5, 6, 7]
    # A torn final line (crash mid-append) loses one row, not the file.
    with open(path, "a") as f:
        f.write('{"kind": "vote", "outco')
    assert len(store.read()) == 5
    # ...and the next append rewrites a clean file.
    store.append("vote", "confirmed", replica=8)
    assert [r["replica"] for r in store.read()] == [4, 5, 6, 7, 8]
    # Missing file reads empty, never raises.
    assert VerdictStore(str(tmp_path / "nope.jsonl")).read() == []


def test_verdict_store_history_and_priors(tmp_path):
    store = VerdictStore(str(tmp_path / "v.jsonl"))
    store.append("suspicion", "opened", replica=2, reason="attribution")
    store.append("vote", "outvoted", replica=2, request_id=3)
    store.append("quarantine", "quarantined", replica=2)
    store.append("incident", "recorded", replica=2,
                 incident_id="incident_000_replica_quarantine")
    store.append("vote", "confirmed", replica=1)
    store.append("adapter_quarantine", "quarantined", adapter="lora-x",
                 tenant="acme")
    assert [r["kind"] for r in store.history(replica=2)] == [
        "suspicion", "vote", "quarantine", "incident"]
    assert store.history(replica=2, tenant="acme") == []
    # priors(): the exact ROADMAP-5a read interface — per-subject
    # (kind, outcome) counts plus the incident ids on record.
    priors = store.priors()
    rep2 = priors["replicas"]["2"]
    assert rep2["counts"] == {"suspicion:opened": 1, "vote:outvoted": 1,
                              "quarantine:quarantined": 1,
                              "incident:recorded": 1}
    assert rep2["incidents"] == ["incident_000_replica_quarantine"]
    assert priors["replicas"]["1"]["counts"] == {"vote:confirmed": 1}
    assert priors["tenants"]["acme"]["counts"] == {
        "adapter_quarantine:quarantined": 1}
    assert priors["adapters"]["lora-x"]["counts"] == {
        "adapter_quarantine:quarantined": 1}


def test_verdict_store_counter_and_trace(tmp_path):
    from trustworthy_dl_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    trace = RecordingTrace()
    store = VerdictStore(str(tmp_path / "v.jsonl"), registry=reg,
                         trace=trace)
    store.append("vote", "outvoted", replica=1)
    store.append("quarantine", "quarantined", replica=1)
    store.append("quarantine", "quarantined", replica=2)
    counter = reg.counter("tddl_verdicts_total", "", labels=("outcome",))
    assert counter.value(outcome="outvoted") == 1
    assert counter.value(outcome="quarantined") == 2
    verdicts = [e for e in trace.events if e["type"] == "verdict"]
    assert len(verdicts) == 3
    assert verdicts[0]["kind"] == "vote"
    assert verdicts[0]["outcome"] == "outvoted"


# ---------------------------------------------------------------------------
# blast_radius: exact attribution from ledger records
# ---------------------------------------------------------------------------


def _rec(rid, attempts, admitted=True, **kw):
    return dict({"request_id": rid, "admitted": admitted,
                 "attempts": attempts}, **kw)


def test_blast_radius_names_exactly_the_touching_requests():
    records = [
        # Decoded off the suspect generation's blocks: IN.
        _rec(0, [{"journal": "2:0", "layout": "paged",
                  "block_ids": [4, 5]}]),
        # Ran on a DIFFERENT replica: OUT.
        _rec(1, [{"journal": "1:0", "layout": "paged",
                  "block_ids": [9]}]),
        # Attempted on the suspect but NEVER PLACED (no blocks, no
        # slot): OUT — an unplaced attempt must not inflate the radius.
        _rec(2, [{"journal": "2:0", "layout": None, "block_ids": [],
                  "slot": -1}]),
        # Migrated OFF the suspect before it was quarantined — the
        # stream started on suspect blocks; cross-replica provenance
        # pulls it IN.
        _rec(3, [{"journal": "0:0", "layout": "paged", "block_ids": [7],
                  "migrated_from": {"journal": "2:0", "replica": 2,
                                    "block_ids": [1, 2]}}]),
        # Hedge loser (admitted False): skipped outright.
        _rec(4, [{"journal": "2:0", "layout": "paged",
                  "block_ids": [8]}], admitted=False),
        # Stripe layout: a seated slot counts as placement.
        _rec(5, [{"journal": "2:0", "layout": "stripe", "slot": 1}]),
    ]
    radius = blast_radius(records, suspect_journals=["2:0"])
    assert radius["requests"] == [0, 3, 5]      # no over, no under
    assert radius["via"]["0"] == [{"journal": "2:0", "blocks": [4, 5]}]
    assert radius["via"]["3"] == [{"journal": "2:0", "blocks": [1, 2],
                                   "migrated_from": 2}]
    # The union of suspect blocks ever touched, per journal.
    assert radius["suspect_blocks"] == {"2:0": [1, 2, 4, 5]}


def test_blast_radius_adapter_and_tenant_reach():
    records = [
        _rec(0, [{"journal": "0:0", "block_ids": [1]}],
             adapter="lora-x", adapter_page=3),
        _rec(1, [{"journal": "1:0", "block_ids": [2]}], tenant="acme"),
        _rec(2, [{"journal": "1:0", "block_ids": [3]}],
             adapter="lora-y"),
    ]
    radius = blast_radius(records, adapter="lora-x", tenant="acme")
    assert radius["requests"] == [0, 1]
    assert radius["via"]["0"] == [{"adapter": "lora-x",
                                   "adapter_page": 3}]
    assert radius["via"]["1"] == [{"tenant": "acme"}]
    # Legacy records without an attempts list fall back to the record
    # itself as the single attempt.
    flat = [{"request_id": 9, "admitted": True, "journal": "2:0",
             "layout": "paged", "block_ids": [5]}]
    assert blast_radius(flat, suspect_journals=["2:0"])["requests"] == [9]


# ---------------------------------------------------------------------------
# IncidentAssembler: causal chain + artifact round-trip
# ---------------------------------------------------------------------------


def _episode_events():
    """A scripted suspect-2 episode with a bystander replica 1."""
    return [
        {"type": "fleet_suspicion", "replica": 2, "score": 0.4,
         "reason": "attribution"},                             # seq 1
        {"type": "serve_admit", "request_id": 0, "replica": 2},
        {"type": "fleet_suspicion", "replica": 1, "score": 0.1,
         "reason": "attribution"},        # bystander: excluded
        {"type": "verdict_vote", "request_id": 0, "replica": 2,
         "outcome": "outvoted"},                               # seq 4
        {"type": "replica_transition", "replica": 2,
         "from_state": "healthy", "to_state": "draining",
         "reason": "verdict_outvoted"},                        # seq 5
        {"type": "kv_migration", "request_id": 0, "from_replica": 2,
         "to_replica": 0, "blocks": 2, "reason": "drain"},     # seq 6
        {"type": "replica_transition", "replica": 2,
         "from_state": "draining", "to_state": "quarantined",
         "reason": "verdict_outvoted"},                        # seq 7
        {"type": "fleet_suspicion", "replica": 2, "score": 0.9,
         "reason": "late"},               # after trigger: excluded
    ]


def test_assembler_builds_causal_chain_and_writes_artifact(tmp_path):
    from trustworthy_dl_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    trace = RecordingTrace()
    trace.events.extend(_episode_events())
    verdicts = VerdictStore(str(tmp_path / "V.jsonl"))
    asm = IncidentAssembler(str(tmp_path), trace=trace,
                            verdicts=verdicts, registry=reg)
    records = [
        _rec(0, [{"journal": "0:0", "block_ids": [3],
                  "migrated_from": {"journal": "2:0", "replica": 2,
                                    "block_ids": [1, 2]}}]),
        _rec(1, [{"journal": "1:0", "block_ids": [9]}]),
    ]
    path = asm.assemble(
        "replica_quarantine", tick=7, suspects=[2],
        suspect_journals=["2:0"], trigger_type="replica_transition",
        counters={"quarantines": 1}, records=records,
        extra={"transition_reason": "verdict_outvoted"})
    assert path and Path(path).name == \
        "incident_000_replica_quarantine.json"
    inc = json.loads(Path(path).read_text())
    assert inc["schema_version"] == INCIDENT_SCHEMA_VERSION
    # Trigger = the LAST matching transition (the quarantine, seq 7,
    # not the drain at seq 5); seq ids thread back into the trace.
    assert inc["trigger"]["seq"] == 7
    assert inc["trigger"]["to_state"] == "quarantined"
    # Contributing signals: suspect-2 signals at or before the trigger
    # — the bystander's and the post-trigger one are excluded.
    assert [(e["type"], e["seq"]) for e in inc["contributing"]] == [
        ("fleet_suspicion", 1), ("verdict_vote", 4)]
    # Actions: everything the control plane did about replica 2.
    assert [(e["type"], e["seq"]) for e in inc["actions"]] == [
        ("replica_transition", 5), ("kv_migration", 6),
        ("replica_transition", 7)]
    assert inc["blast_radius"]["requests"] == [0]
    assert inc["counters"] == {"quarantines": 1}
    assert inc["extra"]["transition_reason"] == "verdict_outvoted"
    # run_metadata-stamped like every other artifact.
    assert inc["run_metadata"]
    # Side channels: metric counter, verdict row, trace event.
    counter = reg.counter("tddl_incidents_total", "", labels=("reason",))
    assert counter.value(reason="replica_quarantine") == 1
    assert verdicts.read()[-1]["incident_id"] == inc["incident_id"]
    assert trace.events[-1]["type"] == "incident"
    assert trace.events[-1]["incident_id"] == inc["incident_id"]


def test_assembler_pairs_with_flight_dump_index(tmp_path):
    asm = IncidentAssembler(str(tmp_path))
    path = asm.assemble("slo_breach",
                        flight_path=str(tmp_path /
                                        "flight_007_slo_breach.json"))
    assert Path(path).name == "incident_007_slo_breach.json"
    # Without a flight dump the private index continues PAST the paired
    # one — ids never collide.
    path2 = asm.assemble("manual")
    assert Path(path2).name == "incident_008_manual.json"
    # With no matching trace event the trigger is explicitly synthetic.
    inc = json.loads(Path(path).read_text())
    assert inc["trigger"]["synthetic"] is True
    assert asm.counts_by_reason() == {"manual": 1, "slo_breach": 1}


def test_assembler_in_memory_mode_counts_without_writing(tmp_path):
    asm = IncidentAssembler()                    # the in-memory mode
    assert asm.assemble("replica_quarantine", suspects=[1]) is None
    assert asm.assemble("replica_quarantine", suspects=[2]) is None
    assert asm.counts_by_reason() == {"replica_quarantine": 2}
    assert list(tmp_path.iterdir()) == []


def test_load_and_find_incidents_tolerate_torn_artifacts(tmp_path):
    asm = IncidentAssembler(str(tmp_path))
    asm.assemble("replica_quarantine", suspects=[2])
    asm.assemble("migration_refused", suspects=[0],
                 refusals=[{"replica": 1, "reason": "claim_refused"}])
    # A torn artifact (crash mid-rename never leaves one, but a full
    # disk can): skipped, not fatal.
    (tmp_path / "incident_099_torn.json").write_text('{"incident')
    (tmp_path / "not_an_incident.json").write_text("{}")
    incidents = load_incidents(str(tmp_path))
    assert [i["reason"] for i in incidents] == ["replica_quarantine",
                                                "migration_refused"]
    # find: full id, bare index, reason substring.
    assert find_incident(str(tmp_path),
                         "incident_000_replica_quarantine")["reason"] \
        == "replica_quarantine"
    assert find_incident(str(tmp_path), "1")["reason"] == \
        "migration_refused"
    assert find_incident(str(tmp_path), "refused")["reason"] == \
        "migration_refused"
    assert find_incident(str(tmp_path), "nope") is None
    assert load_incidents(str(tmp_path / "missing")) == []


def test_renderers_cover_timeline_refusals_and_blast(tmp_path):
    trace = RecordingTrace()
    trace.events.extend(_episode_events())
    asm = IncidentAssembler(str(tmp_path), trace=trace)
    records = [
        _rec(0, [{"journal": "2:0", "block_ids": [1, 2]}],
             adapter="lora-x", adapter_page=5),
    ]
    asm.assemble("replica_quarantine", tick=7, suspects=[2],
                 suspect_journals=["2:0"], adapter="lora-x",
                 trigger_type="replica_transition", records=records,
                 refusals=[{"replica": 1, "reason": "claim_refused"}],
                 counters={"quarantines": 1, "drains": 1, "crashes": 0})
    inc = load_incidents(str(tmp_path))[0]
    shown = render_incident(inc)
    assert "incident_000_replica_quarantine" in shown
    assert "trigger:" in shown and "to_state=quarantined" in shown
    assert "contributing signals (2):" in shown
    assert "actions taken (3):" in shown
    assert "replica 1: claim_refused" in shown
    assert "quarantines=1" in shown and "crashes" not in shown
    blast = render_blast(inc)
    assert "request 0:" in blast
    assert "journal 2:0 blocks [1, 2]" in blast
    assert "adapter lora-x page 5" in blast


def test_incident_schema_round_trip_contract(tmp_path):
    """CONTRACT: the incident artifact's top-level key set is the
    schema — the offline CLI and the training-side prior consumer both
    parse these artifacts with no producer in the process, so a key
    rename is a cross-plane break, not a refactor."""
    asm = IncidentAssembler(str(tmp_path))
    path = asm.assemble("replica_quarantine", step=3, tick=9,
                        suspects=[2], suspect_journals=["2:0"],
                        extra={"k": "v"})
    inc = json.loads(Path(path).read_text())
    assert set(inc) == {
        "schema_version", "incident_id", "reason", "step", "tick",
        "suspect_replicas", "suspect_journals", "adapter", "tenant",
        "flight_dump", "trigger", "contributing", "actions",
        "blast_radius", "counters", "refused_destinations", "perf_tail",
        "t", "run_metadata", "extra"}
    assert set(inc["blast_radius"]) == {"requests", "via",
                                        "suspect_blocks"}
    # Signal/action taxonomies are disjoint: an event is evidence or a
    # response, never both — the timeline renders each exactly once.
    assert not (SIGNAL_EVENTS & ACTION_EVENTS)


# ---------------------------------------------------------------------------
# migrate.py refusal hook + fleet multi-destination walk payloads
# ---------------------------------------------------------------------------


def test_migrate_request_reports_refusal_class():
    from trustworthy_dl_tpu.serve.migrate import migrate_request

    class NoExport:
        def export_request(self, local_id):
            return None

    refusals = []
    out = migrate_request(NoExport(), object(), 0,
                          on_refuse=refusals.append)
    assert out is None and refusals == ["src_not_migratable"]

    class Exports:
        def export_request(self, local_id):
            from types import SimpleNamespace

            return {"task": SimpleNamespace(adapter=None),
                    "block_ids": [1, 2]}

    class RefusesClaim:
        class scheduler:
            @staticmethod
            def claim_migration(n, adapter):
                return None

    refusals = []
    out = migrate_request(Exports(), RefusesClaim(), 0,
                          on_refuse=refusals.append)
    assert out is None and refusals == ["claim_refused"]


# ---------------------------------------------------------------------------
# Serve CLI integration: real artifacts, jax-free offline rendering
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_incident_cli_is_jax_free_over_real_serve_artifacts(tmp_path):
    """End-to-end: a real ``trustworthy-dl-serve`` run with --obs-dir
    leaves trace/ledger/VERDICTS artifacts; an incident assembled
    OFFLINE from those artifacts (the post-mortem workflow: the run is
    gone, the files remain) renders through ``trustworthy-dl-obs
    incident`` in a fresh process that never imports jax — the
    CLI-side enforcement of the HOST_ONLY_MODULES contract, same
    pattern as tests/test_lint.py's lint-CLI pin."""
    from trustworthy_dl_tpu.cli import serve_main

    obs_dir = tmp_path / "obs"
    rc = serve_main(
        ["--checkpoint-dir", str(tmp_path / "ckpt"),
         "--num-requests", "3", "--max-new-tokens", "4",
         "--prompt-len", "4", "--max-seq", "32", "--max-slots", "2",
         "--queue-limit", "8", "--obs-dir", str(obs_dir)],
        model_overrides=dict(n_layer=2, n_embd=32, n_head=4,
                             vocab_size=128, n_positions=32),
    )
    assert rc == 0
    assert (obs_dir / "trace.jsonl").exists()
    assert (obs_dir / "attribution.jsonl").exists()

    # Offline assembly from the run's artifacts alone — no session, no
    # engine, no jax: the trace walks from disk, the ledger reloads.
    code = (
        "import sys\n"
        "from trustworthy_dl_tpu.obs.attribution import read_ledger\n"
        "from trustworthy_dl_tpu.obs.forensics import IncidentAssembler\n"
        "from trustworthy_dl_tpu.cli import obs_main\n"
        f"obs_dir = {str(obs_dir)!r}\n"
        "_, records = read_ledger(obs_dir + '/attribution.jsonl')\n"
        "asm = IncidentAssembler(obs_dir,\n"
        "    trace_path=obs_dir + '/trace.jsonl', ledger=records)\n"
        "path = asm.assemble('manual', suspect_journals=['0:0'])\n"
        "assert path, path\n"
        "assert obs_main(['incident', 'list', '--dir', obs_dir]) == 0\n"
        "assert obs_main(['incident', 'show', 'manual',\n"
        "                 '--dir', obs_dir]) == 0\n"
        "assert obs_main(['incident', 'blast', '0',\n"
        "                 '--dir', obs_dir]) == 0\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in\n"
        "       ('jax', 'jaxlib')]\n"
        "assert not bad, bad\n"
        "print('ok')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout
    incident = find_incident(str(obs_dir), "manual")
    assert incident is not None
    # The offline assembly consumed the run's REAL trace: the serve
    # run's own events (run_start at minimum) are on the timeline side
    # and every admitted request left a ledger record it could walk.
    assert incident["run_metadata"]
