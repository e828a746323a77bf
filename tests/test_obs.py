"""Unified telemetry (trustworthy_dl_tpu/obs/): registry semantics,
event-schema validation, flight-recorder dump-on-rollback, run-metadata
stamping — all host-only (nothing jits), fast tier.

Also the artifact-stamping CONTRACT test: any ``experiments/`` module
that writes a JSON artifact must reference the shared ``run_metadata``
helper — the regression class VERDICT weak #5 flagged
(numbers published without the platform that produced them) stays closed
permanently.
"""

import json
import os
import re
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from trustworthy_dl_tpu.obs import (
    EVENT_SCHEMAS,
    EventType,
    FlightRecorder,
    MetricsRegistry,
    ObsSession,
    PHASES,
    StepTimeReporter,
    TraceBus,
    peak_flops_per_chip,
    run_metadata,
)
from trustworthy_dl_tpu.obs.events import read_jsonl, validate_event
from trustworthy_dl_tpu.obs.meta import RUN_METADATA_KEYS

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("tddl_x_total", "things", labels=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5
    assert c.value(kind="b") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")  # counters only go up

    g = reg.gauge("tddl_x_depth")
    g.set(7)
    g.set(3)
    assert g.value() == 3.0

    h = reg.histogram("tddl_x_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    hv = h.value()
    assert hv["bucket_counts"] == [1, 2, 1]  # <=0.1, <=1.0, +Inf
    assert hv["count"] == 4
    assert hv["sum"] == pytest.approx(6.05)


def test_registry_label_cardinality_bound():
    reg = MetricsRegistry(max_series=2)
    c = reg.counter("tddl_ids_total", labels=("id",))
    c.inc(id=1)
    c.inc(id=2)
    with pytest.raises(ValueError, match="cardinality"):
        c.inc(id=3)
    # Existing series keep working after the bound trips.
    c.inc(id=1)
    assert c.value(id=1) == 2.0


def test_registry_rejects_kind_conflicts_and_bad_names():
    reg = MetricsRegistry()
    reg.counter("tddl_a_total")
    with pytest.raises(ValueError):
        reg.gauge("tddl_a_total")  # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("not a metric name!")
    with pytest.raises(ValueError):
        reg.counter("tddl_b_total", labels=("bad label",))
    # Wrong label set at update time fails loudly too.
    c = reg.counter("tddl_c_total", labels=("kind",))
    with pytest.raises(ValueError):
        c.inc(other="x")


def test_snapshot_json_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tddl_r_total", "help text", labels=("k",)).inc(k="x")
    reg.gauge("tddl_r_depth").set(2.0)
    reg.histogram("tddl_r_seconds", buckets=(0.5,)).observe(0.2)
    snap = reg.snapshot()
    # Through JSON (what snapshot_to_json persists) and back.
    loaded = json.loads(json.dumps(snap))
    rebuilt = MetricsRegistry.from_snapshot(loaded)
    assert rebuilt.snapshot() == snap

    path = tmp_path / "m.json"
    written = reg.snapshot_to_json(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["metrics"] == snap["metrics"]
    assert set(RUN_METADATA_KEYS) <= set(written["run_metadata"])


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("tddl_p_total", "things", labels=("kind",)).inc(kind="a")
    reg.histogram("tddl_p_seconds", buckets=(1.0,)).observe(0.5)
    text = reg.prometheus_text()
    assert '# TYPE tddl_p_total counter' in text
    assert 'tddl_p_total{kind="a"} 1.0' in text
    assert 'tddl_p_seconds_bucket{le="1"} 1' in text
    assert 'tddl_p_seconds_bucket{le="+Inf"} 1' in text
    assert 'tddl_p_seconds_count 1' in text


# ---------------------------------------------------------------------------
# Events / trace bus
# ---------------------------------------------------------------------------


def _minimal_event(etype: EventType) -> dict:
    schema = EVENT_SCHEMAS[etype]
    event = {"type": etype.value, "seq": 1, "t": 0.0, "t_mono": 0.0}
    for key in schema["requires"]:
        event[key] = 1
    for field in schema["fields"]:
        event[field] = "x"
    return event


def test_every_event_type_has_a_schema_and_validates():
    assert set(EVENT_SCHEMAS) == set(EventType)
    for etype in EventType:
        validate_event(_minimal_event(etype))


def test_event_validation_catches_missing_fields_and_unknown_types():
    with pytest.raises(ValueError, match="unknown event type"):
        validate_event({"type": "nonsense"})
    for etype in EventType:
        schema = EVENT_SCHEMAS[etype]
        for key in schema["requires"]:
            bad = _minimal_event(etype)
            del bad[key]
            with pytest.raises(ValueError, match="requires correlation"):
                validate_event(bad)
        for field in schema["fields"]:
            bad = _minimal_event(etype)
            del bad[field]
            with pytest.raises(ValueError, match="missing required"):
                validate_event(bad)


def test_trace_bus_writes_correlated_jsonl(tmp_path):
    path = tmp_path / "trace.jsonl"
    reg = MetricsRegistry()
    bus = TraceBus(str(path), registry=reg)
    bus.emit(EventType.TRAIN_STEP, step=3, loss=1.0, grad_norm=0.5)
    bus.emit(EventType.CKPT_SAVE, step=3, path="/ckpt")
    bus.emit(EventType.SERVE_SUBMIT, request_id=9, prompt_len=4,
             max_new_tokens=8)
    with pytest.raises(ValueError):
        bus.emit(EventType.TRAIN_STEP, loss=1.0, grad_norm=0.5)  # no step
    bus.close()

    events = read_jsonl(str(path))
    assert [e["seq"] for e in events] == [1, 2, 3]
    assert all("t" in e and "t_mono" in e for e in events)
    # Step correlation: the ckpt event joins the train step on step id.
    assert events[0]["step"] == events[1]["step"] == 3
    assert events[2]["request_id"] == 9
    counts = reg.get("tddl_obs_events_total")
    assert counts.value(type="train_step") == 1.0
    assert counts.value(type="ckpt_save") == 1.0


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_bound_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4)
    bus = TraceBus(None, recorder=rec)
    for step in range(10):
        bus.emit(EventType.TRAIN_STEP, step=step, loss=0.0, grad_norm=0.0)
    events = rec.events()
    assert len(events) == 4                       # ring bound
    assert [e["step"] for e in events] == [6, 7, 8, 9]  # newest retained
    assert rec.total_recorded == 10
    assert rec.counts() == {"train_step": 4}

    p1 = rec.dump(str(tmp_path), "rollback", step=9)
    p2 = rec.dump(str(tmp_path), "rollback", step=9)
    assert p1 != p2                               # incidents never collide
    payload = json.loads(Path(p1).read_text())
    assert payload["reason"] == "rollback"
    assert payload["step"] == 9
    assert payload["num_events"] == 4
    assert [e["step"] for e in payload["events"]] == [6, 7, 8, 9]
    assert set(RUN_METADATA_KEYS) <= set(payload["run_metadata"])


def test_supervisor_dumps_flight_recorder_on_rollback(tmp_path):
    """Dump-on-rollback via a seeded fault, host-only: a duck-typed
    trainer whose step is persistently bad (the GRAD_NAN signature —
    masked loss 0.0 with zero finite nodes) drives the real supervisor
    ladder; the rollback must leave flight-recorder dumps next to the
    checkpoints whose events record the retries and the restore."""
    from trustworthy_dl_tpu.engine.supervisor import TrainingSupervisor

    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    bad = SimpleNamespace(loss=np.float32(0.0), grad_norm=np.float32(0.0),
                          finite=np.zeros(4, bool))

    class FakeTrainer:
        def __init__(self):
            self.global_step = 12
            self.state = {"w": np.zeros(2, np.float32)}
            self.attack_plan = None
            self.step_guard = None
            self.chaos = None
            self.obs = None
            self.training_state = None
            self.config = SimpleNamespace(checkpoint_dir=str(ckpt_dir))
            self.checkpointer = SimpleNamespace(
                verified_steps=lambda: [5], chaos=None, trace=None,
            )
            self.restored = []

        def attach_obs(self, session):
            self.obs = session

        def _train_step(self, state, batch, plan):
            return state, bad

        def load_checkpoint(self, step):
            self.restored.append(step)
            self.global_step = step

    trainer = FakeTrainer()
    session = ObsSession(None, registry=MetricsRegistry())  # in-memory
    supervisor = TrainingSupervisor(trainer, max_retries=1,
                                    rollback_after=2, obs=session)
    assert supervisor.after_step(trainer, {}, bad) is None  # streak 1
    assert supervisor.after_step(trainer, {}, bad) is None  # -> rollback
    assert trainer.restored == [5]
    assert supervisor.rollbacks == 1 and supervisor.retries == 2

    dumps = sorted(ckpt_dir.glob("flight_*.json"))
    reasons = [p.name.split("_")[2] for p in dumps]
    assert "guard" in reasons[0]      # first bad step of the streak
    assert any("rollback" in r for r in reasons)
    rollback_dump = json.loads(dumps[-1].read_text())
    types = [e["type"] for e in rollback_dump["events"]]
    assert types.count("supervisor_retry") == 2
    assert types.count("guard_trip") == 2
    assert "supervisor_rollback" in types
    restore_event = next(e for e in rollback_dump["events"]
                         if e["type"] == "supervisor_rollback")
    assert restore_event["step"] == 12
    assert restore_event["restored_step"] == 5
    # Registry absorbed the same ladder counts.
    actions = session.registry.get("tddl_supervisor_actions_total")
    assert actions.value(action="retry") == 2.0
    assert actions.value(action="rollback") == 1.0


# ---------------------------------------------------------------------------
# Step-time reporter / MFU
# ---------------------------------------------------------------------------


def test_step_time_reporter_phases_and_mfu():
    reg = MetricsRegistry()
    reporter = StepTimeReporter(registry=reg)
    reporter.set_model_info(n_params=1_000_000, tokens_per_step=2048,
                            model_kind="lm", num_chips=2)
    for _ in range(3):
        reporter.discard_step()
        time.sleep(0.002)
        reporter.lap("data")
        time.sleep(0.04)        # far apart: a loaded machine stretches a sleep
        reporter.lap("compute")
        reporter.finish_step()
    report = reporter.report()
    assert report["num_steps"] == 3
    phases = report["phases"]
    assert set(phases) == {"data", "compute"}
    assert phases["compute"]["fraction"] > phases["data"]["fraction"]
    assert sum(p["fraction"] for p in phases.values()) == pytest.approx(1.0)
    mfu = report["mfu"]
    assert mfu["mfu"] is not None and mfu["mfu"] > 0
    assert mfu["mfu"] == pytest.approx(        # 6 FLOPs/param/token
        6 * 1_000_000 * mfu["tokens_per_s_per_chip"]
        / mfu["peak_flops_per_chip"], rel=1e-6)
    assert mfu["num_chips"] == 2
    assert mfu["tokens_per_step"] == 2048
    phase_hist = reg.get("tddl_phase_time_seconds")
    assert phase_hist.value(phase="data")["count"] == 3
    assert phase_hist.value(phase="compute")["count"] == 3
    # End-to-end step time stays MetricsCollector's series — the
    # reporter must not publish a near-duplicate under a second name.
    assert reg.get("tddl_step_time_seconds") is None

    with pytest.raises(ValueError):
        reporter.lap("not_a_phase")


def test_step_time_reporter_discard_drops_partial_step():
    reporter = StepTimeReporter()
    reporter.lap("data")
    reporter.discard_step()
    reporter.finish_step()
    assert reporter.num_steps == 0


def test_peak_flops_per_chip_names_its_source():
    peak, source = peak_flops_per_chip("TPU v4")
    assert peak == 275e12
    assert source.startswith("bf16-peak-table")
    assert peak_flops_per_chip("TPU v5 lite") == (197e12,
                                                  "bf16-peak-table:v5 lite")
    # Only the CPU test mesh gets a nominal peak; an accelerator that is
    # not in the table is an error, never a CPU figure under its name.
    assert peak_flops_per_chip("cpu")[1] == "cpu-nominal-estimate"
    for unknown in ("???", "TPU v9"):
        with pytest.raises(ValueError, match="no peak FLOP/s"):
            peak_flops_per_chip(unknown)


def test_phase_names_cover_the_issue_contract():
    # data/forward/backward/optimizer/detection/host_sync are the named
    # vocabulary shared with utils.profiling's trace annotations.
    for name in ("data", "forward", "backward", "optimizer", "detection",
                 "host_sync"):
        assert name in PHASES


# ---------------------------------------------------------------------------
# MetricsCollector -> registry absorption
# ---------------------------------------------------------------------------


def test_metrics_collector_feeds_registry():
    from trustworthy_dl_tpu.utils.metrics import MetricsCollector

    reg = MetricsRegistry()
    collector = MetricsCollector(registry=reg, namespace="t1")
    collector.collect_batch_metrics({
        "loss": 1.5, "step": 3, "epoch": 0,
        "trust_scores": {0: 0.9, 1: 0.8},
    })
    assert reg.get("tddl_t1_loss").value() == 1.5
    assert reg.get("tddl_t1_trust_scores").value(node="0") == 0.9
    assert reg.get("tddl_t1_trust_scores").value(node="1") == 0.8
    assert reg.get("tddl_t1_step") is None       # correlation id, not metric
    collector.tick()
    collector.tick()
    assert reg.get("tddl_t1_step_time_seconds").value()["count"] == 1


# ---------------------------------------------------------------------------
# Run metadata + artifact-stamping contract
# ---------------------------------------------------------------------------


def test_run_metadata_carries_the_required_keys():
    meta = run_metadata()
    assert set(RUN_METADATA_KEYS) <= set(meta)
    assert meta["platform"]        # resolved (cpu under the test harness)
    assert meta["jax_version"]
    json.dumps(meta)               # must be JSON-serialisable as-is


def test_artifact_writers_are_stamped_with_run_metadata():
    """CONTRACT: every experiments/ module that writes a JSON artifact
    (``json.dump`` or ``utils.io.atomic_write_json``) must reference the
    shared run_metadata helper.  A new artifact writer that forgets the
    stamp fails here, not in review — enforced
    by tddl-lint's AST ``artifact-metadata`` rule (PR 14), which
    replaced the substring scan that lived here."""
    assert _lint_package("artifact-metadata") == []


# ---------------------------------------------------------------------------
# ObsSession plumbing
# ---------------------------------------------------------------------------


def test_obs_session_artifacts_and_snapshot_cadence(tmp_path):
    reg = MetricsRegistry()
    session = ObsSession(str(tmp_path), registry=reg,
                         metrics_snapshot_every=5)
    reg.counter("tddl_s_total").inc()
    session.trace.emit(EventType.TRAIN_STEP, step=5, loss=1.0,
                       grad_norm=0.1)
    session.on_step(4)   # not on cadence
    session.on_step(5)   # snapshot
    session.finalize()
    session.finalize()   # idempotent
    names = {p.name for p in tmp_path.iterdir()}
    assert {"trace.jsonl", "metrics_snapshot.json", "metrics.prom",
            "obs_report.json"} <= names
    events = read_jsonl(str(tmp_path / "trace.jsonl"))
    types = [e["type"] for e in events]
    assert types[0] == "run_start" and types[-1] == "run_end"
    # One cadence snapshot + one final.
    assert types.count("metrics_snapshot") == 2
    assert "tddl_s_total 1.0" in (tmp_path / "metrics.prom").read_text()


# ---------------------------------------------------------------------------
# Active plane: spans
# ---------------------------------------------------------------------------


obswatch = pytest.mark.obswatch


@obswatch
def test_span_tracker_lifecycle_and_trace_emission(tmp_path):
    from trustworthy_dl_tpu.obs.spans import SpanTracker

    path = tmp_path / "trace.jsonl"
    bus = TraceBus(str(path))
    spans = SpanTracker(trace=bus)
    root = spans.start("serve.request", kind="serve", request_id=7,
                       prompt_len=4)
    child = spans.start("serve.prefill", kind="serve", parent_id=root,
                        request_id=7)
    assert spans.open_count == 2
    ended = spans.end(child, slot=2)
    assert ended.duration_s >= 0.0 and ended.attrs["slot"] == 2
    assert spans.end(child) is None          # double close is a no-op
    spans.end(root, status="completed")
    with spans.span("engine.tick", kind="serve"):
        pass
    spans.add("synth", 1.0, 1.5, kind="train", step=3)
    bus.close()

    events = read_jsonl(str(path))
    assert all(e["type"] == "span" for e in events)
    by_name = {e["name"]: e for e in events}
    assert by_name["serve.prefill"]["parent_id"] == root
    assert by_name["serve.prefill"]["request_id"] == 7
    assert by_name["serve.request"]["status"] == "completed"
    assert by_name["synth"]["duration_s"] == pytest.approx(0.5)
    assert by_name["synth"]["step"] == 3

    chrome = spans.export_chrome(str(tmp_path / "chrome.json"))
    assert len(chrome["traceEvents"]) == 4
    synth = next(e for e in chrome["traceEvents"] if e["name"] == "synth")
    assert synth["ph"] == "X" and synth["dur"] == pytest.approx(0.5e6)
    # Offline conversion from the JSONL agrees on the event count.
    from trustworthy_dl_tpu.obs.spans import chrome_trace_from_events

    offline = chrome_trace_from_events(events)
    assert len(offline["traceEvents"]) == 4
    # Serving spans land on the request's lane.
    req = next(e for e in offline["traceEvents"]
               if e["name"] == "serve.request")
    assert req["tid"] == 7


@obswatch
def test_step_timer_synthesizes_train_spans():
    """The trainer's per-phase laps become a train.step span with one
    child per lap — no extra instrumentation in the loop itself."""
    from trustworthy_dl_tpu.obs.spans import SpanTracker

    rec = FlightRecorder(64)
    bus = TraceBus(None, recorder=rec)
    reporter = StepTimeReporter()
    reporter.spans = SpanTracker(trace=bus)
    reporter.discard_step()
    time.sleep(0.001)
    reporter.lap("data")
    time.sleep(0.001)
    reporter.lap("compute")
    reporter.finish_step(step=12)
    names = [(e["name"], e.get("step")) for e in rec.events()]
    assert ("train.step", 12) in names
    assert ("train.data", 12) in names and ("train.compute", 12) in names
    root = next(e for e in rec.events() if e["name"] == "train.step")
    child = next(e for e in rec.events() if e["name"] == "train.data")
    assert child["parent_id"] == root["span_id"]
    # Discarded steps synthesize nothing.
    before = len(rec.events())
    reporter.lap("data")
    reporter.discard_step()
    reporter.finish_step(step=13)
    assert len(rec.events()) == before


# ---------------------------------------------------------------------------
# Active plane: streaming percentiles + SLO rules
# ---------------------------------------------------------------------------


@obswatch
def test_p2_quantile_tracks_numpy_percentiles():
    import numpy as np

    rng = np.random.default_rng(0)
    xs = rng.exponential(1.0, 20000)
    for q in (0.5, 0.9, 0.99):
        from trustworthy_dl_tpu.obs.slo import P2Quantile

        est = P2Quantile(q)
        for x in xs:
            est.observe(x)
        exact = float(np.percentile(xs, q * 100))
        assert est.value == pytest.approx(exact, rel=0.05), q
    # Exact below five samples; NaNs are ignored, not absorbed.
    from trustworthy_dl_tpu.obs.slo import P2Quantile

    small = P2Quantile(0.5)
    for x in (3.0, 1.0, float("nan"), 2.0):
        small.observe(x)
    assert small.value == 2.0
    with pytest.raises(ValueError):
        P2Quantile(1.5)


@obswatch
def test_slo_watcher_burn_rate_breach_and_clear(tmp_path):
    from trustworthy_dl_tpu.obs.slo import SLORule, SLOWatcher

    reg = MetricsRegistry()
    rec = FlightRecorder(256)
    bus = TraceBus(None, recorder=rec)
    dumps = []

    def dump(reason, step=None, extra=None):
        dumps.append((reason, step, extra))

    fired = []
    watcher = SLOWatcher(
        [SLORule("itl", signal="itl_s", target=0.1, budget=0.1,
                 window=20, min_count=10, burn_threshold=1.0)],
        registry=reg, trace=bus, dump=dump,
    )
    watcher.on_breach(lambda name, info: fired.append((name, info)))
    for _ in range(20):
        watcher.observe("itl_s", 0.01)
    assert not watcher.breached
    assert watcher.burn_rate("itl") == 0.0
    # 5/20 violating = 25% against a 10% budget -> burn 2.5 -> breach.
    for _ in range(5):
        watcher.observe("itl_s", 0.5)
    assert watcher.breached and watcher.active == ["itl"]
    assert watcher.burn_rate("itl") == pytest.approx(2.5)
    assert reg.get("tddl_slo_burn_rate").value(slo="itl") \
        == pytest.approx(2.5)
    assert reg.get("tddl_slo_breaches_total").value(slo="itl") == 1.0
    assert len(fired) == 1 and fired[0][0] == "itl"
    assert [(r, e["slo_rules"]) for r, _, e in dumps] \
        == [("slo_breach", ["itl"])]
    breaches = [e for e in rec.events() if e["type"] == "slo_breach"]
    assert len(breaches) == 1 and breaches[0]["slo"] == "itl"
    # Still breached = no re-fire; recovery clears the flag.
    watcher.observe("itl_s", 0.5)
    assert len(fired) == 1 and len(dumps) == 1
    for _ in range(25):
        watcher.observe("itl_s", 0.01)
    assert not watcher.breached
    # The estimator sketch rode along.
    pcts = watcher.percentiles("itl_s")
    assert pcts["count"] == 51 and pcts["p50"] < 0.1
    status = watcher.status()
    assert status["breach_total"] == 1 and status["active"] == []


@obswatch
def test_slo_rule_validation():
    from trustworthy_dl_tpu.obs.slo import SLORule, SLOWatcher

    with pytest.raises(ValueError):
        SLORule("x", signal="s", target=1.0, budget=0.0)
    with pytest.raises(ValueError):
        SLORule("x", signal="s", target=1.0, window=4, min_count=5)
    w = SLOWatcher([SLORule("a", signal="s", target=1.0)])
    with pytest.raises(ValueError, match="duplicate"):
        w.add_rule(SLORule("a", signal="s", target=2.0))


# ---------------------------------------------------------------------------
# Active plane: anomaly watcher
# ---------------------------------------------------------------------------


@obswatch
def test_ewma_detector_score_then_absorb_only_clean():
    from trustworthy_dl_tpu.obs.anomaly import EwmaDetector

    det = EwmaDetector(alpha=0.1, warmup=8, z_threshold=6.0)
    rng = np.random.default_rng(0)
    for _ in range(20):
        anomalous, _ = det.observe(1.0 + rng.normal(0, 0.01))
        assert not anomalous
    before = det.count
    anomalous, z = det.observe(100.0)
    assert anomalous and z > 6.0
    assert det.count == before            # outlier NOT absorbed
    anomalous, z = det.observe(float("nan"))
    assert anomalous and np.isinf(z)
    anomalous, _ = det.observe(1.0)
    assert not anomalous and det.count == before + 1


@obswatch
def test_anomaly_watcher_gauges_events_and_episode_dump():
    from trustworthy_dl_tpu.obs.anomaly import AnomalyWatcher

    reg = MetricsRegistry()
    rec = FlightRecorder(256)
    bus = TraceBus(None, recorder=rec)
    dumps = []
    watcher = AnomalyWatcher(
        {"loss": (0.1, 4, 6.0), "step_time": (0.1, 4, 6.0)},
        registry=reg, trace=bus,
        dump=lambda reason, step=None, extra=None:
            dumps.append((reason, step)),
    )
    with pytest.raises(ValueError, match="already watched"):
        watcher.watch("loss")
    for i in range(10):
        watcher.observe("loss", 2.0 + 0.001 * (i % 3), step=i)
        watcher.observe("step_time", 0.1, step=i)
    assert watcher.active == []
    # Two signals break on the SAME step: two anomaly events, two gauge
    # flips, ONE episode dump.
    onset = watcher.observe("loss", float("nan"), step=10)
    assert onset is not None and onset["signal"] == "loss"
    watcher.observe("step_time", 5.0, step=10)
    assert watcher.active == ["loss", "step_time"]
    assert reg.get("tddl_anomaly_active").value(signal="loss") == 1.0
    assert reg.get("tddl_anomaly_active").value(signal="step_time") == 1.0
    assert dumps == [("anomaly", 10)]
    anomalies = [e for e in rec.events() if e["type"] == "anomaly"]
    assert {e["signal"] for e in anomalies} == {"loss", "step_time"}
    nan_event = next(e for e in anomalies if e["signal"] == "loss")
    assert nan_event["zscore"] is None    # NaN has no finite z — and the
    assert nan_event["step"] == 10        # event must still be valid JSON
    # Clean observations clear the gauges and end the episode; the NEXT
    # incident dumps again.
    watcher.observe("loss", 2.0, step=11)
    watcher.observe("step_time", 0.1, step=11)
    assert watcher.active == []
    assert reg.get("tddl_anomaly_active").value(signal="loss") == 0.0
    watcher.observe("step_time", 9.0, step=12)
    assert len(dumps) == 2


@obswatch
def test_seeded_chaos_drill_produces_predicted_anomalies(tmp_path):
    """The obs→trust loop drill: a SEEDED FaultPlan schedules a stall and
    a NaN on the same step; driving the watcher with the plan's faults
    must produce exactly the plan-predicted anomaly events (both signals,
    at the fault step) and exactly ONE anomaly-reason flight dump."""
    from trustworthy_dl_tpu.chaos.plan import FaultEvent, FaultKind, \
        FaultPlan

    plan = FaultPlan.scripted([
        FaultEvent(step=30, kind=FaultKind.STALL, severity=1.0),
        FaultEvent(step=30, kind=FaultKind.GRAD_NAN),
    ], seed=7)
    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    _, anomaly = session.install_watchers(slo_rules=())
    rng = np.random.default_rng(plan.seed)
    for step in range(1, 60):
        stall = plan.at(step, FaultKind.STALL)
        step_time = 0.1 + float(rng.normal(0, 0.002)) \
            + (stall[0].severity if stall else 0.0)
        loss = 2.0 + float(rng.normal(0, 0.01))
        if plan.at(step, FaultKind.GRAD_NAN):
            loss = float("nan")
        anomaly.observe("step_time", step_time, step=step)
        anomaly.observe("loss", loss, step=step)
    session.finalize()

    events = read_jsonl(str(tmp_path / "trace.jsonl"))
    anomalies = [e for e in events if e["type"] == "anomaly"]
    assert {(e["signal"], e["step"]) for e in anomalies} \
        == {("step_time", 30), ("loss", 30)}
    dumps = sorted(tmp_path.glob("flight_*anomaly*.json"))
    assert len(dumps) == 1, [p.name for p in dumps]
    payload = json.loads(dumps[0].read_text())
    assert payload["reason"] == "anomaly" and payload["step"] == 30
    # The registry carries the gauge/counter surface the SLO-aware fleet
    # (ROADMAP item 4) will consume.
    reg = session.registry
    assert reg.get("tddl_anomaly_events_total").value(signal="loss") == 1.0
    assert reg.get("tddl_anomaly_active").value(signal="loss") == 0.0
    # slo_status.json reflects the watchers at finalize.
    status = json.loads((tmp_path / "slo_status.json").read_text())
    assert status["anomaly"]["event_total"] == 2


# ---------------------------------------------------------------------------
# Active plane: attribution ledger
# ---------------------------------------------------------------------------


@obswatch
def test_attribution_ledger_jsonl_roundtrip(tmp_path):
    from trustworthy_dl_tpu.obs.attribution import AttributionLedger, \
        read_ledger, token_hash

    path = tmp_path / "attribution.jsonl"
    ledger = AttributionLedger(str(path), keep=2)
    for rid in range(3):
        ledger.append({"request_id": rid, "status": "completed",
                       "admitted": True, "layout": "paged", "slot": 0,
                       "block_ids": [1], "tokens": 1,
                       "token_hash": token_hash([rid])})
    ledger.close()
    assert ledger.total == 3
    assert [r["request_id"] for r in ledger.records()] == [1, 2]  # ring
    header, records = read_ledger(str(path))           # file keeps all
    assert set(RUN_METADATA_KEYS) <= set(header["run_metadata"])
    assert [r["request_id"] for r in records] == [0, 1, 2]
    assert all("t" in r for r in records)
    assert token_hash([1, 2, 3]) != token_hash([1, 2, 4])
    assert token_hash([]) == token_hash(())


@obswatch
def test_verify_attribution_against_block_allocator_journal():
    from trustworthy_dl_tpu.obs.attribution import verify_attribution
    from trustworthy_dl_tpu.serve.kv_slots import BlockAllocator

    alloc = BlockAllocator(8)
    blocks = alloc.alloc(3)
    alloc.incref(blocks[0])                 # prefix-cache style share
    for b in blocks:
        alloc.release(b)
    record = {"request_id": 0, "status": "completed", "admitted": True,
              "layout": "paged", "slot": 1, "block_ids": list(blocks),
              "prefix_block_ids": [blocks[0]]}
    ok, problems = verify_attribution([record], alloc)
    assert ok, problems

    # Forged claims are caught: a block never allocated, the trash
    # block, duplicates, and a prefix id outside the table.
    forged = dict(record, block_ids=[7], prefix_block_ids=[])
    ok, problems = verify_attribution([record, forged], alloc)
    assert not ok and any("never allocated" in p for p in problems)
    ok, problems = verify_attribution(
        [dict(record, block_ids=[0], prefix_block_ids=[])], alloc)
    assert not ok and any("trash" in p for p in problems)
    ok, problems = verify_attribution(
        [dict(record, block_ids=[blocks[0], blocks[0]])], alloc)
    assert not ok and any("duplicate" in p for p in problems)
    ok, problems = verify_attribution(
        [dict(record, prefix_block_ids=[blocks[1] + 100])], alloc)
    assert not ok and any("subset" in p for p in problems)
    # Unadmitted and stripe records verify structurally.
    ok, _ = verify_attribution(
        [{"request_id": 1, "admitted": False},
         {"request_id": 2, "admitted": True, "layout": "stripe",
          "slot": 0}], alloc)
    assert ok


@obswatch
def test_verify_attribution_survives_journal_ring_rotation():
    """The cumulative ``lifetime`` counts (bounded by pool size) keep
    reconciliation exact after the debug ring overflows — a long-pinned
    block whose alloc entry rotated out must NOT read as forged."""
    from trustworthy_dl_tpu.obs.attribution import verify_attribution
    from trustworthy_dl_tpu.serve.kv_slots import BlockAllocator

    alloc = BlockAllocator(4, journal_capacity=4)
    pinned = alloc.alloc(1)
    for _ in range(8):                     # 16 ops: ring holds only 4
        b = alloc.alloc(1)
        alloc.release(b[0])
    assert not any(op == "alloc" and blk == pinned[0]
                   for op, blk, *_ in alloc.journal)
    record = {"request_id": 0, "status": "completed", "admitted": True,
              "layout": "paged", "slot": 0, "block_ids": list(pinned),
              "prefix_block_ids": []}
    ok, problems = verify_attribution([record], alloc)
    assert ok, problems
    alloc.release(pinned[0])


# ---------------------------------------------------------------------------
# Contract lints: typed emissions + metric-name prefix
# ---------------------------------------------------------------------------


def _lint_package(rule: str) -> list:
    """Run ONE tddl-lint rule over the standing perimeter (package +
    tests), suppressions honoured, NO baseline — these two
    contracts are absolute and may never be grandfathered."""
    from trustworthy_dl_tpu.analysis import run_lint

    result = run_lint(root=str(REPO), rule_names=[rule],
                      use_baseline=False)
    return [f"{f.location}: {f.message}" for f in result.findings]


def test_every_emit_call_site_uses_a_schema_typed_event():
    """CONTRACT: every ``*.emit(...)`` call site in the package passes an
    ``EventType.<NAME>`` whose NAME exists — new instrumentation cannot
    bypass schema validation with a raw string (or a typo'd member).
    Enforced by tddl-lint's AST ``obs-emit-type`` rule (PR 14), which
    replaced the regex scan that lived here: multi-line calls and
    aliased buses resolve the way the interpreter would."""
    assert _lint_package("obs-emit-type") == []


def test_fleet_events_and_gauges_are_inside_the_lint_perimeter():
    """PR 8 extension: the serving-fleet event types carry full schemas
    (so the emit lint + validate_event cover them like every other
    type) and the fleet metric surface keeps the ``tddl_`` naming
    contract — ``tddl_fleet_replicas{state=}`` and the fail-over/hedge/
    transition counters are registered via literal names the
    metric-name lint scans."""
    assert EVENT_SCHEMAS[EventType.REPLICA_TRANSITION]["fields"] == \
        ("replica", "from_state", "to_state", "reason")
    assert EVENT_SCHEMAS[EventType.FLEET_FAILOVER]["requires"] == \
        ("request_id",)
    assert EVENT_SCHEMAS[EventType.FLEET_FAILOVER]["fields"] == \
        ("from_replica", "to_replica", "attempt")
    assert EVENT_SCHEMAS[EventType.FLEET_HEDGE]["fields"] == ("replica",)
    src = (REPO / "trustworthy_dl_tpu" / "serve" / "fleet.py").read_text()
    for name in ("tddl_fleet_replicas", "tddl_fleet_failovers_total",
                 "tddl_fleet_hedges_total", "tddl_fleet_transitions_total"):
        assert f'"{name}"' in src, name


def test_adversary_surface_inside_the_lint_perimeter():
    """PR 12 extension: the adversarial-serving event types (suspicion
    episodes + verdict votes) carry full schemas — the emit lint +
    validate_event cover them like every other type — and the new
    fleet metric surface keeps the ``tddl_`` naming contract via
    literal names the metric-name lint scans."""
    assert EVENT_SCHEMAS[EventType.FLEET_SUSPICION]["fields"] == \
        ("replica", "score", "reason")
    assert EVENT_SCHEMAS[EventType.VERDICT_VOTE]["requires"] == \
        ("request_id",)
    assert EVENT_SCHEMAS[EventType.VERDICT_VOTE]["fields"] == \
        ("replica", "outcome", "agree", "dissent")
    src = (REPO / "trustworthy_dl_tpu" / "serve" / "fleet.py").read_text()
    for name in ("tddl_fleet_suspicion", "tddl_fleet_suspicions_total",
                 "tddl_fleet_votes_total"):
        assert f'"{name}"' in src, name
    # The votes counter is outcome-labelled (confirmed / outvoted /
    # inconclusive) so dashboards can separate audits from verdicts.
    assert 'labels=("outcome",)' in src


def test_control_plane_surface_inside_the_lint_perimeter():
    """PR 13 extension: the fleet control-plane event types (autoscaler
    actions + tenant throttles) carry full schemas — the emit lint +
    validate_event cover them like every other type — and the new
    metric surface keeps the ``tddl_`` naming contract via literal
    names the metric-name lint scans, with the labels dashboards key
    on (tenant / direction / slo_class)."""
    assert EVENT_SCHEMAS[EventType.FLEET_SCALE]["fields"] == \
        ("direction", "from_replicas", "to_replicas", "reason")
    assert EVENT_SCHEMAS[EventType.TENANT_THROTTLE]["fields"] == \
        ("tenant", "tokens", "bucket_level")
    src = (REPO / "trustworthy_dl_tpu" / "serve" / "fleet.py").read_text()
    for name in ("tddl_fleet_tenant_throttled_total",
                 "tddl_fleet_scale_events_total",
                 "tddl_fleet_class_queue_depth"):
        assert f'"{name}"' in src, name
    assert 'labels=("tenant",)' in src
    assert 'labels=("direction",)' in src
    assert 'labels=("slo_class",)' in src


def test_perf_tier_events_and_metrics_inside_the_lint_perimeter():
    """PR 10 extension: the performance-tier event types carry full
    schemas (so the emit lint + validate_event cover them like every
    other type) and the compile/HBM/sentinel metric surface keeps the
    ``tddl_`` naming contract via literal names the metric-name lint
    scans."""
    assert EVENT_SCHEMAS[EventType.COMPILE]["fields"] == \
        ("key", "seconds")
    assert EVENT_SCHEMAS[EventType.COMPILE_STORM]["fields"] == \
        ("scope", "compiles")
    assert EVENT_SCHEMAS[EventType.HBM_SWEEP]["fields"] == \
        ("live_bytes", "watermark_bytes")
    assert EVENT_SCHEMAS[EventType.HBM_PRESSURE]["fields"] == \
        ("requested_bytes", "headroom_bytes")
    assert EVENT_SCHEMAS[EventType.PERF_REGRESSION]["fields"] == \
        ("metric", "value", "baseline")
    assert EVENT_SCHEMAS[EventType.TRACE_ROTATE]["fields"] == \
        ("path", "segment")
    obs = REPO / "trustworthy_dl_tpu" / "obs"
    cw = (obs / "compilewatch.py").read_text()
    for name in ("tddl_compile_total", "tddl_compile_seconds",
                 "tddl_compile_storms_total"):
        assert f'"{name}"' in cw, name
    hbm = (obs / "hbm.py").read_text()
    for name in ("tddl_hbm_live_bytes", "tddl_hbm_watermark_bytes",
                 "tddl_hbm_pressure_total"):
        assert f'"{name}"' in hbm, name
    assert '"tddl_perf_regressions_total"' in \
        (obs / "sentinel.py").read_text()


def test_spec_surface_inside_the_lint_perimeter():
    """Speculative-decoding extension: the spec counters are literal
    ``tddl_`` names the metric-name lint scans, registered through the
    same ``_metric`` replica-label surface as the rest of the
    tddl_serve_* family (fleet mode labels them ``replica=``), and the
    per-tick verify span rides the schema-typed ``span`` event under
    the existing serve span namespace."""
    import re

    engine_src = (REPO / "trustworthy_dl_tpu" / "serve"
                  / "engine.py").read_text()
    for name in ("tddl_serve_spec_proposed_total",
                 "tddl_serve_spec_accepted_total"):
        assert f'"{name}"' in engine_src, name
        # Replica labels in fleet mode: the registration passes the
        # engine's replica label-name tuple, like every serve metric.
        pattern = re.compile(
            rf'"{name}",.*?labels=self\._rlabel_names', re.DOTALL)
        assert pattern.search(engine_src), f"{name} not replica-labelled"
    sched_src = (REPO / "trustworthy_dl_tpu" / "serve"
                 / "scheduler.py").read_text()
    assert '"serve.spec_verify"' in sched_src
    # Spans are schema-typed events — the verify span carries the span
    # schema's required fields via SpanTracker like every other span.
    assert EVENT_SCHEMAS[EventType.SPAN]["fields"] == \
        ("name", "kind", "span_id", "duration_s")


def test_paged_attn_surface_inside_the_lint_perimeter():
    """Paged-attention kernel-tier extension: the attention-path gauge
    is a literal ``tddl_`` name the metric-name lint scans, registered
    through the same ``_metric`` replica-label surface as the rest of
    the tddl_serve_* family with the ``path`` AND per-program
    ``program`` labels (both in the dashboard vocabulary deliberately,
    contracts.KNOWN_METRIC_LABELS)."""
    import re

    from trustworthy_dl_tpu.analysis.contracts import KNOWN_METRIC_LABELS

    engine_src = (REPO / "trustworthy_dl_tpu" / "serve"
                  / "engine.py").read_text()
    assert '"tddl_serve_attn_kernel"' in engine_src
    pattern = re.compile(
        r'"tddl_serve_attn_kernel",.*?'
        r'labels=\("path", "program"\) \+ self\._rlabel_names', re.DOTALL)
    assert pattern.search(engine_src), \
        "tddl_serve_attn_kernel not path+program+replica labelled"
    assert "path" in KNOWN_METRIC_LABELS
    assert "program" in KNOWN_METRIC_LABELS


def test_migration_surface_inside_the_lint_perimeter():
    """Live-migration extension: the kv_migration / pool_rebalance
    event types carry full schemas — the emit lint + validate_event
    cover them like every other type — the migration counter and pool
    gauge are literal ``tddl_`` names the metric-name lint scans, and
    their ``reason`` / ``role`` labels are in the dashboard vocabulary
    (contracts.KNOWN_METRIC_LABELS) deliberately, not by accident."""
    from trustworthy_dl_tpu.analysis.contracts import KNOWN_METRIC_LABELS

    assert EVENT_SCHEMAS[EventType.KV_MIGRATION]["requires"] == \
        ("request_id",)
    assert EVENT_SCHEMAS[EventType.KV_MIGRATION]["fields"] == \
        ("from_replica", "to_replica", "blocks", "reason")
    assert EVENT_SCHEMAS[EventType.POOL_REBALANCE]["requires"] == ()
    assert EVENT_SCHEMAS[EventType.POOL_REBALANCE]["fields"] == \
        ("role", "replicas", "moved")
    src = (REPO / "trustworthy_dl_tpu" / "serve" / "fleet.py").read_text()
    for name in ("tddl_fleet_migrations_total",
                 "tddl_fleet_pool_replicas"):
        assert f'"{name}"' in src, name
    assert 'labels=("reason",)' in src
    assert 'labels=("role",)' in src
    assert "reason" in KNOWN_METRIC_LABELS
    assert "role" in KNOWN_METRIC_LABELS


def test_every_registered_metric_name_carries_the_tddl_prefix():
    """CONTRACT: every literal metric name registered on a registry
    (counter/gauge/histogram, plus serve/engine.py's ``_metric``
    degrade-on-conflict wrapper) starts with ``tddl_`` — the naming
    convention the Prometheus surface promises.  Enforced by
    tddl-lint's AST ``metric-prefix`` rule (PR 14), which replaced the
    regex scan that lived here; the companion ``metric-label-vocab``
    rule additionally pins label names to the dashboard vocabulary."""
    assert _lint_package("metric-prefix") == []
    assert _lint_package("metric-label-vocab") == []


# ---------------------------------------------------------------------------
# ObsSession active-plane plumbing
# ---------------------------------------------------------------------------


@obswatch
def test_obs_session_active_plane_artifacts(tmp_path):
    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    spans = session.enable_spans()
    assert session.enable_spans() is spans          # idempotent
    assert session.step_timer.spans is spans
    slo, anomaly = session.install_watchers()
    assert session.install_watchers() == (slo, anomaly)
    ledger = session.open_ledger()
    with spans.span("serve.request", kind="serve", request_id=1):
        pass
    ledger.append({"request_id": 1, "status": "completed",
                   "admitted": True, "layout": "paged", "slot": 0,
                   "block_ids": [], "tokens": 0, "token_hash": "00"})
    slo.observe("ttft_s", 0.1)
    session.finalize()
    names = {p.name for p in tmp_path.iterdir()}
    assert {"trace.jsonl", "slo_status.json", "trace_events.json",
            "attribution.jsonl"} <= names
    chrome = json.loads((tmp_path / "trace_events.json").read_text())
    assert len(chrome["traceEvents"]) == 1
    status = json.loads((tmp_path / "slo_status.json").read_text())
    assert status["slo"]["signals"]["ttft_s"]["count"] == 1
    # step_time feeds flow through on_step.
    session2 = ObsSession(None, registry=MetricsRegistry())
    session2.install_watchers(slo_rules=())
    session2.step_timer.lap("data")
    time.sleep(0.001)
    session2.step_timer.lap("compute")
    session2.step_timer.finish_step(step=1)
    session2.on_step(1)
    assert session2.anomaly._dets["step_time"].count == 1


# ---------------------------------------------------------------------------
# Incident forensics surface (PR 18)
# ---------------------------------------------------------------------------


def test_forensics_surface_inside_the_lint_perimeter():
    """Forensics extension: the incident / verdict event types carry
    full schemas — the emit lint + validate_event cover them like every
    other type — the ``tddl_incidents_total{reason=}`` /
    ``tddl_verdicts_total{outcome=}`` counters are literal names the
    metric-name lint scans with labels from the dashboard vocabulary,
    and the flight-dump/incident reason strings themselves are pinned
    to ``contracts.ARTIFACT_REASONS`` by the ``artifact-reason-vocab``
    rule — repo-wide, no baseline."""
    from trustworthy_dl_tpu.analysis.contracts import (ARTIFACT_REASONS,
                                                       KNOWN_METRIC_LABELS)

    assert EVENT_SCHEMAS[EventType.INCIDENT]["fields"] == \
        ("incident_id", "reason", "path")
    assert EVENT_SCHEMAS[EventType.VERDICT]["fields"] == \
        ("kind", "outcome")
    obs = REPO / "trustworthy_dl_tpu" / "obs"
    forensics_src = (obs / "forensics.py").read_text()
    assert '"tddl_incidents_total"' in forensics_src
    assert 'labels=("reason",)' in forensics_src
    verdicts_src = (obs / "verdicts.py").read_text()
    assert '"tddl_verdicts_total"' in verdicts_src
    assert 'labels=("outcome",)' in verdicts_src
    assert "reason" in KNOWN_METRIC_LABELS
    assert "outcome" in KNOWN_METRIC_LABELS
    # Every reason a producer uses today is registered — and the lint
    # rule holds the whole perimeter to the vocabulary.
    assert {"guard_trip", "rollback", "preemption", "slo_breach",
            "anomaly", "compile_storm", "replica_quarantine",
            "replica_preempt", "adapter_quarantine",
            "migration_refused", "drill", "manual"} <= ARTIFACT_REASONS
    assert _lint_package("artifact-reason-vocab") == []


@obswatch
def test_obs_session_pairs_incident_with_flight_dump(tmp_path):
    """``enable_forensics()``: every flight dump gets a paired
    ``incident_NNN_<reason>.json`` under the SAME index, assembled from
    the session's own trace, and the durable VERDICTS.jsonl records the
    episode — the full cross-plane loop in one session."""
    from trustworthy_dl_tpu.obs.forensics import load_incidents
    from trustworthy_dl_tpu.obs.verdicts import VerdictStore

    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    forensics = session.enable_forensics()
    assert session.enable_forensics() is forensics      # idempotent
    session.open_ledger()                 # order-free: rebinds ledger
    assert forensics.ledger is session.ledger
    session.trace.emit(EventType.GUARD_TRIP, step=3, loss=0.0,
                       grad_norm=0.0, finite_nodes=0)
    path = session.dump_flight("guard_trip", step=3)
    m = re.match(r"flight_(\d+)_guard_trip", Path(path).name)
    assert m, path
    incidents = load_incidents(str(tmp_path))
    assert len(incidents) == 1
    inc = incidents[0]
    # Paired under the SAME index as the flight dump.
    assert inc["incident_id"] == f"incident_{m.group(1)}_guard_trip"
    assert inc["flight_dump"] == path
    # The trigger resolved from the session's own trace file (the
    # guard_trip event precedes the dump), not synthetically.
    assert inc["trigger"]["type"] == "guard_trip"
    assert not inc["trigger"].get("synthetic")
    # The incident landed in the durable verdict history with its id,
    # and the counters registered under the session's registry.
    store = VerdictStore(str(tmp_path / "VERDICTS.jsonl"))
    rows = store.read()
    assert rows and rows[-1]["kind"] == "incident"
    assert rows[-1]["incident_id"] == inc["incident_id"]
    reg = session.registry
    assert reg.counter("tddl_incidents_total", "",
                       labels=("reason",)).value(reason="guard_trip") == 1
    assert reg.counter("tddl_verdicts_total", "",
                       labels=("outcome",)).value(outcome="recorded") == 1
    session.finalize()
