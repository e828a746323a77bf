"""Runtime performance observability (obs/compilewatch.py, obs/hbm.py,
obs/sentinel.py + trace rotation + the obs diff subcommand).

The compile drills use REAL jitted programs on the cpu backend (tiny
shapes); the serve drills use the vocab-113 tiny model so their decode
geometry never collides with test_serve's 97 / test_quant's 101 /
test_paged_kv's 103 / test_fleet's 107 in the process-global jit cache.
"""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from trustworthy_dl_tpu.obs import (
    EventType,
    MetricsRegistry,
    ObsSession,
    StepTimeReporter,
    TraceBus,
)
from trustworthy_dl_tpu.obs.compilewatch import (
    CompileRegistry,
    CompileWatcher,
)
from trustworthy_dl_tpu.obs.events import (
    read_jsonl,
    read_jsonl_rotated,
    rotated_segments,
)
from trustworthy_dl_tpu.obs.hbm import CostLedger, HbmMonitor, \
    live_buffer_bytes
from trustworthy_dl_tpu.obs.sentinel import (
    PerfLedger,
    PerfSentinel,
    fingerprint,
    load_perf_artifact,
    render_diff,
)

perfwatch = pytest.mark.perfwatch

TINY = dict(vocab_size=113, n_positions=64, n_layer=2, n_embd=32,
            n_head=4)


def _tiny_engine(registry, **kw):
    import jax
    import jax.numpy as jnp

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import ServingEngine

    cfg = gpt2.GPT2Config(dtype=jnp.float32, **TINY)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, max_slots=2, max_seq=48,
                         registry=registry, **kw), cfg


# ---------------------------------------------------------------------------
# CompileRegistry / CompileWatcher
# ---------------------------------------------------------------------------


@perfwatch
def test_compile_registry_counts_real_compiles_and_cache_hits():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    bus_events = []

    class Bus:
        def emit(self, *a, **kw):
            bus_events.append((a, kw))

    # Input arrays built BEFORE the registry installs: jnp.ones itself
    # compiles a broadcast program — the deltas below must count f only.
    x3, x3b, x5 = jnp.ones(3), jnp.ones(3), jnp.ones(5)
    compiles = CompileRegistry(trace=Bus(), registry=reg).install()
    try:
        f = jax.jit(lambda x: x * 2 + 1)
        before = compiles.total
        f(x3).block_until_ready()
        assert compiles.total == before + 1          # one backend compile
        f(x3b).block_until_ready()
        assert compiles.total == before + 1          # cache hit: no event
        f(x5).block_until_ready()
        assert compiles.total == before + 2          # new shape compiles
        summary = compiles.summary()
        assert summary["total"] == compiles.total
        assert summary["seconds"] > 0
        assert "backend_compile" in summary["by_stage"]
        assert reg.get("tddl_compile_total").value() == compiles.total
        seconds = reg.get("tddl_compile_seconds")
        assert seconds.value(stage="backend_compile") > 0
        # One typed `compile` event per backend compile.
        compile_rows = [kw for a, kw in bus_events
                        if a[0] == EventType.COMPILE]
        assert len(compile_rows) == compiles.total
        assert all(r["seconds"] > 0 for r in compile_rows)
    finally:
        compiles.uninstall()
    # Uninstalled: later compiles no longer feed this registry.
    frozen = compiles.total
    jax.jit(lambda x: x - 7)(jnp.ones(4)).block_until_ready()
    assert compiles.total == frozen


@perfwatch
def test_compile_watcher_warmup_storms_and_episode_dumps():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    from trustworthy_dl_tpu.obs import FlightRecorder

    rec = FlightRecorder(128)
    bus = TraceBus(None, recorder=rec, registry=reg)
    dumps = []
    xs = {n: jnp.ones(n) for n in (3, 5, 7, 9, 11)}  # pre-built inputs
    compiles = CompileRegistry(registry=reg).install()
    try:
        watcher = CompileWatcher(
            compiles, trace=bus, registry=reg,
            dump=lambda reason, step=None, extra=None:
                dumps.append((reason, step, extra)),
        )
        f = jax.jit(lambda x: x + 1)
        with watcher.guard("loop", step=0):      # warmup: compile absorbed
            f(xs[3]).block_until_ready()
        with watcher.guard("loop", step=1):      # clean (cache hit)
            f(xs[3]).block_until_ready()
        assert watcher.storm_total == 0
        with watcher.guard("loop", step=2):      # recompile -> storm
            f(xs[5]).block_until_ready()
        with watcher.guard("loop", step=3):      # storm again, SAME episode
            f(xs[7]).block_until_ready()
        with watcher.guard("loop", step=4):      # clean closes the episode
            f(xs[7]).block_until_ready()
        with watcher.guard("loop", step=5):      # new episode -> new dump
            f(xs[9]).block_until_ready()
        assert watcher.storm_total == 3
        assert reg.get("tddl_compile_storms_total").value(scope="loop") \
            == 3.0
        storms = [e for e in rec.events() if e["type"] == "compile_storm"]
        assert [e["step"] for e in storms] == [2, 3, 5]
        assert all(e["scope"] == "loop" for e in storms)
        # Once per EPISODE, not per storm: steps 2-3 are one incident.
        assert [(r, s) for r, s, _ in dumps] \
            == [("compile_storm", 2), ("compile_storm", 5)]
        # reset(): a legitimate rebuild's compile is warmup again.
        watcher.reset("loop")
        with watcher.guard("loop", step=6):
            f(xs[11]).block_until_ready()
        assert watcher.storm_total == 0   # fresh scope state
    finally:
        compiles.uninstall()


@perfwatch
def test_serve_decode_clean_run_zero_storms_and_forced_storm(tmp_path):
    """THE drill pair from the issue: a standard serve run with the
    watcher attached produces ZERO storms (admissions, prefill-program
    compiles and block churn are all outside the decode guard), and one
    forced decode recompile yields exactly ONE typed compile_storm
    event plus ONE flight dump."""
    import jax

    from trustworthy_dl_tpu.serve import ServeRequest

    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    session.enable_compile_watch()
    engine, cfg = _tiny_engine(session.registry, trace=session.trace,
                               compilewatch=session.compilewatch)
    rng = np.random.default_rng(7)
    for i in range(4):
        engine.submit(ServeRequest(
            prompt=rng.integers(0, cfg.vocab_size,
                                3 + (i % 3)).tolist(),
            max_new_tokens=4 + i,
        ))
    engine.run_until_idle()
    assert session.compilewatch.storm_total == 0      # clean-run drill

    # Forced decode recompile: clearing jax's caches invalidates the
    # compiled decode executable, so the NEXT guarded dispatch must
    # recompile — exactly the production failure mode the watcher
    # exists to catch (a silently invalidated/changed decode geometry).
    engine.submit(ServeRequest(prompt=[5, 6, 7], max_new_tokens=8))
    for _ in range(3):
        engine.step()                   # request into steady decode
    assert session.compilewatch.storm_total == 0
    jax.clear_caches()
    engine.run_until_idle()
    assert session.compilewatch.storm_total >= 1
    session.finalize()
    events = read_jsonl(str(tmp_path / "trace.jsonl"))
    storms = [e for e in events if e["type"] == "compile_storm"]
    assert len(storms) == 1, storms     # exactly one storm event
    assert storms[0]["scope"] == "serve_decode"
    dumps = [p.name for p in tmp_path.glob("flight_*compile_storm*.json")]
    assert len(dumps) == 1, dumps       # exactly one flight dump
    # The registry carried the counters alongside.
    assert session.registry.get("tddl_compile_storms_total") \
        .value(scope="serve_decode") == 1.0
    assert session.registry.get("tddl_compile_total").value() > 0


# ---------------------------------------------------------------------------
# HBM accounting + headroom gate
# ---------------------------------------------------------------------------


@perfwatch
def test_live_buffer_bytes_and_watermark_gauges():
    import jax.numpy as jnp

    anchor = jnp.ones((256, 256), jnp.float32)    # 256 KiB held live
    reg = MetricsRegistry()
    monitor = HbmMonitor(registry=reg, budget_bytes=None)
    sweep = monitor.sweep()
    assert sweep["total_bytes"] >= anchor.nbytes
    assert sweep["per_device"]                      # at least one device
    device = next(iter(sweep["per_device"]))
    assert reg.get("tddl_hbm_live_bytes").value(device=device) \
        == float(sweep["per_device"][device])
    # Watermark is monotone: freeing the anchor lowers live, not peak.
    peak = monitor.watermark_bytes
    del anchor
    monitor.sweep()
    assert monitor.watermark_bytes == peak
    assert reg.get("tddl_hbm_watermark_bytes").value(device=device) \
        >= reg.get("tddl_hbm_live_bytes").value(device=device)


@perfwatch
def test_hbm_admit_denies_over_headroom_and_emits_pressure():
    from trustworthy_dl_tpu.obs import FlightRecorder

    reg = MetricsRegistry()
    rec = FlightRecorder(64)
    bus = TraceBus(None, recorder=rec, registry=reg)
    monitor = HbmMonitor(registry=reg, trace=bus,
                         budget_bytes=10 ** 15)      # plenty
    assert monitor.admit(1024, what="small") is True
    monitor.budget_bytes = 1                         # nothing fits now
    assert monitor.admit(1 << 30, what="paged_pool") is False
    assert monitor.pressure_denials == 1
    assert reg.get("tddl_hbm_pressure_total").value() == 1.0
    pressure = [e for e in rec.events() if e["type"] == "hbm_pressure"]
    assert len(pressure) == 1
    assert pressure[0]["requested_bytes"] == 1 << 30
    assert pressure[0]["what"] == "paged_pool"
    # Unknown budget: the gate never blocks.
    open_monitor = HbmMonitor(budget_bytes=None)
    assert open_monitor.admit(1 << 40) is True


@perfwatch
def test_engine_consults_headroom_gate_and_shrinks_pool():
    """Low headroom at construction shrinks the paged pool to what the
    budget buys (floor: one full sequence) instead of allocating past it."""
    reg = MetricsRegistry()
    monitor = HbmMonitor(registry=reg, budget_bytes=1)   # no headroom
    engine, cfg = _tiny_engine(reg, hbm=monitor)
    sched = engine.scheduler
    assert sched.num_blocks == 48 // sched.block_size    # one-sequence floor
    assert monitor.pressure_denials == 1
    # With a generous budget the requested pool passes untouched.
    rich, _ = _tiny_engine(MetricsRegistry(),
                           hbm=HbmMonitor(budget_bytes=10 ** 15))
    assert rich.scheduler.num_blocks == 2 * (48 // 16)


# ---------------------------------------------------------------------------
# Cost ledger + analyzed MFU
# ---------------------------------------------------------------------------


@perfwatch
def test_cost_ledger_analyzes_program_flops_and_memory():
    import jax
    import jax.numpy as jnp

    ledger = CostLedger()
    f = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((16, 16), jnp.float32)
    ledger.analyze("matmul", f, x, x, memory=True)
    entry = ledger.programs["matmul"]
    assert entry["flops"] >= 2 * 16 ** 3 * 0.5     # ~2·n³ at n=16
    assert entry["bytes_accessed"] > 0
    assert "temp_bytes" in entry                   # memory=True path
    assert ledger.flops("matmul") == entry["flops"]
    # Failures degrade to an error entry, never a raise.
    ledger.analyze("broken", f, x, jnp.ones((3,)))
    assert "error" in ledger.programs["broken"]


@perfwatch
def test_report_carries_cost_ledger_and_analyzed_mfu():
    import time

    reporter = StepTimeReporter()
    reporter.set_model_info(n_params=1_000_000, tokens_per_step=2048,
                            model_kind="lm", num_chips=2)
    ledger = CostLedger()
    ledger.note("train_step", {"flops": 1e9, "bytes_accessed": 1e6})
    reporter.cost_ledger = ledger
    for _ in range(2):
        reporter.discard_step()
        time.sleep(0.002)
        reporter.lap("compute")
        reporter.finish_step()
    report = reporter.report()
    assert report["cost_ledger"]["train_step"]["flops"] == 1e9
    analyzed = report["mfu_analyzed"]
    assert analyzed["flops_source"] == "xla-cost-analysis"
    mean = report["step_time_s"]["mean"]
    assert analyzed["achieved_flops_per_s_per_chip"] \
        == pytest.approx(1e9 / mean / 2)
    assert analyzed["mfu"] is not None and analyzed["mfu"] > 0
    # Nominal MFU still rides alongside — the diff view compares them.
    assert report["mfu"]["mfu"] is not None


@perfwatch
def test_serve_engine_program_cost_analysis():
    session_reg = MetricsRegistry()
    engine, _ = _tiny_engine(session_reg)
    ledger = CostLedger()
    engine.analyze_programs(ledger)
    assert {"serve.paged_prefill", "serve.paged_chunk",
            "serve.paged_decode"} <= set(ledger.programs)
    for entry in ledger.programs.values():
        assert entry["flops"] > 0, entry


# ---------------------------------------------------------------------------
# Perf ledger + sentinel
# ---------------------------------------------------------------------------


def _fp(tokens, **extra):
    return fingerprint("test", metric="m", tokens_per_s=tokens,
                       run_metadata={"platform": "cpu",
                                     "device_kind": "cpu"}, **extra)


@perfwatch
def test_perf_ledger_append_read_and_trim(tmp_path):
    ledger = PerfLedger(str(tmp_path / "PERF_LEDGER.jsonl"), keep=3)
    for i in range(5):
        ledger.append(_fp(100.0 + i))
    rows = ledger.read()
    assert len(rows) == 3                            # trimmed to keep
    assert [r["tokens_per_s"] for r in rows] == [102.0, 103.0, 104.0]
    assert ledger.last()["tokens_per_s"] == 104.0
    assert ledger.last(key="no:such:key") is None
    # A torn line degrades to a skipped row, not a crash.
    with open(ledger.path, "a") as f:
        f.write("{torn json\n")
    assert len(ledger.read()) == 3


@perfwatch
def test_sentinel_noise_band_verdicts(tmp_path):
    from trustworthy_dl_tpu.obs import FlightRecorder

    reg = MetricsRegistry()
    rec = FlightRecorder(64)
    bus = TraceBus(None, recorder=rec, registry=reg)
    ledger = PerfLedger(str(tmp_path / "ledger.jsonl"))
    sentinel = PerfSentinel(ledger, trace=bus, registry=reg)

    # Too few baselines: everything passes, and says why.
    verdict = sentinel.check(_fp(100.0))
    assert not verdict["regressed"] and verdict["baseline_n"] == 0
    for tokens in (100.0, 101.0, 99.0, 100.5):
        ledger.append(_fp(tokens))
    # Within the band.
    verdict = sentinel.check(_fp(98.0))
    assert not verdict["regressed"]
    # Far below (higher-is-better metric): regression.
    verdict = sentinel.check(_fp(50.0))
    assert verdict["regressed"]
    check = next(c for c in verdict["checks"]
                 if c["metric"] == "tokens_per_s")
    assert check["regressed"] and check["delta_pct"] < -40
    events = [e for e in rec.events() if e["type"] == "perf_regression"]
    assert len(events) == 1 and events[0]["metric"] == "tokens_per_s"
    assert reg.get("tddl_perf_regressions_total") \
        .value(metric="tokens_per_s") == 1.0
    # Lower-is-better direction: a compile-seconds blowup regresses.
    for _ in range(3):
        ledger.append(_fp(100.0, compile_seconds=1.0))
    verdict = sentinel.check(_fp(100.0, compile_seconds=50.0))
    assert any(c["metric"] == "compile_seconds" and c["regressed"]
               for c in verdict["checks"])
    # A round MARKED regressed is excluded from later baselines.
    bad = _fp(50.0)
    bad["regressed"] = True
    ledger.append(bad)
    assert all(e.get("tokens_per_s") != 50.0
               for e in ledger.baseline(bad["key"]))


@perfwatch
@pytest.mark.parametrize("metric, good, bad, label", [
    ("step_time_s", 1.0, 5.0, "step_time_mean_s"),
    ("compile_total", 10, 50, "compile_total"),
    ("hbm_watermark_bytes", 10 ** 9, 5 * 10 ** 9, "hbm_watermark_bytes"),
])
def test_sentinel_pages_a_regression_in_its_bad_direction(
        tmp_path, metric, good, bad, label):
    """Each lower-is-better fingerprint metric the noise-band test does
    not page pages here: a blow-up past the band regresses in the
    metric's BAD direction, a value inside it does not, and
    ``trustworthy-dl-obs diff`` renders the fingerprint's value."""
    from trustworthy_dl_tpu.obs.sentinel import (
        SENTINEL_METRICS,
        load_perf_artifact,
        render_diff,
    )

    assert SENTINEL_METRICS[metric] == "lower"
    ledger = PerfLedger(str(tmp_path / "ledger.jsonl"))
    for _ in range(3):
        ledger.append(_fp(100.0, **{metric: good}))
    sentinel = PerfSentinel(ledger)
    assert not sentinel.check(_fp(100.0, **{metric: good * 1.02}))[
        "regressed"]
    verdict = sentinel.check(_fp(100.0, **{metric: bad}))
    check = next(c for c in verdict["checks"] if c["metric"] == metric)
    assert verdict["regressed"] and check["regressed"]
    assert check["direction"] == "lower" and check["delta_pct"] > 0
    view = load_perf_artifact(str(tmp_path / "ledger.jsonl"))
    assert label in render_diff(view, view)


@perfwatch
def test_session_finalize_appends_fingerprint_and_checks(tmp_path):
    """ObsSession.finalize() runs the sentinel against the rolling
    ledger and appends this run's fingerprint (verdict stamped)."""
    import time

    ledger_path = tmp_path / "shared_ledger.jsonl"
    for i in range(2):
        session = ObsSession(str(tmp_path / f"run{i}"),
                             registry=MetricsRegistry(),
                             perf_ledger=str(ledger_path))
        session.step_timer.discard_step()
        time.sleep(0.002)
        session.step_timer.lap("compute")
        session.step_timer.finish_step(step=1)
        session.finalize()
        assert session.perf_verdict is not None
    rows = PerfLedger(str(ledger_path)).read()
    assert len(rows) == 2
    assert all(r["source"] == "session" for r in rows)
    assert all("step_time_s" in r for r in rows)
    assert rows[0]["key"] == rows[1]["key"]


# ---------------------------------------------------------------------------
# Trace rotation
# ---------------------------------------------------------------------------


@perfwatch
def test_trace_bus_rotation_round_trips(tmp_path):
    path = tmp_path / "trace.jsonl"
    bus = TraceBus(str(path), max_bytes=4096)
    n = 150
    for step in range(n):
        bus.emit(EventType.TRAIN_STEP, step=step, loss=1.0, grad_norm=0.5)
    bus.close()
    segments = rotated_segments(str(path))
    assert bus.rotations >= 2
    assert [seg for _, seg in segments] == list(range(1, bus.rotations + 1))
    # Each fresh segment opens with the typed rotation announcement.
    for i, (seg_path, seg) in enumerate(segments[1:], start=1):
        first = read_jsonl(seg_path)[0]
        assert first["type"] == "trace_rotate"
        assert first["segment"] == i
    events = read_jsonl_rotated(str(path))
    # Everything is there, in emission order (seq contiguous).
    assert [e["seq"] for e in events] == list(range(1, len(events) + 1))
    steps = [e["step"] for e in events if e["type"] == "train_step"]
    assert steps == list(range(n))
    rotates = [e for e in events if e["type"] == "trace_rotate"]
    assert len(rotates) == bus.rotations
    assert all(os.path.exists(e["path"]) for e in rotates)


@perfwatch
def test_trace_rotation_cap_floor_prevents_recursion(tmp_path):
    """REGRESSION: a cap smaller than one trace_rotate line made the
    rotation announcement itself trip the cap — emit → rotate → emit
    recursion (RecursionError, ~1000 one-line segments).  Tiny caps
    clamp to MIN_ROTATE_BYTES instead."""
    from trustworthy_dl_tpu.obs.events import MIN_ROTATE_BYTES

    path = tmp_path / "trace.jsonl"
    bus = TraceBus(str(path), max_bytes=64)      # would recurse unclamped
    assert bus.max_bytes == MIN_ROTATE_BYTES
    for step in range(50):
        bus.emit(EventType.TRAIN_STEP, step=step, loss=1.0, grad_norm=0.5)
    bus.close()
    events = read_jsonl_rotated(str(path))
    assert [e["step"] for e in events if e["type"] == "train_step"] \
        == list(range(50))
    assert len(rotated_segments(str(path))) == bus.rotations


@perfwatch
def test_obs_cli_walks_rotated_segments(tmp_path, capsys):
    from trustworthy_dl_tpu.cli import obs_main

    session = ObsSession(str(tmp_path), registry=MetricsRegistry(),
                         trace_max_bytes=1024)
    session.enable_spans()
    for step in range(40):
        session.trace.emit(EventType.TRAIN_STEP, step=step, loss=0.1,
                           grad_norm=0.1)
        session.spans.add("train.step", 0.0, 0.001, kind="train",
                          step=step)
    session.finalize()
    assert rotated_segments(str(tmp_path / "trace.jsonl"))
    # The CLI's type filter sees events from SEALED segments too.
    assert obs_main([str(tmp_path), "--type", "train_step",
                     "--tail", "100"]) == 0
    out = capsys.readouterr().out
    lines = [json.loads(l) for l in out.strip().splitlines()]
    assert len(lines) == 40
    # The offline Chrome export converts spans across every segment.
    chrome_out = tmp_path / "chrome.json"
    assert obs_main([str(tmp_path), "--chrome", str(chrome_out)]) == 0
    payload = json.loads(chrome_out.read_text())
    assert len(payload["traceEvents"]) == 40


# ---------------------------------------------------------------------------
# obs diff
# ---------------------------------------------------------------------------


def _write_report(directory: Path, step_mean: float, flops: float):
    directory.mkdir(parents=True, exist_ok=True)
    report = {
        "num_steps": 10,
        "step_time_s": {"mean": step_mean, "p50": step_mean,
                        "p95": step_mean * 1.2, "max": step_mean * 1.5},
        "phases": {"compute": {"fraction": 0.8},
                   "data": {"fraction": 0.2}},
        "mfu": {"mfu": 0.3, "tokens_per_s_per_chip": 1000.0},
        "mfu_analyzed": {"mfu": 0.25},
        "cost_ledger": {"train_step": {"flops": flops,
                                       "temp_bytes": 1024}},
    }
    (directory / "obs_report.json").write_text(json.dumps(report))


@perfwatch
def test_obs_diff_subcommand_offline(tmp_path, capsys):
    from trustworthy_dl_tpu.cli import obs_main

    a, b = tmp_path / "a", tmp_path / "b"
    _write_report(a, 0.10, 1e9)
    _write_report(b, 0.20, 1e9)
    PerfLedger(str(b / "PERF_LEDGER.jsonl")).append(_fp(500.0))
    assert obs_main(["diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "step_time_mean_s" in out
    assert "+100.0%" in out                  # B is 2x slower
    assert "flops[train_step]" in out
    assert "tokens_per_s" in out             # ledger fingerprint merged
    # Missing artifacts fail loudly with rc 2, not a traceback.
    assert obs_main(["diff", str(a), str(tmp_path / "nope")]) == 2


@perfwatch
def test_load_perf_artifact_accepts_dir_report_and_ledger(tmp_path):
    d = tmp_path / "run"
    _write_report(d, 0.1, 1e9)
    assert "report" in load_perf_artifact(str(d))
    assert "report" in load_perf_artifact(str(d / "obs_report.json"))
    ledger = PerfLedger(str(tmp_path / "l.jsonl"))
    ledger.append(_fp(10.0))
    view = load_perf_artifact(str(tmp_path / "l.jsonl"))
    assert view["fingerprint"]["tokens_per_s"] == 10.0
    with pytest.raises(FileNotFoundError):
        load_perf_artifact(str(tmp_path / "empty"))
    text = render_diff(load_perf_artifact(str(d)), view)
    assert "A:" in text and "B:" in text


# ---------------------------------------------------------------------------
# Epoch-boundary placement regression (found BY the compile watcher)
# ---------------------------------------------------------------------------


@perfwatch
def test_epoch_intelligence_preserves_threshold_placement(tmp_path):
    """REGRESSION (caught by the train_step compile guard on the
    canonical drive): the adaptive-threshold push-back replaced the
    mesh-replicated committed ``trust.threshold`` scalar with an
    uncommitted SingleDeviceSharding one, changing the jitted step's
    input signature — the whole train step silently recompiled on the
    first step after every adjustment.  The push-back must keep the
    leaf's placement identical to init."""
    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.engine.trainer import DistributedTrainer

    cfg = TrainingConfig(
        model_name="gpt2", batch_size=8, num_nodes=4,
        checkpoint_dir=str(tmp_path), adaptive_thresholds=True,
    )
    trainer = DistributedTrainer(cfg, model_overrides=dict(
        n_layer=1, n_embd=16, n_head=2, vocab_size=64, n_positions=32,
        seq_len=16))
    trainer.initialize()
    leaf = trainer.state.trust.threshold
    before = (str(leaf.sharding), leaf._committed, str(leaf.dtype))
    trainer._epoch_intelligence()
    after_leaf = trainer.state.trust.threshold
    after = (str(after_leaf.sharding), after_leaf._committed,
             str(after_leaf.dtype))
    assert after == before, (before, after)
    trainer.cleanup()


# ---------------------------------------------------------------------------
# Replica-labelled serve gauges (fleet gauge-aliasing satellite)
# ---------------------------------------------------------------------------


@perfwatch
def test_fleet_mode_serve_gauges_carry_replica_label():
    """Two engines sharing one registry with replica ids keep SEPARATE
    gauge series (the PR 8 last-writer-wins aliasing is gone), while a
    standalone engine keeps the unlabelled form."""
    from trustworthy_dl_tpu.serve import ServeRequest

    reg = MetricsRegistry()
    e0, cfg = _tiny_engine(reg, replica_id=0)
    e1, _ = _tiny_engine(reg, replica_id=1)
    e0.submit(ServeRequest(prompt=[1, 2, 3], max_new_tokens=3))
    e0.run_until_idle()
    e1.step()                                   # idle tick still gauges
    tif = reg.get("tddl_serve_tokens_in_flight")
    assert tif.label_names == ("replica",)
    assert tif.value(replica="0") == 0.0        # drained
    assert tif.value(replica="1") == 0.0
    kv = reg.get("tddl_serve_kv_bytes")
    assert kv.value(replica="0") == kv.value(replica="1") > 0
    req = reg.get("tddl_serve_requests_total")
    assert req.value(status="completed", replica="0") == 1.0
    assert req.value(status="completed", replica="1") is None
    # Collector batch gauges (occupancy/queue depth) are labelled too.
    occ = reg.get("tddl_serve_slot_occupancy")
    assert occ.label_names == ("replica",)
    # Standalone engines stay unlabelled.
    solo_reg = MetricsRegistry()
    solo, _ = _tiny_engine(solo_reg)
    solo.step()
    assert solo_reg.get("tddl_serve_tokens_in_flight").label_names == ()
