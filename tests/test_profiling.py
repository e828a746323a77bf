"""Host spans, profiler sessions and the debug mode (utils/profiling.py),
the phase timer that reads the spans (obs/report.py) and the named scopes
of the trusted step.  CPU runs assert structure and counts, never a time.

The tests that need a trainer share one tiny GPT-2 (module fixture +
``reset_for_run``), so the fast tier pays its compile once.
"""

import glob
import os
import types

import numpy as np
import pytest

import jax

from trustworthy_dl_tpu.core.config import TrainingConfig
from trustworthy_dl_tpu.data import get_dataloader
from trustworthy_dl_tpu.engine import DistributedTrainer
from trustworthy_dl_tpu.obs.report import PHASES, StepTimeReporter
from trustworthy_dl_tpu.utils import profiling
from trustworthy_dl_tpu.utils.profiling import (enable_nan_debugging,
                                                recorded_spans, span,
                                                step_annotation, trace)

TINY = dict(n_layer=2, n_embd=32, n_head=4, vocab_size=128, n_positions=32,
            seq_len=16)
STEPS = 4
#: The parts of the epoch's end, in order (``train.epoch_end.<part>``).
EPOCH_END_PARTS = ("drain", "host_sync", "thresholds", "ml_refit",
                   "collect")
#: The spans of one step of the loop at ``async_host_depth`` 2.
STEP_SPANS = ("train.data_wait", "train.batch_place", "train.host_drain",
              "train.host_drain.wait", "train.host_drain.records")
SCOPES = ("attack.inject", "train.fwd_bwd", "trust.grad_stats",
          "trust.verify", "trust.detect", "trust.update", "trust.aggregate",
          "train.optimizer", "trust.monitor")


def _config(tmp_path, **kwargs):
    return TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext", batch_size=8,
        num_epochs=1, num_nodes=4, optimizer="adamw",
        checkpoint_interval=10_000, checkpoint_dir=str(tmp_path / "ckpt"),
        **kwargs)


def _loader(steps=STEPS):
    return get_dataloader("openwebtext", batch_size=8, seq_len=16,
                          vocab_size=128, num_examples=8 * steps)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    trainer = DistributedTrainer(
        _config(tmp_path_factory.mktemp("profiling")),
        model_overrides=dict(TINY))
    trainer.initialize()
    return trainer


# ---------------------------------------------------------------------------
# profiler sessions
# ---------------------------------------------------------------------------


def test_profile_trace_written(tmp_path):
    """A ``profile_dir`` run writes a trace, and the program's spans stand
    on its host plane beside the step annotation: one clock for both."""
    profile_dir = str(tmp_path / "traces")
    trainer = DistributedTrainer(_config(tmp_path, profile_dir=profile_dir),
                                 model_overrides=dict(TINY))
    result = trainer.train(_loader())
    assert np.isfinite(result["epochs"][0]["train_loss"])
    # jax.profiler writes plugins/profile/<ts>/*.xplane.pb (+ trace.json.gz).
    dumps = glob.glob(os.path.join(profile_dir, "**", "*"), recursive=True)
    assert any(p.endswith((".xplane.pb", ".json.gz")) for p in dumps), dumps
    xplanes = [p for p in dumps if p.endswith(".xplane.pb")]
    if not xplanes:
        return
    data = jax.profiler.ProfileData.from_file(xplanes[0])
    names = {e.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    wanted = {"train_step", "train.epoch_end", "train.host_drain.records",
              "train.data_wait", "train.batch_place"}
    wanted |= {f"train.epoch_end.{part}" for part in EPOCH_END_PARTS}
    assert wanted <= names, wanted - names


def test_trace_noop_without_dir():
    with trace(None):
        pass  # must not create anything or require a profiler session


# ---------------------------------------------------------------------------
# the one span helper
# ---------------------------------------------------------------------------


def test_annotations_are_noop_safe_without_profiler_session():
    """A step annotation and spans of every layer must enter and exit
    cleanly with NO active profiler session and no timer — the trainer
    opens a few in every hot-loop step — and leave no record."""
    before = recorded_spans()
    with step_annotation(7):
        pass
    for name in PHASES:
        with span(f"train.{name}"):
            pass
    with span("train.epoch_end.ml_refit", rows=3) as noted:
        noted["more"] = 1
    assert recorded_spans() == before  # only ``setup.*`` is kept


def test_annotations_survive_a_broken_profiler_backend(monkeypatch):
    """A backend whose profiler plugin raises (construction OR entry)
    degrades to a no-op instead of killing the step loop — and the timer
    still gets the interval."""
    class BoomOnInit:
        def __init__(self, *a, **k):
            raise RuntimeError("no profiler session")

    class BoomOnEnter:
        def __init__(self, *a, **k):
            pass

        def __enter__(self):
            raise RuntimeError("plugin missing")

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(profiling.jax.profiler, "StepTraceAnnotation",
                        BoomOnInit)
    timer = StepTimeReporter()
    for broken in (BoomOnEnter, BoomOnInit):
        monkeypatch.setattr(profiling.jax.profiler, "TraceAnnotation",
                            broken)
        with profiling.step_annotation(1):
            pass
        with profiling.span("train.data_wait", timer):
            pass
    assert timer.report()["spans"]["train.data_wait"]["count"] == 2


def test_spans_nest_and_close_on_an_exception():
    timer = StepTimeReporter()
    with pytest.raises(KeyError):
        with span("train.epoch_end", timer):
            with span("train.epoch_end.drain", timer, depth=0):
                raise KeyError("unwind")
    spans = timer._spans
    assert list(spans) == ["train.epoch_end.drain", "train.epoch_end"]
    (child, attrs), = spans["train.epoch_end.drain"]
    (parent, _), = spans["train.epoch_end"]
    assert 0.0 <= child <= parent and attrs == {"depth": 0}


def test_a_span_decorates_a_function_with_a_fresh_span_per_call():
    @span("setup.test_decorated")
    def build(x):
        return x + 1

    before = len([s for s in recorded_spans()
                  if s[0] == "setup.test_decorated"])
    assert (build(1), build(2)) == (2, 3)
    mine = [s for s in recorded_spans() if s[0] == "setup.test_decorated"]
    assert len(mine) == before + 2
    assert all(start > 1e9 and seconds >= 0.0 for _, start, seconds in mine)


@pytest.mark.parametrize("nested", [False, True])
def test_recorded_spans_are_bounded_and_hold_set_up_only(nested):
    """``nested``: pairs that close TOGETHER each satisfy, exactly, the
    inequality the benchmark's ``span_readers.outermost`` nests by: a
    recorded span's start and length come from one clock (with the wall
    start read apart, 51 of 1,500 such inner spans read as outside)."""
    bound = profiling._RECORDED.maxlen
    assert bound <= 64
    for i in range(1500 if nested else bound + 10):
        with span("setup.test_bound", i=i):
            if nested:
                with span("setup.test_bound.inner"):
                    pass
        with span("train.test_bound"):
            pass
        if nested:
            inner, outer = recorded_spans()[-2:]
            assert (inner[0], outer[0]) == ("setup.test_bound.inner",
                                            "setup.test_bound")
            assert outer[1] <= inner[1], i
            assert inner[1] + inner[2] <= outer[1] + outer[2], i
    kept = recorded_spans()
    assert len(kept) == bound
    assert all(name.startswith("setup.") for name, _, _ in kept)


def test_spans_leave_the_per_step_ring_alone(monkeypatch):
    """The medians behind the phase laps are the same numbers with spans
    recorded between the laps as without: spans live in a ring of their
    own and never move the lap mark."""
    def run(with_spans):
        ticks = iter(range(10_000))
        monkeypatch.setattr("time.perf_counter", lambda: float(next(ticks)))
        timer = StepTimeReporter()
        timer.discard_step()
        for step in range(5):
            timer.lap("data")
            if with_spans:
                timer.record_span("train.host_drain.records", 1.0, 3.0)
            timer.lap("compute")
            timer.lap("host")
            timer.finish_step(step=step)
        if with_spans:
            timer.record_span("train.epoch_end", 0.0, 50.0)
            timer.record_span("train.epoch_end.ml_refit", 1.0, 40.0, rows=7)
        report = timer.report()
        return report, {k: report[k] for k in ("num_steps", "step_time_s",
                                               "phases")}

    plain_report, plain = run(False)
    report, with_spans = run(True)
    assert with_spans == plain and plain["num_steps"] == 5
    assert "spans" not in plain_report and "epoch_end" not in plain_report
    assert report["spans"]["train.host_drain.records"] == {
        "count": 5, "total_s": 10.0, "p50_s": 2.0}
    assert report["epoch_end"] == {
        "epochs": 1, "total_s": 50.0, "p50_s": 50.0,
        "parts": {"ml_refit": {"count": 1, "total_s": 39.0, "p50_s": 39.0}},
        "refit_rows": [7]}


def test_spans_reach_an_attached_span_tracker():
    """What the timer records is what the Chrome timeline shows."""
    from trustworthy_dl_tpu.obs.spans import SpanTracker

    timer = StepTimeReporter()
    timer.spans = SpanTracker()
    with span("train.epoch_end.drain", timer, depth=0):
        pass
    (seconds, _), = timer._spans["train.epoch_end.drain"]
    (closed,) = timer.spans._closed
    assert closed.name == "train.epoch_end.drain" and closed.kind == "train"
    assert closed.duration_s == seconds and closed.attrs == {"depth": 0}


# ---------------------------------------------------------------------------
# the trainer's spans
# ---------------------------------------------------------------------------


def test_set_up_spans_of_a_tiny_trainer(tmp_path):
    with span("setup.test_marker"):
        pass
    trainer = DistributedTrainer(_config(tmp_path),
                                 model_overrides=dict(TINY))
    trainer.initialize()
    names = [name for name, _, _ in recorded_spans()]
    names = names[len(names) - names[::-1].index("setup.test_marker"):]
    # a span is recorded when it closes: children before their parent
    assert names == [
        "setup.trainer_init.host_state", "setup.build_steps",
        "setup.trainer_init", "setup.initialize.model_init",
        "setup.initialize.opt_init", "setup.initialize.place_on_mesh",
        "setup.initialize"]


def test_no_timer_and_no_profiler_leaves_no_record(tiny):
    """The state of every timed window: nothing attached, nothing kept."""
    tiny.reset_for_run()
    assert tiny.obs is None and tiny.phase_timer is None
    before = recorded_spans()
    tiny.train_epoch(_loader(), 0)
    fresh = [name for name, _, _ in recorded_spans()[len(before):]]
    assert fresh in ([], ["setup.first_step"])
    assert tiny.obs is None and tiny.phase_timer is None


def test_attach_phase_timer_feeds_laps_spans_and_the_epoch_end(tiny):
    tiny.reset_for_run()
    timer = tiny.attach_phase_timer()
    assert tiny.obs is None and isinstance(timer, StepTimeReporter)
    for epoch in range(2):
        tiny.train_epoch(_loader(), epoch)
    report = timer.report()
    assert report["num_steps"] == 2 * STEPS
    assert {"data", "compute", "host"} <= set(report["phases"])
    for name in STEP_SPANS:
        assert name in report["spans"], name
    spans = report["spans"]
    assert spans["train.batch_place"]["count"] == 2 * STEPS
    assert spans["train.host_drain"]["count"] == 2 * STEPS
    # every batch is waited for, and each epoch's end of the stream too
    assert spans["train.data_wait"]["count"] == 2 * STEPS + 2
    # depth 2: the loop resolves all but the last two steps of an epoch,
    # the epoch's full drain the rest
    depth = tiny.config.async_host_depth
    for part in ("wait", "records"):
        assert spans[f"train.host_drain.{part}"]["count"] == \
            2 * (STEPS - depth)
        assert spans[f"train.epoch_end.drain.{part}"]["count"] == 2 * depth
    end = report["epoch_end"]
    assert end["epochs"] == 2
    for part in EPOCH_END_PARTS:
        assert end["parts"][part]["count"] == 2, part
    assert {"ml_refit.fit", "ml_refit.score"} <= set(end["parts"])
    # the refit's rows: nothing fitted before ML_MIN_SAMPLES of history
    assert len(end["refit_rows"]) == 2
    assert all(isinstance(rows, int) for rows in end["refit_rows"])
    tiny.reset_for_run()
    assert tiny.phase_timer is None  # per-run, like attach_obs


def test_an_object_shaped_like_the_benchmark_s_phase_laps_still_works(tiny):
    """The accepted benchmark driver assigns such an object straight to
    ``trainer.obs``; the loop must keep feeding its timer."""
    class NullTrace:
        def emit(self, *args, **kwargs):
            pass

    tiny.reset_for_run()
    steps_seen = []
    laps = types.SimpleNamespace(
        anomaly=None, compilewatch=None, cost_ledger=None,
        step_timer=StepTimeReporter(), trace=NullTrace(),
        on_step=steps_seen.append)
    tiny.obs = laps
    tiny.train_epoch(_loader(), 0)
    tiny.obs = None
    report = laps.step_timer.report()
    assert report["num_steps"] == STEPS == len(steps_seen)
    assert {"data", "host"} <= set(report["phases"])
    assert report["epoch_end"]["epochs"] == 1


def test_attach_obs_uses_the_phase_timer(tiny, tmp_path):
    from trustworthy_dl_tpu.obs import ObsSession

    tiny.reset_for_run()
    session = ObsSession(str(tmp_path / "obs"))
    tiny.attach_obs(session)
    assert tiny.phase_timer is session.step_timer
    tiny.reset_for_run()


# ---------------------------------------------------------------------------
# named scopes of the trusted step
# ---------------------------------------------------------------------------


def test_every_equation_of_the_trusted_step_stands_under_a_named_scope(tiny):
    from jax._src import source_info_util

    from trustworthy_dl_tpu.engine.step import build_train_step

    tiny.reset_for_run()
    step = build_train_step(tiny.model, tiny.config, tiny.optimizer)
    batch = tiny._node_batch(next(iter(_loader())))
    args = (tiny.state, batch, tiny.attack_plan)
    outside = []
    for eqn in jax.make_jaxpr(step)(*args).jaxpr.eqns:
        scope = str(eqn.source_info.name_stack).split("/")[0]
        if scope not in SCOPES:
            # constants that tracing hoists out of the model's own code
            # lose their stack; nothing written in train_step may
            frame = source_info_util.user_frame(eqn.source_info.traceback)
            outside.append((str(eqn.primitive), frame.file_name))
    assert not [o for o in outside if o[1].endswith("engine/step.py")], \
        outside
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"{scope}/" in text, scope
    assert "transpose(" in text  # the backward half of train.fwd_bwd


# ---------------------------------------------------------------------------
# debug mode
# ---------------------------------------------------------------------------


def test_nan_debug_mode_traps(monkeypatch):
    enable_nan_debugging(True)
    try:
        with pytest.raises(FloatingPointError):
            jax.jit(lambda x: jax.numpy.log(x - 1.0))(
                jax.numpy.zeros(4)
            ).block_until_ready()
    finally:
        enable_nan_debugging(False)
