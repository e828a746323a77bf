"""The readers of the program's own host spans: by hand on made-up spans,
silent on a trace that has none (``recorded_trace.json``, recorded before
the program had spans), and right on the epoch's end recorded on the chip
(``recorded_epoch_end.json``)."""

import os
import time

import pytest

from bench_paths import BENCH, ROOT, manifest_data

from benchmark.harness import manifest as mf
from benchmark.harness import result, span_readers, xplane

MANIFEST = mf.Manifest(ROOT)
CELLS = ["train-124m-trust-1chip", "train-124m-trust-dp4"]
#: The metrics this file's readers serve, with what each moves.
NEW = {"train_epoch_end_ms": "train_tokens_per_s_per_chip",
       "train_epoch_drain_ms": "train_tokens_per_s_per_chip",
       "train_epoch_sync_ms": "train_tokens_per_s_per_chip",
       "train_epoch_refit_ms": "train_tokens_per_s_per_chip",
       "train_host_work_ms": "train_tokens_per_s_per_chip",
       "train_idle_named_pct": "train_tokens_per_s_per_chip",
       "trainer_build_s": "setup_s"}
RECORDED = os.path.join(BENCH, "harness", "recorded_trace.json")
EPOCH_END = os.path.join(BENCH, "harness", "recorded_epoch_end.json")


def make_run(trace=None, steps=2):
    entry = MANIFEST.cell(CELLS[0])
    run = result.Run(entry, MANIFEST.config(entry["config"]),
                     MANIFEST.traffic(entry["traffic"]), 1, 45.0,
                     trace is not None)
    if trace is not None:
        run.trace = xplane.summarize(trace)
        run.counters["trace_steps"] = steps
    return run


def read(name, run):
    return MANIFEST.reader(name)(run)


# Two steps of 1.0 s on the device, the second followed by an epoch's end
# of 1.2 s on the host: a full drain of 0.5 s (0.2 s of it with the device
# idle), host sync 0.1 s, thresholds 0.05 s, refit 0.5 s, collect 0.05 s.
OPS = [("fusion.1", 0.0, 1.0), ("fusion.2", 1.05, 1.0),
       ("copy.1", 3.5, 0.001)]
HOST = [
    ("bench.traced", 0.0, 3.6),
    ("train.data_wait", 0.0, 0.01), ("train.batch_place", 0.01, 0.01),
    ("train_step", 0.02, 0.02),
    ("train.host_drain", 0.04, 0.96),
    ("train.data_wait", 1.0, 0.01), ("train.batch_place", 1.01, 0.01),
    ("train_step", 1.02, 0.02),
    ("train.host_drain", 1.04, 0.70),
    ("train.host_drain.wait", 1.04, 0.66),
    ("train.host_drain.records", 1.70, 0.004),
    ("train.epoch_end", 1.75, 1.2),
    ("train.epoch_end.drain", 1.75, 0.5),
    ("train.epoch_end.drain.wait", 1.75, 0.3),
    ("train.epoch_end.drain.records", 2.05, 0.006),
    ("train.epoch_end.host_sync", 2.25, 0.1),
    ("train.epoch_end.thresholds", 2.35, 0.05),
    ("train.epoch_end.ml_refit", 2.40, 0.5),
    ("train.epoch_end.ml_refit.fit", 2.40, 0.45),
    ("train.epoch_end.collect", 2.90, 0.05),
    ("PjitFunction(f)", 3.3, 0.25),
]


def test_epoch_end_and_its_parts_by_hand():
    run = make_run(xplane.Trace({0: OPS}, HOST))
    assert read("train_epoch_end_ms", run) == pytest.approx(1200.0)
    assert read("train_epoch_drain_ms", run) == pytest.approx(500.0)
    assert read("train_epoch_sync_ms", run) == pytest.approx(100.0)
    assert read("train_epoch_refit_ms", run) == pytest.approx(550.0)
    # the host's own work for a step: under the loop's drain for one step,
    # under the epoch's full drain for the other
    assert read("train_host_work_ms", run) == pytest.approx((4.0 + 6.0) / 2)


def test_idle_named_share_by_hand():
    """Gaps: 0.05 s between the steps (its middle under the dispatch's
    step annotation ``train_step``, the shortest span over it: not a span
    of the program's ``train.`` vocabulary), 1.45 s after the second step
    (middle under ``train.epoch_end.ml_refit.fit``) and 0.099 s at the end
    (no span)."""
    run = make_run(xplane.Trace({0: OPS}, HOST))
    idle = run.trace.idle_by_host
    assert idle == {"train_step": pytest.approx(0.05),
                    "train.epoch_end.ml_refit.fit": pytest.approx(1.45),
                    "_none_": pytest.approx(0.099)}
    assert read("train_idle_named_pct", run) == pytest.approx(
        100 * 1.45 / 1.599)


def test_spans_outside_the_slice_are_not_read():
    trace = xplane.Trace({0: OPS}, [("bench.traced", 0.0, 1.5)] + HOST[1:])
    run = make_run(trace)
    assert read("train_epoch_end_ms", run) is None
    assert read("train_host_work_ms", run) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_is_silent_on_a_trace_without_the_program_s_spans(name):
    """The trace of PR 26: the parent's program opens no such span."""
    run = make_run(xplane.load_json(RECORDED), steps=1)
    assert not [e for e in run.trace.host
                if e[0].startswith(("train.", "setup."))]
    assert read(name, run) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_entry_has_its_reader_its_cells_and_a_metric_they_report(name):
    entry, = [m for m in manifest_data()["per_layer"] if m["name"] == name]
    assert set(CELLS) <= set(entry["workloads"])    # a later cell appends
    assert entry["moves"] == NEW[name]
    assert callable(MANIFEST.reader(name))
    for cell in CELLS:
        assert entry in MANIFEST.per_layer(cell)
        assert NEW[name] in {m["name"] for m in MANIFEST.end_to_end(cell)}


def test_outermost_counts_a_nested_span_once():
    spans = [("setup.trainer_init", 10.0, 5.0), ("setup.build_steps", 12.0, 1.0),
             ("setup.initialize", 15.5, 3.0), ("setup.build_steps", 30.0, 0.5)]
    assert span_readers.outermost(spans) == [spans[0], spans[2], spans[3]]


def test_trainer_build_s_reads_the_program_s_recorded_set_up_spans():
    profiling = pytest.importorskip("trustworthy_dl_tpu.utils.profiling")
    run = make_run()
    with profiling.span("setup.trainer_init"):       # before this "run"
        pass
    assert read("trainer_build_s", run) is None       # no process start
    time.sleep(0.005)
    run.counters["process_start"] = time.time()
    assert read("trainer_build_s", run) is None       # none since it began
    # A recorded span is its wall-clock start plus a perf_counter length
    # taken a few microseconds later, so ``outermost`` can only tell that a
    # span lies inside another where their ends are further apart than that:
    # an inner span that closed within a microsecond of its outer one read
    # as outside it in 3 of 400 runs on an idle host and 51 of 1,500 on a
    # busy one, and was counted twice (PERF.md section 7).  The sleeps put
    # every end unmistakably where it belongs.
    with profiling.span("setup.trainer_init"):
        with profiling.span("setup.build_steps"):
            time.sleep(0.002)
        time.sleep(0.05)
    time.sleep(0.005)
    with profiling.span("setup.initialize"):
        with profiling.span("setup.initialize.model_init"):
            pass
        time.sleep(0.002)
    with profiling.span("setup.first_step"):          # not the build
        time.sleep(0.002)
    mine = [s for s in profiling.recorded_spans()
            if s[1] >= run.counters["process_start"]]
    want = sum(s[2] for s in mine
               if s[0] in ("setup.trainer_init", "setup.initialize"))
    assert read("trainer_build_s", run) == pytest.approx(want) and want > 0


# -- the epoch's end recorded on the chip -------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """The end of a traced slice of ``train-124m-trust-1chip`` (chip, PR 27,
    seed 2711000022; cut by ``_dev/pr27/make_fixture.py``): the last 300
    device events of the last step, the few that the host sync dispatches,
    the 0.64 s gap under the refit, and the host spans over them (the
    program's, JAX's dispatch, and the runtime's own of 100 us and more).
    ``bench.traced`` is re-cut to start with the first kept device event,
    7.6 ms before the epoch's drain ends, so the spans that began earlier
    read clipped: the whole ``train.epoch_end`` took 924.7 ms."""
    return make_run(xplane.load_json(EPOCH_END), steps=1)


def test_recorded_epoch_end_reduces(recorded):
    trace = recorded.trace
    assert 0 < trace.busy_s < trace.window_s
    assert len(trace.events[0]) == 307
    assert trace.window_s == pytest.approx(0.675838222, rel=1e-6)
    assert trace.busy_s == pytest.approx(0.005902205, rel=1e-6)
    idle = sum(trace.idle_by_host.values())
    assert idle == pytest.approx(trace.window_s - trace.busy_s, abs=1e-9)
    names = {e[0] for e in trace.host}
    for part in ("drain", "host_sync", "thresholds", "ml_refit", "collect"):
        assert f"train.epoch_end.{part}" in names


def test_recorded_epoch_end_is_the_sum_of_its_parts(recorded):
    whole = read("train_epoch_end_ms", recorded)
    parts = [read("train_epoch_drain_ms", recorded),
             read("train_epoch_sync_ms", recorded),
             read("train_epoch_refit_ms", recorded),
             span_readers.span_ms("train.epoch_end.collect")(recorded)]
    assert None not in parts and whole is not None
    assert sum(parts) == pytest.approx(whole, rel=0.05)
    assert sum(parts) <= whole
    assert whole == pytest.approx(675.670842, rel=1e-6)
    assert parts[:3] == pytest.approx([7.637085, 28.289547, 637.07141],
                                      rel=1e-6)
    assert read("train_host_work_ms", recorded) == pytest.approx(
        0.3127, rel=1e-6)


def test_recorded_idle_gap_stands_under_the_program_s_names(recorded):
    idle = recorded.trace.idle_by_host
    assert read("train_idle_named_pct", recorded) >= 90.0
    assert idle.get("_none_", 0.0) < 0.05
    top = max(idle, key=idle.get)
    assert top == "train.epoch_end.ml_refit.fit"
    assert idle[top] == pytest.approx(0.637286572, rel=1e-6)
    assert read("train_idle_named_pct", recorded) == pytest.approx(
        95.82804562, rel=1e-6)
    # the outside-in reading of the same gap, and the span from inside
    from benchmark.harness import readers

    stall = readers.host_stall_ms(recorded)
    assert stall is not None
    assert read("train_epoch_end_ms", recorded) >= stall
