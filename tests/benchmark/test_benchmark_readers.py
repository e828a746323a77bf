"""Every per-layer reader: silent when there is nothing to read, and right
on the recorded trace and on hand-made counters."""

import glob
import os

import pytest

from bench_paths import BENCH, ROOT

from benchmark.harness import kernel_work as kw
from benchmark.harness import manifest as mf
from benchmark.harness import peaks, readers, result, xplane

READERS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(BENCH, "metrics", "*.py")) if not p.endswith("__init__.py"))
MANIFEST = mf.Manifest(ROOT)


def make_run(cell, trace=False):
    entry = MANIFEST.cell(cell)
    run = result.Run(entry, MANIFEST.config(entry["config"]),
                     MANIFEST.traffic(entry["traffic"]), 1, 45.0, trace)
    run.peak = peaks.peak("TPU v5 lite")
    return run


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_when_there_is_nothing_to_read(name):
    run = make_run("train-124m-trust-1chip")
    assert MANIFEST.reader(name)(run) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_never_reads_nought_or_over_a_hundred_percent(name):
    """On the recorded training trace with counters of a real run."""
    run = make_run("train-124m-trust-1chip", trace=True)
    run.trace = xplane.summarize(xplane.load_json(
        os.path.join(BENCH, "harness", "recorded_trace.json")))
    run.counters.update(trace_steps=1, n_params=124_439_808, compile_s=2.0,
                        compiles_in_window=0,
                        phase_laps={"data": {"p50_s": 0.001},
                                    "host": {"p50_s": 0.125}})
    run.end_to_end["train_tokens_per_s_per_chip"] = 63_000.0
    run.device["memory_peak_bytes"] = 10_800_000_000
    value = MANIFEST.reader(name)(run)
    if value is None:
        return
    if "roofline" in name or "mfu" in name:
        assert 0.0 < value <= 100.0


def test_flash_forward_roofline_by_hand():
    run = make_run("train-124m-trust-1chip", trace=True)
    run.trace = xplane.summarize(xplane.load_json(
        os.path.join(BENCH, "harness", "recorded_trace.json")))
    run.counters["trace_steps"] = 1
    seconds, calls = xplane.time_of(run.trace.events[0], r"^_flash_fwd")
    # the cut holds 6 of the 12 layers' calls; the reader counts a whole
    # step's 12, so by hand: 12 layers of work over the 6 calls' time
    one = kw.flash_fwd(8, 12, 1024, 64)
    want = 100.0 * max(12 * one.flops / 197e12,
                       12 * one.bytes / 819e9) / seconds
    assert readers.flash_fwd_roofline(run) == pytest.approx(want)
    assert calls == 6


def test_train_mfu_by_hand():
    run = make_run("train-124m-trust-1chip")
    run.counters["n_params"] = 124_439_808
    run.end_to_end["train_tokens_per_s_per_chip"] = 63_766.8465432039
    assert readers.train_mfu_pct(run) == pytest.approx(
        100 * 6 * 124_439_808 * 63_766.8465432039 / 197e12)


@pytest.mark.parametrize("phase,name", [("data", "train_data_wait_ms"),
                                        ("host", "train_host_drain_ms")])
def test_phase_laps_come_from_the_reporter_s_own_report(phase, name):
    """The program's ``StepTimeReporter.report()`` is what the driver hands
    over; its median of a phase over the steps is the reading."""
    from trustworthy_dl_tpu.obs.report import StepTimeReporter

    timer = StepTimeReporter()
    timer.discard_step()
    for _ in range(3):
        timer.lap("data")
        timer.lap("compute")
        timer.lap("host")
        timer.finish_step()
    run = make_run("train-124m-trust-1chip")
    run.counters["phase_laps"] = timer.report()["phases"]
    value = MANIFEST.reader(name)(run)
    assert value == pytest.approx(
        1e3 * run.counters["phase_laps"][phase]["p50_s"]) and value > 0


def test_idle_share_and_host_stall_by_hand():
    """Two steps of 1.0 s of device time; the gap of 0.1 s is idle while
    stepping, the gap of 1.5 s (longer than a step) is a stall."""
    run = make_run("train-124m-trust-1chip", trace=True)
    run.trace = xplane.summarize(xplane.Trace(
        {0: [("fusion.1", 0.0, 0.9), ("fusion.2", 1.0, 1.1),
             ("copy.1", 3.6, 0.0001)]},
        [("bench.traced", 0.0, 3.7)]))
    run.counters["trace_steps"] = 2
    assert readers.host_stall_ms(run) == pytest.approx(1500.0)
    assert readers.device_idle_pct(run) == pytest.approx(
        100 * (0.1 + 0.0999) / 2.2)
    run.trace = xplane.summarize(xplane.Trace(
        {0: [("fusion.1", 0.0, 0.9), ("fusion.2", 1.0, 1.0)]},
        [("bench.traced", 0.0, 2.0)]))
    assert readers.host_stall_ms(run) is None
    assert readers.device_idle_pct(run) == pytest.approx(5.0)
