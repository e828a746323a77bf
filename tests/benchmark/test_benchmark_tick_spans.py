"""The readers of the serving program's tick and request spans
(``tick_span_readers``): by hand on made-up ticks, silent where the program
opens no such span (``recorded_serve_ticks.json``, recorded before it did)
or keeps no such series, and right on four traced ticks recorded on the chip
(``recorded_serve_tick_spans.json``)."""

import json
import os

import pytest

from bench_paths import BENCH, ROOT, manifest_data, recorded_serve_run

from benchmark.harness import manifest as mf
from benchmark.harness import result, tick_span_readers, xplane

MANIFEST = mf.Manifest(ROOT)
SERVING = ["serve-large-docbatch", "serve-solar2-longdoc",
           "serve-kimi-linear-longctx"]
TRAINING = ["train-124m-trust-1chip", "train-124m-trust-dp4"]
TRACED = ["serve_idle_named_pct", "serve_idle_admit_ms",
          "serve_idle_prefill_ms", "serve_idle_decode_ms",
          "serve_idle_retire_ms", "serve_idle_submit_ms"]
WINDOWED = ["serve_dispatches_per_tick", "serve_host_syncs_per_tick",
            "tick_host_ms", "tick_phase_max_ms"]
NEW = TRACED + WINDOWED
RECORDED = os.path.join(BENCH, "harness", "recorded_serve_tick_spans.json")
WINDOW = "since_last_summary"


def make_run(trace=None):
    entry = MANIFEST.cell(SERVING[0])
    run = result.Run(entry, MANIFEST.config(entry["config"]),
                     MANIFEST.traffic(entry["traffic"]), 1, 45.0,
                     trace is not None)
    if trace is not None:
        run.trace = xplane.summarize(trace)
    return run


def read(name, run):
    return MANIFEST.reader(name)(run)


@pytest.fixture
def registry(monkeypatch):
    """A fresh obs registry in the place of the program's global one."""
    from trustworthy_dl_tpu.obs import registry as module

    fresh = module.MetricsRegistry()
    monkeypatch.setattr(module, "get_registry", lambda: fresh)
    return fresh


# -- the entries ----------------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_each_entry_has_its_reader_and_the_serving_cells_report_it(name):
    (entry,) = [m for m in manifest_data()["per_layer"]
                if m["name"] == name]
    assert entry["layer"] == "serving scheduler"
    assert entry["moves"] == "serve_tokens_per_s"
    assert "workloads" not in entry
    assert entry["source"] == ("program_counter" if name in WINDOWED
                               else "program_span")
    assert callable(MANIFEST.reader(name))
    for cell in SERVING:
        assert entry in MANIFEST.per_layer(cell), cell
    for cell in TRAINING:
        assert entry not in MANIFEST.per_layer(cell), cell


# -- by hand ----------------------------------------------------------------------

# Two traced ticks, seconds.  The device runs five times and idles in six
# gaps between the first ``bench.tick``'s start (0.005) and the last one's
# end (0.100); each gap's middle lies under the spans named beside it.
OPS = [("fusion.1", 0.010, 0.010),      # gap 0.005-0.010: admission
       ("fusion.2", 0.030, 0.014),      # gap 0.020-0.030: the decode pull
       ("fusion.3", 0.060, 0.010),      # gap 0.044-0.060: the key stream
       ("fusion.4", 0.074, 0.002),      # gap 0.070-0.074: the tick itself
       ("fusion.5", 0.096, 0.0005)]     # gap 0.076-0.096: the monitor
#                      gap 0.0965-0.100: the driver's, under no program span
HOST = [
    ("bench.traced", 0.0, 0.110),
    ("bench.tick", 0.005, 0.052),
    ("serve.tick", 0.006, 0.038),
    ("serve.tick.expire", 0.0061, 0.0002),
    ("serve.tick.admit", 0.0065, 0.0025),
    ("serve.prefix_lookup", 0.0066, 0.0002),
    ("serve.decode_tick", 0.0095, 0.0300),
    ("serve.prefill_chunk", 0.0100, 0.0110),
    ("serve.prefill_chunk.dispatch", 0.0101, 0.0020),
    ("serve.prefill_chunk", 0.0125, 0.0090),
    ("serve.prefill_chunk.dispatch", 0.0126, 0.0020),
    ("serve.prefill_chunk.pull", 0.0150, 0.0060),
    ("serve.decode_tick.build", 0.0216, 0.0003),
    ("serve.decode_tick.dispatch", 0.0220, 0.0020),
    ("serve.decode_tick.pull", 0.0241, 0.0150),
    ("ReadSyncFlag", 0.0245, 0.0010),       # the runtime's, shorter
    ("serve.decode_tick.record", 0.0392, 0.0002),
    ("serve.tick.emit", 0.0396, 0.0020),
    ("serve.tick.account", 0.0417, 0.0010),
    ("serve.submit", 0.0450, 0.0110),
    ("serve.submit.key_stream", 0.0460, 0.0090),
    ("bench.tick", 0.058, 0.042),
    ("serve.tick", 0.059, 0.037),
    ("serve.tick.admit", 0.0592, 0.0004),
    ("serve.tick.admit.zero_state", 0.0593, 0.0002),
    ("serve.decode_tick", 0.0600, 0.0090),
    ("serve.decode_tick.dispatch", 0.0610, 0.0020),
    ("serve.decode_tick.pull", 0.0640, 0.0040),
    ("serve.tick.emit", 0.0750, 0.0205),
    ("serve.tick.retire", 0.0752, 0.0200),
    ("serve.monitor", 0.0755, 0.0190),
]
#: Idle seconds by the innermost span of the program over each gap's middle.
BY_SPAN = {"serve.tick.admit": 0.005, "serve.decode_tick.pull": 0.010,
           "serve.submit.key_stream": 0.016, "serve.tick": 0.004,
           "serve.monitor": 0.020, "_outside_": 0.0035}


@pytest.fixture(scope="module")
def made_up():
    return make_run(xplane.Trace({0: list(OPS)}, list(HOST)))


def test_idle_time_goes_to_the_program_s_innermost_span(made_up):
    assert tick_span_readers.traced_ticks(made_up) == pytest.approx(
        (0.005, 0.100, 2))
    idle = tick_span_readers.idle_by_span(made_up)
    assert idle == pytest.approx(BY_SPAN)
    # unfiltered, the runtime's shorter event takes the pull's gap: why
    # the host list is cut to the program's names
    assert made_up.trace.idle_by_host["ReadSyncFlag"] == pytest.approx(0.010)
    groups = tick_span_readers.idle_by_group(made_up)
    assert groups == pytest.approx({
        "admit": 0.005, "prefill": 0.0, "decode": 0.010, "retire": 0.020,
        "submit": 0.016, "serve.tick": 0.004, "_outside_": 0.0035})


@pytest.mark.parametrize("name, value", [
    ("serve_idle_named_pct", 100.0 * 0.055 / 0.0585),
    ("serve_idle_admit_ms", 2.5), ("serve_idle_prefill_ms", 0.0),
    ("serve_idle_decode_ms", 5.0), ("serve_idle_retire_ms", 10.0),
    ("serve_idle_submit_ms", 8.0)])
def test_each_traced_reader_by_hand(made_up, name, value):
    assert read(name, made_up) == pytest.approx(value)


def test_an_op_after_the_last_tick_brings_no_idle_time_beyond_it():
    """``bench.traced`` outlasts the last tick, and the device may run on
    in it: the gap before such an op ends with the tick, and the gaps
    between later ops are nobody's."""
    late = [("fusion.6", 0.104, 0.001), ("fusion.7", 0.108, 0.001)]
    run = make_run(xplane.Trace({0: OPS + late}, list(HOST)))
    assert tick_span_readers.idle_by_span(run) == pytest.approx(BY_SPAN)
    # an op that runs across the last tick's end is cut there
    across = [("fusion.6", 0.099, 0.004)]
    run = make_run(xplane.Trace({0: OPS + across}, list(HOST)))
    assert tick_span_readers.idle_by_span(run) == pytest.approx(
        {**BY_SPAN, "_outside_": 0.0025})


@pytest.mark.parametrize("name, group", [
    ("serve.tick.expire", "admit"), ("serve.tick.admit", "admit"),
    ("serve.tick.admit.zero_state", "admit"),
    ("serve.prefix_lookup", "admit"),
    ("serve.prefill_chunk", "prefill"),
    ("serve.prefill_chunk.dispatch", "prefill"),
    ("serve.prefill_chunk.pull", "prefill"),
    ("serve.decode_tick", "decode"), ("serve.decode_tick.build", "decode"),
    ("serve.decode_tick.record", "decode"),
    ("serve.spec_draft.pull", "decode"), ("serve.spec_verify", "decode"),
    ("serve.tick.emit", "retire"), ("serve.tick.retire", "retire"),
    ("serve.monitor", "retire"), ("serve.tick.account", "retire"),
    ("serve.submit", "submit"), ("serve.submit.key_stream", "submit"),
    ("serve.tick", "serve.tick"), ("serve.something_new", "serve.tick")])
def test_every_span_of_the_vocabulary_has_its_group(name, group):
    assert tick_span_readers.group_of(name) == group


def phase_gauges(registry, seconds, counts, longest):
    for metric, values in ((tick_span_readers.PHASE_SECONDS, seconds),
                           (tick_span_readers.PHASE_COUNT, counts),
                           (tick_span_readers.PHASE_LONGEST, longest)):
        gauge = registry.gauge(metric, "", labels=("phase", "scope"))
        for phase, value in values.items():
            gauge.set(value, phase=phase, scope=WINDOW)
            gauge.set(7 * value + 1, phase=phase, scope="total")


def test_the_window_s_readers_by_hand(registry, capsys):
    phase_gauges(
        registry,
        seconds={"serve.tick": 2.0, "serve.submit": 0.2,
                 "serve.decode_tick.pull": 0.5,
                 "serve.prefill_chunk.pull": 0.3,
                 "serve.prefill_chunk.dispatch": 0.6},
        counts={"serve.tick": 40, "serve.submit": 30,
                "serve.prefill_chunk.dispatch": 180,
                "serve.decode_tick.dispatch": 38,
                "serve.tick.admit.zero_state": 30,
                "serve.prefill_chunk": 180, "serve.prefill_chunk.pull": 29,
                "serve.decode_tick.pull": 38, "serve.submit.key_stream": 30},
        longest={"serve.tick": 0.090, "serve.decode_tick": 0.085,
                 "serve.decode_tick.pull": 0.080, "serve.submit": 0.004,
                 "serve.spec_verify": 0.0})
    run = make_run()
    # every ``.dispatch`` and the state row's zeroing; every ``.pull`` and
    # the key stream; over the window's ticks
    assert read("serve_dispatches_per_tick", run) == (180 + 38 + 30) / 40
    assert read("serve_host_syncs_per_tick", run) == (29 + 38 + 30) / 40
    assert read("tick_host_ms", run) == pytest.approx(
        1e3 * (2.0 + 0.2 - 0.5 - 0.3) / 40)
    assert read("tick_phase_max_ms", run) == pytest.approx(90.0)
    said = capsys.readouterr().err
    assert said.startswith("tick_phase_max_ms: serve.tick 90.000, "
                           "serve.decode_tick 85.000, "
                           "serve.decode_tick.pull 80.000, serve.submit")
    assert "spec_verify" not in said


def test_the_window_s_readers_read_what_a_tiny_engine_keeps(registry):
    """The series' names and scope, held to the program: a tiny engine, two
    summaries, and the window between them."""
    import jax

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

    cfg = gpt2.GPT2Config(vocab_size=181, n_positions=32, n_layer=1,
                          n_embd=32, n_head=4)
    engine = ServingEngine(gpt2.init_params(jax.random.PRNGKey(0), cfg), cfg,
                           max_slots=2, max_seq=32, block_size=8,
                           prefill_chunk=8, registry=registry)
    engine.submit(ServeRequest(prompt=list(range(1, 12)), max_new_tokens=3))
    engine.step()
    engine.metrics_summary()
    ticks = 0
    while engine.busy:
        engine.step()
        ticks += 1
    summary = engine.metrics_summary()
    phases = summary["tick_phases"][WINDOW]
    assert phases["serve.tick"]["count"] == ticks > 1
    run = make_run()
    pulls = sum(block["seconds"] for name, block in phases.items()
                if name.endswith(".pull"))
    assert read("tick_host_ms", run) == pytest.approx(
        1e3 * (phases["serve.tick"]["seconds"] - pulls) / ticks)
    assert read("tick_phase_max_ms", run) == pytest.approx(
        1e3 * max(block["longest_s"] for block in phases.values()))
    # the first tick fed the prompt's first chunk; the window holds its
    # second and last, then a decode call a tick: one call and one pull each
    assert read("serve_dispatches_per_tick", run) == 1.0
    assert read("serve_host_syncs_per_tick", run) == 1.0


# -- silence ----------------------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_every_reader_is_silent_where_there_is_nothing_to_read(name, registry):
    """A run without a trace, the ticks recorded before the program opened
    a span, and an engine's registry without a phase series."""
    assert read(name, make_run()) is None
    run, _ = recorded_serve_run(MANIFEST, SERVING[0])
    assert any(e[0] == "bench.tick" for e in run.trace.host)
    assert read(name, run) is None
    registry.gauge("tddl_serve_state_pool_bytes", "").set(0.0)
    assert read(name, run) is None


# -- four ticks recorded on the chip ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """The run as the readers see it, from ``recorded_serve_tick_spans.json``
    (its ``from`` says which chip run, and what the cut kept)."""
    with open(RECORDED) as f:
        raw = json.load(f)
    trace = xplane.Trace(
        {0: [("busy", start, dur) for start, dur in raw["device_busy"]]},
        [tuple(e) for e in raw["host"]]
        + [(xplane.WINDOW_SPAN, 0.0, raw["traced_s"])])
    run = make_run(trace)
    run.counters["trace_modules"] = [tuple(e) for e in raw["modules"]]
    return run, raw


@pytest.fixture
def recorded_registry(recorded, monkeypatch):
    from benchmark.harness import expert_readers

    _, raw = recorded
    monkeypatch.setattr(expert_readers, "_series",
                        lambda name: raw["registry"].get(name, []))


@pytest.mark.parametrize("name", NEW)
def test_the_recording_reduces_to_what_the_chip_run_printed(
        recorded, recorded_registry, name):
    run, raw = recorded
    assert read(name, run) == pytest.approx(raw["read_on_the_chip"][name],
                                            rel=1e-5, abs=1e-6)


def test_the_groups_sum_to_the_idle_time_of_the_traced_ticks(recorded):
    run, raw = recorded
    groups = tick_span_readers.idle_by_group(run)
    assert set(groups) == {"admit", "prefill", "decode", "retire", "submit",
                           "serve.tick", "_outside_"}
    lo, hi, ticks = tick_span_readers.traced_ticks(run)
    assert ticks == 4
    # every idle second of the slice but the slivers of ``bench.traced``
    # before the first tick and after the last
    slivers = (lo - 0.0) + (raw["traced_s"] - hi)
    assert sum(groups.values()) == pytest.approx(
        sum(run.trace.gaps_s) - slivers, rel=0.02)
    # which is the idle time the accepted ``serve_device_idle_pct`` implies
    implied = raw["read_on_the_chip"]["serve_device_idle_pct"] / 100.0 \
        * raw["bench_traced_s"]
    assert sum(groups.values()) == pytest.approx(implied, rel=0.02)
    by_name = {name: 1e3 * groups[group] / ticks for name, group in (
        ("serve_idle_admit_ms", "admit"), ("serve_idle_prefill_ms", "prefill"),
        ("serve_idle_decode_ms", "decode"), ("serve_idle_retire_ms", "retire"),
        ("serve_idle_submit_ms", "submit"))}
    assert by_name == pytest.approx(
        {name: raw["read_on_the_chip"][name] for name in by_name},
        rel=1e-5, abs=1e-6)


def test_a_dispatch_span_is_one_program_call(recorded):
    """The ``*.dispatch`` spans of the traced ticks are as many as the calls
    of the two serving programs on the device's ``XLA Modules`` line."""
    run, _ = recorded
    spans = tick_span_readers.program_spans(run)
    for span, program in (("serve.prefill_chunk.dispatch", "paged_chunk"),
                          ("serve.decode_tick.dispatch", "paged_decode")):
        calls = [e for e in run.counters["trace_modules"]
                 if program in e[0]]
        assert len(calls) == sum(e[0] == span for e in spans) > 0, span
