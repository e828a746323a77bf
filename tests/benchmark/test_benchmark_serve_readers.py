"""The serving cells' work arithmetic by hand, and every serving reader on
the recorded ticks of a chip run (``recorded_serve_ticks.json``)."""

import pytest

from bench_paths import (RECORDED_READERS, ROOT, recorded_serve_run,
                         serving_readers)

from benchmark.harness import kernel_work as kw
from benchmark.harness import manifest as mf
from benchmark.harness import result, serve_readers, serve_work, xplane
from benchmark.harness.families import gpt2

MANIFEST = mf.Manifest(ROOT)
CELL = "serve-large-docbatch"
SERVE_READERS = serving_readers(MANIFEST.data, CELL)


# -- the work, by hand ---------------------------------------------------------


def test_paged_decode_work_by_hand():
    # two rows of 100 and 33 live positions, 2 heads of 8, blocks of 16
    work = serve_work.paged_decode([100, 33], 2, 8, 16)
    assert work.flops == 2 * 2 * (100 + 33) * 8 * 2       # QK^T and PV
    kv = (7 * 16 + 3 * 16) * 2 * 2 * 8 * 2                # K and V blocks
    assert work.bytes == kv + 2 * (2 * 2 * 8 * 2)         # + Q and O a row
    assert serve_work.paged_decode([], 2, 8, 16) == kw.Work(0, 0)


def test_paged_prefill_work_by_hand():
    # 16 rows from position 64: each row sees what is cached and itself
    work = serve_work.paged_prefill([(64, 16)], 2, 8, 16)
    pairs = 16 * 64 + 16 * 17 / 2
    assert pairs == kw.causal_pairs(16, 80)
    assert work.flops == 2 * 2 * pairs * 8 * 2
    assert work.bytes == 80 * 2 * 2 * 8 * 2 + 2 * 16 * 2 * 8 * 2
    first = serve_work.paged_prefill([(0, 13)], 2, 8, 16)
    assert first.flops == 2 * 2 * (13 * 14 / 2) * 8 * 2
    assert first.bytes == 16 * 2 * 2 * 8 * 2 + 2 * 13 * 2 * 8 * 2
    both = serve_work.paged_prefill([(64, 16), (0, 13)], 2, 8, 16)
    assert both.flops == work.flops + first.flops
    assert both.bytes == work.bytes + first.bytes


def test_model_flops_by_hand():
    cfg = {"n_embd": 8, "n_layer": 3, "vocab_size": 50, "n_head": 2,
           "n_positions": 16}
    body = 3 * (8 * 24 + 8 * 8 + 8 * 32 + 32 * 8)
    assert gpt2.model_flops(cfg, 10, 4) == 2 * body * 10 + 2 * 50 * 8 * 4
    large = dict(MANIFEST.config("gpt2-large-774m"))
    # the body and the tied head together are the parameters that multiply
    n = gpt2.param_count(large)
    d, layers = large["n_embd"], large["n_layer"]
    small = large["n_positions"] * d + layers * 13 * d + 2 * d
    assert gpt2.model_flops(large, 1, 1) == 2 * (n - small)


# -- the readers on ticks recorded on the chip ---------------------------------


@pytest.fixture(scope="module")
def recorded():
    return recorded_serve_run(MANIFEST, CELL)


@pytest.mark.parametrize("name", SERVE_READERS)
def test_every_serving_reader_reads_the_recorded_ticks(recorded, name):
    run, raw = recorded
    value = MANIFEST.reader(name)(run)
    if name not in RECORDED_READERS:    # appended since the recording: it
        assert value is None or value > 0       # may find nothing to read
        return
    assert value is not None and value > 0
    if "roofline" in name or "mfu" in name or name.endswith("_pct"):
        assert value <= 100.0
    whole_window = ("serve_mfu_pct", "tick_wall_ms_p50", "serve_hbm_peak_gb",
                    "prefill_wall_share_pct", "batch_occupancy_pct")
    if name in whole_window:            # the cut keeps all they read
        assert value == pytest.approx(raw["read_on_the_chip"][name],
                                      rel=1e-6)


def test_program_times_are_a_call_s_mean(recorded):
    run, raw = recorded
    decode = [e for e in raw["modules"] if "paged_decode" in e[0]]
    chunks = [e for e in raw["modules"] if "paged_chunk" in e[0]]
    tick = raw["traced_tick"]
    assert len(decode) == 1 and len(chunks) == len(tick["prefill"])
    assert serve_readers.decode_program_ms(run) == pytest.approx(
        1e3 * decode[0][2])
    assert serve_readers.prefill_program_ms(run) == pytest.approx(
        1e3 * sum(e[2] for e in chunks) / len(chunks))


def test_kernel_rooflines_by_hand(recorded):
    run, raw = recorded
    tick = raw["traced_tick"]
    peak = run.peak
    for pattern, work, read in (
            (r"^_paged_attn_call", serve_work.paged_decode(
                tick["decode"], 20, 64, 16), "paged_decode_roofline"),
            (r"^_paged_prefill_call", serve_work.paged_prefill(
                [tuple(c) for c in tick["prefill"]], 20, 64, 16),
             "paged_prefill_roofline")):
        seconds, calls = xplane.time_of(run.trace.events[0], pattern)
        assert calls % 36 == 0 and calls > 0         # one a layer a program
        least = max(36 * work.flops / peak.flops_bf16,
                    36 * work.bytes / peak.hbm_bytes_per_s)
        assert MANIFEST.reader(read)(run) == pytest.approx(
            100.0 * least / seconds)
    # both kernels are bound by the K and V they read, not by their products
    assert kw.roofline_pct(serve_work.paged_decode(
        tick["decode"], 20, 64, 16), 1.0, peak)[1] == "memory"


def test_a_tick_that_held_other_work_silences_the_rooflines(recorded):
    run, raw = recorded
    odd = dict(raw["traced_tick"], tokens=raw["traced_tick"]["tokens"] + 1)
    fresh = result.Run(run.cell, run.config, run.mix, 1, 45.0, True)
    fresh.peak, fresh.trace = run.peak, run.trace
    fresh.counters.update(run.counters, trace_ticks=[odd])
    assert serve_readers.paged_decode_roofline(fresh) is None
    assert serve_readers.paged_prefill_roofline(fresh) is None
    assert serve_readers.decode_program_ms(fresh) is not None


def test_window_readers_by_hand(recorded):
    run, raw = recorded
    ticks = raw["window_ticks"]
    walls = sorted(t["end"] - t["start"] for t in ticks)
    middle = (walls[len(walls) // 2 - 1] + walls[len(walls) // 2]) / 2 \
        if len(walls) % 2 == 0 else walls[len(walls) // 2]
    assert serve_readers.tick_wall_ms_p50(run) == pytest.approx(1e3 * middle)
    fed = sum(rows for t in ticks for _, rows in t["prefill"]) + sum(
        len(t["decode"]) for t in ticks)
    tokens = sum(t["tokens"] for t in ticks)
    assert tokens / raw["window_s"] == pytest.approx(
        raw["read_on_the_chip"]["serve_tokens_per_s"], rel=1e-6)
    flops = gpt2.model_flops(
        {k: int(run.config[k]) for k in ("n_embd", "n_layer", "vocab_size")},
        fed, tokens)
    assert serve_readers.serve_mfu_pct(run) == pytest.approx(
        100 * flops / raw["window_s"] / 197e12)
    assert all(t["tokens"] == t["expected"] for t in ticks)
    assert all(t["in_flight"] == 24 for t in ticks)
    assert serve_readers.batch_occupancy_pct(run) == 100.0
    idle = serve_readers.device_idle_pct(run)
    assert idle == pytest.approx(100 * (1 - run.trace.busy_s
                                        / run.trace.window_s))
