"""Where the benchmark lives, for the tests of this directory (the repo
root goes on ``sys.path`` so that ``benchmark`` imports as a package)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark")


def manifest_data():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def recorded_serve_run(manifest, cell, config=None):
    """A run as the serving readers see it, from the window's ticks and the
    first traced tick of one ``--trace 1`` run of ``serve-large-docbatch``
    (``recorded_serve_ticks.json``; my chip run, PR 29; cut by
    ``_dev/pr29/make_fixture.py``): every paged attention call of that tick
    and every other device op of 0.2 ms or more (``events_in_tick`` ran;
    ``busy_in_tick_s`` is their union), its program calls and the host spans
    of 1 ms or more.  ``config`` stands in for the cell's own file.  Gives
    the run and the recording."""
    from benchmark.harness import peaks, result, xplane

    with open(os.path.join(BENCH, "harness",
                           "recorded_serve_ticks.json")) as f:
        raw = json.load(f)
    entry = manifest.cell(cell)
    run = result.Run(entry, config or manifest.config(entry["config"]),
                     manifest.traffic(entry["traffic"]), 1, 45.0, True)
    run.peak = peaks.peak("TPU v5 lite")
    tick = raw["traced_tick"]
    span = ("bench.tick", 0.0, tick["end"] - tick["start"])
    trace = xplane.Trace(
        {0: [tuple(e) for e in raw["device_ops"]["0"]]},
        [tuple(e) for e in raw["host"]] + [(xplane.WINDOW_SPAN,) + span[1:]])
    run.trace = xplane.summarize(trace)
    run.counters.update(
        window_ticks=raw["window_ticks"], window_s=raw["window_s"],
        trace_ticks=[tick], trace_modules=[tuple(e) for e in raw["modules"]],
        engine_prefill_s=raw["engine_prefill_s"], max_slots=24)
    run.device["memory_peak_bytes"] = int(
        raw["read_on_the_chip"]["serve_hbm_peak_gb"] * 1e9)
    return run, raw


#: The serving readers whose every input ``recorded_serve_ticks.json`` holds
#: (the ten of PR 29 and the schedule counter, which reads no run at all):
#: on the recording each of these has to read something.  A metric that a
#: later PR appends to the serving cell is swept too, and may find nothing
#: there.
RECORDED_READERS = (
    "serve_mfu_pct", "tick_wall_ms_p50", "prefill_wall_share_pct",
    "batch_occupancy_pct", "decode_program_ms", "prefill_program_ms",
    "paged_decode_roofline", "paged_prefill_roofline",
    "serve_device_idle_pct", "serve_hbm_peak_gb", "paged_grid_steps_x")


def serving_readers(data, cell):
    """The per-layer metrics of ``data`` (a parsed BENCHMARK.json) whose
    list of cells names ``cell``: by membership, so that a later cell
    appended to the lists takes none away."""
    return [m["name"] for m in data["per_layer"]
            if cell in (m.get("workloads") or ())]
