"""The arithmetic the serving cells' per-layer readers share.  Each file
under ``benchmark/metrics/`` binds one metric's name to one function here; a
reader that finds nothing to read returns None and the harness leaves the
metric out of the line.

``run.counters['window_ticks']`` and ``['trace_ticks']`` are the driver's own
records of the ticks (``drivers/serve_closed.py``): wall clock round
``step()``, tokens, and the work each held as the clients can tell it.
"""

from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional

from benchmark.harness import kernel_work as kw
from benchmark.harness import serve_work, xplane

#: Program calls on the device's ``XLA Modules`` line.
DECODE_PROGRAM = r"paged_decode"
PREFILL_PROGRAM = r"paged_(chunk|prefill)"
#: The paged attention kernels on the ``XLA Ops`` line.
DECODE_KERNEL = r"^_paged_attn_call"
PREFILL_KERNEL = r"^_paged_prefill_call"


def _window_ticks(run: Any) -> List[Dict[str, Any]]:
    return run.counters.get("window_ticks") or []


# -- whole window ------------------------------------------------------------


def serve_mfu_pct(run: Any) -> Optional[float]:
    """The model's matrix products for every prompt token prefilled and
    every token decoded in the window, over the window's seconds and the
    chip's bf16 peak."""
    ticks = _window_ticks(run)
    if not ticks or run.peak is None:
        return None
    fed = sum(rows for t in ticks for _, rows in t["prefill"]) \
        + sum(len(t["decode"]) for t in ticks)
    sampled = sum(t["tokens"] for t in ticks)
    flops = run.family.model_flops(run.config, fed, sampled)
    return 100.0 * flops / run.counters["window_s"] / run.peak.flops_bf16


# -- scheduler ---------------------------------------------------------------


def tick_wall_ms_p50(run: Any) -> Optional[float]:
    ticks = _window_ticks(run)
    if not ticks:
        return None
    return 1e3 * statistics.median(t["end"] - t["start"] for t in ticks)


def prefill_wall_share_pct(run: Any) -> Optional[float]:
    """The engine's own counter (``prefill_chunk_fraction`` of
    ``metrics_summary()``): host wall inside its prefill-chunk dispatches,
    over the window."""
    seconds = run.counters.get("engine_prefill_s")
    if seconds is None or not run.counters.get("window_s"):
        return None
    return 100.0 * seconds / run.counters["window_s"]


def batch_occupancy_pct(run: Any) -> Optional[float]:
    """Requests in flight when a tick began over the engine's slots, mean
    over the window's ticks."""
    ticks = _window_ticks(run)
    if not ticks:
        return None
    return 100.0 * statistics.fmean(t["in_flight"] for t in ticks) \
        / run.counters["max_slots"]


# -- programs and kernels (the traced slice) ----------------------------------


def _program_ms(run: Any, pattern: str) -> Optional[float]:
    modules = run.counters.get("trace_modules")
    if not modules:
        return None
    seconds, calls = xplane.time_of(modules, pattern)
    return 1e3 * seconds / calls if calls else None


def decode_program_ms(run: Any) -> Optional[float]:
    return _program_ms(run, DECODE_PROGRAM)


def prefill_program_ms(run: Any) -> Optional[float]:
    return _program_ms(run, PREFILL_PROGRAM)


def _kernel_roofline(run: Any, pattern: str, work_of) -> Optional[float]:
    ticks = run.counters.get("trace_ticks")
    if run.trace is None or run.peak is None or not ticks:
        return None
    if any(t["tokens"] != t["expected"] for t in ticks):
        return None                 # the ticks held other work than told
    events = next(iter(run.trace.events.values()))
    seconds, calls = xplane.time_of(events, pattern)
    if not calls:
        return None
    block = int(run.config["deployment"]["serve_config"]["block_size"])
    flops = nbytes = 0.0
    for layers, heads, kv_heads, d in run.family.attention_layers(run.config):
        for tick in ticks:
            one = work_of(tick, heads, d, block, kv_heads)
            flops += one.flops * layers
            nbytes += one.bytes * layers
    if not flops:
        return None                 # the family has no paged attention layer
    return kw.roofline_pct(kw.Work(flops, nbytes), seconds, run.peak)[0]


def paged_decode_roofline(run: Any) -> Optional[float]:
    return _kernel_roofline(
        run, DECODE_KERNEL, lambda tick, heads, d, block, kv_heads:
        serve_work.paged_decode(tick["decode"], heads, d, block,
                                kv_heads=kv_heads))


def paged_prefill_roofline(run: Any) -> Optional[float]:
    return _kernel_roofline(
        run, PREFILL_KERNEL, lambda tick, heads, d, block, kv_heads:
        serve_work.paged_prefill(tick["prefill"], heads, d, block,
                                 kv_heads=kv_heads))


# -- device ------------------------------------------------------------------


def device_idle_pct(run: Any) -> Optional[float]:
    """Idle share of the traced ticks: 1 - busy over the slice."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
