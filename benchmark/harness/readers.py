"""The arithmetic the per-layer readers share.  Each file under
``benchmark/metrics/`` binds one metric's name to one function here (or
holds its own few lines); a reader that finds nothing to read returns None
and the harness leaves the metric out of the line."""

from __future__ import annotations

import re
from typing import Any, List, Optional

from benchmark.harness import kernel_work as kw
from benchmark.harness import xplane

#: Ops of the detector battery, gradient verification and aggregation, as
#: far as op NAMES show them today: the sorts of the order statistics and
#: the fused-moments kernel.  A lower bound until the program names scopes.
TRUST_OPS = r"(^sort|fused_tile_moments|^cumsum|^top-k|^topk)"


def _chip0(run: Any) -> List[xplane.Event]:
    return next(iter(run.trace.events.values()))


def counter(name: str):
    def read(run: Any) -> Optional[float]:
        value = run.counters.get(name)
        return None if value is None else float(value)
    return read


# -- training ----------------------------------------------------------------


def phase_lap_ms(phase: str):
    def read(run: Any) -> Optional[float]:
        lap = (run.counters.get("phase_laps") or {}).get(phase)
        return 1e3 * lap["p50_s"] if lap else None
    return read


def train_step_device_ms(run: Any) -> Optional[float]:
    if run.trace is None:
        return None
    return 1e3 * run.trace.busy_s / run.counters["trace_steps"]


def trust_overhead_pct(run: Any) -> Optional[float]:
    if run.trace is None:
        return None
    rx = re.compile(TRUST_OPS)
    seconds = sum(s for name, s in run.trace.ops.items() if rx.search(name))
    return 100.0 * seconds / run.trace.busy_s if seconds else None


def train_mfu_pct(run: Any) -> Optional[float]:
    rate = run.end_to_end.get("train_tokens_per_s_per_chip")
    if rate is None or run.peak is None:
        return None
    flops = kw.train_flops_per_token(run.counters["n_params"]) * rate
    return 100.0 * flops / run.peak.flops_bf16


def _flash(run: Any, pattern: str, work_fn) -> Optional[float]:
    if run.trace is None or run.peak is None:
        return None
    seconds, calls = xplane.time_of(_chip0(run), pattern)
    if not calls:
        return None
    mix = run.mix
    rows = int(mix["nodes"]) * int(mix["per_node_batch"]) // run.trace.chips
    flops = nbytes = 0.0
    for layers, heads, _, d in run.family.attention_layers(run.config):
        one = work_fn(rows, heads, int(mix["seq_len"]), d)
        traced = layers * int(run.counters["trace_steps"])
        flops += one.flops * traced
        nbytes += one.bytes * traced
    if not flops:
        return None                 # the family has no attention layer
    return kw.roofline_pct(kw.Work(flops, nbytes), seconds, run.peak)[0]


def flash_fwd_roofline(run: Any) -> Optional[float]:
    return _flash(run, r"^_flash_fwd", kw.flash_fwd)


def flash_bwd_roofline(run: Any) -> Optional[float]:
    return _flash(run, r"^_flash_bwd", kw.flash_bwd)


def collective_exposed_ms(run: Any) -> Optional[float]:
    if run.trace is None:
        return None
    per_chip = [xplane.exposed_collective_seconds(ev)
                for ev in run.trace.events.values()
                if any(xplane.COLLECTIVE.match(e[0]) for e in ev)]
    if not per_chip:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.counters["trace_steps"]


# -- device ------------------------------------------------------------------


def _stall_seconds(run: Any) -> float:
    """Idle gaps of chip 0 longer than one step's device time: the device
    waited for the host for more than a whole step, which a trainer with
    steps in flight does at an epoch's end (full drain, host sync) only."""
    step_s = run.trace.busy_s / run.counters["trace_steps"]
    return sum(g for g in run.trace.gaps_s if g > step_s)


def device_idle_pct(run: Any) -> Optional[float]:
    """Idle share while the steps run: the traced window without its
    stalls (``host_stall_ms`` holds those)."""
    if run.trace is None:
        return None
    stall = _stall_seconds(run)
    idle = run.trace.window_s - run.trace.busy_s - stall
    return 100.0 * idle / (run.trace.window_s - stall)


def host_stall_ms(run: Any) -> Optional[float]:
    if run.trace is None:
        return None
    stall = _stall_seconds(run)
    return 1e3 * stall if stall else None


def hbm_peak_gb(run: Any) -> Optional[float]:
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
