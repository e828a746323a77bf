"""The reader of the Mamba-2 decode step's per-layer metric.  The kernel is
found on the device's ``XLA Ops`` line by the name the program gives its
``pallas_call`` (``ops/ssm_step.py``: ``ssm_step``); the mixers' shapes come
from the family's ``ssm_layers(config)``, a list of ``(layers, heads, head
width, state width, groups)``.  A family without the function, a program
without the kernel, an untraced run or ticks that held other work than told
read None and the harness leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Any, Optional

from benchmark.harness import kernel_work as kw
from benchmark.harness import ssm_work, xplane

STEP_KERNEL = r"^_?ssm_step"
#: A share above this is a fault of the count or of the time, not a
#: measurement (PERF.md section 3).
MOST_PCT = 105.0


def ssm_decode_roofline(run: Any) -> Optional[float]:
    """The ``ssm_step`` kernel's calls in the traced ticks against
    ``ssm_work.ssm_decode`` for the rows each tick decoded, summed over
    ticks and layers, at the chip's HBM bandwidth."""
    ticks = run.counters.get("trace_ticks")
    layers_of = getattr(run.family, "ssm_layers", None)
    if run.trace is None or run.peak is None or not ticks \
            or layers_of is None:
        return None
    if any(t["tokens"] != t["expected"] for t in ticks):
        return None                 # the ticks held other work than told
    events = next(iter(run.trace.events.values()))
    seconds, calls = xplane.time_of(events, STEP_KERNEL)
    if not calls:
        return None
    flops = nbytes = 0.0
    for layers, *shape in layers_of(run.config):
        for tick in ticks:
            one = ssm_work.ssm_decode(len(tick["decode"]), *shape)
            flops += one.flops * layers
            nbytes += one.bytes * layers
    if not nbytes:
        return None
    share = kw.roofline_pct(kw.Work(flops, nbytes), seconds, run.peak)[0]
    if share > MOST_PCT:
        raise ValueError(
            f"ssm_decode_roofline reads {share:.1f} %, over {MOST_PCT} %: the "
            "work counted or the kernel's time is wrong")
    return share
