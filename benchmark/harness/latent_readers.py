"""The readers of the latent cache's per-layer metrics.  Each file under
``benchmark/metrics/`` binds one metric's name to one function here; a reader
that finds nothing to read (a family with no latent layer, a program with no
such kernel or gauge) returns None and the harness leaves the metric out of
the line.

The two kernels are found on the device's ``XLA Ops`` line by the name the
program gives their ``pallas_call`` (``ops/paged_attention.py``:
``latent_decode`` and ``latent_prefill``); the layers' shapes come from the
family's ``latent_layers(config)``, a list of ``(layers, heads, nope, rope,
value, rank)``, one entry a group of latent layers that share a shape.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from benchmark.harness import kernel_work as kw
from benchmark.harness import latent_work, xplane
from benchmark.harness.expert_readers import _series

DECODE_KERNEL = r"^_?latent_decode"
PREFILL_KERNEL = r"^_?latent_prefill"
POOL_BYTES = "tddl_serve_latent_pool_bytes"


def _roofline(run: Any, pattern: str, work_of: Callable[..., kw.Work]
              ) -> Optional[float]:
    """The kernel's calls in the traced ticks against ``work_of(tick,
    heads, nope, rope, value, rank, block)`` summed over ticks and latent
    layers."""
    ticks = run.counters.get("trace_ticks")
    layers_of = getattr(run.family, "latent_layers", None)
    if run.trace is None or run.peak is None or not ticks \
            or layers_of is None:
        return None
    if any(t["tokens"] != t["expected"] for t in ticks):
        return None                 # the ticks held other work than told
    events = next(iter(run.trace.events.values()))
    seconds, calls = xplane.time_of(events, pattern)
    if not calls:
        return None
    block = int(run.config["deployment"]["serve_config"]["block_size"])
    flops = nbytes = 0.0
    for layers, *shape in layers_of(run.config):
        for tick in ticks:
            one = work_of(tick, *shape, block)
            flops += one.flops * layers
            nbytes += one.bytes * layers
    if not flops:
        return None
    return kw.roofline_pct(kw.Work(flops, nbytes), seconds, run.peak)[0]


def latent_decode_roofline(run: Any) -> Optional[float]:
    return _roofline(
        run, DECODE_KERNEL, lambda tick, heads, nope, rope, value, rank,
        block: latent_work.latent_decode(tick["decode"], heads, rank + rope,
                                         rank, block))


def latent_prefill_roofline(run: Any) -> Optional[float]:
    return _roofline(
        run, PREFILL_KERNEL, lambda tick, heads, nope, rope, value, rank,
        block: latent_work.latent_prefill(tick["prefill"], heads,
                                          nope + rope, value, rank + rope,
                                          block))


def latent_pool_gb(run: Any) -> Optional[float]:
    held = [float(s["value"]) for s in _series(POOL_BYTES) if s["value"]]
    return max(held) / 1e9 if held else None
