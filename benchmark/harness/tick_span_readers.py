"""Readers of the serving program's own tick and request spans (``serve.*``,
opened through ``utils.profiling.span`` of ``trustworthy_dl_tpu``): on
``/host:CPU`` of the traced ticks beside the device's ops, and, over the
untraced window, as the totals the engine keeps by name in its obs registry
(``metrics_summary()`` writes them; the driver asks at the window's two ends,
so ``scope="since_last_summary"`` holds the WINDOW).  A program that opens no
such span or keeps no such series (any commit before they came, a run that
built no engine) reads None everywhere, and the harness leaves the metric
out.

The device's idle time in the traced ticks is given to the program's phases:
chip 0's gaps between the first ``bench.tick``'s start and the last one's
end, each whole to the SHORTEST ``serve.*`` span over its middle
(``xplane.attribute_gaps`` with the host list filtered to the program's
names, so the runtime's shorter events no longer take the gap).  A gap under
no span of the program is ``_outside_``: the driver's own work between a
tick's return and the next submit or step.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness import expert_readers, xplane
from benchmark.harness.serve_trace import TICK_SPAN

#: Every span of the serving program starts with this.
PROGRAM_PREFIX = "serve."
#: The span round ``engine.step``: what of a tick no phase inside it covers.
TICK = "serve.tick"
#: Idle time under no span of the program.
OUTSIDE = "_outside_"
#: A phase group -> the span names it holds, each with everything that
#: extends it (``serve.prefill_chunk`` holds ``.dispatch`` and ``.pull``).
GROUPS: Dict[str, Tuple[str, ...]] = {
    "admit": ("serve.tick.expire", "serve.tick.admit",
              "serve.prefix_lookup"),
    "prefill": ("serve.prefill_chunk",),
    "decode": ("serve.decode_tick", "serve.spec_draft", "serve.spec_verify"),
    "retire": ("serve.tick.emit", "serve.tick.retire", "serve.monitor",
               "serve.tick.account"),
    "submit": ("serve.submit",),
}
#: One program call each / at least one wait for the device each: counted
#: over the window, from the registry's phase counts.
DISPATCH_SUFFIX, ZERO_STATE = ".dispatch", "serve.tick.admit.zero_state"
PULL_SUFFIX, KEY_STREAM = ".pull", "serve.submit.key_stream"

PHASE_SECONDS = "tddl_serve_phase_seconds"
PHASE_COUNT = "tddl_serve_phase_count"
PHASE_LONGEST = "tddl_serve_phase_longest_seconds"


def group_of(name: str) -> str:
    """The group of a program span; ``serve.tick`` itself, and a name no
    group holds, are the tick's own remainder."""
    for group, heads in GROUPS.items():
        if any(name == head or name.startswith(head + ".")
               for head in heads):
            return group
    return TICK


# -- the traced ticks ----------------------------------------------------------


def traced_ticks(run: Any) -> Optional[Tuple[float, float, int]]:
    """(first tick's start, last tick's end, ticks) of the traced slice."""
    if run.trace is None:
        return None
    ticks = [e for e in run.trace.host if e[0] == TICK_SPAN]
    if not ticks:
        return None
    return (min(e[1] for e in ticks), max(e[1] + e[2] for e in ticks),
            len(ticks))


def program_spans(run: Any) -> List[xplane.Event]:
    """The program's spans that began inside the traced ticks."""
    bounds = traced_ticks(run)
    if bounds is None:
        return []
    lo, hi, _ = bounds
    return [e for e in run.trace.host
            if e[0].startswith(PROGRAM_PREFIX) and lo <= e[1] < hi]


def idle_by_span(run: Any) -> Optional[Dict[str, float]]:
    """Chip 0's idle seconds in the traced ticks by the program span each
    gap lies under, ``_outside_`` for a gap under none."""
    spans = program_spans(run)
    if not spans:
        return None
    lo, hi, _ = traced_ticks(run)
    # ``run.trace.events`` reach to ``bench.traced``'s end, past the last
    # tick: clipped first, or an op after ``hi`` brings a gap beyond it.
    events = xplane.clip(next(iter(run.trace.events.values())), lo, hi)
    idle = xplane.attribute_gaps(xplane.gaps(events, lo, hi), spans)
    idle[OUTSIDE] = idle.pop("_none_", 0.0)
    return idle


def idle_by_group(run: Any) -> Optional[Dict[str, float]]:
    """The same seconds by group: the five of ``GROUPS``, ``serve.tick``
    for the tick's own remainder, and ``_outside_``; they sum to all idle
    seconds of the traced ticks."""
    idle = idle_by_span(run)
    if idle is None:
        return None
    out = dict.fromkeys((*GROUPS, TICK, OUTSIDE), 0.0)
    for name, seconds in idle.items():
        out[name if name == OUTSIDE else group_of(name)] += seconds
    return out


def idle_named_pct(run: Any) -> Optional[float]:
    idle = idle_by_group(run)
    total = sum(idle.values()) if idle else 0.0
    if not total:
        return None
    return 100.0 * (total - idle[OUTSIDE]) / total


def idle_ms(group: str):
    """Idle milliseconds a traced tick under the spans of ``group``."""
    def read(run: Any) -> Optional[float]:
        idle = idle_by_group(run)
        if idle is None:
            return None
        return 1e3 * idle[group] / traced_ticks(run)[2]
    return read


# -- the window, untraced ------------------------------------------------------


def window_phases(metric: str) -> Dict[str, float]:
    """phase -> the window's value of one of the registry's phase series
    (every engine of the process together: sums, the longest as a max)."""
    out: Dict[str, float] = {}
    join = max if metric == PHASE_LONGEST else (lambda a, b: a + b)
    for series in expert_readers._series(metric):
        labels = series["labels"]
        if labels.get("scope") != expert_readers.WINDOW:
            continue
        phase, value = labels.get("phase", ""), float(series["value"])
        out[phase] = join(out[phase], value) if phase in out else value
    return out


def tick_host_ms(run: Any) -> Optional[float]:
    """Host milliseconds a tick of the window: the seconds under
    ``serve.tick`` and ``serve.submit`` less the seconds under every
    ``*.pull`` (where the host only waits for the device), over the ticks."""
    seconds, counts = window_phases(PHASE_SECONDS), window_phases(PHASE_COUNT)
    ticks = counts.get(TICK)
    if not ticks:
        return None
    waited = sum(s for name, s in seconds.items()
                 if name.endswith(PULL_SUFFIX))
    busy = seconds[TICK] + seconds.get("serve.submit", 0.0) - waited
    return 1e3 * busy / ticks


def _calls_per_tick(suffix: str, also: str) -> Optional[float]:
    """The window's spans named ``*suffix`` or ``also``, over its ticks:
    some 800 ticks, where four traced ones hold one final chunk or four."""
    counts = window_phases(PHASE_COUNT)
    ticks = counts.get(TICK)
    if not ticks:
        return None
    return sum(n for name, n in counts.items()
               if name.endswith(suffix) or name == also) / ticks


def dispatches_per_tick(run: Any) -> Optional[float]:
    return _calls_per_tick(DISPATCH_SUFFIX, ZERO_STATE)


def host_syncs_per_tick(run: Any) -> Optional[float]:
    return _calls_per_tick(PULL_SUFFIX, KEY_STREAM)


def tick_phase_max_ms(run: Any) -> Optional[float]:
    """The longest single interval of any phase span inside the window.  The
    five longest go to stderr by name: where ONE stall makes the window
    slow, they are the chain from the tick down to the phase that held it."""
    longest = {name: s for name, s in window_phases(PHASE_LONGEST).items()
               if s > 0.0}
    if not longest:
        return None
    ranked = sorted(longest.items(), key=lambda kv: -kv[1])
    print("tick_phase_max_ms: " + ", ".join(
        f"{name} {1e3 * s:.3f}" for name, s in ranked[:5]), file=sys.stderr)
    return 1e3 * ranked[0][1]
