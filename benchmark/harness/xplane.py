"""From the profiler's ``.xplane.pb`` to busy, idle, kernel time and gaps.

``load`` reads the file into plain lists; everything after it works on those
lists, so the reduction is checked on a small recorded trace
(``recorded_trace.json``, beside this file) without a chip.

Device time is the ``XLA Ops`` line of each ``/device:TPU:n`` plane.  Host
spans are the events of ``/host:CPU`` (the program's step and phase
annotations, the benchmark's ``bench.*`` annotations and
JAX's own ``PjitFunction(...)`` dispatch events), on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_s, duration_s

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: The benchmark's annotation round the traced slice.
WINDOW_SPAN = "bench.traced"


class Trace(NamedTuple):
    device_ops: Dict[int, List[Event]]    # chip -> events of its XLA Ops line
    host: List[Event]                     # host spans, every thread


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An op's event is named by its whole HLO line, ``%fusion.7 = f32[...]
    fusion(...)``; its name is what stands before the `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(match.group(1))] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and not e.name.startswith(
                            ("ThreadpoolListener", "$")):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    return Trace(device_ops, host)


def dump(trace: Trace, path: str, limit: int = 4000) -> None:
    """Write a cut of ``trace`` as JSON (how ``recorded_trace.json`` was
    made): the first ``limit`` device events of each chip and the host
    spans that overlap them."""
    ops = {str(chip): events[:limit]
           for chip, events in trace.device_ops.items()}
    ends = [e[1] + e[2] for events in ops.values() for e in events]
    starts = [e[1] for events in ops.values() for e in events]
    lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    host = [e for e in trace.host if e[1] < hi and e[1] + e[2] > lo]
    with open(path, "w") as f:
        json.dump({"device_ops": ops, "host": host[:limit]}, f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        raw = json.load(f)
    return Trace({int(k): [tuple(e) for e in v]
                  for k, v in raw["device_ops"].items()},
                 [tuple(e) for e in raw["host"]])


# -- reduction ---------------------------------------------------------------


def window_of(trace: Trace) -> Tuple[float, float]:
    """(start, end) of the traced slice: the ``bench.traced`` annotation,
    or, without one, the span of the device's events."""
    for name, start, dur in trace.host:
        if name == WINDOW_SPAN:
            return start, start + dur
    starts = [e[1] for ev in trace.device_ops.values() for e in ev]
    ends = [e[1] + e[2] for ev in trace.device_ops.values() for e in ev]
    if not starts:
        raise ValueError("no device operation in the trace")
    return min(starts), max(ends)


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals in which some event runs."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per op name, counting an op that encloses others (a loop
    round its body) only for the time none of them runs."""
    totals: Dict[str, float] = {}
    stack: List[List[Any]] = []           # [name, end, self_seconds]

    def pop():
        name, _, own = stack.pop()
        totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1] - 1e-12:
            pop()
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        pop()
    return totals


def time_of(events: Sequence[Event], pattern: str) -> Tuple[float, int]:
    """(seconds, calls) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e[0])]
    return sum(e[2] for e in hits), len(hits)


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle [start, end) intervals of one chip inside [lo, hi)."""
    out, cursor = [], lo
    for a, b in union(events):
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def attribute_gaps(idle: Sequence[Tuple[float, float]],
                   host: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    shortest host span that covers its middle (``_none_`` without one)."""
    spans = sorted(host, key=lambda e: e[1])
    starts = [e[1] for e in spans]
    out: Dict[str, float] = {}
    for a, b in idle:
        mid = (a + b) / 2.0
        best: Optional[Event] = None
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            name, start, dur = spans[i]
            if start + dur >= mid and (best is None or dur < best[2]):
                best = spans[i]
            if mid - start > 120.0:
                break
        key = re.sub(r"[^A-Za-z0-9_.\-]", "_", best[0]) if best else "_none_"
        out[key] = out.get(key, 0.0) + (b - a)
    return out


COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def exposed_collective_seconds(events: Sequence[Event]) -> float:
    """Seconds of one chip in which a collective runs and no other op
    does."""
    comm = [e for e in events if COLLECTIVE.match(e[0])]
    compute = union([e for e in events if not COLLECTIVE.match(e[0])])
    exposed = 0.0
    for a, b in union(comm):
        covered = sum(max(0.0, min(b, d) - max(a, c)) for c, d in compute)
        exposed += (b - a) - covered
    return exposed


class Summary(NamedTuple):
    window_s: float
    busy_s: float                          # mean over chips
    chips: int
    ops: Dict[str, float]                  # self seconds by op, chip 0... mean
    idle_by_host: Dict[str, float]         # chip 0
    events: Dict[int, List[Event]]         # clipped, per chip
    host: List[Event]
    gaps_s: Sequence[float] = ()           # chip 0's idle gaps, seconds each


def summarize(trace: Trace) -> Summary:
    lo, hi = window_of(trace)
    events = {chip: clip(ev, lo, hi)
              for chip, ev in sorted(trace.device_ops.items())}
    if not events or not any(events.values()):
        raise ValueError("no device operation ran in the traced window")
    busy = [busy_seconds(ev) for ev in events.values()]
    ops: Dict[str, float] = {}
    for ev in events.values():
        for name, seconds in self_times(ev).items():
            ops[name] = ops.get(name, 0.0) + seconds / len(events)
    first = next(iter(events.values()))
    host = clip([e for e in trace.host if e[0] != WINDOW_SPAN], lo, hi)
    idle = gaps(first, lo, hi)
    return Summary(hi - lo, sum(busy) / len(busy), len(events), ops,
                   attribute_gaps(idle, host), events, host,
                   [b - a for a, b in idle])


def breakdown(summary: Summary, top: int = 10) -> Dict[str, List[List[Any]]]:
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(summary.ops),
            "idle_gaps": rank(summary.idle_by_host)}
