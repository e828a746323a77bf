"""What a run carries to the metric readers, and the line it prints."""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness import families


class Run:
    """One run's facts, as the per-layer readers see them.

    ``counters``: numbers the program or the harness counted.
    ``family``: the module that knows the configuration's architecture.
    ``trace``: the reduced device trace (``xplane.Summary``) of a
    ``--trace 1`` run, else None.  ``end_to_end``: what the window measured.
    """

    def __init__(self, cell: Dict[str, Any], config: Dict[str, Any],
                 mix: Dict[str, Any], seed: int, seconds: float,
                 trace_on: bool):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace_on = seed, seconds, trace_on
        self.device: Dict[str, Any] = {}
        self.peak: Any = None                 # peaks.Peak
        self.counters: Dict[str, Any] = {}
        self.trace: Any = None
        self.end_to_end: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.compare: Dict[str, Tuple[float, float]] = {}
        self.setup_marks: List[Tuple[str, float]] = []

    def mark(self, name: str) -> None:
        """A piece of set-up ends: seconds since the process started."""
        self.setup_marks.append(
            (name, time.time() - self.counters["process_start"]))

    @property
    def family(self) -> Any:
        return families.of(self.config)

    @property
    def correct(self) -> bool:
        """Every compared number is a finite reading at or under its
        limit, and there is at least one."""
        return bool(self.compare) and all(
            value == value and value <= limit
            for value, limit in self.compare.values())


def emit(run: Run, metrics: Dict[str, Dict[str, Any]],
         breakdown: Optional[Dict[str, Any]]) -> None:
    """Compared numbers beside their limits as the last lines of stderr;
    the result as the last line of stdout, ``compare`` last in it."""
    line: Dict[str, Any] = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": run.device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["setup_marks"] = run.setup_marks
    line["compare"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in run.compare.items()}
    sys.stdout.flush()
    for name, (value, limit) in run.compare.items():
        verdict = "ok" if value == value and value <= limit else "OVER"
        print(f"compare {name}: {value!r} limit {limit!r} {verdict}",
              file=sys.stderr)
    print(f"correct: {run.correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
