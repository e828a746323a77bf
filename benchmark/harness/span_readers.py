"""Readers of the program's own host spans (``utils.profiling.span`` of
``trustworthy_dl_tpu``), which stand on ``/host:CPU`` of the traced slice
beside the device's ops, and of its recorded set-up spans.  A program that
opens no such span (any commit before the spans came) reads None everywhere,
and the harness leaves the metric out."""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

#: The span round everything that follows the step loop in ``train_epoch``.
EPOCH_END = "train.epoch_end"
#: A step's own host work (unpack, guard, records, incidents) once its
#: metrics have landed: under the loop's drain for all but the newest steps
#: of an epoch, under the epoch's full drain for those.
STEP_RECORDS = ("train.host_drain.records", EPOCH_END + ".drain.records")
#: Every span of the training program's host loop starts with this.
TRAIN_PREFIX = "train."
#: Building a trainer, up to its first step (which ``setup.first_step`` is).
BUILD_SPANS = ("setup.trainer_init", "setup.initialize", "setup.build_steps")


def _seconds(run: Any, names: Sequence[str]) -> List[float]:
    if run.trace is None:
        return []
    return [dur for name, _, dur in run.trace.host if name in names]


def span_ms(*names: str):
    """Milliseconds of the traced slice under spans of these names."""
    def read(run: Any) -> Optional[float]:
        seconds = _seconds(run, names)
        return 1e3 * sum(seconds) if seconds else None
    return read


def host_work_ms(run: Any) -> Optional[float]:
    """The host's own work for one step, mean over the traced steps."""
    seconds = _seconds(run, STEP_RECORDS)
    if not seconds:
        return None
    return 1e3 * sum(seconds) / run.counters["trace_steps"]


def idle_named_pct(run: Any) -> Optional[float]:
    """Share of chip 0's idle seconds that ``xplane.attribute_gaps`` gave to
    a span of the training program (the rest went to JAX's and the
    runtime's own events, or to ``_none_``)."""
    if run.trace is None:
        return None
    idle = run.trace.idle_by_host
    named = [s for name, s in idle.items() if name.startswith(TRAIN_PREFIX)]
    total = sum(idle.values())
    return 100.0 * sum(named) / total if named and total else None


def outermost(spans: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """``spans`` without those that lie inside another of them."""
    def inside(a, b):
        return a is not b and b[1] <= a[1] and a[1] + a[2] <= b[1] + b[2]
    return [a for a in spans if not any(inside(a, b) for b in spans)]


def trainer_build_s(run: Any) -> Optional[float]:
    """Seconds this run spent building and initialising trainers, from the
    program's recorded set-up spans (wall clock, since the process began);
    a span inside another counts once."""
    start = run.counters.get("process_start")
    if start is None:
        return None
    try:
        from trustworthy_dl_tpu.utils.profiling import recorded_spans
    except ImportError:
        return None
    spans = [s for s in recorded_spans()
             if s[0] in BUILD_SPANS and s[1] >= start]
    return sum(s[2] for s in outermost(spans)) if spans else None
