"""Host spans, profiler sessions and the NaN debug mode.

One vocabulary of host spans, on the clock the device trace uses:

* ``span(name, timer=None, **args)`` — the ONE way the program opens a
  host span.  It always enters a ``jax.profiler.TraceAnnotation`` (which
  lands on ``/host:CPU`` of the profiler's trace, beside the device's
  ops; a no-op without a profiler session), and where a
  ``obs.report.StepTimeReporter`` is given it records the same interval
  into it from one pair of clock reads — so ``obs_report.json``, the
  ``SpanTracker`` Chrome timeline (``cli obs --chrome``) and the xplane
  agree by construction.  Spans are named ``<layer>.<what>[.<part>]``; a
  child's name extends its parent's, so a reader that has only
  ``(name, start, duration)`` can still read the nesting.
* ``step_annotation(step)`` — the ``StepTraceAnnotation`` round one step's
  dispatch (``train_step``); the dispatch gets no second span.
* ``recorded_spans()`` — the set-up spans (``setup.*``) of this process.
  The profiler is not running while a trainer is built, so these, and
  only these, are also kept in a small bounded list on the wall clock.
* ``trace(log_dir)`` — a profiler session round everything inside
  (``TrainingConfig.profile_dir``): device ops, the spans above and the
  ``jax.named_scope`` names of the trusted step (``engine/step.py``), for
  xprof / Perfetto.
* ``enable_nan_debugging()`` — ``jax_debug_nans``: a jitted NaN producer
  re-runs op by op and raises at the exact primitive.  A developer mode
  (``TrainingConfig.debug_nans``); the detection of *adversarial*
  non-finite gradients does not rely on it (the verifier's finite flag
  handles that in-step).

Every annotation is **no-op-safe**: constructing or entering one outside a
profiler session, or on a backend whose profiler plugin is broken, degrades
to a null context — the hot loop opens a few every step, and an
instrumentation shim must never be what kills a run.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["enable_nan_debugging", "recorded_spans", "span",
           "step_annotation", "trace"]

logger = logging.getLogger(__name__)

#: Spans whose name starts with this are kept for ``recorded_spans()``.
SETUP_PREFIX = "setup."
#: (name, wall-clock start, seconds) of the newest set-up spans.  A trainer
#: leaves about ten; the bound only guards a process that builds hundreds.
_RECORDED: Deque[Tuple[str, float, float]] = collections.deque(maxlen=64)


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Profile everything dispatched inside the context into ``log_dir``
    (no-op when log_dir is falsy, so call sites need no branching)."""
    if not log_dir:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    logger.info("profiler: tracing to %s", log_dir)
    with jax.profiler.trace(log_dir):
        yield
    logger.info("profiler: trace written to %s", log_dir)


class _SafeAnnotation:
    """Wraps a jax.profiler annotation so that construction, entry and
    exit failures (no active profiler session, missing plugin) all
    degrade to a no-op.  Re-entrant per instance is not supported —
    build one per ``with`` block, as the factories below do."""

    __slots__ = ("_ctx",)

    def __init__(self, factory, *args, **kwargs):
        try:
            self._ctx = factory(*args, **kwargs)
        except Exception:
            self._ctx = None

    def __enter__(self):
        if self._ctx is not None:
            try:
                self._ctx.__enter__()
            except Exception:
                self._ctx = None
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            try:
                return bool(self._ctx.__exit__(*exc))
            except Exception:
                pass
        return False


def step_annotation(step: int) -> _SafeAnnotation:
    """Label one train step in the trace timeline (no-op-safe)."""
    return _SafeAnnotation(jax.profiler.StepTraceAnnotation, "train_step",
                           step_num=step)


class span(contextlib.ContextDecorator):
    """``with span("train.epoch_end.drain", timer):`` — see the module
    docstring; ``@span("setup.build_steps")`` wraps a whole function.
    ``args`` ride the annotation as its metadata; ``with`` binds them as a
    dict, and what the body adds to it before the span closes reaches the
    timer's record too (a count known only afterwards)."""

    def __init__(self, name: str, timer: Any = None, **args: Any):
        self.name, self.timer, self.args = name, timer, args
        self._kept = name.startswith(SETUP_PREFIX)

    def _recreate_cm(self) -> "span":
        # A decorated function gets a fresh span on every call (its own
        # clock reads and annotation, whatever calls it meanwhile).
        return span(self.name, self.timer, **self.args)

    def __enter__(self) -> Dict[str, Any]:
        if self._kept:
            self._wall = time.time()
        self._ann = _SafeAnnotation(jax.profiler.TraceAnnotation, self.name,
                                    **self.args)
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self.args

    def __exit__(self, *exc: Any) -> bool:
        self._ann.__exit__(*exc)
        t1 = time.perf_counter()
        if self.timer is not None:
            self.timer.record_span(self.name, self._t0, t1, **self.args)
        if self._kept:
            _RECORDED.append((self.name, self._wall, t1 - self._t0))
        return False


def recorded_spans() -> List[Tuple[str, float, float]]:
    """(name, wall-clock start, seconds) of this process's newest set-up
    spans, oldest first."""
    return list(_RECORDED)


def enable_nan_debugging(enabled: bool = True) -> None:
    """jax_debug_nans: jitted NaN producers re-run op-by-op and raise at the
    exact primitive (SURVEY §5.2 plan)."""
    jax.config.update("jax_debug_nans", enabled)
    if enabled:
        logger.warning(
            "NaN debugging enabled: NaN-producing steps re-execute un-jitted "
            "and raise FloatingPointError (debug builds only — this also "
            "fires on adversarial NaN injections the engine would otherwise "
            "gate out in-step)"
        )
