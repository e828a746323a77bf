"""Host spans, profiler sessions and the NaN debug mode.

One vocabulary of host spans, on the clock the device trace uses:

* ``span(name, timer=None, **args)`` — the ONE way the program opens a
  host span, training and serving alike.  It always enters a
  ``jax.profiler.TraceAnnotation`` (which lands on ``/host:CPU`` of the
  profiler's trace, beside the device's ops; a no-op without a profiler
  session), and where a ``obs.report.StepTimeReporter`` is given it
  records the same interval into it from one pair of clock reads — so
  ``obs_report.json``, the reporter's totals by name (``span_totals()``),
  the ``SpanTracker`` Chrome timeline (``cli obs --chrome``) and the
  xplane agree by construction.  Spans are named
  ``<layer>.<what>[.<part>]``; a child's name extends its parent's where
  the name is new, so a reader that has only ``(name, start, duration)``
  can still read the nesting.
* The rule for the serving program: a PHASE of a tick or of ``submit`` is
  a ``span`` into the engine's one timer (``ServingEngine.timer``, shared
  with its scheduler); a span of a REQUEST that crosses ticks
  (``serve.request``, ``serve.queued``, ``serve.prefill``,
  ``serve.decode``) cannot be an annotation, which is a context on one
  thread's stack, and opens and closes on the attached ``SpanTracker``
  (``start`` / ``end``, ``perf_counter`` too).  A phase that works for
  ONE request carries its ``request_id`` and, where a tracker is
  attached, the request's root span as ``parent_id``.  The vocabulary::

      serve.submit                      engine.submit, whole
        serve.submit.key_stream         the key's derivation and split
      serve.tick                        engine.step, whole
        serve.tick.expire               queue deadlines, SLO shedding
        serve.tick.admit                the admission loop
          serve.prefix_lookup             one request's cache lookup
          serve.tick.admit.zero_state     a state row zeroed (one call)
        serve.decode_tick               scheduler.decode_tick, whole
          serve.prefill_chunk             one prefill call: the next chunk
                                          of up to ``chunk_rows``
                                          mid-prefill slots (its ``rows``,
                                          ``padded``, ``final``)
            serve.prefill_chunk.dispatch    uploads and the program call
          serve.decode_tick.build         the NEXT tick's decode call's
                                          host arrays
          serve.decode_tick.dispatch      uploads and the program call
          serve.prefill_chunk.pull        a prefill call's packed pull,
                                          where it finished a prompt:
                                          after the decode dispatch
          serve.decode_tick.pull          the packed pull of the decode
                                          call the tick before dispatched
          serve.decode_tick.record        tokens into their requests
          serve.spec_draft, serve.spec_verify   (``spec_k`` > 0), each
                                          with ``.dispatch`` and ``.pull``
        serve.tick.emit                 streaming, the deadline sweeps
          serve.tick.retire               one request's retirement
            serve.monitor                   the output monitor's verdict:
                                            host arithmetic, no device work
        serve.tick.account              gauges, the collector's row
      serve.decode_tick.settle          outside a tick: the decode call in
                                        flight pulled before a cancel, a
                                        quarantine's release or a
                                        migration reads or frees its slot

  A ``*.dispatch`` span (and ``serve.tick.admit.zero_state``) is ONE
  program call; a ``*.pull`` span (and ``serve.submit.key_stream``)
  blocks on the device at least once.
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
#: Wall clock minus ``perf_counter``, read once.  A recorded span's wall
#: start AND end are its own two ``perf_counter`` reads plus this, so a span
#: that closes with its outer one still reads as inside it (a second clock
#: for the start put it outside, 51 times in 1,500 on a busy host).
_WALL_OFFSET = time.time() - time.perf_counter()
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
            # The length is taken between the two wall values (an exact
            # difference of two floats this close), so start + seconds IS
            # the wall end, and both are monotone in the reads: nesting
            # holds to the last bit, at a quarter of a microsecond's grain.
            start = self._t0 + _WALL_OFFSET
            _RECORDED.append((self.name, start, (t1 + _WALL_OFFSET) - start))
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
