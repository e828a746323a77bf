"""Step-time breakdown + MFU/roofline reporter (``obs_report.json``).

A utilization figure needs an artifact saying where the rest of the
time goes.  This module is that artifact's producer: named-phase
wall-clock accounting on the host step loop, and model-FLOPs utilization
computed from the model config — attached by the trainer
(``--obs-dir``) and the experiment runner.

Phase semantics (the canonical names in :data:`PHASES`):

* Host-measurable phases — ``data`` (loader + host batch assembly +
  shard placement), ``compute`` (dispatch + device execution of the
  fused step, synced at the loss read; dispatch-only under the async
  host pipeline), ``detection`` (host-side verdict processing /
  incident records, synchronous loop), ``host`` (async-pipeline drain:
  time blocked on the lagged metrics landing + the host bookkeeping —
  the number the pipeline exists to collapse; compare it across
  ``async_host_depth`` 0 vs K), ``checkpoint`` — are accounted by
  :class:`StepTimeReporter` per step, from the loop's ``lap`` calls.
* What a step's laps do not cover arrives as SPANS
  (``utils.profiling.span(name, timer)`` → :meth:`record_span`): the
  pieces of a lap (``train.data_wait``, ``train.batch_place``,
  ``train.host_drain`` with ``.wait`` and ``.records``) and the epoch's
  end (``train.epoch_end`` with ``.drain``, ``.host_sync``,
  ``.thresholds``, ``.ml_refit``, ``.collect``), which no lap accounts.
  They are kept apart from the per-step ring, so the phase medians do
  not shift; ``report()`` shows them as ``spans`` and ``epoch_end``.
* ``host_sync``, ``forward``, ``backward``, ``optimizer`` and ``other``
  are names no lap feeds.  The epoch-end host sync is the span
  ``train.epoch_end.host_sync``; forward, backward and optimizer live
  inside the one jitted program, where ``engine/step.py`` names them
  with ``jax.named_scope`` (``train.fwd_bwd``, backward ops carrying
  ``transpose(`` in their ``op_name``; ``train.optimizer``; the trust
  plane as ``trust.*``): a ``profile_dir`` trace shows those names.

MFU uses the standard ~6 FLOPs/param/token transformer-training
estimate (fwd 2 + bwd 4; remat recompute not counted, so achieved
hardware FLOPs are a lower bound) against a per-``device_kind`` peak
table.  A device kind that is not in the table raises; only the CPU
test mesh gets a nominal figure, named as such in
``peak_flops_source``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Deque, Dict, Optional, Tuple

import collections

import numpy as np

#: Canonical phase names of ``lap`` (see the module docstring for which
#: of them the loop feeds).
PHASES = ("data", "forward", "backward", "optimizer", "detection",
          "host", "host_sync", "compute", "checkpoint", "other")

#: The span round everything that follows the step loop in ``train_epoch``.
EPOCH_END_SPAN = "train.epoch_end"

#: Peak dense bf16 FLOP/s per chip by jax ``device_kind`` (marketing
#: peaks; MFU denominators, not guarantees).  Matched by substring so
#: kinds like "TPU v5 lite" and "TPU v5e" both resolve.
PEAK_FLOPS_BF16 = (
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

#: Nominal per-core figure for the CPU test mesh (order of magnitude
#: only), so a CPU-mesh dev run's report still has its MFU block.
CPU_NOMINAL_FLOPS = 5e10


def peak_flops_per_chip(device_kind: str) -> "tuple[float, str]":
    """(peak FLOP/s, source) for one chip of ``device_kind``.  An
    accelerator that is not in the table is an error, not a default."""
    kind = (device_kind or "").lower()
    for token, peak in PEAK_FLOPS_BF16:
        if token in kind:
            return peak, f"bf16-peak-table:{token}"
    if kind == "cpu":
        return CPU_NOMINAL_FLOPS, "cpu-nominal-estimate"
    raise ValueError(
        f"no peak FLOP/s known for device kind {device_kind!r}; add it "
        "to PEAK_FLOPS_BF16 with its source")


def _this_chip_peak() -> "tuple[str, float, str]":
    """(device kind, peak FLOP/s, source) of the chip this process runs
    on."""
    from trustworthy_dl_tpu.obs.meta import run_metadata

    device_kind = run_metadata()["device_kind"]
    return (device_kind, *peak_flops_per_chip(device_kind))


class StepTimeReporter:
    """Lap-based per-step phase accounting.

    Usage (the trainer's loop)::

        reporter.lap("data")       # time since last mark -> "data"
        ... dispatch + sync ...
        reporter.lap("compute")
        ... host verdicts ...
        reporter.lap("detection")
        reporter.finish_step()

    ``lap(name)`` attributes the wall time since the previous mark to
    ``name`` (repeat laps into the same phase accumulate);
    ``finish_step()`` closes the step.  Steps the caller must not
    account (guard-rejected, stale batches) call ``discard_step()``.
    Per-step records are ring-bounded; per-phase aggregates stream into
    the registry as ``tddl_phase_time_seconds{phase=}``.  (End-to-end
    step time already has a registry series —
    ``tddl_<ns>_step_time_seconds`` from ``MetricsCollector.tick`` — so
    the reporter deliberately adds no second one.)
    """

    def __init__(self, registry: Any = None, max_steps: int = 4096):
        self._steps: Deque[Dict[str, float]] = collections.deque(
            maxlen=max_steps
        )
        self._current: Dict[str, float] = {}
        self._laps: list = []          # (phase, start, end) this step
        #: name -> the newest (seconds, attrs) of ``record_span``, ring-
        #: bounded like the steps.
        self._spans: Dict[str, Deque[Tuple[float, Dict[str, Any]]]] = \
            collections.defaultdict(
                lambda: collections.deque(maxlen=max_steps))
        #: name -> [count, seconds, longest, longest since
        #: ``take_longest``] over EVERY ``record_span``, beside the ring.
        self._totals: Dict[str, list] = collections.defaultdict(
            lambda: [0, 0.0, 0.0, 0.0])
        #: name -> {key: running sum} of ``tally``: what the spans of a
        #: name held, beside their totals.
        self._tallies: Dict[str, Dict[str, int]] = collections.defaultdict(
            dict)
        self._mark: Optional[float] = None
        #: Optional obs.spans.SpanTracker: when attached (ObsSession
        #: enable_spans), finish_step synthesizes a ``train.step`` span
        #: plus one child per recorded lap from the SAME perf_counter
        #: marks the phase accounting used — the trainer loop needs no
        #: extra instrumentation for its timeline.
        self.spans: Any = None
        #: Optional obs.hbm.CostLedger — per-program XLA cost blocks
        #: (flops / bytes / temp allocation) stamped into the report,
        #: and the source of the analyzed-FLOPs MFU that replaces the
        #: nominal 6·params·tokens guess when a ``train_step`` entry
        #: exists.
        self.cost_ledger: Any = None
        self.last_step_total: Optional[float] = None
        self.n_params: Optional[int] = None
        self.tokens_per_step: Optional[int] = None
        self.model_kind: str = "lm"
        self.num_chips: int = 1
        self._phase_hist = None
        if registry is not None:
            self._phase_hist = registry.histogram(
                "tddl_phase_time_seconds",
                "Per-phase step-time breakdown", labels=("phase",),
            )

    # -- model info (for MFU) ---------------------------------------------

    @property
    def has_model_info(self) -> bool:
        return self.n_params is not None

    def set_model_info(self, n_params: int, tokens_per_step: int,
                       model_kind: str = "lm", num_chips: int = 1) -> None:
        self.n_params = int(n_params)
        self.tokens_per_step = int(tokens_per_step)
        self.model_kind = model_kind
        self.num_chips = max(int(num_chips), 1)

    # -- timing ------------------------------------------------------------

    def lap(self, phase: str) -> None:
        if phase not in PHASES:
            raise ValueError(f"unknown phase {phase!r}; one of {PHASES}")
        now = time.perf_counter()
        if self._mark is not None:
            self._current[phase] = self._current.get(phase, 0.0) \
                + (now - self._mark)
            self._laps.append((phase, self._mark, now))
        self._mark = now

    def record_span(self, name: str, start: float, end: float,
                    **attrs: Any) -> None:
        """An interval that ``utils.profiling.span`` measured (the
        ``perf_counter`` domain of the laps).  Kept by name, apart from
        the per-step ring: ``lap`` and ``finish_step`` never see it."""
        seconds = end - start
        self._spans[name].append((seconds, attrs))
        total = self._totals[name]
        total[0] += 1
        total[1] += seconds
        if seconds > total[3]:
            total[3] = seconds
            total[2] = max(total[2], seconds)
        if self.spans is not None:
            self.spans.add(name, start, end, kind=name.split(".", 1)[0],
                           **attrs)

    def span_totals(self) -> Dict[str, Tuple[int, float, float]]:
        """name -> (count, seconds, longest single interval) of every span
        recorded so far: cumulative, where the ring keeps the newest."""
        return {name: (t[0], t[1], t[2])
                for name, t in self._totals.items()}

    def tally(self, name: str, **amounts: int) -> None:
        """Add ``amounts`` to the running sums kept under ``name``: what a
        span of that name held (``serve.prefill_chunk``: the real rows and
        the padding rows of its chunk call)."""
        sums = self._tallies[name]
        for key, amount in amounts.items():
            sums[key] = sums.get(key, 0) + amount

    def tallies(self) -> Dict[str, Dict[str, int]]:
        """name -> {key: running sum} of every ``tally`` so far."""
        return {name: dict(sums) for name, sums in self._tallies.items()}

    def take_longest(self) -> Dict[str, float]:
        """name -> the longest single interval since the last call (0.0
        where the name recorded nothing meanwhile)."""
        out = {}
        for name, total in self._totals.items():
            out[name], total[3] = total[3], 0.0
        return out

    def finish_step(self, step: Optional[int] = None) -> None:
        record = self._current
        laps = self._laps
        self._current = {}
        self._laps = []
        self._mark = time.perf_counter()
        if not record:
            return
        record["_total"] = sum(record.values())
        self.last_step_total = record["_total"]
        self._steps.append(record)
        if self._phase_hist is not None:
            for phase, seconds in record.items():
                if not phase.startswith("_"):
                    self._phase_hist.observe(seconds, phase=phase)
        if self.spans is not None and laps:
            # One root span per accounted step, one child per lap, all
            # from the marks the phase accounting already took — the
            # Chrome timeline and obs_report.json agree by construction.
            root = self.spans.add(
                "train.step", laps[0][1], laps[-1][2], kind="train",
                step=step,
            )
            for phase, t0, t1 in laps:
                self.spans.add(f"train.{phase}", t0, t1, kind="train",
                               parent_id=root.span_id, step=step)

    def discard_step(self) -> None:
        """Drop the accumulating step (rejected/retried — its duration
        would poison the per-phase distribution)."""
        self._current = {}
        self._laps = []
        self.last_step_total = None  # nothing fresh for watcher feeds
        self._mark = time.perf_counter()

    @property
    def num_steps(self) -> int:
        return len(self._steps)

    @property
    def step_time_mean(self) -> Optional[float]:
        if not self._steps:
            return None
        return float(np.mean([s["_total"] for s in self._steps]))

    def phase_fractions(self) -> Dict[str, float]:
        """Per-phase share of the accounted wall time (the fingerprint's
        compact view of the full ``phases`` report block)."""
        steps = list(self._steps)
        if not steps:
            return {}
        grand = sum(s["_total"] for s in steps)
        out: Dict[str, float] = {}
        for phase in PHASES:
            total = sum(s.get(phase, 0.0) for s in steps)
            if total > 0.0 and grand > 0.0:
                out[phase] = total / grand
        return out

    # -- reporting ---------------------------------------------------------

    def report(self) -> Dict[str, Any]:
        """The ``obs_report.json`` payload: per-phase breakdown + MFU."""
        from trustworthy_dl_tpu.obs.meta import run_metadata

        steps = list(self._steps)
        out: Dict[str, Any] = {
            "num_steps": len(steps),
            "run_metadata": run_metadata(),
        }
        if steps:
            totals = np.asarray([s["_total"] for s in steps])
            out["step_time_s"] = {
                "mean": float(totals.mean()),
                "p50": float(np.percentile(totals, 50)),
                "p95": float(np.percentile(totals, 95)),
                "max": float(totals.max()),
            }
            grand_total = float(totals.sum())
            phases: Dict[str, Any] = {}
            for phase in PHASES:
                values = np.asarray([s.get(phase, 0.0) for s in steps])
                total = float(values.sum())
                if total <= 0.0:
                    continue
                phases[phase] = {
                    "total_s": total,
                    "mean_s": float(values.mean()),
                    "p50_s": float(np.percentile(values, 50)),
                    "p95_s": float(np.percentile(values, 95)),
                    "fraction": total / grand_total if grand_total else 0.0,
                }
            out["phases"] = phases
        if self.has_model_info and steps:
            mean_step = out["step_time_s"]["mean"]
            if self.model_kind == "lm" and self.tokens_per_step:
                device_kind, peak, source = _this_chip_peak()
                per_chip = self.tokens_per_step / mean_step / self.num_chips
                achieved = 6.0 * float(self.n_params) * per_chip
                out["mfu"] = {
                    "n_params": int(self.n_params),
                    "tokens_per_s_per_chip": per_chip,
                    "model_flops_per_s_per_chip": achieved,
                    "peak_flops_per_chip": peak,
                    "peak_flops_source": source,
                    "device_kind": device_kind,
                    "mfu": achieved / peak if peak > 0 else None,
                    "tokens_per_step": self.tokens_per_step,
                    "num_chips": self.num_chips,
                }
            else:
                # No comparable FLOPs-per-sample formula for convs; the
                # report still carries the throughput inputs.
                out["mfu"] = {
                    "n_params": self.n_params,
                    "samples_per_step": self.tokens_per_step,
                    "mfu": None,
                    "note": "MFU defined for LM (6 FLOPs/param/token) "
                            "only",
                }
        if self._spans:
            out["spans"] = {
                name: self._span_block(name) for name in sorted(self._spans)}
        epoch_end = self._epoch_end_block()
        if epoch_end:
            out["epoch_end"] = epoch_end
        ledger = self.cost_ledger
        if ledger:
            out["cost_ledger"] = ledger.to_dict()
            analyzed = self._analyzed_mfu(out)
            if analyzed is not None:
                out["mfu_analyzed"] = analyzed
        return out

    def _span_block(self, name: str) -> Dict[str, Any]:
        seconds = np.asarray([s for s, _ in self._spans[name]])
        return {"count": len(seconds), "total_s": float(seconds.sum()),
                "p50_s": float(np.percentile(seconds, 50))}

    def _epoch_end_block(self) -> Dict[str, Any]:
        """The epoch's end by its parts: what follows the step loop in
        ``train_epoch`` (``train.epoch_end`` and its children), which no
        lap covers.  ``refit_rows`` are the rows of the matrices the ML
        detectors were refitted on, newest epochs last: the refit is the
        one part whose cost may grow with the run's history."""
        root = EPOCH_END_SPAN
        if root not in self._spans:
            return {}
        parts = {name[len(root) + 1:]: self._span_block(name)
                 for name in sorted(self._spans)
                 if name.startswith(root + ".")}
        block = self._span_block(root)
        block["epochs"] = block.pop("count")
        block["parts"] = parts
        rows = [attrs["rows"] for _, attrs in
                self._spans.get(root + ".ml_refit", ()) if "rows" in attrs]
        if rows:
            block["refit_rows"] = rows[-8:]  # enough to see a trend
        return block

    def _analyzed_mfu(self, out: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """MFU from XLA's OWN flop count of the train step program
        (``cost_ledger['train_step'].flops`` per execution) over the
        measured mean step time — no 6 FLOPs/param/token modelling, no
        samples-vs-tokens ambiguity, and it covers remat recompute and
        the detection battery the nominal estimate ignores.  The peak
        denominator stays the per-device_kind table (its source is
        named, as always)."""
        flops = self.cost_ledger.flops("train_step") \
            if self.cost_ledger is not None else None
        mean_step = (out.get("step_time_s") or {}).get("mean")
        if not flops or not mean_step:
            return None
        _, peak, source = _this_chip_peak()
        achieved = flops / mean_step / max(self.num_chips, 1)
        return {
            "flops_per_step": flops,
            "flops_source": "xla-cost-analysis",
            "achieved_flops_per_s_per_chip": achieved,
            "peak_flops_per_chip": peak,
            "peak_flops_source": source,
            "num_chips": self.num_chips,
            "mfu": achieved / peak if peak > 0 else None,
        }

    def write(self, path: str) -> Dict[str, Any]:
        report = self.report()
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2)
        os.replace(tmp, path)
        return report
