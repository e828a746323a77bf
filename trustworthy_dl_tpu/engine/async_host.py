"""Bounded in-flight dispatch with lagged host telemetry.

The fused train step keeps detection *inside* the device program (the
paper's near-zero-overhead claim), but the synchronous
host loop threw that away: every step ended with a blocking
``float(metrics.loss)`` followed by ~10 separate device→host pulls in
``_record_batch``, so the accelerator idled through all per-step Python
bookkeeping.  This module closes that dispatch gap the way production
JAX trainers (t5x/MaxText-style) do:

* each step's host-facing outputs are packed into ONE flat device array
  (``engine.step.HostMetricsPacker``) whose device→host copy starts
  asynchronously at dispatch time;
* a bounded deque holds up to ``TrainingConfig.async_host_depth`` steps
  in flight — step k+1 dispatches before step k's metrics land;
* completed entries drain through the EXISTING host path
  (``_record_batch``, step-guard checks, obs trace events) lagged by up
  to K steps, with the entry's own step number restored for the duration
  of its drain so every host record is indistinguishable from the
  synchronous path's.

Drain contract (the invariants the lag must not break):

* **checkpoint saves** — the trainer fully drains before ``save_checkpoint``
  and skips the save if the frontier step was guard-rejected, so a
  verified checkpoint always covers a fully-accounted, guard-accepted
  prefix;
* **epoch end / preemption** — ``train_epoch`` drains in a ``finally``, so
  epoch aggregation, ``sync_host_state`` and the supervisor's
  save-on-signal all observe a caught-up host;
* **guard trips** — the lagged guard skips in-place retries (re-running a
  K-step-old batch against the frontier state is not the same
  computation) and, on rollback, restores the newest verified checkpoint
  — which predates the in-flight window by the checkpoint invariant
  above; the rest of the window is then discarded as an abandoned
  timeline;
* **elastic transitions** — evictions detected while draining are
  deferred: the window drains fully (its packed metrics still carry the
  pre-eviction node count), then the eviction/readmission applies once at
  the dispatch frontier.  The in-step trust gate has already zero-weighted
  the compromised node's gradients throughout the lag, so only the host
  bookkeeping (mesh surgery, history records) moves by up to K steps.

Depth 0 bypasses this module entirely (the pre-pipeline synchronous
loop).  Deterministic chaos drills that assert exact retry counts
(``FaultPlan.predict``) must run at depth 0 — see the lagged-guard note
in ``TrainingSupervisor.after_step``.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Any, Deque, Optional, Set

import numpy as np

from trustworthy_dl_tpu.engine.step import HostMetricsPacker, StepMetrics
from trustworthy_dl_tpu.utils.profiling import span

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class DrainContext:
    """Installed as ``trainer._drain_ctx`` while a lagged entry drains:
    ``_record_batch`` reads the fleet-norm streak from the entry's packed
    snapshot (the live ``trainer.state`` is up to K steps ahead) and
    defers elastic evictions into ``evict_coords`` instead of resharding
    mid-window."""

    fleet_streak: Any = None
    evict_coords: Set[int] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-undrained step."""

    step: int
    epoch: int
    batch_idx: int
    node_batch: Any          # kept alive for the (lagged) step guard
    packed: Any              # flat f32 device array, D2H copy in flight
    packer: HostMetricsPacker


class AsyncHostPipeline:
    """The bounded in-flight window for one ``train_epoch`` call.

    ``push`` packs a step's metrics and starts the async device→host
    copy; ``drain`` resolves the oldest entries down to the configured
    depth (or to empty) through the trainer's host path, then applies any
    deferred topology change at the frontier.  ``epoch_loss`` /
    ``num_batches`` accumulate exactly what the synchronous loop's local
    counters would have.

    Spans (``utils.profiling.span``, into ``timer`` where one is given):
    each resolved entry is ``<under>.wait`` (blocked on the device for the
    packed metrics to land) and ``<under>.records`` (the host's own work
    for that step: unpack, guard, records, incidents), ``under`` being the
    caller's span round the drain — ``train.host_drain`` in the step
    loop, ``train.epoch_end.drain`` for the epoch's mandatory full drain,
    whose close is also the moment the host view has caught up.
    """

    def __init__(self, trainer: Any, depth: int, timer: Any = None):
        self.trainer = trainer
        self.depth = int(depth)
        self.timer = timer
        self.entries: Deque[_InFlight] = collections.deque()
        self.packer: Optional[HostMetricsPacker] = None
        self.pending_evicts: Set[int] = set()
        self.epoch_loss = 0.0
        self.num_batches = 0
        self.last_rejected_step: Optional[int] = None
        self._rejected_since_check = False
        self._warned_sync_guard = False

    # -- dispatch side -----------------------------------------------------

    def push(self, epoch: int, batch_idx: int, node_batch: Any,
             metrics: StepMetrics, state: Any) -> None:
        """Pack the step the trainer just dispatched and enqueue it.
        ``state`` is the post-step TrainState — its ``fleet_raw_streak``
        is the step-time value the drain must see."""
        streak = getattr(state, "fleet_raw_streak", None)
        if self.packer is None or not self.packer.matches(metrics, streak):
            # First step, or the node count changed under an elastic
            # transition (applied only at full-drain points, so no mixed
            # layouts ever coexist in the window).
            self.packer = HostMetricsPacker(metrics, streak)
        packed = self.packer.pack(metrics, streak)
        # Retain the batch only for a guard that might retry it (the
        # legacy non-lagged-aware path) — a lagged-aware guard never
        # touches it, and pinning K full device batches for nothing is
        # real HBM at production batch sizes.
        guard = self.trainer.step_guard
        keep_batch = guard is not None and \
            not getattr(guard, "lagged_aware", False)
        self.entries.append(_InFlight(
            step=self.trainer.global_step, epoch=epoch, batch_idx=batch_idx,
            node_batch=node_batch if keep_batch else None,
            packed=packed, packer=self.packer,
        ))

    # -- drain side --------------------------------------------------------

    def drain(self, depth: Optional[int] = None,
              under: str = "train.host_drain") -> None:
        """Resolve oldest entries until at most ``depth`` (default: the
        configured window) remain, then apply deferred topology changes.
        ``drain(0)`` is the mandatory full drain.  ``under`` names the
        caller's span, which the entries' spans extend."""
        target = self.depth if depth is None else int(depth)
        self._drain_until(target, under)
        self._maybe_apply_topology(under)

    def consume_rejection(self) -> bool:
        """True when any entry was guard-rejected since the last check —
        the trainer then discards the frontier step's timer laps, like the
        synchronous loop does for rejected steps (retry/rollback wall time
        must not poison the phase distribution)."""
        rejected = self._rejected_since_check
        self._rejected_since_check = False
        return rejected

    def _drain_until(self, target: int, under: str) -> None:
        while len(self.entries) > target:
            # Peek-then-pop: if the guard raises mid-drain (a preemption
            # signal), the entry stays queued, so the unwind drain still
            # records it — the host stream must never have a mid-run gap
            # the synchronous path could not produce.
            entry = self.entries[0]
            self._drain_one(entry, under)
            if self.entries and self.entries[0] is entry:
                self.entries.popleft()

    def _drain_one(self, entry: _InFlight, under: str) -> None:
        """Run one lagged step through the host path with its own step
        number restored, exactly as the synchronous loop would have."""
        with span(under + ".wait", self.timer):
            packed = np.asarray(entry.packed)
        with span(under + ".records", self.timer):
            self._record_one(entry, packed)

    def _record_one(self, entry: _InFlight, packed: np.ndarray) -> None:
        trainer = self.trainer
        host, streak = entry.packer.unpack(packed)
        frontier = trainer.global_step
        trainer.global_step = entry.step
        try:
            guard = trainer.step_guard
            if guard is not None:
                if getattr(guard, "lagged_aware", False):
                    accepted = guard.after_step(trainer, entry.node_batch,
                                                host, lagged=True)
                else:
                    # Legacy synchronous-only guard running lagged: its
                    # in-place retries re-run an old batch against the
                    # FRONTIER state (not the state that produced it) and
                    # mutate trainer.state under the in-flight window —
                    # tolerated for duck-typed guards, but such runs
                    # should pin async_host_depth=0.
                    if not self._warned_sync_guard:
                        self._warned_sync_guard = True
                        logger.warning(
                            "async pipeline: step guard %s is not "
                            "lagged-aware; its retries run against the "
                            "frontier state — set async_host_depth=0 for "
                            "exact synchronous guard semantics",
                            type(guard).__name__,
                        )
                    accepted = guard.after_step(trainer, entry.node_batch,
                                                host)
                if accepted is not None:
                    # A guard may substitute metrics (a retry-recovered
                    # step); record what it accepted, like the sync loop.
                    host = accepted
                if accepted is None:
                    self.last_rejected_step = entry.step
                    self._rejected_since_check = True
                    if trainer.global_step != entry.step:
                        # Rollback: the guard restored an older verified
                        # checkpoint (global_step re-pointed by
                        # load_checkpoint).  Everything still in flight
                        # was computed on the abandoned timeline — in the
                        # synchronous world those steps never ran.
                        logger.warning(
                            "async pipeline: rollback at lagged step %d — "
                            "discarding %d in-flight step(s)",
                            entry.step, len(self.entries),
                        )
                        self.entries.clear()
                        self.pending_evicts.clear()
                        frontier = trainer.global_step
                    return
            if self.last_rejected_step == entry.step:
                # Training re-advanced to a step number that was rejected
                # on the abandoned timeline; this acceptance supersedes it
                # (a stale marker would suppress that step's checkpoint).
                self.last_rejected_step = None
            trainer.metrics_collector.tick()
            loss = float(host.loss)
            ctx = DrainContext(fleet_streak=streak)
            trainer._drain_ctx = ctx
            try:
                trainer._record_batch(host, entry.epoch, loss)
            finally:
                trainer._drain_ctx = None
            self.pending_evicts.update(ctx.evict_coords)
            self.epoch_loss += loss
            self.num_batches += 1
            if entry.batch_idx % 10 == 0:
                logger.info("Epoch %d, Batch %d, Loss: %.4f",
                            entry.epoch, entry.batch_idx, loss)
        finally:
            trainer.global_step = frontier

    def _maybe_apply_topology(self, under: str) -> None:
        """Deferred elastic transitions: mandatory full drain first, then
        evict/readmit once at the dispatch frontier."""
        trainer = self.trainer
        if not self.pending_evicts and not trainer._readmit_due():
            return
        # may itself add evicts or clear on rollback
        self._drain_until(0, under)
        evicts = sorted(self.pending_evicts)
        self.pending_evicts.clear()
        n = trainer.config.num_nodes
        if len(evicts) >= n:
            # Evictions accumulated across the window would empty the
            # fleet — something the per-step path can never request
            # (eviction needs a surviving majority to migrate onto).
            # Keep the highest coordinate in service; the in-step trust
            # gate has its gradients zero-weighted regardless, and the
            # fleet-level alarm covers the everyone-is-compromised case.
            logger.error(
                "async pipeline: %d deferred evictions would empty the "
                "%d-node fleet; keeping coordinate %d in service",
                len(evicts), n, evicts[-1],
            )
            evicts = evicts[:n - 1]
        if evicts:
            trainer._apply_evictions(evicts)
        trainer._maybe_readmit()
