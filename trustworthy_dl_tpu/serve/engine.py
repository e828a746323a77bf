"""Request lifecycle for the serving engine: queue → prefill → decode →
stream, with deadlines, backpressure, serving metrics, and trust-aware
output monitoring.

The engine is a synchronous iteration loop (``step()``): each iteration
admits queued requests into free slots, runs the scheduler's single fused
decode step, streams new tokens to per-request callbacks, and retires
finished/expired sequences.  Everything host-side is O(MAX_SLOTS) python;
the device work per iteration is one decode program plus a call of the
chunk program for every ``chunk_call_rows`` slots still prefilling, each
call a chunk of each of them (one call where they are that few; a call a
slot where the description's chunk is one slot's, and a whole-prompt call
for each fresh prompt that fits one chunk).

Trust-aware admission control (the inference mirror of the training trust
state machine): every emitted token's logit entropy and top-1 margin are
computed in-step (scheduler._logit_signals); at retirement the request's
mean signal vector is z-scored against a rolling baseline of past *clean*
requests (detect/baseline ring buffer — score-then-absorb-only-clean, the
same hardening the training detector uses so an attacker cannot drag its
own baseline).  A flagged generation marks the request and QUARANTINES the
slot it ran on — a compromised replica's capacity leaves the pool until an
operator releases it, mirroring COMPROMISED → probation on the training
side.

Serving metrics flow through ``utils.metrics.MetricsCollector``: per
iteration (slot occupancy, queue depth, tokens emitted) and per request
(TTFT, ITLs); ``metrics_summary()`` reports tokens/s and p50/p99
inter-token latency.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trustworthy_dl_tpu.models import generate as gen
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.obs import attribution
from trustworthy_dl_tpu.obs.events import EventType
from trustworthy_dl_tpu.obs.registry import get_registry
from trustworthy_dl_tpu.obs.report import StepTimeReporter
from trustworthy_dl_tpu.quant import int8 as q8
from trustworthy_dl_tpu.serve.kv_slots import (
    kv_bytes_per_token,
    resolve_prefill_chunk,
    state_bytes_per_slot,
    validate_paged_geometry,
)
from trustworthy_dl_tpu.serve.scheduler import (
    PagedBatchingScheduler,
    SlotTask,
    refuse_unsupported,
    request_args,
    request_key,
    request_key_stream,
)
from trustworthy_dl_tpu.utils.metrics import MetricsCollector
from trustworthy_dl_tpu.utils.profiling import span

logger = logging.getLogger(__name__)

#: The scheduler's decode-call counters, by name, as ``metrics_summary()``
#: gives them and the registry counts them (``tddl_serve_<name>_total``).
DECODE_COUNTERS = {
    "decode_calls": "Fused decode calls dispatched",
    "decode_ahead_calls": "Decode calls dispatched while the previous "
                          "one's pull was still outstanding",
    "decode_settles": "Decode calls pulled outside a tick, before a slot "
                      "was read or freed",
    "decode_overrun_rows": "Decode rows dispatched past the end of their "
                           "stream (EOS, deadline, cancel, migration) and "
                           "thrown away",
}

#: The scope (a summary's key, a gauge's label) of what was counted between
#: the last two ``metrics_summary()`` calls.
SINCE_LAST = "since_last_summary"
#: What a phase's entry of ``metrics_summary()["tick_phases"]`` holds, in
#: ``StepTimeReporter.span_totals()``'s order.
PHASE_FIELDS = ("count", "seconds", "longest_s")


class _NullMetric:
    """No-op stand-in when a registry rejects a (re-)registration — the
    one case is a label-shape clash (an unlabelled standalone engine
    and a replica-labelled fleet engine sharing one registry).  The
    engine's own rollup counters stay exact; only this engine's export
    series is dropped, loudly at debug level."""

    def inc(self, *a: Any, **kw: Any) -> None:
        pass

    def set(self, *a: Any, **kw: Any) -> None:
        pass

    def observe(self, *a: Any, **kw: Any) -> None:
        pass

    def value(self, *a: Any, **kw: Any) -> None:
        return None


class _BoundMetric:
    """Binds an engine's fixed labels (replica=… in fleet mode) onto a
    registry metric so components that don't know about fleet labelling
    (the AdapterPool's gauge/counter handles) can call plain
    ``set(v)`` / ``inc(tenant=…)``."""

    def __init__(self, metric: Any, labels: Dict[str, str]):
        self._metric = metric
        self._labels = labels

    def set(self, *a: Any, **kw: Any) -> None:
        self._metric.set(*a, **{**kw, **self._labels})

    def inc(self, *a: Any, **kw: Any) -> None:
        self._metric.inc(*a, **{**kw, **self._labels})


@dataclasses.dataclass
class ServeRequest:
    """One generation request.  ``temperature<=0`` decodes greedily;
    ``deadline_s`` is a relative wall-clock budget from submit time (the
    request retires mid-flight with whatever it has when it expires);
    ``on_token`` streams each token as ``on_token(request_id, token)``;
    ``priority`` orders load shedding under an SLO breach — when the
    attached watcher is burning budget, the LOWEST-priority queued
    requests are shed first (ties: newest first).  ``first_submit_id``
    is the retry-age anchor: a resubmission of a previously shed/failed
    request carries its ORIGINAL submission's id so the shed tie-break
    treats it as old as it really is (without it a retry gets a fresh —
    newest — id and is shed again first under sustained pressure;
    fleet fail-over depends on this).  ``span_parent`` re-parents the
    request's ``serve.request`` span under an outer span (the fleet's
    per-attempt span, so one request's timeline survives fail-over).
    ``publish_prefix=False`` keeps the request's prompt blocks OUT of
    the shared PrefixCache — the fleet's verdict-vote replays are
    transient audits that must not perturb cache state.  ``tenant``
    is the end-to-end tenant identity: it rides the attribution-ledger
    record and the ``serve.request`` span, and the FLEET's per-tenant
    token buckets meter admission by it (None = untagged).  ``adapter``
    names the tenant's low-rank adapter (serve/adapters.py) — None
    falls back to the engine's ``adapter_map`` lookup by tenant, and
    the resolved id claims a pool page at admission."""

    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    deadline_s: Optional[float] = None
    rng: Optional[jax.Array] = None
    on_token: Optional[Callable[[int, int], None]] = None
    priority: int = 0
    first_submit_id: Optional[int] = None
    span_parent: Optional[int] = None
    publish_prefix: bool = True
    tenant: Optional[str] = None
    adapter: Optional[str] = None


@dataclasses.dataclass
class ServeResult:
    request_id: int
    tokens: List[int]
    # completed | deadline_exceeded | shed_slo | no_capacity (shed
    # because every slot was quarantined — see run_until_idle) | any
    # caller-chosen status passed to cancel() (the fleet uses
    # "migrated" / "hedge_lost" / "failover")
    status: str
    ttft_s: Optional[float]        # submit -> first token
    itl_s: List[float]             # inter-token latencies
    flagged: bool = False          # output monitor verdict
    monitor_z: float = 0.0
    adapter: Optional[str] = None  # resolved adapter id (serve/adapters.py)


class OutputMonitor:
    """Rolling per-request output-anomaly baseline.

    Signal vector per finished request: [mean logit entropy, mean top-1
    margin].  Both are cheap in-step reductions of the decode logits, and
    together they see the two anomaly directions: a backdoored/looping
    generation collapses entropy and inflates margin; a corrupted replica
    emitting garbage logits does the reverse.  One fleet-wide row, which
    absorbs ONLY requests it did not flag.

    The baseline lives ON THE HOST: a numpy ring of the last ``window``
    clean vectors and a write count.  A verdict's inputs are host floats
    (the decode tick's packed pull brought them back) and its answer is
    read by host code in the same retirement, so scoring on the device
    would be dispatches and pulls with nothing on the chip to overlap
    them.  The arithmetic is that of ``detect/baseline`` (masked mean and
    population deviation over the valid rows, ``|z|`` with 0 where the
    deviation is 0), whose ring stays the DEVICE's where the training
    detector reads it inside the jitted step; ``tests/test_serve.py``
    holds the two to each other."""

    NUM_SIGNALS = 2

    def __init__(self, window: int = 256, warmup: int = 16,
                 z_threshold: float = 4.0):
        self.warmup = warmup
        self.z_threshold = z_threshold
        self._ring = np.zeros((window, self.NUM_SIGNALS), np.float32)
        self._count = 0

    def observe(self, entropies: Sequence[float],
                margins: Sequence[float]) -> tuple:
        """Score one finished request; absorb it iff clean.  Returns
        (flagged, max_z)."""
        vec = np.asarray([sum(entropies) / len(entropies),
                          sum(margins) / len(margins)], np.float32)
        window = self._ring.shape[0]
        valid = min(self._count, window)
        z = 0.0
        if valid:
            # float32 rows, float64 moments (baseline_moments' sums).
            rows = self._ring[:valid]
            mean = rows.sum(axis=0, dtype=np.float64) / valid
            dev = rows - mean
            std = np.sqrt((dev * dev).sum(axis=0) / valid)
            safe = np.where(std > 0, std, 1.0)
            z = float(np.max(np.where(std > 0,
                                      np.abs(vec - mean) / safe, 0.0)))
        flagged = valid >= self.warmup and z > self.z_threshold
        if not flagged:
            self._ring[self._count % window] = vec
            self._count += 1
        return flagged, z

    @property
    def count(self) -> int:
        return self._count


class ServingEngine:
    """Continuous-batching serving over a fixed slot pool.

    ``queue_limit`` is the backpressure bound: ``submit`` returns None
    (shed load) once the admission queue is full — slots exhausted is not
    an error, it is the steady state under heavy traffic.

    Long-lived servers: per-request bookkeeping is dropped at retirement;
    finished ``ServeResult``s accumulate in ``results`` until the caller
    reads them — use ``drain_results()`` on a production loop so host
    memory stays bounded."""

    def __init__(self, params: Any, cfg: Any,
                 max_slots: int = 8, max_seq: int = 256,
                 queue_limit: int = 64,
                 rng: Optional[jax.Array] = None,
                 monitor: Optional[OutputMonitor] = None,
                 enable_monitor: bool = True,
                 metrics: Optional[MetricsCollector] = None,
                 chaos: Any = None, trace: Any = None,
                 registry: Any = None,
                 kv_dtype: str = "model", weight_dtype: str = "model",
                 kv_parity_check: bool = True,
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 spans: Any = None, ledger: Any = None,
                 slo: Any = None, anomaly: Any = None,
                 retain_results: int = 1024,
                 replica_id: Optional[int] = None,
                 retire_hook: Optional[Callable[..., None]] = None,
                 compilewatch: Any = None, hbm: Any = None,
                 spec_k: int = 0, attn_impl: str = "auto",
                 adapter_rank: int = 0,
                 adapter_pool_pages: Optional[int] = None,
                 adapter_dtype: str = "model",
                 adapter_map: Optional[Dict[str, str]] = None,
                 tp_size: int = 1,
                 tp_devices: Optional[Sequence[Any]] = None):
        # ``chaos``: an optional chaos.FaultInjector whose SERVE_POISON
        # events overwrite a retiring request's output signals — the
        # deterministic drill for the monitor→quarantine path (a poisoned
        # replica must lose its slot, not keep serving).
        self.chaos = chaos
        self.cfg = cfg
        # A description with recurrent state (models.decoder): what it
        # cannot be served with yet is refused HERE, one ValueError a
        # mechanism, before any weight is touched.
        refuse_unsupported(
            cfg, prefix_cache=prefix_cache, spec_k=spec_k,
            adapter_rank=adapter_rank, kv_dtype=kv_dtype,
            weight_dtype=weight_dtype, tp_size=tp_size)
        # Tensor-parallel replica: the engine owns a TP submesh over the
        # 'model' axis and the params carry the model's registry-declared
        # TP layout (core/sharding.py:serve_tp_mesh/place_serve_tp — the
        # SAME rules training TP resolves, so one layout serves both
        # planes).  Every jitted serve program then runs GSPMD-partitioned
        # over the group; tp_size=1 is byte-for-byte the single-chip
        # engine.  ``tp_devices`` is the fleet's carved per-replica device
        # slice; None defaults to the first tp_size local devices.
        self.tp_size = int(tp_size)
        self.tp_mesh = None
        if self.tp_size > 1:
            from trustworthy_dl_tpu.core import sharding as shreg

            self.tp_mesh = shreg.serve_tp_mesh(self.tp_size, tp_devices)
            params = shreg.place_serve_tp(params, self.tp_mesh)
        # Paged pool geometry fails loudly HERE, before any model work
        # (kv_slots.validate_paged_geometry — the same check ServeConfig
        # runs, so engines built without a config stay just as safe).
        validate_paged_geometry(max_seq, block_size, num_blocks,
                                prefill_chunk)
        if num_blocks is None:
            # Default pool: every slot can hold a full max_seq sequence.
            num_blocks = max_slots * (max_seq // block_size)
        # HBM headroom gate (obs/hbm.py): the KV pool is the one
        # construction-time allocation an operator sizes to fill HBM —
        # consult the monitor BEFORE allocating and shrink to what the
        # live budget actually has room for (floor: one full sequence),
        # instead of discovering the OOM at device_put.  The
        # denial itself is attributable: ``hbm_pressure`` event +
        # ``tddl_hbm_pressure_total``.
        self.hbm = hbm
        if hbm is not None:
            bpt = kv_bytes_per_token(cfg, jnp.int8) \
                if kv_dtype == "int8" else kv_bytes_per_token(cfg)
            # TP replica: the KV heads shard over the group, so each
            # device holds 1/tp of the pool's bytes — the headroom gate
            # budgets per DEVICE, so it admits the per-shard cost.  This
            # is what lets a scale-UP (bigger TP group) fit more blocks
            # into the same per-chip budget.
            bpt = max(bpt // max(self.tp_size, 1), 1)
            # The byte budget divides between the two kinds of cache: the
            # state rows are fixed by ``max_slots`` (0 for GPT-2), so
            # they are asked for with the pool and the POOL shrinks into
            # what they leave.
            state_bytes = max_slots * state_bytes_per_slot(cfg)
            requested = num_blocks * block_size * bpt + state_bytes
            if not hbm.admit(requested, what="serve_paged_pool"):
                # Size the shrunk pool from the SAME sweep that made
                # the deny decision (admit() stored it) — a second
                # sweep could report headroom the gate never saw.
                headroom = max((hbm.last_headroom or 0) - state_bytes, 0)
                floor = max_seq // block_size
                allowed = max(int(headroom // (block_size * bpt)),
                              floor)
                logger.warning(
                    "HBM headroom gate: paged pool shrunk %d -> %d "
                    "blocks (requested %d bytes, headroom %d)",
                    num_blocks, allowed, requested, headroom,
                )
                num_blocks = allowed
        # Quantization tier (quant/int8.py).  Unknown dtype strings fail
        # HERE; the int8 KV swap is additionally parity-gated: a short
        # eager greedy-token probe against the full-precision path, with
        # automatic fallback to the model-dtype pool on failure (the
        # same always-safe-swap pattern as flash_attention's non-tiling
        # fallback).  ``kv_parity_check=False`` skips the probe (tests
        # that construct many engines).
        q8.validate_dtypes(kv_dtype, weight_dtype)
        # Speculative decoding (README §Serving/"Speculative decoding"):
        # the same loud knob validation ServeConfig runs, so engines
        # built without a config fail identically (weight_dtype must
        # stay "model" — the int8 tier is the DRAFT).
        from trustworthy_dl_tpu.core.config import (validate_adapters,
                                                    validate_spec)

        validate_spec(spec_k, weight_dtype)
        validate_adapters(adapter_rank, adapter_pool_pages, adapter_dtype,
                          spec_k)
        self.spec_k = int(spec_k)
        self.kv_fallback_reason: Optional[str] = None
        # The decode view is built at most ONCE here and shared with the
        # parity probe, the scheduler (its ``view=`` kwarg) and the
        # weight-error histogram — quantize_decode_view walks every block
        # matrix, and a fleet constructs engines in a loop.
        base_view = None
        view = None
        if weight_dtype == "int8" or (kv_dtype == "int8" and kv_parity_check):
            base_view = gen._decode_view(params, cfg)
            view = (q8.quantize_decode_view(params, cfg, view=base_view)
                    if weight_dtype == "int8" else base_view)
        # The int8 self-draft for speculative decoding: built ONCE here
        # (validate_spec already pinned weight_dtype == "model", so the
        # serve view is dense) reusing whatever dense view exists — one
        # weight walk total.  The dense view doubles as the scheduler's
        # serve/verify view so it is not rebuilt there either.
        draft_view = None
        if self.spec_k > 0:
            if base_view is None:
                base_view = gen._decode_view(params, cfg)
            draft_view = q8.draft_decode_view(params, cfg,
                                              dense_view=base_view)
            if view is None:
                view = base_view
        if kv_dtype == "int8" and kv_parity_check:
            if not q8.kv_parity_probe(view, cfg):
                self.kv_fallback_reason = "kv_parity_probe_failed"
                kv_dtype = "model"
                # Keep the HBM budget the int8 sizing planned for: an
                # operator who filled HBM at int8 bytes/token must not
                # have the fallback allocate 2-4x that in the model dtype
                # — on a budgeted deployment that is an OOM at
                # construction, the opposite of "always safe".  Shrink
                # the pool's blocks to what the int8 byte budget buys at
                # model-dtype cost (floor: one full sequence).
                int8_bpt = kv_bytes_per_token(cfg, jnp.int8)
                model_bpt = kv_bytes_per_token(cfg)
                fallback_blocks = max(
                    max_seq // block_size,
                    (num_blocks * int8_bpt) // model_bpt,
                )
                logger.warning(
                    "int8 KV parity probe failed: falling back to "
                    "the model-dtype paged pool, shrinking %d -> %d "
                    "blocks to stay inside the int8 pool's HBM "
                    "budget (safety gate; see README "
                    "§Serving/Quantization)",
                    num_blocks, fallback_blocks,
                )
                num_blocks = fallback_blocks
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        # Multi-tenant adapter tier (serve/adapters.py): the SECOND
        # paged HBM resource, sized through the SAME headroom gate as
        # the KV pool — and sized AFTER it, so the KV pool keeps its
        # claim and the adapter pool shrinks into what remains (floor:
        # one usable page).  ``adapter_map`` routes tenant → adapter id
        # for requests that don't name one explicitly.
        self.adapter_rank = int(adapter_rank)
        self.adapter_dtype = adapter_dtype
        self.adapter_map: Dict[str, str] = dict(adapter_map or {})
        self.adapter_pool: Any = None
        if adapter_rank > 0:
            from trustworthy_dl_tpu.serve.adapters import (
                AdapterPool,
                adapter_bytes_per_page,
                adapter_pool_bytes,
            )

            pages = (adapter_pool_pages if adapter_pool_pages is not None
                     else max_slots)
            if hbm is not None:
                bpp = adapter_bytes_per_page(cfg, adapter_rank,
                                             adapter_dtype)
                requested = adapter_pool_bytes(cfg, pages, adapter_rank,
                                               adapter_dtype)
                if not hbm.admit(requested, what="serve_adapter_pool"):
                    # Re-size from the SAME sweep that denied (the KV
                    # template above): headroom // bytes-per-page, minus
                    # the reserved zero page, floored at one usable page.
                    headroom = max(hbm.last_headroom or 0, 0)
                    allowed = max(int(headroom // bpp) - 1, 1)
                    logger.warning(
                        "HBM headroom gate: adapter pool shrunk %d -> %d "
                        "pages (requested %d bytes, headroom %d)",
                        pages, allowed, requested, headroom,
                    )
                    pages = allowed
            self.adapter_pool = AdapterPool(
                cfg, adapter_rank, pages, adapter_dtype=adapter_dtype,
                trace=trace,
            )
        # ``attn_impl`` selects the decode-attention read (README
        # §Serving/"Decode attention kernel"): "auto" resolves through
        # the shared Pallas gate to the ragged paged-attention kernel
        # (+ fused trust epilogue) on TPU and the jnp gather fallback
        # elsewhere; "pallas"/"jnp" force a path.  Resolution happens
        # once, in the scheduler, and is baked into every compiled
        # program as a static.
        self.scheduler = PagedBatchingScheduler(
            params, cfg, max_slots, max_seq,
            kv_dtype=kv_dtype, weight_dtype=weight_dtype, view=view,
            block_size=block_size, num_blocks=num_blocks,
            prefix_cache=prefix_cache, prefill_chunk=prefill_chunk,
            spec_k=self.spec_k, draft_view=draft_view,
            attn_impl=attn_impl, adapters=self.adapter_pool,
        )
        self.queue_limit = queue_limit
        self.monitor = monitor if monitor is not None else (
            OutputMonitor() if enable_monitor else None
        )
        # ``trace``: optional obs TraceBus — the request lifecycle
        # (submit → admit → retire/quarantine) correlates on request_id.
        # Registry metrics are always on (per-iteration gauges ride the
        # collector's absorption; counters/latency histograms are the
        # serving SLO surface).
        self.trace = trace
        if registry is None:
            registry = get_registry()
        # Fleet-mode metric labelling: under a ServingFleet every engine
        # shares ONE registry, so the per-engine serve gauges would
        # last-writer-win each other (documented in PR 8 as "read only
        # the fleet aggregates").  With a ``replica_id`` the whole
        # tddl_serve_* surface gains a ``replica=`` label instead —
        # per-replica occupancy/blocks/tokens individually readable —
        # while standalone engines keep the unlabelled form.
        self.replica_id = replica_id
        self._rlabel_names = ("replica",) if replica_id is not None else ()
        self._rlabels = ({"replica": str(replica_id)}
                         if replica_id is not None else {})
        self.metrics = metrics or MetricsCollector(
            namespace="serve", registry=registry,
            labels=self._rlabels or None,
        )
        # A registry that ALREADY holds a metric under the other label
        # shape (a standalone engine registered the unlabelled form
        # before a fleet replica arrived, or vice versa) would raise on
        # re-registration; degrade that engine's series to a no-op
        # instead — the rollup dicts stay the source of truth, exactly
        # like MetricsCollector's export path.
        def _metric(register, name, help, labels=(), **kw):
            try:
                return register(name, help, labels=labels, **kw)
            except ValueError:
                logger.debug("serve metrics: registry rejected %s%s",
                             name, labels, exc_info=True)
                return _NullMetric()

        self._req_counter = _metric(
            registry.counter, "tddl_serve_requests_total",
            "Requests retired/shed, by terminal status",
            labels=("status",) + self._rlabel_names,
        )
        self._tok_counter = _metric(
            registry.counter, "tddl_serve_tokens_total", "Tokens emitted",
            labels=self._rlabel_names,
        )
        self._ttft_hist = _metric(
            registry.histogram, "tddl_serve_ttft_seconds",
            "Submit -> first token", labels=self._rlabel_names,
        )
        self._itl_hist = _metric(
            registry.histogram, "tddl_serve_itl_seconds",
            "Inter-token latency", labels=self._rlabel_names,
        )
        # KV-pool capacity surface: bytes resident (values + scales) and
        # slot count by storage dtype — the numbers the quantization
        # A/B moves (int8 ≈ halves bytes/slot → ~2x slots at fixed HBM).
        kv = self.scheduler.kv
        kv_dtype_label = str(kv.k.dtype)
        _metric(
            registry.gauge, "tddl_serve_kv_bytes",
            "KV slot-pool HBM footprint (values + quant scales)",
            labels=self._rlabel_names,
        ).set(float(kv.pool_bytes), **self._rlabels)
        #: What of the pool is LATENT rows (one shared row a position, no
        #: V half): all of it for a description with latent layers, else 0.
        self.latent_pool_bytes = kv.pool_bytes if kv.v is None else 0
        _metric(
            registry.gauge, "tddl_serve_latent_pool_bytes",
            "Latent-attention rows' HBM footprint (0 where the pool keeps "
            "per-head K and V)",
            labels=self._rlabel_names,
        ).set(float(self.latent_pool_bytes), **self._rlabels)
        state = self.scheduler.state
        self.state_pool_bytes = state.pool_bytes if state is not None else 0
        #: The same bytes by the kind of layer that keeps them (KDA's,
        #: the Mamba-2 mixers'); empty for GPT-2.
        self.state_bytes_by_kind = (
            {kind: n for kind, n in state.bytes_by_kind.items() if n}
            if state is not None else {})
        _metric(
            registry.gauge, "tddl_serve_state_pool_bytes",
            "Recurrent-state rows' HBM footprint (0 where every layer "
            "keeps keys and values)",
            labels=self._rlabel_names,
        ).set(float(self.state_pool_bytes), **self._rlabels)
        by_kind = _metric(
            registry.gauge, "tddl_serve_state_kind_bytes",
            "Recurrent-state rows' HBM footprint by the kind of layer",
            labels=("kind",) + self._rlabel_names)
        for kind, n in self.state_bytes_by_kind.items():
            by_kind.set(float(n), kind=kind, **self._rlabels)
        # Expert-layer counters (models.decoder descriptions): pulled off
        # the device by metrics_summary() alone.  The registry holds the
        # running totals and what was counted BETWEEN the last two
        # summaries, so a reader with no handle on the engine can take a
        # window's counts.
        self._expert_pairs_gauge = _metric(
            registry.gauge, "tddl_serve_moe_held_expert_pairs",
            "(token, expert) pairs routed to each held expert, all layers",
            labels=("expert", "scope") + self._rlabel_names,
        )
        self._expert_tokens_gauge = _metric(
            registry.gauge, "tddl_serve_moe_tokens_fed",
            "Tokens fed through an expert layer, a layer each",
            labels=("scope",) + self._rlabel_names,
        )
        # The tick's phases (``serve.*`` spans), kept the same way: totals
        # and what was counted between the last two summaries, written by
        # metrics_summary() alone.
        phase_labels = ("phase", "scope") + self._rlabel_names
        self._phase_gauges = (
            _metric(registry.gauge, "tddl_serve_phase_count",
                    "Spans a serving phase has closed", labels=phase_labels),
            _metric(registry.gauge, "tddl_serve_phase_seconds",
                    "Host wall under a serving phase's spans",
                    labels=phase_labels),
            _metric(registry.gauge, "tddl_serve_phase_longest_seconds",
                    "Longest single span of a serving phase",
                    labels=phase_labels),
        )
        self._tally_gauge = _metric(
            registry.gauge, "tddl_serve_phase_tally",
            "What a serving phase's spans held, by kind (serve.prefill_chunk:"
            " rows, the real rows of its chunk calls; padded, their padding"
            " rows)", labels=("phase", "kind", "scope") + self._rlabel_names)
        #: What the summary before this one saw, by what was counted.
        self._summary_seen: Dict[str, Dict[str, Sequence[float]]] = {}
        _metric(
            registry.gauge, "tddl_serve_slots_total",
            "KV slots in the pool, by storage dtype",
            labels=("dtype",) + self._rlabel_names,
        ).set(float(max_slots), dtype=kv_dtype_label, **self._rlabels)
        # Quantization-error histogram: per-matrix weight roundtrip
        # relative errors (weight-only int8) — empty when nothing is
        # quantized.  Buckets span the int8 regime (~1e-3 rel err).
        self._quant_err_hist = _metric(
            registry.histogram, "tddl_serve_quant_error",
            "Relative quantization error (weight roundtrip, per matrix)",
            labels=self._rlabel_names,
            buckets=(1e-5, 1e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 1.0),
        )
        if weight_dtype == "int8":
            for err in q8.weight_roundtrip_errors(base_view, cfg,
                                                  qview=view):
                self._quant_err_hist.observe(err, **self._rlabels)
        # Paged-pool occupancy surface: blocks referenced (requests +
        # prefix cache), tokens in flight, and prefix-cache reuse.
        self._blocks_gauge = _metric(
            registry.gauge, "tddl_serve_blocks_in_use",
            "Paged-KV blocks currently referenced (requests + prefix "
            "cache)",
            labels=self._rlabel_names,
        )
        self._tif_gauge = _metric(
            registry.gauge, "tddl_serve_tokens_in_flight",
            "Cached tokens currently backing live sequences",
            labels=self._rlabel_names,
        )
        self._prefix_counter = _metric(
            registry.counter, "tddl_serve_prefix_hits_total",
            "Admissions that reused cached prefix blocks",
            labels=self._rlabel_names,
        )
        self._prefix_hits_seen = 0
        # Adapter-pool residency surface (serve/adapters.py): pages
        # resident (impounded included) and evictions by evicted tenant.
        # Registered on every engine so the snapshot shape is uniform;
        # an adapterless engine just exports 0.  The pool receives
        # label-bound handles — it doesn't know about fleet labelling.
        self._adapter_pages_gauge = _metric(
            registry.gauge, "tddl_serve_adapter_pages_in_use",
            "Adapter-pool pages resident (live + warm + impounded); 0 "
            "when the adapter tier is off",
            labels=self._rlabel_names,
        )
        self._adapter_pages_gauge.set(0.0, **self._rlabels)
        self._adapter_evictions_counter = _metric(
            registry.counter, "tddl_serve_adapter_evictions_total",
            "Cold adapters LRU-evicted from the pool, by evicted tenant",
            labels=("tenant",) + self._rlabel_names,
        )
        if self.adapter_pool is not None:
            self.adapter_pool._pages_gauge = _BoundMetric(
                self._adapter_pages_gauge, self._rlabels)
            self.adapter_pool._evictions_counter = _BoundMetric(
                self._adapter_evictions_counter, self._rlabels)
        # Serving-kernel path gauge: one series per (program, path),
        # the active path set to 1 for each of the tier's programs
        # (decode / prefill / verify / adapter) — a silent fallback of
        # ANY program to its slow jnp spelling (gate off, untileable
        # geometry, non-TPU backend) is visible in EVERY serve
        # snapshot, and pages alongside the sentinel's tick fractions
        # instead of hiding inside tokens/s.
        from trustworthy_dl_tpu.ops import paged_attention as pattn

        self._attn_gauge = _metric(
            registry.gauge, "tddl_serve_attn_kernel",
            "Active serving-kernel path per paged program (1 = in "
            "use): the Pallas kernel, its interpret-mode twin, or the "
            "jnp gather/materialise fallback",
            labels=("path", "program") + self._rlabel_names,
        )
        _paths = self.attn_kernel_paths
        for _program in pattn.PAGED_PROGRAMS:
            for _path in ("pallas", "interpret", "jnp"):
                self._attn_gauge.set(
                    1.0 if _path == _paths[_program] else 0.0,
                    path=_path, program=_program, **self._rlabels,
                )
        # Speculative-decode surface: drafted vs accepted tokens (their
        # ratio is the summary's accepted_rate).  Registered on every
        # engine — replica-labelled in fleet mode like the rest of the
        # tddl_serve_* gauges — and incremented only when the spec tier
        # runs.
        self._spec_proposed_counter = _metric(
            registry.counter, "tddl_serve_spec_proposed_total",
            "Draft tokens proposed by the speculative int8 self-draft",
            labels=self._rlabel_names,
        )
        self._spec_accepted_counter = _metric(
            registry.counter, "tddl_serve_spec_accepted_total",
            "Draft tokens accepted by the batched model-dtype verify",
            labels=self._rlabel_names,
        )
        self._spec_seen = (0, 0)   # (proposed, accepted) already counted
        # The decode calls' surface (scheduler.decode_tick): the calls,
        # those dispatched a tick ahead of their record, the drains forced
        # outside a tick and the rows thrown away past a stream's end.
        self._decode_counters = {
            name: _metric(registry.counter, f"tddl_serve_{name}_total",
                          help, labels=self._rlabel_names)
            for name, help in DECODE_COUNTERS.items()}
        self._decode_seen = dict.fromkeys(DECODE_COUNTERS, 0)
        self.peak_tokens_in_flight = 0
        self.peak_active = 0
        # Kept on the host: every request's key is made there.
        self._rng = np.asarray(
            rng if rng is not None else jax.random.PRNGKey(0), np.uint32)
        self._queue: Deque[tuple] = deque()   # (task, request)
        self._inflight: Dict[int, tuple] = {}  # request_id -> (task, req, t)
        self._timing: Dict[int, List[float]] = {}  # request_id -> token times
        self._submit_t: Dict[int, float] = {}
        self.results: Dict[int, ServeResult] = {}
        self.rejected = 0
        self._next_id = 0
        self._iteration = 0
        self._tokens_emitted = 0
        self._t_start: Optional[float] = None
        # The ONE timer of this engine and its scheduler: every phase of
        # a tick and of ``submit`` is ``span(name, self.timer)`` (the
        # vocabulary is in utils/profiling.py), so the profiler's host
        # plane, the totals behind metrics_summary()'s fractions and an
        # attached SpanTracker read one pair of clock reads.
        # Its ring keeps the newest span a name: the totals carry the rest.
        self.timer = StepTimeReporter(max_steps=1)
        self.scheduler.timer = self.timer
        # -- active observability plane (all optional, all host-only) --
        # ``spans``: obs.spans.SpanTracker — request/phase timeline; held
        # by the timer, which forwards every phase span to it.
        # ``ledger``: obs.attribution.AttributionLedger — one durable
        # record per retired request.  ``slo``/``anomaly``: the
        # streaming watchers; when the SLO watcher is burning budget
        # (or an anomaly is active) the admission path sheds the
        # lowest-priority queued requests.  None of these touch the
        # device programs — streams stay bit-identical with all four
        # attached (pinned by tests).
        self.spans = spans
        self.ledger = ledger
        self.slo = slo
        self.anomaly = anomaly
        # Fleet integration (serve/fleet.py): ``replica_id`` names this
        # engine in a ServingFleet — it gates replica-addressed chaos
        # (request ids are replica-local, so an unaddressed poison would
        # be ambiguous across N replicas) and rides trace/ledger rows.
        # ``retire_hook(result, placement)`` fires synchronously at
        # every terminal state — placement is the scheduler's
        # attribution snapshot for admitted requests (None otherwise) —
        # so the fleet sees failures the instant they happen instead of
        # polling ``results``.  (``self.replica_id`` itself is set up
        # top with the replica-labelled metric surface.)
        # Every engine trace event carries the replica index in fleet
        # mode: request ids are replica-LOCAL, so without the tag a
        # shared TraceBus cannot tell replica 0's request 3 from
        # replica 1's (the same ambiguity the replica-gated chaos hook
        # closes for SERVE_POISON).
        self._trace_tags = ({"replica": replica_id}
                            if replica_id is not None else {})
        self.retire_hook = retire_hook
        # Performance tier (obs/compilewatch.py): the fused decode
        # dispatch runs under the watcher's "serve_decode" guard — the
        # compile-once pin enforced at runtime.
        self.compilewatch = compilewatch
        self.scheduler.compilewatch = compilewatch
        self._req_spans: Dict[int, Dict[str, int]] = {}  # rid -> open ids
        # Bounded completed-request retention: ``results`` keeps at most
        # ``retain_results`` finished records (oldest evicted first);
        # the rollup counters + streaming percentile estimators below
        # keep ``metrics_summary`` exact over EVERY request ever
        # retired, evicted or not.
        if retain_results < 1:
            raise ValueError("retain_results must be >= 1")
        self.retain_results = retain_results
        self._status_counts: Dict[str, int] = {}
        self._flagged_total = 0
        # An attached SLO watcher already keeps P² sketches of the same
        # ttft_s/itl_s streams — own a second pair only when unwatched,
        # and read whichever exists in metrics_summary (one marker set
        # per signal, one p50 for both summary and slo_status.json).
        if slo is None:
            from trustworthy_dl_tpu.obs.slo import StreamingPercentiles

            self._ttft_est = StreamingPercentiles()
            self._itl_est = StreamingPercentiles()
        else:
            self._ttft_est = None
            self._itl_est = None
        self.shed_slo = 0

    @classmethod
    def from_config(cls, params: Any, cfg: Any,
                    serve_config: Any, **kwargs: Any) -> "ServingEngine":
        """Build an engine from a ``core.config.ServeConfig`` (whose
        construction already validated the dtype knobs loudly) and a
        model description, ``gpt2.GPT2Config`` or
        ``models.decoder.DecoderConfig``: its type selects the programs;
        ``kwargs`` pass through for the non-config surfaces (rng,
        monitor, trace, registry, ...)."""
        return cls(
            params, cfg,
            max_slots=serve_config.max_slots,
            max_seq=serve_config.max_seq,
            queue_limit=serve_config.queue_limit,
            kv_dtype=serve_config.kv_dtype,
            weight_dtype=serve_config.weight_dtype,
            block_size=serve_config.block_size,
            num_blocks=serve_config.num_blocks,
            prefix_cache=serve_config.prefix_cache,
            prefill_chunk=serve_config.prefill_chunk,
            spec_k=serve_config.spec_k,
            attn_impl=serve_config.attn_impl,
            adapter_rank=serve_config.adapter_rank,
            adapter_pool_pages=serve_config.adapter_pool_pages,
            adapter_dtype=serve_config.adapter_dtype,
            tp_size=serve_config.tp_size,
            **kwargs,
        )

    # -- submission --------------------------------------------------------

    def submit(self, request: ServeRequest) -> Optional[int]:
        """Enqueue a request; returns its request_id, or None when shed by
        backpressure (queue full).  Raises for requests that can never be
        served (longer than the cache)."""
        with span("serve.submit", self.timer, request_id=self._next_id,
                  prompt_len=len(request.prompt)) as noted:
            request_id = self._submit(request)
            noted["request_id"] = request_id      # None: shed
        return request_id

    def _submit(self, request: ServeRequest) -> Optional[int]:
        prompt = np.asarray(list(request.prompt), np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt.size + request.max_new_tokens
        if total > self.scheduler.max_seq:
            raise ValueError(
                f"prompt+new = {total} exceeds max_seq="
                f"{self.scheduler.max_seq}"
            )
        # Tenant → adapter resolution: an explicit request.adapter wins,
        # else the engine's adapter_map by tenant.  Loud when the tier
        # is off — a silently dropped adapter would serve the BASE model
        # under the tenant's name, the exact trust failure the paged
        # adapter tier exists to prevent.
        adapter = request.adapter
        if adapter is None and request.tenant is not None:
            adapter = self.adapter_map.get(request.tenant)
        if adapter is not None and self.adapter_pool is None:
            raise ValueError(
                f"request names adapter {adapter!r} but the adapter tier "
                "is off (adapter_rank=0); serving it on the base model "
                "would silently misattribute the stream"
            )
        if len(self._queue) >= self.queue_limit:
            self.rejected += 1
            self._req_counter.inc(status="rejected", **self._rlabels)
            return None
        request_id = self._next_id
        self._next_id += 1
        # The key's derivation and its split are small device programs and
        # a pull of their own, between ticks.
        with span("serve.submit.key_stream", self.timer,
                  max_new_tokens=int(request.max_new_tokens)):
            rng = request.rng
            if rng is None:
                rng = request_key(self._rng, request_id)
            keys = request_key_stream(rng, int(request.max_new_tokens))
        task = SlotTask(
            request_id=request_id,
            prompt=prompt,
            max_new_tokens=int(request.max_new_tokens),
            temperature=float(request.temperature),
            keys=keys,
            eos_id=request.eos_id,
            publish_prefix=bool(request.publish_prefix),
            adapter=adapter,
        )
        self._queue.append((task, request))
        self._submit_t[request_id] = time.perf_counter()
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_SUBMIT, request_id=request_id,
                            prompt_len=int(prompt.size),
                            max_new_tokens=int(request.max_new_tokens), **self._trace_tags)
        if self.spans is not None:
            root = self.spans.start("serve.request", kind="serve",
                                    parent_id=request.span_parent,
                                    request_id=request_id,
                                    replica=self.replica_id,
                                    tenant=request.tenant,
                                    prompt_len=int(prompt.size),
                                    max_new_tokens=int(
                                        request.max_new_tokens))
            queued = self.spans.start("serve.queued", kind="serve",
                                      parent_id=root,
                                      request_id=request_id)
            self._req_spans[request_id] = {"root": root, "queued": queued}
            task.span_root = root
        return request_id

    # -- terminal bookkeeping ----------------------------------------------

    def _record_result(self, result: ServeResult,
                       placement: Optional[Dict[str, Any]] = None) -> None:
        """The ONE rollup path every terminal state goes through: status
        counters (exact forever), bounded ``results`` retention (oldest
        evicted first), registry counter, and the fleet's
        ``retire_hook`` (placement = the scheduler's attribution
        snapshot for admitted requests, None for queue-side sheds)."""
        self._status_counts[result.status] = \
            self._status_counts.get(result.status, 0) + 1
        if result.flagged:
            self._flagged_total += 1
        self.results[result.request_id] = result
        while len(self.results) > self.retain_results:
            del self.results[next(iter(self.results))]
        self._req_counter.inc(status=result.status, **self._rlabels)
        if self.retire_hook is not None:
            self.retire_hook(result, placement)

    def _close_request_spans(self, rid: int, status: str,
                             **attrs: Any) -> None:
        handles = self._req_spans.pop(rid, None)
        if handles is None or self.spans is None:
            return
        for name in ("queued", "prefill", "decode", "monitor"):
            sid = handles.get(name)
            if sid is not None:
                self.spans.end(sid)
        self.spans.end(handles["root"], status=status, **attrs)

    def _span_first_token(self, rid: int) -> None:
        """prefill → decode span transition at the request's first
        emitted token."""
        handles = self._req_spans.get(rid)
        if handles is None or self.spans is None:
            return
        sid = handles.pop("prefill", None)
        if sid is not None:
            self.spans.end(sid)
        handles["decode"] = self.spans.start(
            "serve.decode", kind="serve", parent_id=handles["root"],
            request_id=rid,
        )

    def _ledger_unadmitted(self, rid: int, status: str,
                           tenant: Optional[str] = None) -> None:
        if self.ledger is None:
            return
        self.ledger.append({
            "request_id": rid, "status": status, "admitted": False,
            "slot": -1, "layout": "paged",
            "block_ids": [], "prefix_block_ids": [],
            "prefix_publishers": {},
            "kv_dtype": self.kv_dtype, "weight_dtype": self.weight_dtype,
            "kv_fallback_reason": self.kv_fallback_reason,
            "flagged": False, "monitor_z": 0.0,
            "tokens": 0, "token_hash": attribution.token_hash([]),
            "tenant": tenant,
        })

    def _request_age_id(self, task: SlotTask, request: ServeRequest) -> int:
        """Submission-order age for shed tie-breaks: the ORIGINAL
        submission's id when the request is a retry
        (``first_submit_id``), its own id otherwise.  Without the
        anchor, a shed-and-resubmitted request gets a fresh (newest) id
        and is shed again first under sustained pressure — a retry
        starvation loop the fleet's fail-over path would otherwise
        inherit."""
        if request.first_submit_id is not None:
            return int(request.first_submit_id)
        return int(task.request_id)

    def _shed_for_slo(self) -> None:
        """The watcher's host-side shed hook: while an SLO rule is
        burning budget (or an anomaly is active), drop the
        LOWEST-priority queued request (ties: newest first, by ORIGINAL
        submission age — retries inherit theirs) — but only when the
        queue exceeds the currently free capacity, so shedding relieves
        real pressure instead of burning goodput.  At most one shed per
        iteration: pressure is re-evaluated every step."""
        breached = ((self.slo is not None and self.slo.breached)
                    or (self.anomaly is not None
                        and self.anomaly.any_active))
        if not breached or not self._queue:
            return
        if len(self._queue) <= self.scheduler.allocator.free_count:
            return
        idx = min(range(len(self._queue)),
                  key=lambda i: (self._queue[i][1].priority,
                                 -self._request_age_id(*self._queue[i])))
        task, _request = self._queue[idx]
        del self._queue[idx]
        rid = task.request_id
        self._submit_t.pop(rid, None)
        self.shed_slo += 1
        self._record_result(ServeResult(
            request_id=rid, tokens=[], status="shed_slo", ttft_s=None,
            itl_s=[],
        ))
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_RETIRE, request_id=rid,
                            status="shed_slo", tokens=0, admitted=False, **self._trace_tags)
        self._close_request_spans(rid, "shed_slo")
        self._ledger_unadmitted(rid, "shed_slo", tenant=_request.tenant)

    # -- iteration loop ----------------------------------------------------

    def step(self) -> int:
        """One scheduler iteration: expire → admit → decode → retire.
        Returns the number of tokens emitted this iteration."""
        now = time.perf_counter()
        if self._t_start is None:
            self._t_start = now
        self._iteration += 1
        timer = self.timer
        with span("serve.tick", timer, iteration=self._iteration,
                  queued=len(self._queue),
                  active=self.scheduler.active_count):
            with span("serve.tick.expire", timer):
                self._expire_queued(now)
                self._shed_for_slo()
            with span("serve.tick.admit", timer) as noted:
                emitted, noted["admitted"] = self._admit_queued()
            with span("serve.decode_tick", timer) as noted:
                ticked = self.scheduler.decode_tick()
                noted.update(tokens=len(ticked),
                             active=self.scheduler.active_count)
            with span("serve.tick.emit", timer) as noted:
                emitted += self._emit(ticked)
                noted["tokens"] = emitted
            with span("serve.tick.account", timer):
                self._account(emitted)
        return emitted

    def _admit_queued(self) -> "tuple[int, int]":
        """Admit as many queued requests as there are free slots.
        Admission only books host-side state (block claim + prefix-cache
        lookup); the chunked prefill runs inside subsequent decode_ticks —
        the first token lands when the final chunk completes.  Returns the
        tokens admission itself brought and the requests it admitted."""
        emitted = admitted = 0
        while self._queue and self.scheduler.has_free_slot:
            task, request = self._queue.popleft()
            if not self.scheduler.admit(task):
                self._queue.appendleft((task, request))
                break
            admitted += 1
            rid = task.request_id
            self._inflight[rid] = (task, request)
            if self.trace is not None:
                self.trace.emit(EventType.SERVE_ADMIT, request_id=rid,
                                slot=int(task.slot), **self._trace_tags)
            handles = self._req_spans.get(rid)
            if handles is not None:
                sid = handles.pop("queued", None)
                if sid is not None:
                    self.spans.end(sid, slot=int(task.slot))
                handles["prefill"] = self.spans.start(
                    "serve.prefill", kind="serve",
                    parent_id=handles["root"], request_id=rid,
                    slot=int(task.slot),
                )
            if task.emitted:
                self._timing[rid] = [time.perf_counter()]
                self._span_first_token(rid)
                self._stream(request, rid, task.emitted[-1])
                emitted += 1
                if task.done:
                    self._finish(task, request, "completed")
        return emitted, admitted

    def _emit(self, ticked: List[SlotTask]) -> int:
        """Stream the tick's tokens, retire what finished or ran out of
        time; returns the tokens streamed."""
        emitted = 0
        for task in ticked:
            rid = task.request_id
            if rid not in self._inflight:
                continue
            _, request = self._inflight[rid]
            times = self._timing.setdefault(rid, [])
            if not times:
                self._span_first_token(rid)
            # A speculative tick can emit SEVERAL tokens at once
            # (``tick_tokens``, in emission order); every single-token
            # path leaves it None and streams emitted[-1] exactly as
            # before.  The burst's intra-tick ITLs are honest
            # near-zeros: the tokens really did land together.
            new_tokens = (task.tick_tokens
                          if task.tick_tokens is not None
                          else [task.emitted[-1]])
            for token in new_tokens:
                times.append(time.perf_counter())
                self._stream(request, rid, token)
                emitted += 1
            deadline = request.deadline_s
            expired = (deadline is not None
                       and time.perf_counter() - self._submit_t[rid]
                       > deadline)
            if task.done:
                self._finish(task, request, "completed")
            elif expired:
                self._finish(task, request, "deadline_exceeded")
        # Mid-prefill deadline check (paged chunked prefill): a slot
        # still feeding prompt chunks emits nothing from decode_tick, so
        # the loop above never sees it — without this an already-expired
        # long prompt would keep burning chunk programs (and delaying
        # every other slot's tick) until its first token.
        for rid, (task, request) in list(self._inflight.items()):
            if task.done or task.emitted:
                continue
            deadline = request.deadline_s
            if (deadline is not None
                    and time.perf_counter() - self._submit_t[rid]
                    > deadline):
                self._finish(task, request, "deadline_exceeded")
        return emitted

    def _account(self, emitted: int) -> None:
        """The tick's gauges and its row for the metrics collector."""
        self._tokens_emitted += emitted
        if emitted:
            self._tok_counter.inc(emitted, **self._rlabels)

        tif = self.scheduler.tokens_in_flight
        self.peak_tokens_in_flight = max(self.peak_tokens_in_flight, tif)
        self.peak_active = max(self.peak_active,
                               self.scheduler.active_count)
        self._tif_gauge.set(float(tif), **self._rlabels)
        if self.slo is not None:
            self.slo.observe("occupancy", self.scheduler.occupancy)
        self._blocks_gauge.set(float(self.scheduler.blocks_in_use),
                                **self._rlabels)
        hits = self.scheduler.prefix_hits
        if hits > self._prefix_hits_seen:
            self._prefix_counter.inc(hits - self._prefix_hits_seen,
                                     **self._rlabels)
            self._prefix_hits_seen = hits
        if self.spec_k:
            proposed = self.scheduler.spec_proposed
            accepted = self.scheduler.spec_accepted
            seen_p, seen_a = self._spec_seen
            if proposed > seen_p:
                self._spec_proposed_counter.inc(proposed - seen_p,
                                                **self._rlabels)
            if accepted > seen_a:
                self._spec_accepted_counter.inc(accepted - seen_a,
                                                **self._rlabels)
            self._spec_seen = (proposed, accepted)
        for name, counter in self._decode_counters.items():
            now = getattr(self.scheduler, name)
            if now > self._decode_seen[name]:
                counter.inc(now - self._decode_seen[name], **self._rlabels)
                self._decode_seen[name] = now
        self.metrics.collect_batch_metrics({
            "step": self._iteration,
            "active_slots": self.scheduler.active_count,
            "slot_occupancy": self.scheduler.occupancy,
            "queue_depth": len(self._queue),
            "tokens_emitted": emitted,
            "tokens_in_flight": tif,
            "slots_in_service": self.scheduler.allocator.capacity,
        })
        self.metrics.tick()

    def run_until_idle(self, max_iterations: int = 100_000
                       ) -> Dict[int, ServeResult]:
        """Drive ``step()`` until queue and slots drain (or the iteration
        bound trips — a liveness backstop, not a normal exit)."""
        it = 0
        while self._queue or self._inflight:
            idle_before = not self._inflight
            qlen = len(self._queue)
            self.step()
            it += 1
            # Starvation check: with nothing in flight before the step,
            # a step that admitted nothing and shed nothing proves the
            # queue can never drain — every row quarantined, or
            # quarantined BLOCKS starving the pool even after
            # prefix-cache eviction; no retirement can ever free more
            # capacity.  Shed the queue instead of spinning to the
            # iteration bound.
            if (idle_before and not self._inflight
                    and self._queue and len(self._queue) == qlen):
                while self._queue:
                    task, request = self._queue.popleft()
                    rid = task.request_id
                    self._submit_t.pop(rid, None)
                    self._record_result(ServeResult(
                        request_id=rid, tokens=[],
                        status="no_capacity", ttft_s=None, itl_s=[],
                    ))
                    if self.trace is not None:
                        self.trace.emit(EventType.SERVE_RETIRE,
                                        request_id=rid,
                                        status="no_capacity", tokens=0,
                                        admitted=False, **self._trace_tags)
                    self._close_request_spans(rid, "no_capacity")
                    self._ledger_unadmitted(rid, "no_capacity",
                                            tenant=request.tenant)
                break
            if it >= max_iterations:
                raise RuntimeError(
                    f"serving loop did not drain in {max_iterations} "
                    "iterations"
                )
        return self.results

    # -- internals ---------------------------------------------------------

    def _stream(self, request: ServeRequest, request_id: int,
                token: int) -> None:
        if request.on_token is not None:
            request.on_token(request_id, token)

    def _expire_queued(self, now: float) -> None:
        """Shed queued requests whose deadline passed before admission."""
        keep: Deque[tuple] = deque()
        while self._queue:
            task, request = self._queue.popleft()
            rid = task.request_id
            if (request.deadline_s is not None
                    and now - self._submit_t[rid] > request.deadline_s):
                self._submit_t.pop(rid, None)
                self._record_result(ServeResult(
                    request_id=rid, tokens=[],
                    status="deadline_exceeded", ttft_s=None, itl_s=[],
                ))
                if self.trace is not None:
                    self.trace.emit(EventType.SERVE_RETIRE, request_id=rid,
                                    status="deadline_exceeded", tokens=0,
                                    admitted=False, **self._trace_tags)
                self._close_request_spans(rid, "deadline_exceeded")
                self._ledger_unadmitted(rid, "deadline_exceeded",
                                        tenant=request.tenant)
            else:
                keep.append((task, request))
        self._queue = keep

    def cancel(self, request_id: int, status: str = "cancelled",
               quarantine: bool = False) -> bool:
        """Terminate a queued or in-flight request NOW with ``status``
        (no monitor scoring): the fleet's migrate/hedge hook — a
        draining replica's queue moves elsewhere, a lost hedge's
        duplicate stream stops burning decode slots, a live migration
        releases its source half after the destination commits.
        Resources (slot, blocks) free immediately; partial tokens ride
        the result.  ``quarantine=True`` IMPOUNDS instead of freeing
        (scheduler.retire's quarantine path: row + unshared blocks leave
        the pool) — the source side of a migration OFF a quarantined/
        trust-draining replica must not return suspect blocks to
        service.  Returns False when the id is unknown/already
        terminal."""
        for i in range(len(self._queue)):
            task, _request = self._queue[i]
            if task.request_id != request_id:
                continue
            del self._queue[i]
            self._submit_t.pop(request_id, None)
            self._record_result(ServeResult(
                request_id=request_id, tokens=[], status=status,
                ttft_s=None, itl_s=[],
            ))
            if self.trace is not None:
                self.trace.emit(EventType.SERVE_RETIRE,
                                request_id=request_id, status=status,
                                tokens=0, admitted=False, **self._trace_tags)
            self._close_request_spans(request_id, status)
            self._ledger_unadmitted(request_id, status,
                                    tenant=_request.tenant)
            return True
        pair = self._inflight.get(request_id)
        if pair is None:
            return False
        task, _request = pair
        self.scheduler.settle(task.slot)
        placement = (self.scheduler.attribution_info(task)
                     if self.ledger is not None
                     or self.retire_hook is not None else None)
        self.scheduler.retire(task, quarantine=quarantine)
        times = self._timing.pop(request_id, [])
        t0 = self._submit_t.pop(request_id, None)
        ttft = (times[0] - t0) if times and t0 is not None else None
        self._record_result(ServeResult(
            request_id=request_id, tokens=list(task.emitted),
            status=status, ttft_s=ttft,
            itl_s=[b - a for a, b in zip(times, times[1:])],
            adapter=task.adapter,
        ), placement=placement)
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_RETIRE, request_id=request_id,
                            status=status, tokens=len(task.emitted), **self._trace_tags)
            if quarantine:
                self.trace.emit(EventType.SERVE_QUARANTINE,
                                request_id=request_id,
                                slot=int(task.slot), **self._trace_tags)
        if self.ledger is not None:
            self.ledger.append({
                "request_id": request_id, "status": status,
                "admitted": True, **placement,
                "kv_dtype": self.kv_dtype,
                "weight_dtype": self.weight_dtype,
                "kv_fallback_reason": self.kv_fallback_reason,
                "flagged": False, "monitor_z": 0.0,
                "tokens": len(task.emitted),
                "token_hash": attribution.token_hash(task.emitted),
                "ttft_s": ttft,
                "tenant": _request.tenant,
            })
        self._close_request_spans(request_id, status,
                                  tokens=len(task.emitted))
        self._inflight.pop(request_id, None)
        return True

    # -- live migration (serve/migrate.py orchestrates) --------------------

    def export_request(self, request_id: int) -> Optional[Dict[str, Any]]:
        """Source half of a live migration: the scheduler's block-table
        snapshot (decode-phase only — mid-prefill and unknown ids
        refuse with None, nothing touched) plus the engine-level timing
        state that must travel for TTFT/ITL and deadline math to stay
        exact across the hand-off.  Read-only: the request keeps
        decoding here until ``cancel(..., status="migrated")`` releases
        it AFTER the destination commits."""
        pair = self._inflight.get(request_id)
        if pair is None:
            return None
        task, request = pair
        snap = self.scheduler.export_migration(task)
        if snap is None:
            return None
        snap["request"] = request
        snap["submit_t"] = self._submit_t.get(request_id)
        snap["times"] = list(self._timing.get(request_id, []))
        snap["replica"] = self.replica_id
        return snap

    def adopt_request(self, snapshot: Dict[str, Any],
                      claim: Dict[str, Any], *,
                      on_token: Optional[Callable[[int, int], None]] = None,
                      migrated_from: Optional[Dict[str, Any]] = None
                      ) -> int:
        """Destination COMMIT half of a live migration: register the
        migrated stream under a fresh LOCAL id on the claimed row —
        pure host bookkeeping (the physical block copy already landed),
        so it cannot fail after the claim.  The continuation task
        copies the source's emitted stream, trust signals and the WHOLE
        sampling key stream (the next key index is ``len(emitted)`` —
        rng position travels by construction); ``publish_prefix`` is
        forced off (the destination never prefilled these blocks — the
        prompt was published, if at all, by the source).  Source-side
        ``submit_t``/token times carry over verbatim (same process
        clock), so deadlines, TTFT and ITL read as one request, not
        two."""
        src_task: SlotTask = snapshot["task"]
        src_request: ServeRequest = snapshot["request"]
        rid = self._next_id
        self._next_id += 1
        task = SlotTask(
            request_id=rid,
            prompt=np.asarray(src_task.prompt, np.int32),
            max_new_tokens=int(src_task.max_new_tokens),
            temperature=float(src_task.temperature),
            keys=src_task.keys,
            eos_id=src_task.eos_id,
            publish_prefix=False,
            adapter=src_task.adapter,
        )
        task.emitted = list(src_task.emitted)
        task.next_token = src_task.next_token
        task.entropies = list(src_task.entropies)
        task.margins = list(src_task.margins)
        request = dataclasses.replace(
            src_request,
            on_token=(on_token if on_token is not None
                      else src_request.on_token),
        )
        self.scheduler.commit_migration(task, claim, snapshot["length"],
                                        migrated_from=migrated_from)
        self._inflight[rid] = (task, request)
        t0 = snapshot.get("submit_t")
        self._submit_t[rid] = (t0 if t0 is not None
                               else time.perf_counter())
        self._timing[rid] = list(snapshot.get("times", []))
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_ADMIT, request_id=rid,
                            slot=int(task.slot), migrated=True,
                            **self._trace_tags)
        return rid

    def _finish(self, task: SlotTask, request: ServeRequest,
                status: str) -> None:
        with span("serve.tick.retire", self.timer, status=status,
                  **request_args(task)):
            self._retire(task, request, status)

    def _retire(self, task: SlotTask, request: ServeRequest,
                status: str) -> None:
        rid = task.request_id
        if self.chaos is not None:
            # Chaos hook point: a SERVE_POISON event for this request id
            # (replica-gated — local ids are ambiguous across a fleet)
            # or an active REPLICA_POISON on this replica rewrites the
            # recorded entropy/margin signals before the monitor scores
            # them (simulating a compromised replica).
            self.chaos.on_serve_retire(task, replica=self.replica_id)
        # Placement snapshot BEFORE retire() clears the slot's table —
        # the attribution record must name the physical blocks the
        # stream actually decoded from.
        placement = (self.scheduler.attribution_info(task)
                     if self.ledger is not None
                     or self.retire_hook is not None else None)
        flagged, z = False, 0.0
        if self.monitor is not None and task.entropies:
            with span("serve.monitor", self.timer,
                      **request_args(task)) as noted:
                flagged, z = self.monitor.observe(task.entropies,
                                                  task.margins)
                noted.update(flagged=flagged, monitor_z=float(z))
        self.scheduler.retire(task, quarantine=flagged)
        times = self._timing.pop(rid, [])
        t0 = self._submit_t.pop(rid, None)
        ttft = (times[0] - t0) if times and t0 is not None else None
        itl = [b - a for a, b in zip(times, times[1:])]
        self._record_result(ServeResult(
            request_id=rid, tokens=list(task.emitted), status=status,
            ttft_s=ttft, itl_s=itl, flagged=flagged, monitor_z=z,
            adapter=task.adapter,
        ), placement=placement)
        if ttft is not None:
            self._ttft_hist.observe(ttft, **self._rlabels)
            if self.slo is not None:
                self.slo.observe("ttft_s", ttft)
            else:
                self._ttft_est.observe(ttft)
        for dt in itl:
            self._itl_hist.observe(dt, **self._rlabels)
            if self.slo is not None:
                self.slo.observe("itl_s", dt)
            else:
                self._itl_est.observe(dt)
            if self.anomaly is not None:
                self.anomaly.observe("itl", dt)
        if self.trace is not None:
            self.trace.emit(EventType.SERVE_RETIRE, request_id=rid,
                            status=status, tokens=len(task.emitted),
                            flagged=flagged, monitor_z=z, **self._trace_tags)
            if flagged:
                self.trace.emit(EventType.SERVE_QUARANTINE, request_id=rid,
                                slot=int(task.slot), **self._trace_tags)
        if self.ledger is not None:
            thash = attribution.token_hash(task.emitted)
            record = {
                "request_id": rid, "status": status, "admitted": True,
                **placement,
                "kv_dtype": self.kv_dtype,
                "weight_dtype": self.weight_dtype,
                "kv_fallback_reason": self.kv_fallback_reason,
                "flagged": bool(flagged), "monitor_z": float(z),
                "tokens": len(task.emitted), "token_hash": thash,
                "ttft_s": ttft,
                "tenant": request.tenant,
            }
            self.ledger.append(record)
            if self.trace is not None:
                self.trace.emit(EventType.ATTRIBUTION, request_id=rid,
                                slot=int(task.slot),
                                n_blocks=len(placement["block_ids"]),
                                token_hash=thash, flagged=bool(flagged),
                                adapter=placement.get("adapter"),
                                adapter_page=placement.get(
                                    "adapter_page", 0), **self._trace_tags)
        self._close_request_spans(rid, status, tokens=len(task.emitted),
                                  flagged=bool(flagged))
        self.metrics.collect_batch_metrics({
            "step": self._iteration,
            "request_id": rid,
            "ttft_s": ttft if ttft is not None else -1.0,
            "tokens": len(task.emitted),
            "flagged": int(flagged),
        })
        self._inflight.pop(rid, None)

    # -- reporting ---------------------------------------------------------

    @property
    def spans(self) -> Any:
        """The attached obs.spans.SpanTracker (None without one): the
        timer holds it and forwards every phase span to it; the spans of
        a request that cross ticks open and close on it directly."""
        return self.timer.spans

    @spans.setter
    def spans(self, tracker: Any) -> None:
        self.timer.spans = tracker

    @property
    def busy(self) -> bool:
        """Work still queued or in flight."""
        return bool(self._queue or self._inflight)

    @property
    def queued_ids(self) -> List[int]:
        """Local request ids awaiting admission (fleet migrate hook)."""
        return [task.request_id for task, _ in self._queue]

    @property
    def inflight_ids(self) -> List[int]:
        """Local request ids holding a slot (fleet fail-over hook)."""
        return list(self._inflight)

    @property
    def decode_ready_ids(self) -> List[int]:
        """In-flight ids past prefill with tokens emitted — the set a
        disaggregated fleet moves off a prefill-specialist replica (a
        migration snapshot exists exactly for these)."""
        prefilling = self.scheduler._prefill
        return [rid for rid, (task, _) in self._inflight.items()
                if task.emitted and not task.done
                and task.slot not in prefilling]

    @property
    def load(self) -> int:
        """Queued + in-flight — the fleet router's least-loaded key."""
        return len(self._queue) + len(self._inflight)

    @property
    def open_requests(self) -> int:
        """Accepted-but-unfinished requests — the closed-loop driver's
        in-flight count (engine spelling of the fleet property)."""
        return self.load

    @property
    def in_service_capacity(self) -> int:
        """Slots currently serviceable (total minus quarantined)."""
        return self.scheduler.allocator.capacity

    def drain_results(self) -> Dict[int, ServeResult]:
        """Return finished results and clear them — the bounded-memory
        retrieval API for long-lived serving loops."""
        out = self.results
        self.results = {}
        return out

    @property
    def attn_kernel_path(self) -> str:
        """The resolved decode-attention path this engine's compiled
        programs bake in: "pallas" | "interpret" | "jnp".  The
        monitor's entropy/margin come from the kernel's fused trust
        epilogue exactly when this is not "jnp"."""
        return self.scheduler.attn_impl

    @property
    def attn_kernel_paths(self) -> Dict[str, str]:
        """Per-program resolved paths for the whole serving-kernel tier
        (ops.paged_attention.PAGED_PROGRAMS: decode / prefill / verify /
        adapter), each "pallas" | "interpret" | "jnp"."""
        return dict(self.scheduler.attn_impls)

    @property
    def quarantined_slots(self):
        return self.scheduler.allocator.quarantined

    def release_quarantine(self, slot: int) -> None:
        # Routed through the scheduler: the paged pool returns the
        # blocks impounded with the slot, not just the decode row.
        self.scheduler.release_quarantine(slot)

    def quarantine_adapter(self, name: str) -> None:
        """Apply a fleet-level trust verdict against an ADAPTER to this
        replica's pool: future resolves refuse, the page impounds when
        its last in-flight request drains.  The replica itself stays in
        service — adapter trust and replica trust are separate axes
        (serve/fleet.py owns the verdict and the fleet-wide event)."""
        if self.adapter_pool is not None:
            self.adapter_pool.quarantine(name)

    def unquarantine_adapter(self, name: str) -> None:
        """Operator action: lift an adapter verdict on this replica."""
        if self.adapter_pool is not None:
            self.adapter_pool.unquarantine(name)

    @property
    def quarantined_adapters(self):
        return (self.adapter_pool.quarantined
                if self.adapter_pool is not None else set())

    def metrics_summary(self) -> Dict[str, Any]:
        """Serving-side rollup: throughput, latency percentiles, trust.

        Counters come from the terminal-status rollup and the latency
        percentiles from the streaming P² estimators — both exact/stable
        over EVERY request ever retired, regardless of how many finished
        records the bounded ``results`` ring still retains."""
        elapsed = (
            (time.perf_counter() - self._t_start)
            if self._t_start is not None else 0.0
        )
        totals = self.timer.span_totals()

        def share(name: str) -> float:
            """A phase's seconds over the wall since the first tick."""
            if name not in totals or elapsed <= 0:
                return 0.0
            return totals[name][1] / elapsed

        out: Dict[str, Any] = {
            "requests_completed": self._status_counts.get("completed", 0),
            "requests_deadline_exceeded":
                self._status_counts.get("deadline_exceeded", 0),
            "requests_rejected": self.rejected,
            "requests_shed_slo": self.shed_slo,
            "requests_flagged": self._flagged_total,
            "quarantined_slots": sorted(self.quarantined_slots),
            "tokens_emitted": self._tokens_emitted,
            "tokens_per_s":
                self._tokens_emitted / elapsed if elapsed > 0 else 0.0,
            "iterations": self._iteration,
            "peak_tokens_in_flight": self.peak_tokens_in_flight,
            "peak_active_requests": self.peak_active,
            # Decode-phase share of the serve wall: the number the perf
            # sentinel bands (a silent attention-path fallback inflates
            # it) and the gauge's companion.
            "decode_tick_fraction": share("serve.decode_tick"),
            "attn_kernel_path": self.attn_kernel_path,
            "attn_kernel_paths": self.attn_kernel_paths,
        }
        sched = self.scheduler
        out["blocks_in_use"] = sched.blocks_in_use
        # Phase-share companions to decode_tick_fraction for the
        # prefill and verify kernel arms: wall share spent advancing
        # prefill chunks / inside the batched spec verify (both
        # direction LOWER in the sentinel fingerprint — a kernel arm
        # that does not shrink them is a regression signal).
        out["prefill_chunk_fraction"] = share("serve.prefill_chunk.dispatch")
        out["spec_verify_fraction"] = share("serve.spec_verify")
        for name in DECODE_COUNTERS:
            out[name] = getattr(sched, name)
        out["tick_phases"] = self._phase_summary(totals)
        out["prefix_lookups"] = sched.prefix_lookups
        out["prefix_hits"] = sched.prefix_hits
        out["prefix_tokens_reused"] = sched.prefix_tokens_reused
        out["prefix_hit_rate"] = (
            sched.prefix_hits / sched.prefix_lookups
            if sched.prefix_lookups else 0.0
        )
        out["kv_pool_bytes"] = sched.kv.pool_bytes
        out["latent_pool_bytes"] = self.latent_pool_bytes
        out["state_pool_bytes"] = self.state_pool_bytes
        out["state_bytes_by_kind"] = dict(self.state_bytes_by_kind)
        experts = sched.expert_counters()
        if experts is not None:
            out["moe"] = self._expert_summary(experts)
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["spec_proposed"] = sched.spec_proposed
            out["spec_accepted"] = sched.spec_accepted
            out["accepted_rate"] = round(sched.accepted_rate, 4)
            out["spec_near_tie_flips"] = sched.spec_near_tie_flips
            out["spec_ticks"] = sched.spec_ticks
            out["spec_fallback_ticks"] = sched.spec_fallback_ticks
        if self.adapter_pool is not None:
            out["adapters"] = {
                "rank": self.adapter_rank,
                "dtype": self.adapter_dtype,
                **self.adapter_pool.metrics(),
            }
        for name, signal, est in (("itl", "itl_s", self._itl_est),
                                  ("ttft", "ttft_s", self._ttft_est)):
            if self.slo is not None:
                p50 = self.slo.quantile(signal, 0.5)
                p99 = self.slo.quantile(signal, 0.99)
            else:
                p50 = est.quantile(0.5) if est.count else None
                p99 = est.quantile(0.99) if est.count else None
            if p50 is not None:
                out[f"{name}_p50_ms"] = float(p50 * 1e3)
                out[f"{name}_p99_ms"] = float(p99 * 1e3)
        return out

    def _since_last_summary(self, what: str,
                            now: Dict[str, Sequence[float]]
                            ) -> Dict[str, List[float]]:
        """Running totals ``now`` (name -> numbers) less what the summary
        before this one saw of ``what`` (nothing, for the first): the ONE
        place "since the last summary" is reckoned, for the expert
        counters and the phases alike.  A caller that asks at a
        window's two ends gets the window."""
        seen = self._summary_seen.get(what, {})
        self._summary_seen[what] = now
        return {name: [a - b for a, b in
                       zip(values, seen.get(name) or [0] * len(values))]
                for name, values in now.items()}

    def _expert_summary(self, now: Dict[str, Any]) -> Dict[str, Any]:
        """The expert counters as a summary gives them: the running totals
        and, under ``since_last_summary``, what was counted since the
        summary before this one (the whole run for the first); both go to
        the registry too.  The device's token counter is an int32 that
        wraps, so its difference is taken modulo 2**32."""
        delta = self._since_last_summary("experts", {
            "held_expert_pairs": now["held_expert_pairs"],
            "tokens_fed": [now["tokens_fed"]]})
        since = {"held_expert_pairs": delta["held_expert_pairs"],
                 "tokens_fed": delta["tokens_fed"][0] % (1 << 32)}
        first = self.cfg.first_expert
        for scope, counts in (("total", now), (SINCE_LAST, since)):
            for i, n in enumerate(counts["held_expert_pairs"]):
                self._expert_pairs_gauge.set(
                    float(n), expert=str(first + i), scope=scope,
                    **self._rlabels)
            self._expert_tokens_gauge.set(
                float(counts["tokens_fed"]), scope=scope, **self._rlabels)
        return {"first_expert": first, **now, SINCE_LAST: since}

    def _phase_summary(self, totals: Dict[str, tuple]) -> Dict[str, Any]:
        """The timer's ``span_totals()`` by name, as count, seconds and
        the longest single interval, with what the timer tallied for the
        name beside them (``serve.prefill_chunk``: ``rows`` and
        ``padded``), and the same under ``since_last_summary``; both go to
        the registry too."""
        recent = self.timer.take_longest()
        tallies = self.timer.tallies()
        tallied = {name: tallies.get(name, {}) for name in totals}
        delta = self._since_last_summary(
            "phases", {name: [*t[:2], *tallied[name].values()]
                       for name, t in totals.items()})
        now = {name: (*t, *tallied[name].values())
               for name, t in totals.items()}
        since = {name: (*delta[name][:2], recent[name], *delta[name][2:])
                 for name in totals}
        for scope, by_name in (("total", now), (SINCE_LAST, since)):
            for name, values in by_name.items():
                for gauge, value in zip(self._phase_gauges, values):
                    gauge.set(float(value), phase=name, scope=scope,
                              **self._rlabels)
                for key, value in zip(tallied[name], values[3:]):
                    self._tally_gauge.set(float(value), phase=name, kind=key,
                                          scope=scope, **self._rlabels)

        def blocks(by_name: Dict[str, tuple]) -> Dict[str, Any]:
            return {name: {**dict(zip(PHASE_FIELDS, values)),
                           **dict(zip(tallied[name], values[3:]))}
                    for name, values in by_name.items()}

        return {**blocks(now), SINCE_LAST: blocks(since)}

    def analyze_programs(self, ledger: Any,
                         memory: Optional[bool] = None) -> Any:
        """Stamp this engine's serve programs (prefill/chunk/decode)
        into an ``obs.hbm.CostLedger`` — analyzed FLOPs and bytes per
        program, temp allocation too when ``memory`` (or
        ``TDDL_OBS_MEMORY_ANALYSIS=1``) is on.  Lowering-only by
        default: no extra backend compile, safe to call after a serve
        run on any engine."""
        self.scheduler.analyze_costs(ledger, memory=memory)
        return ledger

    def verify_attribution(self) -> "tuple[bool, list]":
        """Reconcile the attached ledger's records against the paged
        pool's block-lifecycle journal (obs.attribution) — the audit the
        serve-trust acceptance runs."""
        if self.ledger is None:
            raise ValueError("engine has no attribution ledger attached")
        return attribution.verify_attribution(self.ledger.records(),
                                              self.scheduler.blocks)
