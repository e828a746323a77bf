"""obs — unified telemetry: metrics registry, structured trace bus,
flight recorder, and the step-time/MFU reporter (SURVEY §5.1 tracing,
§6 measurement contract).

The framework trains, serves and self-heals; this package makes it
*explain itself*: every claim the repo publishes — detection overhead,
recovery counts, step-time, MFU — is backed by an emitted,
machine-readable record rather than a builder-transcribed number.

Four pieces, composable separately and bundled by :class:`ObsSession`:

* :mod:`obs.registry` — process-wide counters/gauges/histograms with
  labels, JSON snapshot + Prometheus text export.  Absorbs the ad-hoc
  metrics previously scattered over ``utils/metrics.py``,
  ``serve/engine.py`` (TTFT/ITL/occupancy), ``engine/supervisor.py``
  (retries/rollbacks/restarts) and ``chaos/injector.py`` (faults).
* :mod:`obs.events` — typed JSONL trace events with monotonic
  timestamps and step/request correlation ids, validated against a
  per-type schema.
* :mod:`obs.recorder` — a bounded ring buffer of recent events the
  supervisor dumps next to the checkpoint directory on rollback, guard
  trip or preemption, so every recovery has a post-mortem artifact.
* :mod:`obs.report` — named-phase step-time breakdown + model-FLOPs
  utilization (MFU), written as ``obs_report.json``; also the shared
  ``run_metadata()`` stamp every experiment artifact carries.

Since the active-plane PR the package also WATCHES what it records:

* :mod:`obs.spans` — hierarchical request/step spans emitted through the
  trace bus, exportable as a Chrome/Perfetto timeline;
* :mod:`obs.attribution` — the per-request attribution ledger
  (replica/slot/blocks/weight-tier/verdict per served stream) +
  ``verify_attribution`` against the block allocator's journal;
* :mod:`obs.slo` — bounded-memory P² percentile estimators and
  declarative target/window/burn-rate SLO rules
  (``tddl_slo_burn_rate{slo=}``);
* :mod:`obs.anomaly` — EWMA/z-score anomaly detection on step-time /
  loss / grad-norm / ITL (``tddl_anomaly_active{signal=}``), with
  flight-recorder dumps on breach/anomaly episodes.

Metric naming convention: ``tddl_<subsystem>_<what>[_unit]`` —
e.g. ``tddl_train_loss``, ``tddl_serve_ttft_seconds``,
``tddl_supervisor_rollbacks_total``.
"""

from trustworthy_dl_tpu.obs.anomaly import AnomalyWatcher, EwmaDetector
from trustworthy_dl_tpu.obs.attribution import (
    AttributionLedger,
    read_ledger,
    token_hash,
    verify_attribution,
)
from trustworthy_dl_tpu.obs.compilewatch import (
    CompileRegistry,
    CompileWatcher,
)
from trustworthy_dl_tpu.obs.events import (
    EVENT_SCHEMAS,
    EventType,
    TraceBus,
    read_jsonl_rotated,
)
from trustworthy_dl_tpu.obs.forensics import (
    IncidentAssembler,
    blast_radius,
    load_incidents,
)
from trustworthy_dl_tpu.obs.hbm import (
    CostLedger,
    HbmMonitor,
    analyze_program,
    live_buffer_bytes,
)
from trustworthy_dl_tpu.obs.sentinel import (
    PerfLedger,
    PerfSentinel,
    fingerprint as perf_fingerprint,
)
from trustworthy_dl_tpu.obs.meta import run_metadata
from trustworthy_dl_tpu.obs.recorder import FlightRecorder
from trustworthy_dl_tpu.obs.registry import (
    MetricsRegistry,
    get_registry,
)
from trustworthy_dl_tpu.obs.report import PHASES, StepTimeReporter, \
    peak_flops_per_chip
from trustworthy_dl_tpu.obs.session import ObsSession
from trustworthy_dl_tpu.obs.slo import (
    P2Quantile,
    SLORule,
    SLOWatcher,
    StreamingPercentiles,
    default_serve_rules,
)
from trustworthy_dl_tpu.obs.spans import (
    SpanTracker,
    chrome_trace_from_events,
)
from trustworthy_dl_tpu.obs.verdicts import VERDICT_OUTCOMES, VerdictStore

__all__ = [
    "AnomalyWatcher",
    "AttributionLedger",
    "CompileRegistry",
    "CompileWatcher",
    "CostLedger",
    "EVENT_SCHEMAS",
    "EventType",
    "EwmaDetector",
    "FlightRecorder",
    "HbmMonitor",
    "IncidentAssembler",
    "MetricsRegistry",
    "ObsSession",
    "P2Quantile",
    "PHASES",
    "PerfLedger",
    "PerfSentinel",
    "SLORule",
    "SLOWatcher",
    "SpanTracker",
    "StepTimeReporter",
    "StreamingPercentiles",
    "TraceBus",
    "VERDICT_OUTCOMES",
    "VerdictStore",
    "analyze_program",
    "blast_radius",
    "chrome_trace_from_events",
    "default_serve_rules",
    "get_registry",
    "live_buffer_bytes",
    "load_incidents",
    "peak_flops_per_chip",
    "perf_fingerprint",
    "read_jsonl_rotated",
    "read_ledger",
    "run_metadata",
    "token_hash",
    "verify_attribution",
]
