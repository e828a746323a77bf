"""Trust-aware TPU-native inference serving (beyond-reference).

The framework trains and batch-samples (models/generate.py) but the ROADMAP
north star — heavy traffic from millions of users — needs a *serving* path:
concurrent requests with heterogeneous prompt/output lengths, admitted and
retired mid-flight without recompiles.  This package is the Orca/vLLM-style
answer, shaped for XLA's static-shape world:

* ``kv_slots``  — the KV memory pool: fixed-size token blocks
  [L, NB+1, BLOCK, H·Dh] (a position's heads in one row: the shape the
  row write, the kernels and the resting layout agree on, so a serving
  program never copies the pool) + host-side block tables/refcounts + radix
  prefix cache, vLLM/RadixAttention-style, so occupancy is bounded by
  tokens in flight, not requests; no dynamic shapes anywhere — block
  tables are traced gather indices.
* ``scheduler`` — continuous (iteration-level) batching: chunked prefill
  interleaved with ONE fused decode step for all active slots,
  mid-flight retirement and slot/block reuse.
* ``engine``    — request lifecycle (queue → prefill → decode → stream),
  deadlines, backpressure, serving metrics (TTFT / ITL / tokens/s / slot
  occupancy), and trust-aware output monitoring: per-request logit
  entropy / top-1 margin z-scored against a rolling baseline, with
  anomalous generations quarantining the issuing slot — the inference
  mirror of the training-side trust state machine.

* ``fleet``     — the robustness layer (README §Fleet): N engine
  replicas behind one ``submit()`` with a per-replica lifecycle state
  machine (healthy → degraded → draining → quarantined → restarting)
  driven by the obs signals, request fail-over with bounded retries +
  hedged duplicates (dedup-at-retire), trust-aware routing/drain, and
  seeded REPLICA_* chaos drills with ``predict_fleet()``-pinned
  outcomes.
* ``workload``  — seeded traffic generator (bursty arrivals,
  heavy-tailed prompt/output lengths, tenant priority skew) for the
  CLI's ``serve --fleet-replicas`` path and the fleet drills.

The int8 quantization tier (``quant/``, ``ServeConfig.kv_dtype`` /
``weight_dtype``) roughly halves KV bytes per slot (per-(head, position)
scaled int8 K/V — ~2x the slot pool at fixed HBM) and the decode weight
stream (weight-only int8); the KV swap is parity-gated at engine
construction with automatic fallback to the model-dtype pool (README
§Serving/Quantization).
"""

from trustworthy_dl_tpu.core.config import ServeConfig
from trustworthy_dl_tpu.serve.control import (
    DEFAULT_SLO_CLASSES,
    AutoscalerConfig,
    PredictiveArmConfig,
    SLOClass,
    TenantQuotaConfig,
)
from trustworthy_dl_tpu.serve.engine import (
    OutputMonitor,
    ServeRequest,
    ServeResult,
    ServingEngine,
)
from trustworthy_dl_tpu.serve.fleet import (
    FleetConfig,
    FleetResult,
    ReplicaState,
    ServingFleet,
    backoff_ticks,
)
from trustworthy_dl_tpu.serve.workload import (
    Tenant,
    WorkloadConfig,
    WorkloadItem,
    drive_closed_loop,
    generate_workload,
    replay_workload,
)
from trustworthy_dl_tpu.serve.kv_slots import (
    BlockAllocator,
    PagedKV,
    PrefixCache,
    SlotAllocator,
    init_paged_pool,
    kv_bytes_per_token,
    paged_pool_blocks,
)
from trustworthy_dl_tpu.serve.scheduler import PagedBatchingScheduler

__all__ = [
    "AutoscalerConfig",
    "BlockAllocator",
    "DEFAULT_SLO_CLASSES",
    "FleetConfig",
    "FleetResult",
    "OutputMonitor",
    "PagedBatchingScheduler",
    "PagedKV",
    "PredictiveArmConfig",
    "PrefixCache",
    "ReplicaState",
    "SLOClass",
    "ServeConfig",
    "ServeRequest",
    "ServeResult",
    "ServingEngine",
    "ServingFleet",
    "SlotAllocator",
    "Tenant",
    "TenantQuotaConfig",
    "WorkloadConfig",
    "WorkloadItem",
    "backoff_ticks",
    "drive_closed_loop",
    "generate_workload",
    "init_paged_pool",
    "kv_bytes_per_token",
    "paged_pool_blocks",
    "replay_workload",
]
