"""Configuration tree for the TPU-native framework.

Mirrors the reference's three config surfaces and unifies them (the reference
never unified its own: dataclasses at distributed_trainer.py:48-61 and
experiment_runner.py:31-46, a YAML schema documented only in README.md:111-132,
and an argparse CLI whose --config flag was parsed but ignored,
experiment_runner.py:605,613-623).  Here one dataclass tree backs all three,
and the YAML loader honours the README schema for real.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple


# Parallelism strategy names accepted by ``TrainingConfig.parallelism``.
#  - "data":     node axis = data shards, trust-gated gradient psum
#  - "model":    node axis = pipeline stages (the reference's only real
#                strategy, distributed_trainer.py:124-135)
#  - "tensor":   intra-layer sharding over a 'model' mesh axis (GSPMD)
#  - "sequence": sequence-dim sharding (Ulysses all_to_all / ring attention)
#  - "expert":   MoE expert-dim sharding over an 'expert' mesh axis
#  - "hybrid":   explicit mesh_shape dict combining several axes
PARALLELISM_MODES = ("data", "model", "tensor", "sequence", "expert", "hybrid")

#: Largest accepted ``ServeConfig.spec_k`` — the speculative draft depth
#: is a compile-time shape (one fixed-[R, k+1] verify program per
#: engine lifetime); acceptance rates past a handful of tokens decay
#: geometrically, so a deeper draft only burns verify FLOPs.
SPEC_K_MAX = 8


def validate_spec(spec_k: int, weight_dtype: str) -> None:
    """Loud construction-time validation of the speculative-decoding
    knob — shared by ``ServeConfig`` and the serving engine so a bad
    combination fails where the operator typed it.

    * ``spec_k`` must sit in [0, SPEC_K_MAX] (0 = disabled — the
      serve path is bit-for-bit today's).
    * the draft model IS the weight-only int8 tier, built automatically
      at engine construction; the verify pass is the MODEL-dtype tier —
      ``weight_dtype="int8"`` would collapse draft and verify onto the
      same weights (no cheap draft left, and fallback ticks would emit
      int8-decoded tokens inside a model-dtype-verified stream), so it
      is rejected loudly.
    """
    if not 0 <= int(spec_k) <= SPEC_K_MAX:
        raise ValueError(
            f"spec_k must be in [0, {SPEC_K_MAX}], got {spec_k}"
        )
    if spec_k > 0 and weight_dtype != "model":
        raise ValueError(
            f"spec_k > 0 requires weight_dtype='model' (got "
            f"{weight_dtype!r}): the int8 weight tier is the DRAFT — "
            "it is built automatically — and the verify pass must be "
            "the model-dtype tier, or draft and verify would share one "
            "set of weights"
        )


@dataclass
class NodeConfig:
    """Per-node configuration (reference: distributed_trainer.py:37-46).

    On TPU a "node" is a mesh coordinate; ``device_id`` generalises the
    reference's ``gpu_id``.
    """

    node_id: int
    rank: int
    world_size: int
    device_id: int = 0
    model_partition: str = ""
    trust_score: float = 1.0
    status: str = "trusted"

    # Back-compat alias for the reference's field name.
    @property
    def gpu_id(self) -> int:
        return self.device_id


@dataclass
class TrainingConfig:
    """Training configuration (reference: distributed_trainer.py:48-61,
    extended with the TPU execution knobs the reference never had)."""

    model_name: str = "gpt2"
    dataset_name: str = "openwebtext"
    batch_size: int = 32
    learning_rate: float = 5e-5
    num_epochs: int = 10
    num_nodes: int = 4
    trust_threshold: float = 0.7
    attack_detection_enabled: bool = True
    gradient_verification_enabled: bool = True
    checkpoint_interval: int = 100
    max_reassignment_attempts: int = 3

    # ---- TPU-native execution knobs (no reference equivalent) ----
    parallelism: str = "data"          # one of PARALLELISM_MODES
    mesh_shape: Optional[Dict[str, int]] = None  # for "hybrid" (within-slice)
    # Across-slice (DCN) extents for multi-slice pods: {axis: n_slices}.
    # Axes listed here parallelise over DCN; all others stay on ICI.
    dcn_mesh_shape: Optional[Dict[str, int]] = None
    # Pipeline schedule depth; 0 = auto (largest M dividing the
    # per-replica-row batch, capped at 4*S — the measured sweet spot of
    # experiments/pipeline_schedule_study: bubble (S-1)/(M+S-1) falls
    # with M, marginal gain < ~6 % past 4*S).
    num_microbatches: int = 0
    # Gradient accumulation (data-parallel modes): each node's batch is
    # processed in this many sequential microbatches inside the step
    # (lax.scan), averaging the gradients — activation memory shrinks by
    # the same factor, so effective batches grow without remat/chunking.
    # Detector semantics: batteries run on the ACCUMULATED gradient (what
    # is aggregated); output stats ride the last microbatch's features.
    grad_accum_steps: int = 1
    dtype: str = "bfloat16"            # compute dtype (params stay f32)
    seed: int = 0
    remat: bool = False                # jax.checkpoint the blocks
    # Trust/detector timing: the reference decays trust by wall-clock seconds
    # (trust_manager.py:113-114); inside a compiled step we use
    # step_count * time_per_step as the clock so the math stays pure.
    time_per_step: float = 1.0
    # Remat granularity when ``remat`` is set: "block" (whole transformer
    # block) or "attention" (only the O(T²) attention core recomputes;
    # falls back to block for non-"full" attention impls).
    remat_policy: str = "block"
    # Exact order statistics (median/percentiles) cost a sort on TPU
    # (attack_detector.py:190-196 computes them on host numpy); disable to
    # trade fidelity for speed — see SURVEY §7.4(2).
    exact_order_stats: bool = True
    detector_history: int = 1000       # rolling window (attack_detector.py:44)
    # Input-pipeline double buffering: batch k+1 assembles on the host
    # (native gathers) while batch k trains on device.  0 disables.
    prefetch_depth: int = 2
    detector_warmup: int = 10          # min history before verdicts (:91,:126)
    # Async host pipeline (engine/async_host.py): keep up to this many
    # steps in flight — each step's host-facing metrics are packed into ONE
    # flat device array whose device→host copy starts asynchronously, and
    # the host bookkeeping (detector history feed, trust mirror, incident
    # records, step guard) drains up to this many steps behind the
    # dispatch frontier, so the accelerator never idles waiting for Python.
    # 0 = fully synchronous (the pre-pipeline behavior: every step blocks
    # on ~10 separate device→host pulls before the next dispatch).
    # Semantics at depth K>0 are identical on the healthy path (same
    # losses/trust/incidents, just observed up to K steps late); supervisor
    # guard trips within the in-flight window roll back to the newest
    # verified checkpoint, which by construction predates the window
    # (checkpoint saves force a full drain first) — see README
    # §Performance.  Deterministic chaos drills that assert exact retry
    # counts (FaultPlan.predict) must run at depth 0: the lagged guard
    # skips in-place retries.
    async_host_depth: int = 2
    # Epoch-cadence host intelligence — the reference defined these but never
    # called them (SURVEY §7.5: trust_manager.py:333; attack_detector.py:381).
    adaptive_thresholds: bool = True   # trust_manager.adaptive_threshold_adjustment
    ml_detectors: bool = True          # attack_detector.update_detection_models
    # Pipeline-mode canary probe length (per-stage Byzantine/backdoor
    # reference signal, SURVEY §7.4(4)).
    canary_tokens: int = 8
    # Profiling/debug subsystems (SURVEY §5.1, §5.2 — absent in the
    # reference).  profile_dir: jax.profiler traces of training (viewable in
    # TensorBoard/Perfetto) with per-step annotations.  debug_nans: trap the
    # first NaN-producing primitive (developer mode; adversarial NaNs are
    # normally gated in-step by the verifier instead).
    profile_dir: Optional[str] = None
    debug_nans: bool = False
    # TensorBoard event-file export of batch/epoch metrics (the reference
    # pinned tensorboard in requirements but never wrote an event).
    tensorboard_dir: Optional[str] = None
    # Vocab-chunked fused lm-head+cross-entropy (ops/fused_ce.py): the LM
    # loss never materialises the [B, T, V] logits — removes the dominant
    # HBM tensor of the loss step and unlocks larger per-chip batches.
    # -1 (default) leaves the model's "auto" per-shape dispatch in charge
    # (gpt2.resolve_lm_head_chunk); 0 forces the materialised-logits CE;
    # >0 forces chunking at that width (multiple of 128 for MXU tiling,
    # typical 8192).
    lm_head_chunk: int = -1
    # ZeRO-1-style optimizer-state sharding over the data axis (data
    # parallelism only).  Pure GSPMD annotation: the Adam moments shard
    # across the data devices, XLA partitions the update computation and
    # gathers the params — identical numerics, ~(1 - 1/n_data) of the
    # moment memory reclaimed per chip.
    shard_opt_state: bool = False
    # FSDP/ZeRO-3-style PARAMETER sharding over the data axis (data
    # parallelism only), via the same registry rule as shard_opt_state
    # (core/sharding.py:place_zero_sharded): each weight's first evenly-
    # divisible dim shards across the data devices and GSPMD gathers it
    # where the forward needs it — ~1/n_data of the param bytes resident
    # per chip.  Composes with shard_opt_state; identical numerics.
    shard_params: bool = False
    # Storage dtype for the optimizer's FIRST moment (optax mu_dtype;
    # SGD's momentum accumulator).  None keeps the parameter dtype (f32);
    # "bfloat16" frees 2 bytes/param.  The second moment stays f32; for
    # the big second-moment saving use optimizer="adafactor".
    moment_dtype: Optional[str] = None
    checkpoint_dir: str = "checkpoints"
    # Async checkpointing: save() returns after the device→host snapshot;
    # disk serialisation overlaps the next training steps (Orbax async
    # path).  cleanup()/restore join any in-flight write.
    async_checkpoint: bool = False
    # Migration-time model rate for reassignment estimates.  The reference
    # hardcodes 1 GB/s (distributed_trainer.py:360); on TPU the transfer
    # rides ICI, so measure and override (elastic/reassignment.py).
    migration_gbps: float = 1.0
    # Real elastic eviction (elastic/reassignment.py): on a confirmed
    # compromise, remove the node's mesh coordinate, migrate state to the
    # surviving devices and re-jit.  Off by default: the in-step trust gate
    # already neutralises the node immediately; eviction additionally
    # reclaims its device at the cost of a recompile.
    elastic_resharding: bool = False
    # Recovery / readmission (trust_manager.py:198-206 semantics, wired
    # into the engine — the reference exposed initiate_recovery but no
    # path ever called it).  A confirmed-compromised (hard-gated, NOT
    # evicted) node that produces this many consecutive clean steps
    # transitions COMPROMISED -> RECOVERING in-step (boosted recovery
    # rate, weight restored); 0 disables the probation path.
    recovery_probation_steps: int = 25
    # Elastic-readmission: an evicted mesh coordinate is re-admitted
    # (device restored to the mesh, fresh detector rows, RECOVERING
    # status) this many steps after its eviction.  0 disables — an
    # eviction is then permanent, and a false positive costs 1/n of the
    # fleet for the rest of the run.
    readmit_after_steps: int = 0
    # Optimizer
    optimizer: str = "adamw"
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.0        # 0 disables
    # LR schedule — the reference steps a torch scheduler once per epoch
    # (distributed_trainer.py:478-489) but never constructs one; here the
    # schedule is a real optax schedule evaluated per step inside the
    # compiled update.  "constant" | "cosine" | "linear"; warmup_steps
    # prepends a linear ramp from 0.  lr_decay_steps sets the decay
    # horizon (0 → num_epochs is unknown at build time, stay constant
    # after warmup).
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    lr_decay_steps: int = 0
    min_lr_ratio: float = 0.0          # floor as a fraction of peak LR
    # Trust dynamics (trust_manager.py:31-32,49-54; README.md:72-74 uses
    # 0.1/0.05 — we expose both, defaulting to the code's values per SURVEY
    # §7.5).
    initial_trust: float = 1.0
    trust_decay_rate: float = 0.01
    trust_recovery_rate: float = 0.005
    trust_alpha: float = 0.1           # EMA learning rate (trust_manager.py:117)

    def __post_init__(self) -> None:
        if self.parallelism not in PARALLELISM_MODES:
            raise ValueError(
                f"parallelism must be one of {PARALLELISM_MODES}, "
                f"got {self.parallelism!r}"
            )
        if self.remat_policy not in ("block", "attention"):
            raise ValueError(
                "remat_policy must be 'block' or 'attention', "
                f"got {self.remat_policy!r}"
            )
        if self.async_host_depth < 0:
            raise ValueError(
                "async_host_depth must be >= 0 (0 = synchronous), "
                f"got {self.async_host_depth}"
            )


@dataclass
class ExperimentConfig:
    """Experiment configuration (reference: experiment_runner.py:31-46)."""

    experiment_name: str
    model_name: str = "gpt2"
    dataset_name: str = "openwebtext"
    num_nodes: int = 4
    num_epochs: int = 10
    batch_size: int = 32
    learning_rate: float = 5e-5
    attack_enabled: bool = True
    attack_start_epoch: int = 2
    # Transient attacks: deactivate injection from this epoch on (None =
    # sustained for the rest of the run) — the vehicle for recovery /
    # readmission experiments.
    attack_end_epoch: Optional[int] = None
    attack_intensity: float = 0.5
    trust_threshold: float = 0.7
    save_interval: int = 100
    output_dir: str = "results"
    # TPU extensions
    parallelism: str = "data"
    steps_per_epoch: int = 50
    seed: int = 0
    attack_types: List[str] = field(
        default_factory=lambda: ["gradient_poisoning", "data_poisoning"]
    )
    # The reference hardcodes nodes [1, 3] (experiment_runner.py:93).
    target_nodes: List[int] = field(default_factory=lambda: [1, 3])
    num_microbatches: int = 0  # 0 = auto (see TrainingConfig)
    # Elastic / recovery knobs forwarded to the trainer (recovery
    # experiments: transient attack -> eviction -> readmission).
    elastic_resharding: bool = False
    readmit_after_steps: int = 0
    recovery_probation_steps: int = 25

    def to_training_config(self) -> TrainingConfig:
        """Build the trainer config the way the reference runner does
        (experiment_runner.py:66-75)."""
        return TrainingConfig(
            model_name=self.model_name,
            dataset_name=self.dataset_name,
            batch_size=self.batch_size,
            learning_rate=self.learning_rate,
            num_epochs=self.num_epochs,
            num_nodes=self.num_nodes,
            trust_threshold=self.trust_threshold,
            parallelism=self.parallelism,
            num_microbatches=self.num_microbatches,
            seed=self.seed,
            elastic_resharding=self.elastic_resharding,
            readmit_after_steps=self.readmit_after_steps,
            recovery_probation_steps=self.recovery_probation_steps,
        )


def validate_adapters(adapter_rank: int,
                      adapter_pool_pages: Optional[int],
                      adapter_dtype: str, spec_k: int) -> None:
    """Loud construction-time validation of the adapter-tier knobs —
    shared by ``ServeConfig`` and ``serve.adapters`` so a bad
    combination fails where the operator typed it.

    * ``adapter_rank`` must be >= 0 (0 = disabled: the serve programs
      keep their adapter-free signatures, bit-for-bit today's output).
    * ``spec_k`` > 0 is rejected: the int8 draft model carries no
      adapter deltas, so draft and verify would diverge on every
      adapter-carrying request and speculation would never accept.
    * ``adapter_dtype`` must be "model" or "int8".
    * ``adapter_pool_pages`` (when given) must be >= 1 usable page.
    """
    if adapter_rank < 0:
        raise ValueError(
            f"adapter_rank must be >= 0 (0 disables), got {adapter_rank}"
        )
    if adapter_rank == 0:
        return
    if spec_k > 0:
        raise ValueError(
            "adapter_rank > 0 is incompatible with spec_k > 0: the int8 "
            "draft model carries no adapter deltas, so draft and verify "
            "would diverge on every adapter-carrying request"
        )
    if adapter_dtype not in ("model", "int8"):
        raise ValueError(
            f"adapter_dtype must be 'model' or 'int8', got "
            f"{adapter_dtype!r}"
        )
    if adapter_pool_pages is not None and adapter_pool_pages < 1:
        raise ValueError(
            f"adapter_pool_pages must be >= 1 (or None = max_slots), "
            f"got {adapter_pool_pages}"
        )


@dataclass
class ServeConfig:
    """Serving-engine configuration (serve/engine.py).

    The quantization knobs select the KV-pool storage dtype and the
    decode weight tier (quant/int8.py):

    * ``kv_dtype``: "model" (follow the model compute dtype — the
      pre-quantization behaviour), "bfloat16", "float32", or "int8"
      (per-(head, position) scaled int8 — roughly half the KV bytes per
      slot, so ~2x the slot pool at fixed HBM; parity-gated at engine
      construction with automatic fallback to "model").
    * ``weight_dtype``: "model" or "int8" (weight-only int8 for the
      decode matmuls; embedding/lm-head stay high precision).

    The pool knobs size the one KV layout, block-pooled KV with
    per-slot block tables — occupancy bounded by tokens in flight, not
    request count (README §Serving):

    * ``block_size``: token positions per block (``max_seq`` must be a
      multiple).
    * ``num_blocks``: usable pool blocks; ``None`` sizes the pool so
      every one of ``max_slots`` can hold a full ``max_seq`` sequence.
    * ``prefix_cache``: radix prefix cache — requests sharing a prompt
      prefix reuse already-filled blocks copy-on-write.
    * ``prefill_chunk``: positions fed per chunked-prefill tick (a
      multiple of ``block_size``); ``None`` auto-sizes.

    ``spec_k`` enables self-speculative decoding (README §Serving
    /"Speculative decoding"): per decode tick the engine drafts
    ``spec_k`` tokens per active slot with the int8 weight tier (built
    automatically as the draft model), verifies them all in ONE batched
    model-dtype forward over the same paged cache, accepts the longest
    draft/target-matching prefix (a greedy near-tie flip under the
    parity-probe margin is tolerated and emits the DRAFT token — the
    one counted departure from spec-off bit-parity), and rolls back
    rejected draft KV by COW refcount decrement.  0 (default) disables
    — the serve path is bit-for-bit today's; ``spec_k`` > 0 requires
    ``weight_dtype="model"`` (:func:`validate_spec`).

    Unknown dtype strings and bad paged geometry fail HERE, at
    construction — never at trace time inside a jitted serving program.
    """

    max_slots: int = 8
    max_seq: int = 256
    queue_limit: int = 64
    kv_dtype: str = "model"
    weight_dtype: str = "model"
    block_size: int = 16
    num_blocks: Optional[int] = None
    prefix_cache: bool = True
    prefill_chunk: Optional[int] = None
    spec_k: int = 0
    # Decode-attention path: "auto" resolves through
    # the shared Pallas gate (TDDL_PAGED_ATTN; kernel on TPU, jnp gather
    # fallback elsewhere), "pallas"/"interpret"/"jnp" force a path —
    # README §Serving/"Decode attention kernel".
    attn_impl: str = "auto"
    # Multi-tenant adapter tier (serve/adapters.py; README §Adapters):
    # per-tenant rank-r low-rank A/B deltas on the attention out
    # projection + the MLP, stored in a SECOND paged HBM pool keyed by
    # a traced per-slot adapter-page table, so tenant mix / adapter
    # churn never recompiles the decode/prefill programs.
    #
    # * ``adapter_rank``: the low-rank width r; 0 (default) disables —
    #   the serve path is bit-for-bit today's (the adapter arguments
    #   stay structurally absent from every program signature).
    # * ``adapter_pool_pages``: usable adapter pages (resident tenants);
    #   None sizes the pool to ``max_slots`` (every slot could carry a
    #   distinct adapter).  One extra reserved zero page (page 0) always
    #   exists — the adapter-off slot's identity delta.
    # * ``adapter_dtype``: "model" stores deltas in the model compute
    #   dtype; "int8" stores symmetric-quantized int8 A/B with per-
    #   (layer, page, site) scales, dequantized in-register inside the
    #   low-rank matmul (ops/fused_dequant_matmul.py's template).
    adapter_rank: int = 0
    adapter_pool_pages: Optional[int] = None
    adapter_dtype: str = "model"
    # Tensor-parallel replica width: the engine owns a tp_size-device
    # submesh over the 'model' axis and the weights carry the model's
    # registry-declared TP layout (core/sharding.py) — the KV pool's
    # heads shard with them, so the HBM headroom gate sizes the pool
    # per SHARD.  1 (default) is byte-for-byte the single-chip engine.
    tp_size: int = 1

    def __post_init__(self) -> None:
        from trustworthy_dl_tpu.quant import validate_dtypes
        from trustworthy_dl_tpu.serve.kv_slots import validate_paged_geometry

        validate_dtypes(self.kv_dtype, self.weight_dtype)
        if self.tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {self.tp_size}")
        if self.attn_impl not in ("auto", "pallas", "interpret", "jnp"):
            # Mirrors ops.paged_attention.ATTN_IMPLS — checked here with
            # a literal so a bad knob fails without touching jax.
            raise ValueError(
                f"attn_impl must be one of ('auto', 'pallas', "
                f"'interpret', 'jnp'), got {self.attn_impl!r}"
            )
        validate_spec(self.spec_k, self.weight_dtype)
        validate_adapters(self.adapter_rank, self.adapter_pool_pages,
                          self.adapter_dtype, self.spec_k)
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")
        validate_paged_geometry(self.max_seq, self.block_size,
                                self.num_blocks, self.prefill_chunk)


@dataclass
class AttackConfig:
    """Adversarial attack configuration (implied module; call sites at
    experiment_runner.py:90-97)."""

    attack_types: List[str] = field(
        default_factory=lambda: ["gradient_poisoning", "data_poisoning"]
    )
    target_nodes: List[int] = field(default_factory=lambda: [1, 3])
    intensity: float = 0.5
    start_step: int = 200
    seed: int = 0
    # Adaptive-adversary knobs: slow-boil intensity ramp (added per
    # attacked step on top of `intensity`) and colluding coordination
    # (all attackers submit the same perturbation direction).
    intensity_ramp: float = 0.0
    collude: bool = False


# ---------------------------------------------------------------------------
# YAML loading — honours the README schema (README.md:111-132):
#   model: {name, size}
#   training: {batch_size, learning_rate, num_epochs}
#   distributed: {num_nodes, parallelism}
#   security: {trust_threshold, attack_detection, gradient_verification}
# Flat keys matching TrainingConfig fields are also accepted, and flag-style
# overrides win over file values (fixing the reference's ignored --config).
# ---------------------------------------------------------------------------

_MODEL_SIZE_SUFFIX = {"small": "", "medium": "-medium", "large": "-large", "xl": "-xl"}


def _config_from_mapping(raw: Dict[str, Any]) -> Dict[str, Any]:
    """Flatten the README-schema nested mapping into TrainingConfig kwargs."""
    out: Dict[str, Any] = {}
    model = raw.get("model", {})
    if isinstance(model, dict):
        name = model.get("name")
        if name:
            size = str(model.get("size", "")).lower()
            suffix = _MODEL_SIZE_SUFFIX.get(size, "")
            out["model_name"] = f"{name}{suffix}" if name.startswith("gpt") else name
    training = raw.get("training", {})
    if isinstance(training, dict):
        for key in ("batch_size", "learning_rate", "num_epochs",
                    "lr_schedule", "warmup_steps", "lr_decay_steps",
                    "min_lr_ratio", "optimizer", "weight_decay",
                    "grad_clip_norm"):
            if key in training:
                out[key] = training[key]
    distributed = raw.get("distributed", {})
    if isinstance(distributed, dict):
        if "num_nodes" in distributed:
            out["num_nodes"] = distributed["num_nodes"]
        if "parallelism" in distributed:
            out["parallelism"] = distributed["parallelism"]
        if "mesh_shape" in distributed:
            out["mesh_shape"] = dict(distributed["mesh_shape"])
        if "dcn_mesh_shape" in distributed:
            out["dcn_mesh_shape"] = dict(distributed["dcn_mesh_shape"])
        if "num_microbatches" in distributed:
            out["num_microbatches"] = distributed["num_microbatches"]
    security = raw.get("security", {})
    if isinstance(security, dict):
        if "trust_threshold" in security:
            out["trust_threshold"] = security["trust_threshold"]
        if "attack_detection" in security:
            out["attack_detection_enabled"] = bool(security["attack_detection"])
        if "gradient_verification" in security:
            out["gradient_verification_enabled"] = bool(
                security["gradient_verification"]
            )
    if "dataset" in raw:
        out["dataset_name"] = raw["dataset"]
    # Flat TrainingConfig field names pass straight through.
    valid = {f.name for f in dataclasses.fields(TrainingConfig)}
    for key, value in raw.items():
        if key in valid:
            out[key] = value
    return out


def _load_mapping(path: str) -> Dict[str, Any]:
    """Parse a YAML (or JSON) config file to a mapping."""
    import json

    with open(path) as f:
        text = f.read()
    raw: Optional[Dict[str, Any]] = None
    try:
        import yaml  # type: ignore

        raw = yaml.safe_load(text)
    except ImportError:
        try:
            raw = json.loads(text)
        except json.JSONDecodeError as e:
            raise RuntimeError(
                f"pyyaml unavailable and {path} is not JSON: {e}"
            ) from e
    if not isinstance(raw, dict):
        raise ValueError(f"config file {path} did not parse to a mapping")
    return raw


def load_config(path: str, **overrides: Any) -> TrainingConfig:
    """Load a TrainingConfig from a YAML (or JSON) file.

    ``overrides`` (e.g. CLI flags) take precedence over file values — the
    behaviour the reference documented but never implemented
    (experiment_runner.py:605,613-623).
    """
    kwargs = _config_from_mapping(_load_mapping(path))
    kwargs.update({k: v for k, v in overrides.items() if v is not None})
    return TrainingConfig(**kwargs)


def load_experiment_config(path: str, **overrides: Any) -> ExperimentConfig:
    """Load an ExperimentConfig from a YAML/JSON file.

    Accepts both the nested README schema (README.md:111-132 — shared with
    ``load_config``) and flat ExperimentConfig field names; unknown keys are
    ignored rather than raising, so a single config file can feed both
    console scripts.  Flag overrides win over file values.
    """
    raw = _load_mapping(path)
    flat = _config_from_mapping(raw)
    valid = {f.name for f in dataclasses.fields(ExperimentConfig)}
    kwargs = {k: v for k, v in flat.items() if k in valid}
    for key, value in raw.items():
        if key in valid:
            kwargs[key] = value
    kwargs.update({k: v for k, v in overrides.items() if v is not None})
    kwargs.setdefault("experiment_name", "experiment")
    return ExperimentConfig(**kwargs)
