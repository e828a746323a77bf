"""DistributedTrainer — the L4 orchestrator, TPU-native.

API parity with the reference trainer (distributed_trainer.py:63-527):
``train`` / ``train_epoch`` / ``validate`` / ``get_training_stats`` /
``save_checkpoint`` / ``load_checkpoint`` (new — the reference had no load
path) / ``cleanup``, the same host-facing component objects (TrustManager,
NodeMonitor, GradientVerifier, AttackDetector, MetricsCollector), and the
same attack/reassignment bookkeeping.

Execution is re-designed: instead of a sequential Python loop over node
partitions (:148-175), every batch runs one jitted SPMD step
(engine/step.py) over a device mesh; the host loop only feeds batches,
reacts to verdicts (recording attack/reassignment history, flipping the
TrainingState machine) and syncs reporting state at epoch cadence.
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import logging
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from trustworthy_dl_tpu.attacks.adversarial import AttackPlan, null_plan
from trustworthy_dl_tpu.core import sharding as shreg
from trustworthy_dl_tpu.core.config import NodeConfig, TrainingConfig
from trustworthy_dl_tpu.core.mesh import DATA_AXIS, STAGE_AXIS, \
    bind_mode_mesh, build_mesh
from trustworthy_dl_tpu.data.loader import PrefetchLoader
from trustworthy_dl_tpu.detect.detector import AttackDetector, AttackType
from trustworthy_dl_tpu.detect.stats import (
    GRADIENT_STAT_NAMES,
    NUM_TENSOR_STATS,
    TENSOR_STAT_NAMES,
)
from trustworthy_dl_tpu.detect.verifier import FleetEpisodeTracker, \
    GradientVerifier
from trustworthy_dl_tpu.engine.checkpoint import CheckpointManager
from trustworthy_dl_tpu.engine.optimizer import build_optimizer
from trustworthy_dl_tpu.engine.state import TrainState, \
    fleet_scalar_fields, init_train_state
from trustworthy_dl_tpu.engine.step import StepMetrics, \
    build_node_eval_step, \
    build_train_step
from trustworthy_dl_tpu.models.factory import ModelFactory
from trustworthy_dl_tpu.obs.compilewatch import guarded
from trustworthy_dl_tpu.obs.events import EventType
from trustworthy_dl_tpu.trust.manager import TrustManager
from trustworthy_dl_tpu.trust.state import NodeStatus
from trustworthy_dl_tpu.utils.metrics import MetricsCollector
from trustworthy_dl_tpu.utils.monitor import NodeMonitor
from trustworthy_dl_tpu.utils.profiling import enable_nan_debugging, \
    span, step_annotation, trace

logger = logging.getLogger(__name__)

#: What every dispatch but a built step's first is wrapped in (reusable).
_NO_SPAN = contextlib.nullcontext()


def _sklearn_available() -> bool:
    try:
        import sklearn  # noqa: F401
        return True
    except ImportError:
        return False


class TrainingState(enum.Enum):
    """Trainer lifecycle (distributed_trainer.py:30-35)."""

    INITIALIZING = "initializing"
    TRAINING = "training"
    UNDER_ATTACK = "under_attack"
    RECOVERING = "recovering"
    COMPLETED = "completed"


class DistributedTrainer:
    """Main distributed training orchestrator with adversarial attack
    mitigation."""

    @span("setup.trainer_init")
    def __init__(self, config: TrainingConfig,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 model_overrides: Optional[Dict[str, Any]] = None):
        self.config = config
        self.training_state = TrainingState.INITIALIZING
        if config.debug_nans:
            enable_nan_debugging()

        # Epoch-cadence ML tier, gated once on sklearn availability:
        # without it the refit is a permanent no-op, so the per-step
        # battery feed (device->host transfers + dict building on the hot
        # path) would be pure waste.
        self._ml_enabled = config.ml_detectors and _sklearn_available()
        # Fleet size the jitted steps are built for (reset_for_run guard).
        self._constructed_num_nodes = config.num_nodes
        with span("setup.trainer_init.host_state"):
            self._init_host_state()

        # Model / optimizer / mesh / step.
        model_overrides = dict(model_overrides or {})
        if config.parallelism == "sequence" and config.model_name.startswith(
            "gpt"
        ):
            model_overrides.setdefault("attn_impl", "ring")
        if config.lm_head_chunk >= 0 and config.model_name.startswith("gpt"):
            # -1 = model default ("auto" dispatch); 0 = force materialised;
            # >0 = force that chunk width.
            model_overrides.setdefault("lm_head_chunk", config.lm_head_chunk)
        if config.model_name.startswith("gpt"):
            if config.remat:
                model_overrides.setdefault("remat", True)
                model_overrides.setdefault("remat_policy",
                                           config.remat_policy)
        self.model = ModelFactory().create_model(
            config.model_name, **model_overrides
        )
        self.optimizer = build_optimizer(config)
        self.mesh = mesh if mesh is not None else build_mesh(
            config.num_nodes, config.parallelism, config.mesh_shape,
            dcn_mesh_shape=config.dcn_mesh_shape,
        )
        bind_mode_mesh(self.mesh, config.parallelism)
        if config.parallelism == "expert" and \
                "-moe" not in self.config.model_name:
            logger.warning(
                "parallelism='expert' with non-MoE model %r: the "
                "'expert' mesh axis will carry no sharded computation",
                self.config.model_name,
            )
        if config.parallelism == "model" and config.num_microbatches == 0:
            # Auto schedule depth.  Resolve into a COPY: the trainer owns
            # (and mutates) its config, but the caller's object must stay
            # pristine — a second trainer built from it (different mesh,
            # different dp) needs the 0 sentinel intact to re-resolve.
            from trustworthy_dl_tpu.parallel.pipeline import (
                choose_num_microbatches,
            )

            self.config = dataclasses.replace(
                config,
                num_microbatches=choose_num_microbatches(
                    config.batch_size, config.num_nodes,
                    self.mesh.shape.get(DATA_AXIS, 1),
                ),
            )
        self._build_steps()
        self.checkpointer = CheckpointManager(config.checkpoint_dir)

        self.state: Optional[TrainState] = None
        logger.info(
            "Initialized DistributedTrainer with %d nodes (%s parallelism, "
            "mesh %s)", config.num_nodes, config.parallelism,
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
        )

    # ------------------------------------------------------------------
    # Setup
    # ------------------------------------------------------------------

    @span("setup.build_steps")
    def _build_steps(self) -> None:
        """(Re)jit the train and eval steps for the CURRENT model, config
        and mesh — the constructor's spelling, shared by every site that
        changes the topology (elastic eviction and readmission, pipeline
        restaff, checkpoint topology adoption).  The steps are traced
        ``for_mesh``: over more than one device GSPMD partitions them, and
        a partitioned program cannot hold a compiled Mosaic kernel (ops/)."""
        from trustworthy_dl_tpu.ops import for_mesh

        if self.config.parallelism == "model":
            from trustworthy_dl_tpu.parallel.pipeline import (
                build_pipeline_eval_step,
                build_pipeline_train_step,
            )

            train = build_pipeline_train_step(self.model, self.config,
                                              self.optimizer, self.mesh)
            evaluate = build_pipeline_eval_step(self.model, self.config,
                                                self.mesh)
        else:
            train = build_train_step(self.model, self.config,
                                     self.optimizer)
            evaluate = build_node_eval_step(self.model)
        if self.mesh.size > 1 and jax.default_backend() == "tpu":
            logger.info(
                "steps over %d devices are GSPMD-partitioned programs: "
                "Pallas kernels off, XLA paths in their place",
                self.mesh.size)
        self._train_step = jax.jit(for_mesh(train, self.mesh),
                                   donate_argnums=(0,))
        self._eval_step = jax.jit(for_mesh(evaluate, self.mesh))
        # The next dispatch traces, lowers and compiles (or loads from the
        # cache): it runs under the span ``setup.first_step``.
        self._first_step = True

    def _init_host_state(self) -> None:
        """Per-run host world-view, shared verbatim by the constructor and
        ``reset_for_run`` so the two can never drift: any host attribute a
        run mutates MUST be (re)initialised here, or a later
        ``reset_for_run`` would leak one run's state into the next."""
        config = self.config
        self.current_epoch = 0
        self.global_step = 0

        # Host-facing components (reference: distributed_trainer.py:74-84).
        self.trust_manager = TrustManager(
            num_nodes=config.num_nodes,
            trust_threshold=config.trust_threshold,
            initial_trust=config.initial_trust,
            decay_rate=config.trust_decay_rate,
            recovery_rate=config.trust_recovery_rate,
            alpha=config.trust_alpha,
        )
        self.node_monitor = NodeMonitor()
        self.gradient_verifier = GradientVerifier()
        self.attack_detector = AttackDetector(
            exact_order_stats=config.exact_order_stats
        )
        self.metrics_collector = MetricsCollector(
            tensorboard_dir=config.tensorboard_dir
        )
        self._warned_trim = False
        self._trimmed_sizes: set = set()

        # Node configurations (reference: :85-87).  On TPU, rank == mesh
        # coordinate along the node axis.
        self.node_configs: Dict[int, NodeConfig] = {
            i: NodeConfig(node_id=i, rank=i, world_size=config.num_nodes,
                          device_id=i, model_partition=f"shard_{i}")
            for i in range(config.num_nodes)
        }

        self.attack_history: List[Dict] = []
        self.reassignment_history: List[Dict] = []
        # Fleet-level norm-surge episodes (unattributed majority-attack
        # alarms) — separate from attack_history, whose records name a
        # node and feed per-node precision/recall accounting.  The tracker
        # also records HOW each episode closed ("recovered" vs
        # "absorbed-while-raw" at the latch limit — see
        # detect/verifier.FleetEpisodeTracker).
        self._fleet_tracker = FleetEpisodeTracker()
        self.fleet_alerts: List[Dict] = self._fleet_tracker.episodes
        # Epoch-cadence ML-tier verdicts (original node id -> bool).
        self.ml_flags: Dict[int, bool] = {}
        # Mesh coordinate -> ORIGINAL node id.  Identity until elastic
        # eviction removes coordinates (elastic/reassignment.py); all host
        # bookkeeping (trust manager, histories, reports) keys on original
        # ids so identities survive resharding.
        self.node_map: List[int] = list(range(config.num_nodes))
        # Nodes currently in a recorded-compromised episode: a sustained
        # attack fires the detector every batch, but we record the incident
        # and trigger reassignment only on the clean→compromised transition
        # (the reference re-records per batch, which grows history without
        # bound on long runs).
        self._open_incidents: set = set()
        # Elastic-readmission bookkeeping: original id -> eviction step /
        # the device its coordinate occupied (None in dev mode), and the
        # per-original-id injection bits so a readmitted node's attack
        # schedule survives the mask compaction/expansion round-trip.
        self._evicted_at: Dict[int, int] = {}
        self._evicted_devices: Dict[int, Any] = {}
        self._plan_bits: Dict[int, bool] = {}
        # Pipeline restaff: healthy survivors a stage-count repartition
        # could not seat (id -> their parked devices); re-staffed by the
        # next restaff (elastic/restaff.py).
        self._idle_pool: Dict[int, Any] = {}
        # Loader auto-resize after topology changes (per-node microbatch
        # captured lazily from the first batch seen).
        self._active_loader: Any = None
        self._per_node_batch: Optional[int] = None
        self._trim_grace = 0
        self.attack_plan: AttackPlan = null_plan(config.num_nodes)
        # Robustness hook points (chaos/ + engine/supervisor.py).  Both are
        # per-run host state so reset_for_run detaches them: ``chaos`` is a
        # chaos.FaultInjector consulted in the step loop (fault injection);
        # ``step_guard`` is a supervisor implementing ``after_step(trainer,
        # node_batch, metrics) -> Optional[StepMetrics]`` — returning None
        # rejects the step (the trainer must not account it).
        self.chaos: Any = None
        self.step_guard: Any = None
        # Telemetry (obs/): an ObsSession attached via ``attach_obs``.
        # Per-run like chaos/step_guard — a reset detaches it so a stale
        # session never records a fresh run's events against old
        # correlation ids.  ``_last_status`` backs the trust-transition
        # event stream (emit on change, not per step).
        self.obs: Any = None
        # The phase timer of ``attach_phase_timer`` (an
        # obs.report.StepTimeReporter): the step loop's laps and spans go
        # to it, session or none.
        self.phase_timer: Any = None
        self._last_status: Optional[np.ndarray] = None
        # Async host pipeline (engine/async_host.py): while a LAGGED step
        # drains, ``_drain_ctx`` carries that step's packed fleet-norm
        # streak (the live state is up to K steps ahead) and collects
        # elastic evictions for deferred application at the frontier.
        # None whenever the synchronous path runs — per-run state like
        # chaos/step_guard so a reset can never leak a stale context.
        self._drain_ctx: Any = None
        # A supervisor also wires its injector into the checkpointer's
        # commit hooks; detach that too on reset, or a previous run's
        # UNFIRED checkpoint faults would fire in the next clean run.
        # (hasattr: the constructor calls this before the checkpointer
        # exists.)
        if hasattr(self, "checkpointer"):
            self.checkpointer.chaos = None
            self.checkpointer.trace = None

    @span("setup.initialize")
    def initialize(self, seed: Optional[int] = None) -> TrainState:
        """Init params/optimizer/world-view.  Params are replicated over the
        mesh; per-node batches shard over the data axis."""
        seed = self.config.seed if seed is None else seed
        rng = jax.random.PRNGKey(seed)
        k_params, k_state = jax.random.split(rng)
        with span("setup.initialize.model_init"):
            params = self.model.init(k_params)
        num_monitor_leaves = None
        if self.config.parallelism == "model":
            # Stage-major stacking: [L, ...] -> [S, L/S, ...], sharded over
            # the 'stage' mesh axis — the reference's layer partitioning
            # (distributed_trainer.py:126-134) as a sharding.
            from trustworthy_dl_tpu.parallel.pipeline import stack_stages

            params = dict(params)
            params["blocks"] = stack_stages(params["blocks"],
                                            self.config.num_nodes)
            num_monitor_leaves = len(
                jax.tree_util.tree_leaves(params["blocks"])
            )
            stage_sharding = shreg.row_sharding(self.mesh, STAGE_AXIS)
            params["blocks"] = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, stage_sharding), params["blocks"]
            )
        if self.config.parallelism in ("tensor", "hybrid"):
            from trustworthy_dl_tpu.parallel.tensor_parallel import (
                apply_tp_sharding,
            )

            # No-op when the mesh has no 'model' axis (hybrid without TP).
            params = apply_tp_sharding(params, self.mesh)
        with span("setup.initialize.opt_init"):
            opt_state = self.optimizer.init(params)
        canary = None
        if self.config.parallelism == "model":
            from trustworthy_dl_tpu.parallel.pipeline import (
                init_canary_state,
                make_canary,
            )

            canary = init_canary_state(
                self.config.num_nodes,
                make_canary(self.model.config, self.config.canary_tokens),
            )
        with span("setup.initialize.place_on_mesh"):
            self.state = self._place_on_mesh(init_train_state(
                k_state, params, opt_state,
                num_nodes=self.config.num_nodes,
                trust_threshold=self.config.trust_threshold,
                initial_trust=self.config.initial_trust,
                decay_rate=self.config.trust_decay_rate,
                recovery_rate=self.config.trust_recovery_rate,
                detector_window=self.config.detector_history,
                num_monitor_leaves=num_monitor_leaves,
                canary=canary,
            ))
        self.training_state = TrainingState.TRAINING
        # The default (null) plan rides every step dispatch too — commit
        # it to the mesh once, like set_attack_plan does for real plans.
        self.attack_plan = self._place_plan(self.attack_plan)
        return self.state

    def reset_for_run(self, seed: Optional[int] = None) -> TrainState:
        """Fresh run on the SAME jitted step: re-initialises device state
        (params/optimizer/trust/detector baselines) AND the host
        world-view (trust manager, detector histories, incident records,
        metrics, step counter) without touching the compiled train/eval
        steps — repeated experiment cells (e.g. the detection-envelope
        sweep) pay the XLA compile once instead of per cell.

        Only valid while the topology is unchanged (no eviction in the
        previous run); it raises otherwise, because the compiled step is
        shaped for the constructor's node count.  The guard compares
        against the CONSTRUCTOR's fleet size — an eviction of a trailing
        node leaves node_map an identity map, so identity alone cannot
        detect it."""
        if self.config.num_nodes != self._constructed_num_nodes or \
                self.node_map != list(range(self._constructed_num_nodes)):
            raise RuntimeError(
                "reset_for_run after a topology change; rebuild the "
                "trainer instead"
            )
        self._init_host_state()
        return self.initialize(seed=seed)

    def _place_on_mesh(self, state: TrainState) -> TrainState:
        """Explicit mesh placement of the whole TrainState, every rule
        resolved through the sharding registry (core/sharding.py):
        per-node rows shard over the node axis ('stage' under pipelining,
        'data' otherwise) via the shared ``row_placer``, ZeRO/FSDP state
        shards via the shared ``place_zero_sharded``, leaves already laid
        out on this mesh (stage-stacked blocks, TP params and their
        optimizer mirrors) keep their shardings, and everything else
        replicates.  Elastic migration (elastic/reassignment.py) calls
        the SAME helpers, so an evict/readmit cycle reproduces exactly
        these shardings.

        Freshly-initialised arrays would otherwise sit uncommitted on
        device 0 — fine for the first jitted step (GSPMD replicates them),
        but a checkpoint restored into that template comes back COMMITTED
        to device 0 and the next step fails mixing it with mesh-sharded
        arrays.  Explicit placement makes init and resume identical."""
        mesh = self.mesh
        if len(list(mesh.devices.flat)) <= 1:
            return state
        node_axis = STAGE_AXIS if self.config.parallelism == "model" else \
            DATA_AXIS
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n = self.config.num_nodes
        repl = shreg.replicated_sharding(mesh)

        def keep_or_repl(leaf):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                return leaf  # already mesh-placed (stage/TP layouts)
            return jax.device_put(leaf, repl)

        place_row = shreg.row_placer(mesh, node_axis, n)

        per_node = dict(
            trust=state.trust, out_baseline=state.out_baseline,
            grad_baseline=state.grad_baseline, verifier=state.verifier,
            monitor=state.monitor, prev_suspects=state.prev_suspects,
            clean_streak=state.clean_streak,
        )
        if state.canary is not None:
            per_node["canary"] = state.canary
        placed = {k: jax.tree_util.tree_map(place_row, v)
                  for k, v in per_node.items()}
        data_sharded = self.config.parallelism == "data" and \
            sizes.get(DATA_AXIS, 1) > 1
        if self.config.shard_params and data_sharded:
            # FSDP: weights shard over the data axis by the same registry
            # rule as the moments; GSPMD gathers per-layer where needed.
            params = shreg.place_zero_sharded(state.params, mesh, DATA_AXIS)
        else:
            params = jax.tree_util.tree_map(keep_or_repl, state.params)
        if data_sharded and (self.config.shard_opt_state
                             or self.config.shard_params):
            # ZeRO-1 (and FSDP, which subsumes it): one shared spelling
            # with elastic migration — see place_zero_sharded.
            opt_state = shreg.place_zero_sharded(state.opt_state, mesh,
                                                 DATA_AXIS)
        else:
            opt_state = jax.tree_util.tree_map(keep_or_repl, state.opt_state)
        shared = {
            "params": params,
            "opt_state": opt_state,
        }
        scalars = jax.tree_util.tree_map(
            lambda l: jax.device_put(l, repl),
            {"step": state.step, "epoch": state.epoch, "rng": state.rng,
             **fleet_scalar_fields(state)},
        )
        return state._replace(**placed, **shared, **scalars)

    def _place_plan(self, plan: AttackPlan) -> AttackPlan:
        """Commit the attack plan's leaves onto the mesh ONCE, in the
        layout the compiled step infers (per-node [n] rows over the node
        axis, scalars replicated).  An uncommitted plan is re-placed by
        the runtime at EVERY dispatch — an implicit per-step transfer the
        async pipeline's transfer-guard test pins out of the hot path."""
        mesh = self.mesh
        if len(list(mesh.devices.flat)) <= 1:
            return plan
        node_axis = STAGE_AXIS if self.config.parallelism == "model" else \
            DATA_AXIS
        # Same registry rule as the TrainState's per-node rows.
        place = shreg.row_placer(mesh, node_axis, self.config.num_nodes)
        return jax.tree_util.tree_map(place, plan)

    def set_attack_plan(self, plan: AttackPlan,
                        target_ids: Optional[Sequence[int]] = None) -> None:
        """Install the experiment's fault-injection schedule.

        ``target_ids`` optionally names the targeted ORIGINAL identities —
        pass it when identities may be off-mesh at install time (evicted
        before activation): the coordinate-space mask cannot carry their
        bit, and without it a later readmission would wrongly re-enter
        them as clean."""
        self.attack_plan = self._place_plan(plan)
        if target_ids is not None:
            targets = {int(i) for i in target_ids}
            self._plan_bits = {
                nid: nid in targets
                for nid in set(self.node_map) | targets
            }
        else:
            mask = np.asarray(plan.target_mask)
            self._plan_bits = {
                self.node_map[i]: bool(mask[i])
                for i in range(min(len(mask), len(self.node_map)))
            }

    def attach_obs(self, session: Any) -> None:
        """Install an :class:`obs.ObsSession`: step/trust/detection/
        checkpoint events flow to its trace bus (and flight recorder),
        and the step loop feeds its phase timer.  Also wires the
        checkpointer and any already-installed chaos injector so commit
        and fault events share the run's correlation ids, and re-binds
        the metrics collector onto the session's (per-run) registry."""
        self.obs = session
        self.attach_phase_timer(session.step_timer)
        self.checkpointer.trace = session.trace
        self.metrics_collector.bind_registry(session.registry)
        if self.chaos is not None:
            self.chaos.trace = session.trace

    def attach_phase_timer(self, reporter: Any = None) -> Any:
        """Phase laps and spans of the step loop and the epoch's end with
        no ``ObsSession``: no trace bus, no registry, no file.  Returns
        the timer (a fresh ``obs.report.StepTimeReporter`` unless one is
        given), whose ``report()`` holds the ``phases``, ``spans`` and
        ``epoch_end`` blocks; ``record_span`` of a reporter of the
        caller's own is also the hook on a span's close (the epoch's full
        drain is ``train.epoch_end.drain``).  Per-run, like ``attach_obs``:
        a reset detaches it."""
        if reporter is None:
            from trustworthy_dl_tpu.obs.report import StepTimeReporter

            reporter = StepTimeReporter()
        self.phase_timer = reporter
        return reporter

    def _timer(self) -> Any:
        """The attached phase timer, else the timer of whatever stands as
        ``self.obs`` (a session, or an object shaped like one)."""
        if self.phase_timer is not None:
            return self.phase_timer
        return self.obs.step_timer if self.obs is not None else None

    def _obs_note_model_info(self, node_batch: Dict[str, Any],
                             timer: Any) -> None:
        """Lazily give the step timer what MFU needs: param count and
        work units per step (tokens for LMs, samples for vision)."""
        if timer.has_model_info:
            return
        first = node_batch.get("input")
        if first is None:
            first = next(iter(node_batch.values()))
        if self.model.kind == "lm":
            # [n, b, T] (node split) or [B, T] (pipeline): size = tokens.
            units = int(np.prod(first.shape))
        elif self.config.parallelism == "model":
            units = int(first.shape[0])
        else:
            units = int(first.shape[0] * first.shape[1])
        timer.set_model_info(
            self.model.num_params(self.state.params), units,
            model_kind=self.model.kind,
            num_chips=len(list(self.mesh.devices.flat)),
        )
        ledger = getattr(self.obs, "cost_ledger", None)
        if ledger is not None and "train_step" not in ledger.programs:
            # XLA's own cost view of THE train step (obs/hbm.py):
            # analyzed FLOPs/bytes from one lowering pass (no backend
            # compile unless TDDL_OBS_MEMORY_ANALYSIS=1 adds the
            # temp-allocation block) — obs_report.json's cost ledger
            # and the analyzed-FLOPs MFU come from this entry.
            ledger.analyze("train_step", self._train_step, self.state,
                           node_batch, self.attack_plan)

    # ------------------------------------------------------------------
    # Batch plumbing
    # ------------------------------------------------------------------

    def _node_batch(self, batch: Dict[str, np.ndarray],
                    for_eval: bool = False
                    ) -> Optional[Dict[str, jax.Array]]:
        """[B, ...] -> [n, B//n, ...] with the node axis laid over the
        mesh's data axis — the reference's per-node data split, as sharding.
        Pipeline mode keeps the global batch (microbatching is internal) but
        trims B to a multiple of num_microbatches.  Returns None for a
        stale undersized batch during a topology-growth transition (the
        caller skips it).

        ``for_eval``: validation has no accumulation quantum and must not
        crash on a ragged final batch (drop_last=False loaders).  In
        non-pipeline modes a batch whose size doesn't divide by n is
        evaluated as a single replicated node row (no example dropped);
        in pipeline mode the stage ring's shapes are fixed, so a tail
        smaller than the microbatch quantum is SKIPPED (None) and a
        larger ragged tail is trimmed to the quantum — the closest the
        pipe can get without a per-tail-shape recompile of all S stages.
        Eval never feeds the training-side trim warnings."""
        if self.config.parallelism == "model":
            m = self.config.num_microbatches
            # DP pipeline replica rows (TPU (group, S) mesh) additionally
            # shard each microbatch over the data axis, so mb must divide
            # by the row count.
            dp = self.mesh.shape.get(DATA_AXIS, 1)
            quantum = m * dp
            out = {}
            for key, arr in batch.items():
                b = (arr.shape[0] // quantum) * quantum
                if b == 0:
                    if for_eval:
                        return None  # sub-quantum tail: skip, don't crash
                    raise ValueError(
                        f"batch size {arr.shape[0]} < num_microbatches x "
                        f"dp rows = {quantum}"
                    )
                out[key] = jnp.asarray(np.asarray(arr[:b]))
            return out
        n = self.config.num_nodes
        out = {}
        if for_eval:
            lead = min(arr.shape[0] for arr in batch.values())
            if lead == 0:
                return None
            # Ragged tail: one replicated node row — every example is
            # still evaluated (the row count change costs one extra
            # compile per distinct tail shape, bounded by the loader).
            n_eval = n if lead % n == 0 else 1
            for key, arr in batch.items():
                reshaped = np.asarray(arr[:lead]).reshape(
                    (n_eval, lead // n_eval) + arr.shape[1:]
                )
                out[key] = self._shard_node_rows(reshaped, n_eval)
            return out
        accum = max(self.config.grad_accum_steps, 1)
        # Trim ragged batches (drop_last=False loaders) to a multiple of
        # nodes × accumulation steps — same trimming contract as the node
        # split and the pipeline microbatch branch.  Trim bookkeeping runs
        # once per BATCH (input/target share the leading size), keyed on
        # the size: a single ragged tail is normal and stays silent, the
        # same size trimmed on a second batch means the loader's batch
        # size never divides nodes×accum — warn once per trainer.
        lead = min(arr.shape[0] for arr in batch.values())
        b = (lead // (n * accum)) * n * accum
        if b == 0:
            if self._trim_grace > 0:
                # Stale pre-resize batch after a GROWTH transition
                # (readmission): too small to split over the larger fleet.
                # Skip it rather than crash — the resized loader's batches
                # are already behind it in the queue.
                self._trim_grace -= 1
                return None
            raise ValueError(
                f"batch size {lead} < num_nodes x grad_accum_steps = "
                f"{n * accum}"
            )
        if b < lead and not self._warned_trim:
            if self._trim_grace > 0:
                # Transitional old-size batches right after a topology
                # resize (prefetch queue backlog) — expected, not a
                # persistent mismatch.
                self._trim_grace -= 1
            elif lead in self._trimmed_sizes:
                self._warned_trim = True
                logger.warning(
                    "batches of %d are persistently trimmed to %d "
                    "(num_nodes=%d x grad_accum_steps=%d); pick a "
                    "divisible batch size to avoid dropping examples",
                    lead, b, n, accum,
                )
            else:
                self._trimmed_sizes.add(lead)
        for key, arr in batch.items():
            reshaped = np.asarray(arr[:b]).reshape((n, b // n) + arr.shape[1:])
            out[key] = self._shard_node_rows(reshaped, n)
        return out

    def _shard_node_rows(self, reshaped: np.ndarray, rows: int) -> jax.Array:
        """Place a node-split [rows, ...] array: leading axis over the
        mesh's data axis when the row count tiles it, replicated
        otherwise."""
        data_size = dict(
            zip(self.mesh.axis_names, self.mesh.devices.shape)
        ).get(DATA_AXIS, 1)
        if data_size > 1 and rows % data_size == 0:
            sharding = shreg.row_sharding(self.mesh, DATA_AXIS,
                                          reshaped.ndim)
            return jax.device_put(reshaped, sharding)
        return jnp.asarray(reshaped)

    # ------------------------------------------------------------------
    # Training (distributed_trainer.py:382-433,465-492)
    # ------------------------------------------------------------------

    def train_epoch(self, dataloader: Iterable[Dict[str, np.ndarray]],
                    epoch: int) -> float:
        if self.state is None:
            self.initialize()
        self.current_epoch = epoch
        epoch_loss, num_batches = 0.0, 0
        # Per-epoch loader binding: the per-node microbatch is re-derived
        # from THIS loader's first batch, so a later epoch with a
        # different-sized loader is never resized against a stale capture.
        self._per_node_batch = None
        timer = self._timer()

        if self.config.prefetch_depth > 0 and not isinstance(
            dataloader, PrefetchLoader
        ):
            # Host/device overlap: the next batch's host-side assembly
            # (native row gathers) runs while the current step trains.
            dataloader = PrefetchLoader(dataloader,
                                        depth=self.config.prefetch_depth,
                                        timer=timer)
        self._active_loader = dataloader
        if timer is not None:
            timer.discard_step()  # anchor the first step's "data" lap

        # Async host pipeline (engine/async_host.py): at depth K > 0 the
        # loop below dispatches step k+1 before step k's host-facing
        # metrics have landed — the bookkeeping drains lagged through the
        # same host path, and the mandatory full drains (checkpoint saves,
        # epoch end via the finally, guard rollbacks, elastic transitions,
        # preemption unwind) keep the verified-checkpoint semantics exact.
        pipe = None
        depth = max(int(getattr(self.config, "async_host_depth", 0)), 0)
        if depth > 0:
            from trustworthy_dl_tpu.engine.async_host import (
                AsyncHostPipeline,
            )

            pipe = AsyncHostPipeline(self, depth, timer=timer)

        with contextlib.ExitStack() as epoch_end:
            try:
                for batch_idx, batch in enumerate(dataloader):
                    self.global_step += 1
                    if self._per_node_batch is None and \
                            self.config.parallelism != "model":
                        lead = min(arr.shape[0] for arr in batch.values())
                        accum = max(self.config.grad_accum_steps, 1)
                        per = lead // (self.config.num_nodes * accum)
                        if per > 0:
                            self._per_node_batch = per
                    if self.chaos is not None:
                        # Fault-injection hooks (chaos/injector.py): a lost
                        # batch (simulated data-iterator failure) rides the
                        # stale-batch skip path; on_step_start may stall
                        # (straggler) or raise SimulatedPreemption for the
                        # supervisor to catch.
                        batch = self.chaos.on_batch(self.global_step, batch)
                        if batch is None:
                            self.global_step -= 1
                            continue
                        self.chaos.on_step_start(self.global_step)
                    with span("train.batch_place", timer):
                        node_batch = self._node_batch(batch)
                    if node_batch is None:  # stale undersized batch
                        self.global_step -= 1
                        if timer is not None:
                            timer.discard_step()
                        continue
                    if timer is not None:
                        self._obs_note_model_info(node_batch, timer)
                        timer.lap("data")  # loader + host assembly + placement
                    # Compile-once runtime contract (obs/compilewatch.py):
                    # the dispatch runs under the watcher's "train_step"
                    # guard — the first guarded step's compile is warmup,
                    # any later recompile storms (rebuild sites reset the
                    # scope so planned recompiles stay silent).
                    compilewatch = getattr(self.obs, "compilewatch", None) \
                        if self.obs is not None else None
                    first_step = _NO_SPAN
                    if self._first_step:
                        self._first_step = False
                        first_step = span("setup.first_step")
                    with first_step, step_annotation(self.global_step), \
                            guarded(compilewatch, "train_step",
                                    step=self.global_step):
                        self.state, metrics = self._train_step(
                            self.state, node_batch, self.attack_plan
                        )
                    if self.chaos is not None:
                        self.state, metrics = self.chaos.on_step_end(
                            self.global_step, self.state, metrics
                        )

                    if pipe is not None:
                        # Asynchronous accounting: pack + start the D2H copy,
                        # then drain only what has fallen out of the window.
                        # Guard checks / records / readmission run lagged
                        # inside the drain.
                        dispatched = self.global_step
                        ckpt_step = dispatched % \
                            self.config.checkpoint_interval == 0
                        with span("train.host_drain", timer):
                            pipe.push(epoch, batch_idx, node_batch, metrics,
                                      self.state)
                            if timer is not None:
                                timer.lap("compute")  # dispatch, no sync
                            pipe.drain()
                            if ckpt_step:
                                pipe.drain(0)  # mandatory full drain pre-save
                        if timer is not None:
                            # Both drains land here: blocked-on-lagged-metrics
                            # time is the "host" phase even on save steps (the
                            # save itself is the "checkpoint" lap below).
                            timer.lap("host")
                        if ckpt_step:
                            # Save only when the frontier step survived the
                            # drain intact: a rollback moved the counter (and
                            # re-saving the checkpoint just restored would be
                            # pure waste), and a guard-rejected frontier step
                            # must not be enshrined as "verified".
                            if self.global_step == dispatched and \
                                    pipe.last_rejected_step != dispatched:
                                self.save_checkpoint()
                        if timer is not None:
                            timer.lap("checkpoint")
                            if pipe.consume_rejection():
                                # Same contract as the synchronous path: a
                                # rejected step's wall time (rollback restore)
                                # would poison the phase distribution.
                                timer.discard_step()
                            else:
                                timer.finish_step(step=self.global_step)
                        if self.obs is not None:
                            self.obs.on_step(self.global_step)
                        continue

                    # Synchronous path (async_host_depth=0): every step blocks
                    # on the host pulls before the next dispatch.
                    if self.step_guard is not None:
                        metrics = self.step_guard.after_step(self, node_batch,
                                                             metrics)
                        if metrics is None:
                            # Step rejected (non-finite / wedged) — possibly
                            # rolled back to a verified checkpoint (global_step
                            # restored by load_checkpoint).  Nothing to
                            # account.  A rejected step's wall time (retries,
                            # rollback restore) would poison the phase
                            # distribution — drop it.
                            if timer is not None:
                                timer.discard_step()
                            continue
                    self.metrics_collector.tick()
                    # tddl-lint: disable=host-sync — the sync path's ONE
                    # deliberate pull; async_host_depth>0 takes the packed
                    # D2H pipeline instead.
                    loss = float(metrics.loss)  # host sync closes the step
                    if timer is not None:
                        timer.lap("compute")  # dispatch + device step + sync
                    self._record_batch(metrics, epoch, loss)
                    self._maybe_readmit()
                    if timer is not None:
                        timer.lap("detection")  # host verdicts/incidents
                    epoch_loss += loss
                    num_batches += 1

                    if self.global_step % self.config.checkpoint_interval == 0:
                        self.save_checkpoint()
                    if timer is not None:
                        timer.lap("checkpoint")
                        timer.finish_step(step=self.global_step)
                    if self.obs is not None:
                        self.obs.on_step(self.global_step)
                    if batch_idx % 10 == 0:
                        logger.info("Epoch %d, Batch %d, Loss: %.4f",
                                    epoch, batch_idx, loss)
            finally:
                # The epoch's end, by its parts (device idle throughout):
                # the span opens at the loop's exit, however it came, and
                # closes with ``epoch_end`` at the return or on the unwind.
                epoch_end.enter_context(span("train.epoch_end", timer))
                if pipe is not None:
                    # Mandatory full drain: epoch aggregation, the
                    # epoch-end host sync below, and — on a preemption or
                    # supervisor unwind — the save-on-signal all need a
                    # caught-up host view.
                    with span("train.epoch_end.drain", timer):
                        pipe.drain(0, under="train.epoch_end.drain")
                    epoch_loss += pipe.epoch_loss
                    num_batches += pipe.num_batches

            # Epoch-cadence host sync: reporting objects absorb device state.
            with span("train.epoch_end.host_sync", timer):
                self.sync_host_state()
            self._epoch_intelligence(timer)
            avg = epoch_loss / max(num_batches, 1)
            with span("train.epoch_end.collect", timer):
                self.metrics_collector.collect_epoch_metrics({
                    "epoch": epoch,
                    "avg_loss": avg,
                    "num_batches": num_batches,
                    "system_trust":
                        self.trust_manager.calculate_system_trust(),
                })
        logger.info("Epoch %d completed. Average loss: %.4f", epoch, avg)
        return avg

    def _epoch_intelligence(self, timer: Any = None) -> None:
        """Epoch-cadence host intelligence the reference defined but never
        called (SURVEY §7.5): adaptive trust thresholds
        (trust_manager.py:333-348) pushed back into the device state, and
        ML-detector refit + secondary verdicts (attack_detector.py:381-425)."""
        with span("train.epoch_end.thresholds", timer):
            self._adjust_thresholds()
        with span("train.epoch_end.ml_refit", timer) as noted:
            rows = self._refit_ml_detectors(timer)
            if rows is not None:
                noted["rows"] = rows

    def _adjust_thresholds(self) -> None:
        if self.config.adaptive_thresholds:
            self.trust_manager.adaptive_threshold_adjustment()
            threshold = jnp.asarray(
                self.trust_manager.trust_threshold, jnp.float32
            )
            if len(list(self.mesh.devices.flat)) > 1:
                # Same replicated placement as init/_place_on_mesh: a
                # bare jnp scalar is an UNCOMMITTED SingleDeviceSharding
                # leaf, which changes the jitted step's input signature
                # and silently recompiled the whole train step on the
                # first step of every post-adjustment epoch (caught by
                # the compile watcher's train_step guard).
                threshold = jax.device_put(
                    threshold, shreg.replicated_sharding(self.mesh)
                )
            self.state = self.state._replace(
                trust=self.state.trust._replace(threshold=threshold)
            )

    def _refit_ml_detectors(self, timer: Any) -> Optional[int]:
        """Refit the per-node ML detectors on their histories and score
        each node's newest row.  Returns the rows fitted, over the nodes
        (None with the ML tier off)."""
        if not self._ml_enabled:
            return None
        with span("train.epoch_end.ml_refit.fit", timer):
            rows = self.attack_detector.update_detection_models()
        with span("train.epoch_end.ml_refit.score", timer):
            self.ml_flags = {}
            for orig in self.node_map:
                features = self.attack_detector.latest_features(orig)
                if features:
                    self.ml_flags[orig] = \
                        self.attack_detector.detect_with_ml_models(
                            features, orig)
        if any(self.ml_flags.values()):
            logger.warning(
                "ML detectors flagged nodes: %s",
                [n for n, v in self.ml_flags.items() if v],
            )
        return rows

    def _record_batch(self, metrics: StepMetrics, epoch: int, loss: float
                      ) -> None:
        attacked = np.asarray(metrics.attacked)
        trust = np.asarray(metrics.trust_scores)
        id_of = self.node_map  # coordinate -> original node id
        if self.obs is not None:
            grad_norm = float(np.asarray(metrics.grad_norm))
            self.obs.trace.emit(
                EventType.TRAIN_STEP, step=self.global_step, epoch=epoch,
                loss=loss,
                grad_norm=grad_norm,
                system_trust=float(np.asarray(metrics.system_trust)),
            )
            if self.obs.anomaly is not None:
                # Anomaly watcher feed: only guard-ACCEPTED steps reach
                # this path, so the EWMA baseline is the healthy run —
                # drift/spikes that pass the (non-finite-only) guard
                # still flag here; NaNs reach the watcher through the
                # supervisor's guard-trip feed instead.
                self.obs.anomaly.observe("loss", loss,
                                         step=self.global_step)
                self.obs.anomaly.observe("grad_norm", grad_norm,
                                         step=self.global_step)
            # Trust-state transitions: emitted on CHANGE (keyed by
            # original identity), not per step — the trace stays joinable
            # on step id without carrying n gauges per row.
            status_now = np.asarray(metrics.status)
            prev = self._last_status
            if prev is not None and len(prev) == len(status_now):
                for coord in np.nonzero(status_now != prev)[0]:
                    self.obs.trace.emit(
                        EventType.TRUST_TRANSITION, step=self.global_step,
                        node=int(id_of[int(coord)]),
                        from_status=NodeStatus(int(prev[coord])).name,
                        to_status=NodeStatus(int(status_now[coord])).name,
                        trust=float(trust[int(coord)]),
                    )
            self._last_status = status_now.copy()
        self.metrics_collector.collect_batch_metrics(
            {
                "loss": loss,
                "step": self.global_step,
                "epoch": epoch,
                "trust_scores": {
                    id_of[i]: float(trust[i]) for i in range(len(trust))
                },
                # Model diagnostics (e.g. MoE capacity-drop fraction).
                # ``model_aux`` is a None sentinel when absent (mutable {}
                # NamedTuple defaults are a shared instance) — normalise.
                **{k: float(v)
                   for k, v in (getattr(metrics, "model_aux", None)
                                or {}).items()},
            }
        )
        # Feed the stat batteries into the host detector's history — the
        # training corpus for the epoch-cadence ML tier
        # (attack_detector.py:381-425, which the reference never called).
        if self._ml_enabled:
            out_stats = np.asarray(metrics.out_stats)
            grad_stats = np.asarray(metrics.grad_stats)
            for coord, orig in enumerate(id_of):
                # Output batteries carry 12 real stats + 5 zero pads
                # (shape-matched to the 17-stat gradient battery inside the
                # step); label only the real columns so the key set agrees
                # with the host detector's own output-history entries.
                self.attack_detector.output_history[orig].append(
                    {"stats": dict(zip(
                        TENSOR_STAT_NAMES,
                        out_stats[coord][:NUM_TENSOR_STATS],
                    ))}
                )
                self.attack_detector.gradient_history[orig].append(
                    {"stats": dict(zip(GRADIENT_STAT_NAMES, grad_stats[coord]))}
                )

        # Fleet-level norm-surge alarm (majority-attack backstop): the
        # in-step verdict is unattributed — with >= 50 % of the fleet
        # poisoning together the median itself lies, so no node is gated
        # or evicted; the episode is recorded for operator action and the
        # training-state machine flips to UNDER_ATTACK.
        fleet_alert = getattr(metrics, "fleet_alert", None)
        if fleet_alert is not None:
            if self._drain_ctx is not None:
                # Lagged drain: the live state is up to K steps ahead of
                # this record — use the streak packed with the step itself.
                streak = self._drain_ctx.fleet_streak
            else:
                streak = getattr(self.state, "fleet_raw_streak", None)
            streak = int(np.asarray(streak)[0]) if streak is not None else 0
            opened = self._fleet_tracker.update(
                bool(np.asarray(fleet_alert)), streak, self.global_step,
                extra={
                    "epoch": epoch,
                    "median_grad_norm": float(
                        np.median(np.asarray(metrics.grad_norm))
                    ),
                },
            )
            if opened is not None:
                logger.error(
                    "FLEET-LEVEL norm surge at step %d: the "
                    "cross-sectional median gradient norm departed "
                    "its own history — consistent with a "
                    "majority/coordinated attack the per-node gate "
                    "cannot attribute", self.global_step,
                )
                self.training_state = TrainingState.UNDER_ATTACK
                if self.obs is not None:
                    self.obs.trace.emit(
                        EventType.FLEET_ALERT, step=self.global_step,
                        median_grad_norm=opened.get("median_grad_norm"),
                    )

        # Host incidents fire only on confirmed evidence: debounced verdicts
        # (metrics.attacked already folds in sustained norm-verification
        # failures) or non-finite gradients.  A single-step statistical blip
        # is excluded from that step's aggregate in-step but is NOT an
        # incident.
        finite = np.asarray(metrics.finite)
        flagged = attacked | ~finite
        # Close incidents for nodes the device-side state machine has
        # rehabilitated, so a later re-attack records a fresh incident.
        # (Evicted nodes have no coordinate and stay closed-out forever.)
        status = np.asarray(metrics.status)
        coord_of = {orig: i for i, orig in enumerate(id_of)}
        for orig in list(self._open_incidents):
            coord = coord_of.get(orig)
            if coord is not None and not flagged[coord] and status[
                coord
            ] != int(NodeStatus.COMPROMISED):
                self._open_incidents.discard(orig)
        evict_coords: List[int] = []
        if flagged.any():
            types = np.asarray(metrics.attack_type)
            # All nodes flagged THIS step are unfit reassignment targets,
            # even before their own incident is processed (nodes 1 and 3
            # confirmed in the same step must not be handed each other's
            # shards).
            flagged_ids = {id_of[int(c)] for c in np.nonzero(flagged)[0]}
            for coord in np.nonzero(flagged)[0]:
                orig = id_of[int(coord)]
                if orig in self._open_incidents:
                    continue
                self._open_incidents.add(orig)
                self._handle_detected_attack(
                    orig,
                    attack_type=AttackType(int(types[coord])).label
                    if attacked[coord] else "gradient_verification_failure",
                    metrics=metrics,
                    coord=int(coord),
                    exclude=flagged_ids,
                )
                evict_coords.append(int(coord))
        if self._drain_ctx is not None:
            # Lagged drain (async pipeline): resharding mid-window would
            # orphan the in-flight entries' packed metrics (their node
            # count predates the surgery) — collect the coordinates and
            # let the pipeline apply them at the frontier after its
            # mandatory full drain.
            self._drain_ctx.evict_coords.update(evict_coords)
        else:
            self._apply_evictions(evict_coords)

    def _apply_evictions(self, evict_coords: Sequence[int]) -> None:
        """Elastic reaction to confirmed compromises: evict the flagged
        mesh coordinates and reshard (or restaff the pipeline).  Split out
        of ``_record_batch`` so the async drain can defer it to a
        full-drain point; the synchronous path calls it immediately with
        identical semantics."""
        evict_coords = list(evict_coords)
        if (evict_coords and self.config.elastic_resharding
                and len(evict_coords) < self.config.num_nodes):
            from trustworthy_dl_tpu.elastic.reassignment import (
                elastic_supported,
                evict_and_reshard,
            )

            evict_record = None
            if elastic_supported(self.config):
                record = evict_record = evict_and_reshard(self,
                                                          evict_coords)
                record["step"] = self.global_step
                self.reassignment_history.append(record)
                for orig in record["evicted_nodes"]:
                    self._evicted_at[int(orig)] = self.global_step
                self._resize_loader()
            elif self.config.parallelism == "model":
                # Model-parallel restaff: the compromised stage's layer
                # shard migrates to trusted hardware and the model
                # repartitions — ALL layers keep training
                # (elastic/restaff.py), not the freeze+relabel the
                # reference ships.
                from trustworthy_dl_tpu.elastic.restaff import (
                    restaff_pipeline,
                )

                record = evict_record = restaff_pipeline(self,
                                                         evict_coords)
                record["step"] = self.global_step
                self.reassignment_history.append(record)
                for orig in record["evicted_nodes"]:
                    # Start the cool-off clock: a cooled-off stage
                    # identity re-enters the restaff candidate pool
                    # (_maybe_readmit).
                    self._evicted_at[int(orig)] = self.global_step
            if evict_record is not None and self.obs is not None:
                self.obs.trace.emit(
                    EventType.ELASTIC_EVICT, step=self.global_step,
                    nodes=[int(n) for n in evict_record["evicted_nodes"]],
                    live_nodes=self.config.num_nodes,
                )

    def _readmit_due(self) -> bool:
        """Cheap predicate: would ``_maybe_readmit`` act right now?  The
        async drain polls this to decide when a readmission (a topology
        change) forces a mandatory full drain — without paying the import
        and record machinery on every step."""
        cfg = self.config
        if not (cfg.elastic_resharding and cfg.readmit_after_steps > 0
                and self._evicted_at):
            return False
        return any(self.global_step - when >= cfg.readmit_after_steps
                   for when in self._evicted_at.values())

    def _maybe_readmit(self) -> None:
        """Re-admit evicted coordinates whose cool-off has elapsed
        (config.readmit_after_steps) — the elastic counterpart of the
        in-step probation: without it a false-positive eviction costs 1/n
        of the fleet for the rest of the run.  Mode-agnostic like the
        reference's recovery ladder (trust_manager.py:198-206):
        data/tensor/sequence restore the coordinate (and its device
        group); model mode returns the identity to the restaff candidate
        pool and regrows the stage count when the arithmetic allows."""
        cfg = self.config
        if not (cfg.elastic_resharding and cfg.readmit_after_steps > 0
                and self._evicted_at):
            return
        due = sorted(
            nid for nid, when in self._evicted_at.items()
            if self.global_step - when >= cfg.readmit_after_steps
        )
        if not due:
            return
        from trustworthy_dl_tpu.elastic.reassignment import (
            elastic_supported,
            readmit_and_reshard,
        )

        if elastic_supported(cfg):
            record = readmit_and_reshard(self, due)
            record["step"] = self.global_step
            self.reassignment_history.append(record)
            self._resize_loader()
        elif cfg.parallelism == "model":
            self._readmit_stages(due)
        if self.obs is not None:
            self.obs.trace.emit(
                EventType.ELASTIC_READMIT, step=self.global_step,
                nodes=[int(n) for n in due],
                live_nodes=self.config.num_nodes,
            )

    def _readmit_stages(self, due: Sequence[int]) -> None:
        """Model-mode return path: cooled-off evicted stage identities
        re-enter the restaff candidate pool on probation (RECOVERING with
        the 0.5 readmission trust floor), and an immediate restaff
        re-expands S' -> S when the layer arithmetic allows; otherwise the
        identity waits in the idle pool for the next restaff."""
        from trustworthy_dl_tpu.elastic.restaff import (
            choose_stage_count,
            restaff_pipeline,
        )

        for nid in due:
            self._idle_pool[nid] = self._evicted_devices.pop(nid, []) or []
            self._evicted_at.pop(nid, None)
            self._open_incidents.discard(nid)
            self.trust_manager.begin_probation(nid)
        blocks = self.state.params["blocks"]
        lead = jax.tree_util.tree_leaves(blocks)[0]
        num_layers = lead.shape[0] * lead.shape[1]
        grown = choose_stage_count(
            num_layers, self.config.num_nodes + len(self._idle_pool)
        )
        if grown > self.config.num_nodes:
            record = restaff_pipeline(self, [])
            record["step"] = self.global_step
            self.reassignment_history.append(record)

    def _resize_loader(self) -> None:
        """Re-size the live data pipeline after a topology change so batch
        sizes divide nodes × accum again — without this, every post-change
        batch is trimmed and silently drops the same samples' worth of data
        each step.  Works on any loader exposing a ``batch_size``
        attribute (all bundled loaders); foreign loaders keep the trimming
        fallback with its warning."""
        import dataclasses

        loader = self._active_loader
        if loader is None or self._per_node_batch is None or \
                self.config.parallelism == "model":
            return
        accum = max(self.config.grad_accum_steps, 1)
        new_bs = self._per_node_batch * self.config.num_nodes * accum
        target = loader.loader if isinstance(loader, PrefetchLoader) else loader
        if hasattr(target, "batch_size") and target.batch_size != new_bs:
            logger.info(
                "Loader re-sized for new topology: batch %d -> %d "
                "(%d nodes x %d/node x %d accum)", target.batch_size,
                new_bs, self.config.num_nodes, self._per_node_batch, accum,
            )
            target.batch_size = new_bs
            self.config = dataclasses.replace(self.config,
                                              batch_size=new_bs)
            # A few old-size batches may already sit in the prefetch queue
            # (and the current epoch of an epoch-partitioned loader keeps
            # its size until re-iterated): tolerate that transition without
            # tripping the persistent-trim warning.
            self._warned_trim = False
            self._trimmed_sizes.clear()
            self._trim_grace = max(self.config.prefetch_depth, 1) + 1

    def _handle_detected_attack(self, node_id: int, attack_type: str,
                                metrics: StepMetrics,
                                coord: Optional[int] = None,
                                exclude: Optional[set] = None) -> None:
        """Host-side reaction (distributed_trainer.py:273-322): record the
        incident, mirror compromise into the host TrustManager, trigger
        reassignment.  The in-step mitigation (grad gating) already happened
        on device in the same step.  ``node_id`` is the ORIGINAL id;
        ``coord`` its current mesh coordinate (equal until eviction)."""
        coord = node_id if coord is None else coord
        logger.error("Attack detected on node %d (%s)", node_id, attack_type)
        # Ground-truth accounting: the injection plan knows whether this
        # node was actually under attack this step, so the host detector's
        # TP/FP counters report reality (the reference initialised them and
        # never incremented either — its rates were always 0.0).
        plan = self.attack_plan
        mask = np.asarray(plan.target_mask)
        live = bool(plan.active) and (self.global_step - 1) >= int(
            plan.start_step
        )
        is_tp = live and coord < len(mask) and bool(mask[coord])
        ds = self.attack_detector.detection_stats
        ds["total_detections"] += 1
        ds["attack_types"][attack_type] += 1
        ds["true_positives" if is_tp else "false_positives"] += 1
        if self.obs is not None:
            self.obs.trace.emit(
                EventType.DETECTION_VERDICT, step=self.global_step,
                node=int(node_id), attack_type=attack_type,
                ground_truth_positive=is_tp,
                out_score=float(np.asarray(metrics.out_score)[coord]),
                grad_score=float(np.asarray(metrics.grad_score)[coord]),
            )
        self.attack_history.append(
            {
                "node_id": node_id,
                "timestamp": time.time(),
                "step": self.global_step,
                "attack_type": attack_type,
                "output_stats": {
                    "anomaly_score": float(np.asarray(metrics.out_score)[coord]),
                    "gradient_score": float(np.asarray(metrics.grad_score)[coord]),
                },
            }
        )
        self.trust_manager.mark_compromised(node_id, attack_type)
        from trustworthy_dl_tpu.elastic.reassignment import (
            elastic_supported,
        )

        if not (self.config.elastic_resharding
                and (elastic_supported(self.config)
                     or self.config.parallelism == "model")):
            # Legacy greedy handoff (relabel) — elastic mode replaces it
            # with the real group eviction (ELASTIC_MODES) or stage
            # restaff (model) in _record_batch.
            self.reassign_node_tasks(node_id, exclude=exclude)
        self.training_state = TrainingState.UNDER_ATTACK

    # ------------------------------------------------------------------
    # Reassignment (distributed_trainer.py:324-380)
    # ------------------------------------------------------------------

    def reassign_node_tasks(self, compromised_node_id: int,
                            exclude: Optional[set] = None) -> None:
        unfit = set(exclude or ()) | {compromised_node_id}
        trusted = self.trust_manager.get_trusted_nodes()
        trusted = [n for n in trusted if n not in unfit]
        if not trusted:
            logger.error("No trusted nodes available for reassignment")
            return
        best = max(trusted, key=self.trust_manager.get_trust_score)
        migration_time = self.estimate_migration_time(compromised_node_id, best)
        self.perform_task_reassignment(compromised_node_id, best)
        self.reassignment_history.append(
            {
                "from_node": compromised_node_id,
                "to_node": best,
                "timestamp": time.time(),
                "migration_time": migration_time,
                "step": self.global_step,
            }
        )

    def estimate_migration_time(self, source_node: int, target_node: int
                                ) -> float:
        """Migration model (distributed_trainer.py:354-365): bytes / rate +
        setup.  The reference hardcodes 1 GB/s + 2 s — on TPU the transfer
        rides ICI, so the rate is configurable via ``migration_gbps`` (the
        elastic subsystem measures it; see elastic/reassignment.py)."""
        if self.state is None:
            return 2.0
        n_params = self.model.num_params(self.state.params)
        # In data-parallel the migrating unit is the node's optimizer+param
        # replica share; in stage parallel it is the stage slice.
        shard = n_params / max(self.config.num_nodes, 1)
        transfer = shard * 4 / (self.config.migration_gbps * 1024**3)
        return transfer + 2.0

    def perform_task_reassignment(self, source_node: int, target_node: int
                                  ) -> None:
        """In SPMD data-parallel the compromised node's contribution is
        already zero-weighted inside the step (the immediate mitigation,
        SURVEY §5.3); reassignment relabels the shard ownership so the
        recovered data shard flows to the target node.  Real device-set
        resharding lives in elastic/reassignment.py."""
        self.node_configs[target_node].model_partition = (
            f"shard_{source_node}+{self.node_configs[target_node].model_partition}"
        )
        logger.info("Task reassignment completed: %d -> %d",
                    source_node, target_node)

    # ------------------------------------------------------------------
    # Epochs / validation / stats
    # ------------------------------------------------------------------

    def train(self, train_dataloader, val_dataloader=None,
              num_epochs: Optional[int] = None) -> Dict[str, Any]:
        if num_epochs is None:
            num_epochs = self.config.num_epochs
        logger.info("Starting training for %d epochs", num_epochs)
        if self.state is None:
            self.initialize()
        self.training_state = TrainingState.TRAINING
        history = []
        with trace(self.config.profile_dir):
            for epoch in range(num_epochs):
                avg_loss = self.train_epoch(train_dataloader, epoch)
                record = {"epoch": epoch, "train_loss": avg_loss}
                if val_dataloader is not None:
                    val = self.validate(val_dataloader)
                    record.update(val_loss=val)
                    logger.info("Validation loss: %.4f", val)
                if self.training_state == TrainingState.UNDER_ATTACK:
                    logger.info(
                        "Training under attack - implementing recovery measures"
                    )
                    self.training_state = TrainingState.RECOVERING
                history.append(record)
        self.training_state = TrainingState.COMPLETED
        logger.info("Training completed successfully")
        return {"epochs": history, "stats": self.get_training_stats()}

    def validate(self, val_dataloader) -> float:
        """Mean validation loss (reference signature,
        distributed_trainer.py:494-508)."""
        return self.validate_metrics(val_dataloader)["loss"]

    def validate_metrics(self, val_dataloader) -> Dict[str, float]:
        """Full validation metrics: loss, accuracy, and (for LMs)
        perplexity — the eval step already computes them; the reference
        only surfaced loss."""
        total, acc, examples = 0.0, 0.0, 0
        for batch in val_dataloader:
            # Node-split + 'data'-axis sharding exactly like training
            # (model mode trims to a microbatch multiple instead), so on
            # an n-chip mesh each chip evaluates 1/n of the batch rather
            # than replicating the whole thing.
            batch = self._node_batch(batch, for_eval=True)
            if batch is None:  # empty / stale batch
                continue
            out = self._eval_step(self.state.params, batch)
            # Example-weighted mean: a ragged tail batch must count by
            # its size, not as a full batch.
            first = next(iter(batch.values()))
            # Model mode feeds the global batch [B, ...]; other modes the
            # node split [rows, per_row, ...].
            weight = int(first.shape[0]) if \
                self.config.parallelism == "model" else \
                int(first.shape[0] * first.shape[1])
            total += float(out["loss"]) * weight
            acc += float(out["accuracy"]) * weight
            examples += weight
        n = max(examples, 1)
        metrics = {"loss": total / n, "accuracy": acc / n}
        if self.model.kind == "lm":
            metrics["perplexity"] = float(np.exp(min(metrics["loss"], 30.0)))
        return metrics

    def sync_host_state(self) -> None:
        """Epoch-cadence absorption of device state into the host reporting
        objects (TrustManager / NodeMonitor).  After elastic eviction the
        device arrays cover only surviving coordinates; ``node_map``
        routes them to their original host ids."""
        if self.state is None:
            return
        self.trust_manager.sync_from_device(self.state.trust,
                                            node_ids=self.node_map)
        self.node_monitor.sync_from_device(self.state.monitor,
                                           node_ids=self.node_map)

    def get_training_stats(self) -> Dict[str, Any]:
        """distributed_trainer.py:510-521."""
        return {
            "current_epoch": self.current_epoch,
            "global_step": self.global_step,
            "training_state": self.training_state.value,
            "trust_scores": {
                i: self.trust_manager.get_trust_score(i)
                for i in range(self.config.num_nodes)
            },
            "attack_count": len(self.attack_history),
            "reassignment_count": len(self.reassignment_history),
            "fleet_alert_count": len(self.fleet_alerts),
            "metrics": self.metrics_collector.get_summary(),
            "trust_threshold": self.trust_manager.trust_threshold,
            "ml_flags": dict(self.ml_flags),
            "predicted_reliability": {
                i: self.trust_manager.predict_node_reliability(i)
                for i in range(self.config.num_nodes)
            },
        }

    # ------------------------------------------------------------------
    # Checkpointing (distributed_trainer.py:448-463 + restore, new)
    # ------------------------------------------------------------------

    def save_checkpoint(self) -> Optional[str]:
        if self.state is None:
            return None
        # Never persist non-finite params over the last good checkpoint:
        # "verified" means integrity-verified AND taken from sane state.
        # Without this gate, corruption landing exactly on a save step
        # would poison the rollback target itself — the supervisor would
        # then restore NaN state forever while reporting recovery.  Cost
        # is one reduction per param leaf at save cadence, not per step.
        finite = all(
            bool(jnp.all(jnp.isfinite(leaf)))
            for leaf in jax.tree_util.tree_leaves(self.state.params)
            if jnp.issubdtype(leaf.dtype, jnp.floating)
        )
        if not finite:
            logger.error(
                "Refusing to checkpoint non-finite params at step %d; "
                "keeping the last good checkpoint", self.global_step,
            )
            return None
        # Sidecar and payload must stay in sync: CheckpointManager.save
        # skips an existing COMMITTED step directory, so a pre-existing
        # payload (a reused checkpoint_dir) must not get its topology
        # overwritten — but uncommitted junk from a crashed save IS
        # rewritten by save(), so its sidecar must be rewritten with it.
        already = self.checkpointer.check_integrity(
            self.global_step, verify=False
        )[0]
        path = self.checkpointer.save(
            self.state, self.global_step,
            block=not self.config.async_checkpoint,
        )
        if self.obs is not None:
            self.obs.trace.emit(EventType.CKPT_SAVE, step=self.global_step,
                                path=path,
                                blocking=not self.config.async_checkpoint)
        if already:
            logger.warning(
                "Checkpoint step %d already existed; keeping its sidecar "
                "(payload was not rewritten)", self.global_step,
            )
            return path
        # Topology sidecar: after elastic eviction the live node count and
        # coordinate->identity map differ from the constructor config; a
        # resume needs them BEFORE it can shape the restore template.
        self.checkpointer.save_metadata(self.global_step, {
            "num_nodes": self.config.num_nodes,
            "node_map": list(self.node_map),
            "parallelism": self.config.parallelism,
            # The live mesh's device ids: after an eviction the mesh is NOT
            # "the first n devices" (the evicted chip is missing from the
            # middle), and a resume that guessed would collide with the
            # evicted device on readmission.
            "mesh_devices": [d.id for d in self.mesh.devices.flat],
            # Evicted identities have no device row anymore; their
            # compromised standing must survive the resume on the host.
            "compromised_nodes": sorted(
                int(i) for i in self.trust_manager.get_compromised_nodes()
            ),
            # Elastic bookkeeping: a pending readmission cool-off and
            # idle-pool identities must survive a resume — without them an
            # eviction silently becomes permanent despite
            # readmit_after_steps>0, and parked restaff survivors can never
            # re-enter.  Devices persist by id and re-resolve on the
            # resumed host.
            "evicted_at": {
                str(nid): int(step)
                for nid, step in self._evicted_at.items()
            },
            "evicted_devices": {
                str(nid): [d.id for d in (devs or [])]
                for nid, devs in self._evicted_devices.items()
            },
            "idle_pool": {
                str(nid): [d.id for d in devs]
                for nid, devs in self._idle_pool.items()
            },
        })
        return path

    def _adopt_topology(self, meta: Dict[str, Any]) -> None:
        """Rebuild mesh/step/template for a checkpoint saved on a different
        (post-eviction) node count — SURVEY §5.4's resume requirement."""
        import dataclasses

        from trustworthy_dl_tpu.elastic.reassignment import ELASTIC_MODES

        if self.config.parallelism not in ELASTIC_MODES + ("model",):
            raise NotImplementedError(
                "post-eviction resume onto a different node count is only "
                "defined for the modes eviction itself supports "
                "(elastic/reassignment.py ELASTIC_MODES + "
                "elastic/restaff.py)"
            )
        n = int(meta["num_nodes"])
        logger.info(
            "Checkpoint topology has %d node(s) (config says %d): adopting "
            "the saved topology for resume", n, self.config.num_nodes,
        )
        from trustworthy_dl_tpu.elastic.reassignment import (
            _check_hybrid_elastic,
            elastic_mesh_shape,
        )

        self.config = dataclasses.replace(
            self.config, num_nodes=n,
            mesh_shape=elastic_mesh_shape(self.config, n),
        )
        if self.config.parallelism == "hybrid":
            # Only elastic-eligible hybrid layouts can have produced a
            # different-topology checkpoint; a multi-slice/stage hybrid
            # must fail loudly here rather than silently rebuild a
            # single-slice mesh without its DCN extents.
            _check_hybrid_elastic(self.config)
        # Rebuild the SAVED device set when the sidecar has it: post-
        # eviction the live mesh is missing a chip from the middle, and a
        # first-n guess would seat the evicted device twice once it is
        # readmitted.
        devices = None
        ids = meta.get("mesh_devices")
        if ids is not None:
            by_id = {d.id: d for d in jax.devices()}
            devs = [by_id[i] for i in ids if i in by_id]
            if len(devs) == len(ids):
                devices = devs
        self.mesh = build_mesh(n, self.config.parallelism,
                               self.config.mesh_shape, devices=devices,
                               dcn_mesh_shape=self.config.dcn_mesh_shape)
        bind_mode_mesh(self.mesh, self.config.parallelism)
        self._build_steps()
        self.node_map = [int(i) for i in meta["node_map"]]
        # Any attack plan was shaped for the constructor's node count;
        # injection targets are per-run anyway — reset, caller re-plans.
        # Placed on the rebuilt mesh here (initialize() would re-place it
        # too, but the invariant "attack_plan is always mesh-committed"
        # must not depend on which caller runs next).
        self.attack_plan = self._place_plan(null_plan(n))
        self.state = None  # template must be rebuilt with the new shapes
        if self.obs is not None and \
                getattr(self.obs, "compilewatch", None) is not None:
            # The step was legitimately rebuilt for the new topology —
            # its next compile is warmup, not a storm.
            self.obs.compilewatch.reset("train_step")

    def load_checkpoint(self, step: Optional[int] = None) -> TrainState:
        """Restore the full world-view — weights AND trust state — then
        mirror into the host objects.  A checkpoint written after elastic
        eviction (fewer live nodes than the constructor config) restores
        onto the saved topology."""
        if step is None:
            step = self.checkpointer.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoints under {self.config.checkpoint_dir}"
                )
        meta = self.checkpointer.load_metadata(step)
        if meta and int(meta["num_nodes"]) != self.config.num_nodes:
            self._adopt_topology(meta)
        if self.state is None:
            self.initialize()
        self.state = self.checkpointer.restore(self.state, step)
        # Two resume hazards fixed here, in order:
        # 1. Ownership: on CPU-backed platforms the checkpoint reader can
        #    hand back arrays that zero-copy alias ITS host memory, and
        #    the train step's donate_argnums would then free buffers XLA
        #    does not own (observed as intermittent heap corruption a few
        #    dozen donated steps after any resume).  The eager copy
        #    re-homes every leaf into runtime-owned buffers.
        # 2. Placement: a leaf the host replaced mid-run with an
        #    uncommitted array (e.g. _epoch_intelligence's threshold
        #    push-back) restores COMMITTED to device 0, and the next step
        #    would refuse to mix it with mesh-committed params —
        #    _place_on_mesh re-pins everything exactly like initialize().
        self.state = self._place_on_mesh(
            jax.tree_util.tree_map(jnp.copy, self.state)
        )
        if meta:
            self.node_map = [int(i) for i in meta["node_map"]]
            # Original ids can exceed the constructor's node count (e.g. a
            # fresh trainer built with the post-eviction live count): grow
            # the host bookkeeping so no live identity is silently dropped
            # by the sync scatter's bounds filter.
            max_id = max(
                self.node_map + [int(i) for i in
                                 meta.get("compromised_nodes", [])],
                default=-1,
            )
            if max_id >= self.trust_manager.num_nodes:
                self.trust_manager.initialize_node(max_id)
            live = set(self.node_map)
            for node_id in meta.get("compromised_nodes", []):
                node_id = int(node_id)
                if node_id not in live and (
                    self.trust_manager.get_node_status(node_id)
                    != NodeStatus.COMPROMISED
                ):
                    # Evicted before the save: no device row to sync from,
                    # so restore the host-side standing directly (once —
                    # repeated restores must not duplicate attack records).
                    self.trust_manager.mark_compromised(
                        node_id, attack_type="restored_from_checkpoint"
                    )
            # Rehydrate elastic bookkeeping so pending readmission
            # cool-offs and parked idle-pool identities survive the resume
            # (devices re-resolve by id; one lost to a host change degrades
            # to the dev-mode no-device path rather than dropping the
            # identity).
            by_id = {d.id: d for d in jax.devices()}
            self._evicted_at = {
                int(k): int(v)
                for k, v in meta.get("evicted_at", {}).items()
            }
            self._evicted_devices = {
                int(k): [by_id[i] for i in ids if i in by_id]
                for k, ids in meta.get("evicted_devices", {}).items()
            }
            self._idle_pool = {
                int(k): [by_id[i] for i in ids if i in by_id]
                for k, ids in meta.get("idle_pool", {}).items()
            }
        self.global_step = int(self.state.step)
        # A restore redraws the fleet's status rows; transition tracking
        # must re-anchor or the first post-resume step emits bogus diffs.
        self._last_status = None
        if self.obs is not None:
            self.obs.trace.emit(EventType.CKPT_RESTORE, step=step,
                                restored_step=self.global_step)
        self.sync_host_state()
        return self.state

    def cleanup(self) -> None:
        """distributed_trainer.py:523-527."""
        self.checkpointer.wait()  # join any in-flight async save
        self.metrics_collector.close()  # flush + release the TB writer
        self.state = None
        logger.info("Distributed training cleanup completed")
