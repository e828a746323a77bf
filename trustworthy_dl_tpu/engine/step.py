"""The trusted train step — one jitted SPMD program per batch.

This is the TPU-native re-design of the reference's per-batch loop
(distributed_trainer.py:382-428): forward, detection, backward, gradient
verification, trust update, trust-gated aggregation and the optimizer step
all trace into a single XLA program.  The reference's per-node Python loop
(:148-175) becomes a vmapped node axis; when the node axis is laid over the
mesh's 'data' axis, the trust-gated weighted mean over nodes lowers to a
weighted psum over ICI — the keystone collective (SURVEY §2.5).

Execution order per step (mirroring the reference's loop semantics):
  1. poison batch (attack injection, experiment-controlled)     [:187-188]
  2. per-node forward + loss + output stats                     [:148-175]
  3. per-node grads; poison gradients (injection)               [:177-195]
  4. detector verdicts on output & gradient stat batteries      [:168,:199]
  5. gradient verification (finite + norm z-score)              [:199-205]
  6. mark compromised (detected ∪ unverified)                   [:293,:319]
  7. trust update from output-deviation / gradient-consistency  [:209-226]
  8. trust-gated weighted gradient aggregation  ← fixes :441-446
  9. optimizer update; monitor absorbs clean samples
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from trustworthy_dl_tpu.attacks.adversarial import AttackPlan, poison_batch, \
    poison_gradients
from trustworthy_dl_tpu.core.config import TrainingConfig
from trustworthy_dl_tpu.detect import baseline as bl
from trustworthy_dl_tpu.detect import stats as st
from trustworthy_dl_tpu.detect.detector import Verdicts, anomaly_verdicts
from trustworthy_dl_tpu.detect.verifier import absorb_norms, \
    fleet_surge_update, norm_suspicions
from trustworthy_dl_tpu.engine.state import MonitorState, TrainState, \
    update_monitor
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.models.factory import ModelBundle
from trustworthy_dl_tpu.trust import state as ts

Array = jax.Array


def _gradient_stat_vector(grads: Any, max_sort: int) -> Tuple[Array, Array, Array]:
    """17-stat battery for one node's gradients (+ leaf norms, finite flag).
    Matches detect/stats.gradient_statistics column layout.

    Streaming: per-leaf fused reductions combined via raw moments — the full
    gradient vector is never concatenated (that cost O(P) extra HBM traffic
    per node per step).  Order statistics and the intra-step cosine signal
    run on the deterministic ≤max_sort subsample, keeping the rolling
    baselines self-consistent."""
    leaves = [g.reshape(-1).astype(jnp.float32)
              for g in jax.tree_util.tree_leaves(grads)]
    base, leaf_norms, finite, sample = st.leafwise_statistics(leaves, max_sort)
    extra = jnp.stack(
        [
            jnp.asarray(float(len(leaves)), jnp.float32),
            jnp.mean(leaf_norms),
            jnp.std(leaf_norms),
            jnp.max(leaf_norms),
            st.chunked_cosine_mean(sample),
        ]
    )
    return jnp.concatenate([base, extra]), leaf_norms, finite


def _output_stat_vector(logits: Array, max_sort: int) -> Array:
    """17-padded output battery (12 real stats + zero padding), streaming
    (raw-moment single pass — logits can be b·T·V ≈ 10⁷ elements/node).
    The bf16→f32 cast stays fused inside the reductions: materialising a
    f32 copy of the logits costs more than the whole battery."""
    flat = logits.reshape(-1)
    base, _, _, _ = st.leafwise_statistics([flat], max_sort)
    pad = jnp.zeros((st.NUM_GRADIENT_STATS - st.NUM_TENSOR_STATS,), jnp.float32)
    return jnp.concatenate([base, pad])


def guarded_update(do_update: Array, optimizer: optax.GradientTransformation,
                   grads: Any, opt_state: Any, params: Any
                   ) -> Tuple[Any, Any]:
    """Apply the optimizer only when ``do_update`` (traced bool[]) holds;
    otherwise params AND opt_state pass through unchanged.  Merely zeroing
    the gradients is not a skip for stateful optimizers: AdamW would still
    move every parameter from stale momentum plus decoupled weight decay —
    an update with no trusted gradient behind it."""
    updates, opt_new = optimizer.update(grads, opt_state, params)
    params_new = optax.apply_updates(params, updates)
    sel = lambda new, old: jnp.where(do_update, new, old)
    return (jax.tree_util.tree_map(sel, params_new, params),
            jax.tree_util.tree_map(sel, opt_new, opt_state))


def _median_mad(values: Array) -> Tuple[Array, Array, Array]:
    """[n, d] -> (median [1, d], |dev| [n, d], σ-consistent MAD [1, d]).

    The single cross-node robust-location/scale statistic behind all three
    cross-sectional checks (score gate, hard verdict, log-norm gate) —
    they differ only in the floor applied to the MAD and the aggregation.
    MAD is scaled by 1.4826 to be σ-consistent under normality."""
    med = jnp.median(values, axis=0, keepdims=True)
    abs_dev = jnp.abs(values - med)
    mad = jnp.median(abs_dev, axis=0, keepdims=True) * 1.4826
    return med, abs_dev, mad


def _cross_sectional_score(stats: Array) -> Array:
    """f32[n]: mean robust z of each node's stat vector against the
    *current-step* cross-node distribution (median/MAD).

    Rationale: in SPMD all nodes share parameters, so legitimate training
    dynamics (early-phase drift of logits/gradient scales) shift every
    node's statistics together — temporal z-scores alone read that drift as
    an anomaly.  An actual attack perturbs one node *relative to its peers*,
    which this measure isolates; it assumes a majority of honest nodes
    (standard Byzantine setting).
    """
    _, abs_dev, mad = _median_mad(stats)
    usable = mad[0] > 1e-12
    z = jnp.where(usable[None, :], abs_dev / jnp.maximum(mad, 1e-12), 0.0)
    return jnp.sum(z, axis=1) / jnp.maximum(jnp.sum(usable), 1)


CROSS_SECTIONAL_THRESHOLD = 3.0

# Hard cross-sectional verdict threshold (see _hard_cross_outliers).
HARD_CROSS_Z = 25.0

# Log-norm cross-sectional gate: MAD floor 0.1 in log-space ≈ 10 % norm
# spread (honest per-node batch variation); outlier beyond 3 robust σ.
NORM_CROSS_Z = 3.0
NORM_MAD_FLOOR = 0.1


def _hard_cross_outliers(stats: Array) -> Array:
    """bool[n]: nodes whose battery is an *astronomical* outlier vs their
    peers this step — median/MAD with a floor RELATIVE to the median (5 %),
    so only order-of-magnitude deviations fire, never honest batch noise.

    This is the baseline-poisoning-proof detection path: temporal z-scores
    are blind to an attack live from step 0 (the rolling baseline never
    sees clean data to deviate from), but in SPMD all nodes share params,
    so a node whose gradient/output statistics sit 25+ robust σ from the
    cross-node median is compromised regardless of history.  Assumes a
    majority of honest nodes (standard Byzantine setting); requires ≥4
    nodes like the cross-sectional gate."""
    med, abs_dev, mad = _median_mad(stats)
    floor = jnp.maximum(0.05 * jnp.abs(med), 1e-6)
    z = abs_dev / jnp.maximum(mad, floor)
    return jnp.mean(z, axis=1) > HARD_CROSS_Z


# Cross-node loss outlier: one-sided robust z above which a node's loss
# has detached from the fleet (floor ≈ honest shard-difficulty spread).
LOSS_CROSS_Z = 6.0
LOSS_MAD_FLOOR_REL = 0.05
LOSS_MAD_FLOOR_ABS = 0.02


def _loss_cross_outliers(losses: Array) -> Array:
    """bool[n]: node whose per-shard loss sits far ABOVE the cross-node
    median — the data-poisoning signature the stat batteries cannot see.

    A scrambled-token / shifted-label shard produces gradients and
    activations statistically close to honest ones (measured: full-
    intensity data poisoning moves every battery z < 2), but the node can
    never FIT its corrupted data: all nodes share parameters, so while
    honest shards' losses fall together, the poisoned shard's loss
    detaches upward and stays detached.  One-sided (above median only —
    a lucky low-loss shard is not evidence of attack), median/MAD with a
    relative floor for honest shard-difficulty spread, and the standard
    two-consecutive-steps debounce + warmup gate at the call site.
    This check has no reference analogue: detect_output_anomaly
    (attack_detector.py:71-107) watched output tensors only and was blind
    to exactly this attack class."""
    med = jnp.median(losses)
    dev = losses - med
    mad = jnp.median(jnp.abs(dev)) * 1.4826
    floor = jnp.maximum(LOSS_MAD_FLOOR_REL * jnp.abs(med),
                        LOSS_MAD_FLOOR_ABS)
    z = dev / jnp.maximum(mad, floor)
    return z > LOSS_CROSS_Z


def _norm_cross_outliers(global_norms: Array) -> Array:
    """bool[n]: cross-sectional outlier gate on the per-node log gradient
    norm.  In SPMD all nodes share params, so legitimate norm drift
    (early-training decay, loss-plateau shifts) moves every node's temporal
    z together; a real inflation attack makes the node an outlier vs its
    peers *this step*."""
    log_norm = jnp.log(jnp.maximum(global_norms, 1e-30))
    _, abs_dev, mad = _median_mad(log_norm[:, None])
    z = abs_dev / jnp.maximum(mad, NORM_MAD_FLOOR)
    return z[:, 0] > NORM_CROSS_Z


class StepMetrics(NamedTuple):
    loss: Array               # f32[] aggregate (trust-weighted)
    per_node_loss: Array      # f32[n]
    trust_scores: Array       # f32[n]
    status: Array             # i32[n]
    attacked: Array           # bool[n] confirmed (debounced) verdicts this step
    verified: Array           # bool[n] gradient verification passed
    finite: Array             # bool[n] gradients free of NaN/Inf
    weights: Array            # f32[n] contribution gate actually used
    system_trust: Array       # f32[]
    grad_norm: Array          # f32[]  aggregated gradient norm
    out_score: Array          # f32[n] output anomaly score
    grad_score: Array         # f32[n] gradient anomaly score
    attack_type: Array        # i32[n] classifier output (valid iff attacked)
    byzantine: Array          # bool[n]
    backdoor: Array           # bool[n]
    out_stats: Array          # f32[n, 17] output stat battery (ML-tier feed)
    grad_stats: Array         # f32[n, 17] gradient stat battery
    # Model-specific diagnostics averaged over nodes (e.g. MoE
    # {"moe_drop_fraction"}: share of routed assignments dropped at expert
    # capacity — invisible in the loss on any single step).  None for
    # models/modes that report none — a None SENTINEL, not a shared {}
    # literal: a mutable NamedTuple default is one dict instance shared by
    # every StepMetrics ever constructed without the field, so an in-place
    # mutation by any consumer would leak across steps and trainers.
    # Read sites normalise with ``metrics.model_aux or {}``.
    model_aux: Optional[Dict[str, Array]] = None
    # Fleet-level norm-surge alarm (bool[], debounced) — the
    # majority-attack backstop; None when the step doesn't compute it
    # (pipeline mode, verification off).
    fleet_alert: Any = None


class HostMetricsPacker:
    """Packs the host-facing slice of a step's outputs into ONE flat f32
    device array so the per-step device→host traffic is a single transfer
    whose copy can start asynchronously (``copy_to_host_async``) while the
    next step dispatches — the engine of the async host pipeline
    (engine/async_host.py).

    The synchronous host path pulls ~10 separate arrays per step
    (``float(metrics.loss)`` + per-field ``np.asarray`` in
    ``_record_batch``), each a blocking round-trip.  The packer instead
    concatenates every ``StepMetrics`` leaf (plus the post-step
    ``fleet_raw_streak``, which the drain needs at its *step-time* value —
    by drain time ``trainer.state`` has moved on) into one vector inside a
    tiny jitted program, and ``unpack`` restores the exact original
    dtypes/shapes host-side, so the drained metrics are bit-identical to
    what the synchronous path would have read.

    All packed dtypes survive the f32 round-trip exactly: bool → {0.0, 1.0}
    → bool, and the i32 fields (status, attack_type) hold values far below
    2**24.  The layout is frozen from a template step's structure; a
    topology change (elastic eviction/readmission) changes the node count,
    which ``matches`` detects so the pipeline rebuilds the packer.
    """

    def __init__(self, metrics: StepMetrics, fleet_streak: Any = None):
        self._layout: list = []  # (key, shape, size, dtype)
        offset = 0
        for key, leaf in self._leaves(metrics, fleet_streak):
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            self._layout.append((key, tuple(leaf.shape), size,
                                 np.dtype(leaf.dtype)))
            offset += size
        self.total = offset
        self.num_nodes = int(metrics.trust_scores.shape[0])
        self._jit_pack = jax.jit(self._pack_impl)

    @staticmethod
    def _leaves(metrics: StepMetrics, fleet_streak: Any):
        """Deterministic (key, array) walk shared by layout and pack."""
        for name in StepMetrics._fields:
            value = getattr(metrics, name)
            if name == "model_aux":
                for k in sorted(value or {}):
                    yield f"model_aux:{k}", value[k]
            elif value is not None:
                yield name, value
        if fleet_streak is not None:
            yield "fleet_raw_streak", fleet_streak

    def matches(self, metrics: StepMetrics, fleet_streak: Any = None) -> bool:
        """Same structure/shapes as the template this packer was built on?"""
        probe = [(k, tuple(v.shape)) for k, v in
                 self._leaves(metrics, fleet_streak)]
        return probe == [(k, s) for k, s, _, _ in self._layout]

    def _pack_impl(self, metrics: StepMetrics, fleet_streak: Any
                   ) -> jax.Array:
        parts = [leaf.astype(jnp.float32).reshape(-1)
                 for _, leaf in self._leaves(metrics, fleet_streak)]
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def pack(self, metrics: StepMetrics, fleet_streak: Any = None
             ) -> jax.Array:
        """One flat f32[total] device array; dispatch only, no host sync."""
        packed = self._jit_pack(metrics, fleet_streak)
        # Start the device→host copy now so it overlaps the next step's
        # dispatch/execution; by drain time np.asarray is (near) free.
        copy_async = getattr(packed, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()
        return packed

    def unpack(self, flat: np.ndarray) -> Tuple[StepMetrics, Any]:
        """(StepMetrics with numpy leaves, fleet_raw_streak or None) from
        the pulled flat vector — original dtypes and shapes restored."""
        flat = np.asarray(flat)
        fields: Dict[str, Any] = {"model_aux": None, "fleet_alert": None}
        aux: Dict[str, Any] = {}
        streak = None
        offset = 0
        for key, shape, size, dtype in self._layout:
            chunk = flat[offset:offset + size].astype(dtype).reshape(shape)
            offset += size
            if key.startswith("model_aux:"):
                aux[key.split(":", 1)[1]] = chunk
            elif key == "fleet_raw_streak":
                streak = chunk
            else:
                fields[key] = chunk
        if aux:
            fields["model_aux"] = aux
        return StepMetrics(**fields), streak


def build_train_step(
    bundle: ModelBundle,
    config: TrainingConfig,
    optimizer: optax.GradientTransformation,
    num_classes: Optional[int] = None,
    max_sort: int = 16384,
) -> Callable[[TrainState, Dict[str, Array], AttackPlan],
              Tuple[TrainState, StepMetrics]]:
    """Build the jitted train step for ``num_nodes`` logical nodes.

    The returned function expects batches with a leading node axis:
    {'input': [n, b, ...], 'target': [n, b, ...]} — the trainer reshapes the
    global batch (and shards the node axis over the mesh's 'data' axis on
    real hardware).
    """
    n_nodes = config.num_nodes
    detection = config.attack_detection_enabled
    verification = config.gradient_verification_enabled
    if num_classes is None:
        num_classes = bundle.input_spec.get(
            "num_classes", bundle.input_spec.get("vocab_size", 2)
        )

    def node_loss(params, node_batch):
        # Detector signals ride on `feats` — the node-boundary activations
        # (what the reference's per-partition hook watched,
        # distributed_trainer.py:160-170).  For LMs these are ~65× smaller
        # than the logits, keeping the battery off the CE-loss fusion path.
        model_aux = {}
        if bundle.loss_monitor is not None:
            # Loss-bearing path: lets the model fuse head+CE (the vocab-
            # chunked fused head never materialises logits at all).  A
            # 4th element, when present, is a dict of model diagnostics
            # (MoE capacity-drop fraction) surfaced into StepMetrics.
            out = bundle.loss_monitor(params, node_batch)
            loss, feats, mean_logits = out[:3]
            if len(out) > 3:
                model_aux = out[3]
        elif bundle.apply_monitor is not None:
            logits, feats, mean_logits = bundle.apply_monitor(
                params, node_batch["input"]
            )
            loss = L.cross_entropy_loss(logits, node_batch["target"])
        else:
            logits = bundle.apply(params, node_batch["input"])
            feats = logits
            lead = tuple(range(logits.ndim - 1))
            mean_logits = jnp.mean(logits.astype(jnp.float32), axis=lead)
            loss = L.cross_entropy_loss(logits, node_batch["target"])
        out_stats = _output_stat_vector(feats, max_sort)
        aux = (out_stats, jnp.mean(feats), jnp.std(feats), mean_logits,
               model_aux)
        return loss, aux

    grad_fn = jax.value_and_grad(node_loss, has_aux=True)

    accum = max(int(getattr(config, "grad_accum_steps", 1)), 1)
    if accum > 1:
        base_grad_fn = grad_fn

        def grad_fn(params, node_batch):  # noqa: F811 — accumulated variant
            """Sequential microbatches inside the step (lax.scan):
            gradients/losses are averaged (exactly the full-batch mean for
            equal-size microbatches of a mean loss); mean_logits averages
            (linear, exact); the stat batteries combine across microbatches
            with per-column reducers (combine_microbatch_stats: min/max/linf
            keep their extreme-value semantics, sum-moments average), so
            output-anomaly detection sees every microbatch — a corruption
            confined to a single microbatch still moves the battery at full
            strength."""
            mbs = jax.tree_util.tree_map(
                lambda v: v.reshape((accum, v.shape[0] // accum)
                                    + v.shape[1:]),
                node_batch,
            )

            def body(carry, mb):
                loss_sum, grad_sum, ml_sum = carry
                (loss, aux), g = base_grad_fn(params, mb)
                out_stats, f_mean, f_std, ml, model_aux = aux
                carry = (
                    loss_sum + loss,
                    jax.tree_util.tree_map(jnp.add, grad_sum, g),
                    ml_sum + ml,
                )
                return carry, (out_stats, f_mean, f_std, model_aux)

            init = (
                jnp.zeros((), jnp.float32),
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                ),
                jnp.zeros((num_classes,), jnp.float32),
            )
            (loss_sum, grad_sum, ml_sum), stacked = jax.lax.scan(
                body, init, mbs
            )
            from trustworthy_dl_tpu.detect.stats import (
                combine_microbatch_stats,
            )

            stacked_stats, f_means, f_stds, stacked_model_aux = stacked
            out_stats = combine_microbatch_stats(stacked_stats)
            f_mean = jnp.mean(f_means, axis=0)
            f_std = jnp.mean(f_stds, axis=0)
            # Model diagnostics are per-microbatch means -> average them.
            model_aux = jax.tree_util.tree_map(
                lambda v: jnp.mean(v, axis=0), stacked_model_aux
            )
            inv = 1.0 / accum
            grads = jax.tree_util.tree_map(lambda g: g * inv, grad_sum)
            aux = (out_stats, f_mean, f_std, ml_sum * inv, model_aux)
            return (loss_sum * inv, aux), grads

    def train_step(state: TrainState, batch: Dict[str, Array],
                   plan: AttackPlan) -> Tuple[TrainState, StepMetrics]:
        # The named scopes are metadata only (the compiled program is the
        # same): each op's ``op_name`` carries its scope, which a
        # ``profile_dir`` trace shows, and backward ops inside
        # ``train.fwd_bwd`` carry ``transpose(`` besides.  Every equation of
        # this function stands under one (tests/test_profiling.py), in the
        # order it always had: a moved equation is another program to the
        # compile cache.
        with jax.named_scope("attack.inject"):
            rng, k_data, k_grad = jax.random.split(state.rng, 3)
        with jax.named_scope("trust.update"):
            now = state.step.astype(jnp.float32) * config.time_per_step

        with jax.named_scope("attack.inject"):
            # 1. Attack injection on the data path (before forward, so output
            # anomalies arise organically).  lax.cond skips the corruption work
            # entirely on clean steps while keeping activation recompile-free.
            batch = jax.lax.cond(
                plan.is_live(state.step),
                lambda b: poison_batch(plan, b, state.step, k_data,
                                       num_classes),
                lambda b: b,
                batch,
            )

        with jax.named_scope("train.fwd_bwd"):
            # 2-3. Per-node forward/backward.  vmap over the node axis — on a
            # ('data',)-sharded mesh each node's compute stays on its device
            # and the later weighted reduction becomes the psum.
            (losses, aux), grads = jax.vmap(grad_fn, in_axes=(None, 0))(
                state.params, batch
            )
            out_stats, out_mean, out_std, mean_logits, model_aux = aux
            # Per-node diagnostics -> fleet mean (capacity health, not a
            # per-node detection signal).
            model_aux = jax.tree_util.tree_map(
                lambda v: jnp.mean(v, axis=0), model_aux
            )
        with jax.named_scope("attack.inject"):
            grads = jax.lax.cond(
                plan.is_live(state.step),
                lambda g: poison_gradients(plan, g, state.step, k_grad),
                lambda g: g,
                grads,
            )

        with jax.named_scope("trust.grad_stats"):
            # Per-node gradient batteries.
            grad_stats, leaf_norms, finite = jax.vmap(
                lambda g: _gradient_stat_vector(g, max_sort)
            )(grads)
            global_norms = jnp.sqrt(
                jnp.sum(leaf_norms * leaf_norms, axis=1)
            )  # f32[n]

        with jax.named_scope("trust.verify"):
            # 4. Gradient verification verdict
            # (distributed_trainer.py:199-205).  Pure read — the Welford
            # baseline absorbs AFTER the detector block below, according to
            # the FINAL clean-this-step judgement: a node excluded for a
            # suspect norm must not push its stats into any rolling window
            # (attack drags its own baseline), while a shared legitimate norm
            # shift every node exhibits at once must still be absorbed (else z
            # never recovers and training freezes).
            finite_b = finite.astype(bool)
            if verification:
                norm_suspect = norm_suspicions(state.verifier, global_norms)
                if n_nodes >= 4:
                    # Cross-sectional gate (see _norm_cross_outliers): only a
                    # node that is also an outlier vs its peers this step stays
                    # suspect — shared drift is legitimate.
                    norm_suspect = norm_suspect & _norm_cross_outliers(
                        global_norms
                    )
            else:
                norm_suspect = jnp.zeros_like(finite_b)
            # The acted-on verdict: finite AND not (gated) norm-suspect.  Uses
            # the post-gate suspicion so a fleet-wide legitimate shift can
            # never zero every node's weight and stall training.
            verified = finite_b & ~norm_suspect

            # 4b. Fleet-level norm-surge alarm (majority-attack backstop).
            # The cross-sectional gate above deliberately clears suspicions
            # every node shares — which also blinds it when >= 50 % of the
            # fleet inflates norms together (the median itself is poisoned;
            # boundary measured in tests/test_adaptive_attacker.py).  The
            # MEDIAN log-norm z-scored against its OWN Welford history sees
            # exactly that case: a fleet-wide 10x surge is steps, not drift.
            # The alarm is UNATTRIBUTED (no node is gated or evicted by it —
            # with a poisoned median there is no trustworthy attribution);
            # the host surfaces it as a fleet incident for operator action.
            # Clean-only absorption: surge steps never enter the baseline.
            if verification and state.fleet_norm is not None:
                fleet_median = jnp.median(global_norms)[None]        # f32[1]
                _, new_fleet_norm, new_fleet_streak = fleet_surge_update(
                    state.fleet_norm, fleet_median, state.fleet_raw_streak
                )
                # 2-step debounce, same spirit as the per-node verdicts.
                fleet_alert = (new_fleet_streak >= 2)[0]
            else:
                fleet_alert = None
                new_fleet_norm = state.fleet_norm
                new_fleet_streak = state.fleet_raw_streak

        with jax.named_scope("trust.detect"):
            # 5. Detector verdicts (attack_detector.py:71-141), plus the
            # Byzantine cross-node check (:143-162) and consensus-KL backdoor
            # check (:164-183) the reference defined but never wired in.
            if detection:
                # Deliberate deviation from the reference's ordering
                # (attack_detector.py:84-100 appends the current sample before
                # building the baseline it z-scores against): a single outlier
                # among k window samples is then bounded at z ≤ (k-1)/√k, so
                # with short histories detection *mathematically cannot* fire.
                # We score against the past-only window, then absorb the sample
                # into the baseline only if it wasn't flagged — which also
                # stops an attacker from slow-boiling the baseline toward the
                # attack.
                out_v = anomaly_verdicts(
                    out_stats, state.out_baseline,
                    warmup=config.detector_warmup,
                )
                grad_v = anomaly_verdicts(
                    grad_stats, state.grad_baseline,
                    warmup=config.detector_warmup,
                )
                if n_nodes >= 4:
                    # Temporal z alone reads shared training drift as anomaly;
                    # require the node to also be a cross-node outlier *this
                    # step* (see _cross_sectional_score).
                    out_cross = _cross_sectional_score(out_stats)
                    grad_cross = _cross_sectional_score(grad_stats)
                    out_v = out_v._replace(
                        is_attack=out_v.is_attack
                        & (out_cross > CROSS_SECTIONAL_THRESHOLD)
                    )
                    grad_v = grad_v._replace(
                        is_attack=grad_v.is_attack
                        & (grad_cross > CROSS_SECTIONAL_THRESHOLD)
                    )
                # Byzantine cross-node comparison on softmax *signatures* of
                # the mean logits: probability vectors are positive, so honest
                # nodes (same params, same data distribution) sit near cosine 1
                # while a garbage-output node diverges hard — raw mean logits
                # at init are near-zero noise and would false-positive.
                # Warm-up gated like the statistical detectors
                # (attack_detector.py:91).
                warm_nodes = state.out_baseline.count >= config.detector_warmup
                if n_nodes >= 3:
                    signatures = jax.nn.softmax(mean_logits, axis=-1)
                    byz = st.byzantine_verdicts(signatures) & warm_nodes
                else:
                    byz = jnp.zeros((n_nodes,), bool)
                # Backdoor: each node's mean output distribution vs the
                # cross-node consensus (replicated-canary style, SURVEY
                # §7.4(4)).
                consensus = jnp.mean(mean_logits, axis=0, keepdims=True)
                kl = jax.vmap(
                    lambda m: st.backdoor_divergence(m[None, :], consensus)
                )(mean_logits)
                backdoor = (kl > 2.0) & warm_nodes
                # Per-node loss detachment (see _loss_cross_outliers): the one
                # signal a data-poisoned shard cannot hide.  ≥4 nodes for a
                # meaningful median/MAD, warm-gated like the batteries.
                if n_nodes >= 4:
                    loss_outlier = _loss_cross_outliers(losses) & warm_nodes
                else:
                    loss_outlier = jnp.zeros((n_nodes,), bool)
                candidates = (out_v.is_attack | grad_v.is_attack | byz
                              | backdoor | loss_outlier)
                if n_nodes >= 4:
                    # Hard cross-sectional verdict: catches attacks live from
                    # step 0, which the temporal batteries cannot (their
                    # baselines never saw clean data) — see
                    # _hard_cross_outliers.
                    candidates = candidates | _hard_cross_outliers(out_stats) \
                        | _hard_cross_outliers(grad_stats)
                # Absorb this step's stats into the rolling baselines only for
                # nodes with NO suspicion of any kind this step — battery,
                # byzantine/backdoor, verifier norm_suspect, or non-finite
                # gradients — an attacker must not drag its own baseline.
                clean_now = ~(candidates | norm_suspect | ~finite_b)
                out_bl = bl.push_stats(state.out_baseline, out_stats,
                                       mask=clean_now)
                grad_bl = bl.push_stats(state.grad_baseline, grad_stats,
                                        mask=clean_now)
                # Debounce: a candidate node is excluded from this step's
                # aggregation immediately (no poisoned gradient ever lands),
                # but is only *confirmed* compromised — trust nuked, incident
                # recorded — after two consecutive anomalous steps.  Real
                # attacks are sustained; single-step blips from small per-node
                # batches are not.
                attacked = candidates & state.prev_suspects
                out_score, grad_score = out_v.score, grad_v.score
                # Attribution ladder (VERDICT r3 weak #7): reference rule
                # labels where its rules really fired, explicit consensus
                # checks next, dominant-signature family instead of the
                # blanket "byzantine" default — see attribute_attack.
                from trustworthy_dl_tpu.detect.detector import attribute_attack

                attack_type = attribute_attack(grad_v, out_v, byz, backdoor,
                                               loss_outlier)
            else:
                out_bl, grad_bl = state.out_baseline, state.grad_baseline
                attacked = jnp.zeros((n_nodes,), bool)
                candidates = byz = backdoor = attacked
                out_score = grad_score = jnp.zeros((n_nodes,), jnp.float32)
                attack_type = jnp.zeros((n_nodes,), jnp.int32)
                clean_now = verified

            # Statistical norm suspicion joins the debounced candidate set: the
            # node is excluded from THIS step's aggregate (weights gate below)
            # but is only confirmed-compromised on the second consecutive hit —
            # a one-step z blip on a legitimate node must not nuke its trust.
            candidates = candidates | norm_suspect
            attacked = attacked | (norm_suspect & state.prev_suspects)

        with jax.named_scope("trust.verify"):
            # 5b. Verifier baseline absorption — the same clean-this-step rule
            # as the stat baselines (no candidate of any kind): a stats-visible
            # attacker must not drag the norm baseline either, while shared
            # legitimate norm shifts (cross-gate cleared) are absorbed so the
            # temporal z can recover.
            if verification:
                verifier = absorb_norms(state.verifier, global_norms,
                                        clean_now)
            else:
                verifier = state.verifier

        with jax.named_scope("trust.update"):
            # 6. Compromise marking (:273-299,:301-322 → trust_manager.py:183).
            # Immediate only for unambiguous evidence: confirmed (debounced)
            # verdicts and non-finite gradients.
            newly_compromised = attacked | ~finite_b
            trust = ts.mark_compromised(state.trust, newly_compromised)

            # 7. Trust-signal computation against the monitor's expected
            # behaviour (distributed_trainer.py:228-271) and the EMA update.
            warm = state.monitor.warm
            exp_mean = state.monitor.out_mean_avg
            exp_std = jnp.maximum(state.monitor.out_std_avg, 1e-6)
            mean_dev = jnp.abs(out_mean - exp_mean) / exp_std
            std_dev = jnp.abs(out_std - state.monitor.out_std_avg) / exp_std
            output_deviation = jnp.where(
                warm, jnp.minimum(1.0, (mean_dev + std_dev) / 2.0), 0.0
            )
            exp_norms = state.monitor.grad_norm_avg
            per_leaf = jnp.minimum(
                1.0, leaf_norms / jnp.maximum(exp_norms, 1e-12))
            usable = exp_norms > 0
            cons = jnp.sum(jnp.where(usable, per_leaf, 0.0), axis=1) \
                / jnp.maximum(jnp.sum(usable, axis=1), 1)
            gradient_consistency = jnp.where(warm, cons, 1.0)
            trust = ts.update_trust(
                trust, output_deviation, gradient_consistency, now,
                alpha=config.trust_alpha,
            )

            # 7b. Probation recovery (trust_manager.py:198-206 wired in): a
            # hard-gated node with recovery_probation_steps consecutive clean
            # steps re-enters as RECOVERING — its weight returns below, and the
            # status machine promotes it to TRUSTED once trust climbs.  A
            # single false positive costs bounded steps, not the run.
            trust, clean_streak = ts.probation_recovery(
                trust, state.clean_streak, verified & ~candidates,
                config.recovery_probation_steps,
            )

        with jax.named_scope("trust.aggregate"):
            # 8. Trust-gated aggregation — the psum the reference never issued
            # (SURVEY §2.5).  Gated-out nodes are hard-masked with jnp.where,
            # not merely scaled: 0 * NaN = NaN, so a node emitting non-finite
            # gradients would otherwise poison the aggregate despite its zero
            # weight.  When every node is gated out, the update is skipped
            # entirely (zero aggregate) — falling back to uniform weighting
            # would apply the very gradients that failed verification.
            weights = ts.contribution_weights(trust, verified & ~candidates)
            denom = jnp.sum(weights)
            inv = jnp.where(denom > 0, 1.0 / jnp.maximum(denom, 1e-30), 0.0)

            def _gate(g):
                mask = (weights > 0).reshape((n_nodes,) + (1,) * (g.ndim - 1))
                w = (weights * inv).astype(g.dtype)
                return jnp.einsum("n,n...->...", w, jnp.where(mask, g, 0))

            agg = jax.tree_util.tree_map(_gate, grads)

        with jax.named_scope("train.optimizer"):
            # 9. Optimizer + monitor absorption (clean samples only).  All
            # nodes gated -> full skip: params and optimizer state both freeze
            # (zeroed grads alone would still let AdamW's momentum/weight-decay
            # move the params).
            params, opt_state = guarded_update(
                denom > 0, optimizer, agg, state.opt_state, state.params
            )
        with jax.named_scope("trust.monitor"):
            absorb = verified & ~candidates
            monitor = update_monitor(state.monitor, out_mean, out_std,
                                     leaf_norms, absorb)

            agg_norm = optax.global_norm(agg)
            # Same masking for the reported loss: a gated node's (possibly NaN)
            # loss must not contaminate the aggregate.  All-gated → 0.0, with
            # weights all-zero in the metrics making the cause unambiguous.
            loss = jnp.sum(jnp.where(weights > 0, losses, 0.0) * weights) * inv
            new_state = TrainState(
                params=params,
                opt_state=opt_state,
                trust=trust,
                out_baseline=out_bl,
                grad_baseline=grad_bl,
                verifier=verifier,
                monitor=monitor,
                prev_suspects=candidates,
                step=state.step + 1,
                epoch=state.epoch,
                rng=rng,
                clean_streak=clean_streak,
                fleet_norm=new_fleet_norm,
                fleet_raw_streak=new_fleet_streak,
            )
            metrics = StepMetrics(
                loss=loss,
                per_node_loss=losses,
                trust_scores=trust.scores,
                status=trust.status,
                attacked=attacked,
                verified=verified,
                finite=finite_b,
                weights=weights,
                system_trust=ts.system_trust(trust),
                grad_norm=agg_norm,
                out_score=out_score,
                grad_score=grad_score,
                attack_type=attack_type,
                byzantine=byz,
                backdoor=backdoor,
                out_stats=out_stats,
                grad_stats=grad_stats,
                model_aux=model_aux,
                fleet_alert=fleet_alert,
            )
        return new_state, metrics

    return train_step


def build_eval_step(bundle: ModelBundle
                    ) -> Callable[[Any, Dict[str, Array]], Dict[str, Array]]:
    """Validation step (distributed_trainer.py:494-508): loss + accuracy on
    an un-noded batch, no detection machinery.  LMs with the fused
    vocab-chunked head keep its memory contract in eval too — the
    [B, T, V] logits never materialise."""
    chunk = getattr(bundle.config, "lm_head_chunk", 0)
    if bundle.kind == "lm" and chunk and "moe" not in bundle.name:
        from trustworthy_dl_tpu.models import gpt2 as _g
        from trustworthy_dl_tpu.ops.fused_ce import fused_lm_eval

        cfg = bundle.config

        def eval_step(params, batch):
            # "auto" resolves per shape at trace time (one predicate,
            # gpt2.resolve_lm_head_chunk) — same dispatch as training.
            c = _g.resolve_lm_head_chunk(cfg, int(batch["target"].size))
            if not c:
                logits = bundle.apply(params, batch["input"])
                return {
                    "loss": L.cross_entropy_loss(logits, batch["target"]),
                    "accuracy": L.accuracy(logits, batch["target"]),
                }
            x = _g.embed(params, batch["input"], cfg)
            x = _g.apply_blocks(params["blocks"], x, cfg)
            normed = L.layernorm(params["ln_f"], x)
            loss, acc = fused_lm_eval(normed, params["wte"],
                                      batch["target"], c, cfg.dtype)
            return {"loss": loss, "accuracy": acc}

        return eval_step

    def eval_step(params, batch):
        logits = bundle.apply(params, batch["input"])
        loss = L.cross_entropy_loss(logits, batch["target"])
        acc = L.accuracy(logits, batch["target"])
        return {"loss": loss, "accuracy": acc}

    return eval_step


def build_node_eval_step(bundle: ModelBundle
                         ) -> Callable[[Any, Dict[str, Array]],
                                       Dict[str, Array]]:
    """Validation over the node axis: the batch arrives node-split
    [n, B/n, ...] with the node axis laid over the mesh's 'data' axis —
    exactly like training — so on an n-chip mesh each chip evaluates 1/n
    of the batch instead of replicating the whole thing (the reference
    replicated: distributed_trainer.py:494-508).  Node rows are equal-
    sized, so the mean of per-node means is the global mean."""
    eval_step = build_eval_step(bundle)

    def node_eval_step(params, node_batch):
        out = jax.vmap(lambda b: eval_step(params, b))(node_batch)
        return jax.tree_util.tree_map(jnp.mean, out)

    return node_eval_step
