"""Pipeline (stage) parallelism — the reference's one real strategy,
TPU-native.

The reference splits ``transformer.h`` into contiguous per-node chunks and
runs them in a *sequential Python loop in one process*
(distributed_trainer.py:124-135, 148-175).  Here the same partitioning is an
SPMD program: stacked block params [L, ...] reshape to [S, L/S, ...] and
shard over the mesh's 'stage' axis; a GPipe microbatch schedule runs inside
``shard_map``, rotating activations to the next stage with ``lax.ppermute``
each tick.  The backward schedule is not hand-written — JAX transposes the
``ppermute`` under ``jax.grad``, so reverse-mode AD *is* the backward
pipeline.

Per-stage trust integration:
  * each stage computes the detector battery over its boundary activations
    (masked mean over its active ticks) — the pipeline analogue of the
    reference's per-node ``detect_output_anomaly`` hook (:168-170);
  * per-stage gradient batteries come from the [S, ...] leading axis of the
    block gradients;
  * the trust gate zeroes a compromised stage's *parameter updates* (its
    layers freeze until reassignment) — unlike the reference, which silently
    drops compromised layers from the forward pass and corrupts the model
    (:154-157, flagged in SURVEY §7.5).
  * the cross-sectional outlier filter used in data-parallel mode is OFF
    here: different stages legitimately have different activation
    distributions, so only temporal z-scores apply (SURVEY §7.4(4)).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from trustworthy_dl_tpu.core import sharding as shreg

from trustworthy_dl_tpu.attacks.adversarial import AttackPlan, \
    corrupt_stage_compute, poison_gradients
from trustworthy_dl_tpu.core.config import TrainingConfig
from trustworthy_dl_tpu.core.mesh import DATA_AXIS, STAGE_AXIS
from trustworthy_dl_tpu.detect import baseline as bl
from trustworthy_dl_tpu.detect import stats as st
from trustworthy_dl_tpu.detect.detector import AttackType, anomaly_verdicts
from trustworthy_dl_tpu.detect.verifier import absorb_norms, norm_suspicions
from trustworthy_dl_tpu.engine.state import TrainState, update_monitor
from trustworthy_dl_tpu.engine.step import (
    StepMetrics,
    _gradient_stat_vector,
    guarded_update,
)
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models import layers as L
from trustworthy_dl_tpu.trust import state as ts

Array = jax.Array

#: Registry rules for pipeline mode ("model"): the stage axis carries
#: the trust nodes, microbatch rows shard over the DP replica rows.
_PP_RULES = shreg.rules_for("model")


def stack_stages(blocks: Any, num_stages: int) -> Any:
    """[L, ...] stacked blocks -> [S, L/S, ...] stage-major stacking — the
    TPU analogue of the reference's contiguous layer chunks
    (distributed_trainer.py:126-134)."""
    def reshape(leaf):
        l = leaf.shape[0]
        if l % num_stages:
            raise ValueError(
                f"{l} layers not divisible by {num_stages} stages"
            )
        return leaf.reshape((num_stages, l // num_stages) + leaf.shape[1:])
    return jax.tree_util.tree_map(reshape, blocks)


def unstack_stages(blocks: Any) -> Any:
    """Inverse of stack_stages."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((leaf.shape[0] * leaf.shape[1],) + leaf.shape[2:]),
        blocks,
    )


def _right_rotation(axis: str, size: int):
    return [(i, (i + 1) % size) for i in range(size)]


def choose_num_microbatches(batch_size: int, num_stages: int,
                            dp: int = 1) -> int:
    """Auto schedule depth (``TrainingConfig.num_microbatches = 0``).

    The bubble fraction (S-1)/(M+S-1) falls with M, so fixed global batch
    wants M as large as the batch allows — measured on the 8-stage mesh
    (experiments/pipeline_schedule_study): B=64 step time drops 3.0x
    from M=2 to M=16.  Past M ≈ 4·S the marginal bubble gain is < ~6 %
    while per-tick battery/bookkeeping overhead keeps growing linearly
    and per-microbatch arithmetic intensity falls (mb shrinks toward 1),
    so the cap keeps the MXU fed.  An exact divisor of the per-replica-row
    batch B/dp is preferred (every microbatch full, no samples trimmed);
    when none <= cap exists (prime-ish batches) the fallback picks the
    trim-tolerant M that maximises the utilised batch (M * (per_row // M),
    ties resolved toward the larger M for the smaller bubble) instead of
    silently degrading to M=1 — at S=8 that old fallback ran an ~88 %
    bubble, far worse than trimming a couple of samples per row (the
    trainer's _node_batch already trims every batch to the M*dp quantum).
    Degraded auto-selection is logged with the utilisation it settles for.
    """
    import logging as _logging

    per_row = max(batch_size // max(dp, 1), 1)
    cap = min(per_row, 4 * num_stages)
    for m in range(cap, 1, -1):
        if per_row % m == 0:
            return m
    best_m, best_used = 1, 0
    for m in range(2, cap + 1):
        used = (per_row // m) * m
        if used >= best_used:  # >= : ties prefer the deeper schedule
            best_m, best_used = m, used
    if best_m > 1:
        _logging.getLogger(__name__).warning(
            "no exact microbatch divisor of per-row batch %d <= cap %d; "
            "auto-selected trim-tolerant M=%d (utilises %d/%d samples "
            "per row, bubble %.0f%% vs %.0f%% at M=1)",
            per_row, cap, best_m, best_used, per_row,
            100.0 * bubble_fraction(num_stages, best_m),
            100.0 * bubble_fraction(num_stages, 1),
        )
    return best_m


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """GPipe pipeline bubble: the idle fraction of the M + S - 1 tick
    schedule, (S-1)/(M+S-1).  The backward schedule is the AD transpose of
    the same ``ppermute`` ring, so it mirrors the forward bubble — raising
    ``num_microbatches`` is the schedule-level lever (M=4,S=4 → 43 %;
    M=32,S=4 → 8.6 %), and DP pipeline replica rows (the TPU (group, S)
    mesh) scale batch throughput without touching it."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def build_pipeline_apply(
    cfg: gpt2.GPT2Config,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    max_sort: int = 16384,
) -> Callable[[Any, Array], Tuple[Array, Array, Array, Array]]:
    """Returns pipe_apply(stage_blocks, x_microbatches) ->
    (y_microbatches, stage_stats[S,17], act_mean[S], act_std[S]).

    ``stage_blocks`` leaves are [S, L/S, ...] (sharded P('stage')),
    ``x_microbatches`` is [M, mb, T, D] — its mb dim shards over the
    mesh's data axis when the mesh carries DP pipeline replica rows (the
    TPU (group, S) layout, core/mesh.py), so surplus chips beyond S scale
    batch throughput.  The schedule runs M + S - 1 ticks; each tick every
    stage applies its layer slice to its current activation and passes it
    right around the ring (per data row — shard_map scopes the ppermute
    to each row's stage subgroup).
    """
    S, M = num_stages, num_microbatches
    total_ticks = M + S - 1
    dp = mesh.shape.get(DATA_AXIS, 1)

    def apply_local(local_blocks, x):
        def body(h, block):
            return gpt2.block_forward(block, h, cfg), None
        y, _ = jax.lax.scan(body, x, local_blocks)
        return y

    def pipe_local(local_blocks, x_mb):
        # Inside shard_map: local_blocks [1, L/S, ...] (this stage's slice),
        # x_mb [M, mb, T, D] (full, replicated).
        local_blocks = jax.tree_util.tree_map(lambda a: a[0], local_blocks)
        stage = jax.lax.axis_index(STAGE_AXIS)
        mb_shape = x_mb.shape[1:]
        state0 = jnp.zeros(mb_shape, x_mb.dtype)
        outputs0 = jnp.zeros((M,) + mb_shape, x_mb.dtype)
        # Sufficient statistics of boundary activations over active ticks.
        stats0 = jnp.zeros((st.NUM_GRADIENT_STATS,), jnp.float32)
        acc0 = (state0, outputs0, stats0, jnp.zeros((), jnp.float32),
                jnp.asarray(0.0), jnp.asarray(0.0))

        def tick(carry, t):
            state, outputs, stats_sum, n_active, mean_sum, std_sum = carry
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            safe_idx = jnp.clip(mb_idx, 0, M - 1)
            # Stage 0 ingests a fresh microbatch; others use the ring input.
            fresh = x_mb[jnp.clip(t, 0, M - 1)]
            current = jnp.where(stage == 0, fresh, state)
            out = apply_local(local_blocks, current)
            # Boundary battery for this tick (zeros batched out when idle).
            # stop_gradient: the battery is diagnostics, constant under
            # differentiation by contract (same as ops/fused_moments) —
            # and keeping it out of the VJP keeps its per-stage scalar
            # accumulators out of the shard_map residual set, whose spec
            # check this container's jax (0.4.37) enforces even under
            # check_rep=False (unreplicated scalar residuals -> a
            # _SpecError at trace time on dp>1 meshes).
            out_sg = jax.lax.stop_gradient(out)
            tick_stats = st.tensor_statistics_sampled(
                out_sg.reshape(-1).astype(jnp.float32), max_sort
            )
            tick_stats = jnp.concatenate(
                [tick_stats,
                 jnp.zeros((st.NUM_GRADIENT_STATS - st.NUM_TENSOR_STATS,),
                           jnp.float32)]
            )
            stats_sum = stats_sum + jnp.where(active, tick_stats, 0.0)
            mean_sum = mean_sum + jnp.where(active, jnp.mean(out_sg), 0.0)
            std_sum = std_sum + jnp.where(active, jnp.std(out_sg), 0.0)
            n_active = n_active + active.astype(jnp.float32)
            # Final stage records completed microbatches.
            write = active & (stage == S - 1)
            outputs = jnp.where(
                write,
                outputs.at[safe_idx].set(out),
                outputs,
            )
            # Rotate activations one stage rightward over ICI.
            nxt = jax.lax.ppermute(
                out, STAGE_AXIS, _right_rotation(STAGE_AXIS, S)
            )
            return (nxt, outputs, stats_sum, n_active, mean_sum, std_sum), None

        (_, outputs, stats_sum, n_active, mean_sum, std_sum), _ = jax.lax.scan(
            tick, acc0, jnp.arange(total_ticks)
        )
        denom = jnp.maximum(n_active, 1.0)
        stage_stats = (stats_sum / denom)[None, :]           # [1, 17] local
        act_mean = (mean_sum / denom)[None]
        act_std = (std_sum / denom)[None]
        if dp > 1:
            # DP replica rows each saw a different microbatch shard:
            # average the boundary batteries across rows so the per-stage
            # baseline describes the whole batch (consistent with the
            # tick-average above).
            stage_stats = jax.lax.psum(stage_stats, DATA_AXIS) / dp
            act_mean = jax.lax.psum(act_mean, DATA_AXIS) / dp
            act_std = jax.lax.psum(act_std, DATA_AXIS) / dp
        # Completed outputs live only on the last stage; psum replicates
        # them (other stages contribute zeros) so unembed/loss is SPMD.
        outputs = jax.lax.psum(outputs, STAGE_AXIS)
        return outputs, stage_stats, act_mean, act_std

    pipe = jax.shard_map(
        pipe_local,
        mesh=mesh,
        # mb (dim 1 of x_mb / outputs) shards over the DP replica rows; on
        # the (1, S) mesh the spec degenerates to full replication.
        in_specs=(_PP_RULES.partition_spec(shreg.STAGE),
                  _PP_RULES.partition_spec(None, shreg.BATCH)),
        out_specs=(_PP_RULES.partition_spec(None, shreg.BATCH),
                   _PP_RULES.partition_spec(shreg.STAGE),
                   _PP_RULES.partition_spec(shreg.STAGE),
                   _PP_RULES.partition_spec(shreg.STAGE)),
        check_vma=False,
    )
    return pipe


class CanaryState(NamedTuple):
    """Per-stage reference signal for Byzantine/backdoor detection under
    pipeline parallelism (SURVEY §7.4(4)).

    Cross-stage comparison is meaningless (stages compute different layers)
    and a poisoned stage corrupts all downstream activations, so each stage
    is probed *in isolation*: every step it applies its layer slice to the
    same fixed replicated canary activations.  Honest stages change their
    transform only by one optimizer step (tiny relative delta); a Byzantine
    stage that corrupts its compute moves abruptly (``prev`` check), and a
    slow persistent repurposing of the transform drifts away from the
    long-horizon EMA signature (``sig_ema`` KL check)."""

    prev: Array     # f32[S, cb, tc, d] last step's canary outputs
    sig_ema: Array  # f32[S, d] EMA softmax signature of canary outputs
    count: Array    # i32[] probes absorbed


def init_canary_state(num_stages: int, canary: Array) -> CanaryState:
    cb, tc, d = canary.shape
    return CanaryState(
        prev=jnp.zeros((num_stages, cb, tc, d), jnp.float32),
        sig_ema=jnp.full((num_stages, d), 1.0 / d, jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def make_canary(cfg: gpt2.GPT2Config, canary_tokens: int = 8,
                canary_batch: int = 1) -> Array:
    """The fixed probe input: deterministic unit-Gaussian activations at the
    block interface (constant across the run — the whole point)."""
    return jax.random.normal(
        jax.random.PRNGKey(0xCA9A12),
        (canary_batch, canary_tokens, cfg.n_embd),
        jnp.float32,
    )


CANARY_BYZ_REL_CHANGE = 0.25   # honest per-step transform drift is ~lr-sized
CANARY_BACKDOOR_KL = 2.0       # same bar as the reference's backdoor check
                               # (attack_detector.py:164-183)


def canary_probe(
    canary_state: CanaryState,
    blocks: Any,
    canary: Array,
    cfg: gpt2.GPT2Config,
    warmup: int,
) -> Tuple[CanaryState, Array, Array]:
    """Probe every stage's transform; returns (new_state, byz[S], backdoor[S]).

    ``blocks`` leaves are [S, L/S, ...]; the vmap over the stage axis rides
    the 'stage' sharding, so each stage probes on its own device with the
    replicated canary — one tiny forward per stage, no extra collectives."""

    def one_stage(stage_blocks):
        def body(h, block):
            return gpt2.block_forward(block, h, cfg), None
        y, _ = jax.lax.scan(body, canary, stage_blocks)
        return y.astype(jnp.float32)

    y = jax.vmap(one_stage)(blocks)                      # [S, cb, tc, d]
    s_axes = tuple(range(1, y.ndim))

    # Abrupt-change (Byzantine) check vs the previous step's probe.
    delta = jnp.sqrt(jnp.sum((y - canary_state.prev) ** 2, axis=s_axes))
    ref = jnp.sqrt(jnp.sum(canary_state.prev ** 2, axis=s_axes)) + 1e-8
    byz = (delta / ref > CANARY_BYZ_REL_CHANGE) & (canary_state.count >= 1)

    # Slow-drift (backdoor) check: softmax signature vs long-horizon EMA.
    sig = jax.nn.softmax(jnp.mean(y, axis=(1, 2)), axis=-1)      # [S, d]
    ema = canary_state.sig_ema
    kl = jnp.sum(sig * (jnp.log(sig + 1e-12) - jnp.log(ema + 1e-12)), axis=-1)
    backdoor = (kl > CANARY_BACKDOOR_KL) & (canary_state.count >= warmup)

    flagged = byz | backdoor
    new_ema = jnp.where(flagged[:, None], ema, 0.9 * ema + 0.1 * sig)
    # Freeze BOTH references on flagged stages: absorbing a corrupted probe
    # into prev would make the first *clean* step after the attack ends read
    # as another abrupt change and re-flag an honest stage.
    new_prev = jnp.where(
        flagged.reshape((-1,) + (1,) * (y.ndim - 1)), canary_state.prev, y
    )
    new_state = CanaryState(
        prev=new_prev, sig_ema=new_ema, count=canary_state.count + 1
    )
    return new_state, byz, backdoor


def build_pipeline_train_step(
    bundle,
    config: TrainingConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    max_sort: int = 16384,
) -> Callable[[TrainState, Dict[str, Array], AttackPlan],
              Tuple[TrainState, StepMetrics]]:
    """Jitted pipeline train step.  TrainState.params must hold 'blocks'
    stacked as [S, L/S, ...] (see stack_stages); the trainer handles that.

    Batches are global {'input': [B, T], 'target': [B, T]} with
    B % num_microbatches == 0.
    """
    if bundle.kind != "lm":
        raise ValueError(
            "pipeline parallelism currently supports the GPT family only "
            "(the reference's partitioner also only implemented GPT, "
            "distributed_trainer.py:124-144)"
        )
    cfg = bundle.config
    S = config.num_nodes
    M = config.num_microbatches
    detection = config.attack_detection_enabled
    verification = config.gradient_verification_enabled
    pipe_apply = build_pipeline_apply(cfg, mesh, S, M, max_sort)
    canary_const = make_canary(cfg, config.canary_tokens)

    dp = mesh.shape.get(DATA_AXIS, 1)
    logger_msg = (
        "pipeline schedule: S=%d stages, M=%d microbatches, %d DP replica "
        "row(s); GPipe bubble fraction %.1f%%" % (
            S, M, dp, 100.0 * bubble_fraction(S, M))
    )
    import logging as _logging

    _logging.getLogger(__name__).info(logger_msg)

    def loss_fn(params, batch):
        x = gpt2.embed(params, batch["input"], cfg)
        b, t, d = x.shape
        mb = b // M
        x_mb = x.reshape(M, mb, t, d)
        y_mb, stage_stats, act_mean, act_std = pipe_apply(params["blocks"], x_mb)
        if dp > 1:
            # Merge with mb leading so the data-sharded dim stays the
            # (contiguous) row dim of the merged batch — a plain
            # [M, mb] → [b] merge would need a strided sharding and
            # GSPMD would all-gather the activations instead.  Targets
            # take the identical permutation; the loss is a mean over
            # all positions, so the reorder changes nothing but
            # summation order.
            y = y_mb.transpose(1, 0, 2, 3).reshape(b, t, d)
            targets = batch["target"].reshape(M, mb, t).transpose(
                1, 0, 2
            ).reshape(b, t)
        else:
            y = y_mb.reshape(b, t, d)
            targets = batch["target"]
        # Head via the shared helper: honours cfg.lm_head_chunk (fused
        # vocab-chunked CE — the logits never materialise), identical to
        # the data-parallel loss path so the modes cannot drift.
        loss, _ = gpt2.head_loss_and_signature(params, y, targets, cfg)
        return loss, (stage_stats, act_mean, act_std)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch: Dict[str, Array],
                   plan: AttackPlan) -> Tuple[TrainState, StepMetrics]:
        rng, k_grad, k_byz = jax.random.split(state.rng, 3)
        now = state.step.astype(jnp.float32) * config.time_per_step

        # Byzantine *compute* corruption: the attacked stage's transform is
        # garbage for this step (forward AND the canary probe below ride the
        # same corrupted blocks), while stored params stay clean.
        fwd_params = dict(state.params)
        fwd_params["blocks"] = jax.lax.cond(
            plan.is_live(state.step) & plan.byzantine,
            lambda b: corrupt_stage_compute(plan, b, state.step, k_byz),
            lambda b: b,
            state.params["blocks"],
        )

        (loss, aux), grads = grad_fn(fwd_params, batch)
        stage_stats_out, act_mean, act_std = aux

        # Attack injection: a compromised stage emits poisoned block
        # gradients (the [S, ...] leading axis maps nodes → stages).
        grads = dict(grads)
        grads["blocks"] = jax.lax.cond(
            plan.is_live(state.step),
            lambda g: poison_gradients(plan, g, state.step, k_grad),
            lambda g: g,
            grads["blocks"],
        )

        # Per-stage gradient batteries over each stage's block slice.
        grad_stats, leaf_norms, finite = jax.vmap(
            lambda g: _gradient_stat_vector(g, max_sort)
        )(grads["blocks"])
        global_norms = jnp.sqrt(jnp.sum(leaf_norms**2, axis=1))

        # Gradient verification verdict (pure read) BEFORE the detector so
        # the raw norm suspicion can mask this step's baseline absorption
        # (a stage excluded for a suspect norm must not push that step's
        # stats into the rolling windows).  The Welford baseline absorbs
        # after the probe below, under the same clean-this-step rule as
        # every other baseline — in particular NOT during a live
        # canary-Byzantine verdict, when every stage's gradients flow
        # through a corrupted pipeline.
        finite_b = finite.astype(bool)
        if verification:
            norm_suspect = norm_suspicions(state.verifier, global_norms)
        else:
            norm_suspect = jnp.zeros_like(finite_b)

        if detection:
            out_v = anomaly_verdicts(stage_stats_out, state.out_baseline,
                                     warmup=config.detector_warmup)
            grad_v = anomaly_verdicts(grad_stats, state.grad_baseline,
                                      warmup=config.detector_warmup)
            # Per-stage canary probe (SURVEY §7.4(4)): the Byzantine/backdoor
            # checks cross-node comparison can't provide under pipelining.
            canary_state, byz, backdoor = canary_probe(
                state.canary, fwd_params["blocks"], canary_const, cfg,
                config.detector_warmup,
            )
            # Stages are serially dependent: a Byzantine stage corrupts every
            # downstream activation AND the whole backward pass, so while a
            # canary-Byzantine verdict is live (byz_any) only the canary can
            # localise the culprit — the statistical batteries would
            # false-flag honest stages on the contaminated gradients.  They
            # are suppressed, the rolling baselines freeze (no contaminated
            # absorption), and the optimizer update is skipped entirely
            # below.  Otherwise, compromise verdicts come from the gradient
            # battery, the canary, and the verifier: stage activation
            # distributions drift legitimately as the model trains and,
            # unlike DP, there is no cross-node population to separate drift
            # from attack — so the output battery feeds the output_deviation
            # *trust signal* and the reported score, not the hard verdict.
            byz_any = jnp.any(byz)
            stat_cand = grad_v.is_attack & ~byz_any
            candidates = stat_cand | byz | backdoor
            # Absorb only stages with NO suspicion of any kind this step —
            # battery/canary verdicts, verifier norm-suspect, or non-finite
            # gradients — and never while a Byzantine verdict is live (the
            # whole pipeline's stats are contaminated then).
            clean_now = ~(candidates | norm_suspect | ~finite_b) & ~byz_any
            out_bl = bl.push_stats(state.out_baseline, stage_stats_out,
                                   mask=clean_now)
            grad_bl = bl.push_stats(state.grad_baseline, grad_stats,
                                    mask=clean_now)
            # Canary verdicts are unambiguous (fixed probe, no statistical
            # drift), so they confirm immediately — only the statistical
            # battery needs the two-consecutive-steps debounce.
            attacked = (stat_cand & state.prev_suspects) | byz | backdoor
            out_score, grad_score = out_v.score, grad_v.score
            attack_type = jnp.select(
                [byz, backdoor, stat_cand],
                [jnp.full((S,), int(AttackType.BYZANTINE), jnp.int32),
                 jnp.full((S,), int(AttackType.BACKDOOR), jnp.int32),
                 grad_v.attack_type],
                default=out_v.attack_type,
            )
        else:
            out_bl, grad_bl = state.out_baseline, state.grad_baseline
            canary_state = state.canary
            candidates = attacked = byz = backdoor = jnp.zeros((S,), bool)
            byz_any = jnp.zeros((), bool)
            out_score = grad_score = jnp.zeros((S,), jnp.float32)
            attack_type = jnp.zeros((S,), jnp.int32)
            clean_now = finite_b & ~norm_suspect

        # No cross-stage gate on norm suspicion (stages differ
        # legitimately), but a live canary verdict contaminates every
        # stage's gradients, so it is suppressed like the statistical
        # battery.
        norm_suspect = norm_suspect & ~byz_any
        verified = finite_b & ~norm_suspect

        # Verifier baseline absorption under the same clean-this-step rule
        # as the stat baselines (incl. the ~byz_any freeze carried by
        # clean_now): corrupted-pipeline norms must never form the Welford
        # baseline honest stages are later z-scored against.
        if verification:
            verifier = absorb_norms(state.verifier, global_norms, clean_now)
        else:
            verifier = state.verifier

        # Statistical norm suspicion debounces like the battery verdicts:
        # excluded from this step's update immediately (weights gate), but
        # confirmed-compromised only on the second consecutive hit.
        candidates = candidates | norm_suspect
        attacked = attacked | (norm_suspect & state.prev_suspects)

        trust = ts.mark_compromised(state.trust, attacked | ~finite_b)

        # Trust signals per stage (distributed_trainer.py:228-271 analogue).
        warm = state.monitor.warm
        exp_mean = state.monitor.out_mean_avg
        exp_std = jnp.maximum(state.monitor.out_std_avg, 1e-6)
        deviation = jnp.where(
            warm,
            jnp.minimum(
                1.0,
                (jnp.abs(act_mean - exp_mean) / exp_std
                 + jnp.abs(act_std - state.monitor.out_std_avg) / exp_std) / 2.0,
            ),
            0.0,
        )
        per_leaf = jnp.minimum(
            1.0, leaf_norms / jnp.maximum(state.monitor.grad_norm_avg, 1e-12)
        )
        usable = state.monitor.grad_norm_avg > 0
        consistency = jnp.where(
            warm,
            jnp.sum(jnp.where(usable, per_leaf, 0.0), axis=1)
            / jnp.maximum(jnp.sum(usable, axis=1), 1),
            1.0,
        )
        # While a Byzantine stage is live the deviation/consistency signals
        # of every stage are computed through corrupted activations —
        # freeze the trust EMA rather than punish honest stages with
        # garbage metrics.
        trust = ts.update_trust(trust, deviation, consistency, now,
                                alpha=config.trust_alpha,
                                update_mask=jnp.broadcast_to(~byz_any, (S,)))

        # Probation recovery (trust_manager.py:198-206 wired in): a frozen
        # stage with enough consecutive clean steps re-enters as RECOVERING
        # and its updates resume.  ~byz_any: a live canary verdict means the
        # whole pipeline's evidence is contaminated — no streak credit.
        trust, clean_streak = ts.probation_recovery(
            trust, state.clean_streak,
            verified & ~candidates & ~byz_any,
            config.recovery_probation_steps,
        )

        # Gate: a flagged stage's parameters freeze (update zeroed) — the
        # model topology is preserved, unlike the reference's layer-drop.
        # Hard-mask with jnp.where, not scale: 0 * NaN = NaN, so a frozen
        # stage emitting non-finite gradients would otherwise still poison
        # its own (and via the optimizer, the shared) parameter updates.
        weights = ts.contribution_weights(trust, verified & ~candidates)
        # Global skip under a live canary-Byzantine verdict: the step's loss
        # was computed through a corrupted pipeline, so NO stage's gradient
        # is trustworthy (serial dependence) — zero the whole update.
        step_scale = jnp.where(byz_any, 0.0, 1.0)

        def _gate_stage(g):
            shape = (S,) + (1,) * (g.ndim - 1)
            mask = (weights > 0).reshape(shape)
            gated = jnp.where(mask, g * weights.reshape(shape).astype(g.dtype), 0)
            return gated * step_scale.astype(g.dtype)

        blocks = jax.tree_util.tree_map(_gate_stage, grads["blocks"])
        # Shared leaves (embed/unembed) are not per-stage gated; zero any
        # non-finite leaf so a NaN forward cannot corrupt shared params.
        # (Block grads are already handled by _gate_stage — a non-finite
        # stage always fails the finite check and carries weight 0.)
        grads = {
            k: (blocks if k == "blocks" else jax.tree_util.tree_map(
                lambda g: jnp.where(jnp.all(jnp.isfinite(g)), g, 0)
                * step_scale.astype(g.dtype), v))
            for k, v in grads.items()
        }
        # True skip on the "zero the whole update" paths: a live canary-
        # Byzantine verdict, or every stage gated out — params and optimizer
        # state freeze together (zeroed grads alone would still let AdamW's
        # momentum/weight-decay move every parameter).
        params, opt_state = guarded_update(
            ~byz_any & (jnp.sum(weights) > 0), optimizer, grads,
            state.opt_state, state.params,
        )

        absorb = verified & ~candidates & ~byz_any
        monitor = update_monitor(state.monitor, act_mean, act_std, leaf_norms,
                                 absorb)
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
            canary=canary_state,
            clean_streak=clean_streak,
            # Fleet norm-surge state passes through untouched: the alarm
            # is a data-mode construct (pipeline stages compute different
            # layers, so a cross-stage norm median is meaningless; the
            # canary probe is this mode's fleet-level check).
            fleet_norm=state.fleet_norm,
            fleet_raw_streak=state.fleet_raw_streak,
        )
        metrics = StepMetrics(
            loss=loss,
            per_node_loss=jnp.broadcast_to(loss, (S,)),
            trust_scores=trust.scores,
            status=trust.status,
            attacked=attacked,
            verified=verified,
            finite=finite_b,
            weights=weights,
            system_trust=ts.system_trust(trust),
            grad_norm=optax.global_norm(grads),
            out_score=out_score,
            grad_score=grad_score,
            attack_type=attack_type,
            byzantine=byz,
            backdoor=backdoor,
            out_stats=stage_stats_out,
            grad_stats=grad_stats,
        )
        return new_state, metrics

    return train_step


def build_pipeline_eval_step(bundle, config: TrainingConfig, mesh: Mesh
                             ) -> Callable[[Any, Dict[str, Array]],
                                           Dict[str, Array]]:
    """Validation through the pipeline (params hold stacked [S, L/S, ...]
    blocks, so the DP eval path cannot be reused)."""
    cfg = bundle.config
    pipe_apply = build_pipeline_apply(cfg, mesh, config.num_nodes,
                                      config.num_microbatches)

    dp = mesh.shape.get(DATA_AXIS, 1)

    def eval_step(params, batch):
        tokens = batch["input"]
        x = gpt2.embed(params, tokens, cfg)
        b, t, d = x.shape
        M = config.num_microbatches
        mb = b // M
        x_mb = x.reshape(M, mb, t, d)
        y_mb, _, _, _ = pipe_apply(params["blocks"], x_mb)
        if dp > 1:
            # Same sharding-preserving merge + target permutation as the
            # train loss (see build_pipeline_train_step.loss_fn).
            y = y_mb.transpose(1, 0, 2, 3).reshape(b, t, d)
            batch = dict(
                batch,
                target=batch["target"].reshape(M, mb, t).transpose(
                    1, 0, 2
                ).reshape(b, t),
            )
        else:
            y = y_mb.reshape(b, t, d)
        chunk = gpt2.resolve_lm_head_chunk(cfg, int(batch["target"].size))
        if chunk:
            # Same memory contract as training: the fused eval never
            # materialises the [B, T, V] logits (ops/fused_ce.py).
            from trustworthy_dl_tpu.ops.fused_ce import fused_lm_eval

            normed = L.layernorm(params["ln_f"], y)
            loss, acc = fused_lm_eval(normed, params["wte"],
                                      batch["target"], chunk,
                                      cfg.dtype)
            return {"loss": loss, "accuracy": acc}
        logits = gpt2.unembed(params, y, cfg)
        return {
            "loss": L.cross_entropy_loss(logits, batch["target"]),
            "accuracy": L.accuracy(logits, batch["target"]),
        }

    return eval_step
