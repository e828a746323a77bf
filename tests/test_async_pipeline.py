"""Async host pipeline (engine/async_host.py) regressions.

Three contracts pin the perf work:

* **transfer-free hot path** — the jitted step body + metrics packing
  dispatch under ``jax.transfer_guard("disallow")``: no implicit per-step
  device↔host transfer can sneak back in (the packed single async copy
  is the only host-facing traffic, and it is explicit);
* **sync/async equivalence** — ``async_host_depth=0`` and ``=2`` produce
  bit-identical loss/trust/status trajectories and identical detector
  incident records (the lag changes WHEN the host observes a step, never
  WHAT it observes);
* **lagged-guard rollback** — a guard trip detected K steps late skips
  the in-place retry rung and rolls back to a checkpoint that predates
  the in-flight window, discarding the abandoned timeline.

All tests share one tiny-GPT-2 trainer (module fixture +
``reset_for_run``) so the fast tier pays the SPMD compile once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trustworthy_dl_tpu.attacks.adversarial import AdversarialAttacker
from trustworthy_dl_tpu.core.config import AttackConfig, TrainingConfig
from trustworthy_dl_tpu.data import get_dataloader
from trustworthy_dl_tpu.engine.step import HostMetricsPacker
from trustworthy_dl_tpu.engine.trainer import DistributedTrainer
from trustworthy_dl_tpu.obs import ObsSession
from trustworthy_dl_tpu.obs.registry import MetricsRegistry

pytestmark = pytest.mark.asyncpipe

TINY = dict(n_layer=2, n_embd=32, n_head=4, vocab_size=128,
            n_positions=32, seq_len=16)
NODES, BATCH, SEQ = 4, 8, 16
STEPS_PER_EPOCH = 8


@pytest.fixture(scope="module")
def shared_trainer(tmp_path_factory):
    """One compiled trusted step for the whole module; tests call
    ``reset_for_run`` (fresh device + host state, zero recompiles)."""
    config = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext",
        batch_size=BATCH, num_nodes=NODES, learning_rate=3e-3,
        detector_warmup=2, checkpoint_interval=4,
        checkpoint_dir=str(tmp_path_factory.mktemp("asyncpipe") / "ckpt"),
    )
    trainer = DistributedTrainer(config, model_overrides=dict(TINY))
    trainer.initialize()
    return trainer


def _loader():
    return get_dataloader("openwebtext", batch_size=BATCH, seq_len=SEQ,
                          vocab_size=TINY["vocab_size"],
                          num_examples=BATCH * STEPS_PER_EPOCH)


# ---------------------------------------------------------------------------
# Packer unit contract
# ---------------------------------------------------------------------------


def test_host_metrics_packer_roundtrip(shared_trainer):
    """One flat f32 pack → host → unpack restores every field's dtype,
    shape and bits, including model_aux/fleet_alert handling and the
    step-time fleet streak."""
    trainer = shared_trainer
    trainer.reset_for_run()
    batch = trainer._node_batch(jax.tree_util.tree_map(
        np.asarray,
        trainer.model.example_batch(BATCH, jax.random.PRNGKey(0)),
    ))
    state, metrics = trainer._train_step(trainer.state, batch,
                                         trainer.attack_plan)
    trainer.state = state
    packer = HostMetricsPacker(metrics, state.fleet_raw_streak)
    assert packer.num_nodes == NODES
    assert packer.matches(metrics, state.fleet_raw_streak)

    packed = packer.pack(metrics, state.fleet_raw_streak)
    assert packed.dtype == jnp.float32 and packed.ndim == 1
    host, streak = packer.unpack(np.asarray(packed))

    for name in type(metrics)._fields:
        want = getattr(metrics, name)
        got = getattr(host, name)
        if want is None or name == "model_aux":
            continue
        want = np.asarray(want)
        assert got.dtype == want.dtype, name
        assert got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    np.testing.assert_array_equal(streak,
                                  np.asarray(state.fleet_raw_streak))
    # Shape drift (an elastic transition's node-count change) is detected
    # so the pipeline rebuilds the packer instead of mis-slicing.
    shrunk = metrics._replace(trust_scores=metrics.trust_scores[:-1])
    assert not packer.matches(shrunk, state.fleet_raw_streak)


# ---------------------------------------------------------------------------
# Transfer-guard pin on the hot step body
# ---------------------------------------------------------------------------


def test_step_body_and_pack_are_transfer_free(shared_trainer):
    """The steady-state hot path — step dispatch + metrics pack — runs
    under ``jax.transfer_guard("disallow")``: every per-step host pull
    must go through the ONE packed explicit copy, pulled outside the
    guarded region.  Any implicit transfer reintroduced into the step
    body (a numpy leaf in the attack plan, a stray ``float()``) fails
    here, not in a TPU profile three PRs later."""
    trainer = shared_trainer
    trainer.reset_for_run()
    batch = trainer._node_batch(jax.tree_util.tree_map(
        np.asarray,
        trainer.model.example_batch(BATCH, jax.random.PRNGKey(1)),
    ))
    # Warm: compile both programs and settle all operands onto devices.
    state, metrics = trainer._train_step(trainer.state, batch,
                                         trainer.attack_plan)
    packer = HostMetricsPacker(metrics, state.fleet_raw_streak)
    np.asarray(packer.pack(metrics, state.fleet_raw_streak))

    with jax.transfer_guard("disallow"):
        for _ in range(2):  # steady state, not a first-call artifact
            state, metrics = trainer._train_step(state, batch,
                                                 trainer.attack_plan)
            packed = packer.pack(metrics, state.fleet_raw_streak)
    trainer.state = state
    host, _ = packer.unpack(np.asarray(packed))
    assert np.isfinite(host.loss)


# ---------------------------------------------------------------------------
# Sync-vs-async equivalence
# ---------------------------------------------------------------------------

_TIME_KEYS = ("seq", "t", "t_mono", "path")


def _normalized_events(session):
    return [{k: v for k, v in e.items() if k not in _TIME_KEYS}
            for e in session.recorder.events()]


def _run_training(trainer, depth, ckpt_dir, epochs=2):
    trainer.config = dataclasses.replace(
        trainer.config, async_host_depth=depth, checkpoint_dir=str(ckpt_dir)
    )
    # CheckpointManager is constructed from the config dir; rebuild it so
    # each arm writes its own tree (save-skip-because-exists must not
    # make the second arm diverge).
    from trustworthy_dl_tpu.engine.checkpoint import CheckpointManager

    trainer.checkpointer = CheckpointManager(str(ckpt_dir))
    trainer.reset_for_run()
    attacker = AdversarialAttacker(AttackConfig(
        attack_types=["gradient_poisoning"], target_nodes=[1],
        intensity=1.5, start_step=4,
    ))
    attacker.activate_attacks()
    trainer.set_attack_plan(attacker.plan(NODES))
    session = ObsSession(None, registry=MetricsRegistry())
    # Acceptance pin for the active obs plane: equivalence must hold
    # with span tracking attached (train.step spans ride the trace).
    session.enable_spans()
    trainer.attach_obs(session)
    dl = _loader()
    for epoch in range(epochs):
        trainer.train_epoch(dl, epoch)
    all_events = _normalized_events(session)
    # Span rows carry wall-clock durations (inherently run-dependent)
    # and the async arm legitimately laps a "host" phase sync folds into
    # compute — equivalence compares everything EXCEPT spans, then span
    # COVERAGE is asserted per arm.
    events = [e for e in all_events if e["type"] != "span"]
    spans = [e for e in all_events if e["type"] == "span"]
    history = [{k: v for k, v in rec.items() if k != "timestamp"}
               for rec in trainer.attack_history]
    stats = trainer.get_training_stats()
    return events, history, {
        "trust_scores": stats["trust_scores"],
        "attack_count": stats["attack_count"],
        "global_step": stats["global_step"],
        "training_state": stats["training_state"],
    }, spans


def test_sync_async_equivalence(shared_trainer, tmp_path):
    """Depth 0 and depth 2 must be indistinguishable to the host: the
    same per-step TRAIN_STEP floats (bit-identical — the packed f32 round
    trip is exact), the same trust transitions and detection verdicts,
    the same incident records, the same final stats.  Only WHEN the host
    observes a step may differ, and full drains erase even that by epoch
    end."""
    sync = _run_training(shared_trainer, 0, tmp_path / "sync")
    async_ = _run_training(shared_trainer, 2, tmp_path / "async")

    for name, s, a in (("events", sync[0], async_[0]),
                       ("history", sync[1], async_[1]),
                       ("stats", sync[2], async_[2])):
        assert s == a, f"{name} diverged between depth 0 and depth 2"

    # The run must actually exercise the machinery the claim covers.
    types = {e["type"] for e in sync[0]}
    assert "train_step" in types and "ckpt_save" in types
    assert "detection_verdict" in types, (
        "attack plan produced no incidents — equivalence test is vacuous"
    )
    assert sync[1], "no incident records"
    assert {rec["node_id"] for rec in sync[1]} == {1}
    steps = [e["step"] for e in sync[0] if e["type"] == "train_step"]
    assert len(steps) == 2 * STEPS_PER_EPOCH
    assert steps == sorted(steps)
    # Span tracking was live in BOTH arms: every accounted step got a
    # train.step root span (children per lap ride the same trace).
    for name, spans in (("sync", sync[3]), ("async", async_[3])):
        roots = sorted(e["step"] for e in spans
                       if e["name"] == "train.step")
        assert roots == steps, f"{name} arm span coverage"


# ---------------------------------------------------------------------------
# Lagged guard: rollback to the pre-window checkpoint
# ---------------------------------------------------------------------------


def test_lagged_guard_rolls_back_to_prewindow_checkpoint(
        shared_trainer, tmp_path):
    """A bad step surfacing K steps late must NOT be retried in place
    (the frontier state is not the state that produced it) and must roll
    back to a verified checkpoint OLDER than the whole in-flight window,
    discarding the lagged entries dispatched on top of the bad step —
    the documented K-step rollback caveat."""
    from trustworthy_dl_tpu.engine.checkpoint import CheckpointManager
    from trustworthy_dl_tpu.engine.supervisor import TrainingSupervisor

    trainer = shared_trainer
    trainer.config = dataclasses.replace(
        trainer.config, async_host_depth=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    trainer.checkpointer = CheckpointManager(trainer.config.checkpoint_dir)
    trainer.reset_for_run()

    real_step = trainer._train_step
    calls = {"n": 0}

    def poisoned(state, batch, plan):
        calls["n"] += 1
        state, m = real_step(state, batch, plan)
        if calls["n"] >= 6:  # steps 6+ report a non-finite loss
            m = m._replace(loss=jnp.asarray(jnp.nan, jnp.float32))
        return state, m

    trainer._train_step = poisoned
    try:
        supervisor = TrainingSupervisor(trainer, max_retries=2,
                                        rollback_after=1, backoff_base_s=0)
        supervisor.run(_loader(), num_epochs=1)
    finally:
        trainer._train_step = real_step
        trainer.step_guard = None

    assert supervisor.rollbacks == 1
    # Lagged verdicts skip the in-place retry rung entirely.
    assert supervisor.retries == 0
    assert supervisor.bad_steps == 1
    # The restore target predates the in-flight window: the last full
    # drain accepted through step 4 (checkpoint cadence), the bad step
    # was 6, and the window held steps 7-8 when the verdict landed.
    assert supervisor.rollback_steps == [4]
    assert trainer.global_step == 4
    # Discarded-timeline steps were never accounted by the host.
    assert all(rec["step"] <= 6 for rec in trainer.attack_history)

