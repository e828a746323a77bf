"""Unified logical-axis sharding registry (core/sharding.py).

Fast tier: the rule-table contracts — logical-axis → mesh-axis
resolution per parallelism mode, loud failure on unknown axes, the
generalized ZeRO/FSDP shard rule, the shared row-placement rule the
trainer and elastic migration both funnel through, serve TP submesh
construction, and the control-plane additions this PR rides in
(per-role predictive envelopes, scale-out vs scale-up).

Slow tier: layout equivalence — the SAME seeded training run under
dp / fsdp / tp layouts keeps its loss trajectory and its detection
verdicts; served streams under a TP submesh stay bit-identical to
``generate()`` with the decode step compiled exactly once; and an
evict/readmit cycle reproduces exactly the registry shardings a fresh
trainer would choose (the one-spelling guarantee the registry exists
for).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from trustworthy_dl_tpu.core import sharding as shreg
from trustworthy_dl_tpu.core.config import TrainingConfig
from trustworthy_dl_tpu.core.mesh import (DATA_AXIS, MODEL_AXIS, SEQ_AXIS,
                                          STAGE_AXIS)

pytestmark = pytest.mark.shard

TINY = dict(n_layer=2, n_embd=32, n_head=4, vocab_size=128, n_positions=32,
            seq_len=16)


def data_mesh(devices, n=None):
    import numpy as onp

    devs = list(devices)[: (n or len(devices))]
    return Mesh(onp.array(devs), (DATA_AXIS,))


# --------------------------------------------------------------------------
# Fast tier: rule-table resolution
# --------------------------------------------------------------------------


def test_axis_rules_tables_per_mode():
    data = shreg.axis_rules("data")
    assert data[shreg.BATCH] == DATA_AXIS
    assert data[shreg.NODE] == DATA_AXIS
    assert data[shreg.W_TP] is None
    assert data[shreg.W_FSDP] is None

    tensor = shreg.axis_rules("tensor")
    assert tensor[shreg.W_TP] == MODEL_AXIS
    assert tensor[shreg.HIDDEN] is None

    # Under pipelining the trust node IS the stage — the rename the
    # table exists to own.
    model = shreg.axis_rules("model")
    assert model[shreg.NODE] == STAGE_AXIS
    assert model[shreg.STAGE] == STAGE_AXIS

    seq = shreg.axis_rules("sequence")
    assert seq[shreg.SEQLEN] == SEQ_AXIS
    assert seq[shreg.HEAD] == SEQ_AXIS  # Ulysses: heads ride the seq axis

    hybrid = shreg.axis_rules("hybrid")
    assert hybrid[shreg.W_TP] == MODEL_AXIS
    assert hybrid[shreg.STAGE] == STAGE_AXIS

    # FSDP is a RULE, not a code path.
    assert shreg.axis_rules("data")[shreg.W_FSDP] is None
    assert shreg.axis_rules("data", fsdp=True)[shreg.W_FSDP] == DATA_AXIS

    with pytest.raises(ValueError, match="no sharding rules"):
        shreg.axis_rules("diagonal")


def test_rules_resolution_and_unknown_axis_is_loud():
    rules = shreg.rules_for("tensor")
    spec = rules.partition_spec(None, shreg.HIDDEN, shreg.W_TP)
    assert tuple(spec) == (None, None, MODEL_AXIS)
    assert tuple(rules.partition_spec()) == ()
    # A typo'd axis silently replicating is exactly the drift the
    # registry exists to prevent — it must raise, naming the vocabulary.
    with pytest.raises(ValueError, match="unknown logical axis"):
        rules.partition_spec(shreg.BATCH, "hiden")
    with pytest.raises(ValueError, match="batch"):
        rules.mesh_axis("w_pt")


def test_named_sharding_drops_axes_absent_from_mesh(eight_devices):
    # One logical declaration serves every mesh the mode can build: on
    # a data-only mesh the tensor rules' 'model' axis resolves to None
    # instead of failing.
    mesh = data_mesh(eight_devices)
    rules = shreg.rules_for("tensor")
    ns = rules.named_sharding(mesh, shreg.BATCH, shreg.W_TP)
    assert tuple(ns.spec) == (DATA_AXIS, None)


def test_resolve_tree_translates_logical_declarations():
    rules = shreg.rules_for("tensor")
    tree = {
        "qkv": {"w": (None, shreg.HIDDEN, shreg.W_TP), "b": (shreg.W_TP,)},
        "proj": {"w": (None, shreg.W_TP, shreg.HIDDEN)},
    }
    specs = shreg.resolve_tree(tree, rules)
    assert tuple(specs["qkv"]["w"]) == (None, None, MODEL_AXIS)
    assert tuple(specs["qkv"]["b"]) == (MODEL_AXIS,)
    assert tuple(specs["proj"]["w"]) == (None, MODEL_AXIS, None)


def test_model_logical_axes_resolve_to_the_shipped_tp_layout():
    # The model's declaration + the registry == the hand-written spec
    # tree the TP tests pin; the declaration is the single source.
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.parallel.tensor_parallel import gpt2_tp_specs

    specs = gpt2_tp_specs(None)
    assert tuple(specs["blocks"]["attn"]["qkv"]["w"]) == \
        (None, None, MODEL_AXIS)
    assert tuple(specs["blocks"]["attn"]["proj"]["w"]) == \
        (None, MODEL_AXIS, None)
    assert tuple(specs["wte"]) == (None, None)
    resolved = shreg.resolve_tree(gpt2.logical_axes(),
                                  shreg.rules_for("tensor"))
    assert resolved == specs


# --------------------------------------------------------------------------
# Fast tier: ZeRO/FSDP shard rule + shared placement helpers
# --------------------------------------------------------------------------


def test_zero_shard_spec_picks_first_divisible_dim():
    assert tuple(shreg.zero_shard_spec((16, 4), 8, DATA_AXIS)) == \
        (DATA_AXIS, None)
    # First dim indivisible -> the rule walks to the next.
    assert tuple(shreg.zero_shard_spec((3, 24), 8, DATA_AXIS)) == \
        (None, DATA_AXIS)
    # No divisible dim (scalars, odd shapes) -> replicated.
    assert tuple(shreg.zero_shard_spec((6,), 8, DATA_AXIS)) == ()
    assert tuple(shreg.zero_shard_spec((), 8, DATA_AXIS)) == ()


def test_place_zero_sharded_bytes_per_device(eight_devices):
    mesh = data_mesh(eight_devices)
    tree = {
        "w": jnp.zeros((16, 16), jnp.float32),    # shards: 1024 -> 128 B
        "b": jnp.zeros((5,), jnp.float32),        # replicates: 20 B
    }
    placed = shreg.place_zero_sharded(tree, mesh, DATA_AXIS)
    assert tuple(placed["w"].sharding.spec) == (DATA_AXIS, None)
    assert tuple(placed["b"].sharding.spec) == ()
    assert shreg.tree_bytes_per_device(placed) == 1024 // 8 + 20
    # On a 1-device mesh the helper is a safe replicate-everything.
    solo = data_mesh(eight_devices, 1)
    placed1 = shreg.place_zero_sharded(tree, solo, DATA_AXIS)
    assert shreg.tree_bytes_per_device(placed1) == 1024 + 20


def test_trainer_fsdp_placement_shards_params_and_moments(eight_devices,
                                                         tmp_path):
    """The TRAINER's own FSDP placement, not a toy tree: with
    ``shard_params`` and ``shard_opt_state`` on eight nodes, the
    parameters and the optimizer state each sit on a device at close
    to 1/8 of their bytes (the leaves no dim of which divides by 8
    replicate, hence the allowance)."""
    trainer = make_trainer(tmp_path, "fsdp-bytes", shard_params=True,
                           shard_opt_state=True)
    for tree in (trainer.state.params, trainer.state.opt_state):
        total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))
        ratio = shreg.tree_bytes_per_device(tree) / total
        assert 1.0 / 8 - 0.01 <= ratio <= 1.0 / 8 + 0.15, ratio


def test_row_placer_is_the_one_shared_rule(eight_devices):
    # Trainer placement and elastic migration share ONE per-node-row
    # rule: leading dim == n shards rows, everything else replicates.
    from trustworthy_dl_tpu.elastic import reassignment

    mesh = data_mesh(eight_devices)
    place = shreg.row_placer(mesh, DATA_AXIS, 8)
    rows = place(jnp.zeros((8, 3)))
    assert tuple(rows.sharding.spec) == (DATA_AXIS, None)
    odd = place(jnp.zeros((5, 3)))
    assert tuple(odd.sharding.spec) == ()
    # The elastic spelling IS the registry spelling.
    e_place, e_repl = reassignment.row_placer(mesh, DATA_AXIS, 8)
    assert tuple(e_place(jnp.zeros((8, 3))).sharding.spec) == \
        (DATA_AXIS, None)
    assert tuple(e_repl.spec) == ()


def test_serve_tp_mesh_contract(eight_devices):
    mesh = shreg.serve_tp_mesh(4, eight_devices)
    assert mesh.axis_names == (MODEL_AXIS,)
    assert mesh.devices.shape == (4,)
    with pytest.raises(ValueError, match=">= 1"):
        shreg.serve_tp_mesh(0)
    with pytest.raises(ValueError, match="needs 16 devices"):
        shreg.serve_tp_mesh(16, eight_devices)


# --------------------------------------------------------------------------
# Fast tier: control-plane riders (per-role predictive, scale-out vs up)
# --------------------------------------------------------------------------


def test_predictive_role_share_validation_and_partition():
    from trustworthy_dl_tpu.serve.control import (PredictiveArmConfig,
                                                  predicted_replicas)

    base = dict(mean_rps=16.0, burstiness=0.0, burst_period_s=4.0,
                per_replica_rps=8.0, lead_s=0.0, tick_duration_s=0.05)
    with pytest.raises(ValueError, match="in \\(0, 1\\]"):
        PredictiveArmConfig(role_share={"prefill": 0.0}, **base)
    with pytest.raises(ValueError, match="sum to <= 1.0"):
        PredictiveArmConfig(role_share={"prefill": 0.6, "decode": 0.6},
                            **base)
    cfg = PredictiveArmConfig(role_share={"prefill": 0.25, "decode": 0.75},
                              **base)
    # Fleet-wide: 16 rps / 8 per replica = 2.  The shares PARTITION it:
    # ceil(4*0.25)=1 prefill + ceil(4*0.75... no — rate first: 16*0.25=4
    # rps -> 1 replica; 16*0.75=12 rps -> 2 replicas.
    assert predicted_replicas(cfg, 0) == 2
    assert predicted_replicas(cfg, 0, role="prefill") == 1
    assert predicted_replicas(cfg, 0, role="decode") == 2
    # An undeclared role must raise — a silently fleet-wide number
    # would double-provision the pool that asked.
    with pytest.raises(ValueError, match="declares no share"):
        predicted_replicas(cfg, 0, role="draft")
    no_shares = PredictiveArmConfig(**base)
    with pytest.raises(ValueError, match="declares no share"):
        predicted_replicas(no_shares, 0, role="prefill")


def test_choose_scale_action_out_vs_up():
    from trustworthy_dl_tpu.serve.control import (AutoscalerConfig,
                                                  ScaleSignals,
                                                  choose_scale_action)

    cfg = AutoscalerConfig(min_replicas=1, max_replicas=4,
                           scale_up_queue_per_replica=4.0,
                           scale_down_queue_per_replica=0.5,
                           scale_up_occupancy=0.9,
                           scale_down_occupancy=0.2)

    def sig(q, occ):
        return ScaleSignals(tick=0, in_service=2, queue_per_replica=q,
                            occupancy=occ)

    # Occupancy-driven pressure with a shallow queue: the replicas are
    # compute-bound, not backlogged — wider shards help, more replicas
    # don't.  Scale UP.
    assert choose_scale_action(cfg, sig(1.0, 0.95), 2, 8) == "up"
    # Queue-driven pressure: more replicas drain a backlog.  Scale OUT.
    assert choose_scale_action(cfg, sig(8.0, 0.95), 2, 8) == "out"
    assert choose_scale_action(cfg, sig(1.0, 0.5), 2, 8) == "out"
    # At the TP ceiling the only move left is out.
    assert choose_scale_action(cfg, sig(1.0, 0.95), 8, 8) == "out"


def test_pool_mode_predictive_no_double_provision():
    """Re-enabling the predictive arm in pool mode: each pool consumes
    ONLY its declared share of the envelope (the per-role signal), an
    undeclared-share config keeps pool scalers reactive, and a quiet
    correctly-sized fleet performs ZERO scale actions — pinned against
    ``predict_fleet()`` (which predicts none)."""
    from test_fleet import FakeEngine

    from trustworthy_dl_tpu.chaos import FaultPlan
    from trustworthy_dl_tpu.obs.registry import MetricsRegistry
    from trustworthy_dl_tpu.serve import FleetConfig, ServingFleet
    from trustworthy_dl_tpu.serve.control import (AutoscalerConfig,
                                                  PredictiveArmConfig,
                                                  predicted_replicas)

    pred = PredictiveArmConfig(
        mean_rps=16.0, burstiness=0.0, burst_period_s=4.0,
        per_replica_rps=8.0, lead_s=0.0, tick_duration_s=0.05,
        role_share={"prefill": 0.25, "decode": 0.75})
    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=3, pool_roles=("prefill", "decode", "decode"),
            autoscale=AutoscalerConfig(
                min_replicas=1, max_replicas=4,
                scale_up_queue_per_replica=4.0,
                scale_down_queue_per_replica=-1.0,  # never idle-drain
                scale_up_occupancy=1.1, scale_down_occupancy=-1.0,
                scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
                scale_down_idle_ticks=10 ** 6,
                predictive=pred),
        ),
        engine_factory=factory, registry=MetricsRegistry(),
    )
    # Per-pool signals carry the pool's SLICE of the envelope, and the
    # slices can never jointly exceed the fleet-wide ask.
    sig_pre = fleet._scale_signals("prefill")
    sig_dec = fleet._scale_signals("decode")
    assert sig_pre.predicted_replicas == \
        predicted_replicas(pred, fleet.tick, role="prefill") == 1
    assert sig_dec.predicted_replicas == \
        predicted_replicas(pred, fleet.tick, role="decode") == 2
    assert fleet._scale_signals(None).predicted_replicas == \
        predicted_replicas(pred, fleet.tick) == 2
    # The demand is already covered (1 prefill + 2 decode in service):
    # a quiet fleet must breathe ZERO scale actions — predict_fleet of
    # an eventless plan pins exactly that.
    for _ in range(12):
        fleet.step()
    predicted = FaultPlan.scripted([]).predict_fleet(autoscale=True)
    observed = {k: fleet.counters[k] for k in predicted
                if k in fleet.counters}
    assert all(v == 0 for v in observed.values()), observed
    assert observed["scale_ups"] == predicted["scale_ups"] == 0
    # Without declared shares the pool signal is None (reactive-only,
    # the pre-split behaviour) — not the fleet-wide number.
    fleet2 = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=2, pool_roles=("prefill", "decode"),
            autoscale=AutoscalerConfig(
                min_replicas=1, max_replicas=4,
                scale_up_queue_per_replica=4.0,
                scale_down_queue_per_replica=-1.0,
                scale_up_occupancy=1.1, scale_down_occupancy=-1.0,
                predictive=PredictiveArmConfig(
                    mean_rps=16.0, burstiness=0.0, burst_period_s=4.0,
                    per_replica_rps=8.0)),
        ),
        engine_factory=factory, registry=MetricsRegistry(),
    )
    assert fleet2._scale_signals("decode").predicted_replicas is None
    assert fleet2._scale_signals(None).predicted_replicas == 2


def test_fleet_tp_scale_up_arrives_with_wider_shards():
    """Occupancy pressure with a shallow queue scales UP: the new
    capacity arrives with doubled TP (counted in chips_in_service),
    sticky across rebuilds, and the tp_scale_ups counter records the
    decision.  Queue pressure keeps scaling OUT at the current width."""
    from test_fleet import FakeEngine

    from trustworthy_dl_tpu.obs.registry import MetricsRegistry
    from trustworthy_dl_tpu.serve import FleetConfig, ServingFleet
    from trustworthy_dl_tpu.serve.control import AutoscalerConfig

    fakes = {}

    def factory(index, **kwargs):
        fakes[index] = FakeEngine(index, **kwargs)
        fakes[index].scheduler = type(  # compute-bound, empty queue
            "S", (), {"occupancy": 1.0, "max_seq": 64,
                      "tokens_in_flight": 0})()
        return fakes[index]

    fleet = ServingFleet(
        fleet_config=FleetConfig(
            num_replicas=2, tp_size=1, tp_max=4,
            autoscale=AutoscalerConfig(
                min_replicas=2, max_replicas=4,
                scale_up_queue_per_replica=4.0,
                scale_down_queue_per_replica=-1.0,
                scale_up_occupancy=0.9, scale_down_occupancy=-1.0,
                scale_up_cooldown_ticks=1, scale_down_cooldown_ticks=1,
                scale_down_idle_ticks=10 ** 6),
        ),
        engine_factory=factory, registry=MetricsRegistry(),
    )
    assert fleet.chips_in_service() == 2          # 2 replicas x tp 1
    fleet.step()                                   # occupancy fires: up
    assert fleet.counters["scale_ups"] == 1
    assert fleet.counters["tp_scale_ups"] == 1
    assert len(fleet.replicas) == 3
    assert fleet.replicas[2].tp == 2               # arrived wider
    assert fleet.chips_in_service() == 2 + 2


# --------------------------------------------------------------------------
# Slow tier: layout equivalence (dp / fsdp / tp)
# --------------------------------------------------------------------------


def make_trainer(tmp_path, tag, num_nodes=8, **cfg):
    trainer_cfg = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext",
        batch_size=2 * num_nodes, num_nodes=num_nodes, optimizer="adamw",
        learning_rate=3e-3, checkpoint_interval=10 ** 9,
        checkpoint_dir=str(tmp_path / f"ck_{tag}"), **cfg)
    from trustworthy_dl_tpu.engine import DistributedTrainer

    trainer = DistributedTrainer(trainer_cfg, model_overrides=dict(TINY))
    trainer.initialize()
    return trainer


@pytest.mark.slow
def test_layout_equivalence_dp_vs_fsdp_losses_and_verdicts(
        eight_devices, tmp_path):
    """The SAME seeded run under replicated and FSDP layouts: loss
    trajectories match within accumulation-order tolerance, the FSDP
    arm's params+moments are actually sharded (bytes/device near
    1/8th), and the detection verdicts — attacked mask, per-node
    status, trust trajectory — are IDENTICAL under a real poisoning
    plan."""
    from trustworthy_dl_tpu.attacks import (AdversarialAttacker,
                                            AttackConfig)

    t_dp = make_trainer(tmp_path, "dp", detector_warmup=4)
    t_fs = make_trainer(tmp_path, "fsdp", detector_warmup=4,
                        shard_params=True, shard_opt_state=True)
    ratio = (shreg.tree_bytes_per_device(t_fs.state.params)
             / shreg.tree_bytes_per_device(t_dp.state.params))
    assert ratio <= 1.0 / 8 + 0.15, ratio          # actually sharded
    ratio_opt = (shreg.tree_bytes_per_device(t_fs.state.opt_state)
                 / shreg.tree_bytes_per_device(t_dp.state.opt_state))
    assert ratio_opt <= 1.0 / 8 + 0.15, ratio_opt

    attacker = AdversarialAttacker(AttackConfig(
        attack_types=["gradient_poisoning"], target_nodes=[1],
        intensity=0.5, start_step=6))
    attacker.activate_attacks()
    plan = attacker.plan(8)
    batch = t_dp._node_batch(t_dp.model.example_batch(16))
    s_dp, s_fs = t_dp.state, t_fs.state
    for step in range(10):
        s_dp, m_dp = t_dp._train_step(s_dp, batch, plan)
        s_fs, m_fs = t_fs._train_step(s_fs, batch, plan)
        # Same math, different GSPMD accumulation order — the zero1
        # suite documents why early-Adam steps amplify epsilon noise.
        np.testing.assert_allclose(float(m_dp.loss), float(m_fs.loss),
                                   rtol=1e-3)
        # Verdicts are thresholded booleans — layout must not move them.
        assert np.array_equal(np.asarray(m_dp.attacked),
                              np.asarray(m_fs.attacked)), step
        assert np.array_equal(np.asarray(m_dp.status),
                              np.asarray(m_fs.status)), step
        # Trust scores are EMA-smoothed floats downstream of the loss, so
        # they inherit (and accumulate) the same layout noise; verdict
        # booleans above are the exact pins.
        np.testing.assert_allclose(np.asarray(m_dp.trust_scores),
                                   np.asarray(m_fs.trust_scores),
                                   atol=1e-3)


@pytest.mark.slow
def test_layout_equivalence_tp_training_loss(eight_devices, tmp_path):
    """Tensor-parallel training (2 nodes x 4-way TP) vs plain dp with
    the same seed: the loss trajectory agrees within GSPMD
    accumulation tolerance — the registry's tensor rules change the
    layout, not the math."""
    from trustworthy_dl_tpu.attacks import null_plan

    t_dp = make_trainer(tmp_path, "dp2", num_nodes=2)
    t_tp = make_trainer(tmp_path, "tp", num_nodes=2,
                        parallelism="tensor")
    qkv = t_tp.state.params["blocks"]["attn"]["qkv"]["w"]
    assert qkv.addressable_shards[0].data.shape[-1] < qkv.shape[-1]
    # One seeded batch, placed per trainer (the meshes differ: 2-way
    # data vs 2x4 data-model).
    raw = jax.tree_util.tree_map(
        np.asarray, t_dp.model.example_batch(4, jax.random.PRNGKey(0)))
    b_dp = t_dp._node_batch(raw)
    b_tp = t_tp._node_batch(raw)
    plan = null_plan(2)
    s_dp, s_tp = t_dp.state, t_tp.state
    for _ in range(4):
        s_dp, m_dp = t_dp._train_step(s_dp, b_dp, plan)
        s_tp, m_tp = t_tp._train_step(s_tp, b_tp, plan)
        np.testing.assert_allclose(float(m_dp.loss), float(m_tp.loss),
                                   rtol=2e-3)


@pytest.mark.slow
def test_serve_tp_streams_bit_identical_with_compile_once(eight_devices):
    """A TP-2 serve replica's streams are BIT-identical to single-device
    ``generate()`` (greedy), with the decode step compiled exactly once
    — the registry resolves one layout for both planes."""
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.models.generate import generate
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

    # Unique decode geometry (vocab 149): continues the process-global
    # jit-cache isolation sequence documented in test_fleet.py.
    cfg = gpt2.GPT2Config(vocab_size=149, n_positions=64, n_layer=2,
                          n_embd=32, n_head=4, dtype=jnp.float32)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    reqs = []
    for _ in range(5):
        plen = int(rng.integers(3, 10))
        new = int(rng.integers(2, 8))
        reqs.append((rng.integers(0, cfg.vocab_size, plen).tolist(), new))

    for tp in (1, 2):
        engine = ServingEngine(params, cfg, max_slots=3, max_seq=48,
                               queue_limit=16, tp_size=tp)
        cache_before = engine.scheduler.decode_cache_size()
        rids = [engine.submit(ServeRequest(prompt=p, max_new_tokens=n))
                for p, n in reqs]
        results = engine.run_until_idle()
        assert engine.scheduler.decode_cache_size() - cache_before == 1
        for rid, (prompt, new) in zip(rids, reqs):
            ref = np.asarray(generate(
                params, cfg, jnp.asarray([prompt], jnp.int32), new,
                temperature=0.0))[0, len(prompt):].tolist()
            assert results[rid].tokens == ref, (tp, rid)


@pytest.mark.slow
def test_evict_readmit_reproduces_registry_shardings(
        eight_devices, tmp_path):
    """Satellite regression: an evict/readmit cycle funnels through the
    SAME registry placement the trainer's init does, so after readmit
    the param/opt sharding specs are exactly the fresh-trainer specs —
    no layout drift across elastic churn."""
    from trustworthy_dl_tpu.attacks import null_plan
    from trustworthy_dl_tpu.elastic.reassignment import (
        evict_and_reshard, readmit_and_reshard)

    trainer = make_trainer(tmp_path, "elastic", shard_params=True,
                           shard_opt_state=True)
    before_params = shreg.mesh_spec_tree(trainer.state.params)
    before_opt = shreg.mesh_spec_tree(trainer.state.opt_state)
    batch = trainer._node_batch(trainer.model.example_batch(16))
    state = trainer.state
    for _ in range(2):
        state, _ = trainer._train_step(state, batch, null_plan(8))
    trainer.state = state

    record = evict_and_reshard(trainer, drop=[1, 3, 5, 7])
    assert record["new_device_count"] == 4
    # Mid-churn the 4-device mesh re-shards with the same rule (leaves
    # stay divisible), so bytes/device stays ~1/4 of replicated.
    sharded = [l for l in jax.tree_util.tree_leaves(trainer.state.params)
               if any(s == DATA_AXIS for s in l.sharding.spec)]
    assert sharded, "params lost their sharding after eviction"

    readmit_and_reshard(trainer, node_ids=[1, 3, 5, 7])
    after_params = shreg.mesh_spec_tree(trainer.state.params)
    after_opt = shreg.mesh_spec_tree(trainer.state.opt_state)
    assert after_params == before_params
    assert after_opt == before_opt
    # And training continues finitely on the restored layout (fresh
    # batch: the readmitted mesh enumerates devices in survivor-first
    # order, so pre-churn placements are a different device list).
    batch = trainer._node_batch(trainer.model.example_batch(16))
    state, metrics = trainer._train_step(trainer.state, batch,
                                         null_plan(8))
    assert np.isfinite(float(metrics.loss))
