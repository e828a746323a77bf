"""Pipeline-stage restaffing: REAL layer-shard migration in model-parallel
mode.

This is the reference's headline capability on its own parallelism
strategy — ``reassign_node_tasks`` / ``perform_task_reassignment``
(distributed_trainer.py:324-380) promise to hand a compromised node's layer
partition to the max-trust node, but actually only alias a Python object and
relabel a string; the compromised layers either keep running or are silently
dropped from the forward pass (:154-157).

TPU-native restaffing is a *repartition*: block params are stage-stacked
[S, L/S, ...] over the 'stage' mesh axis (parallel/pipeline.py), so moving
layer shards is a reshape + device_put —

1. the compromised stage's device column leaves the mesh;
2. blocks (and their optimizer moments) unstack to [L, ...] and restack to
   [S', L/S'] where S' is the largest stage count ≤ S-1 dividing L — every
   layer, including the compromised stage's, keeps training on trusted
   hardware;
3. the S' highest-trust candidates staff the new stages (the reference's
   max-trust selection, :337-344) — candidates are the surviving on-mesh
   stages plus the trainer's idle pool (healthy nodes a previous restaff
   could not seat); unseated survivors park in the pool with their
   devices and re-enter at the next restaff;
4. per-stage detector/canary state re-initialises (stage k now computes a
   different layer slice — its old baselines describe the wrong
   distribution), trust rows carry over with their owners;
5. the pipeline step re-jits for S' stages (rare path, recompilation
   accepted per SURVEY §7.4(1)).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trustworthy_dl_tpu.core.mesh import STAGE_AXIS, build_mesh
from trustworthy_dl_tpu.engine.state import fleet_scalar_fields, \
    init_monitor_state

logger = logging.getLogger(__name__)


def choose_stage_count(num_layers: int, max_stages: int) -> int:
    """Largest S' ≤ max_stages with num_layers % S' == 0 (S'=1 always
    works: the degenerate single-stage pipeline is still a valid, complete
    model)."""
    for s in range(max_stages, 0, -1):
        if num_layers % s == 0:
            return s
    return 1


def _restack_leaf(leaf: Any, new_stages: int) -> Any:
    """[S, L/S, ...] -> [S', L/S', ...] preserving layer order."""
    total = leaf.shape[0] * leaf.shape[1]
    return leaf.reshape((new_stages, total // new_stages) + leaf.shape[2:])


def _under_blocks(path) -> bool:
    """THE 'this optimizer/param leaf belongs to the stage-stacked blocks
    subtree' predicate — shared by the moment restack and the placement
    pass so the two can never drift."""
    return any(
        getattr(k, "key", getattr(k, "name", None)) == "blocks"
        for k in path
    )


def restack_blocks(blocks: Any, new_stages: int) -> Any:
    """[S, L/S, ...] -> [S', L/S', ...] preserving layer order — the layer
    migration itself.  Works on any params-shaped pytree (block params and
    their optimizer moment mirrors alike)."""
    return jax.tree_util.tree_map(
        lambda leaf: _restack_leaf(leaf, new_stages), blocks
    )


def _restack_in_opt_state(opt_state: Any, new_stages: int,
                          old_shape_prefix) -> Any:
    """Restack every optimizer leaf that mirrors a stage-stacked block
    leaf.  Moments are per-parameter, so reshaping them alongside their
    layers is exact — Adam's mu/nu follow their weights to the new stage."""
    def maybe(path, leaf):
        if _under_blocks(path) and getattr(leaf, "ndim", 0) >= 2 and \
                tuple(leaf.shape[:2]) == old_shape_prefix:
            return _restack_leaf(leaf, new_stages)
        return leaf
    return jax.tree_util.tree_map_with_path(maybe, opt_state)


def restaff_pipeline(trainer, drop: Sequence[int]) -> Dict[str, Any]:
    """Evict compromised stage coordinates and repartition the model over
    the survivors.  ``drop`` holds CURRENT stage coordinates.  Returns the
    migration record (same contract as evict_and_reshard)."""
    from trustworthy_dl_tpu.parallel.pipeline import (
        init_canary_state,
        make_canary,
    )

    config = trainer.config
    if config.parallelism != "model":
        raise ValueError("restaff_pipeline requires parallelism='model'")
    S = config.num_nodes
    drop = sorted(set(int(d) for d in drop))
    survivors = [i for i in range(S) if i not in drop]
    if not survivors:
        raise ValueError("cannot evict every stage")

    # Quiesce the in-flight step before repartitioning and dropping the
    # old state (see evict_and_reshard — freeing still-being-written
    # output buffers races the async runtime).
    jax.block_until_ready(trainer.state)
    state = trainer.state
    blocks = state.params["blocks"]
    lead = jax.tree_util.tree_leaves(blocks)[0]
    num_layers = lead.shape[0] * lead.shape[1]

    # Staffing candidates: surviving on-mesh stages PLUS the idle pool —
    # healthy nodes parked by an earlier restaff (when S' < survivor
    # count, the leftovers wait here instead of being discarded; their
    # devices return to the mesh the next time the stage count allows).
    pool: Dict[int, list] = getattr(trainer, "_idle_pool", {})
    trust_scores = np.asarray(state.trust.scores)
    candidates = [
        (float(trust_scores[c]), trainer.node_map[c], c) for c in survivors
    ] + [
        (trainer.trust_manager.get_trust_score(nid), nid, None)
        for nid in sorted(pool)
    ]
    new_S = choose_stage_count(num_layers, len(candidates))

    t0 = time.perf_counter()

    # --- staffing: highest-trust candidates take the new stages ----------
    ranked = sorted(candidates, key=lambda x: -x[0])
    chosen = sorted(ranked[:new_S], key=lambda x: x[1])  # stable id order
    chosen_keys = {(nid, coord) for _, nid, coord in chosen}
    idle_entries = [e for e in candidates
                    if (e[1], e[2]) not in chosen_keys]

    # --- devices: evicted columns leave; chosen pool nodes bring theirs
    # back; idle columns park in the pool for the next restaff ----------
    mesh = trainer.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    old_devices = list(mesh.devices.flat)
    multi_device = sizes.get(STAGE_AXIS, 1) == S
    new_pool: Dict[int, list] = {}
    if multi_device:
        grid = mesh.devices.reshape(-1, S)
        new_devices = []
        for _, nid, coord in chosen:
            if coord is not None:
                new_devices.extend(list(grid[:, coord]))
            else:
                new_devices.extend(pool.get(nid, []))
        for _, nid, coord in idle_entries:
            new_pool[nid] = list(grid[:, coord]) if coord is not None \
                else list(pool.get(nid, []))
    else:
        # Dev mode (stages vmapped within fewer devices): no device moves.
        new_devices = old_devices
        for _, nid, coord in idle_entries:
            new_pool[nid] = []
    # Park the evicted stages' device columns so a cooled-off identity can
    # bring them back through the idle pool (_readmit_stages) — the
    # model-mode return path; without this an evicted column's hardware
    # would be lost to the run forever.
    for i in drop:
        trainer._evicted_devices[trainer.node_map[i]] = (
            list(mesh.devices.reshape(-1, S)[:, i]) if multi_device else []
        )
    new_mesh = build_mesh(new_S, "model", devices=new_devices)
    new_config = dataclasses.replace(config, num_nodes=new_S)

    # --- trust rows: on-mesh rows carry over; pool rows synthesise from
    # the host TrustManager's standing — TRUSTED for a healthy survivor a
    # previous restaff could not seat, RECOVERING with the boosted rate
    # for a cooled-off evicted identity re-entering on probation
    # (begin_probation; the reference's mode-blind recovery ladder,
    # trust_manager.py:198-206) ------------------------------------------
    from trustworthy_dl_tpu.trust.state import METRIC_DEFAULTS

    now = float(state.step) * config.time_per_step
    host = trainer.trust_manager.state

    def host_row(attr, nid, default):
        arr = np.asarray(getattr(host, attr))
        return arr[nid] if nid < arr.shape[0] else default

    def gather_rows(field, synth):
        rows = []
        arr = np.asarray(field)
        for score, nid, coord in chosen:
            rows.append(arr[coord] if coord is not None
                        else synth(score, nid))
        return jnp.asarray(np.stack(rows))

    trust = state.trust._replace(
        scores=gather_rows(state.trust.scores,
                           lambda s, nid: np.float32(s)),
        status=gather_rows(
            state.trust.status,
            lambda s, nid: np.int32(
                int(trainer.trust_manager.get_node_status(nid))
            ),
        ),
        update_count=gather_rows(state.trust.update_count,
                                 lambda s, nid: np.int32(0)),
        last_updated=gather_rows(state.trust.last_updated,
                                 lambda s, nid: np.float32(now)),
        decay_rate=gather_rows(state.trust.decay_rate,
                               lambda s, nid: np.float32(
                                   config.trust_decay_rate)),
        recovery_rate=gather_rows(
            state.trust.recovery_rate,
            lambda s, nid: np.float32(host_row(
                "recovery_rate", nid, config.trust_recovery_rate
            )),
        ),
        metrics=gather_rows(state.trust.metrics,
                            lambda s, nid: np.asarray(METRIC_DEFAULTS)),
        attack_count=gather_rows(
            state.trust.attack_count,
            lambda s, nid: np.int32(host_row("attack_count", nid, 0)),
        ),
    )

    # --- the layer migration: restack blocks + their moments ------------
    old_prefix = tuple(lead.shape[:2])
    new_blocks = restack_blocks(blocks, new_S)
    params = dict(state.params)
    params["blocks"] = new_blocks
    opt_state = _restack_in_opt_state(state.opt_state, new_S, old_prefix)

    # --- fresh per-stage intelligence (stage k = new layer slice) --------
    from trustworthy_dl_tpu.detect.baseline import init_baseline_state
    from trustworthy_dl_tpu.detect.stats import NUM_GRADIENT_STATS
    from trustworthy_dl_tpu.detect.verifier import init_verifier_state

    window = state.out_baseline.ring.shape[1]
    num_leaves = state.monitor.grad_norm_avg.shape[1]
    out_bl = init_baseline_state(new_S, window, NUM_GRADIENT_STATS)
    grad_bl = init_baseline_state(new_S, window, NUM_GRADIENT_STATS)
    verifier = init_verifier_state(new_S)
    monitor = init_monitor_state(new_S, num_leaves)
    canary = init_canary_state(
        new_S, make_canary(trainer.model.config, config.canary_tokens)
    )

    # --- placement on the new mesh (declared logical-axis layout) --------
    # Stage-stacked leaves are DECLARED [STAGE, ...] in the sharding
    # registry's model-parallel rule table; resolving the repartition
    # through rules_for("model") + named_sharding keeps restaff on the
    # same declaration every other placement site reads, instead of
    # re-deriving the row split through the reassignment helpers (which
    # encode the per-NODE rule, coincidentally identical today).
    from trustworthy_dl_tpu.core import sharding as shreg

    rules = shreg.rules_for("model")
    repl = shreg.replicated_sharding(new_mesh)
    stage_size = dict(new_mesh.shape).get(STAGE_AXIS, 1)

    def place_stage(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd >= 1 and leaf.shape[0] == new_S and stage_size > 1 \
                and new_S % stage_size == 0:
            sharding = rules.named_sharding(
                new_mesh, shreg.STAGE, *([None] * (nd - 1)))
            return jax.device_put(leaf, sharding)
        return jax.device_put(leaf, repl)

    params["blocks"] = jax.tree_util.tree_map(place_stage, params["blocks"])
    params = {
        k: (v if k == "blocks"
            else jax.tree_util.tree_map(lambda a: jax.device_put(a, repl), v))
        for k, v in params.items()
    }

    def place_opt(path, leaf):
        if _under_blocks(path) and getattr(leaf, "ndim", 0) >= 2 and \
                leaf.shape[0] == new_S:
            return place_stage(leaf)
        return jax.device_put(leaf, repl)

    opt_state = jax.tree_util.tree_map_with_path(place_opt, opt_state)

    per_stage = dict(
        trust=trust, out_baseline=out_bl, grad_baseline=grad_bl,
        verifier=verifier, monitor=monitor, canary=canary,
        prev_suspects=jnp.zeros((new_S,), bool),
        clean_streak=jnp.zeros((new_S,), jnp.int32),
    )
    per_stage = {k: jax.tree_util.tree_map(place_stage, v)
                 for k, v in per_stage.items()}
    scalars = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, repl),
        {"step": state.step, "epoch": state.epoch, "rng": state.rng,
         **fleet_scalar_fields(state)},
    )
    new_state = state._replace(params=params, opt_state=opt_state,
                               **per_stage, **scalars)
    # NOTE: no jnp.copy re-owning here (unlike evict/readmit_and_reshard):
    # the restaff path has not exhibited the donated-alias crash the
    # data-parallel migrations did, and the pipeline step's shard_map
    # spec checks are strict about the exact placements this function
    # constructs — re-add the copy only with pipeline coverage green on
    # the target container.
    jax.block_until_ready(new_state)
    migration_time = time.perf_counter() - t0
    bytes_moved = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(
            (params["blocks"],)
        )
    )
    measured_gbps = bytes_moved / max(migration_time, 1e-9) / 1024**3

    # --- re-jit + host bookkeeping ---------------------------------------
    trainer.mesh = new_mesh
    trainer.config = new_config
    trainer._build_steps()
    trainer.state = new_state
    evicted_ids = [trainer.node_map[i] for i in drop]
    idle_ids = sorted(new_pool)
    new_map = [nid for _, nid, _ in chosen]
    trainer.node_map = new_map
    trainer._idle_pool = new_pool
    bits = np.array([bool(trainer._plan_bits.get(nid, False))
                     for nid in new_map], bool)
    trainer.attack_plan = trainer._place_plan(
        trainer.attack_plan._replace(target_mask=jnp.asarray(bits))
    )

    record = {
        "evicted_nodes": evicted_ids,
        "surviving_nodes": list(new_map),
        "idle_nodes": idle_ids,
        "old_num_stages": S,
        "new_num_stages": new_S,
        "layers_per_stage": num_layers // new_S,
        "migration_time_s": migration_time,
        "bytes_moved": bytes_moved,
        "measured_gbps": measured_gbps,
        "new_device_count": len(new_devices),
        "timestamp": time.time(),
    }
    logger.warning(
        "Pipeline restaff: stage(s) %s evicted; %d layers repartitioned "
        "%d -> %d stages on %d device(s) (%.1f MB in %.3fs); idle "
        "survivors %s", evicted_ids, num_layers, S, new_S,
        len(new_devices), bytes_moved / 2**20, migration_time, idle_ids,
    )
    return record
