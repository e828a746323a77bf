"""Worker for the 2-process distributed smoke test (test_multiprocess.py).

Each process owns 4 virtual CPU devices; together they form one 8-device
'data' mesh.  The worker runs initialize_multihost -> build_mesh -> ONE
jitted trusted data-parallel train step on globally-sharded arrays — the
end-to-end path the reference only ever initialised
(distributed_trainer.py:99-114: NCCL init, zero collectives) — and prints
a parseable verdict.

Run:  python multiproc_worker.py <process_id> <num_processes> <port>
"""

from __future__ import annotations

import os
import sys


def main() -> int:
    process_id = int(sys.argv[1])
    num_processes = int(sys.argv[2])
    port = int(sys.argv[3])

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trustworthy_dl_tpu.core.mesh import (
        DATA_AXIS,
        build_mesh,
        initialize_multihost,
        shutdown_multihost,
    )

    initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes, jax.process_count()
    n_global = len(jax.devices())
    assert n_global == 4 * num_processes, n_global

    from trustworthy_dl_tpu.attacks import null_plan
    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.engine.state import init_train_state
    from trustworthy_dl_tpu.engine.step import build_train_step
    from trustworthy_dl_tpu.engine.optimizer import build_optimizer
    from trustworthy_dl_tpu.models import create_model

    num_nodes = n_global
    config = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext",
        batch_size=2 * num_nodes, num_nodes=num_nodes, optimizer="adamw",
        learning_rate=1e-3, checkpoint_interval=10_000, detector_warmup=2,
        parallelism="data",
    )
    mesh = build_mesh(num_nodes, "data")
    bundle = create_model("gpt2", n_layer=2, n_embd=32, n_head=4,
                          vocab_size=128, n_positions=32, seq_len=16)
    optimizer = build_optimizer(config)

    # Same seed on every process -> identical host values; explicit
    # device_put with a replicated NamedSharding makes them one logical
    # (globally consistent) array per leaf.
    params = bundle.init(jax.random.PRNGKey(0))
    state = init_train_state(
        jax.random.PRNGKey(1), params, optimizer.init(params),
        num_nodes=num_nodes, trust_threshold=config.trust_threshold,
        initial_trust=config.initial_trust,
        decay_rate=config.trust_decay_rate,
        recovery_rate=config.trust_recovery_rate,
        detector_window=config.detector_history,
    )
    repl = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, repl), state
    )

    # Per-process batch shard: each process materialises only the node
    # rows its local devices own, then assembles the global [n, b, T]
    # array — the multi-host data path of SURVEY §2.5.
    rng = np.random.default_rng(0)
    per_node = 2
    local_nodes = num_nodes // num_processes
    local = rng.integers(
        0, 128, (local_nodes, per_node, 16), dtype=np.int64
    )
    batch_sharding = NamedSharding(mesh, P(DATA_AXIS, None, None))
    batch = {
        "input": jax.make_array_from_process_local_data(
            batch_sharding, local, (num_nodes, per_node, 16)
        ),
        "target": jax.make_array_from_process_local_data(
            batch_sharding, np.roll(local, -1, -1),
            (num_nodes, per_node, 16)
        ),
    }

    train_step = jax.jit(build_train_step(bundle, config, optimizer),
                         donate_argnums=(0,))
    plan = null_plan(num_nodes)
    state, metrics = train_step(state, batch, plan)
    loss = float(metrics.loss)
    assert np.isfinite(loss), loss
    assert metrics.trust_scores.shape == (num_nodes,)
    print(f"MULTIPROC_OK process={process_id} loss={loss:.4f}", flush=True)
    shutdown_multihost()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
