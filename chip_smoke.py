#!/usr/bin/env python
"""Does the system still start on the chip?  The quickest proof.

``python chip_smoke.py`` drives the main path ONCE on one TPU chip, through
the entry points a user would call, at the published widths of GPT-2 124M
(12 layers, 768 wide, 12 heads, vocabulary 50257, 1024 positions; weights
random, from ``--seed``):

* **train** — ``trustworthy_dl_tpu.cli.main`` (4 logical nodes, data
  parallel, detection and gradient verification ON, a handful of steps,
  then the checkpoint save the CLI does); then a few more steps through
  ``DistributedTrainer(model_overrides=...)`` at sequence length 1024, where
  ``attn_impl="auto"`` puts the Pallas flash kernel in forward and backward.
* **kernels** — the paged decode and chunked-prefill kernels against the
  ``jnp`` reference, for every KV storage dtype the serve CLI offers.
* **serve** — ``trustworthy_dl_tpu.cli.serve_main`` on the checkpoint the
  train phase wrote: paged pool, output monitor ON, ``attn_impl`` at its
  default; greedy streams are compared with ``generate()``.
* whether ``block_until_ready`` waits on this chip.

``python chip_smoke.py --chips 4`` runs ONLY the four-chip phase: the
canonical attacked drive (gradient poisoning on one node, detection, trust
collapse, elastic eviction, training continues on three) with one node per
chip, beside the same seeded drive on a one-device mesh.

One process; JAX is touched once; no child needs the chip.  Everything
worth reading is printed on earlier lines; the LAST line of stdout is one
JSON object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  No TPU, a failed check or a raised phase is ``"ok":
false`` and a non-zero exit — there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, NamedTuple, Optional, Sequence
from unittest import mock


class Size(NamedTuple):
    """What a run is sized by.  ``FULL`` is what the chip runs; the tests
    drive the same phase functions on the CPU mesh at a tiny size."""

    model_overrides: Dict[str, Any]   # {} = GPT-2 124M as published
    lr: float
    cli_steps: int                    # steps of the CLI train run
    cli_batch: int                    # its global batch (4 nodes)
    long_seq: int                     # sequence length of the second run
    long_batch: int                   # its global batch (4 nodes)
    long_steps: int
    long_remat: bool
    serve_max_seq: int
    serve_prompt_len: int
    serve_new_tokens: int
    serve_requests: int
    drive_seq: int                    # --chips 4: the attacked drive
    drive_per_node_batch: int
    drive_epoch_steps: int


FULL = Size(model_overrides={}, lr=3e-4, cli_steps=8, cli_batch=16,
            long_seq=1024, long_batch=8, long_steps=4, long_remat=False,
            serve_max_seq=256, serve_prompt_len=24, serve_new_tokens=16,
            serve_requests=6, drive_seq=128, drive_per_node_batch=2,
            drive_epoch_steps=8)

#: Attention outputs of the kernel and of the jnp path, on unit-variance
#: inputs with bf16 queries: one bf16 rounding of an O(1) value is 2**-8,
#: and the two paths accumulate in a different order.
KERNEL_ATOL = 2e-2
#: Pre-attack losses of the four-device and the one-device drive: the same
#: bf16 arithmetic, summed across devices in a different order.
DRIVE_LOSS_RTOL = 2e-2


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(message, flush=True)


def _finite(values: Sequence[float]) -> bool:
    return all(math.isfinite(float(v)) for v in values)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _check_training(trainer, steps: int, label: str) -> List[float]:
    """The checks both training runs share; returns the losses."""
    records = trainer.metrics_collector.batch_metrics
    losses = [r["loss"] for r in records]
    check(len(losses) == steps and trainer.global_step == steps,
          f"{label}: asked for {steps} steps, global_step="
          f"{trainer.global_step} with {len(losses)} recorded losses")
    check(_finite(losses), f"{label}: a loss is not finite: {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall ({losses[0]:.4f} -> "
          f"{losses[-1]:.4f})")
    trust = list(records[-1]["trust_scores"].values())
    check(_finite(trust), f"{label}: trust scores not finite: {trust}")
    return losses


def train_cli(size: Size, seed: int, workdir: str) -> str:
    """Train through the console entry point; returns the checkpoint
    directory it saved to."""
    from trustworthy_dl_tpu import cli
    from trustworthy_dl_tpu.engine import trainer as trainer_mod

    ckpt_dir = os.path.join(workdir, "checkpoints")
    config_path = os.path.join(workdir, "train_config.json")
    with open(config_path, "w") as f:
        # The CLI has no flag for these; its --config file does.
        json.dump({"seed": seed, "optimizer": "adamw",
                   "checkpoint_interval": 10 ** 9}, f)
    made: List[Any] = []

    class Recorded(trainer_mod.DistributedTrainer):
        """Keeps the trainer the entry point builds so its losses can be
        read afterwards; at the full size it changes nothing."""

        def __init__(self, config, mesh=None, model_overrides=None):
            super().__init__(config, mesh,
                             model_overrides or size.model_overrides)
            made.append(self)

    argv = ["--config", config_path, "--model", "gpt2", "--nodes", "4",
            "--parallelism", "data", "--epochs", "1",
            "--steps-per-epoch", str(size.cli_steps),
            "--batch-size", str(size.cli_batch),
            "--learning-rate", str(size.lr), "--checkpoint-dir", ckpt_dir]
    log(f"train[cli]: trustworthy-dl-train {' '.join(argv)}")
    t0 = time.perf_counter()
    with mock.patch.object(trainer_mod, "DistributedTrainer", Recorded):
        rc = cli.main(argv)
    wall = time.perf_counter() - t0
    check(rc == 0 and len(made) == 1, f"train[cli]: exit code {rc}")
    trainer = made[0]
    check(trainer.config.attack_detection_enabled
          and trainer.config.gradient_verification_enabled,
          "train[cli]: detection or gradient verification is off")
    losses = _check_training(trainer, size.cli_steps, "train[cli]")
    ticks = trainer.metrics_collector.step_time_stats()
    log(f"train[cli]: {len(losses)} steps at sequence length 128, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, wall {wall:.1f}s "
        f"(compile included), median host tick "
        f"{ticks.get('p50_s', float('nan')):.4f}s, checkpoint saved "
        f"under {ckpt_dir}")
    return ckpt_dir


def train_long(size: Size, seed: int, workdir: str, on_chip: bool) -> None:
    """A few steps through ``DistributedTrainer(model_overrides=...)`` (the
    README's library entry) at the sequence length where ``auto``
    attention picks the flash kernel."""
    import jax

    from trustworthy_dl_tpu import (DistributedTrainer, TrainingConfig,
                                    get_dataloader)

    config = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext",
        batch_size=size.long_batch, num_nodes=4, optimizer="adamw",
        learning_rate=size.lr, checkpoint_interval=10 ** 9,
        parallelism="data", seed=seed, async_host_depth=0,
        checkpoint_dir=os.path.join(workdir, "long_checkpoints"),
    )
    overrides = dict(size.model_overrides, seq_len=size.long_seq)
    if size.long_remat:
        overrides.update(remat=True, remat_policy="block")
    trainer = DistributedTrainer(config, model_overrides=overrides)
    trainer.initialize()
    loader = get_dataloader(
        "openwebtext", batch_size=size.long_batch, seq_len=size.long_seq,
        vocab_size=trainer.model.config.vocab_size,
        num_examples=size.long_batch * size.long_steps, seed=seed)
    batch = trainer._node_batch(next(iter(loader)))
    t0 = time.perf_counter()
    compiled = trainer._train_step.lower(
        trainer.state, batch, trainer.attack_plan).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    check(has_kernel == on_chip,
          f"train[T={size.long_seq}]: flash kernel in the compiled step is "
          f"{has_kernel}, expected {on_chip}")
    trainer.train(loader, num_epochs=1)
    losses = _check_training(trainer, size.long_steps,
                             f"train[T={size.long_seq}]")
    # Steady step time: the same compiled step, host clock closed by
    # block_until_ready.
    state, times = trainer.state, []
    for _ in range(3):
        t0 = time.perf_counter()
        state, metrics = trainer._train_step(state, batch,
                                             trainer.attack_plan)
        jax.block_until_ready(metrics.loss)
        times.append(time.perf_counter() - t0)
    trainer.state = state
    check(_finite([float(metrics.loss)]),
          f"train[T={size.long_seq}]: timed step's loss is not finite")
    tokens = size.long_batch * size.long_seq
    log(f"train[T={size.long_seq}]: {len(losses)} steps of {tokens} tokens "
        f"(4 nodes, detection ON, remat={size.long_remat}), loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, flash kernel in the step: "
        f"{has_kernel}, compile {compile_s:.1f}s, steady step "
        f"{sorted(times)[1]:.4f}s (median of 3, block_until_ready)")
    trainer.cleanup()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernel_parity(size: Size, seed: int, on_chip: bool) -> None:
    """Paged decode and chunked-prefill attention against the jnp reference
    at the server's pool geometry, for each KV storage dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.ops import paged_attention as pattn
    from trustworthy_dl_tpu.quant import int8 as q8

    cfg = gpt2.GPT2Config.from_name("gpt2", **{
        k: v for k, v in size.model_overrides.items() if k != "seq_len"})
    heads, head_dim = cfg.n_head, cfg.n_embd // cfg.n_head
    block, slots = 16, 4
    nbps = size.serve_max_seq // block
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    # Every slot owns a shuffled set of physical blocks; block 0 is trash.
    table = jnp.asarray(
        rng.permutation(np.arange(1, slots * nbps + 1)).reshape(slots, nbps),
        jnp.int32)
    pool_shape = (slots * nbps + 1, heads, block, head_dim)
    k_f32 = jax.random.normal(keys[0], pool_shape, jnp.float32)
    v_f32 = jax.random.normal(keys[1], pool_shape, jnp.float32)

    def stacked(a):
        """[NB, H, BLOCK(, Dh)] -> the pool's [2, NB, BLOCK, H(·Dh)],
        layer 1 the content and layer 0 its reverse: a kernel that read
        the wrong layer would not agree."""
        a = jnp.moveaxis(a, 1, 2).reshape(a.shape[0], block, -1)
        return jnp.stack([a[::-1], a])

    worst = 0.0
    for kv_dtype in ("float32", "bfloat16", "int8"):
        if kv_dtype == "int8":
            # quantize_kv scales per (head, position) over [.., T, Dh].
            k, ks = map(stacked, q8.quantize_kv(k_f32))
            v, vs = map(stacked, q8.quantize_kv(v_f32))
        else:
            k = stacked(k_f32.astype(kv_dtype))
            v = stacked(v_f32.astype(kv_dtype))
            ks = vs = None
        chunk = min(64, size.serve_max_seq // 2)
        for program, rows, r in (("decode", 1, slots),
                                 ("prefill", chunk, 1)):
            check(pattn.supports_paged_attention(
                head_dim=head_dim, block_size=block, kv_dtype=k.dtype,
                interpret=not on_chip, program=program, n_embd=cfg.n_embd),
                f"kernels: {program}/{kv_dtype} refused by the predicate")
            q = jax.random.normal(keys[2], (r, heads, rows, head_dim),
                                  jnp.bfloat16)
            start = jnp.asarray(
                rng.integers(block, size.serve_max_seq - rows, r), jnp.int32)
            attend = (pattn.paged_attention if program == "decode"
                      else pattn.paged_prefill_attention)
            got = attend(q, k, v, table[:r], start, layer=1, k_scale=ks,
                         v_scale=vs, interpret=not on_chip)
            with jax.default_matmul_precision("highest"):
                want = pattn.paged_attention_reference(
                    q, k, v, table[:r], start, layer=1, k_scale=ks,
                    v_scale=vs)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            check(err <= KERNEL_ATOL,
                  f"kernels: {program}/{kv_dtype} differs from the jnp "
                  f"reference by {err:.3e} (tolerance {KERNEL_ATOL})")
            worst = max(worst, err)
    log(f"kernels: paged decode and prefill x f32/bf16/int8 KV at block "
        f"{block}, {heads} heads x {head_dim}: max |kernel - jnp| = "
        f"{worst:.3e} (tolerance {KERNEL_ATOL}, "
        f"{'compiled' if on_chip else 'interpret'})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def _first_divergence(params, cfg, prompt: List[int], served: List[int],
                      ref: List[int]) -> str:
    """Where a served greedy stream leaves ``generate()``'s, and how far
    apart the two candidates' logits are there (plain f32 forward)."""
    import jax.numpy as jnp

    from trustworthy_dl_tpu.models import gpt2

    pos = next(i for i, (a, b) in enumerate(zip(served, ref)) if a != b)
    context = jnp.asarray([prompt + ref[:pos]], jnp.int32)
    logits = gpt2.forward(params, context, cfg)[0, -1].astype(jnp.float32)
    gap = float(logits[ref[pos]] - logits[served[pos]])
    return (f"position {pos}: served {served[pos]}, generate() {ref[pos]}, "
            f"logit gap {gap:.4e}")


def serve(size: Size, seed: int, ckpt_dir: str, on_chip: bool,
          kernels_agree: bool) -> None:
    """Serve through the console entry point on the trained checkpoint."""
    import jax.numpy as jnp
    import numpy as np

    from trustworthy_dl_tpu import cli
    from trustworthy_dl_tpu.models.generate import generate
    from trustworthy_dl_tpu.serve import ServingEngine

    built: List[Any] = []
    submitted: List[Any] = []
    from_config = ServingEngine.from_config.__func__

    def recording_from_config(cls, params, cfg, *args, **kwargs):
        engine = from_config(cls, params, cfg, *args, **kwargs)
        submit = engine.submit

        def recording_submit(request):
            rid = submit(request)
            if rid is not None:
                submitted.append((rid, request))
            return rid

        engine.submit = recording_submit
        built.append((engine, params, cfg))
        return engine

    argv = ["--model", "gpt2", "--checkpoint-dir", ckpt_dir,
            "--max-seq", str(size.serve_max_seq),
            "--num-requests", str(size.serve_requests),
            "--max-new-tokens", str(size.serve_new_tokens),
            "--prompt-len", str(size.serve_prompt_len),
            "--temperature", "0", "--seed", str(seed)]
    log(f"serve: trustworthy-dl-serve {' '.join(argv)}")
    # What the entry point prints is read the way its user would read it.
    captured = io.StringIO()
    with mock.patch.object(ServingEngine, "from_config",
                           classmethod(recording_from_config)), \
            contextlib.redirect_stdout(captured):
        rc = cli.serve_main(
            argv, model_overrides=size.model_overrides or None)
    printed = captured.getvalue()
    log(printed.rstrip())
    check(rc == 0 and len(built) == 1, f"serve: exit code {rc}")
    check(f"restored step {size.cli_steps} from {ckpt_dir}" in printed
          and "random init" not in printed,
          "serve: did not restore the train phase's checkpoint")
    paths = dict(
        item.split("=") for line in printed.splitlines()
        if line.strip().startswith("attn_kernel_paths:")
        for item in line.split(":", 1)[1].split())
    want = "pallas" if on_chip else "jnp"
    check(paths.get("decode") == want and paths.get("prefill") == want,
          f"serve: decode and prefill resolved to {paths}, expected {want}")
    engine, params, cfg = built[0]
    check(engine.monitor is not None,
          "serve: the output monitor is off")
    check(len(submitted) == size.serve_requests,
          f"serve: {len(submitted)} of {size.serve_requests} admitted")
    differing = []
    for rid, request in submitted:
        result = engine.results[rid]
        check(result.status == "completed"
              and len(result.tokens) == request.max_new_tokens,
              f"serve: request {rid} ended {result.status} with "
              f"{len(result.tokens)}/{request.max_new_tokens} tokens")
        prompt = list(request.prompt)
        ref = np.asarray(generate(
            params, cfg, jnp.asarray([prompt], jnp.int32),
            request.max_new_tokens, temperature=0.0))[0, len(prompt):]
        if result.tokens != ref.tolist():
            differing.append(f"request {rid}: " + _first_divergence(
                params, cfg, prompt, result.tokens, ref.tolist()))
    for line in differing:
        log(f"serve: stream differs from generate() — {line}")
    # A differing stream is a near-tie flipped by the kernel's summation
    # order only if the kernels agree with the jnp path; otherwise it is
    # a wrong answer.
    check(not differing or kernels_agree,
          f"serve: {len(differing)} stream(s) differ from generate() and "
          "the kernels do not agree with the jnp path")
    log(f"serve: {len(submitted)} requests completed with the tokens asked "
        f"for, {len(submitted) - len(differing)} greedy streams identical "
        f"to generate(), kernel paths {paths}")


# ---------------------------------------------------------------------------
# block_until_ready
# ---------------------------------------------------------------------------


def block_until_ready_waits(on_chip: bool) -> bool:
    """Time a chain of matmuls whose cost is known: at the chip's peak it
    cannot finish sooner than FLOPs / peak.  The benchmark's training
    driver closes its windows with ``jax.block_until_ready``; on another
    backend it once returned early."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trustworthy_dl_tpu.obs.report import peak_flops_per_chip

    n, links = (4096, 256) if on_chip else (256, 8)
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(a, x):
        # One dispatch of ``links`` dependent matmuls; every product is
        # again the matrix of 1/n, exactly, so nothing over- or underflows.
        return jax.lax.fori_loop(
            0, links, lambda _, a: (a @ x).astype(jnp.bfloat16), a)

    def timed():
        t0 = time.perf_counter()
        out = chain(x, x)
        dispatched = time.perf_counter() - t0
        jax.block_until_ready(out)
        ready = time.perf_counter() - t0
        corner = float(np.asarray(out[0, 0]))
        return dispatched, ready, time.perf_counter() - t0, corner

    timed()                          # compiles the chain and the pull
    dispatched, ready, pulled, corner = timed()
    check(corner == 1.0 / n, f"the chain computed {corner}, not {1.0 / n}")
    peak, source = peak_flops_per_chip(jax.devices()[0].device_kind)
    floor = 2.0 * n ** 3 * links / peak
    waits = ready >= floor and (pulled - ready) <= 0.25 * ready
    log(f"block_until_ready waits on this device: {waits} — {links} "
        f"chained {n}x{n} bf16 matmuls in one program: dispatched "
        f"{dispatched:.4f}s, ready {ready:.4f}s, host pull after it "
        f"+{pulled - ready:.4f}s; floor at peak {floor:.4f}s ({source})")
    return waits


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------


def _devices_of(tree) -> set:
    import jax

    return {d for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.sharding.device_set}


def _row_devices(node_batch) -> set:
    """Devices holding the per-node rows, one distinct row block each."""
    arr = node_batch["input"]
    rows = {shard.device: shard.index[0].start or 0
            for shard in arr.addressable_shards}
    check(len(set(rows.values())) == len(rows),
          f"two devices hold the same node rows: {rows}")
    return set(rows)


def attacked_drive(size: Size, seed: int, devices, workdir: str,
                   on_chip: bool) -> Dict[str, Any]:
    """The canonical attacked drive of the verify skill on ``devices``:
    4 nodes train clean, node 2's gradients are poisoned, detection names
    it, its trust collapses, it is evicted, three nodes go on."""
    import numpy as np
    from jax.sharding import Mesh

    from trustworthy_dl_tpu import (AdversarialAttacker, AttackConfig,
                                    DistributedTrainer, TrainingConfig,
                                    get_dataloader)
    from trustworthy_dl_tpu.core.mesh import DATA_AXIS

    nodes, target = 4, 2
    batch = nodes * size.drive_per_node_batch
    steps = size.drive_epoch_steps
    config = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext", batch_size=batch,
        num_nodes=nodes, optimizer="adamw", learning_rate=size.lr,
        detector_warmup=4, checkpoint_interval=10 ** 9, parallelism="data",
        elastic_resharding=True, seed=seed, async_host_depth=0,
        checkpoint_dir=os.path.join(workdir, f"drive_{len(devices)}"),
    )
    trainer = DistributedTrainer(
        config, mesh=Mesh(np.array(devices), (DATA_AXIS,)),
        model_overrides=dict(size.model_overrides, seq_len=size.drive_seq))
    trainer.initialize()
    attacker = AdversarialAttacker(AttackConfig(
        attack_types=["gradient_poisoning"], target_nodes=[target],
        intensity=0.5, start_step=steps))
    attacker.activate_attacks()
    trainer.set_attack_plan(attacker.plan(nodes))

    def loader(num_nodes):
        return get_dataloader(
            "openwebtext", batch_size=num_nodes * size.drive_per_node_batch,
            seq_len=size.drive_seq,
            vocab_size=trainer.model.config.vocab_size,
            num_examples=batch * steps, seed=seed)

    def placement():
        node_batch = trainer._node_batch(
            next(iter(loader(trainer.config.num_nodes))))
        return _devices_of(trainer.state.params), _row_devices(node_batch)

    label = f"drive[{len(devices)} device(s)]"
    # Which spelling of the detector's reductions the step holds is a
    # dispatch decision (ops.for_mesh), so it is stated, not assumed: one
    # device takes the Mosaic moments kernel, a step GSPMD partitions over
    # several cannot hold one and takes the XLA reductions.
    kernels = trainer._train_step.lower(
        trainer.state, trainer._node_batch(next(iter(loader(nodes)))),
        trainer.attack_plan).as_text().count("tpu_custom_call")
    check((kernels > 0) == (on_chip and len(devices) == 1),
          f"{label}: {kernels} Mosaic kernel(s) in the step")

    # Epoch 0 is clean (the attack starts at step ``steps``); the attacked
    # epochs run until the one in which the eviction happened is over, so
    # the survivors have taken steps of their own.
    clean = loader(nodes)
    trainer.train_epoch(clean, 0)
    before = placement()
    pre_losses = [r["loss"] for r in trainer.metrics_collector.batch_metrics]
    attacked = loader(nodes)
    for epoch in (1, 2, 3):
        trainer.train_epoch(attacked, epoch)
        if trainer.reassignment_history:
            break
    after = placement()
    losses = [r["loss"] for r in trainer.metrics_collector.batch_metrics]
    named = [(int(r["node_id"]), str(r["attack_type"]))
             for r in trainer.attack_history]
    evictions = [r for r in trainer.reassignment_history
                 if "evicted_nodes" in r]
    check(_finite(losses), f"{label}: a loss is not finite")
    check(named[:1] == [(target, "gradient_poisoning")],
          f"{label}: node {target} was not the first named; "
          f"attack_history names {named}")
    check([r["evicted_nodes"] for r in evictions] == [[target]]
          and trainer.config.num_nodes == nodes - 1,
          f"{label}: expected exactly node {target} evicted "
          f"({trainer.reassignment_history})")
    evicted_at = evictions[0]["step"]
    check(trainer.global_step > evicted_at,
          f"{label}: no step ran after the eviction at step {evicted_at}")
    trust = trainer.trust_manager.get_trust_score(target)
    check(trust < 0.3, f"{label}: trust of node {target} is {trust:.3f}")
    log(f"{label}: {kernels} Mosaic kernel(s) in the step, "
        f"{trainer.global_step} steps, pre-attack loss "
        f"{pre_losses[0]:.4f} -> {pre_losses[-1]:.4f}, attack_history "
        f"names {named}, node {target} trust {trust:.3f}, evicted at step "
        f"{evicted_at}, {trainer.global_step - evicted_at} steps on "
        f"{trainer.config.num_nodes} nodes after; params/rows on "
        f"{len(before[0])}/{len(before[1])} device(s) before, "
        f"{len(after[0])}/{len(after[1])} after")
    trainer.cleanup()
    return {"pre_losses": pre_losses, "named": named, "before": before,
            "after": after}


def multichip(size: Size, seed: int, devices, workdir: str,
              on_chip: bool) -> None:
    """One node per chip beside the same seeded drive on one device."""
    import numpy as np

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    spread = attacked_drive(size, seed, list(devices[:4]), workdir, on_chip)
    single = attacked_drive(size, seed, list(devices[:1]), workdir, on_chip)
    for name, want_before, want_after, drive in (
            ("four-device", 4, 3, spread), ("one-device", 1, 1, single)):
        got = [len(s) for s in drive["before"] + drive["after"]]
        check(got == [want_before, want_before, want_after, want_after],
              f"{name} drive: params/rows on {got[:2]} devices before the "
              f"eviction and {got[2:]} after, expected {want_before} "
              f"and {want_after}")
    check(spread["named"] == single["named"],
          f"the drives name different attackers: {spread['named']} vs "
          f"{single['named']}")
    a, b = np.asarray(spread["pre_losses"]), np.asarray(single["pre_losses"])
    check(a.shape == b.shape and np.allclose(a, b, rtol=DRIVE_LOSS_RTOL),
          f"pre-attack losses differ past rtol {DRIVE_LOSS_RTOL}: "
          f"{a.tolist()} vs {b.tolist()}")
    log(f"multichip: placed on 4 distinct devices before the eviction and "
        f"3 after; both drives name {spread['named']}; pre-attack losses "
        f"agree to max rel {float(np.max(np.abs(a - b) / np.abs(b))):.2e} "
        f"(tolerance {DRIVE_LOSS_RTOL})")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def describe_device() -> Dict[str, Any]:
    """The device as JAX reports it — the first touch of JAX."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def run(chips: int, seed: int, device: Dict[str, Any]) -> None:
    import jax

    from trustworthy_dl_tpu import native
    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    check(device["platform"] == "tpu",
          f"no TPU: JAX found {device} (there is no CPU fallback)")
    check(device["count"] >= chips,
          f"asked for {chips} chips, JAX found {device['count']}")
    log(f"compile cache: {configure_compile_cache()}")
    log("loader tier: "
        + ("C++ (libtddl_native)" if native.native_available()
           else "numpy fallback"))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if chips == 4:
            multichip(FULL, seed, jax.devices(), workdir, on_chip=True)
            return
        check(block_until_ready_waits(on_chip=True),
              "block_until_ready returned before the work was done")
        kernel_parity(FULL, seed, on_chip=True)
        ckpt_dir = train_cli(FULL, seed, workdir)
        serve(FULL, seed, ckpt_dir, on_chip=True, kernels_agree=True)
        train_long(FULL, seed, workdir, on_chip=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights and the data")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4 runs only the four-chip attacked drive")
    args = parser.parse_args(argv)
    device, ok = None, False
    try:
        device = describe_device()
        log(f"device: {json.dumps(device)}")
        run(args.chips, args.seed, device)
        ok = True
    except Exception as exc:  # any failure is "ok": false and exit 1
        traceback.print_exc()
        log(f"FAILED: {type(exc).__name__}: {exc}")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
