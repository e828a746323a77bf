"""Traffic kind ``train-steps``: the trusted trainer, whole steps.

Drives ``DistributedTrainer`` as ``cli.main`` builds it (config, optimizer,
``train_epoch`` with its prefetch loader and async host queue).  Set-up
builds ONE trainer, takes it from the seed through its first steps (what
``correct`` compares) and a few warm steps, and hands the same object to
the window (``WindowFeed``).  The window counts the tokens of every step it
dispatches while less than ``--seconds`` have passed, and closes when
``train_epoch`` has returned (its full drain of the async host queue and its
epoch-end host sync done) and the last step's parameters are ready.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time
from typing import Any, Dict, Iterator, List

import numpy as np

from benchmark.harness import common, correct_train, traffic
from benchmark.harness.window import Window


class _NullTrace:
    def emit(self, *args: Any, **kwargs: Any) -> None:
        pass


class _PhaseLaps:
    """What ``trainer.obs`` has to be for the step loop to feed the
    program's ``StepTimeReporter`` phase laps, and nothing else: no trace
    bus, no watcher, no file."""

    anomaly = None
    compilewatch = None
    cost_ledger = None

    def __init__(self) -> None:
        from trustworthy_dl_tpu.obs.report import StepTimeReporter

        self.step_timer = StepTimeReporter()
        self.trace = _NullTrace()

    def on_step(self, step: int) -> None:
        pass


def build_trainer(run: Any) -> Any:
    """The trainer as ``cli.main`` builds it, for this cell's deployment,
    with the weights of the configuration's family in place of the model's
    own init."""
    from jax.sharding import Mesh

    from trustworthy_dl_tpu import DistributedTrainer, TrainingConfig
    from trustworthy_dl_tpu.core.mesh import DATA_AXIS

    mix, deployment = run.mix, run.config["deployment"]
    family = run.family
    nodes = int(mix["nodes"])
    config = TrainingConfig(
        model_name=deployment["model_name"],
        batch_size=nodes * int(mix["per_node_batch"]), num_nodes=nodes,
        seed=int(run.seed) % (1 << 31), checkpoint_interval=10 ** 9,
        checkpoint_dir=os.path.join(common.trace_dir(), "no_checkpoint"),
        **deployment["training_config"])
    trainer = DistributedTrainer(
        config, mesh=Mesh(np.array(run.devices), (DATA_AXIS,)),
        model_overrides=dict(family.sizes(run.config),
                             seq_len=int(mix["seq_len"])))
    trainer.model = dataclasses.replace(
        trainer.model,
        init=lambda key: family.make_weights(run.seed, run.config))
    trainer.initialize()
    if not (trainer.config.attack_detection_enabled
            and trainer.config.gradient_verification_enabled):
        raise RuntimeError("detection or gradient verification is off")
    return trainer


def batches(run: Any, start: int, count: int) -> List[Dict[str, np.ndarray]]:
    rows = traffic.offered(run.mix)["rows"]
    return [traffic.train_batch(
        run.seed, step, rows, int(run.mix["seq_len"]),
        run.family.vocab(run.config))
        for step in range(start, start + count)]


def _first_moment(opt_state: Any) -> Any:
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def proof_steps(run: Any, trainer: Any) -> Dict[str, Any]:
    """The first steps through ``train_epoch``: each loss, the first
    gradient's leaf norms (from Adam's first moment after step 1) and the
    leaf norms of the parameters' change after the last."""
    import jax

    steps = int(run.mix["proof_steps"])
    b1 = float(run.config["assumed"]["optimizer"]["b1"])
    family = run.family
    trainer.train_epoch(batches(run, 0, 1), 0)
    mu = _first_moment(trainer.state.opt_state)
    grad_norms = np.asarray(family.leaf_norms(mu)) / (1.0 - b1)
    trainer.train_epoch(batches(run, 1, steps - 1), 1)
    change = jax.tree_util.tree_map(
        lambda a, b: a - b, trainer.state.params,
        family.make_weights(run.seed, run.config))
    change_norms = np.asarray(family.leaf_norms(change))
    del change, mu
    records = trainer.metrics_collector.batch_metrics[:steps]
    return {"losses": [float(r["loss"]) for r in records],
            "grad_norms": grad_norms, "change_norms": change_norms}


class WindowFeed:
    """The window's feed, and the warm steps before it, in ONE epoch: the
    trainer builds its async host queue (and jits its metrics packer) anew
    in every ``train_epoch``, so a window in an epoch of its own would open
    on a compile.  The feed hands out the warm batches, waits until the
    device has finished the last of them, opens the window, and then hands
    out batches for as long as the window admits another step."""

    def __init__(self, run: Any, trainer: Any, window: Window,
                 first_step: int, on_open: Any):
        self.run, self.trainer, self.window = run, trainer, window
        self.step, self.on_open = first_step, on_open
        self.warm = int(run.mix["warm_steps"])
        self.tokens = traffic.offered(run.mix)["tokens"]

    def _next(self) -> Dict[str, np.ndarray]:
        batch = batches(self.run, self.step, 1)[0]
        self.step += 1
        return batch

    def _device_step(self) -> int:
        try:
            return int(self.trainer.state.step)
        except RuntimeError:
            # Read between a dispatch, which donates the old state, and the
            # loop's assignment of the new one: look again.
            return -1

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        for _ in range(self.warm):
            yield self._next()
        # The device's own step counter says when the last warm step is
        # done; the loop's thread is then waiting for the next batch.
        # (A broken step that never advances it is not waited for for ever;
        # what such a step produces is for ``correct`` to catch.)
        give_up = time.perf_counter() + 15.0
        while (self._device_step() < self.step
               and time.perf_counter() < give_up):
            time.sleep(0.002)
        self.on_open()
        while self.window.admits():
            self.window.count(self.tokens)
            yield self._next()


def run(run: Any, manifest: Any) -> None:
    import jax

    common.configure_jax(run)
    compiles = common.CompileCounter()
    mix = run.mix
    trainer = build_trainer(run)
    run.mark("trainer built, weights made")
    program = proof_steps(run, trainer)
    run.mark("three proof steps")
    done = int(mix["proof_steps"])

    # -- warm steps, then the window, in one epoch ---------------------------
    window = Window(run.seconds)

    def opened() -> None:
        gc.collect()
        gc.disable()
        compiles.mark_open()
        run.mark("warm steps, window opens")
        run.end_to_end["setup_s"] = common.setup_seconds(run)
        window.open()

    feed = WindowFeed(run, trainer, window, done, opened)
    try:
        trainer.train_epoch(feed, 2)
        jax.block_until_ready(trainer.state.params)
        window.close()
    finally:
        gc.enable()
    compiles.mark_close()
    compiles.into(run)
    chips = int(run.cell["chips"])
    run.end_to_end["train_tokens_per_s_per_chip"] = window.rate() / chips
    run.counters.update(window_steps=window.units, window_s=window.elapsed,
                        tokens_per_step=feed.tokens,
                        n_params=trainer.model.num_params(
                            trainer.state.params))
    losses = [float(r["loss"]) for r in
              trainer.metrics_collector.batch_metrics[-window.units:]]
    run.attempted = window.units
    run.failed = int(window.units - sum(np.isfinite(losses)))
    run.device["memory_peak_bytes"] = common.memory_peak_bytes(run)

    # -- the traced slice --------------------------------------------------
    if run.trace_on:
        laps = _PhaseLaps()
        trainer.obs = laps
        sliced = common.TracedSlice(run)
        steps = int(mix["trace_steps"])
        trainer.train_epoch(batches(run, feed.step, steps), 3)
        jax.block_until_ready(trainer.state.params)
        sliced.finish()
        trainer.obs = None
        run.counters["trace_steps"] = steps
        run.counters["phase_laps"] = laps.step_timer.report().get("phases")

    # -- correct: the reference, once the program's state is freed ----------
    trainer.cleanup()
    trainer.state = None
    del trainer
    gc.collect()
    opt = dict(run.config["assumed"]["optimizer"], nodes=int(mix["nodes"]))
    reference = correct_train.reference_readings(
        run.seed, run.config, batches(run, 0, int(mix["proof_steps"])), opt,
        rows=int(mix["reference_rows"]))
    correct_train.judge(run, program, reference,
                        manifest.limits(run.cell["name"]))
