"""The command itself: it fails without a TPU and prints no device metric;
and, past the look for a chip, a run at a tiny size comes out correct, while
each fault a cell can have, and each control, comes out NOT correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT, manifest_data

RUN = os.path.join(BENCH, "run.py")
TINY = dict(vocab_size=128, n_positions=64, n_ctx=64, n_embd=32, n_layer=2,
            n_head=4)


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_fails_without_a_tpu_and_prints_no_result():
    done = subprocess.run(
        [sys.executable, RUN, "--workload", "train-124m-trust-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_cpu_env(), cwd=ROOT,
        timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    data = manifest_data()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in data["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "train-124m-trust-1chip", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=_cpu_env(), cwd=tmp_path,
        timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_unknown_cell_is_an_error():
    done = subprocess.run(
        [sys.executable, RUN, "--workload", "no-such-cell"],
        capture_output=True, text=True, env=_cpu_env(), cwd=ROOT,
        timeout=300)
    assert done.returncode != 0 and done.stdout.strip() == ""


# -- past the look for a chip, at a tiny size ---------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The benchmark's own data files, cut to a size a test run can hold:
    same cells, mixes, metrics and drivers; tiny widths are for tests only."""
    root = tmp_path_factory.mktemp("tiny")
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), root / "benchmark" / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)

    def edit(rel, change):
        path = root / "benchmark" / rel
        data = json.load(open(path))
        change(data)
        json.dump(data, open(path, "w"))

    edit("configs/gpt2-124m.json", lambda c: c.update(TINY))
    edit("traffic/trust-4x2x1024.json",
         lambda m: m.update(seq_len=16, warm_steps=1))
    edit("traffic/trust-dp4-7x1024.json",
         lambda m: m.update(seq_len=16, warm_steps=1, per_node_batch=2))
    for cell in ("train-124m-trust-1chip", "train-124m-trust-dp4"):
        edit(f"limits/{cell}.json", lambda d: d.update(
            limits={"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4,
                    "grad_norm_gap": 0.006, "param_change_gap": 0.1}))
    return str(root)


def drive(cell, root, capsys, seed=5):
    import importlib.util

    spec = importlib.util.spec_from_file_location("benchmark_run_main", RUN)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    code = module.main(["--workload", cell, "--seed", str(seed),
                        "--seconds", "1", "--trace", "0", "--root", root],
                       skip_device_check=True)
    assert code == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compare"
    assert "correct:" in out.err.strip().splitlines()[-1]
    return line


TRAIN_CELLS = ["train-124m-trust-1chip", "train-124m-trust-dp4"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_sound_training_run_is_correct(tiny_root, capsys, cell):
    line = drive(cell, tiny_root, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert set(line["compare"]) == {"loss1", "loss2", "loss3",
                                    "grad_norm_gap", "param_change_gap"}


def test_a_number_with_a_null_limit_is_not_compared():
    from benchmark.harness import correct_train, result

    readings = {"losses": [1.0, 2.0], "grad_norms": [1.0, 2.0, 3.0],
                "change_norms": [1.0, 2.0, 3.0]}
    run = result.Run({}, {}, {}, 0, 1.0, False)
    correct_train.judge(run, readings, readings, {
        "loss1": 1e-5, "loss2": None, "grad_norm_gap": 0.01,
        "param_change_gap": 0.01})
    assert set(run.compare) == {"loss1", "grad_norm_gap",
                                "param_change_gap"} and run.correct
    with pytest.raises(KeyError):
        correct_train.judge(run, readings, readings, {"loss1": 1e-5})


def _break_step(monkeypatch, wrap):
    """Plant a fault under the timed path: the trainer's compiled step."""
    from benchmark.harness.drivers import train_steps

    build = train_steps.build_trainer

    def broken(run):
        trainer = build(run)
        trainer._train_step = wrap(trainer._train_step)
        return trainer

    monkeypatch.setattr(train_steps, "build_trainer", broken)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_root, capsys, monkeypatch):
    import jax

    def wrap(step):
        def unchanged(state, batch, plan):
            kept = jax.tree_util.tree_map(lambda a: a + 0, state)
            _, metrics = step(state, batch, plan)
            return kept, metrics
        return unchanged

    _break_step(monkeypatch, wrap)
    line = drive("train-124m-trust-1chip", tiny_root, capsys)
    assert line["correct"] is False
    over = {k for k, v in line["compare"].items() if v["value"] > v["limit"]}
    assert "param_change_gap" in over and "grad_norm_gap" in over


def test_half_of_the_batch_left_out_is_not_correct(tiny_root, capsys,
                                                   monkeypatch):
    import jax.numpy as jnp

    def wrap(step):
        def half(state, batch, plan):
            nodes = batch["input"].shape[0]
            first = {k: jnp.concatenate([v[:nodes // 2]] * 2)
                     for k, v in batch.items()}
            return step(state, first, plan)
        return half

    _break_step(monkeypatch, wrap)
    line = drive("train-124m-trust-1chip", tiny_root, capsys)
    assert line["correct"] is False


def test_the_exchange_between_chips_left_out_is_not_correct(
        tiny_root, capsys, monkeypatch):
    """Every chip is fed the first chip's rows, so the gathered mean is the
    first shard's gradient: what reaches the optimizer when no gradient
    crosses between chips."""
    import jax.numpy as jnp

    def wrap(step):
        def alone(state, batch, plan):
            first = {k: jnp.broadcast_to(v[:1], v.shape)
                     for k, v in batch.items()}
            return step(state, first, plan)
        return alone

    _break_step(monkeypatch, wrap)
    line = drive("train-124m-trust-dp4", tiny_root, capsys)
    assert line["correct"] is False
    over = {k for k, v in line["compare"].items() if v["value"] > v["limit"]}
    assert "grad_norm_gap" in over


@pytest.mark.parametrize("precision", ["fp8"])
def test_training_control_in_lower_precision_is_not_correct(
        tiny_root, capsys, monkeypatch, precision):
    """The reference, put in the program's place, computed in the nearest
    precision below the bfloat16 the configuration states."""
    from benchmark.harness import correct_train
    from benchmark.harness.drivers import train_steps

    def control(run, trainer):
        opt = dict(run.config["assumed"]["optimizer"],
                   nodes=int(run.mix["nodes"]))
        return correct_train.reference_readings(
            run.seed, run.config,
            train_steps.batches(run, 0, int(run.mix["proof_steps"])), opt,
            precision=precision)

    monkeypatch.setattr(train_steps, "proof_steps", control)
    line = drive("train-124m-trust-1chip", tiny_root, capsys)
    assert line["correct"] is False
