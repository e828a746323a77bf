"""chip_smoke.py on the CPU: it must FAIL there (no CPU fallback), and its
phase functions must run end to end at a tiny size on the virtual mesh.

The chip run itself cannot happen here; what can is rehearsals 1 and 2 of
the on-chip-measurement guide — the control flow of every phase, and the
four-chip placement on four virtual devices.  The phase runs build jitted
trainers, so by the suite's budget policy they are ``slow``; the failing
paths, the ``block_until_ready`` probe and the compile-cache helper are
fast.
"""

from __future__ import annotations

import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from trustworthy_dl_tpu.utils import compile_cache  # noqa: E402

# Width cut to a toy; the CLI's loader still feeds 128-token windows of a
# 512-token vocabulary, so the position table and vocabulary hold those.
TINY = chip_smoke.Size(
    model_overrides=dict(n_layer=2, n_embd=32, n_head=4, vocab_size=512,
                         n_positions=128),
    lr=3e-3, cli_steps=6, cli_batch=8, long_seq=64, long_batch=8,
    long_steps=3, long_remat=False, serve_max_seq=64, serve_prompt_len=8,
    serve_new_tokens=6, serve_requests=3, drive_seq=16,
    drive_per_node_batch=2, drive_epoch_steps=8)


def _run_smoke(*args, cwd=REPO, script=REPO / "chip_smoke.py"):
    return subprocess.run(
        [sys.executable, str(script), *args], capture_output=True,
        text=True, timeout=300, cwd=cwd)


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one-chip", "four-chips"])
def test_smoke_on_the_cpu_fails_with_ok_false(args):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` (conftest sets the
    variable): non-zero exit, last line ``"ok": false`` with the device
    JAX did find, and no phase ran."""
    proc = _run_smoke(*args)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in proc.stdout
    assert "train[" not in proc.stdout and "drive[" not in proc.stdout


def test_smoke_without_the_program_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script still fails cleanly: ``"ok": false``, non-zero."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text((REPO / "chip_smoke.py").read_text())
    proc = _run_smoke(cwd=tmp_path, script=alone)
    assert proc.returncode != 0
    assert "FAILED: ModuleNotFoundError" in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


def test_last_line_shape_is_the_drivers_contract(monkeypatch, capsys):
    """On success the last line is exactly the contract's object, built
    from what JAX reports — checked with the phases stubbed out."""
    monkeypatch.setattr(chip_smoke, "describe_device", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(chip_smoke, "run", lambda chips, seed, device: None)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    monkeypatch.setattr(chip_smoke, "run", lambda chips, seed, device:
                        chip_smoke.check(False, "a phase failed"))
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAILED: SmokeFailure: a phase failed" in out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is False


def test_block_until_ready_probe_reports(capsys):
    """The probe times a chain of known cost and prints its verdict; on
    the CPU only its shape is pinned (the nominal CPU peak is no floor)."""
    assert chip_smoke.block_until_ready_waits(on_chip=False) in (True, False)
    line = capsys.readouterr().out
    assert re.search(r"block_until_ready waits on this device: "
                     r"(True|False) — 8 chained 256x256", line)
    assert "floor at peak" in line and "cpu-nominal-estimate" in line


# --------------------------------------------------------------------------
# The compile-cache helper: placed from outside, else fixed in the checkout
# --------------------------------------------------------------------------


def test_cache_honours_the_environment_and_sets_nothing(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own handling stands: the
    helper returns the variable's value and never updates the config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")

    def refuse(name, value):
        raise AssertionError(f"jax.config.update({name!r}) under the env var")

    monkeypatch.setattr(jax.config, "update", refuse)
    assert compile_cache.configure_compile_cache() == "/placed/from/outside"


def test_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    """Unset, the cache is ``<checkout>/.jax_cache`` — the same path for
    every process and run, never a temporary or per-run directory — and a
    second call is a no-op."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.DEFAULT_CACHE_DIR == want
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    monkeypatch.setattr(jax.config, "update", lambda *a: pytest.fail(
        "re-pointed an already placed cache"))
    assert compile_cache.configure_compile_cache() == want


def test_only_the_helper_places_the_cache():
    """No other code path sets ``jax_compilation_cache_dir``, and every
    entry point goes through the helper."""
    setter = re.compile(r"update\(\s*[\"']jax_compilation_cache_dir")
    sources = list(REPO.glob("*.py")) + [
        p for root in ("trustworthy_dl_tpu", "tests", "examples",
                       "experiments") for p in (REPO / root).rglob("*.py")]
    setters = sorted(str(p.relative_to(REPO)) for p in sources
                     if setter.search(p.read_text()))
    assert setters == ["trustworthy_dl_tpu/utils/compile_cache.py"]
    for entry in ("trustworthy_dl_tpu/cli.py", "benchmark/harness/common.py",
                  "chip_smoke.py", "tests/conftest.py"):
        assert "configure_compile_cache()" in (REPO / entry).read_text(), \
            entry


# --------------------------------------------------------------------------
# Rehearsals: the phases at a tiny size on the virtual CPU mesh (slow tier)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """The CLI train phase, run once; yields (workdir, checkpoint dir)."""
    workdir = str(tmp_path_factory.mktemp("smoke"))
    return workdir, chip_smoke.train_cli(TINY, 0, workdir)


@pytest.mark.slow
def test_train_cli_phase_saves_a_checkpoint(trained, capsys):
    _, ckpt_dir = trained
    assert Path(ckpt_dir).is_dir() and any(Path(ckpt_dir).iterdir())


@pytest.mark.slow
def test_train_long_phase(trained, capsys):
    """The library-entry run: off the chip ``auto`` attention stays on the
    XLA path, and the phase checks exactly that."""
    chip_smoke.train_long(TINY, 0, trained[0], on_chip=False)
    assert "flash kernel in the step: False" in capsys.readouterr().out
    with pytest.raises(chip_smoke.SmokeFailure, match="flash kernel"):
        chip_smoke.train_long(TINY, 0, trained[0], on_chip=True)


@pytest.mark.slow
def test_kernel_parity_phase(capsys):
    chip_smoke.kernel_parity(TINY, 0, on_chip=False)
    assert "max |kernel - jnp|" in capsys.readouterr().out


@pytest.mark.slow
def test_serve_phase_restores_the_trained_checkpoint(trained, capsys):
    """serve_main restores the step the train phase saved, every request
    completes, streams equal generate(), and the printed kernel paths are
    what the smoke reads them from."""
    chip_smoke.serve(TINY, 0, trained[1], on_chip=False, kernels_agree=True)
    out = capsys.readouterr().out
    assert f"restored step {TINY.cli_steps} from {trained[1]}" in out
    assert "attn_kernel_paths: decode=jnp prefill=jnp" in out
    assert "3 greedy streams identical to generate()" in out
    # On the chip the same output must say "pallas": a jnp path fails.
    with pytest.raises(chip_smoke.SmokeFailure, match="expected pallas"):
        chip_smoke.serve(TINY, 0, trained[1], on_chip=True,
                         kernels_agree=True)


@pytest.mark.slow
def test_serve_phase_refuses_random_init(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="did not restore"):
        chip_smoke.serve(TINY, 0, str(tmp_path / "no_checkpoint"),
                         on_chip=False, kernels_agree=True)


@pytest.mark.slow
def test_multichip_phase_places_on_four_devices_then_three(tmp_path,
                                                           capsys):
    """``--chips 4`` on four virtual devices: the attacked drive with one
    node per device beside the same drive on one device."""
    chip_smoke.multichip(TINY, 0, jax.devices()[:4], str(tmp_path),
                         on_chip=False)
    out = capsys.readouterr().out
    assert "params/rows on 4/4 device(s) before, 3/3 after" in out
    assert "params/rows on 1/1 device(s) before, 1/1 after" in out
    assert "both drives name [(2, 'gradient_poisoning')]" in out
