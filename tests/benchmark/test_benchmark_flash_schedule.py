"""``flash_pairs_scored_x``: the flash kernels' schedule counter over the
pairs causal attention needs, by hand on a stub schedule, on the shipped
one, and silent where there is nothing to read."""

import importlib

import jax
import pytest

from bench_paths import ROOT, manifest_data

from benchmark.harness import kernel_work as kw
from benchmark.harness import manifest as mf
from benchmark.harness import result

MANIFEST = mf.Manifest(ROOT)
NAME = "flash_pairs_scored_x"
CELL = "train-124m-trust-1chip"
# (``ops.flash_attention`` the attribute is the entry function, which
# shadows its submodule.)
fa = importlib.import_module("trustworthy_dl_tpu.ops.flash_attention")


def make_run(cell=CELL, **mix):
    entry = MANIFEST.cell(cell)
    traffic = dict(MANIFEST.traffic(entry["traffic"]), **mix)
    return result.Run(entry, MANIFEST.config(entry["config"]), traffic, 1,
                      45.0, True)


def read(run):
    return MANIFEST.reader(NAME)(run)


@pytest.fixture
def on_tpu(monkeypatch):
    """The program takes the flash kernel on the TPU backend only
    (``auto_picks_flash``): say that this is one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_manifest_entry():
    (entry,) = [m for m in manifest_data()["per_layer"] if m["name"] == NAME]
    cells = entry.pop("workloads")      # a later cell appends its name
    assert entry == {
        "name": NAME, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_tokens_per_s_per_chip"}
    assert CELL in cells and "train-124m-trust-dp4" not in cells
    assert NAME in [m["name"] for m in MANIFEST.per_layer(CELL)]
    assert NAME not in [m["name"]
                        for m in MANIFEST.per_layer("train-124m-trust-dp4")]


def test_whole_tile_schedule_reads_1_998(on_tpu, monkeypatch):
    """One tile scored whole, masked half included: what two [512, 1024]
    grid steps a (batch, head) scored before PR 28."""
    monkeypatch.setattr(fa, "_blocks_for", lambda t, d: fa.Blocks(t, t))
    assert read(make_run()) == pytest.approx(1024 * 1024 / 524800)
    assert read(make_run()) == pytest.approx(1.998, abs=5e-4)


@pytest.mark.parametrize("sub,expected", [(128, 36 / 32.03125),
                                          (256, 40 / 32.03125),
                                          (512, 48 / 32.03125)])
def test_sub_tiles_by_hand(on_tpu, monkeypatch, sub, expected):
    """[sub, sub] sub-tiles of one [1024, 1024] tile: n (n + 1) / 2 of
    them on or under the diagonal, in units of 128 x 128 pairs."""
    monkeypatch.setattr(fa, "_blocks_for", lambda t, d: fa.Blocks(t, sub))
    assert read(make_run()) == pytest.approx(expected)


def test_shipped_schedule(on_tpu):
    run = make_run()
    t, d = 1024, 64
    assert (int(run.mix["seq_len"]), int(run.config["n_embd"])
            // int(run.config["n_head"])) == (t, d)
    value = read(run)
    assert value == fa.scheduled_pairs(t, d, True) / kw.causal_pairs(t, t)
    assert 1.0 <= value <= 1.5


@pytest.mark.parametrize("t", [2048, 4096, 8192])
def test_longer_sequences_waste_less(on_tpu, t):
    assert 1.0 <= read(make_run(seq_len=t)) < read(make_run())


def test_silent_without_a_sequence_length(on_tpu):
    run = make_run()
    del run.mix["seq_len"]
    assert read(run) is None


def test_silent_on_a_program_without_the_counter(on_tpu, monkeypatch):
    monkeypatch.delattr(fa, "scheduled_pairs")
    assert read(make_run()) is None


def test_silent_where_the_program_takes_no_flash_kernel(on_tpu, monkeypatch):
    """Under the length ``auto`` gives to XLA, and off the TPU backend."""
    assert read(make_run(seq_len=512)) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert read(make_run()) is None
