"""``paged_grid_steps_x``: the paged kernels' grid steps a layer over one
step a (row, block), by hand on the schedule of before PR 32, on the shipped
one, and silent where there is nothing to read."""

import copy

import pytest

from bench_paths import ROOT, manifest_data

from benchmark.harness import manifest as mf
from benchmark.harness import result
from benchmark.harness.families import gpt2
from trustworthy_dl_tpu.ops import paged_attention as pa

MANIFEST = mf.Manifest(ROOT)
NAME = "paged_grid_steps_x"
CELL = "serve-large-docbatch"


def make_run(cell=CELL, **serve_config):
    entry = MANIFEST.cell(cell)
    config = copy.deepcopy(MANIFEST.config(entry["config"]))
    if serve_config:
        config["deployment"]["serve_config"].update(serve_config)
    return result.Run(entry, config, MANIFEST.traffic(entry["traffic"]), 1,
                      45.0, True)


def read(run):
    return MANIFEST.reader(NAME)(run)


def test_manifest_entry():
    (entry,) = [m for m in manifest_data()["per_layer"] if m["name"] == NAME]
    # Listed in the serving cell since PR 35 (the sweep of
    # ``test_benchmark_serve_readers.py`` selects by membership); a later
    # serving cell appends its name to the list.
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s"}
    assert CELL in cells
    assert NAME in [m["name"] for m in MANIFEST.per_layer(CELL)]
    for cell in ("train-124m-trust-1chip", "train-124m-trust-dp4"):
        assert NAME not in [m["name"] for m in MANIFEST.per_layer(cell)]


def test_one_head_eight_queries_a_step_reads_25_6(monkeypatch):
    """The schedule of before PR 32, a step a (row, head, tile of 8
    queries, block): 1 x 20 x 8 x 64 steps a chunk call and 24 x 20 x 64 a
    decode call, over (1 + 24) x 64."""
    monkeypatch.setattr(
        pa, "grid_steps",
        lambda program, rows, heads, nbps, t, head_dim, block_size,
        kv_dtype: (rows, heads, -(-t // 8), nbps))
    assert read(make_run()) == pytest.approx((10240 + 30720) / 1600)
    assert read(make_run()) == pytest.approx(25.6)


def test_shipped_schedule_is_one_step_a_row_and_block():
    run = make_run()
    serve = run.config["deployment"]["serve_config"]
    assert (run.config["n_head"], run.config["n_embd"], serve["max_slots"],
            serve["max_seq"], serve["block_size"], serve["kv_dtype"],
            run.config["deployment"]["prefill_chunk_positions"]) == (
        20, 1280, 24, 1024, 16, "model", 64)
    assert pa.grid_steps("prefill", 1, 20, 64, 64, 64, 16,
                         "bfloat16") == (1, 1, 1, 64)
    assert pa.grid_steps("decode", 24, 20, 64, 1, 64, 16,
                         "bfloat16") == (24, 1, 1, 64)
    assert read(run) == 1.0


def test_the_int8_tier_and_large_blocks_by_hand():
    """int8 blocks of 16 still take every head; f32 blocks of 1,024
    positions (2 MiB a head: K and V, lane-padded, double-buffered) leave
    the 8 MiB for 2 of the 20 heads a step: 10 steps a (row, block) in
    both calls."""
    assert read(make_run(kv_dtype="int8")) == 1.0
    assert pa.grid_steps("decode", 24, 20, 1, 1, 64, 1024,
                         "float32") == (24, 10, 1, 1)
    assert read(make_run(block_size=1024, kv_dtype="float32")) == 10.0


def test_silent_on_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(pa, "grid_steps")
    assert read(make_run()) is None


@pytest.mark.parametrize("cell", ["train-124m-trust-1chip",
                                  "train-124m-trust-dp4"])
def test_silent_where_nothing_is_served(cell):
    assert read(make_run(cell)) is None


def test_silent_without_a_chunk_or_a_paged_attention_layer(monkeypatch):
    run = make_run()
    del run.config["deployment"]["prefill_chunk_positions"]
    assert read(run) is None
    # a family none of whose layers is a paged attention layer
    monkeypatch.setattr(gpt2, "attention_layers", lambda config: [])
    assert read(make_run()) is None
    # and one where only some are: the others take no step
    monkeypatch.setattr(gpt2, "attention_layers",
                        lambda config: [(9, 20, 20, 64)])
    assert read(make_run()) == 1.0
