"""``paged_blocks_walked_x``: the blocks the paged kernels' walks copy over
the blocks that hold live positions, by hand on the recorded tick for the
shipped walk and for a schedule that walks the whole table (every row its 64
blocks, what a grid over the table's blocks pays), and silent where there is
nothing to read."""

import copy

import pytest

from bench_paths import ROOT, manifest_data, recorded_serve_run

from benchmark.harness import manifest as mf
from benchmark.harness import result
from benchmark.harness.families import gpt2
from trustworthy_dl_tpu.ops import paged_attention as pa

MANIFEST = mf.Manifest(ROOT)
NAME = "paged_blocks_walked_x"
CELL = "serve-large-docbatch"
ENTRY = {"name": NAME, "unit": "x", "better": "lower",
         "source": "program_counter", "layer": "kernels",
         "moves": "serve_tokens_per_s", "workloads": [CELL]}


def read(run):
    return MANIFEST.reader(NAME)(run)


def recorded(config=None):
    return recorded_serve_run(MANIFEST, CELL, config)[0]


def test_manifest_entry():
    """Appended to the serving cell's list and to no other: the solar
    cell's test pins its exact set, and the long-context cell's layers are
    latent (the family lists no paged attention layer)."""
    (entry,) = [m for m in manifest_data()["per_layer"] if m["name"] == NAME]
    # (a later serving cell appends its name to the list)
    cells = entry.pop("workloads")
    assert entry == {k: v for k, v in ENTRY.items() if k != "workloads"}
    assert CELL in cells
    assert NAME in [m["name"] for m in MANIFEST.per_layer(CELL)]
    for cell in ("train-124m-trust-1chip", "train-124m-trust-dp4",
                 "serve-solar2-longdoc", "serve-kimi-linear-longctx"):
        assert NAME not in [m["name"] for m in MANIFEST.per_layer(cell)]


#: The recorded tick: 18 decoding rows, 6 slots mid-prefill.
LENGTHS = [708, 1011, 977, 652, 657, 876, 755, 805, 955, 787, 819, 965, 764,
           575, 711, 682, 752, 694]
CHUNKS = [[640, 64], [640, 64], [512, 64], [320, 64], [320, 64], [64, 64]]
LIVE = 894 + 180        # blocks of 16 under the rows; under the chunks


def test_the_recorded_tick_is_the_one_reckoned_by_hand():
    tick, = recorded().counters["trace_ticks"]
    assert (tick["decode"], tick["prefill"]) == (LENGTHS, CHUNKS)
    assert sum(-(-n // 16) for n in LENGTHS) == 894
    assert sum(-(-(pos + rows) // 16) for pos, rows in CHUNKS) == 180


def test_every_row_the_whole_table_read_1_79(monkeypatch):
    """A schedule with the walk as the grid's fourth dimension: 64 blocks a
    row whatever it holds, 24 rows a decode call and one a chunk call."""
    monkeypatch.setattr(
        pa, "walked_blocks",
        lambda program, work, heads, nbps, t, **shape: nbps)
    assert read(recorded()) == pytest.approx((24 + 6) * 64 / LIVE)
    assert read(recorded()) == pytest.approx(1.7877, abs=1e-4)


def test_shipped_walk_by_hand():
    """A decode row walks waves of 16 blocks: ten of the rows end in their
    third wave (48 blocks), eight in the fourth (64), and each of the six
    mid-prefill slots' trash rows walks one wave for nothing.  A chunk
    walks waves of 8 beside its 64 query rows: 6, 6, 5, 3, 3 and 1."""
    kw = dict(head_dim=64, block_size=16, kv_dtype="bfloat16")
    assert pa._step_shape("decode", heads=20, t=1, **kw)[2] == 16
    assert pa._step_shape("prefill", heads=20, t=64, **kw)[2] == 8
    decode = [pa.walked_blocks("decode", n, 20, 64, 1, **kw)
              for n in LENGTHS]
    assert sorted(decode) == [48] * 10 + [64] * 8
    assert pa.walked_blocks("decode", 0, 20, 64, 1, **kw) == 16
    chunks = [pa.walked_blocks("prefill", tuple(c), 20, 64, 64, **kw)
              for c in CHUNKS]
    assert chunks == [48, 48, 40, 24, 24, 8]
    walked = 10 * 48 + 8 * 64 + 6 * 16 + 192
    assert read(recorded()) == pytest.approx(walked / LIVE)
    assert read(recorded()) == pytest.approx(1.1918, abs=1e-4)


def test_layers_cancel_and_a_family_under_other_names_reads_the_same(
        monkeypatch):
    ours = read(recorded())
    monkeypatch.setattr(gpt2, "attention_layers",
                        lambda config: [(9, 20, 20, 64)])
    assert read(recorded()) == pytest.approx(ours)


def test_silent_on_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(pa, "walked_blocks")
    assert read(recorded()) is None


def test_silent_without_traced_ticks_or_on_ticks_that_held_other_work():
    entry = MANIFEST.cell(CELL)
    untraced = result.Run(entry, MANIFEST.config(entry["config"]),
                          MANIFEST.traffic(entry["traffic"]), 1, 45.0, False)
    assert read(untraced) is None
    run = recorded()
    run.counters["trace_ticks"] = [dict(run.counters["trace_ticks"][0],
                                        expected=17)]
    assert read(run) is None


@pytest.mark.parametrize("cell", ["train-124m-trust-1chip",
                                  "train-124m-trust-dp4"])
def test_silent_where_nothing_is_served(cell):
    entry = MANIFEST.cell(cell)
    run = result.Run(entry, MANIFEST.config(entry["config"]),
                     MANIFEST.traffic(entry["traffic"]), 1, 45.0, True)
    assert read(run) is None


def test_silent_without_a_chunk_or_a_paged_attention_layer(monkeypatch):
    config = copy.deepcopy(MANIFEST.config(MANIFEST.cell(CELL)["config"]))
    del config["deployment"]["prefill_chunk_positions"]
    assert read(recorded(config)) is None
    monkeypatch.setattr(gpt2, "attention_layers", lambda config: [])
    assert read(recorded()) is None
