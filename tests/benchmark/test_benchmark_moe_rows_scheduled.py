"""``moe_rows_scheduled_x``: the rows the grouped expert products' schedule
multiplies over the live pairs, by hand on XLA's 512 rows an expert, on the
shipped rule, and silent where there is nothing to read."""

import copy

import pytest

from bench_paths import ROOT, manifest_data

from benchmark.harness import manifest as mf
from benchmark.harness import result
from trustworthy_dl_tpu.ops import grouped_matmul as gm

MANIFEST = mf.Manifest(ROOT)
NAME = "moe_rows_scheduled_x"
CELL = "serve-solar2-longdoc"


def make_run(cell=CELL):
    entry = MANIFEST.cell(cell)
    config = copy.deepcopy(MANIFEST.config(entry["config"]))
    return result.Run(entry, config, MANIFEST.traffic(entry["traffic"]), 1,
                      45.0, True)


def read(run):
    return MANIFEST.reader(NAME)(run)


ENTRY = {"name": NAME, "unit": "x", "better": "lower",
         "source": "program_counter", "layer": "expert layer",
         "moves": "serve_tokens_per_s", "workloads": [CELL]}


def test_the_reader_is_found_by_name_and_its_entry_waits_for_its_list():
    """The reader is a file of its own, found by the metric's name.
    ``BENCHMARK.json`` cannot list it for the cell yet:
    ``test_benchmark_solar_open2.py`` pins the EXACT set of metrics that
    name the cell, and no PR but a ``benchmark`` PR may edit that file.
    Once one has made that test ask by membership and appended ``ENTRY``,
    this is the entry's form, and the cell reports the metric."""
    assert callable(MANIFEST.reader(NAME))
    data = copy.deepcopy(manifest_data())
    listed = [m for m in data["per_layer"] if m["name"] == NAME]
    assert listed in ([], [ENTRY])
    grown = mf.Manifest(ROOT)
    grown.data = dict(data, per_layer=[m for m in data["per_layer"]
                                       if m["name"] != NAME] + [ENTRY])
    assert NAME in [m["name"] for m in grown.per_layer(CELL)]
    for cell in ("serve-large-docbatch", "train-124m-trust-1chip",
                 "train-124m-trust-dp4"):
        assert NAME not in [m["name"] for m in grown.per_layer(cell)]


def test_512_rows_an_expert_read_37_6(monkeypatch):
    """XLA's schedule, 512 rows a held expert whatever it got: 40 x 512 in
    the decode call and in the chunk call, over 64 + 1,024 live pairs."""
    monkeypatch.setattr(gm, "scheduled_rows",
                        lambda m, sizes: 512 * len(sizes))
    assert read(make_run()) == pytest.approx(2 * 40 * 512 / 1088)
    assert read(make_run()) == pytest.approx(37.647, abs=1e-3)


def test_shipped_rule_by_hand():
    """A decode call: 64 slots x 8 = 512 sorted rows, 64 live, 24 experts
    of 2 pairs and 16 of 1; in tiles of 16 no expert straddles (every
    boundary is even and falls between two experts of 2): 40 visits.  A
    chunk call: 8,192 rows, 1,024 live, 24 experts of 26 and 16 of 25; in
    tiles of 128 each of the 7 inner boundaries cuts an expert: 47."""
    run = make_run()
    deployment = run.config["deployment"]
    assert (run.config["num_experts_per_tok"], run.config["n_routed_experts"],
            run.config["n_routed_experts_published"],
            deployment["serve_config"]["max_slots"],
            deployment["prefill_chunk_positions"]) == (8, 40, 320, 64, 1024)
    assert (gm.row_tile(512, 40), gm.row_tile(8192, 40)) == (16, 128)
    assert gm.scheduled_rows(512, [2] * 24 + [1] * 16) == 40 * 16
    assert gm.scheduled_rows(8192, [26] * 24 + [25] * 16) == 47 * 128
    assert read(run) == pytest.approx((40 * 16 + 47 * 128) / (64 + 1024))


def test_silent_on_a_program_without_the_counter(monkeypatch):
    monkeypatch.delattr(gm, "scheduled_rows")
    assert read(make_run()) is None


@pytest.mark.parametrize("cell", ["train-124m-trust-1chip",
                                  "train-124m-trust-dp4",
                                  "serve-large-docbatch"])
def test_silent_where_no_routed_expert_is_served(cell):
    assert read(make_run(cell)) is None


def test_silent_without_a_chunk():
    run = make_run()
    del run.config["deployment"]["prefill_chunk_positions"]
    assert read(run) is None
