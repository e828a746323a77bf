"""The traffic generator: a mix's work does not depend on the seed."""

import glob
import json
import os

import numpy as np
import pytest

from bench_paths import BENCH

from benchmark.harness import traffic

MIXES = {os.path.basename(p)[:-5]: json.load(open(p))
         for p in sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))}
TRAIN = [name for name, mix in MIXES.items() if mix["kind"] == "train-steps"]
SEEDS = [(0, 1), (7, 2_500_000_123), (2 ** 31 + 5, 3)]


@pytest.mark.parametrize("seeds", SEEDS, ids=str)
@pytest.mark.parametrize("name", TRAIN)
def test_two_seeds_offer_the_same_tokens(name, seeds):
    mix = MIXES[name]
    one = traffic.offered(mix)
    assert one["tokens"] == (mix["nodes"] * mix["per_node_batch"]
                             * mix["seq_len"])
    a, b = (traffic.train_batch(s, 4, one["rows"], mix["seq_len"], 50257)
            for s in seeds)
    assert a["input"].size == b["input"].size == one["tokens"]
    assert not np.array_equal(a["input"], b["input"])


@pytest.mark.parametrize("stream", ["train:0", "train:1", "x"])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 33 + 1])
def test_rng_takes_seeds_wider_than_32_bits(seed, stream):
    a = traffic.rng_for(seed, stream).integers(0, 1 << 30, 4)
    b = traffic.rng_for(seed, stream).integers(0, 1 << 30, 4)
    c = traffic.rng_for(seed + (1 << 32), stream).integers(0, 1 << 30, 4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


@pytest.mark.parametrize("name", TRAIN)
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7])
def test_train_batches(name, seed):
    mix = MIXES[name]
    rows = mix["nodes"] * mix["per_node_batch"]
    a = traffic.train_batch(seed, 0, rows, mix["seq_len"], 50257)
    b = traffic.train_batch(seed, 0, rows, mix["seq_len"], 50257)
    c = traffic.train_batch(seed, 1, rows, mix["seq_len"], 50257)
    assert np.array_equal(a["input"], b["input"])
    assert not np.array_equal(a["input"], c["input"])
    assert a["input"].shape == a["target"].shape == (rows, mix["seq_len"])
    assert np.array_equal(a["input"][:, 1:], a["target"][:, :-1])
    assert len({row.tobytes() for row in a["input"]}) == rows
    assert a["input"].max() > 40000 and a["input"].min() >= 0
