"""The Solar Open 2 family and its cell: the family resolves by its
``model_type`` and gives every entry a serving cell calls; its work counts
by hand at the configuration's sizes; the configuration's file against the
catalog's row; the mix fits; and, at a tiny size on the CPU through the real
``ServingEngine`` and the whole ``benchmark/run.py`` command, the sound
engine is ``correct`` while the fp8 control and each of the four planted
faults are not, and the new readers read their counters."""

import json
import os
import shutil

import numpy as np
import pytest

from bench_paths import BENCH, ROOT

from benchmark.harness import (correct_serve, families, manifest as mf,
                               result, serve_traffic)
from benchmark.harness.families import solar_open2 as family

CELL = "serve-solar2-longdoc"
CONFIG = "solar-open2-250b-ep8-1of8"
MANIFEST = mf.Manifest(ROOT)
FILE = MANIFEST.config(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: The cell's shapes at a size the CPU runs: 2 periods, 4 query heads over 2
#: K/V heads of 8, KDA of 4 heads of 8, 16 experts top-2 of which 4 held.
TINY = {
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 8, "num_key_value_heads": 2, "vocab_size": 16384,
    "moe_intermediate_size": 32, "gqa_layers": [0, 4],
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_expert": 8, "num_experts_per_tok": 2,
    "published": {"num_hidden_layers": 8},
}
TINY_MIX = dict(clients=4, requests=12,
                prompt={"median": 30, "sigma": 0.3, "min": 20, "max": 44},
                reply={"median": 10, "sigma": 0.4, "min": 6, "max": 16},
                warm_completed=4, trace_ticks=3, correct_sample=8)
#: At the tiny size (bfloat16 weights, as the cell) the sound engine reads
#: at most 0.024 / 0.00066 on the CPU over three seeds; the weakest fault
#: (every expert reading its neighbour's weights) at least 0.128 / 0.0046,
#: the fp8 control 0.65 / 0.10, the other three faults 2.7 / 0.58 and more.
TINY_LIMITS = {"worst_shortfall": 0.06, "mean_shortfall": 0.002,
               "argmax_miss_share": None}
ENTRIES = ("sizes", "vocab", "make_weights", "model", "compute_dtype",
           "reply_logits", "chosen_tokens", "planted", "faulty_context",
           "model_flops", "attention_layers")


# -- the family and its counts -------------------------------------------------


def test_the_family_resolves_by_its_model_type():
    assert FILE["model_type"] == "solar_open2"
    assert families.of(FILE) is family
    for name in ENTRIES:
        assert callable(getattr(family, name)), name
    assert family.vocab(FILE) == 24576
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-closed64", 1)


def test_the_description_is_the_configuration_s():
    cfg = family.model(FILE)
    assert (cfg.n_layer, cfg.n_attn_layers, cfg.n_kda_layers) == (4, 1, 3)
    assert cfg.period == ("attn", "kda", "kda", "kda")
    assert (cfg.q_heads, cfg.kv_heads, cfg.head_dim) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size) == (64, 128, 4)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert,
            cfg.experts_per_tok) == (320, 40, 0, 8)
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.vocab_size) == (
        4096, 1280, 24576)
    import jax.numpy as jnp

    assert family.compute_dtype(FILE) == jnp.bfloat16
    with pytest.raises(ValueError, match="whole periods"):
        family.sizes(dict(FILE, num_hidden_layers=6))
    with pytest.raises(ValueError, match="whole periods"):
        family.sizes(dict(FILE, gqa_layers=[1]))


def test_model_flops_and_attention_layers_by_hand():
    part = family.layer_weights(FILE)
    assert part == {"attn": 109_051_904, "kda": 137_723_904,
                    "router": 1_310_720, "shared": 15_728_640,
                    "expert": 15_728_640}
    # One period: attention + 3 KDA, and in each of the 4 layers the router,
    # the shared expert and 8 * 40 / 320 = 1 routed expert.
    body = 109_051_904 + 3 * 137_723_904 + 4 * (
        1_310_720 + 15_728_640 + 15_728_640)
    head = 4096 * 24576
    assert family.model_flops(FILE, 1000, 10) == pytest.approx(
        2.0 * body * 1000 + 2.0 * head * 10, rel=1e-12)
    assert family.model_flops(FILE, 0, 1) == 2.0 * 100_663_296
    assert family.attention_layers(FILE) == [(1, 64, 8, 128)]
    # What the chip holds: the issue's 3.31 B parameters.
    held = 109_051_904 + 3 * 137_723_904 + 4 * (
        1_310_720 + 15_728_640 + 40 * 15_728_640) + 2 * head
    assert held == pytest.approx(3.31e9, rel=0.003)


def test_the_file_is_the_catalog_s_row_but_for_what_it_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Solar-Open2-250B")
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == FILE["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(FILE["reduced"]) == sorted(
        ["num_hidden_layers", "gqa_layers", "n_routed_experts",
         "vocab_size"])
    for key, value in row["config"].items():
        if key in FILE["reduced"]:
            assert FILE["published"][key] == value
        else:
            assert FILE[key] == value, key
    assert (FILE["num_hidden_layers"], FILE["gqa_layers"],
            FILE["n_routed_experts"], FILE["vocab_size"]) == (
        4, [0], 40, 24576)
    assert FILE["n_routed_experts_published"] == 320
    assert FILE["first_expert"] == 0
    deployment = FILE["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (64, 8, 8)
    for key in ("router", "attention_gate", "qk_norm", "kda", "state",
                "weights", "memory"):
        assert FILE["assumed"][key]


def test_the_mix_is_the_issue_s_and_its_longest_request_fits():
    mix = MANIFEST.traffic("longdoc-closed64")
    assert (mix["kind"], mix["clients"], mix["requests"]) == (
        "serve-closed", 64, 96)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.35, "min": 2048,
                             "max": 7680}
    assert mix["reply"] == {"median": 256, "sigma": 0.4, "min": 128,
                            "max": 512}
    assert (mix["temperature"], mix["order_constant"]) == (0.0, 29)
    serve = FILE["deployment"]["serve_config"]
    assert (serve["max_slots"], serve["max_seq"], serve["block_size"],
            serve["prefill_chunk"], serve["prefix_cache"]) == (
        64, 8192, 64, 1024, False)
    assert FILE["deployment"]["prefill_chunk_positions"] == 1024
    offered = serve_traffic.offered(mix)
    assert offered["longest"] <= serve["max_seq"]
    limits = MANIFEST.limits(CELL)
    assert set(limits) == {"worst_shortfall", "mean_shortfall",
                           "argmax_miss_share"}


def test_the_cell_is_on_the_lists_the_issue_names():
    listed = {m["name"] for m in MANIFEST.data["per_layer"]
              if CELL in (m.get("workloads") or ())}
    assert listed == {
        "serve_mfu_pct", "tick_wall_ms_p50", "prefill_wall_share_pct",
        "batch_occupancy_pct", "decode_program_ms", "prefill_program_ms",
        "paged_decode_roofline", "paged_prefill_roofline",
        "serve_device_idle_pct", "serve_hbm_peak_gb",
        "moe_held_pairs_per_token", "moe_expert_load_max_x",
        "recurrent_state_gb"}
    rate = next(m for m in MANIFEST.data["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"]


# -- the weights and the faults -----------------------------------------------


@pytest.fixture(scope="module")
def tiny_config():
    config = json.loads(json.dumps(FILE))
    config.update(TINY)
    config["deployment"]["serve_config"].update(
        max_slots=4, max_seq=64, block_size=8, prefill_chunk=16)
    config["deployment"]["prefill_chunk_positions"] = 16
    return config


def test_the_weights_follow_the_seed_and_arrive_in_bfloat16(tiny_config):
    import jax
    import jax.numpy as jnp

    a, b, c = (family.make_weights(s, tiny_config) for s in (5, 5, 6))
    leaves = jax.tree_util.tree_leaves_with_path(a)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])
    f32 = {"norm1", "norm2", "final_norm", "o_norm", "a_log", "dt_bias",
           "router_bias"}
    for path, leaf in leaves:
        name = path[-1].key
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16), \
            name
    assert len(a["periods"]) == 4
    kda = a["periods"][1]["kda"]
    assert kda["wq"].shape == (2, 64, 32) and kda["a_log"].shape == (2, 4)
    assert float(kda["a_log"].min()) >= 0.0               # log U(1, 16)
    assert a["periods"][0]["moe"]["w_gate_up"].shape == (2, 4, 64, 64)
    assert a["periods"][0]["moe"]["router"].shape == (2, 64, 16)
    assert a["head"].shape == (64, 16384)


def test_each_fault_is_what_it_says(tiny_config):
    import jax.numpy as jnp

    params = family.make_weights(3, tiny_config)
    unwritten = family.planted(params, "kda_state_unwritten", tiny_config)
    # Six KDA layers: the middle one is period 1, position 1 of the period.
    assert float(jnp.abs(unwritten["periods"][1]["kda"]["wv"][1]).max()) == 0
    assert float(jnp.abs(unwritten["periods"][1]["kda"]["wv"][0]).max()) > 0
    assert unwritten["periods"][2] is params["periods"][2]
    swapped = family.planted(params, "neighbour_experts", tiny_config)
    for position in range(4):
        was = params["periods"][position]["moe"]
        now = swapped["periods"][position]["moe"]
        assert np.array_equal(now["w_down"][:, 0], was["w_down"][:, 1])
        assert np.array_equal(now["w_gate_up"][:, 3], was["w_gate_up"][:, 0])
        assert now["router"] is was["router"]
    assert family.planted(params, "", tiny_config) is params
    assert family.planted(params, "neighbour_slot", tiny_config) is params
    with pytest.raises(ValueError):
        family.planted(params, "no-such-fault", tiny_config)
    prompt, other = np.arange(37), np.arange(100, 120)
    assert len(family.faulty_context("last_chunk_dropped", prompt, other,
                                     16)) == 32
    moved = family.faulty_context("neighbour_slot", prompt, other, 16)
    assert len(moved) == 37 and np.array_equal(moved[:20], other)
    assert family.faulty_context("", prompt, other, 16) is prompt


# -- a tiny engine, really driven ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, tiny_config):
    root = tmp_path_factory.mktemp("tiny_solar")
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), root / "benchmark" / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "benchmark"
    json.dump(tiny_config, open(bench / "configs" / f"{CONFIG}.json", "w"))
    mix = dict(MANIFEST.traffic("longdoc-closed64"), **TINY_MIX)
    json.dump(mix, open(bench / "traffic" / "longdoc-closed64.json", "w"))
    json.dump({"limits": TINY_LIMITS},
              open(bench / "limits" / f"{CELL}.json", "w"))
    return str(root)


@pytest.fixture(scope="module")
def driven(tiny_root):
    """One closed loop at the tiny size, 60 ticks."""
    import time

    from benchmark.harness import common
    from benchmark.harness.drivers import serve_closed

    man = mf.Manifest(tiny_root)
    cell = man.cell(CELL)
    run = result.Run(cell, man.config(cell["config"]),
                     man.traffic(cell["traffic"]), 7, 0.2, False)
    run.counters["process_start"] = time.time()
    common.configure_jax(run)
    loop = serve_closed.ClosedLoop(run, serve_closed.build_engine(run))
    for _ in range(60):
        loop.tick()
    return run, loop


def test_every_tick_held_the_work_the_clients_foresaw(driven):
    run, loop = driven
    assert loop.model_misses == 0
    assert all(t["in_flight"] == 4 for t in loop.ticks[8:])
    assert len(loop.finished) >= 12
    for flight in loop.finished:
        assert flight.status == "completed"
        assert flight.tokens == flight.result_tokens
    summary = loop.engine.metrics_summary()
    assert summary["prefix_hit_rate"] == 0.0 and summary["moe"]["tokens_fed"]


def served_pairs(driven):
    run, loop = driven
    sample = correct_serve.sample(loop.finished, run.seed, 8)
    return run, [(f.prompt, f.result_tokens) for f in sample]


def judged(run, found):
    fresh = result.Run(run.cell, run.config, run.mix, run.seed, 1.0, False)
    correct_serve.judge(fresh, dict(found), TINY_LIMITS)
    return fresh


def test_the_sound_engine_is_correct(driven):
    run, pairs = served_pairs(driven)
    found = correct_serve.readings(run.seed, run.config, pairs, 16, chunk=16)
    assert found["compared_requests"] == 8 and found["compared_tokens"] > 60
    run = judged(run, found)
    assert run.correct, run.compare
    assert set(run.compare) == {"worst_shortfall", "mean_shortfall"}


@pytest.mark.parametrize("control", [
    {"precision": "fp8"}, {"fault": "last_chunk_dropped"},
    {"fault": "kda_state_unwritten"}, {"fault": "neighbour_experts"},
    {"fault": "neighbour_slot"}], ids=lambda c: next(iter(c.values())))
def test_the_control_and_each_planted_fault_are_not_correct(driven, control):
    run, pairs = served_pairs(driven)
    found = correct_serve.readings(run.seed, run.config, pairs, 16, chunk=16,
                                   **control)
    run = judged(run, found)
    assert not run.correct
    assert all(value > limit for value, limit in run.compare.values()), \
        run.compare


# -- the whole command, past the look for a chip -------------------------------


def test_a_sound_run_of_the_whole_command_is_correct_and_is_read(
        tiny_root, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_run_main", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    code = module.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                        "--seconds", "0.5", "--trace", "0", "--root",
                        tiny_root], skip_device_check=True)
    assert code == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1       # a loaded machine finishes few
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(line["compare"]) == {"worst_shortfall", "mean_shortfall"}
    assert "tick_model_misses 0" in out.err
    # The window's counters, as the three new readers find them in the
    # program's registry once the engine is gone.
    man = mf.Manifest(tiny_root)
    pairs = man.reader("moe_held_pairs_per_token")(None)
    load = man.reader("moe_expert_load_max_x")(None)
    state = man.reader("recurrent_state_gb")(None)
    assert 0.1 < pairs < 1.2            # near 2 * 4 / 16 = 0.5
    assert 1.0 <= load <= 4.0
    # 6 KDA layers x 4 slots x (4 heads x 8 x 8 + 3 x 96) float32.
    assert state == pytest.approx(6 * 4 * (256 + 288) * 4 / 1e9)


def test_the_readers_read_nothing_where_there_is_no_counter(monkeypatch):
    from benchmark.harness import expert_readers
    from trustworthy_dl_tpu.obs import registry

    monkeypatch.setattr(registry, "_DEFAULT_REGISTRY",
                        registry.MetricsRegistry())
    for name in ("moe_held_pairs_per_token", "moe_expert_load_max_x",
                 "recurrent_state_gb"):
        assert MANIFEST.reader(name)(None) is None
    gauge = registry.get_registry().gauge(
        expert_readers.STATE_BYTES, "as a GPT-2 engine leaves it")
    gauge.set(0.0)
    assert MANIFEST.reader("recurrent_state_gb")(None) is None
