"""The Falcon-H1 family and its cell: the family resolves by its
``model_type`` and gives every entry a serving cell calls; its work counts and
the Mamba-2 decode step's by hand at the configuration's sizes; the
configuration's file against the catalog's row; the mix fits; and, at a tiny
size on the CPU through the real ``ServingEngine`` and the whole
``benchmark/run.py`` command, the sound engine is ``correct`` while the fp8
control and each of the six planted faults are not, and the new reader
reads the ``ssm_step`` kernel alone."""

import json
import os
import shutil

import numpy as np
import pytest

from bench_paths import BENCH, ROOT

from benchmark.harness import (correct_serve, families, manifest as mf,
                               result, serve_traffic, ssm_readers, ssm_work)
from benchmark.harness.families import falcon_h1 as family

CELL = "serve-falcon-h1-longreply"
CONFIG = "falcon-h1-34b-pp18-1of18"
MIX = "longreply-closed64"
MANIFEST = mf.Manifest(ROOT)
FILE = MANIFEST.config(CONFIG)
#: The published configuration's catalog row, kept beside this test.
CATALOG_ROW = os.path.join(os.path.dirname(__file__),
                           "falcon_h1_catalog_row.json")

#: The cell's shapes at a size the CPU runs: 2 hybrid layers of 256, 10
#: query heads over 2 K/V heads of 16 (the cell's 5 a group), a Mamba-2
#: mixer of 8 heads of 16 with a state of 32 in 2 groups, blocks of 8.  The
#: embedding's and the mixers' output multipliers are 1 here: at the file's
#: (5.66 on the embedding, 0.088 and 0.0375 on the mixers) two layers of
#: 256 add a thousandth to what the embedding carries to the head, and no
#: fault in them flips a token; the key's is 1 (at 0.011 the scores over
#: 20 to 60 positions are flat and the rotary path moves nothing) and dt's
#: 30 (at the file's 0.35 a state moves too little in a tiny request for
#: its loss to be seen).  The mixer's ``W_in`` is drawn at 0.02 here: the
#: file's 0.23 answers 5,120 inputs, and at 256 with dt's 30 it would put
#: dt's input near 7.
TINY = {
    "weight_std": {"wq": 0.23, "wk": 0.23},
    "embedding_multiplier": 1.0, "ssm_out_multiplier": 1.0,
    "attention_out_multiplier": 1.0, "key_multiplier": 1.0,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        30.0],
    "hidden_size": 256, "num_attention_heads": 10, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_ssm": 128, "mamba_d_state": 32, "mamba_n_groups": 2,
    "mamba_chunk_size": 8, "num_hidden_layers": 2, "vocab_size": 512,
    "intermediate_size": 384, "rope_theta": 10000.0,
    "published": {"num_hidden_layers": 2},
}
TINY_MIX = dict(clients=4, requests=12,
                prompt={"median": 30, "sigma": 0.3, "min": 20, "max": 44},
                reply={"median": 10, "sigma": 0.4, "min": 6, "max": 16},
                warm_completed=4, trace_ticks=3, correct_sample=8)
#: At the tiny size (bfloat16 weights, as the cell) the sound engine reads
#: at most 0.0144 / 0.00038 on the CPU over three seeds (7 to 9); the
#: weakest faults, the middle layer's state row never written (one layer in
#: two) and every layer's convolution tail never written, at least 0.052 /
#: 0.0012 and 0.073 / 0.0027, the fp8 control 0.46 / 0.049 and the other
#: three faults 1.4 / 0.33 and more.  The share of flipped tokens separates
#: nothing here (a sound seed's 0.034 against the state fault's 0.049).
TINY_LIMITS = {"worst_shortfall": 0.03, "mean_shortfall": 0.001,
               "argmax_miss_share": None}
ENTRIES = ("sizes", "vocab", "make_weights", "model", "compute_dtype",
           "reply_logits", "chosen_tokens", "planted", "faulty_context",
           "model_flops", "attention_layers", "ssm_layers")
CONTROLS = [{"precision": "fp8"}, {"fault": "last_chunk_dropped"},
            {"fault": "ssm_state_unwritten"}, {"fault": "ssm_state_reset"},
            {"fault": "conv_tail_unwritten"},
            {"fault": "rope_position_shifted"}, {"fault": "neighbour_slot"}]


@pytest.fixture(scope="module", autouse=True)
def _a_registry_of_this_file_s_own():
    """The engines this file builds fill the program's process-wide obs
    registry, which the readers read; a file that runs after this one in the
    same process expects to find nothing there."""
    from trustworthy_dl_tpu.obs import registry

    was = registry._DEFAULT_REGISTRY
    registry._DEFAULT_REGISTRY = registry.MetricsRegistry()
    yield
    registry._DEFAULT_REGISTRY = was


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """The persistent compilation cache off around the file: the harness
    writes every program to it however small, and a copy of this directory
    run in a second process at the same time (``test_benchmark_families``'
    grown tree) would read the same entries while they are written."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# -- the family and its counts -------------------------------------------------


def test_the_family_resolves_by_its_model_type():
    assert FILE["model_type"] == "falcon_h1"
    assert families.of(FILE) is family
    for name in ENTRIES:
        assert callable(getattr(family, name)), name
    assert family.vocab(FILE) == 261120
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200


def test_the_description_is_the_configuration_s():
    import jax.numpy as jnp

    cfg = family.model(FILE)
    assert cfg.period == ("hybrid",) and cfg.n_periods == 4 and not cfg.lead
    assert (cfg.n_layer, cfg.n_expert_layers, cfg.n_kda_layers,
            cfg.n_hybrid_layers, cfg.n_attn_layers) == (4, 0, 0, 4, 4)
    assert (cfg.q_heads, cfg.kv_heads, cfg.head_dim) == (20, 4, 128)
    assert (cfg.rope_theta, cfg.attn_gate) == (1e11, False)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.mamba_groups, cfg.mamba_block, cfg.conv_size) == (
        32, 128, 256, 2, 128, 4)
    assert (cfg.mamba_width, cfg.mamba_conv_channels) == (4096, 5120)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (
        5120, 21504, 261120)
    assert cfg.n_experts == 0
    assert cfg.key_multiplier == FILE["key_multiplier"]
    assert cfg.ssm_multipliers == tuple(FILE["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(FILE["mlp_multipliers"])
    assert (cfg.embedding_multiplier, cfg.lm_head_multiplier,
            cfg.attn_in_multiplier, cfg.attn_out_multiplier,
            cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) == (
        FILE["embedding_multiplier"], FILE["lm_head_multiplier"], 1.0,
        0.0375, 0.25, FILE["ssm_out_multiplier"])
    assert family.compute_dtype(FILE) == jnp.bfloat16
    hash(cfg)                                   # a static jit argument
    for changed in ({"mamba_d_ssm": 2048}, {"attn_layer_indices": [0]},
                    {"mamba_norm_before_gate": True},
                    {"tie_word_embeddings": True},
                    {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError):
            family.sizes(dict(FILE, **changed))


def test_model_flops_by_hand():
    part = family.layer_weights(FILE)
    # Attention: q 5,120 x 2,560, k and v 5,120 x 512, o 2,560 x 5,120.
    attention = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    # The mixer: W_in 5,120 x (4,096 + 5,120 + 32), W_out 4,096 x 5,120.
    mamba = 5120 * 9248 + 4096 * 5120
    mlp = 3 * 5120 * 21504
    assert part == {"attention": attention, "mamba": mamba, "mlp": mlp}
    # 430.12 M a layer, 77 % of it the MLP (the taps, the
    # conv bias, D, A_log, dt_bias and norms are the other 0.04 M).
    assert attention + mamba + mlp == 430_080_000
    assert mlp / (attention + mamba + mlp) == pytest.approx(0.768, abs=0.001)
    head = 5120 * 261120
    assert family.model_flops(FILE, 1000, 10) == pytest.approx(
        2.0 * 4 * 430_080_000 * 1000 + 2.0 * head * 10, rel=1e-12)
    assert family.model_flops(FILE, 0, 1) == 2.0 * head
    assert family.attention_layers(FILE) == [(4, 20, 4, 128)]
    assert family.ssm_layers(FILE) == [(4, 32, 128, 256, 2)]


def test_the_ssm_work_counts_by_hand():
    # One decoding row of one layer: the state [32, 128, 256] f32 read and
    # written, x and y [32, 128], dt [32], B and C [2, 256], all float32.
    work = ssm_work.ssm_decode(1, 32, 128, 256, 2)
    state = 32 * 128 * 256 * 4
    assert work.bytes == 2 * state + 4 * (2 * 4096 + 32 + 2 * 512)
    assert work.flops == 5 * 32 * 128 * 256
    # 60 rows over the cell's 4 layers: some 2 GB a decode call.
    assert 4 * ssm_work.ssm_decode(60, 32, 128, 256, 2).bytes == \
        pytest.approx(2.03e9, rel=0.01)
    assert ssm_work.ssm_decode(0, 32, 128, 256, 2) == (0, 0)


def test_the_file_is_the_catalog_s_row_but_for_what_it_lists():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    assert row["name"] == "Falcon-H1-34B-Instruct"
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == FILE["source"] == row["source_url"]
    assert entry["reduced"] == FILE["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in FILE["reduced"]:
            assert FILE["published"][key] == value == 72
        else:
            assert FILE[key] == value, key
    assert FILE["num_hidden_layers"] == 4
    deployment = FILE["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"]) == (18, 18, 1)
    for key in ("ssm_in_multiplier", "ssm_multipliers", "gated_norm",
                "state", "mamba", "attention", "layer", "weights",
                "compute_dtype", "decoding", "memory"):
        assert FILE["assumed"][key]
    assert FILE["weight_std"] == {"wq": 0.23, "wk": 0.23, "w_in": 0.23} \
        == family.weight_stds(FILE)
    assert "weight_std" in FILE["assumed"]["weights"]


def test_the_mix_is_the_cell_s_and_its_longest_request_fits():
    mix = MANIFEST.traffic(MIX)
    assert (mix["kind"], mix["clients"], mix["requests"]) == (
        "serve-closed", 64, 96)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.35, "min": 2048,
                             "max": 7168}
    assert mix["reply"] == {"median": 512, "sigma": 0.4, "min": 256,
                            "max": 1024}
    assert (mix["temperature"], mix["order_constant"]) == (0.0, 29)
    assert (mix["ramp_ticks"], mix["warm_completed"], mix["trace_ticks"],
            mix["correct_sample"]) == (2, 8, 48, 6)
    serve = FILE["deployment"]["serve_config"]
    assert (serve["max_slots"], serve["max_seq"], serve["block_size"],
            serve["prefill_chunk"], serve["prefix_cache"],
            serve["kv_dtype"], serve["spec_k"]) == (
        64, 8192, 64, 1024, False, "model", 0)
    assert FILE["deployment"]["prefill_chunk_positions"] == 1024
    assert FILE["deployment"]["enable_monitor"] is True
    offered = serve_traffic.offered(mix)
    assert offered["longest"] <= serve["max_seq"]
    assert 4200 < offered["prompt_tokens"] / 96 < 4400
    assert 520 < offered["reply_tokens"] / 96 < 580
    limits = MANIFEST.limits(CELL)
    assert set(limits) == {"worst_shortfall", "mean_shortfall",
                           "argmax_miss_share"}


def test_the_cell_is_on_the_lists_it_belongs_on():
    """Exactly the lists the cell belongs on, and the one metric it brings."""
    listed = {m["name"] for m in MANIFEST.data["per_layer"]
              if CELL in (m.get("workloads") or ())}
    assert listed == {
        "serve_mfu_pct", "tick_wall_ms_p50", "prefill_wall_share_pct",
        "batch_occupancy_pct", "decode_program_ms", "prefill_program_ms",
        "paged_decode_roofline", "paged_prefill_roofline",
        "serve_device_idle_pct", "serve_hbm_peak_gb", "recurrent_state_gb",
        "ssm_decode_roofline"}
    entry = next(m for m in MANIFEST.data["per_layer"]
                 if m["name"] == "ssm_decode_roofline")
    assert entry == {"name": "ssm_decode_roofline", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "serve_tokens_per_s",
                     "workloads": [CELL]}
    assert callable(MANIFEST.reader("ssm_decode_roofline"))
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
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])
    f32 = {"norm1", "norm2", "final_norm", "gate_norm", "a_log", "dt_bias",
           "d_skip", "conv_bias"}
    for path, leaf in jax.tree_util.tree_leaves_with_path(a):
        name = path[-1].key
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16), \
            name
    (layer,) = a["periods"]
    assert set(layer) == {"norm1", "norm2", "attn", "mamba", "mlp"}
    assert layer["attn"]["wq"].shape == (2, 256, 160)
    assert layer["attn"]["wk"].shape == layer["attn"]["wv"].shape == (
        2, 256, 32)
    assert "wg" not in layer["attn"]                  # no output gate
    mamba = layer["mamba"]
    assert mamba["w_in"].shape == (2, 256, 128 + 256 + 8)
    assert mamba["conv"].shape == (2, 4, 256)
    assert mamba["conv_bias"].shape == (2, 256)
    assert mamba["a_log"].shape == mamba["d_skip"].shape == (2, 8)
    assert mamba["gate_norm"].shape == (2, 128)
    assert mamba["w_out"].shape == (2, 128, 256)
    assert float(mamba["a_log"].min()) >= 0.0               # log U(1, 16)
    assert layer["mlp"]["gate_up"].shape == (2, 256, 768)
    assert a["head"].shape == (256, 512)
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))
    assert std(layer["attn"]["wq"]) == pytest.approx(0.23, rel=0.05)
    assert std(layer["attn"]["wk"]) == pytest.approx(0.23, rel=0.1)
    assert std(layer["attn"]["wv"]) == pytest.approx(0.02, rel=0.1)
    # Residual projections over sqrt(2 * published depth).
    assert std(layer["mlp"]["down"]) / std(layer["mlp"]["gate_up"]) == \
        pytest.approx(0.5, rel=0.05)
    assert std(mamba["w_out"]) == pytest.approx(0.01, rel=0.05)
    assert std(mamba["w_in"]) == pytest.approx(0.02, rel=0.05)
    # The file's own draw: the mixer's input projection at 0.23.
    shapes = family._layer_shapes(family.sizes(FILE))
    assert shapes["mamba"]["w_in"] == (4, 5120, 9248)
    wide = family.make_weights(5, dict(tiny_config,
                                       weight_std=FILE["weight_std"]))
    assert std(wide["periods"][0]["mamba"]["w_in"]) == pytest.approx(
        0.23, rel=0.05)
    assert std(wide["periods"][0]["mamba"]["w_out"]) == pytest.approx(
        0.01, rel=0.05)


def test_a_large_matrix_is_drawn_in_blocks_of_rows():
    """No float32 draw of a whole large matrix: a draw of a quarter of the
    bytes limit's rows, the same numbers whatever the limit blocks it into
    is NOT asked (a seed fixes the blocks)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    whole = family._normal(key, (64, 32), 1.0)
    was = family.DRAW_BYTES
    try:
        family.DRAW_BYTES = 16 * 32 * 4
        blocked = family._normal(key, (64, 32), 1.0)
        jaxpr = str(jax.make_jaxpr(
            lambda k: family._normal(k, (64, 32), 1.0))(key))
    finally:
        family.DRAW_BYTES = was
    assert blocked.shape == (64, 32) and blocked.dtype == jnp.bfloat16
    assert not np.array_equal(whole, blocked)
    assert "f32[16,32]" in jaxpr and "f32[64,32]" not in jaxpr
    assert float(jnp.std(blocked.astype(jnp.float32))) == pytest.approx(
        1.0, rel=0.2)


def test_each_fault_is_what_it_says(tiny_config):
    import jax.numpy as jnp

    params = family.make_weights(3, tiny_config)
    (layer,) = params["periods"]
    frozen = family.planted(params, "ssm_state_unwritten", tiny_config)
    hook = frozen["periods"][0]["mamba"]["state_unwritten"]
    assert hook.tolist() == [False, True]            # the middle of 2
    assert frozen["periods"][0]["attn"] is layer["attn"]
    reset = family.planted(params, "ssm_state_reset", tiny_config)
    assert reset["periods"][0]["mamba"]["state_reset"].tolist() == [
        False, True]
    assert "state_unwritten" not in reset["periods"][0]["mamba"]
    tail = family.planted(params, "conv_tail_unwritten", tiny_config)
    assert tail["periods"][0]["mamba"]["conv_unwritten"].tolist() == [
        True, True]
    shifted = family.planted(params, "rope_position_shifted", tiny_config)
    assert shifted["periods"][0]["attn"]["key_shift"].tolist() == [1, 1]
    assert shifted["periods"][0]["attn"]["wk"] is layer["attn"]["wk"]
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
    # Each hook moves the reference's logits (by a thousandth of their
    # spread and less at this size, where the embedding's path to the head
    # is most of them).
    f32 = lambda t: __import__("jax").tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, t)
    rng = np.random.default_rng(0)
    prompt, reply = rng.integers(0, 512, 40), rng.integers(0, 512, 9)
    sound = family.reply_logits(f32(params), prompt, reply, tiny_config, 16)
    for broken in (frozen, reset, tail, shifted):
        moved = family.reply_logits(f32(broken), prompt, reply, tiny_config,
                                    16)
        assert float(jnp.max(jnp.abs(moved - sound))) > 1e-5 * float(
            jnp.std(sound))


# -- a tiny engine, really driven ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, tiny_config):
    root = tmp_path_factory.mktemp("tiny_falcon")
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), root / "benchmark" / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "benchmark"
    json.dump(tiny_config, open(bench / "configs" / f"{CONFIG}.json", "w"))
    mix = dict(MANIFEST.traffic(MIX), **TINY_MIX)
    json.dump(mix, open(bench / "traffic" / f"{MIX}.json", "w"))
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
    assert summary["prefix_hit_rate"] == 0.0 and "moe" not in summary
    # 2 hybrid layers x (K and V) x (4 slots x 8 blocks + trash) x 8 x 32.
    assert summary["kv_pool_bytes"] == 2 * 2 * 33 * 8 * 32 * 2
    # 2 layers x 4 slots x (8 x 16 x 32 + 3 x 256) float32, all Mamba-2.
    state = 2 * 4 * (8 * 16 * 32 + 3 * 256) * 4
    assert summary["state_pool_bytes"] == state
    assert summary["state_bytes_by_kind"] == {"hybrid": state}


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


@pytest.mark.parametrize("control", CONTROLS,
                         ids=lambda c: next(iter(c.values())))
def test_the_control_and_each_planted_fault_are_not_correct(driven, control):
    run, pairs = served_pairs(driven)
    found = correct_serve.readings(run.seed, run.config, pairs, 16, chunk=16,
                                   **control)
    run = judged(run, found)
    assert not run.correct, run.compare


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
    man = mf.Manifest(tiny_root)
    assert man.reader("recurrent_state_gb")(None) == pytest.approx(
        2 * 4 * (8 * 16 * 32 + 3 * 256) * 4 / 1e9)


def test_the_roofline_reads_the_ssm_kernel_alone():
    """A traced run as the reader sees it: the kernel's events by the name
    the program gives it, the work from the ticks' decoding rows; a family
    with no Mamba-2 mixer reads None, and a share over 105 % raises."""
    from benchmark.harness import peaks
    from benchmark.harness.families import solar_open2

    class Trace:
        events = {0: [("ssm_step.3", 0.0, 1e-3), ("ssm_step.3", 1.0, 1e-3),
                      ("_paged_attn_call.1", 2.0, 1.0),
                      ("fusion.7", 3.0, 1.0)]}

    class Run:
        trace = Trace()
        peak = peaks.peak("TPU v5 lite")
        config = FILE
        counters = {"trace_ticks": [
            {"tokens": 3, "expected": 3, "decode": [5000, 6000, 7000],
             "prefill": [(4096, 1024)]}]}

    run = Run()
    run.family = family
    work = ssm_work.ssm_decode(3, 32, 128, 256, 2)
    want = 100.0 * 4 * work.bytes / run.peak.hbm_bytes_per_s / 2e-3
    assert MANIFEST.reader("ssm_decode_roofline")(run) == pytest.approx(want)
    assert 0 < want < 100
    run.counters = {"trace_ticks": [dict(run.counters["trace_ticks"][0],
                                         decode=[1] * 200)]}
    with pytest.raises(ValueError, match="105"):
        ssm_readers.ssm_decode_roofline(run)
    run.family = solar_open2
    assert MANIFEST.reader("ssm_decode_roofline")(run) is None
    run.family = family
    run.counters = {"trace_ticks": [dict(run.counters["trace_ticks"][0],
                                         tokens=2)]}
    assert MANIFEST.reader("ssm_decode_roofline")(run) is None

    class Untraced:
        trace = None
        peak = None
        counters = {}

    Untraced.family = family
    assert MANIFEST.reader("ssm_decode_roofline")(Untraced()) is None
