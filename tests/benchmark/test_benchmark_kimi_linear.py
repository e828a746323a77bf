"""The Kimi Linear family and its cell: the family resolves by its
``model_type`` and gives every entry a serving cell calls; its work counts
and the latent kernels' by hand at the configuration's sizes; the
configuration's file against the catalog's row; the mix fits; and, at a tiny
size on the CPU through the real ``ServingEngine`` and the whole
``benchmark/run.py`` command, the sound engine is ``correct`` while the fp8
control and each of the five planted faults are not, and the new readers read
their counters."""

import json
import os
import shutil

import numpy as np
import pytest

from bench_paths import BENCH, ROOT

from benchmark.harness import (correct_serve, families, latent_readers,
                               latent_work, manifest as mf, result,
                               serve_traffic)
from benchmark.harness.families import kimi_linear as family

CELL = "serve-kimi-linear-longctx"
CONFIG = "kimi-linear-48b-ep2-1of2"
MIX = "longctx-closed64"
MANIFEST = mf.Manifest(ROOT)
FILE = MANIFEST.config(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]

#: The cell's shapes at a size the CPU runs: a leading KDA + dense layer and
#: 2 periods of (KDA, KDA, MLA, KDA); 4 heads of 8 + 4 over a latent of
#: 16 + 4; KDA of 4 heads of 8; 16 experts top-2 of which 8 are held.
TINY = {
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 9,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7, 9],
                           "full_attn_layers": [4, 8], "head_dim": 8,
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "kv_lora_rank": 16, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_token": 2, "vocab_size": 16384,
    "published": {"num_hidden_layers": 9},
    # Queries sharp enough at these widths that the latent layer's scores
    # decide something (at 0.02 a fault in them reads 0.0).
    "latent_query_std": 3.0,
}
TINY_MIX = dict(clients=4, requests=12,
                prompt={"median": 30, "sigma": 0.3, "min": 20, "max": 44},
                reply={"median": 10, "sigma": 0.4, "min": 6, "max": 16},
                warm_completed=4, trace_ticks=3, correct_sample=8)
#: At the tiny size (bfloat16 weights, as the cell) the sound engine reads
#: at most 0.0111 / 0.00013 on the CPU over three seeds; the weakest faults
#: (every expert reading its neighbour's weights, the latent row's k_r
#: columns never written) at least 0.130 / 0.0102, the fp8 control 0.53 /
#: 0.066, the other three faults 2.0 / 0.59 and more.
TINY_LIMITS = {"worst_shortfall": 0.04, "mean_shortfall": 0.002,
               "argmax_miss_share": None}
ENTRIES = ("sizes", "vocab", "make_weights", "model", "compute_dtype",
           "reply_logits", "chosen_tokens", "planted", "faulty_context",
           "model_flops", "attention_layers", "latent_layers")
CONTROLS = [{"precision": "fp8"}, {"fault": "last_chunk_dropped"},
            {"fault": "kda_state_unwritten"}, {"fault": "neighbour_experts"},
            {"fault": "neighbour_slot"}, {"fault": "latent_rope_unwritten"}]


@pytest.fixture(scope="module", autouse=True)
def _a_registry_of_this_file_s_own():
    """The engines this file builds fill the program's process-wide obs
    registry, which the readers read; a file that runs after this one in the
    same process (``test_benchmark_readers.py``) expects to find nothing
    there."""
    from trustworthy_dl_tpu.obs import registry

    was = registry._DEFAULT_REGISTRY
    registry._DEFAULT_REGISTRY = registry.MetricsRegistry()
    yield
    registry._DEFAULT_REGISTRY = was


# -- the family and its counts -------------------------------------------------


def test_the_family_resolves_by_its_model_type():
    assert FILE["model_type"] == "kimi_linear"
    assert families.of(FILE) is family
    for name in ENTRIES:
        assert callable(getattr(family, name)), name
    assert family.vocab(FILE) == 81920
    cell = MANIFEST.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200


def test_the_description_is_the_configuration_s():
    cfg = family.model(FILE)
    assert cfg.lead == ("kda",) and cfg.intermediate_size == 9216
    assert cfg.period == ("kda", "kda", "mla", "kda") and cfg.n_periods == 1
    assert (cfg.n_layer, cfg.n_expert_layers, cfg.n_kda_layers,
            cfg.n_mla_layers, cfg.n_attn_layers) == (5, 4, 4, 1, 0)
    assert (cfg.q_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank) == (32, 128, 64, 128, 512)
    assert (cfg.latent_width, cfg.latent_lanes) == (576, 640)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size, cfg.kda_rank,
            cfg.kda_beta_scale) == (32, 128, 4, 128, 1.0)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert,
            cfg.experts_per_tok, cfg.n_shared_experts) == (256, 128, 0, 8, 1)
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.vocab_size) == (
        2304, 1024, 81920)
    assert (cfg.norm_topk_prob, cfg.routed_scaling_factor) == (True, 2.446)
    import jax.numpy as jnp

    assert family.compute_dtype(FILE) == jnp.bfloat16
    with pytest.raises(ValueError, match="do not name each"):
        family.sizes(dict(FILE, num_hidden_layers=6))
    with pytest.raises(ValueError, match="no rotary term"):
        family.sizes(dict(FILE, mla_use_nope=False))
    with pytest.raises(ValueError, match="query compression"):
        family.sizes(dict(FILE, q_lora_rank=1536))


def test_model_flops_by_hand():
    part = family.layer_weights(FILE)
    # KDA: q, k, v, o of 2,304 x 4,096; two rank-128 pairs; beta; 4 taps.
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 \
        + 4 * 12288
    # MLA: wq 2,304 x 6,144; w_kv_a 2,304 x 576; w_kv_b 512 x 8,192; wo.
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert part == {"mla": mla, "kda": kda, "dense": 3 * 2304 * 9216,
                    "router": 2304 * 256, "shared": 3 * 2304 * 1024,
                    "expert": 3 * 2304 * 1024}
    assert kda == pytest.approx(39.5e6, rel=0.005)
    assert mla == pytest.approx(29.1e6, rel=0.005)
    assert part["dense"] == pytest.approx(63.7e6, rel=0.005)
    # 4 KDA + 1 MLA mixers, the dense MLP, and in each of the 4 expert
    # layers the router, the shared expert and 8 * 128 / 256 = 4 routed.
    body = 4 * kda + mla + 63_700_992 + 4 * (
        589_824 + 7_077_888 + 4 * 7_077_888)
    head = 2304 * 81920
    assert head == 188_743_680
    assert family.model_flops(FILE, 1000, 10) == pytest.approx(
        2.0 * body * 1000 + 2.0 * head * 10, rel=1e-12)
    assert family.model_flops(FILE, 0, 1) == 2.0 * head
    assert family.attention_layers(FILE) == []
    assert family.latent_layers(FILE) == [(1, 32, 128, 64, 128, 512)]
    # What the chip holds: the issue's 4.28 B parameters.
    held = 4 * kda + mla + 63_700_992 + 4 * (
        589_824 + 7_077_888 + 128 * 7_077_888) + 2 * head
    assert held == pytest.approx(4.28e9, rel=0.003)


def test_the_latent_work_counts_by_hand():
    # Decode, one row of 1,000 positions over blocks of 256: 32 heads score
    # 576 values and sum 512 of each of 1,000 rows; 4 blocks of 576-wide
    # rows once (NO V term), the absorbed queries in and the sums out.
    work = latent_work.latent_decode([1000], 32, 576, 512, 256)
    assert work.flops == 2 * (576 + 512) * 32 * 1000
    assert work.bytes == 4 * 256 * 576 * 2 + 32 * (576 + 512) * 2
    # A chunk of 1,024 rows from position 2,048: the least either form
    # needs, 2 x (192 + 128) x 32 a causal pair; 12 blocks once, Q and O.
    pairs = 1024 * 2048 + 1024 * 1025 / 2
    work = latent_work.latent_prefill([(2048, 1024)], 32, 192, 128, 576, 256)
    assert work.flops == 2 * 320 * 32 * pairs
    assert work.bytes == 12 * 256 * 576 * 2 + 1024 * 32 * 320 * 2
    # An absorbed kernel's own products over that count: 1,088 / 320.
    absorbed = 2 * (576 + 512) * 32 * pairs
    assert work.flops / absorbed == pytest.approx(0.294, abs=0.001)
    two = latent_work.latent_decode([1000, 300], 32, 576, 512, 256)
    assert two.flops == 2 * 1088 * 32 * 1300
    assert latent_work.latent_prefill([], 32, 192, 128, 576, 256) == (0, 0)


def test_the_file_is_the_catalog_s_row_but_for_what_it_lists():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    entry = next(c for c in MANIFEST.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == FILE["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(FILE["reduced"]) == sorted(
        REDUCED)
    for key, value in row["config"].items():
        if key in FILE["reduced"]:
            assert FILE["published"][key] == value
        else:
            assert FILE[key] == value, key
    assert (FILE["num_hidden_layers"], FILE["num_experts"],
            FILE["vocab_size"]) == (5, 128, 81920)
    linear = FILE["linear_attn_config"]
    assert (linear["kda_layers"], linear["full_attn_layers"]) == (
        [1, 2, 3, 5], [4])
    # Nothing of the nested group changes but the two lists of layers.
    published = row["config"]["linear_attn_config"]
    assert {k: v for k, v in linear.items() if k not in (
        "kda_layers", "full_attn_layers")} == {
        k: v for k, v in published.items() if k not in (
            "kda_layers", "full_attn_layers")}
    deployment = FILE["deployment"]
    assert (deployment["chips"], deployment["pipeline_stages"],
            deployment["chips_sharing_a_layer"], deployment["first_expert"],
            deployment["num_experts_published"]) == (12, 6, 2, 0, 256)
    for key in ("router", "mla", "kda", "dense_layer", "state", "weights",
                "compute_dtype", "memory"):
        assert FILE["assumed"][key]
    # The one leaf not drawn at 0.02, stated in the file and under assumed.
    assert FILE["latent_query_std"] == 0.1 == family.latent_query_std(FILE)
    assert "latent_query_std" in FILE["assumed"]["weights"]


def test_the_mix_is_the_issue_s_and_its_longest_request_fits():
    mix = MANIFEST.traffic(MIX)
    assert (mix["kind"], mix["clients"], mix["requests"]) == (
        "serve-closed", 64, 96)
    assert mix["prompt"] == {"median": 8192, "sigma": 0.5, "min": 3072,
                             "max": 30720}
    assert mix["reply"] == {"median": 384, "sigma": 0.4, "min": 192,
                            "max": 768}
    assert (mix["temperature"], mix["order_constant"]) == (0.0, 29)
    serve = FILE["deployment"]["serve_config"]
    assert (serve["max_slots"], serve["max_seq"], serve["block_size"],
            serve["prefill_chunk"], serve["prefix_cache"],
            serve["kv_dtype"]) == (64, 32768, 256, 1024, False, "model")
    assert FILE["deployment"]["prefill_chunk_positions"] == 1024
    assert FILE["deployment"]["enable_monitor"] is True
    offered = serve_traffic.offered(mix)
    assert offered["longest"] <= serve["max_seq"]
    assert mix["prompt"]["max"] + mix["reply"]["max"] <= serve["max_seq"]
    assert 9000 < offered["prompt_tokens"] / 96 < 9600
    limits = MANIFEST.limits(CELL)
    assert set(limits) == {"worst_shortfall", "mean_shortfall",
                           "argmax_miss_share"}


def test_the_cell_is_on_the_lists_the_issue_names():
    """By MEMBERSHIP on the lists other cells share; exact for the three
    metrics this cell brings."""
    listed = {m["name"] for m in MANIFEST.data["per_layer"]
              if CELL in (m.get("workloads") or ())}
    assert listed >= {
        "serve_mfu_pct", "tick_wall_ms_p50", "prefill_wall_share_pct",
        "batch_occupancy_pct", "decode_program_ms", "prefill_program_ms",
        "serve_device_idle_pct", "serve_hbm_peak_gb",
        "moe_held_pairs_per_token", "moe_expert_load_max_x",
        "recurrent_state_gb"}
    assert not listed & {"paged_decode_roofline", "paged_prefill_roofline",
                         "paged_grid_steps_x"}
    for name, layer, source, unit in (
            ("latent_decode_roofline", "kernels", "device_trace", "%"),
            ("latent_prefill_roofline", "kernels", "device_trace", "%"),
            ("latent_pool_gb", "serving scheduler", "program_counter",
             "GB")):
        entry = next(m for m in MANIFEST.data["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["source"], entry["unit"],
                entry["moves"]) == (layer, source, unit,
                                    "serve_tokens_per_s")
        assert name in listed and callable(MANIFEST.reader(name))
    rate = next(m for m in MANIFEST.data["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"]


# -- the weights and the faults -----------------------------------------------


@pytest.fixture(scope="module")
def tiny_config():
    config = json.loads(json.dumps(FILE))
    config.update(TINY)
    config["deployment"].update(first_expert=8, num_experts_published=16)
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
           "router_bias", "kv_norm"}
    for path, leaf in leaves:
        name = path[-1].key
        assert leaf.dtype == (jnp.float32 if name in f32 else jnp.bfloat16), \
            name
    assert len(a["lead"]) == 1 and len(a["periods"]) == 4
    lead = a["lead"][0]
    assert set(lead) == {"norm1", "norm2", "kda", "mlp"}
    assert lead["kda"]["wq"].shape == (64, 32)          # no leading axis
    assert lead["mlp"]["gate_up"].shape == (64, 192)
    assert lead["mlp"]["down"].shape == (96, 64)
    mla = a["periods"][2]["mla"]
    assert mla["wq"].shape == (2, 64, 4 * 12)
    assert mla["w_kv_a"].shape == (2, 64, 20)
    assert mla["w_kv_b"].shape == (2, 16, 4 * 16)
    assert mla["wo"].shape == (2, 32, 64) and mla["kv_norm"].shape == (2, 16)
    # latent_query_std: the latent W_q alone is drawn at it (3.0 here).
    assert float(jnp.std(mla["wq"].astype(jnp.float32))) == pytest.approx(
        3.0, rel=0.05)
    assert float(jnp.std(mla["w_kv_a"].astype(jnp.float32))) == \
        pytest.approx(0.02, rel=0.05)
    kda = a["periods"][1]["kda"]
    assert kda["wq"].shape == (2, 64, 32) and kda["a_log"].shape == (2, 4)
    assert float(kda["a_log"].min()) >= 0.0               # log U(1, 16)
    assert a["periods"][0]["moe"]["w_gate_up"].shape == (2, 8, 64, 64)
    assert a["periods"][0]["moe"]["router"].shape == (2, 64, 16)
    assert a["head"].shape == (64, 16384)
    # Residual projections over sqrt(2 * published depth): the dense MLP's
    # too.
    ratio = float(jnp.std(lead["mlp"]["down"].astype(jnp.float32))
                  / jnp.std(lead["mlp"]["gate_up"].astype(jnp.float32)))
    assert ratio == pytest.approx(1 / np.sqrt(18), rel=0.05)


def test_each_fault_is_what_it_says(tiny_config):
    import jax.numpy as jnp

    params = family.make_weights(3, tiny_config)
    # Seven KDA layers (lead, then 3 a period): the middle one is period 0,
    # the last position of the period.
    assert [where for where, _ in family.kda_layers(tiny_config)] == [
        ("lead", 0), ("periods", 0), ("periods", 1), ("periods", 3),
        ("periods", 0), ("periods", 1), ("periods", 3)]
    unwritten = family.planted(params, "kda_state_unwritten", tiny_config)
    assert float(jnp.abs(unwritten["periods"][3]["kda"]["wv"][0]).max()) == 0
    assert float(jnp.abs(unwritten["periods"][3]["kda"]["wv"][1]).max()) > 0
    assert unwritten["periods"][1] is params["periods"][1]
    assert unwritten["lead"] is params["lead"]
    swapped = family.planted(params, "neighbour_experts", tiny_config)
    for position in range(4):
        was = params["periods"][position]["moe"]
        now = swapped["periods"][position]["moe"]
        assert now["held_shift"].tolist() == [1, 1]
        assert now["w_down"] is was["w_down"] and now["router"] is was[
            "router"]
    # ... which is every held expert computing with its neighbour's
    # matrices: the same layer as one whose experts are rolled.
    from benchmark.harness.families import reference_kimi_linear as ref

    layer = {k: v[0] for k, v in params["periods"][0]["moe"].items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)),
                    jnp.float32)
    shape = family.sizes(tiny_config)
    rolled = dict(layer, **{name: jnp.roll(layer[name], -1, axis=0)
                            for name in ("w_gate_up", "w_down")})
    shifted = ref.expert_layer(dict(layer, held_shift=jnp.int32(1)), x,
                               shape)
    assert float(jnp.max(jnp.abs(
        shifted - ref.expert_layer(rolled, x, shape)))) < 1e-6
    assert float(jnp.max(jnp.abs(
        shifted - ref.expert_layer(layer, x, shape)))) > 1e-4
    lost = family.planted(params, "latent_rope_unwritten", tiny_config)
    was, now = (t["periods"][2]["mla"]["w_kv_a"] for t in (params, lost))
    assert float(jnp.abs(now[..., 16:]).max()) == 0       # k_r reads 0
    assert np.array_equal(now[..., :16], was[..., :16])   # c as it was
    assert lost["periods"][2]["mla"]["wq"] is params["periods"][2]["mla"][
        "wq"]
    assert lost["periods"][0] is params["periods"][0]
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
    root = tmp_path_factory.mktemp("tiny_kimi")
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
    assert summary["prefix_hit_rate"] == 0.0 and summary["moe"]["tokens_fed"]
    # 2 latent layers x (4 slots x 8 blocks + trash) x 8 x 128 lanes x 2 B.
    assert summary["latent_pool_bytes"] == 2 * 33 * 8 * 128 * 2
    assert loop.engine.scheduler.kv.v is None


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
    # The window's counters, as the readers find them in the program's
    # registry once the engine is gone.
    man = mf.Manifest(tiny_root)
    pairs = man.reader("moe_held_pairs_per_token")(None)
    load = man.reader("moe_expert_load_max_x")(None)
    assert 0.5 < pairs < 1.5            # near 2 * 8 / 16 = 1.0
    assert 1.0 <= load <= 4.0
    # 7 KDA layers x 4 slots x (4 heads x 8 x 8 + 3 x 96) float32.
    assert man.reader("recurrent_state_gb")(None) == pytest.approx(
        7 * 4 * (256 + 288) * 4 / 1e9)
    assert man.reader("latent_pool_gb")(None) == pytest.approx(
        2 * 33 * 8 * 128 * 2 / 1e9)


def test_the_readers_read_nothing_where_there_is_no_counter(monkeypatch):
    from trustworthy_dl_tpu.obs import registry

    monkeypatch.setattr(registry, "_DEFAULT_REGISTRY",
                        registry.MetricsRegistry())
    assert MANIFEST.reader("latent_pool_gb")(None) is None
    gauge = registry.get_registry().gauge(
        latent_readers.POOL_BYTES, "as a GPT-2 or Solar engine leaves it")
    gauge.set(0.0)
    assert MANIFEST.reader("latent_pool_gb")(None) is None

    class Untraced:
        trace = None
        peak = None
        counters = {}
        family = family

    for name in ("latent_decode_roofline", "latent_prefill_roofline"):
        assert MANIFEST.reader(name)(Untraced()) is None


def test_the_rooflines_read_the_latent_kernels_alone():
    """A traced run as the readers see it: the latent kernels' events by the
    names the program gives them, the work from the ticks' lengths; a family
    with no latent layer (Solar, GPT-2) reads None."""
    from benchmark.harness import peaks
    from benchmark.harness.families import solar_open2

    class Trace:
        events = {0: [("latent_decode.2", 0.0, 2e-3),
                      ("latent_prefill.2", 1.0, 4e-3),
                      ("_paged_attn_call.1", 2.0, 1.0),
                      ("fusion.7", 3.0, 1.0)]}

    class Run:
        trace = Trace()
        peak = peaks.peak("TPU v5 lite")
        config = FILE
        counters = {"trace_ticks": [
            {"tokens": 3, "expected": 3, "decode": [9000, 12000],
             "prefill": [(4096, 1024)]}]}

    run = Run()
    run.family = family
    decode = latent_work.latent_decode([9000, 12000], 32, 576, 512, 256)
    want = 100.0 * max(decode.flops / run.peak.flops_bf16,
                       decode.bytes / run.peak.hbm_bytes_per_s) / 2e-3
    assert MANIFEST.reader("latent_decode_roofline")(run) == pytest.approx(
        want)
    assert 0 < want < 100
    prefill = latent_work.latent_prefill([(4096, 1024)], 32, 192, 128, 576,
                                         256)
    want = 100.0 * max(prefill.flops / run.peak.flops_bf16,
                       prefill.bytes / run.peak.hbm_bytes_per_s) / 4e-3
    assert MANIFEST.reader("latent_prefill_roofline")(run) == pytest.approx(
        want)
    run.family = solar_open2
    assert MANIFEST.reader("latent_decode_roofline")(run) is None
    run.family = family
    run.counters = {"trace_ticks": [dict(run.counters["trace_ticks"][0],
                                         tokens=2)]}
    assert MANIFEST.reader("latent_prefill_roofline")(run) is None
