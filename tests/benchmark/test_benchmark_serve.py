"""The serving side: the mix's work does not depend on the seed, the closed
loop keeps every slot taken through the engine's public surface alone, the
window counts whole ticks, and ``correct`` passes on the sound engine and
FAILS for the fp8 control, for each planted fault and for a token altered
where it is produced.  CPU, a tiny GPT-2 through the real ``ServingEngine``."""

import glob
import json
import os
import re
import shutil
import time

import numpy as np
import pytest

from bench_paths import BENCH, ROOT

from benchmark.harness import correct_serve, manifest as mf, result
from benchmark.harness import serve_traffic, weights

MIXES = {os.path.basename(p)[:-5]: json.load(open(p))
         for p in sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))}
SERVE = [name for name, mix in MIXES.items() if mix["kind"] == "serve-closed"]
#: For each mix, the ``max_seq`` of the configurations whose cells use it;
#: for a mix that no cell uses yet, the largest any serving configuration
#: has, so that the cap never goes silently.
MANIFEST = mf.Manifest(ROOT)


def _max_seq(config):
    return MANIFEST.config(config).get("deployment", {}).get(
        "serve_config", {}).get("max_seq")


SERVED = [n for n in (_max_seq(c["name"]) for c in MANIFEST.data["configs"])
          if n is not None]
MAX_SEQ = {name: [
    _max_seq(cell["config"]) for cell in MANIFEST.data["workloads"]
    if cell["traffic"] == name] or [max(SERVED)] for name in SERVE}
CELL = "serve-large-docbatch"
DRIVER = os.path.join(BENCH, "harness", "drivers", "serve_closed.py")
TINY = dict(vocab_size=16384, n_positions=64, n_ctx=64, n_embd=64, n_layer=2,
            n_head=4)
TINY_MIX = dict(clients=4, requests=12,
                prompt={"median": 30, "sigma": 0.3, "min": 20, "max": 44},
                reply={"median": 10, "sigma": 0.4, "min": 6, "max": 16},
                warm_completed=4, trace_ticks=3, correct_sample=8)
#: At the tiny size the sound engine reads 0 in all three on the CPU, the
#: fp8 control at least 0.02 / 0.0003 / 0.0125 and every fault more.
TINY_LIMITS = {"worst_shortfall": 0.005, "mean_shortfall": 0.0001,
               "argmax_miss_share": 0.005}


# -- the generator -------------------------------------------------------------


@pytest.mark.parametrize("name", SERVE)
def test_the_lengths_are_the_file_s_and_no_seed_s(name):
    mix = MIXES[name]
    table = serve_traffic.shapes(mix)
    assert table == serve_traffic.shapes(json.loads(json.dumps(mix)))
    assert len(table) == mix["requests"]
    prompts, replies = zip(*table)
    assert min(prompts) >= mix["prompt"]["min"]
    assert max(prompts) <= mix["prompt"]["max"]
    assert min(replies) >= mix["reply"]["min"]
    assert max(replies) <= mix["reply"]["max"]
    offered = serve_traffic.offered(mix)
    assert MAX_SEQ[name]
    for max_seq in MAX_SEQ[name]:       # of every configuration it runs on
        assert offered["longest"] <= max_seq
    assert offered["prompt_tokens"] == sum(prompts)
    assert sorted(prompts)[len(prompts) // 2] == pytest.approx(
        mix["prompt"]["median"], rel=0.02)
    other = serve_traffic.shapes(
        dict(mix, order_constant=mix["order_constant"] + 1))
    assert other != table              # another constant, another order
    assert sorted(p for p, _ in other) == sorted(prompts)
    assert sorted(r for _, r in other) == sorted(replies)


@pytest.mark.parametrize("seeds", [(0, 1), (7, 2_500_000_123),
                                   (2 ** 31 + 5, 3)], ids=str)
@pytest.mark.parametrize("name", SERVE)
def test_two_seeds_offer_the_same_lengths_and_other_token_ids(name, seeds):
    table = serve_traffic.shapes(MIXES[name])
    for index in (0, 5, len(table) + 5):
        a, b = (serve_traffic.request(table, seed, index, 50257)
                for seed in seeds)
        assert len(a["prompt"]) == len(b["prompt"]) == table[index % len(
            table)][0]
        assert a["max_new_tokens"] == b["max_new_tokens"]
        assert not np.array_equal(a["prompt"], b["prompt"])
        again = serve_traffic.request(table, seeds[0], index, 50257)
        assert np.array_equal(a["prompt"], again["prompt"])
        assert a["prompt"].max() > 40000 and a["prompt"].min() >= 0
    first = serve_traffic.request(table, seeds[0], 5, 50257)
    round_two = serve_traffic.request(table, seeds[0], len(table) + 5, 50257)
    assert not np.array_equal(first["prompt"], round_two["prompt"])


def test_quantiles_are_evenly_spaced_and_clipped():
    spec = {"median": 100, "sigma": 0.5, "min": 60, "max": 150}
    values = serve_traffic.lognormal_quantiles(spec, 40)
    assert values == sorted(values) and len(values) == 40
    assert values[0] == 60 and values[-1] == 150
    assert values[19] <= 100 <= values[20]


# -- the driver touches the public surface only --------------------------------


def test_the_driver_reads_no_private_attribute():
    source = open(DRIVER).read()
    assert not re.findall(r"\._[a-z]", source)
    for name in ("from_config", "submit(", "step()", "drain_results()",
                 "metrics_summary()", "on_token"):
        assert name in source


# -- a tiny engine, really driven ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_serve")
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), root / "benchmark" / sub)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)

    def edit(rel, change):
        path = root / "benchmark" / rel
        data = json.load(open(path))
        change(data)
        json.dump(data, open(path, "w"))

    def config(data):
        data.update(TINY)
        data["deployment"]["serve_config"].update(
            max_slots=4, max_seq=64, prefill_chunk=16)
        data["deployment"]["prefill_chunk_positions"] = 16

    edit("configs/gpt2-large-774m.json", config)
    edit("traffic/docbatch-closed24.json", lambda m: m.update(TINY_MIX))
    edit(f"limits/{CELL}.json", lambda d: d.update(limits=TINY_LIMITS))
    return str(root)


def tiny_run(root, seed=7, seconds=0.2):
    man = mf.Manifest(root)
    cell = man.cell(CELL)
    run = result.Run(cell, man.config(cell["config"]),
                     man.traffic(cell["traffic"]), seed, seconds, False)
    run.counters["process_start"] = time.time()
    return run, man


class StepClock:
    """A clock that moves only when the loop says a tick took time."""

    def __init__(self):
        self.now = 50.0

    def __call__(self):
        return self.now


@pytest.fixture(scope="module")
def driven(tiny_root):
    """One closed loop at the tiny size, 60 ticks, on a stepped clock."""
    from benchmark.harness import common
    from benchmark.harness.drivers import serve_closed

    run, _ = tiny_run(tiny_root)
    common.configure_jax(run)
    clock = StepClock()
    loop = serve_closed.ClosedLoop(run, serve_closed.build_engine(run), clock)
    step = loop.engine.step

    def timed_step():
        clock.now += 0.25
        return step()

    loop.engine.step = timed_step
    for _ in range(60):
        loop.tick()
    return run, loop


def test_the_closed_loop_keeps_every_slot_taken(driven):
    run, loop = driven
    clients = run.mix["clients"]
    ramp = clients * run.mix["ramp_ticks"]
    assert [t["in_flight"] for t in loop.ticks[:ramp:run.mix["ramp_ticks"]]] \
        == list(range(1, clients + 1))
    assert all(t["in_flight"] == clients for t in loop.ticks[ramp:])
    assert loop.engine.metrics_summary()["peak_active_requests"] == clients
    assert len(loop.flights) == clients and len(loop.finished) >= 12
    assert loop.submitted == len(loop.finished) + clients


def test_every_tick_held_the_work_the_clients_foresaw(driven):
    run, loop = driven
    assert loop.model_misses == 0
    assert all(t["tokens"] == t["expected"] for t in loop.ticks)
    table = serve_traffic.shapes(run.mix)
    for flight in loop.finished:
        plen, rlen = table[flight.index % len(table)]
        assert len(flight.prompt) == plen and flight.reply_len == rlen
        assert flight.status == "completed"
        assert flight.tokens == flight.result_tokens
        assert len(flight.tokens) == rlen
        chunks = -(-plen // 16)
        assert flight.done_tick == flight.first_tick + chunks + rlen - 2
    busy = [t for t in loop.ticks if t["prefill"] and t["decode"]]
    assert busy and all(rows <= 16 for t in busy for _, rows in t["prefill"])
    assert loop.engine.metrics_summary()["prefix_hit_rate"] == 0.0


def test_a_flagged_request_costs_the_loop_no_slot(tiny_root, monkeypatch):
    """The monitor flags the sixth request it scores; the engine quarantines
    its slot; the driver, as the operator, releases it before the next tick,
    so every tick still holds every client and the work the model foresaw."""
    from trustworthy_dl_tpu.serve import engine as serve_engine

    from benchmark.harness import common
    from benchmark.harness.drivers import serve_closed

    scored = []

    def observe(self, entropies, margins):
        scored.append(len(entropies))
        return len(scored) == 6, 9.0

    monkeypatch.setattr(serve_engine.OutputMonitor, "observe", observe)
    run, _ = tiny_run(tiny_root, seed=11)
    common.configure_jax(run)
    loop = serve_closed.ClosedLoop(run, serve_closed.build_engine(run))
    for _ in range(50):
        loop.tick()
    assert loop.flagged == 1 and len(scored) >= 10
    assert loop.engine.metrics_summary()["requests_flagged"] == 1
    assert not loop.engine.quarantined_slots
    clients = run.mix["clients"]
    ramp = clients * run.mix["ramp_ticks"]
    assert all(t["in_flight"] == clients for t in loop.ticks[ramp:])
    assert loop.model_misses == 0
    assert all(f.status == "completed" for f in loop.finished)


def test_the_window_counts_whole_ticks(driven):
    from benchmark.harness.window import Window

    _, loop = driven
    clock = loop.clock
    window = Window(1.1, clock)
    before = len(loop.ticks)
    window.open()
    while window.admits():
        window.count(loop.tick()["tokens"])
    window.close()
    ticks = loop.ticks[before:]
    assert len(ticks) == 5 and window.units == 5     # 0.25 s a tick
    assert window.elapsed == pytest.approx(1.25)
    assert window.work == sum(t["tokens"] for t in ticks) > 0
    assert window.rate() == pytest.approx(window.work / 1.25)
    assert all(t["end"] - t["start"] == pytest.approx(0.25) for t in ticks)


def test_the_sample_holds_the_longest_and_follows_the_seed(driven):
    _, loop = driven
    done = loop.finished
    picked = correct_serve.sample(done, 7, 8)
    assert len(picked) == 8 and len({f.index for f in picked}) == 8
    longest = max(len(f.prompt) + len(f.result_tokens) for f in done)
    assert len(picked[0].prompt) + len(picked[0].result_tokens) == longest
    assert correct_serve.sample(done[:5], 7, 8) == sorted(
        done[:5], key=lambda f: (len(f.prompt) + len(f.result_tokens),
                                 f.index))
    assert [f.index for f in correct_serve.sample(done, 7, 8)] == \
        [f.index for f in picked]
    others = {tuple(f.index for f in correct_serve.sample(done, seed, 8))
              for seed in range(12)}
    assert len(others) > 1


# -- correct: sound, control, faults -------------------------------------------


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
    found = correct_serve.readings(run.seed, run.config, pairs, 16,
                                   chunk=16)
    assert found["compared_requests"] == 8 and found["compared_tokens"] > 60
    run = judged(run, found)
    assert run.correct and set(run.compare) == set(TINY_LIMITS)


@pytest.mark.parametrize("control", [
    {"precision": "fp8"}, {"fault": "last_chunk_dropped"},
    {"fault": "layer_cache_unwritten"}, {"fault": "neighbour_blocks"}],
    ids=lambda c: next(iter(c.values())))
def test_the_control_and_each_planted_fault_are_not_correct(driven, control):
    """The reference put in the program's place: in the precision below the
    configuration's bfloat16, and with each fault a paged server can have."""
    run, pairs = served_pairs(driven)
    found = correct_serve.readings(run.seed, run.config, pairs, 16,
                                   chunk=16, **control)
    run = judged(run, found)
    assert not run.correct
    assert any(value > limit for value, limit in run.compare.values())


def test_an_unknown_fault_and_a_missing_limit_are_errors(driven):
    run, pairs = served_pairs(driven)
    with pytest.raises(ValueError):
        correct_serve.readings(run.seed, run.config, pairs, 16,
                               fault="no-such-fault")
    fresh = result.Run(run.cell, run.config, run.mix, run.seed, 1.0, False)
    with pytest.raises(KeyError):
        correct_serve.judge(fresh, {"worst_shortfall": 0.0}, {})
    correct_serve.judge(fresh, {"worst_shortfall": 0.0, "mean_shortfall":
                                0.0}, {"worst_shortfall": 0.1,
                                       "mean_shortfall": None})
    assert set(fresh.compare) == {"worst_shortfall"} and fresh.correct


def test_no_finished_request_is_not_correct():
    run = result.Run({}, {}, {}, 0, 1.0, False)
    correct_serve.judge(run, correct_serve.numbers([], []), TINY_LIMITS)
    assert set(run.compare) == set(TINY_LIMITS) and not run.correct


def test_the_serving_reference_is_the_full_forward():
    """``reply_logits`` against ``reference_gpt2.hidden`` and the tied head,
    unpadded, and each fault's context."""
    import jax.numpy as jnp

    from benchmark.harness import reference_gpt2 as ref
    from benchmark.harness import reference_gpt2_serve as serve_ref

    model = dict(TINY, vocab_size=512)
    model.pop("n_ctx")
    params = weights.make(3, model)
    rng = np.random.default_rng(0)
    prompt, reply = rng.integers(0, 512, 37), rng.integers(0, 512, 9)
    got = serve_ref.reply_logits(params, prompt, reply, 4, 64, 16)
    tokens = jnp.asarray(np.concatenate([prompt, reply])[None])
    want = ref._mm(ref.hidden(params, tokens, 4)[0], params["wte"].T, "f32")
    assert got.shape == (9, 512)
    assert np.allclose(got, want[36:45], atol=1e-5)
    late = serve_ref.reply_logits(params, rng.integers(0, 512, 58),
                                  reply[:6], 4, 64, 16)
    assert late.shape == (6, 512)
    with pytest.raises(ValueError):
        serve_ref.reply_logits(params, prompt, rng.integers(0, 512, 28), 4,
                               64, 16)
    other = rng.integers(0, 512, 20)
    assert len(serve_ref.faulty_context("last_chunk_dropped", prompt, other,
                                        16)) == 32
    assert len(serve_ref.faulty_context(
        "last_chunk_dropped", prompt[:32], other, 16)) == 16
    swapped = serve_ref.faulty_context("neighbour_blocks", prompt, other, 16)
    assert len(swapped) == 37 and np.array_equal(swapped[:20], other)
    assert serve_ref.faulty_context("", prompt, other, 16) is prompt
    broken = serve_ref.unwrite_layer_cache(params, 1)
    qkv = broken["blocks"]["attn"]["qkv"]
    assert float(jnp.abs(qkv["w"][1, :, 128:]).max()) == 0.0
    assert float(jnp.abs(qkv["w"][0, :, 128:]).max()) > 0.0
    assert float(jnp.abs(qkv["w"][1, :, :128]).max()) > 0.0


# -- the whole command, past the look for a chip -------------------------------


def drive(root, capsys, seed=5):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_run_main", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    code = module.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                        "0.3", "--trace", "0", "--root", root],
                       skip_device_check=True)
    assert code == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compare"
    assert "correct:" in out.err.strip().splitlines()[-1]
    return line, out.err


def test_a_sound_serving_run_is_correct(tiny_root, capsys):
    line, err = drive(tiny_root, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert set(line["compare"]) == set(TINY_LIMITS)
    assert "tick_model_misses 0" in err and "prefix_hit_rate 0.0" in err


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, capsys, monkeypatch):
    """Under the timed path: every token the engine's scheduler records is
    shifted by one, so what the clients are streamed, what the results hold
    and what is fed back as the next input are all the altered tokens."""
    from trustworthy_dl_tpu.serve import scheduler

    record = scheduler.SlotTask._record

    def altered(self, token, ent, margin):
        record(self, (token + 1) % TINY["vocab_size"], ent, margin)

    monkeypatch.setattr(scheduler.SlotTask, "_record", altered)
    line, _ = drive(tiny_root, capsys)
    assert line["correct"] is False and line["failed"] == 0
    over = {k for k, v in line["compare"].items() if v["value"] > v["limit"]}
    assert {"worst_shortfall", "argmax_miss_share"} <= over
