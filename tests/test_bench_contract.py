"""Contract tests for bench.py.

``python bench.py`` measures the chip in ONE process.  With no TPU, or on
any failure, it exits non-zero and prints no record: a number from a CPU
run is never published under the name of a device metric, and nothing is
dressed as a skip.  The optional legs' record shapes are pinned below on
a tiny model.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_bench(extra_env, timeout=300):
    env = dict(os.environ)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("extra", [
    pytest.param({"JAX_PLATFORMS": "cpu"}, id="cpu-only"),
    pytest.param({"JAX_PLATFORMS": "bogus"}, id="backend-cannot-start"),
    pytest.param({"JAX_PLATFORMS": "cpu", "TDDL_BENCH_SERVE": "1"},
                 id="cpu-only-serve-leg"),
])
def test_bench_without_a_tpu_fails_and_prints_no_record(extra):
    """No chip is a failure, whatever legs are on: non-zero exit, nothing
    on stdout (least of all a ``skipped`` record at exit 0)."""
    proc = _run_bench(extra)
    assert proc.returncode != 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", proc.stdout
    assert "skipped" not in proc.stderr


def test_bench_is_one_process_with_no_probe_or_watchdog():
    """The measured body runs in the process ``main()`` runs in: the only
    child bench.py can start is the jax-free lint leg."""
    src = (REPO / "bench.py").read_text()
    assert src.count("subprocess.") == 2        # lint: run + TimeoutExpired
    for gone in ("_skip_record", "_probe_backend", "WATCHDOG",
                 "FAKE_WEDGE", "_TDDL_BENCH_INNER", "RETRY_SLEEP"):
        assert gone not in src, gone


def test_bench_sentinel_ledger_defaults_under_the_output_dir(monkeypatch):
    """``PERF_LEDGER.jsonl`` at the repo root is the driver's record; the
    script's own sentinel ledger lives under the run's output directory
    unless TDDL_BENCH_PERF_LEDGER names a file."""
    sys.path.insert(0, str(REPO))
    import bench

    monkeypatch.delenv("TDDL_BENCH_PERF_LEDGER", raising=False)
    monkeypatch.delenv("TDDL_BENCH_OBS_DIR", raising=False)
    assert bench._perf_ledger_path() == os.path.join(
        "chiprun_out", "bench", "PERF_LEDGER.jsonl")
    monkeypatch.setenv("TDDL_BENCH_OBS_DIR", "/some/run")
    assert bench._perf_ledger_path() == "/some/run/PERF_LEDGER.jsonl"
    monkeypatch.setenv("TDDL_BENCH_PERF_LEDGER", "/elsewhere/ledger.jsonl")
    assert bench._perf_ledger_path() == "/elsewhere/ledger.jsonl"


def test_bench_serve_sweep_records(monkeypatch):
    """bench_serve's offered-load sweep on a tiny model: per-rate records
    carry the throughput/latency keys the JSON contract publishes."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_SERVE_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_SERVE_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_SERVE_REQUESTS", "5")
    monkeypatch.setenv("TDDL_BENCH_SERVE_NEW", "4")
    monkeypatch.setenv("TDDL_BENCH_SERVE_RATES", "100")
    records = bench.bench_serve()
    assert len(records) == 1
    row = records[0]
    for key in ("offered_rps", "tokens_per_s", "itl_p50_ms", "itl_p99_ms",
                "ttft_p50_ms", "completed", "shed"):
        assert key in row, row
    assert row["completed"] + row["shed"] == 5
    assert row["tokens_per_s"] > 0
    # SLO evidence rides every sweep arm: streaming percentile sketches
    # + per-rule burn rates + breach counts.
    slo = row["slo"]
    assert {r["name"] for r in slo["rules"]} == {"ttft", "itl"}
    for rule in slo["rules"]:
        assert rule["burn_rate"] >= 0.0
    assert slo["breach_total"] >= 0 and "shed_slo" in slo
    assert slo["itl_s"]["count"] > 0 and slo["itl_s"]["p50"] > 0.0
    assert slo["ttft_s"]["count"] == row["completed"]


def test_bench_spec_ab_records(monkeypatch):
    """bench_spec's spec-off vs spec_k A/B on a tiny model: the off arm
    carries EXACTLY today's serve-sweep record shape (enabling the spec
    leg must not mutate the baseline contract), every arm serves the
    identical seeded workload to completion, and the spec arms report
    accepted_rate + draft/verify tick fractions; the record's top-level
    accepted_rate is what the sentinel fingerprint lifts."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_SPEC_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_SPEC_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_SPEC_REQUESTS", "5")
    monkeypatch.setenv("TDDL_BENCH_SPEC_NEW", "6")
    monkeypatch.setenv("TDDL_BENCH_SPEC_RATE", "100")
    monkeypatch.setenv("TDDL_BENCH_SERVE_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_SERVE_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_SERVE_REQUESTS", "5")
    monkeypatch.setenv("TDDL_BENCH_SERVE_NEW", "4")
    monkeypatch.setenv("TDDL_BENCH_SERVE_RATES", "100")
    record = bench.bench_spec()
    assert set(record["arms"]) == {"off", "k2", "k4"}
    off = record["arms"]["off"]
    # The off arm IS today's serve record shape, key for key.
    serve_row = bench.bench_serve()[0]
    assert set(off) == set(serve_row)
    for label in ("off", "k2", "k4"):
        row = record["arms"][label]
        assert row["completed"] + row["shed"] == 5
        assert row["tokens_per_s"] > 0
    assert record["arms"]["k2"]["completed"] == off["completed"]
    for label in ("k2", "k4"):
        spec = record["arms"][label]["spec"]
        assert spec["proposed"] > 0
        assert 0.0 <= spec["accepted_rate"] <= 1.0
        assert spec["accepted"] <= spec["proposed"]
        assert abs(spec["draft_frac"] + spec["verify_frac"] - 1.0) < 1e-3
    assert record["accepted_rate"] \
        == record["arms"]["k4"]["spec"]["accepted_rate"]
    assert record["tokens_per_s_ratio"] > 0


def test_bench_paged_attn_ab_records(monkeypatch):
    """bench_paged_attn's kernel-vs-jnp A/B: on the CPU container it
    returns the HONEST skip record (compiled Mosaic cannot dispatch —
    interpret mode would measure the interpreter, not the kernel); under
    the record-shape smoke knob the arms are the shared serve record
    shape riding decode_tick_fraction + attn_kernel_path, the top-level
    decode_tick_fraction is the kernel arm's (what the sentinel
    fingerprint lifts), and the monitor-reduction microbench reports the
    epilogue-vs-jnp cost delta."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    # Honest skip off-TPU: attributable reason, no arms.
    monkeypatch.delenv("TDDL_BENCH_PAGED_ATTN_INTERPRET", raising=False)
    skip = bench.bench_paged_attn()
    assert skip["skipped"] and "pallas_undispatchable" in skip["reason"]

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_INTERPRET", "1")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_BLOCK", "8")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_REQUESTS", "4")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_NEW", "4")
    monkeypatch.setenv("TDDL_BENCH_PAGED_ATTN_RATE", "100")
    record = bench.bench_paged_attn()
    assert set(record["arms"]) == {"pallas", "jnp"}
    # Both arms ride the shared serve record shape, so enabling the leg
    # can never fork the serve contract.
    assert set(record["arms"]["pallas"]) == set(record["arms"]["jnp"])
    for label, path in (("pallas", "interpret"), ("jnp", "jnp")):
        row = record["arms"][label]
        assert row["completed"] + row["shed"] == 4
        assert row["tokens_per_s"] > 0
        assert 0.0 < row["decode_tick_fraction"] <= 1.0
        assert row["attn_kernel_path"] == path
    assert record["decode_tick_fraction"] \
        == record["arms"]["pallas"]["decode_tick_fraction"]
    assert record["streams_identical"] is True
    assert record["tokens_per_s_ratio"] > 0
    # The tier's two new A/B pairs ride the same serve record shape
    # plus their own serve-wall fraction — the sentinel lifts the
    # kernel arm's number for each.
    for arms_key, frac in (("prefill_arms", "prefill_chunk_fraction"),
                           ("verify_arms", "spec_verify_fraction")):
        assert set(record[arms_key]) == {"pallas", "jnp"}
        for label in ("pallas", "jnp"):
            row = record[arms_key][label]
            assert row["completed"] + row["shed"] == 4
            assert row["tokens_per_s"] > 0
            assert 0.0 < row[frac] <= 1.0
        assert record[frac] == record[arms_key]["pallas"][frac]
    assert record["prefill_streams_identical"] is True
    assert record["verify_streams_identical"] is True
    assert record["prefill_tokens_per_s_ratio"] > 0
    assert record["verify_tokens_per_s_ratio"] > 0
    assert record["monitor_us_jnp"] > 0
    assert record["monitor_us_kernel"] > 0
    assert "monitor_cost_delta_us" in record


def test_bench_quant_ab_records(monkeypatch):
    """bench_quant's equal-HBM A/B on a tiny model: the int8 arm admits
    >= 1.5x slots inside the baseline pool's byte budget, serves the
    whole workload, and the record carries the contract keys."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_QUANT_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_QUANT_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_QUANT_REQUESTS", "6")
    monkeypatch.setenv("TDDL_BENCH_QUANT_NEW", "4")
    record = bench.bench_quant()
    assert set(record["arms"]) == {"base", "int8"}
    base, quant = record["arms"]["base"], record["arms"]["int8"]
    assert record["slots_ratio"] >= 1.5             # the acceptance bar
    assert quant["kv_bytes"] <= record["budget_bytes"]  # equal-HBM arm
    assert quant["kv_fallback"] is None
    assert base["completed"] == quant["completed"] == 6
    for row in (base, quant):
        for key in ("slots", "kv_bytes", "kv_dtype", "weight_dtype",
                    "tokens_per_s", "wall_s"):
            assert key in row, row


def test_bench_adapters_ab_records(monkeypatch):
    """bench_adapters' equal-HBM A/B on a tiny model: the adapter arm
    pays for its low-rank pool in KV blocks (block-for-block inside the
    base arm's byte budget), drains the same seeded Zipf multi-tenant
    workload, and the record carries the sentinel lift keys
    (hit_rate, tokens_per_s_ratio)."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_REQUESTS", "8")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_NEW", "4")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_RANK", "2")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_PAGES", "2")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_TENANTS", "4")
    monkeypatch.setenv("TDDL_BENCH_ADAPTERS_COUNT", "3")
    record = bench.bench_adapters()
    assert set(record["arms"]) == {"off", "on"}
    off, on = record["arms"]["off"], record["arms"]["on"]
    # Equal-HBM contract: the KV blocks given back cover the low-rank
    # pool in full, so the adapter arm never exceeds the base budget.
    assert on["kv_bytes"] + record["adapter_pool_bytes"] \
        <= record["budget_bytes"]
    assert on["blocks"] < off["blocks"]
    assert "adapters" not in off          # base arm carries no pool
    pool = on["adapters"]
    assert pool["uploads"] >= 1           # Zipf traffic touched the pool
    assert 0.0 <= record["hit_rate"] <= 1.0
    assert record["tokens_per_s_ratio"] > 0
    assert record["evictions"] == pool["evictions"]
    for row in (off, on):
        assert row["completed"] >= 1
        assert row["tokens_per_s"] > 0


def test_bench_perf_sections_and_sentinel_fingerprint(monkeypatch,
                                                      tmp_path):
    """CONTRACT: every bench record carries the perf
    observability sections — "compile" (XLA compilations), "hbm"
    (live-buffer sweep + watermark) and "sentinel" (the ledger
    fingerprint + noise-band verdict) — and the fingerprint really
    lands in the rolling ledger.  ``_attach_perf_sections`` is the one
    function ``measure`` routes every measured record through."""
    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.obs.sentinel import PerfLedger

    ledger_path = tmp_path / "PERF_LEDGER.jsonl"
    monkeypatch.setenv("TDDL_BENCH_PERF_LEDGER", str(ledger_path))

    def record(value):
        return {"metric": "gpt2_tokens_per_sec_per_chip_detection_on",
                "value": value, "unit": "tokens/sec/chip",
                "vs_baseline": 1.0,
                "run_metadata": {"platform": "cpu",
                                 "device_kind": "cpu"}}

    rec = bench._attach_perf_sections(record(1000.0))
    for section in ("compile", "hbm", "sentinel"):
        assert section in rec, section
    assert rec["hbm"]["watermark_bytes"] >= 0
    sentinel = rec["sentinel"]
    assert sentinel["ledger"] == str(ledger_path)
    assert sentinel["fingerprint"]["tokens_per_s"] == 1000.0
    assert sentinel["regressed"] is False        # no baseline yet
    assert len(PerfLedger(str(ledger_path)).read()) == 1
    # `measure` routes the measured record through the helper.
    src = (REPO / "bench.py").read_text()
    assert "_attach_perf_sections(record" in src

    # Build a baseline, then a collapsed round -> confirmed regression.
    for value in (1010.0, 990.0, 1005.0):
        bench._attach_perf_sections(record(value))
    bad = bench._attach_perf_sections(record(100.0))
    assert bad["sentinel"]["regressed"] is True
    # The CI arm: rc 3 only when BOTH the env is on and the record
    # confirmed a regression (both arms covered).
    monkeypatch.delenv("TDDL_BENCH_SENTINEL", raising=False)
    assert bench._sentinel_rc(bad) == 0          # off by default
    monkeypatch.setenv("TDDL_BENCH_SENTINEL", "1")
    assert bench._sentinel_rc(bad) == 3
    assert bench._sentinel_rc(rec) == 0          # clean record stays rc 0


def test_bench_fleet_records(monkeypatch, tmp_path):
    """bench_fleet's goodput-under-SLO sweep on a tiny model: chaos-off
    and chaos-on arms over IDENTICAL seeded workloads, each row carrying
    the goodput/offered-load/recovery keys the JSON contract publishes."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_FLEET_REPLICAS", "2")
    monkeypatch.setenv("TDDL_BENCH_FLEET_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_FLEET_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_FLEET_REQUESTS", "6")
    monkeypatch.setenv("TDDL_BENCH_FLEET_RATES", "100")
    record = bench.bench_fleet()
    assert record["replicas"] == 2
    assert set(record["arms"]) == {"baseline", "chaos"}
    for arm in ("baseline", "chaos"):
        rows = record["arms"][arm]
        assert len(rows) == 1
        row = rows[0]
        for key in ("offered_rps", "goodput_tokens_per_s", "completed",
                    "deadline_exceeded", "shed", "failovers", "drains",
                    "quarantines", "restarts", "wall_s", "per_class"):
            assert key in row, (arm, row)
        # Zero lost accepted requests in EITHER arm: every request is
        # accounted as completed, deadline-shed or explicitly shed.
        assert row["completed"] + row["deadline_exceeded"] \
            + row["shed"] == 6, (arm, row)
        # Goodput-per-class curves (PR 13): the default ladder rides
        # every row, and the per-class completions sum to the row's.
        per_class = row["per_class"]
        assert set(per_class) == {"batch", "standard", "premium"}
        for cls in per_class.values():
            for key in ("completed", "tokens", "shed",
                        "goodput_tokens_per_s"):
                assert key in cls, (arm, cls)
        assert sum(c["completed"] for c in per_class.values()) \
            == row["completed"], (arm, per_class)
    chaos_row = record["arms"]["chaos"][0]
    # The chaos arm really injected: recovery machinery engaged.
    assert chaos_row["restarts"] >= 1
    assert chaos_row["failovers"] + chaos_row["drains"] >= 1
    # PR 18: the chaos arms run under an in-memory IncidentAssembler,
    # and the record publishes what the forensics engine counted —
    # every reason from the registered vocabulary, every count a
    # positive int, and the arm's quarantines mirrored exactly.
    from trustworthy_dl_tpu.analysis.contracts import ARTIFACT_REASONS
    incidents = record["incidents"]
    assert isinstance(incidents, dict)
    assert set(incidents) <= ARTIFACT_REASONS, incidents
    assert all(isinstance(n, int) and n > 0
               for n in incidents.values()), incidents
    if chaos_row["quarantines"]:
        assert incidents.get("replica_quarantine", 0) \
            >= chaos_row["quarantines"], incidents


@pytest.mark.migrate
def test_bench_migrate_records(monkeypatch, tmp_path):
    """bench_migrate's two A/B pairs on a tiny model: drain-by-runout
    vs drain-by-migration under an identical scripted REPLICA_PREEMPT,
    and unified vs disaggregated pools under the same bimodal prompt
    workload.  The migration arm's recoveries are block copies (the
    runout arm's are replays — live_migration=False pins the pre-PR
    arc), and the record's top-level migration_fraction is what the
    sentinel fingerprint lifts."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_REPLICAS", "3")
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_REQUESTS", "6")
    # Effectively-instant arrivals: the replay driver is wall-clock
    # paced, so at a modest rate the scripted tick-6 preempt races the
    # arrival schedule (warm jit caches tick faster than requests land
    # and the preempted replica can be caught mid-prefill, where export
    # refuses and the loss degrades to a replay failover).  Submitting
    # everything up front pins the in-flight set the fault hits.
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_RATE", "100000")
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_BIMODAL", "0.5")
    monkeypatch.setenv("TDDL_BENCH_MIGRATE_LONG_MEDIAN", "16")
    record = bench.bench_migrate()
    assert record["replicas"] == 3
    assert record["bimodal_frac"] == 0.5
    assert set(record["drain"]) == {"runout", "migration"}
    assert set(record["disagg"]) == {"unified", "disaggregated"}
    row_keys = {"goodput_tokens_per_s", "completed", "deadline_exceeded",
                "migrations", "preempts", "failovers", "wall_s"}
    for pair in (record["drain"], record["disagg"]):
        for arm, row in pair.items():
            assert row_keys <= set(row), (arm, row)
            assert row["completed"] + row["deadline_exceeded"] == 6, \
                (arm, row)
    # Both drain arms really lost the replica; they differ only in HOW
    # the in-flight work came back.
    assert record["drain"]["runout"]["preempts"] == 1
    assert record["drain"]["migration"]["preempts"] == 1
    assert record["drain"]["runout"]["migrations"] == 0
    assert record["drain"]["migration"]["migrations"] >= 1
    assert record["drain"]["migration"]["failovers"] == 0
    # The disaggregated arm hands every served request off once.
    assert record["disagg"]["unified"]["migrations"] == 0
    assert record["disagg"]["disaggregated"]["migrations"] \
        >= record["disagg"]["disaggregated"]["completed"]
    assert record["migration_fraction"] == 1.0


@pytest.mark.shard
def test_bench_shard_ab_records(monkeypatch):
    """bench_shard's equal-chip A/B on a tiny model: the FSDP arm's
    params+opt bytes per device must actually shrink toward 1/shards
    (measured from the placed shardings, not estimated), both arms must
    train to a finite loss, and the record carries the HBM watermark
    keys the perf artifact publishes."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_SHARD_NODES", "8")
    monkeypatch.setenv("TDDL_BENCH_SHARD_BATCH", "1")
    monkeypatch.setenv("TDDL_BENCH_SHARD_SEQ", "32")
    monkeypatch.setenv("TDDL_BENCH_SHARD_STEPS", "2")
    monkeypatch.setenv("TDDL_BENCH_SHARD_WARMUP", "1")
    record = bench.bench_shard()
    assert record["shards"] == 8
    assert record["tokens_per_step"] == 8 * 32
    row_keys = {"tokens_per_s", "hbm_watermark_bytes",
                "params_bytes_per_device", "opt_bytes_per_device",
                "final_loss"}
    for arm in ("replicated", "fsdp"):
        row = record[arm]
        assert row_keys <= set(row), (arm, row)
        assert row["tokens_per_s"] > 0
        assert row["params_bytes_per_device"] > 0
        assert row["hbm_watermark_bytes"] > 0
    # The headline: FSDP's per-device param/opt bytes near 1/shards of
    # the replicated arm's.  Not every leaf divides by 8 (biases,
    # layernorm scales stay replicated), so allow the small remainder.
    assert record["params_bytes_ratio"] <= 1.0 / 8 + 0.15, record
    assert record["opt_bytes_ratio"] <= 1.0 / 8 + 0.15, record
    assert record["params_bytes_ratio"] >= 1.0 / 8 - 0.01, record


@pytest.mark.fleetctl
def test_bench_autoscale_records(monkeypatch, tmp_path):
    """bench_autoscale's static-vs-autoscaled A/B on a tiny model:
    IDENTICAL seeded bursty traffic, the static arm pinned at max
    replicas, the autoscaled arm breathing min->max.  The record
    carries the replica-count trace, the scale-event counts and the
    per-class goodput the contract publishes — and the autoscaled arm
    really scaled (trace leaves the floor) while serving every
    accepted request."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_MIN", "1")
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_MAX", "2")
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_SLOTS", "2")
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_REQUESTS", "10")
    monkeypatch.setenv("TDDL_BENCH_AUTOSCALE_INFLIGHT", "8")
    record = bench.bench_autoscale()
    assert record["replicas_min"] == 1 and record["replicas_max"] == 2
    assert set(record["arms"]) == {"static", "autoscaled"}
    for arm, row in record["arms"].items():
        for key in ("accepted", "completed", "goodput_tokens_per_s",
                    "scale_ups", "scale_downs", "replica_trace",
                    "per_class", "wall_s"):
            assert key in row, (arm, row)
        assert row["completed"] == row["accepted"] == 10
        assert sum(c["completed"] for c in row["per_class"].values()) \
            == row["completed"]
    static, auto = record["arms"]["static"], record["arms"]["autoscaled"]
    # The static arm never scales; the autoscaled arm's trace shows the
    # breath (up under the closed-loop pressure, back down at drain).
    assert static["scale_ups"] == static["scale_downs"] == 0
    assert auto["scale_ups"] >= 1
    counts = [n for _, n in auto["replica_trace"]]
    assert counts[0] == 1 and max(counts) == 2
    assert auto["scale_downs"] >= 1 and counts[-1] == 1


@pytest.mark.adversary
def test_bench_adversary_records(monkeypatch, tmp_path):
    """bench_adversary's goodput-under-attack A/B on a tiny model:
    voting-off and voting-on arms over IDENTICAL seeded traffic.  The
    contract the record publishes: with voting OFF the sub-threshold
    attacker is never quarantined and serves corrupted streams for the
    whole run; with voting ON it is outvoted into quarantine and serves
    no more of them than the unprotected arm."""
    import jax.numpy as jnp

    sys.path.insert(0, str(REPO))
    import bench
    from trustworthy_dl_tpu.models import gpt2

    tiny = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2,
                           n_embd=32, n_head=4, dtype=jnp.float32)
    monkeypatch.setattr(gpt2.GPT2Config, "from_name",
                        staticmethod(lambda name, **kw: tiny))
    monkeypatch.setenv("TDDL_BENCH_ADVERSARY_REPLICAS", "3")
    # 6 slots: per-slot quarantine exhaustion needs 6 flags — the
    # sub-threshold attacker never banks that many, so the off arm
    # really is the measured blind spot (not a slow flag-tier catch).
    monkeypatch.setenv("TDDL_BENCH_ADVERSARY_SLOTS", "6")
    monkeypatch.setenv("TDDL_BENCH_ADVERSARY_SEQ", "48")
    monkeypatch.setenv("TDDL_BENCH_ADVERSARY_REQUESTS", "60")
    monkeypatch.setenv("TDDL_BENCH_ADVERSARY_MONITOR", "16")
    record = bench.bench_adversary()
    assert record["replicas"] == 3
    assert set(record["arms"]) == {"voting_off", "voting_on"}
    for arm, row in record["arms"].items():
        for key in ("vote_k", "inflight_target", "goodput_tokens_per_s",
                    "completed", "corrupted_served",
                    "final_attacker_strength", "attacker_flag_rate",
                    "suspicions", "votes", "outvotes", "drains",
                    "quarantines", "wall_s"):
            assert key in row, (arm, row)
    off = record["arms"]["voting_off"]
    on = record["arms"]["voting_on"]
    # The blind spot, measured: sub-threshold -> ladder never fires.
    assert off["quarantines"] == 0 and off["votes"] == 0
    assert off["corrupted_served"] > 0
    # Voting catches what the ladder cannot, on the SAME traffic.
    assert on["votes"] >= on["outvotes"] >= 2
    assert on["quarantines"] >= 1
    assert on["corrupted_served"] <= off["corrupted_served"]
