#!/usr/bin/env python
"""Throughput benchmark: GPT-2 trusted training, detection ON vs OFF.

Measures tokens/sec/chip of the jitted trusted train step (engine/step.py)
on the available accelerator, with the full in-step detection battery
(17-stat batteries, Byzantine/backdoor checks, verification, trust update,
trust-gated aggregation) enabled vs disabled.  The detection overhead is the
framework's headline number — BASELINE.md sets a ≤15 % target (the reference
publishes no numbers of its own).

Prints ONE JSON line on stdout:
  {"metric": ..., "value": tokens/sec/chip with detection ON,
   "unit": "tokens/sec/chip",
   "vs_baseline": ON/OFF throughput ratio (1.0 = free detection; the
                  baseline is this framework's own detection-off path)}
Diagnostics go to stderr.

Env overrides: TDDL_BENCH_MODEL (gpt2), TDDL_BENCH_NODES (4),
TDDL_BENCH_BATCH (per-node, 16), TDDL_BENCH_SEQ (512),
TDDL_BENCH_STEPS (20), TDDL_BENCH_WARMUP (3), TDDL_BENCH_REMAT (1),
TDDL_BENCH_CHUNK (unset = model default "auto"; 0 forces the
materialised-logits CE; >0 forces the fused vocab-chunked head),
TDDL_BENCH_ATTN (model default), TDDL_BENCH_ACCUM (grad accumulation
microbatches, 1).  Optional legs: TDDL_BENCH_LONGCTX=1 (flash vs XLA
long-context A/B), TDDL_BENCH_GEN=1 (decode), TDDL_BENCH_SERVE=1
(continuous-batching offered-load sweep; TDDL_BENCH_SPEC=1 rides it
and adds the speculative-decode A/B — spec off vs spec_k ∈ {2,4} over
identical
seeded traffic, accepted_rate + draft/verify tick fractions +
tokens/s per arm, "spec" record key whose accepted_rate feeds the
sentinel fingerprint, TDDL_BENCH_SPEC_* knobs; TDDL_BENCH_PAGED_ATTN=1
also rides it and adds the paged-attention kernel A/B — attn_impl
"pallas" vs the jnp gather fallback over identical seeded traffic,
tokens/s + decode-tick fraction + standalone monitor-reduction cost
delta, "paged_attn" record key whose decode_tick_fraction feeds the
sentinel fingerprint; honest skip off-TPU where compiled Mosaic cannot
dispatch, TDDL_BENCH_PAGED_ATTN_* knobs), TDDL_BENCH_CHAOS=1 (seeded
chaos survival sweep through the self-healing supervisor),
TDDL_BENCH_ASYNC=1 (async host-pipeline A/B: trainer loop at
async_host_depth 0 vs default, tokens/sec + obs phase shares),
TDDL_BENCH_QUANT=1 (int8 KV quantization A/B: model-dtype vs int8 KV
pool at EQUAL HBM budget — slots, KV bytes and tokens/s per arm;
TDDL_BENCH_QUANT_W8=1 adds weight-only int8 to the quantized arm),
TDDL_BENCH_MIGRATE=1 (live KV-migration A/B: capacity loss as block
copy vs prompt replay + unified vs disaggregated prefill/decode pools
under a bimodal prompt mix, "migrate" record key whose
migration_fraction feeds the sentinel fingerprint,
TDDL_BENCH_MIGRATE_* knobs),
TDDL_BENCH_SHARD=1 (equal-chip replicated vs FSDP train state:
tokens/s, per-device HBM watermark, params/opt bytes per device from
the placed shardings — ratio near 1/shards; TDDL_BENCH_SHARD_* knobs),
TDDL_BENCH_FLEET=1 (serving-fleet goodput-under-SLO vs offered load,
chaos OFF vs ON over identical seeded workloads — "fleet" record key,
TDDL_BENCH_FLEET_* knobs), TDDL_BENCH_ADVERSARY=1 (goodput under an
adaptive sub-threshold poison attack, verdict voting OFF vs ON over
identical seeded traffic — "adversary" record key,
TDDL_BENCH_ADVERSARY_* knobs), TDDL_BENCH_AUTOSCALE=1 (fleet control
plane A/B: static fleet at max replicas vs autoscaled min→max over
identical seeded bursty traffic — replica-count trace, scale event
counts and per-class goodput per arm, "autoscale" record key,
TDDL_BENCH_AUTOSCALE_* knobs; the fleet leg's rows also carry
per-class goodput now).
Infra knobs: TDDL_BENCH_LINT=1 (tddl-lint static-analysis leg in a
jax-free subprocess before any device work: clean -> "lint" record
section, findings -> rc 4; TDDL_BENCH_LINT_TIMEOUT seconds, default 300).

The measured body runs in this one process and needs a TPU: with no
chip, or on any failure, the exit code is non-zero and no record is
printed.  The persistent compilation cache is on
(utils/compile_cache.py: ``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``).

``--config <preset>`` selects a BASELINE.md benchmark-matrix shape
(`--config list` prints them); env overrides still apply on top.  The
default preset is the measured single-v5e sweet spot: per-node batch 16
(64 x 512 tokens/step) with block rematerialisation.
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _perf_ledger_path() -> str:
    """Home of the script's own rolling perf-fingerprint ledger, the
    sentinel's baseline (TDDL_BENCH_PERF_LEDGER overrides; default: under
    the run's output directory — TDDL_BENCH_OBS_DIR, else
    ``chiprun_out/bench``, what a chip call brings back.
    ``PERF_LEDGER.jsonl`` at the repo root is the driver's record, not
    this one)."""
    return os.environ.get("TDDL_BENCH_PERF_LEDGER") or os.path.join(
        os.environ.get("TDDL_BENCH_OBS_DIR")
        or os.path.join("chiprun_out", "bench"), "PERF_LEDGER.jsonl")


def _sentinel_rc(record: dict) -> int:
    """Exit code for the sentinel CI arm: TDDL_BENCH_SENTINEL=1 turns a
    confirmed regression (outside the ledger noise band) into rc 3 —
    off by default."""
    if os.environ.get("TDDL_BENCH_SENTINEL") != "1":
        return 0
    sentinel = record.get("sentinel") or {}
    return 3 if sentinel.get("regressed") else 0


def bench_lint() -> "dict | None":
    """Static-analysis leg (TDDL_BENCH_LINT=1): run trustworthy-dl-lint
    in a SUBPROCESS — the lint process is host-only by contract and
    never imports jax, so it does not contend for the chip.  No-op
    (None) when unset.

    Clean lint attaches a compact "lint" section to the round's record;
    findings fail the round loudly with rc 4 BEFORE any device work is
    paid for — the CI arm asserts rc 0 exactly like the sentinel's rc-3
    contract."""
    if os.environ.get("TDDL_BENCH_LINT") != "1":
        return None
    import subprocess

    t0 = time.time()
    timeout = float(os.environ.get("TDDL_BENCH_LINT_TIMEOUT", "300"))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "trustworthy_dl_tpu.analysis",
             "--format", "json"],
            capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        # A hung lint subprocess is a reportable lint failure (rc -1).
        return {"rc": -1, "timeout_s": timeout,
                "wall_s": round(time.time() - t0, 2),
                "files_scanned": None, "findings": [], "by_rule": {},
                "baselined": 0, "stale_baseline": [],
                "error": f"lint subprocess exceeded {timeout:g}s"}
    try:
        payload = json.loads(proc.stdout.strip() or "{}")
    except ValueError:
        payload = {}
    record = {
        "rc": proc.returncode,
        "wall_s": round(time.time() - t0, 2),
        "files_scanned": payload.get("files_scanned"),
        "findings": payload.get("findings", []),
        "by_rule": payload.get("by_rule", {}),
        "baselined": payload.get("baselined", 0),
        "stale_baseline": payload.get("stale_baseline", []),
    }
    if proc.returncode != 0 and proc.stderr:
        record["stderr"] = proc.stderr[-2000:]
    return record


def _attach_perf_sections(record: dict, compiles=None, hbm=None) -> dict:
    """The performance-observability sections every bench record
    carries: "compile" (XLA compilations observed during the
    body), "hbm" (live-buffer sweep + watermark), "sentinel" (the perf
    fingerprint appended to the rolling ledger + the noise-band
    verdict against prior rounds)."""
    from trustworthy_dl_tpu.obs.compilewatch import CompileRegistry
    from trustworthy_dl_tpu.obs.hbm import HbmMonitor
    from trustworthy_dl_tpu.obs.sentinel import (
        PerfLedger,
        PerfSentinel,
        fingerprint,
    )

    if compiles is None:
        compiles = CompileRegistry()   # uninstalled: an all-zero section
    record["compile"] = compiles.summary()
    if hbm is None:
        hbm = HbmMonitor()
    sweep = hbm.sweep()
    record["hbm"] = {
        "live_bytes_per_device": sweep["per_device"],
        "total_bytes": sweep["total_bytes"],
        "watermark_bytes": sweep["watermark_bytes"],
    }
    ledger = PerfLedger(_perf_ledger_path())
    fp = fingerprint(
        "bench",
        metric=record.get("metric"),
        tokens_per_s=record.get("value") or None,
        compile_total=(record.get("compile") or {}).get("total"),
        compile_seconds=(record.get("compile") or {}).get("seconds"),
        hbm_watermark_bytes=sweep["watermark_bytes"] or None,
        # Speculative-decode draft quality (TDDL_BENCH_SPEC rounds):
        # rides the fingerprint so the sentinel bands it (direction
        # higher-is-better) like any perf metric.
        accepted_rate=(record.get("spec") or {}).get("accepted_rate"),
        # Decode-phase serve-wall share of the paged-attention kernel arm
        # (TDDL_BENCH_PAGED_ATTN rounds): direction lower-is-better — a
        # silent fallback to the jnp gather path inflates it.
        decode_tick_fraction=(record.get("paged_attn")
                              or {}).get("decode_tick_fraction"),
        # Prefill-chunk / spec-verify serve-wall shares of the kernel
        # arms (same rounds): direction lower-is-better — a silent
        # fallback of the chunked-prefill flash program or the fused
        # verify tail inflates exactly one of them, and the per-program
        # attn-kernel gauge names which.
        prefill_chunk_fraction=(record.get("paged_attn")
                                or {}).get("prefill_chunk_fraction"),
        spec_verify_fraction=(record.get("paged_attn")
                              or {}).get("spec_verify_fraction"),
        # Adapter-pool locality + equal-HBM personalisation cost
        # (TDDL_BENCH_ADAPTERS rounds): both higher-is-better — a
        # colder pool or a pricier adapter path bands like a perf
        # regression.
        adapter_hit_rate=(record.get("adapters") or {}).get("hit_rate"),
        adapter_tokens_ratio=(record.get("adapters")
                              or {}).get("tokens_per_s_ratio"),
        # Live-migration success under capacity loss (TDDL_BENCH_MIGRATE
        # rounds): higher-is-better — a silent fall-back to prompt
        # replay (geometry drift, claim refusals) drops it.
        migration_fraction=(record.get("migrate")
                            or {}).get("migration_fraction"),
        run_metadata=record.get("run_metadata"),
        extra={"vs_baseline": record.get("vs_baseline")},
    )
    verdict = PerfSentinel(ledger).check(fp)
    fp["regressed"] = verdict["regressed"]
    ledger.append(fp)
    record["sentinel"] = {
        "ledger": ledger.path,
        "baseline_n": verdict["baseline_n"],
        "regressed": verdict["regressed"],
        "checks": verdict["checks"],
        "fingerprint": fp,
    }
    if verdict["regressed"]:
        log(f"perf sentinel: REGRESSION outside the noise band: "
            f"{[c['metric'] for c in verdict['checks'] if c.get('regressed')]}"
            f" (TDDL_BENCH_SENTINEL=1 makes this exit non-zero)")
    return record


# BASELINE.md benchmark-matrix presets (configs 1-4 shapes + extras), so
# driver BENCH_r*.json runs can capture any row reproducibly instead of
# builder-transcribed tables.  Values are defaults; TDDL_BENCH_* env
# overrides still win.
PRESETS = {
    # The headline row: GPT-2 small, 4 nodes x b16 x T512, remat.
    "default": {},
    # BASELINE config 1 shape: ResNet-32 / CIFAR-10.
    "resnet32": dict(model="resnet32", batch=64),
    # BASELINE config 2 shape: VGG-16 / CIFAR-10 (the conv-battery row).
    "vgg16": dict(model="vgg16", batch=64),
    "resnet50": dict(model="resnet50", batch=64),
    # BASELINE config 5's model (the sweep itself is an experiments
    # preset; this row gives its throughput baseline).
    "resnet101": dict(model="resnet101", batch=32),
    # BASELINE config 4 shape: GPT-2 medium.
    "gpt2-medium": dict(model="gpt2-medium", batch=8),
    # Long-context row: GPT-2 medium at T=1024, auto attention.
    "longctx": dict(model="gpt2-medium", batch=4, seq=1024),
}


def apply_preset(name: str) -> None:
    """Materialise a preset as TDDL_BENCH_* defaults (env wins)."""
    if name == "list":
        log("available presets: " + ", ".join(sorted(PRESETS)))
        sys.exit(0)
    if name not in PRESETS:
        log(f"unknown preset {name!r}; available: {sorted(PRESETS)}")
        sys.exit(2)
    keymap = {"model": "TDDL_BENCH_MODEL", "nodes": "TDDL_BENCH_NODES",
              "batch": "TDDL_BENCH_BATCH", "seq": "TDDL_BENCH_SEQ"}
    for key, value in PRESETS[name].items():
        os.environ.setdefault(keymap[key], str(value))


def _build_bench_trainer(detection: bool, model: str, num_nodes: int,
                         per_node_batch: int, seq_len: int):
    """(trainer, initial state, node batch) — ONE construction shared by
    the sequential and interleaved measurement paths so their model
    overrides (remat / attention / lm-head chunk) can never diverge."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.engine import DistributedTrainer

    config = TrainingConfig(
        model_name=model,
        dataset_name="openwebtext",
        batch_size=num_nodes * per_node_batch,
        num_nodes=num_nodes,
        optimizer=os.environ.get("TDDL_BENCH_OPT", "adamw"),
        learning_rate=1e-4,
        checkpoint_interval=10 ** 9,
        attack_detection_enabled=detection,
        gradient_verification_enabled=detection,
        parallelism="data",
        grad_accum_steps=int(os.environ.get("TDDL_BENCH_ACCUM", "1")),
        moment_dtype=os.environ.get("TDDL_BENCH_MU_DTYPE") or None,
    )
    overrides: dict = {}
    if model.startswith("gpt"):
        # Unset -> the model's lm_head_chunk="auto" dispatch; an explicit
        # value (including 0 = force materialised) overrides it.
        chunk_env = os.environ.get("TDDL_BENCH_CHUNK", "")
        if chunk_env != "":
            overrides["lm_head_chunk"] = int(chunk_env)
        overrides["seq_len"] = seq_len
        if seq_len > 1024:
            # Long-context runs need the position table to match.
            overrides["n_positions"] = seq_len
        attn = os.environ.get("TDDL_BENCH_ATTN")
        if attn:
            overrides["attn_impl"] = attn
        if os.environ.get("TDDL_BENCH_REMAT", "1") == "1":
            overrides["remat"] = True
            overrides["remat_policy"] = os.environ.get(
                "TDDL_BENCH_REMAT_POLICY", "block"
            )
    trainer = DistributedTrainer(config, model_overrides=overrides)
    trainer.initialize()
    batch = trainer._node_batch(jax.tree_util.tree_map(
        np.asarray,
        trainer.model.example_batch(num_nodes * per_node_batch,
                                    jax.random.PRNGKey(0)),
    ))
    return trainer, trainer.state, batch


def bench_mode(detection: bool, model: str, num_nodes: int,
               per_node_batch: int, seq_len: int, steps: int,
               warmup: int) -> "tuple[float, int]":
    """(steps/sec, param count) of the jitted step, driven device-side
    (no host sync in the timed loop beyond dispatch)."""
    import jax
    import numpy as np

    trainer, state, batch = _build_bench_trainer(
        detection, model, num_nodes, per_node_batch, seq_len
    )
    n_params = trainer.model.num_params(state.params)
    plan = trainer.attack_plan

    for _ in range(max(warmup, 1)):
        state, metrics = trainer._train_step(state, batch, plan)
    jax.block_until_ready(metrics.loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer._train_step(state, batch, plan)
    jax.block_until_ready(metrics.loss)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(float(metrics.loss)), "bench step produced NaN loss"
    return steps / elapsed, n_params


def bench_overhead_interleaved(model: str, num_nodes: int,
                               per_node_batch: int, seq_len: int,
                               block_steps: int, rounds: int,
                               warmup: int) -> "tuple[float, float, int]":
    """(steps/sec detection-ON, ON/OFF ratio, param count), measured as
    INTERLEAVED paired blocks: both step functions are compiled up front,
    then each round times one OFF block and one ON block back-to-back and
    the ratio is the median of per-round ratios.

    Rationale: for short-step (vision) configs a slow drift of throughput
    over multi-second windows swamps the sequential
    all-OFF-then-all-ON difference.  Pairing blocks a few hundred ms
    apart cancels the drift; the remaining per-round scatter is reported
    to stderr."""
    import numpy as np

    tr_on, st_on, b_on = _build_bench_trainer(
        True, model, num_nodes, per_node_batch, seq_len
    )
    tr_off, st_off, b_off = _build_bench_trainer(
        False, model, num_nodes, per_node_batch, seq_len
    )
    n_params = tr_on.model.num_params(st_on.params)

    def block(trainer, state, batch, steps):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer._train_step(state, batch,
                                           trainer.attack_plan)
        loss = float(np.asarray(m.loss))  # host close: real execution
        assert np.isfinite(loss)
        return state, time.perf_counter() - t0

    for _ in range(max(warmup, 1)):
        st_on, _ = block(tr_on, st_on, b_on, 1)
        st_off, _ = block(tr_off, st_off, b_off, 1)

    ratios, on_rates = [], []
    for r in range(rounds):
        st_off, t_off = block(tr_off, st_off, b_off, block_steps)
        st_on, t_on = block(tr_on, st_on, b_on, block_steps)
        ratios.append(t_off / t_on)
        on_rates.append(block_steps / t_on)
        log(f"  round {r}: OFF {block_steps / t_off:7.2f} ON "
            f"{block_steps / t_on:7.2f} steps/s (ratio {t_off / t_on:.4f})")
    return (float(np.median(on_rates)), float(np.median(ratios)), n_params)


def bench_longctx() -> None:
    """Optional long-context A/B (TDDL_BENCH_LONGCTX=1): flash-kernel vs
    XLA full attention, fwd+bwd, at sequence lengths where the [T, T]
    score matrix starts to dominate HBM.  Iterations chain (q feeds back)
    inside one jitted fori_loop, the close is a host materialisation,
    and the per-call constant is removed with a two-iteration-count
    slope.  Diagnostics only — stderr."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trustworthy_dl_tpu.models.gpt2 import full_attention
    from trustworthy_dl_tpu.ops.flash_attention import flash_attention

    b, h, d = 1, 12, 64
    i1 = int(os.environ.get("TDDL_BENCH_LONGCTX_ITERS", "4"))
    i2 = 4 * i1
    for t in (4096, 8192, 16384):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
                   for kk in ks)

        def make(attn, iters):
            def loss(q):
                return jnp.sum(attn(q, k, v, True).astype(jnp.float32) ** 2)

            def body(_, q):
                return q + 1e-3 * jax.grad(loss)(q)

            @jax.jit
            def run(q):
                out = jax.lax.fori_loop(0, iters, body, q)
                return jnp.sum(out.astype(jnp.float32))

            return run

        for name, attn in (("flash", flash_attention),
                           ("full", full_attention)):
            try:
                f1, f2 = make(attn, i1), make(attn, i2)
                np.asarray(f1(q)); np.asarray(f2(q))  # compile + settle

                def timed(fn):
                    t0 = time.perf_counter()
                    np.asarray(fn(q))  # host close: real execution
                    return time.perf_counter() - t0

                t_1 = min(timed(f1) for _ in range(3))
                t_2 = min(timed(f2) for _ in range(3))
                ms = (t_2 - t_1) / (i2 - i1) * 1e3
                log(f"longctx T={t:5d} {name:5s} fwd+bwd "
                    f"{ms:8.2f} ms/iter ({b * t / ms * 1e3:,.0f} tok/s; "
                    f"slope over {i2}-{i1} iters)")
            except Exception as exc:  # OOM on the full path is the point
                log(f"longctx T={t:5d} {name:5s} failed: "
                    f"{type(exc).__name__}: {str(exc)[:120]}")


def _drive_serve_open_loop(engine, workload) -> int:
    """Drive seeded ``(t_arrive, request)`` pairs through an engine
    open-loop (arrivals honoured against the wall clock, so queueing
    delay is real) — the ONE spelling of the serve-bench driver, shared
    by the offered-load sweep and the speculative-decode A/B so their
    rows measure the same thing.  Returns how many requests were shed."""
    t0 = time.perf_counter()
    pending = list(workload)
    shed = 0
    while pending or engine.busy:
        # A slot is only quarantined at retirement, so zero capacity
        # implies nothing is in flight either.
        if engine.in_service_capacity == 0:
            # Every slot quarantined mid-bench: nothing queued or
            # pending can ever be served — shed the remainder rather
            # than spin until the watchdog kills the whole body
            # (run_until_idle has the same guard).
            shed += len(pending)
            pending.clear()
            engine.run_until_idle()  # records queued as no_capacity
            break
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, req = pending.pop(0)
            if engine.submit(req) is None:
                shed += 1
        if not engine.busy and pending:
            # Idle gap before the next arrival: sleep instead of
            # spinning step() — empty iterations would pile metrics
            # bookkeeping onto the numbers this sweep reports.
            time.sleep(min(max(pending[0][0] - now, 0.0), 0.05))
            continue
        engine.step()
    return shed


def _serve_sweep_row(engine, watcher, rate, shed) -> dict:
    """The serve-bench record row (throughput/latency/SLO keys) — one
    builder, so every arm that claims "today's serve record shape"
    really has it."""
    summary = engine.metrics_summary()
    status = watcher.status()
    return {
        "offered_rps": rate,
        "tokens_per_s": round(summary["tokens_per_s"], 1),
        # Decode-phase share of the serve wall + the attention path that
        # produced it — the pair the perf sentinel / attn-kernel gauge
        # watch for silent fallbacks to the slow jnp gather.
        "decode_tick_fraction": round(summary["decode_tick_fraction"], 4),
        "attn_kernel_path": summary["attn_kernel_path"],
        "itl_p50_ms": round(summary.get("itl_p50_ms", 0.0), 3),
        "itl_p99_ms": round(summary.get("itl_p99_ms", 0.0), 3),
        "ttft_p50_ms": round(summary.get("ttft_p50_ms", 0.0), 3),
        "completed": summary["requests_completed"],
        "shed": shed,
        "slo": {
            "rules": [{"name": r["name"], "target": r["target"],
                       "burn_rate": round(r["burn_rate"], 4),
                       "active": r["active"]}
                      for r in status["rules"]],
            "breach_total": status["breach_total"],
            "shed_slo": summary.get("requests_shed_slo", 0),
            "ttft_s": {k: round(v, 6) if isinstance(v, float) else v
                       for k, v in watcher.percentiles(
                           "ttft_s").items()},
            "itl_s": {k: round(v, 6) if isinstance(v, float) else v
                      for k, v in watcher.percentiles(
                          "itl_s").items()},
        },
    }


def bench_serve() -> "list[dict]":
    """Serving-engine leg (TDDL_BENCH_SERVE=1): offered-load sweep over the
    continuous-batching engine (serve/) — tokens/s, p50/p99 inter-token
    latency and p50 TTFT per offered request rate.  Returned as a list of
    per-rate records merged into the bench JSON under "serve" (the skip
    contract is untouched: a dead backend never reaches this leg).

    Arrivals are simulated open-loop: requests carry seeded arrival times
    and are submitted when the wall clock passes them, so queueing delay is
    real — TTFT degrades visibly once the offered rate passes the slot
    pool's capacity."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_SERVE_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    max_slots = int(os.environ.get("TDDL_BENCH_SERVE_SLOTS", "8"))
    max_seq = int(os.environ.get("TDDL_BENCH_SERVE_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_SERVE_REQUESTS", "32"))
    max_new = int(os.environ.get("TDDL_BENCH_SERVE_NEW", "32"))
    rates = [float(r) for r in os.environ.get(
        "TDDL_BENCH_SERVE_RATES", "4,16,64").split(",")]
    rng = np.random.default_rng(0)

    records = []
    for rate in rates:
        # SLO evidence rides every sweep arm: streaming P2 TTFT/ITL
        # estimates + breach counts land in the record's "slo" section
        # (stamped run_metadata at the bench-JSON top level as always).
        from trustworthy_dl_tpu.obs.slo import SLOWatcher, \
            default_serve_rules

        watcher = SLOWatcher(default_serve_rules())
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), slo=watcher)
        workload = []
        t_arrive = 0.0
        # Exclusive draw bound: plen <= max_seq - max_new, so prompt+new
        # can never exceed the slot depth whatever the env overrides say.
        plen_hi = min(64, max_seq - max_new + 1)
        if plen_hi <= 8:
            raise ValueError(
                f"TDDL_BENCH_SERVE_SEQ={max_seq} leaves no room for "
                f"prompts >= 8 tokens at TDDL_BENCH_SERVE_NEW={max_new}"
            )
        for _ in range(n_requests):
            t_arrive += rng.exponential(1.0 / rate)
            plen = int(rng.integers(8, plen_hi))
            workload.append((t_arrive, ServeRequest(
                prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                max_new_tokens=int(rng.integers(min(4, max_new),
                                                max_new + 1)),
                temperature=0.8,
            )))
        shed = _drive_serve_open_loop(engine, workload)
        row = _serve_sweep_row(engine, watcher, rate, shed)
        log(f"serve offered={rate:6.1f} req/s: "
            f"{row['tokens_per_s']:8.1f} tok/s, ITL p50 "
            f"{row['itl_p50_ms']:.2f} ms / p99 {row['itl_p99_ms']:.2f} ms, "
            f"TTFT p50 {row['ttft_p50_ms']:.1f} ms, shed {shed}")
        records.append(row)
    return records


def bench_spec() -> "dict":
    """Speculative-decode A/B (TDDL_BENCH_SPEC=1, riding
    TDDL_BENCH_SERVE=1): the SAME seeded open-loop workload through a
    spec-off arm and spec_k ∈ {2, 4} arms of the paged engine.  The off
    arm's row is built by the exact same helpers as the offered-load
    sweep — today's serve record shape, key for key — so the contract
    test can pin that enabling spec never mutates the baseline record;
    the spec arms add a "spec" block: accepted_rate (drafted tokens the
    model-dtype verify kept), draft/verify tick fractions, near-tie
    flips, and the end-to-end tokens/s already in the shared row.
    Greedy workload: acceptance is then the pure int8-draft-vs-target
    agreement the sentinel fingerprint tracks."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.obs.slo import SLOWatcher, default_serve_rules
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_SERVE_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    max_slots = int(os.environ.get("TDDL_BENCH_SPEC_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_SPEC_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_SPEC_REQUESTS", "16"))
    max_new = int(os.environ.get("TDDL_BENCH_SPEC_NEW", "32"))
    rate = float(os.environ.get("TDDL_BENCH_SPEC_RATE", "64"))
    ks = [int(x) for x in os.environ.get("TDDL_BENCH_SPEC_KS",
                                         "2,4").split(",")]
    plen_hi = min(64, max_seq - max_new + 1)
    if plen_hi <= 8:
        raise ValueError(
            f"TDDL_BENCH_SPEC_SEQ={max_seq} leaves no room for prompts "
            f">= 8 tokens at TDDL_BENCH_SPEC_NEW={max_new}"
        )

    def build_workload():
        # Re-seeded per arm: every arm serves an IDENTICAL request
        # sequence, so tokens/s differences are the spec tier's alone.
        rng = np.random.default_rng(17)
        workload = []
        t_arrive = 0.0
        for _ in range(n_requests):
            t_arrive += rng.exponential(1.0 / rate)
            plen = int(rng.integers(8, plen_hi))
            workload.append((t_arrive, ServeRequest(
                prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                max_new_tokens=int(rng.integers(min(4, max_new),
                                                max_new + 1)),
                temperature=0.0,
            )))
        return workload

    record: dict = {"arms": {}, "offered_rps": rate}
    for label, spec_k in [("off", 0)] + [(f"k{k}", k) for k in ks]:
        watcher = SLOWatcher(default_serve_rules())
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), slo=watcher,
                               spec_k=spec_k)
        shed = _drive_serve_open_loop(engine, build_workload())
        row = _serve_sweep_row(engine, watcher, rate, shed)
        if spec_k:
            summary = engine.metrics_summary()
            phases = summary["tick_phases"]
            draft_s = phases["serve.spec_draft"]["seconds"]
            verify_s = phases["serve.spec_verify"]["seconds"]
            wall = max(draft_s + verify_s, 1e-9)
            row["spec"] = {
                "spec_k": spec_k,
                "proposed": summary["spec_proposed"],
                "accepted": summary["spec_accepted"],
                "accepted_rate": summary["accepted_rate"],
                "near_tie_flips": summary["spec_near_tie_flips"],
                "spec_ticks": summary["spec_ticks"],
                "fallback_ticks": summary["spec_fallback_ticks"],
                # Fractions of the spec-phase wall (host-observed; the
                # draft chain syncs at its token pull, the verify at
                # the packed pull) — where a tick's time actually goes.
                "draft_frac": round(draft_s / wall, 4),
                "verify_frac": round(verify_s / wall, 4),
            }
            log(f"spec k={spec_k}: {row['tokens_per_s']:8.1f} tok/s, "
                f"accepted_rate {row['spec']['accepted_rate']:.3f} "
                f"(draft {row['spec']['draft_frac']:.0%} / verify "
                f"{row['spec']['verify_frac']:.0%} of spec time)")
        else:
            log(f"spec off:  {row['tokens_per_s']:8.1f} tok/s (baseline)")
        record["arms"][label] = row
    best = f"k{max(ks)}"
    record["accepted_rate"] = \
        record["arms"][best]["spec"]["accepted_rate"]
    off_tps = record["arms"]["off"]["tokens_per_s"]
    record["tokens_per_s_ratio"] = round(
        record["arms"][best]["tokens_per_s"] / max(off_tps, 1e-9), 3)
    return record


def bench_paged_attn() -> "dict":
    """Paged-attention kernel-tier A/B (TDDL_BENCH_PAGED_ATTN=1, riding
    TDDL_BENCH_SERVE=1): the SAME seeded open-loop workload through a
    kernel-on arm (``attn_impl="pallas"`` — the ragged Pallas
    paged-decode attention + fused trust epilogue) and the jnp-fallback
    arm (``attn_impl="jnp"`` — today's gather path), both rows in the
    shared serve record shape (tokens/s, latency percentiles, SLO block,
    decode_tick_fraction + attn_kernel_path).  Two more A/B pairs cover
    the rest of the tier over the same workload: a chunked-prefill pair
    (``prefill_chunk`` on — the flash chunk program vs the gathered
    view; ``prefill_chunk_fraction``) and a speculative-verify pair
    (``spec_k`` on — the fused verify tail vs materialise-then-reduce;
    ``spec_verify_fraction``), each fraction joining the sentinel
    fingerprint direction lower.  On top it microbenches
    the output monitor's per-token reductions standalone — the jnp
    log_softmax/exp/top-k battery vs the single-pass trust epilogue over
    decode-shaped [slots, vocab] logits — so the "trust monitoring is
    literally free" claim has its own number (``monitor_cost_delta_us``
    per tick).

    HONEST SKIP: compiled Mosaic cannot dispatch on a non-TPU backend
    (interpret mode measures the Pallas interpreter, not the kernel), so
    off-TPU this returns a skip record with the reason — unless
    TDDL_BENCH_PAGED_ATTN_INTERPRET=1, the record-shape smoke knob the
    contract test uses (its numbers are interpreter wall time, never a
    perf claim).  An untileable pool geometry (int8 KV with block_size
    not a multiple of 32, f32 not a multiple of 8) skips the same way.

    Env: TDDL_BENCH_SERVE_MODEL (gpt2), TDDL_BENCH_PAGED_ATTN_SLOTS (4),
    TDDL_BENCH_PAGED_ATTN_SEQ (256), TDDL_BENCH_PAGED_ATTN_BLOCK (16),
    TDDL_BENCH_PAGED_ATTN_REQUESTS (16), TDDL_BENCH_PAGED_ATTN_NEW (32),
    TDDL_BENCH_PAGED_ATTN_RATE (64), TDDL_BENCH_PAGED_ATTN_CHUNK
    (2*block), TDDL_BENCH_PAGED_ATTN_SPEC_K (2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.obs.slo import SLOWatcher, default_serve_rules
    from trustworthy_dl_tpu.ops.paged_attention import (
        logit_trust_stats,
        supports_paged_attention,
    )
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
    from trustworthy_dl_tpu.serve.scheduler import _logit_signals

    backend = jax.default_backend()
    interpret_smoke = \
        os.environ.get("TDDL_BENCH_PAGED_ATTN_INTERPRET") == "1"
    if backend != "tpu" and not interpret_smoke:
        log(f"paged_attn A/B skipped: backend={backend} cannot dispatch "
            "compiled Mosaic (interpret mode would measure the "
            "interpreter, not the kernel)")
        return {"skipped": True,
                "reason": f"pallas_undispatchable:backend={backend}"}
    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_SERVE_MODEL", "gpt2")
    )
    max_slots = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_SEQ", "256"))
    block = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_BLOCK", "16"))
    n_requests = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_REQUESTS",
                                    "16"))
    max_new = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_NEW", "32"))
    rate = float(os.environ.get("TDDL_BENCH_PAGED_ATTN_RATE", "64"))
    kernel_impl = "interpret" if backend != "tpu" else "pallas"
    if not supports_paged_attention(
            head_dim=cfg.n_embd // cfg.n_head, block_size=block,
            kv_dtype=cfg.dtype, interpret=(kernel_impl == "interpret")):
        log(f"paged_attn A/B skipped: geometry does not tile "
            f"(head_dim={cfg.n_embd // cfg.n_head}, block_size={block})")
        return {"skipped": True,
                "reason": f"pallas_untileable:block_size={block}"}
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    plen_hi = min(64, max_seq - max_new + 1)
    if plen_hi <= 8:
        raise ValueError(
            f"TDDL_BENCH_PAGED_ATTN_SEQ={max_seq} leaves no room for "
            f"prompts >= 8 tokens at TDDL_BENCH_PAGED_ATTN_NEW={max_new}"
        )

    def build_workload():
        # Re-seeded per arm: identical request sequences, so tokens/s
        # differences are the attention path's alone.
        rng = np.random.default_rng(23)
        workload = []
        t_arrive = 0.0
        for _ in range(n_requests):
            t_arrive += rng.exponential(1.0 / rate)
            plen = int(rng.integers(8, plen_hi))
            workload.append((t_arrive, ServeRequest(
                prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                max_new_tokens=int(rng.integers(min(4, max_new),
                                                max_new + 1)),
                temperature=0.0,
            )))
        return workload

    record: dict = {"arms": {}, "offered_rps": rate,
                    "backend": backend, "block_size": block}
    streams = {}
    for label, impl in (("pallas", kernel_impl), ("jnp", "jnp")):
        watcher = SLOWatcher(default_serve_rules())
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), slo=watcher,
                               block_size=block, attn_impl=impl)
        shed = _drive_serve_open_loop(engine, build_workload())
        row = _serve_sweep_row(engine, watcher, rate, shed)
        record["arms"][label] = row
        streams[label] = {r: v.tokens
                          for r, v in engine.results.items()
                          if v.status == "completed"}
        log(f"paged_attn [{label}/{engine.attn_kernel_path}]: "
            f"{row['tokens_per_s']:8.1f} tok/s, decode-tick fraction "
            f"{row['decode_tick_fraction']:.3f}")
    # Greedy workload: the two paths must emit the same streams for the
    # A/B to mean anything (near-tie flips are possible in principle —
    # report, don't assert; the kernel tests pin equality properly).
    record["streams_identical"] = streams["pallas"] == streams["jnp"]
    record["tokens_per_s_ratio"] = round(
        record["arms"]["pallas"]["tokens_per_s"]
        / max(record["arms"]["jnp"]["tokens_per_s"], 1e-9), 3)
    # The headline the sentinel fingerprint lifts: the KERNEL arm's
    # decode-phase share of the serve wall.
    record["decode_tick_fraction"] = \
        record["arms"]["pallas"]["decode_tick_fraction"]

    # Prefill-chunk arm: the SAME seeded workload with chunked prefill
    # on, kernel tier vs jnp — the chunk program is the only prefill
    # path an adapter-carrying or prefix-resumed prompt can take, so
    # its wall share gets its own A/B and fingerprint entry.
    chunk = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_CHUNK",
                               str(2 * block)))
    record["prefill_arms"] = {}
    prefill_streams = {}
    for label, impl in (("pallas", kernel_impl), ("jnp", "jnp")):
        watcher = SLOWatcher(default_serve_rules())
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), slo=watcher,
                               block_size=block, attn_impl=impl,
                               prefill_chunk=chunk)
        shed = _drive_serve_open_loop(engine, build_workload())
        row = _serve_sweep_row(engine, watcher, rate, shed)
        row["prefill_chunk_fraction"] = round(
            engine.metrics_summary()["prefill_chunk_fraction"], 4)
        record["prefill_arms"][label] = row
        prefill_streams[label] = {r: v.tokens
                                  for r, v in engine.results.items()
                                  if v.status == "completed"}
        log(f"paged_attn prefill [{label}]: "
            f"{row['tokens_per_s']:8.1f} tok/s, prefill-chunk fraction "
            f"{row['prefill_chunk_fraction']:.3f}")
    record["prefill_streams_identical"] = \
        prefill_streams["pallas"] == prefill_streams["jnp"]
    record["prefill_tokens_per_s_ratio"] = round(
        record["prefill_arms"]["pallas"]["tokens_per_s"]
        / max(record["prefill_arms"]["jnp"]["tokens_per_s"], 1e-9), 3)
    record["prefill_chunk_fraction"] = \
        record["prefill_arms"]["pallas"]["prefill_chunk_fraction"]

    # Speculative-verify arm: drafting on (spec_k), kernel tier vs jnp
    # — the fused one-pass verify tail vs materialise-then-reduce; the
    # verify-tick wall share is the fingerprint entry.
    spec_k = int(os.environ.get("TDDL_BENCH_PAGED_ATTN_SPEC_K", "2"))
    record["verify_arms"] = {}
    verify_streams = {}
    for label, impl in (("pallas", kernel_impl), ("jnp", "jnp")):
        watcher = SLOWatcher(default_serve_rules())
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), slo=watcher,
                               block_size=block, attn_impl=impl,
                               spec_k=spec_k)
        shed = _drive_serve_open_loop(engine, build_workload())
        row = _serve_sweep_row(engine, watcher, rate, shed)
        summary = engine.metrics_summary()
        row["spec_verify_fraction"] = round(
            summary["spec_verify_fraction"], 4)
        if "accepted_rate" in summary:
            row["accepted_rate"] = round(summary["accepted_rate"], 4)
        record["verify_arms"][label] = row
        verify_streams[label] = {r: v.tokens
                                 for r, v in engine.results.items()
                                 if v.status == "completed"}
        log(f"paged_attn verify [{label}]: "
            f"{row['tokens_per_s']:8.1f} tok/s, spec-verify fraction "
            f"{row['spec_verify_fraction']:.3f}")
    record["verify_streams_identical"] = \
        verify_streams["pallas"] == verify_streams["jnp"]
    record["verify_tokens_per_s_ratio"] = round(
        record["verify_arms"]["pallas"]["tokens_per_s"]
        / max(record["verify_arms"]["jnp"]["tokens_per_s"], 1e-9), 3)
    record["spec_verify_fraction"] = \
        record["verify_arms"]["pallas"]["spec_verify_fraction"]

    # Monitor-cost microbench: the output monitor's per-token reductions
    # over decode-shaped logits, jnp battery vs fused epilogue, jitted
    # and timed standalone.  This is the "trust monitoring becomes
    # literally free" delta, per decode tick.
    logits = jax.random.normal(jax.random.PRNGKey(3),
                               (max_slots, cfg.vocab_size),
                               jnp.float32) * 4.0
    def _jnp_reductions(x):
        return _logit_signals(x, "jnp")

    def _kernel_reductions(x):
        return _logit_signals(x, kernel_impl)

    jnp_fn = jax.jit(_jnp_reductions)
    ker_fn = jax.jit(_kernel_reductions)
    timings = {}
    for name, fn in (("jnp", jnp_fn), ("kernel", ker_fn)):
        jax.block_until_ready(fn(logits))          # compile + warm
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(logits)
        jax.block_until_ready(out)
        timings[name] = (time.perf_counter() - t0) / reps * 1e6
    record["monitor_us_jnp"] = round(timings["jnp"], 2)
    record["monitor_us_kernel"] = round(timings["kernel"], 2)
    record["monitor_cost_delta_us"] = round(
        timings["jnp"] - timings["kernel"], 2)
    log(f"paged_attn monitor reductions: jnp {timings['jnp']:.1f} us vs "
        f"epilogue {timings['kernel']:.1f} us per tick "
        f"(delta {record['monitor_cost_delta_us']:.1f} us)")
    return record


def bench_fleet() -> "dict":
    """Serving-fleet leg (TDDL_BENCH_FLEET=1): goodput-under-SLO vs
    offered load, chaos OFF vs ON, over a replica fleet driven by the
    seeded workload generator (bursty arrivals, heavy-tailed lengths,
    tenant priority skew — serve/workload.py).

    Per offered rate, two arms on IDENTICAL traffic (same workload
    seed): *baseline* (no faults) and *chaos* (a seeded REPLICA_* fault
    plan: crash + stall + poison).  Goodput counts only tokens from
    requests that COMPLETED inside their deadline — the number the
    robustness layer is supposed to defend; the gap between the arms at
    each rate is the price of the injected failures after fail-over,
    drain and quarantine have done their work.  Each row also carries a
    ``per_class`` breakdown (the fleet runs the default SLO-class
    ladder, so the workload's tenant priorities map onto batch/
    standard/premium): goodput-per-class curves show WHO paid for the
    chaos — the control-plane contract is that the bottom class pays
    first.

    Env: TDDL_BENCH_FLEET_MODEL (gpt2), TDDL_BENCH_FLEET_REPLICAS (3),
    TDDL_BENCH_FLEET_SLOTS (4, per replica), TDDL_BENCH_FLEET_SEQ (256),
    TDDL_BENCH_FLEET_REQUESTS (32), TDDL_BENCH_FLEET_RATES ("4,16"),
    TDDL_BENCH_FLEET_SEED (0)."""
    import jax

    from trustworthy_dl_tpu.chaos import FaultEvent, FaultInjector, \
        FaultKind, FaultPlan
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import (
        DEFAULT_SLO_CLASSES,
        FleetConfig,
        ServeRequest,
        ServingFleet,
        WorkloadConfig,
        generate_workload,
    )
    from trustworthy_dl_tpu.serve.workload import replay_workload

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_FLEET_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    replicas = int(os.environ.get("TDDL_BENCH_FLEET_REPLICAS", "3"))
    max_slots = int(os.environ.get("TDDL_BENCH_FLEET_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_FLEET_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_FLEET_REQUESTS", "32"))
    rates = [float(r) for r in os.environ.get(
        "TDDL_BENCH_FLEET_RATES", "4,16").split(",")]
    seed = int(os.environ.get("TDDL_BENCH_FLEET_SEED", "0"))

    def fault_plan() -> FaultPlan:
        # One scripted arc per chaos arm: an early poison (flag-rate →
        # drain → quarantine), a mid-run crash (fail-over + restart) and
        # a stall (heartbeat drain) — replica indices spread so the
        # fleet is never down to zero.
        return FaultPlan.scripted([
            FaultEvent(step=4, kind=FaultKind.REPLICA_POISON,
                       target=replicas - 1),
            FaultEvent(step=8, kind=FaultKind.REPLICA_CRASH, target=0),
            FaultEvent(step=14, kind=FaultKind.REPLICA_STALL,
                       target=min(1, replicas - 1), severity=8),
        ], seed=seed)

    arms: "dict[str, list]" = {"baseline": [], "chaos": []}
    # Forensic incident counts, by reason, summed over every chaos arm
    # (the baseline arms have no faults to assemble incidents for).
    # In-memory assembler: directory=None counts without writing files.
    incident_counts: "dict[str, int]" = {}
    for rate in rates:
        workload = generate_workload(
            WorkloadConfig(seed=seed, num_requests=n_requests,
                           mean_rps=rate),
            cfg.vocab_size, max_seq,
        )
        for arm in ("baseline", "chaos"):
            chaos = (FaultInjector(fault_plan()) if arm == "chaos"
                     else None)
            forensics = None
            if chaos is not None:
                from trustworthy_dl_tpu.obs.forensics import \
                    IncidentAssembler

                forensics = IncidentAssembler()
            fleet = ServingFleet(
                params, cfg,
                # Cool-off pinned past the run: an unhealed poisoned
                # replica re-trips on every readmission probe, and this
                # sweep wants the injected faults' cost, not a
                # quarantine-probe-quarantine churn tail.
                fleet_config=FleetConfig(num_replicas=replicas,
                                         max_retries=6,
                                         quarantine_cooloff_ticks=10 ** 6,
                                         slo_classes=DEFAULT_SLO_CLASSES),
                chaos=chaos, rng=jax.random.PRNGKey(1),
                max_slots=max_slots, max_seq=max_seq,
                queue_limit=n_requests, forensics=forensics,
            )
            t0 = time.perf_counter()
            replay_workload(fleet, workload, lambda item: ServeRequest(
                prompt=list(item.prompt),
                max_new_tokens=item.max_new_tokens,
                temperature=0.8, priority=item.priority,
                deadline_s=item.deadline_s,
                tenant=item.tenant,
            ))
            wall = time.perf_counter() - t0
            summary = fleet.metrics_summary()
            statuses = summary["statuses"]
            good_tokens = summary["completed_tokens"]
            row = {
                "offered_rps": rate,
                "goodput_tokens_per_s": round(good_tokens / wall, 1)
                if wall > 0 else 0.0,
                "completed": statuses.get("completed", 0),
                "deadline_exceeded": statuses.get("deadline_exceeded", 0),
                "shed": (statuses.get("shed_slo", 0)
                         + statuses.get("no_capacity", 0)
                         + statuses.get("failover_exhausted", 0)
                         + fleet.rejected),
                "failovers": summary["fleet_failovers"],
                "drains": summary["fleet_drains"],
                "quarantines": summary["fleet_quarantines"],
                "restarts": summary["fleet_restarts"],
                "wall_s": round(wall, 2),
                # Goodput-per-class: completed requests/tokens (and the
                # per-class goodput rate) for each SLO class this arm.
                "per_class": {
                    name: {
                        "completed": cls["completed"],
                        "tokens": cls["tokens"],
                        "shed": cls["shed"],
                        "goodput_tokens_per_s":
                            round(cls["tokens"] / wall, 1)
                            if wall > 0 else 0.0,
                    }
                    for name, cls in summary["per_class"].items()
                },
            }
            arms[arm].append(row)
            if forensics is not None:
                for why, n in forensics.counts_by_reason().items():
                    incident_counts[why] = (
                        incident_counts.get(why, 0) + n)
            log(f"fleet {arm:8s} offered={rate:6.1f} req/s: "
                f"goodput {row['goodput_tokens_per_s']:8.1f} tok/s, "
                f"completed {row['completed']}/{n_requests}, "
                f"failovers {row['failovers']}, drains {row['drains']}, "
                f"quarantines {row['quarantines']}")
    return {
        "replicas": replicas,
        "max_slots_per_replica": max_slots,
        "requests_per_arm": n_requests,
        "arms": arms,
        "incidents": dict(sorted(incident_counts.items())),
    }


def bench_migrate() -> "dict":
    """Live KV-migration A/B (TDDL_BENCH_MIGRATE=1): what a capacity
    loss costs when in-flight work moves as a block copy vs replaying
    from the prompt, plus what disaggregated prefill/decode pools buy
    under a bimodal prompt mix.  Two pairs of arms, each pair on
    IDENTICAL seeded traffic:

    * **drain** — a scripted mid-run REPLICA_PREEMPT: the ``runout``
      arm pins ``FleetConfig(live_migration=False)`` (the preempted
      replica's accepted requests replay from scratch elsewhere — the
      pre-PR arc), the ``migration`` arm leaves the default on (each
      loss is a block-table copy).  The gap is recomputed tokens.
    * **disagg** — a bimodal prompt workload (short chat head + a long
      RAG tail): ``unified`` (pool_roles=None) vs ``disaggregated``
      (one prefill specialist, the rest decode — requests hand off at
      first decode token).

    The migration arm's ``migration_fraction`` (migrations over
    migrations + replay failovers) joins the sentinel fingerprint: a
    structural regression that quietly degrades losses back to replay
    bands before goodput noise shows it.

    Env: TDDL_BENCH_MIGRATE_MODEL (gpt2), TDDL_BENCH_MIGRATE_REPLICAS
    (3), TDDL_BENCH_MIGRATE_SLOTS (4), TDDL_BENCH_MIGRATE_SEQ (256),
    TDDL_BENCH_MIGRATE_REQUESTS (24), TDDL_BENCH_MIGRATE_RATE (16),
    TDDL_BENCH_MIGRATE_SEED (0), TDDL_BENCH_MIGRATE_BIMODAL (0.25),
    TDDL_BENCH_MIGRATE_LONG_MEDIAN (seq/4)."""
    import jax

    from trustworthy_dl_tpu.chaos import FaultEvent, FaultInjector, \
        FaultKind, FaultPlan
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import (
        FleetConfig,
        ServeRequest,
        ServingFleet,
        WorkloadConfig,
        generate_workload,
    )
    from trustworthy_dl_tpu.serve.workload import replay_workload

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_MIGRATE_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    replicas = int(os.environ.get("TDDL_BENCH_MIGRATE_REPLICAS", "3"))
    max_slots = int(os.environ.get("TDDL_BENCH_MIGRATE_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_MIGRATE_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_MIGRATE_REQUESTS", "24"))
    rate = float(os.environ.get("TDDL_BENCH_MIGRATE_RATE", "16"))
    seed = int(os.environ.get("TDDL_BENCH_MIGRATE_SEED", "0"))
    bimodal = float(os.environ.get("TDDL_BENCH_MIGRATE_BIMODAL", "0.25"))
    long_median = int(os.environ.get("TDDL_BENCH_MIGRATE_LONG_MEDIAN",
                                     str(max(max_seq // 4, 16))))

    def run_arm(workload, fleet_cfg, chaos):
        fleet = ServingFleet(
            params, cfg, fleet_config=fleet_cfg, chaos=chaos,
            rng=jax.random.PRNGKey(1), max_slots=max_slots,
            max_seq=max_seq, queue_limit=n_requests,
        )
        t0 = time.perf_counter()
        replay_workload(fleet, workload, lambda item: ServeRequest(
            prompt=list(item.prompt),
            max_new_tokens=item.max_new_tokens,
            temperature=0.8, priority=item.priority,
            deadline_s=item.deadline_s, tenant=item.tenant,
        ))
        wall = time.perf_counter() - t0
        summary = fleet.metrics_summary()
        statuses = summary["statuses"]
        good_tokens = summary["completed_tokens"]
        return {
            "goodput_tokens_per_s": round(good_tokens / wall, 1)
            if wall > 0 else 0.0,
            "completed": statuses.get("completed", 0),
            "deadline_exceeded": statuses.get("deadline_exceeded", 0),
            "migrations": fleet.counters["migrations"],
            "preempts": fleet.counters["preempts"],
            "failovers": summary["fleet_failovers"],
            "wall_s": round(wall, 2),
        }

    # -- drain pair: preempt mid-run, runout vs migration --------------
    drain_workload = generate_workload(
        WorkloadConfig(seed=seed, num_requests=n_requests, mean_rps=rate),
        cfg.vocab_size, max_seq,
    )

    def preempt_plan() -> FaultInjector:
        return FaultInjector(FaultPlan.scripted([
            FaultEvent(step=6, kind=FaultKind.REPLICA_PREEMPT, target=0),
        ], seed=seed))

    drain = {}
    for arm, live in (("runout", False), ("migration", True)):
        drain[arm] = run_arm(
            drain_workload,
            FleetConfig(num_replicas=replicas, max_retries=6,
                        live_migration=live),
            preempt_plan(),
        )
        log(f"migrate drain {arm:9s}: goodput "
            f"{drain[arm]['goodput_tokens_per_s']:8.1f} tok/s, "
            f"migrations {drain[arm]['migrations']}, "
            f"failovers {drain[arm]['failovers']}")

    # -- disagg pair: bimodal prompts, unified vs split pools ----------
    disagg_workload = generate_workload(
        WorkloadConfig(seed=seed, num_requests=n_requests, mean_rps=rate,
                       prompt_bimodal_frac=bimodal,
                       prompt_long_median=long_median),
        cfg.vocab_size, max_seq,
    )
    roles = ("prefill",) + ("decode",) * (replicas - 1)
    disagg = {}
    for arm, pool_roles in (("unified", None), ("disaggregated", roles)):
        disagg[arm] = run_arm(
            disagg_workload,
            FleetConfig(num_replicas=replicas, max_retries=6,
                        pool_roles=pool_roles),
            None,
        )
        log(f"migrate disagg {arm:13s}: goodput "
            f"{disagg[arm]['goodput_tokens_per_s']:8.1f} tok/s, "
            f"migrations {disagg[arm]['migrations']}")

    mig = drain["migration"]
    frac = (mig["migrations"]
            / max(mig["migrations"] + mig["failovers"], 1))
    return {
        "replicas": replicas,
        "max_slots_per_replica": max_slots,
        "requests_per_arm": n_requests,
        "bimodal_frac": bimodal,
        "prompt_long_median": long_median,
        "drain": drain,
        "disagg": disagg,
        # The headline the sentinel fingerprint lifts: the share of
        # capacity-loss recoveries that were block copies, not replays.
        "migration_fraction": round(frac, 3),
    }


def bench_shard() -> "dict":
    """Equal-chip sharded-train-state A/B (TDDL_BENCH_SHARD=1):
    replicated vs FSDP train state on the SAME chips and the same
    seeded batch.  Both arms run the identical jitted step; the FSDP
    arm turns on ``TrainingConfig.shard_params`` (+ opt-state
    sharding), so params and optimizer moments live ZeRO-sharded over
    the data axis via the core/sharding registry and GSPMD gathers per
    layer.  Reported per arm: tokens/s, the per-device HBM watermark
    (obs/hbm.py live-buffer sweep while the arm's state is still
    resident), and ``params_bytes_per_device``/``opt_bytes_per_device``
    measured from the placed shardings (core/sharding.
    tree_bytes_per_device) — bytes the registry actually returned to
    the budget, not an estimate.  The headline ``params_bytes_ratio``
    (fsdp / replicated) must sit near 1/shards.

    Env: TDDL_BENCH_SHARD_MODEL (gpt2), TDDL_BENCH_SHARD_NODES (device
    count), TDDL_BENCH_SHARD_BATCH (per-node, 4), TDDL_BENCH_SHARD_SEQ
    (256), TDDL_BENCH_SHARD_STEPS (8), TDDL_BENCH_SHARD_WARMUP (2)."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.core import sharding as shreg
    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.engine import DistributedTrainer
    from trustworthy_dl_tpu.obs.hbm import HbmMonitor

    model = os.environ.get("TDDL_BENCH_SHARD_MODEL", "gpt2")
    num_nodes = int(os.environ.get("TDDL_BENCH_SHARD_NODES",
                                   str(jax.device_count())))
    per_node_batch = int(os.environ.get("TDDL_BENCH_SHARD_BATCH", "4"))
    seq_len = int(os.environ.get("TDDL_BENCH_SHARD_SEQ", "256"))
    steps = int(os.environ.get("TDDL_BENCH_SHARD_STEPS", "8"))
    warmup = int(os.environ.get("TDDL_BENCH_SHARD_WARMUP", "2"))
    tokens_per_step = num_nodes * per_node_batch * seq_len

    def run_arm(shard: bool) -> "dict":
        config = TrainingConfig(
            model_name=model,
            dataset_name="openwebtext",
            batch_size=num_nodes * per_node_batch,
            num_nodes=num_nodes,
            learning_rate=1e-4,
            checkpoint_interval=10 ** 9,
            attack_detection_enabled=False,
            gradient_verification_enabled=False,
            parallelism="data",
            shard_params=shard,
            shard_opt_state=shard,
        )
        overrides: dict = {}
        if model.startswith("gpt"):
            overrides["seq_len"] = seq_len
            if seq_len > 1024:
                overrides["n_positions"] = seq_len
        trainer = DistributedTrainer(config, model_overrides=overrides)
        trainer.initialize()
        state = trainer.state
        batch = trainer._node_batch(jax.tree_util.tree_map(
            np.asarray,
            trainer.model.example_batch(num_nodes * per_node_batch,
                                        jax.random.PRNGKey(0)),
        ))
        plan = trainer.attack_plan
        for _ in range(max(warmup, 1)):
            state, metrics = trainer._train_step(state, batch, plan)
        jax.block_until_ready(metrics.loss)
        monitor = HbmMonitor()
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = trainer._train_step(state, batch, plan)
        jax.block_until_ready(metrics.loss)
        elapsed = time.perf_counter() - t0
        assert np.isfinite(float(metrics.loss)), "shard arm NaN loss"
        # Sweep while the arm's state is still resident — the watermark
        # is the arm's true peak, not a post-teardown floor.
        monitor.sweep()
        return {
            "tokens_per_s": round(steps * tokens_per_step / elapsed, 1)
            if elapsed > 0 else 0.0,
            "hbm_watermark_bytes": monitor.watermark_bytes,
            "params_bytes_per_device":
                shreg.tree_bytes_per_device(state.params),
            "opt_bytes_per_device":
                shreg.tree_bytes_per_device(state.opt_state),
            "final_loss": round(float(metrics.loss), 4),
        }

    arms = {}
    for name, shard in (("replicated", False), ("fsdp", True)):
        arms[name] = run_arm(shard)
        log(f"shard {name:10s}: {arms[name]['tokens_per_s']:10.1f} tok/s,"
            f" params "
            f"{arms[name]['params_bytes_per_device'] / 2 ** 20:8.1f} "
            f"MiB/dev, opt "
            f"{arms[name]['opt_bytes_per_device'] / 2 ** 20:8.1f} MiB/dev")

    repl, fsdp = arms["replicated"], arms["fsdp"]
    params_ratio = (fsdp["params_bytes_per_device"]
                    / max(repl["params_bytes_per_device"], 1))
    opt_ratio = (fsdp["opt_bytes_per_device"]
                 / max(repl["opt_bytes_per_device"], 1))
    log(f"shard ratios: params {params_ratio:.3f}, opt {opt_ratio:.3f} "
        f"(ideal {1.0 / num_nodes:.3f} over {num_nodes} shards)")
    return {
        "model": model,
        "shards": num_nodes,
        "tokens_per_step": tokens_per_step,
        "replicated": repl,
        "fsdp": fsdp,
        # The headline the A/B exists for: the per-device param bytes
        # the registry's ZeRO placement returned (ideal = 1/shards).
        "params_bytes_ratio": round(params_ratio, 4),
        "opt_bytes_ratio": round(opt_ratio, 4),
    }


def bench_adversary() -> "dict":
    """Goodput-under-attack leg (TDDL_BENCH_ADVERSARY=1): an adaptive
    poisoned replica that corrupts served streams while holding its
    public flag rate just below the quarantine threshold, measured with
    cross-replica verdict voting OFF vs ON over IDENTICAL seeded
    traffic.

    The number that matters is ``corrupted_served``: with voting off
    the sub-threshold attacker is never quarantined and keeps serving
    corrupted streams for the whole run; with voting on it is outvoted
    (``quarantines >= 1``) and the corruption stops at the verdict.
    Both arms pay the same fleet overheads, so the goodput gap is the
    audit cost of voting (replays on K clean replicas).

    The driver is CLOSED-LOOP (a saturating in-flight target over the
    seeded request list, tick-driven) rather than the open-loop
    wall-clock replay the fleet leg uses: the suspicion/vote arc needs
    the degraded suspect to keep receiving work, which only happens
    when the healthy replicas' bounded queues backpressure — a
    condition an open-loop rate only meets on a machine-specific
    service-rate knife edge.

    Env: TDDL_BENCH_ADVERSARY_MODEL (gpt2),
    TDDL_BENCH_ADVERSARY_REPLICAS (3), TDDL_BENCH_ADVERSARY_SLOTS (4),
    TDDL_BENCH_ADVERSARY_SEQ (256), TDDL_BENCH_ADVERSARY_REQUESTS (64),
    TDDL_BENCH_ADVERSARY_SEED (0), TDDL_BENCH_ADVERSARY_K (2),
    TDDL_BENCH_ADVERSARY_QUEUE (6 — kept BOUNDED so the backpressure
    above exists), TDDL_BENCH_ADVERSARY_MONITOR (margin threshold,
    14)."""
    import jax

    from trustworthy_dl_tpu.chaos import (
        AdaptivePoisonAttacker,
        AdversaryConfig,
        FaultEvent,
        FaultInjector,
        FaultKind,
        FaultPlan,
        MarginSignatureMonitor,
    )
    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import (
        FleetConfig,
        ServeRequest,
        ServingFleet,
        WorkloadConfig,
        drive_closed_loop,
        generate_workload,
    )

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_ADVERSARY_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    replicas = int(os.environ.get("TDDL_BENCH_ADVERSARY_REPLICAS", "3"))
    max_slots = int(os.environ.get("TDDL_BENCH_ADVERSARY_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_ADVERSARY_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_ADVERSARY_REQUESTS", "64"))
    seed = int(os.environ.get("TDDL_BENCH_ADVERSARY_SEED", "0"))
    vote_k = int(os.environ.get("TDDL_BENCH_ADVERSARY_K", "2"))
    queue_limit = int(os.environ.get("TDDL_BENCH_ADVERSARY_QUEUE", "6"))
    monitor_th = float(os.environ.get("TDDL_BENCH_ADVERSARY_MONITOR",
                                      "14"))
    target = replicas - 1

    workload = generate_workload(
        WorkloadConfig(seed=seed, num_requests=n_requests),
        cfg.vocab_size, max_seq,
    )
    inflight_target = replicas * (max_slots + queue_limit)
    arms: "dict[str, dict]" = {}
    for arm, k in (("voting_off", 0), ("voting_on", vote_k)):
        adversary = AdaptivePoisonAttacker(AdversaryConfig(
            target=target, seed=seed, signal_jitter=0.5,
            vocab_size=cfg.vocab_size,
            # Conservative walk: with ~max_slots requests in flight the
            # flag-rate observation LAGS the corruption, so an
            # aggressive climb overshoots into ladder territory before
            # the backoff lands — this attacker climbs gently and bails
            # early, which is exactly what keeps it sub-threshold.
            step_up=0.05, safety_margin=0.08,
        ))
        injector = FaultInjector(FaultPlan.scripted([FaultEvent(
            step=1, kind=FaultKind.REPLICA_ADAPTIVE_POISON,
            target=target,
        )], seed=seed), adversary=adversary)
        fleet = ServingFleet(
            params, cfg,
            fleet_config=FleetConfig(
                num_replicas=replicas, max_retries=6,
                flag_window=16, flag_min_count=4,
                vote_k=k, vote_outvote_limit=2,
                # Cool-off pinned past the run (same reasoning as
                # bench_fleet: measure the catch, not probe churn).
                quarantine_cooloff_ticks=10 ** 6,
            ),
            chaos=injector, rng=jax.random.PRNGKey(1),
            max_slots=max_slots, max_seq=max_seq,
            queue_limit=queue_limit,
            # Deterministic margin-threshold monitor: the attacker's
            # flag probability is then a smooth function of strength
            # (chaos/adversary.py) on both arms identically.
            monitor=MarginSignatureMonitor(monitor_th),
        )
        t0 = time.perf_counter()
        # ONE spelling of the closed-loop bounded-queue driver, shared
        # with the drills and the autoscale leg (serve/workload.py).
        drive_closed_loop(
            fleet, workload,
            lambda item: ServeRequest(
                prompt=list(item.prompt),
                max_new_tokens=item.max_new_tokens,
                temperature=0.8, priority=item.priority,
                deadline_s=item.deadline_s,
                tenant=item.tenant,
            ),
            inflight_target,
        )
        wall = time.perf_counter() - t0
        summary = fleet.metrics_summary()
        statuses = summary["statuses"]
        corrupted_served = sum(
            1 for r in fleet.results.values()
            if r.status == "completed" and r.replica == target
        )
        row = {
            "vote_k": k,
            "inflight_target": inflight_target,
            "goodput_tokens_per_s":
                round(summary["completed_tokens"] / wall, 1)
                if wall > 0 else 0.0,
            "completed": statuses.get("completed", 0),
            "corrupted_served": corrupted_served,
            "final_attacker_strength": round(adversary.strength, 4),
            "attacker_flag_rate":
                round(fleet.replicas[target].flag_rate, 4),
            "suspicions": summary["fleet_suspicions"],
            "votes": summary["fleet_votes"],
            "outvotes": summary["fleet_outvotes"],
            "drains": summary["fleet_drains"],
            "quarantines": summary["fleet_quarantines"],
            "wall_s": round(wall, 2),
        }
        arms[arm] = row
        log(f"adversary {arm:10s}: goodput "
            f"{row['goodput_tokens_per_s']:8.1f} tok/s, corrupted "
            f"served {corrupted_served}, votes {row['votes']}, "
            f"quarantines {row['quarantines']}")
    return {
        "replicas": replicas,
        "max_slots_per_replica": max_slots,
        "requests_per_arm": n_requests,
        "vote_k": vote_k,
        "arms": arms,
    }


def bench_autoscale() -> "dict":
    """Autoscale A/B (TDDL_BENCH_AUTOSCALE=1): a STATIC fleet pinned at
    ``max`` replicas vs an AUTOSCALED fleet breathing between ``min``
    and ``max``, over IDENTICAL seeded bursty traffic (the closed-loop
    bounded-queue driver — backpressure keeps the scaling decisions
    engaged deterministically).

    Reading it: the autoscaled arm's ``replica_trace`` is the replica
    count over fleet ticks (scale-ups chase the bursts, scale-downs
    drain the troughs); ``scale_ups``/``scale_downs`` count the control
    actions; both arms report goodput and the per-class breakdown, so
    the cost of breathing — goodput given up while warming — is read
    directly against the static fleet's always-on capacity.

    Env: TDDL_BENCH_AUTOSCALE_MODEL (gpt2),
    TDDL_BENCH_AUTOSCALE_MIN (1), TDDL_BENCH_AUTOSCALE_MAX (3),
    TDDL_BENCH_AUTOSCALE_SLOTS (4), TDDL_BENCH_AUTOSCALE_SEQ (256),
    TDDL_BENCH_AUTOSCALE_REQUESTS (48), TDDL_BENCH_AUTOSCALE_SEED (0),
    TDDL_BENCH_AUTOSCALE_INFLIGHT (default 3x slots)."""
    import jax

    from trustworthy_dl_tpu.serve import (
        DEFAULT_SLO_CLASSES,
        AutoscalerConfig,
        FleetConfig,
        ServeRequest,
        ServingFleet,
        WorkloadConfig,
        drive_closed_loop,
        generate_workload,
    )
    from trustworthy_dl_tpu.models import gpt2

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_AUTOSCALE_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    n_min = int(os.environ.get("TDDL_BENCH_AUTOSCALE_MIN", "1"))
    n_max = int(os.environ.get("TDDL_BENCH_AUTOSCALE_MAX", "3"))
    max_slots = int(os.environ.get("TDDL_BENCH_AUTOSCALE_SLOTS", "4"))
    max_seq = int(os.environ.get("TDDL_BENCH_AUTOSCALE_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_AUTOSCALE_REQUESTS",
                                    "48"))
    seed = int(os.environ.get("TDDL_BENCH_AUTOSCALE_SEED", "0"))
    inflight = int(os.environ.get("TDDL_BENCH_AUTOSCALE_INFLIGHT",
                                  str(3 * max_slots)))

    workload = generate_workload(
        WorkloadConfig(seed=seed, num_requests=n_requests,
                       burstiness=0.8),
        cfg.vocab_size, max_seq,
    )
    arms: "dict[str, dict]" = {}
    for arm in ("static", "autoscaled"):
        autoscale = None
        if arm == "autoscaled":
            autoscale = AutoscalerConfig(
                min_replicas=n_min, max_replicas=n_max,
                scale_up_queue_per_replica=float(max_slots),
                scale_down_queue_per_replica=max(max_slots / 8.0, 0.5),
                scale_up_cooldown_ticks=8,
                scale_down_cooldown_ticks=16,
                scale_down_idle_ticks=8,
            )
        fleet = ServingFleet(
            params, cfg,
            fleet_config=FleetConfig(
                num_replicas=(n_max if arm == "static" else n_min),
                max_retries=6,
                quarantine_cooloff_ticks=10 ** 6,
                slo_classes=DEFAULT_SLO_CLASSES,
                autoscale=autoscale,
            ),
            rng=jax.random.PRNGKey(1),
            max_slots=max_slots, max_seq=max_seq,
            queue_limit=n_requests,
        )
        t0 = time.perf_counter()
        accepted = drive_closed_loop(
            fleet, workload,
            lambda item: ServeRequest(
                prompt=list(item.prompt),
                max_new_tokens=item.max_new_tokens,
                temperature=0.8, priority=item.priority,
                deadline_s=item.deadline_s, tenant=item.tenant,
            ),
            inflight,
        )
        # Let a trailing scale-down land before reading the trace: the
        # drive exits at drain, the controller breathes a beat later.
        for _ in range(64):
            fleet.step()
        wall = time.perf_counter() - t0
        summary = fleet.metrics_summary()
        statuses = summary["statuses"]
        row = {
            "accepted": accepted,
            "completed": statuses.get("completed", 0),
            "goodput_tokens_per_s":
                round(summary["completed_tokens"] / wall, 1)
                if wall > 0 else 0.0,
            "scale_ups": summary["fleet_scale_ups"],
            "scale_downs": summary["fleet_scale_downs"],
            "replica_trace": summary.get(
                "replica_trace",
                [(0, n_max if arm == "static" else n_min)]),
            "per_class": {
                name: {
                    "completed": cls["completed"],
                    "tokens": cls["tokens"],
                    "shed": cls["shed"],
                    "goodput_tokens_per_s":
                        round(cls["tokens"] / wall, 1)
                        if wall > 0 else 0.0,
                }
                for name, cls in summary["per_class"].items()
            },
            "wall_s": round(wall, 2),
        }
        arms[arm] = row
        log(f"autoscale {arm:10s}: goodput "
            f"{row['goodput_tokens_per_s']:8.1f} tok/s, completed "
            f"{row['completed']}/{n_requests}, scale_ups "
            f"{row['scale_ups']}, scale_downs {row['scale_downs']}")
    return {
        "replicas_min": n_min,
        "replicas_max": n_max,
        "max_slots_per_replica": max_slots,
        "requests_per_arm": n_requests,
        "inflight_target": inflight,
        "arms": arms,
    }


def bench_chaos() -> "list[dict]":
    """Survival sweep (TDDL_BENCH_CHAOS=1): seeded chaos fault plans
    driven through the self-healing supervisor on a tiny GPT-2, one row
    per seed — survived?, rollbacks/retries/restarts, recovered final
    loss vs the fault-free baseline on the same data.

    Env: TDDL_BENCH_CHAOS_SEEDS ("0,1,2"), TDDL_BENCH_CHAOS_EPOCHS (3),
    TDDL_BENCH_CHAOS_RATE (0.04)."""
    import shutil
    import tempfile

    import numpy as np

    from trustworthy_dl_tpu import (
        DistributedTrainer,
        TrainingConfig,
        TrainingSupervisor,
        get_dataloader,
    )
    from trustworthy_dl_tpu.chaos import FaultInjector, FaultKind, FaultPlan

    seeds = [int(s) for s in os.environ.get(
        "TDDL_BENCH_CHAOS_SEEDS", "0,1,2").split(",")]
    epochs = int(os.environ.get("TDDL_BENCH_CHAOS_EPOCHS", "3"))
    rate = float(os.environ.get("TDDL_BENCH_CHAOS_RATE", "0.04"))
    tiny = dict(n_layer=2, n_embd=64, n_head=4, vocab_size=512,
                n_positions=64, seq_len=32)
    ckpt_dir = tempfile.mkdtemp(prefix="tddl_bench_chaos_")
    config = TrainingConfig(
        model_name="gpt2", dataset_name="openwebtext", batch_size=16,
        num_nodes=4, learning_rate=3e-3, detector_warmup=4,
        checkpoint_interval=5, checkpoint_dir=ckpt_dir, num_epochs=epochs,
        # FaultPlan.predict's retry/rollback arithmetic assumes the
        # synchronous step guard; the async pipeline's lagged guard skips
        # in-place retries (engine/async_host.py).
        async_host_depth=0,
    )
    trainer = DistributedTrainer(config, model_overrides=tiny)
    dl = get_dataloader("openwebtext", batch_size=16, seq_len=32,
                        vocab_size=512, num_examples=128)
    steps_per_epoch = 128 // 16
    horizon = steps_per_epoch * epochs

    trainer.initialize()
    base = trainer.train(dl, num_epochs=epochs)
    base_loss = base["epochs"][-1]["train_loss"]
    log(f"chaos baseline (fault-free): final loss {base_loss:.4f} "
        f"({horizon} steps)")

    rows = []
    for seed in seeds:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        trainer.reset_for_run()
        plan = FaultPlan.generate(seed, horizon, {
            FaultKind.GRAD_NAN: rate,
            FaultKind.DATA_LOSS: rate,
            FaultKind.STALL: rate / 2,
            FaultKind.PREEMPT: rate / 2,
            FaultKind.CKPT_CRASH: rate / 2,
            FaultKind.CKPT_CORRUPT: rate / 2,
        }, severity=0.05)
        injector = FaultInjector(plan)
        supervisor = TrainingSupervisor(
            trainer, max_retries=1, rollback_after=2,
            max_restarts=plan.count(FaultKind.PREEMPT) + 1,
            chaos=injector,
        )
        row = {"seed": seed, "faults_planned": len(plan.events)}
        try:
            res = supervisor.run(dl, num_epochs=epochs)
            rep = res["supervisor"]
            final = res["epochs"][-1]["train_loss"]
            row.update(
                survived=True,
                final_loss=round(final, 4),
                baseline_loss=round(base_loss, 4),
                loss_gap=round(final - base_loss, 4),
                rollbacks=rep["rollbacks"], retries=rep["retries"],
                restarts=rep["restarts"],
                faults_fired=rep.get("faults_fired", {}),
            )
        except Exception as exc:  # survival is the metric, not a crash
            row.update(survived=False,
                       error=f"{type(exc).__name__}: {str(exc)[:120]}")
        log(f"chaos seed {seed}: {row}")
        rows.append(row)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return rows


def bench_async() -> "dict | None":
    """Async host-pipeline A/B (TDDL_BENCH_ASYNC=1): the REAL trainer host
    loop (``train_epoch``) at ``async_host_depth=0`` (every step blocks on
    the host pulls) vs the config default (bounded in-flight dispatch,
    lagged host drain) — tokens/sec and the obs phase shares per arm, so
    the record shows the blocked-on-host time collapsing.  LM-only (the
    headline row); one trainer is built and the arms share its compiled
    step via ``reset_for_run``.

    Env: TDDL_BENCH_ASYNC_STEPS (measured steps per arm; default
    TDDL_BENCH_STEPS), plus the usual TDDL_BENCH_MODEL/NODES/BATCH/SEQ
    shape overrides."""
    import dataclasses

    import jax

    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.data import get_dataloader
    from trustworthy_dl_tpu.obs import ObsSession

    model = os.environ.get("TDDL_BENCH_MODEL", "gpt2")
    if not model.startswith("gpt"):
        log("async A/B skipped: defined for the LM headline row "
            f"(TDDL_BENCH_MODEL={model})")
        return None
    num_nodes = int(os.environ.get("TDDL_BENCH_NODES", "4"))
    per_node_batch = int(os.environ.get("TDDL_BENCH_BATCH", "16"))
    seq_len = int(os.environ.get("TDDL_BENCH_SEQ", "512"))
    steps = int(os.environ.get(
        "TDDL_BENCH_ASYNC_STEPS", os.environ.get("TDDL_BENCH_STEPS", "20")))
    n_chips = jax.device_count()
    batch_size = num_nodes * per_node_batch
    tokens_per_step = batch_size * seq_len
    default_depth = TrainingConfig().async_host_depth

    trainer, _, _ = _build_bench_trainer(True, model, num_nodes,
                                         per_node_batch, seq_len)
    vocab = trainer.model.config.vocab_size
    warm_dl = get_dataloader("openwebtext", batch_size=batch_size,
                             seq_len=seq_len, vocab_size=vocab,
                             num_examples=batch_size * 3)
    dl = get_dataloader("openwebtext", batch_size=batch_size,
                        seq_len=seq_len, vocab_size=vocab,
                        num_examples=batch_size * steps)

    arms = {}
    for label, depth in (("sync", 0), ("async", default_depth)):
        trainer.config = dataclasses.replace(trainer.config,
                                             async_host_depth=depth)
        trainer.reset_for_run()
        trainer.attach_obs(ObsSession(None))  # warmup arm — discarded
        trainer.train_epoch(warm_dl, 0)
        session = ObsSession(None)
        trainer.attach_obs(session)
        t0 = time.perf_counter()
        trainer.train_epoch(dl, 1)
        elapsed = time.perf_counter() - t0
        phases = session.step_timer.report().get("phases", {})
        arms[label] = {
            "async_host_depth": depth,
            "tokens_per_s_per_chip": round(
                steps * tokens_per_step / elapsed / n_chips, 1),
            "steps_per_s": round(steps / elapsed, 3),
            "phase_fractions": {
                name: round(stats["fraction"], 4)
                for name, stats in phases.items()
            },
        }
        log(f"async A/B [{label} depth={depth}]: "
            f"{arms[label]['steps_per_s']:.3f} steps/s, phases "
            f"{arms[label]['phase_fractions']}")
    speedup = (arms["async"]["tokens_per_s_per_chip"]
               / max(arms["sync"]["tokens_per_s_per_chip"], 1e-9))
    arms["speedup"] = round(speedup, 4)
    log(f"async A/B speedup (depth {default_depth} vs 0): {speedup:.4f}x")
    return arms


def bench_quant() -> "dict | None":
    """int8 quantization A/B (TDDL_BENCH_QUANT=1): serving throughput at
    an EQUAL HBM BUDGET — the budget is what the baseline (model-dtype)
    KV pool of TDDL_BENCH_QUANT_SLOTS slots costs; the int8 arm admits
    ``floor(budget / bytes_per_slot_int8)`` slots (>= 1.5x at GPT-2 head
    dims: 2*(Dh+4) int8+scale bytes vs 2*2*Dh bf16 bytes per cached
    position).  Both arms drain the same seeded closed-loop workload;
    the record reports slots, KV bytes and tokens/s per arm plus the
    slot and throughput ratios.  TDDL_BENCH_QUANT_W8=1 additionally
    puts weight-only int8 under the quantized arm (off by default so
    the A/B isolates the KV tier).

    Env: TDDL_BENCH_QUANT_MODEL (gpt2), TDDL_BENCH_QUANT_SLOTS (8),
    TDDL_BENCH_QUANT_SEQ (256), TDDL_BENCH_QUANT_REQUESTS (32),
    TDDL_BENCH_QUANT_NEW (32)."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import (
        ServeRequest,
        ServingEngine,
        kv_bytes_per_token,
    )

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_QUANT_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    base_slots = int(os.environ.get("TDDL_BENCH_QUANT_SLOTS", "8"))
    max_seq = int(os.environ.get("TDDL_BENCH_QUANT_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_QUANT_REQUESTS", "32"))
    max_new = int(os.environ.get("TDDL_BENCH_QUANT_NEW", "32"))
    w8 = os.environ.get("TDDL_BENCH_QUANT_W8") == "1"

    import jax.numpy as jnp

    budget = base_slots * max_seq * kv_bytes_per_token(cfg)
    int8_slots = budget // (max_seq * kv_bytes_per_token(cfg, jnp.int8))
    plen_hi = min(64, max_seq - max_new + 1)
    if plen_hi <= 8:
        raise ValueError(
            f"TDDL_BENCH_QUANT_SEQ={max_seq} leaves no room for prompts "
            f">= 8 tokens at TDDL_BENCH_QUANT_NEW={max_new}"
        )

    def workload(rng):
        out = []
        for _ in range(n_requests):
            plen = int(rng.integers(8, plen_hi))
            out.append(ServeRequest(
                prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                max_new_tokens=int(rng.integers(min(4, max_new),
                                                max_new + 1)),
                temperature=0.0,
            ))
        return out

    record = {"budget_bytes": int(budget), "arms": {}}
    arm_defs = (
        ("base", dict(max_slots=base_slots)),
        ("int8", dict(max_slots=int(int8_slots), kv_dtype="int8",
                      weight_dtype="int8" if w8 else "model")),
    )
    for label, kw in arm_defs:
        engine = ServingEngine(params, cfg, max_seq=max_seq,
                               queue_limit=n_requests,
                               rng=jax.random.PRNGKey(1), **kw)
        reqs = workload(np.random.default_rng(0))
        t0 = time.perf_counter()
        for req in reqs:
            engine.submit(req)
        engine.run_until_idle()
        elapsed = time.perf_counter() - t0
        summary = engine.metrics_summary()
        record["arms"][label] = {
            "slots": engine.scheduler.allocator.max_slots,
            "kv_bytes": int(engine.scheduler.kv.pool_bytes),
            "kv_dtype": engine.kv_dtype,
            "weight_dtype": engine.weight_dtype,
            "kv_fallback": engine.kv_fallback_reason,
            "tokens_per_s": round(summary["tokens_per_s"], 1),
            "completed": summary["requests_completed"],
            "wall_s": round(elapsed, 3),
        }
        log(f"quant A/B [{label}]: {record['arms'][label]['slots']} "
            f"slot(s) / {record['arms'][label]['kv_bytes'] / 1e6:.1f} MB "
            f"KV, {record['arms'][label]['tokens_per_s']:.1f} tok/s "
            f"({record['arms'][label]['completed']} completed)")
    base, quant = record["arms"]["base"], record["arms"]["int8"]
    record["slots_ratio"] = round(quant["slots"] / base["slots"], 3)
    record["tokens_per_s_ratio"] = round(
        quant["tokens_per_s"] / max(base["tokens_per_s"], 1e-9), 3)
    log(f"quant A/B: {record['slots_ratio']}x slots at equal HBM "
        f"budget ({budget / 1e6:.1f} MB), "
        f"{record['tokens_per_s_ratio']}x tokens/s")
    return record


def bench_adapters() -> "dict | None":
    """Paged adapter-pool A/B (TDDL_BENCH_ADAPTERS=1): multi-tenant
    serving throughput at an EQUAL HBM BUDGET — the budget is what the
    adapter-OFF arm's paged KV pool costs; the adapter arm carves its
    low-rank pool (serve/adapters.py) out of that SAME budget, giving
    back KV blocks block-for-block, so the row answers the deployment
    question: what does per-tenant personalisation cost at fixed HBM?
    Both arms drain an IDENTICAL seeded Zipf multi-tenant workload
    (``zipf_adapter_assignments`` — a hot adapter head + a long tail, so
    pool pages << adapters forces real LRU eviction traffic).  The
    record reports tokens/s per arm plus the pool's hit rate, eviction
    and upload counts; hit rate and the tokens/s ratio ride the perf
    sentinel fingerprint so pool-locality regressions band-check (and
    page) like throughput regressions.

    Env: TDDL_BENCH_ADAPTERS_MODEL (gpt2), TDDL_BENCH_ADAPTERS_SLOTS
    (8), TDDL_BENCH_ADAPTERS_SEQ (256), TDDL_BENCH_ADAPTERS_REQUESTS
    (48), TDDL_BENCH_ADAPTERS_NEW (16), TDDL_BENCH_ADAPTERS_RANK (8),
    TDDL_BENCH_ADAPTERS_PAGES (4), TDDL_BENCH_ADAPTERS_TENANTS (12),
    TDDL_BENCH_ADAPTERS_COUNT (8, distinct adapters),
    TDDL_BENCH_ADAPTERS_DTYPE (model|int8)."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
    from trustworthy_dl_tpu.serve.adapters import adapter_pool_bytes
    from trustworthy_dl_tpu.serve.workload import (
        WorkloadConfig,
        generate_workload,
        make_tenant_population,
        zipf_adapter_assignments,
    )

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_ADAPTERS_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    max_slots = int(os.environ.get("TDDL_BENCH_ADAPTERS_SLOTS", "8"))
    max_seq = int(os.environ.get("TDDL_BENCH_ADAPTERS_SEQ", "256"))
    n_requests = int(os.environ.get("TDDL_BENCH_ADAPTERS_REQUESTS", "48"))
    max_new = int(os.environ.get("TDDL_BENCH_ADAPTERS_NEW", "16"))
    rank = int(os.environ.get("TDDL_BENCH_ADAPTERS_RANK", "8"))
    pages = int(os.environ.get("TDDL_BENCH_ADAPTERS_PAGES", "4"))
    n_tenants = int(os.environ.get("TDDL_BENCH_ADAPTERS_TENANTS", "12"))
    n_adapters = int(os.environ.get("TDDL_BENCH_ADAPTERS_COUNT", "8"))
    adapter_dtype = os.environ.get("TDDL_BENCH_ADAPTERS_DTYPE", "model")

    tenants = make_tenant_population(n_tenants)
    adapter_map = zipf_adapter_assignments(
        [t.name for t in tenants], n_adapters, seed=0)
    wl = generate_workload(
        WorkloadConfig(seed=0, num_requests=n_requests,
                       output_median=max_new // 2 or 1,
                       max_output=max_new, tenants=tenants),
        vocab_size=cfg.vocab_size, max_seq=max_seq)

    block_size = 16
    base_blocks = max_slots * (max_seq // block_size)

    def run_arm(label, num_blocks, **kw):
        engine = ServingEngine(params, cfg, max_slots=max_slots,
                               max_seq=max_seq, queue_limit=n_requests,
                               block_size=block_size,
                               num_blocks=num_blocks,
                               rng=jax.random.PRNGKey(1), **kw)
        t0 = time.perf_counter()
        for item in wl:
            engine.submit(ServeRequest(
                prompt=list(item.prompt),
                max_new_tokens=item.max_new_tokens,
                temperature=0.0, tenant=item.tenant))
        engine.run_until_idle()
        elapsed = time.perf_counter() - t0
        summary = engine.metrics_summary()
        row = {
            "blocks": num_blocks,
            "kv_bytes": int(engine.scheduler.kv.pool_bytes),
            "tokens_per_s": round(summary["tokens_per_s"], 1),
            "completed": summary["requests_completed"],
            "wall_s": round(elapsed, 3),
        }
        if "adapters" in summary:
            row["adapters"] = summary["adapters"]
        log(f"adapters A/B [{label}]: {num_blocks} block(s), "
            f"{row['tokens_per_s']:.1f} tok/s "
            f"({row['completed']} completed)")
        return engine, row

    record = {"arms": {}, "rank": rank, "pages": pages,
              "adapter_dtype": adapter_dtype,
              "tenants": n_tenants, "adapters": n_adapters}
    engine, row = run_arm("off", base_blocks)
    record["budget_bytes"] = int(engine.scheduler.kv.pool_bytes)
    bpb = engine.scheduler.kv.bytes_per_block
    record["arms"]["off"] = row
    pool_bytes = adapter_pool_bytes(cfg, pages, rank, adapter_dtype)
    give_back = -(-int(pool_bytes) // bpb)   # ceil: the pool pays in full
    on_blocks = base_blocks - give_back
    if on_blocks < max_slots:
        raise ValueError(
            f"TDDL_BENCH_ADAPTERS_PAGES={pages} at rank {rank} costs "
            f"{give_back} of {base_blocks} KV blocks — under one block "
            f"per slot; shrink the pool or the rank")
    _, row = run_arm("on", on_blocks, adapter_rank=rank,
                     adapter_pool_pages=pages,
                     adapter_dtype=adapter_dtype,
                     adapter_map=adapter_map)
    record["arms"]["on"] = row
    record["adapter_pool_bytes"] = int(pool_bytes)
    pool = row["adapters"]
    record["hit_rate"] = round(pool["hit_rate"], 4)
    record["evictions"] = pool["evictions"]
    record["uploads"] = pool["uploads"]
    record["tokens_per_s_ratio"] = round(
        row["tokens_per_s"]
        / max(record["arms"]["off"]["tokens_per_s"], 1e-9), 3)
    log(f"adapters A/B: {record['tokens_per_s_ratio']}x tokens/s at "
        f"equal HBM ({record['budget_bytes'] / 1e6:.1f} MB; pool "
        f"{pool_bytes / 1e6:.2f} MB = {give_back} blocks), hit rate "
        f"{record['hit_rate']}, {record['evictions']} eviction(s)")
    return record


def bench_generate() -> None:
    """Optional decode benchmark (TDDL_BENCH_GEN=1): KV-cache generation
    steady-state cost on the full GPT-2.  Diagnostics only — stderr.

    Every call closes with a host materialisation (np.asarray), and the
    per-call constant (dispatch + prefill, NOT a property of the decode
    program) is removed by differencing two generation lengths:
    slope = (t(N2) - t(N1)) / (N2 - N1) is the steady-state per-token
    cost.  Calls chain (output tail feeds the next prompt) so nothing
    can be served from a cache."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.models import gpt2
    from trustworthy_dl_tpu.models.generate import generate

    cfg = gpt2.GPT2Config.from_name(
        os.environ.get("TDDL_BENCH_GEN_MODEL", "gpt2")
    )
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    prompt_len = 32
    n1, n2 = 16, int(os.environ.get("TDDL_BENCH_GEN_NEW", "128"))
    if n2 <= n1:
        # TDDL_BENCH_GEN_NEW is the slope's LONG length; keep the
        # difference positive for small values instead of dividing by <=0.
        n1 = max(1, n2 // 2)
    reps = int(os.environ.get("TDDL_BENCH_GEN_REPS", "12"))

    def median_call(batch, new, **kw):
        prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, prompt_len), 0, cfg.vocab_size)
        cur = prompt
        full = generate(params, cfg, cur, new, **kw)
        np.asarray(full)  # compile + first execution
        cur = full[:, -prompt_len:]
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            full = generate(params, cfg, cur, new,
                            rng=jax.random.PRNGKey(i), **kw)
            np.asarray(full)  # host materialisation = real execution
            ts.append(time.perf_counter() - t0)
            cur = full[:, -prompt_len:]
        return float(np.median(ts))

    for batch in (1, 32):
        for name, kw in (("greedy", {}),
                         ("top_k=40", dict(temperature=0.8, top_k=40))):
            t1 = median_call(batch, n1, **kw)
            t2 = median_call(batch, n2, **kw)
            slope = (t2 - t1) / (n2 - n1)
            log(f"generate b={batch:3d} {name:9s}: "
                f"{slope * 1e3:6.3f} ms/token steady-state "
                f"({batch / slope:,.0f} tok/s; dispatch+prefill constant "
                f"{(t1 - n1 * slope) * 1e3:.0f} ms/call excluded)")


def main() -> None:
    if "--config" in sys.argv:
        idx = sys.argv.index("--config") + 1
        if idx >= len(sys.argv):
            log("usage: bench.py --config <preset>  (--config list to "
                "enumerate)")
            sys.exit(2)
        apply_preset(sys.argv[idx])

    # Static-analysis leg first: host-only and cheapest.
    lint_record = bench_lint()
    if lint_record is not None:
        log(f"lint: rc {lint_record['rc']} over "
            f"{lint_record['files_scanned']} files "
            f"({len(lint_record['findings'])} finding(s), "
            f"{lint_record['baselined']} baselined)")
        if lint_record["rc"] != 0:
            print(json.dumps({"metric": "lint_findings",
                              "lint": lint_record}))
            sys.exit(4)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    n_chips = len(devices)
    if platform != "tpu":
        # A device metric from a CPU run is an error, not a record.
        log(f"bench.py measures the chip: found {n_chips} {platform} "
            "device(s) and no TPU")
        sys.exit(1)

    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    log(f"persistent compilation cache: {configure_compile_cache()}")

    record = measure(n_chips, platform)
    if lint_record is not None:
        record["lint"] = lint_record
    print(json.dumps(record))
    # Sentinel CI arm (off by default): a confirmed regression outside
    # the ledger noise band exits non-zero AFTER the record is out.
    sys.exit(_sentinel_rc(record))


def measure(n_chips: int, platform: str) -> dict:
    """The measured bench body: the headline detection ON/OFF row plus
    whichever optional legs the environment turns on."""
    model = os.environ.get("TDDL_BENCH_MODEL", "gpt2")
    num_nodes = int(os.environ.get("TDDL_BENCH_NODES", "4"))
    per_node_batch = int(os.environ.get("TDDL_BENCH_BATCH", "16"))
    seq_len = int(os.environ.get("TDDL_BENCH_SEQ", "512"))
    steps = int(os.environ.get("TDDL_BENCH_STEPS", "20"))
    warmup = int(os.environ.get("TDDL_BENCH_WARMUP", "3"))

    # Performance observability for the whole measured body: every XLA
    # compilation metered from here on (obs/compilewatch.py), live-HBM
    # swept at the end — both land in the record's "compile"/"hbm"
    # sections with the sentinel fingerprint/verdict.
    from trustworthy_dl_tpu.obs.compilewatch import CompileRegistry
    from trustworthy_dl_tpu.obs.hbm import HbmMonitor

    compiles = CompileRegistry().install()
    hbm_monitor = HbmMonitor()

    is_lm = model.startswith("gpt")
    log(f"bench: {model} nodes={num_nodes} batch/node={per_node_batch} "
        f"seq={seq_len} steps={steps} on {n_chips} {platform} device(s)")

    # Work per step: tokens for LMs, samples for vision models.
    tokens_per_step = num_nodes * per_node_batch * (seq_len if is_lm else 1)
    unit = "tokens/sec/chip" if is_lm else "samples/sec/chip"

    # Vision steps are short, so slow throughput drift swamps the
    # sequential all-OFF-then-all-ON difference there; interleaved paired
    # blocks cancel the drift.  LM steps are long, and the sequential
    # design keeps the single-trainer memory footprint for big models.
    interleave_env = os.environ.get("TDDL_BENCH_INTERLEAVE")
    interleave = (interleave_env == "1") if interleave_env else not is_lm
    if interleave:
        # Blocks must dwarf the host-close constant of each block.
        block_steps = max(50, steps)
        sps_on, ratio, n_params = bench_overhead_interleaved(
            model, num_nodes, per_node_batch, seq_len, block_steps,
            rounds=int(os.environ.get("TDDL_BENCH_ROUNDS", "7")),
            warmup=warmup,
        )
        log(f"interleaved: detection ON {sps_on:.3f} steps/s, "
            f"median ON/OFF ratio {ratio:.4f}")
    else:
        sps_off, n_params = bench_mode(False, model, num_nodes,
                                       per_node_batch, seq_len, steps,
                                       warmup)
        log(f"detection OFF: {sps_off:.3f} steps/s "
            f"({sps_off * tokens_per_step / n_chips:,.0f} {unit})")
        sps_on, _ = bench_mode(True, model, num_nodes, per_node_batch,
                               seq_len, steps, warmup)
        log(f"detection ON:  {sps_on:.3f} steps/s "
            f"({sps_on * tokens_per_step / n_chips:,.0f} {unit})")
        ratio = sps_on / sps_off

    tps_on = sps_on * tokens_per_step / n_chips
    # Watermark sweep while the measured trainers' state is still live —
    # the optional legs below free/rebuild models, and the final sweep in
    # _attach_perf_sections would miss the training-footprint peak.
    hbm_monitor.sweep()
    overhead_pct = (1.0 - ratio) * 100.0
    log(f"detection overhead: {overhead_pct:.1f}% (target <=15%)")
    # Run-metadata stamp + MFU via the shared obs helpers — the bench
    # record carries the same metadata block every experiment artifact
    # does, and the MFU figure names its peak-FLOPs source instead of
    # leaving the roofline implicit.
    from trustworthy_dl_tpu.obs.meta import run_metadata
    from trustworthy_dl_tpu.obs.report import mfu_from_throughput

    meta = run_metadata()
    tflops = None
    mfu = None
    if is_lm:
        # Standard transformer-training estimate: ~6 FLOPs per param per
        # token (fwd 2 + bwd 4); remat adds recompute not counted here, so
        # this is a lower bound on hardware FLOPs actually executed.  (No
        # comparable param-count formula for convs, so vision skips it.)
        tflops = 6.0 * n_params * tps_on / 1e12
        mfu = mfu_from_throughput(n_params, tps_on,
                                  device_kind=meta["device_kind"])
        log(f"achieved model FLOPs: {tflops:.1f} TFLOP/s/chip "
            f"({n_params / 1e6:.0f}M params); MFU {mfu['mfu']:.3f} vs "
            f"{mfu['peak_flops_source']}")

    if os.environ.get("TDDL_BENCH_FUSED") == "1":
        # Native-tier A/B: detection ON with the Pallas fused moment battery
        # (ops/fused_stats.py) instead of XLA's fused reductions.
        os.environ["TDDL_FUSED_STATS"] = "1"
        try:
            sps_fused, _ = bench_mode(True, model, num_nodes, per_node_batch,
                                      seq_len, steps, warmup)
        finally:
            del os.environ["TDDL_FUSED_STATS"]
        log(f"detection ON (pallas fused stats): {sps_fused:.3f} steps/s "
            f"(vs {sps_on:.3f} XLA)")

    if os.environ.get("TDDL_BENCH_LONGCTX") == "1":
        bench_longctx()
    if os.environ.get("TDDL_BENCH_GEN") == "1":
        bench_generate()
    serve_records = None
    spec_record = None
    paged_attn_record = None
    if os.environ.get("TDDL_BENCH_SERVE") == "1":
        serve_records = bench_serve()
        if os.environ.get("TDDL_BENCH_SPEC") == "1":
            spec_record = bench_spec()
        if os.environ.get("TDDL_BENCH_PAGED_ATTN") == "1":
            paged_attn_record = bench_paged_attn()
    fleet_record = None
    if os.environ.get("TDDL_BENCH_FLEET") == "1":
        fleet_record = bench_fleet()
    migrate_record = None
    if os.environ.get("TDDL_BENCH_MIGRATE") == "1":
        migrate_record = bench_migrate()
    shard_record = None
    if os.environ.get("TDDL_BENCH_SHARD") == "1":
        shard_record = bench_shard()
    adversary_record = None
    if os.environ.get("TDDL_BENCH_ADVERSARY") == "1":
        adversary_record = bench_adversary()
    autoscale_record = None
    if os.environ.get("TDDL_BENCH_AUTOSCALE") == "1":
        autoscale_record = bench_autoscale()
    chaos_records = None
    if os.environ.get("TDDL_BENCH_CHAOS") == "1":
        chaos_records = bench_chaos()
    async_records = None
    if os.environ.get("TDDL_BENCH_ASYNC") == "1":
        async_records = bench_async()
    quant_records = None
    if os.environ.get("TDDL_BENCH_QUANT") == "1":
        quant_records = bench_quant()
    adapters_record = None
    if os.environ.get("TDDL_BENCH_ADAPTERS") == "1":
        adapters_record = bench_adapters()

    record = {
        "metric": f"{model}_{unit.split('/')[0]}_per_sec_per_chip"
                  "_detection_on",
        "value": round(tps_on, 1),
        "unit": unit,
        "vs_baseline": round(ratio, 4),
        "detection_overhead_pct": round(overhead_pct, 2),
        "platform": platform,
        "num_chips": n_chips,
        ("tokens_per_step" if is_lm else "samples_per_step"):
            tokens_per_step,
        "model_tflops_per_chip": round(tflops, 2) if tflops else None,
        "mfu": mfu,
        "run_metadata": meta,
    }
    if spec_record is not None:
        # Attached BEFORE the perf sections: the sentinel fingerprint
        # lifts accepted_rate from it, so draft-quality regressions
        # band-check (and page) exactly like throughput regressions.
        record["spec"] = spec_record
    if paged_attn_record is not None:
        # Same contract: the fingerprint lifts the kernel arm's
        # decode_tick_fraction, so a silent fall-back to the jnp gather
        # bands (and pages) like a perf regression.
        record["paged_attn"] = paged_attn_record
    if adapters_record is not None:
        # Same contract: the fingerprint lifts the adapter pool's hit
        # rate and the equal-HBM tokens/s ratio, so pool-locality and
        # personalisation-cost regressions band (and page) like perf.
        record["adapters"] = adapters_record
    if migrate_record is not None:
        # Same contract: the fingerprint lifts migration_fraction, so a
        # structural break that degrades capacity losses back to prompt
        # replay bands (and pages) like a perf regression.
        record["migrate"] = migrate_record
    _attach_perf_sections(record, compiles=compiles, hbm=hbm_monitor)
    if serve_records is not None:
        record["serve"] = serve_records
    if fleet_record is not None:
        record["fleet"] = fleet_record
    if shard_record is not None:
        record["shard"] = shard_record
    if adversary_record is not None:
        record["adversary"] = adversary_record
    if autoscale_record is not None:
        record["autoscale"] = autoscale_record
    if chaos_records is not None:
        record["chaos"] = chaos_records
    if async_records is not None:
        record["async"] = async_records
    if quant_records is not None:
        record["quant"] = quant_records
    obs_dir = os.environ.get("TDDL_BENCH_OBS_DIR")
    if obs_dir:
        # Attach the per-run obs report next to whatever artifact set the
        # caller is collecting (the record itself rides stdout; this is
        # the on-disk copy experiments can join against).
        os.makedirs(obs_dir, exist_ok=True)
        report_path = os.path.join(obs_dir, "obs_report.json")
        from trustworthy_dl_tpu.utils.io import atomic_write_json

        atomic_write_json(report_path, {
            "source": "bench", "run_metadata": meta, "mfu": mfu,
            "steps_per_s_detection_on": sps_on,
            "throughput": record["value"], "unit": unit})
        log(f"obs report written to {report_path}")
    return record


if __name__ == "__main__":
    main()
