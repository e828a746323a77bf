"""Console entry point ``trustworthy-dl-train`` (setup_py.py:62-64 implies
``trustworthy_dl.cli:main``; the module itself is absent from the reference
snapshot — interface reconstructed from the README usage example,
README.md:50-78, and the YAML schema at README.md:111-132).

Unlike the reference, ``--config`` actually loads the file, and flag
overrides win over file values (experiment_runner.py:605,613-623 parsed the
flag and ignored it)."""

from __future__ import annotations

import argparse
import logging
from typing import List, Optional

logging.basicConfig(
    level=logging.INFO,
    format="%(asctime)s %(name)s %(levelname)s %(message)s",
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trustworthy-dl-train",
        description="Trust-gated distributed training on TPU meshes",
    )
    parser.add_argument("--config", type=str,
                        help="YAML/JSON config (README.md:111-132 schema)")
    parser.add_argument("--model", type=str, default=None,
                        help="gpt2[-medium|-large|-xl], resnet32/50/101, "
                             "vgg11/13/16")
    parser.add_argument("--dataset", type=str, default=None)
    parser.add_argument("--nodes", type=int, default=None)
    parser.add_argument("--epochs", type=int, default=None)
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--learning-rate", type=float, default=None)
    parser.add_argument("--parallelism", type=str, default=None,
                        choices=["data", "model", "tensor", "sequence",
                                 "expert", "hybrid"])
    parser.add_argument("--checkpoint-dir", type=str, default=None)
    parser.add_argument("--resume", action="store_true",
                        help="restore the latest checkpoint before training")
    parser.add_argument("--no-detection", action="store_true",
                        help="disable the in-step attack detector")
    parser.add_argument("--steps-per-epoch", type=int, default=50,
                        help="synthetic-data epoch length")
    parser.add_argument("--async-host-depth", type=int, default=None,
                        help="steps kept in flight by the async host "
                             "pipeline (engine/async_host.py): dispatch "
                             "runs up to this many steps ahead of the "
                             "host bookkeeping, which drains lagged "
                             "through one packed device->host copy per "
                             "step; 0 = fully synchronous (config "
                             "default: 2).  Deterministic chaos drills "
                             "asserting exact retry counts need 0")
    # Self-healing supervisor (engine/supervisor.py) + chaos drills.
    parser.add_argument("--supervise", action="store_true",
                        help="wrap training in the self-healing supervisor: "
                             "non-finite step guard, bounded retries, "
                             "verified-checkpoint rollback, SIGTERM "
                             "save-on-signal + capped auto-resume")
    parser.add_argument("--max-retries", type=int, default=2,
                        help="per-step retry budget before a step counts "
                             "as bad (supervisor)")
    parser.add_argument("--rollback-after", type=int, default=3,
                        help="consecutive bad steps before rolling back to "
                             "the last verified checkpoint (supervisor)")
    parser.add_argument("--max-restarts", type=int, default=3,
                        help="auto-resume budget after preemptions "
                             "(supervisor)")
    parser.add_argument("--chaos-seed", type=int, default=None,
                        help="run under a seeded chaos fault plan "
                             "(implies --supervise): non-finite state, "
                             "stalls, lost batches, preemptions, "
                             "checkpoint corruption — chaos/plan.py")
    parser.add_argument("--chaos-rate", type=float, default=0.02,
                        help="per-step probability of each drill fault "
                             "kind under --chaos-seed")
    # Unified telemetry (trustworthy_dl_tpu/obs/).
    parser.add_argument("--obs-dir", type=str, default=None,
                        help="write run telemetry here: trace.jsonl "
                             "(structured events with step correlation "
                             "ids), metrics_snapshot.json + metrics.prom "
                             "(registry export), obs_report.json "
                             "(per-phase step-time breakdown + MFU), and "
                             "flight-recorder dumps")
    parser.add_argument("--metrics-snapshot-every", type=int, default=0,
                        help="re-write the metrics snapshot every N steps "
                             "(0 = only at run end); needs --obs-dir")
    parser.add_argument("--trace-max-bytes", type=int, default=0,
                        help="rotate trace.jsonl once it exceeds this "
                             "many bytes (trace.1.jsonl, trace.2.jsonl, "
                             "...; 0 = no rotation; env "
                             "TDDL_TRACE_MAX_BYTES is the default)")
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    from trustworthy_dl_tpu.core.config import TrainingConfig, load_config
    from trustworthy_dl_tpu.data import get_dataloader
    from trustworthy_dl_tpu.engine.trainer import DistributedTrainer
    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    args = build_parser().parse_args(argv)
    overrides = {
        k: v for k, v in {
            "model_name": args.model,
            "dataset_name": args.dataset,
            "num_nodes": args.nodes,
            "num_epochs": args.epochs,
            "batch_size": args.batch_size,
            "learning_rate": args.learning_rate,
            "parallelism": args.parallelism,
            "checkpoint_dir": args.checkpoint_dir,
            "async_host_depth": args.async_host_depth,
        }.items() if v is not None
    }
    if args.no_detection:
        overrides["attack_detection_enabled"] = False
    if args.config:
        config = load_config(args.config, **overrides)
    else:
        config = TrainingConfig(**overrides)
    configure_compile_cache()

    trainer = DistributedTrainer(config)
    trainer.initialize()
    obs_session = None
    if args.obs_dir:
        from trustworthy_dl_tpu.obs import ObsSession

        obs_session = ObsSession(
            args.obs_dir,
            metrics_snapshot_every=args.metrics_snapshot_every,
            trace_max_bytes=args.trace_max_bytes,
        )
        # Active plane: per-step spans (train.step → per-phase children)
        # and the EWMA anomaly watcher on step-time/loss/grad-norm; no
        # serving SLO rules on a training run, but the step_time_s
        # percentile sketch still lands in slo_status.json.
        obs_session.enable_spans()
        obs_session.install_watchers(slo_rules=())
        # Forensics: the supervisor's guard-trip/rollback/preemption
        # dumps each get a paired incident with the causal ladder.
        obs_session.enable_forensics()
        # Performance tier: every XLA compile metered + the train-step
        # compile-once contract enforced at runtime, live-HBM watermark
        # gauges, and the perf fingerprint appended at finalize.
        obs_session.enable_compile_watch()
        obs_session.enable_hbm()
        trainer.attach_obs(obs_session)
    if args.resume:
        trainer.load_checkpoint()

    num_examples = config.batch_size * args.steps_per_epoch
    train_dl = get_dataloader(config.dataset_name, split="train",
                              batch_size=config.batch_size,
                              num_examples=num_examples)
    val_dl = get_dataloader(config.dataset_name, split="validation",
                            batch_size=config.batch_size,
                            num_examples=max(num_examples // 10,
                                             config.batch_size))
    if args.supervise or args.chaos_seed is not None:
        from trustworthy_dl_tpu.chaos import FaultInjector, FaultKind, \
            FaultPlan
        from trustworthy_dl_tpu.engine.supervisor import TrainingSupervisor

        injector = None
        max_restarts = args.max_restarts
        if args.chaos_seed is not None:
            horizon = args.steps_per_epoch * config.num_epochs
            rate = args.chaos_rate
            plan = FaultPlan.generate(args.chaos_seed, horizon, {
                FaultKind.GRAD_NAN: rate,
                FaultKind.DATA_LOSS: rate,
                FaultKind.STALL: rate,
                FaultKind.PREEMPT: rate / 4,
                FaultKind.CKPT_CRASH: rate / 4,
                FaultKind.CKPT_CORRUPT: rate / 4,
            }, severity=0.05)
            injector = FaultInjector(plan)
            # Every planned preemption costs one restart; keep the budget
            # above the plan so the drill exercises resume, not give-up.
            max_restarts = max(max_restarts,
                               plan.count(FaultKind.PREEMPT) + 1)
            print(f"chaos drill: seed {args.chaos_seed}, "
                  f"{len(plan.events)} fault(s) over {horizon} steps")
        supervisor = TrainingSupervisor(
            trainer, max_retries=args.max_retries,
            rollback_after=args.rollback_after, max_restarts=max_restarts,
            chaos=injector, handle_signals=True, obs=obs_session,
        )
        result = supervisor.run(train_dl, val_dl)
        print(f"supervisor report: {result['supervisor']}")
    else:
        result = trainer.train(train_dl, val_dl)
    stats = result["stats"]
    print(f"Training completed: {stats['global_step']} steps, "
          f"final state {stats['training_state']}")
    trainer.save_checkpoint()
    if obs_session is not None:
        obs_session.hbm.sweep(emit=True)
        obs_session.finalize()
        print(f"obs artifacts in {args.obs_dir}: trace.jsonl, "
              "metrics_snapshot.json, metrics.prom, obs_report.json")
        _print_perf_verdict(obs_session)
    trainer.cleanup()
    return 0


def _print_perf_verdict(obs_session) -> None:
    """One-line sentinel summary at the end of an instrumented run."""
    verdict = obs_session.perf_verdict
    if verdict is None:
        return
    if verdict["regressed"]:
        bad = [f"{c['metric']} {c.get('delta_pct', 0):+.1f}%"
               for c in verdict["checks"] if c.get("regressed")]
        print(f"perf sentinel: REGRESSION vs {verdict['baseline_n']} "
              f"baseline run(s): {', '.join(bad)}")
    else:
        print(f"perf sentinel: within the noise band "
              f"({verdict['baseline_n']} baseline run(s), ledger "
              f"{obs_session.perf_ledger_path})")


def build_generate_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trustworthy-dl-generate",
        description="Sample from a trained GPT-2 checkpoint with the "
                    "KV-cache decoder (beyond-reference; the reference "
                    "trains GPT-2 but cannot sample from it)",
    )
    parser.add_argument("--model", type=str, default="gpt2")
    parser.add_argument("--checkpoint-dir", type=str, default="checkpoints",
                        help="restore the latest checkpoint from here "
                             "(falls back to fresh init with a warning)")
    parser.add_argument("--prompt", type=str, default="1,2,3,4",
                        help="comma-separated token ids")
    parser.add_argument("--prompt-text", type=str, default=None,
                        help="raw text prompt; needs --tokenizer-dir "
                             "(output is decoded back to text)")
    parser.add_argument("--tokenizer-dir", type=str, default=None,
                        help="vocab.json + merges.txt directory "
                             "(trustworthy-dl-prepare-data writes one)")
    parser.add_argument("--max-new-tokens", type=int, default=32)
    parser.add_argument("--temperature", type=float, default=0.8)
    parser.add_argument("--top-k", type=int, default=40)
    parser.add_argument("--top-p", type=float, default=1.0)
    parser.add_argument("--seed", type=int, default=0)
    return parser


def generate_main(argv: Optional[List[str]] = None,
                  model_overrides: Optional[dict] = None) -> int:
    """Console entry point ``trustworthy-dl-generate``.

    ``model_overrides`` is an internal hook (tests shrink the model with
    it); the CLI surface restores whatever the checkpoint was trained as.
    """
    import jax
    import jax.numpy as jnp

    from trustworthy_dl_tpu.core.config import TrainingConfig
    from trustworthy_dl_tpu.engine.checkpoint import CheckpointManager
    from trustworthy_dl_tpu.engine.trainer import DistributedTrainer
    from trustworthy_dl_tpu.models.generate import generate
    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    args = build_generate_parser().parse_args(argv)
    if not args.model.startswith("gpt") or args.model.endswith("-moe"):
        print("generation supports the dense GPT-2 family")
        return 2
    # Pipeline-trained checkpoints store stage-stacked [S, L/S, ...] block
    # params — a different tree than the decoder's; refuse clearly rather
    # than let Orbax fail with a structure mismatch.  The topology sidecar
    # records the training parallelism for exactly this check.
    probe = CheckpointManager(args.checkpoint_dir)
    # verified=False: this probe only reads the topology sidecar to
    # refuse pipeline checkpoints — no reason to checksum the whole
    # payload here (load_checkpoint verifies on the actual restore).
    latest = probe.latest_step(verified=False)
    if latest is not None:
        meta = probe.load_metadata(latest) or {}
        if meta.get("parallelism") == "model":
            print("checkpoint was trained with pipeline (stage) "
                  "parallelism; generation needs a data-parallel "
                  "checkpoint (params stage-stacked)")
            return 2
    # Validate the prompt BEFORE the expensive init/restore: the int parse
    # needs nothing, the vocab bound only needs the (cheap) model config.
    tokenizer = None
    if args.prompt_text is not None:
        if not args.tokenizer_dir:
            print("--prompt-text requires --tokenizer-dir")
            return 2
        from trustworthy_dl_tpu.data.tokenizer import BPETokenizer

        try:
            tokenizer = BPETokenizer.load(args.tokenizer_dir)
        except (OSError, ValueError) as exc:
            print(f"could not load tokenizer from {args.tokenizer_dir!r}: "
                  f"{exc}")
            return 2
        tokens = tokenizer.encode(args.prompt_text)
    else:
        try:
            tokens = [int(t) for t in args.prompt.split(",") if t.strip()]
        except ValueError:
            print(f"--prompt must be comma-separated token ids, got "
                  f"{args.prompt!r}")
            return 2
    configure_compile_cache()
    config = TrainingConfig(model_name=args.model, num_nodes=1, batch_size=1,
                            checkpoint_dir=args.checkpoint_dir)
    trainer = DistributedTrainer(config, model_overrides=model_overrides)
    vocab = trainer.model.config.vocab_size
    if not tokens or any(not 0 <= t < vocab for t in tokens):
        if tokenizer is not None:
            print(f"--prompt-text encoded to {len(tokens)} token id(s); "
                  f"the model accepts ids in [0, {vocab}) — the tokenizer "
                  f"(vocab {tokenizer.vocab_size}) and model vocabularies "
                  "must be compatible and the prompt non-empty")
        else:
            print(f"--prompt needs at least one token id in [0, {vocab})")
        return 2
    trainer.initialize()
    try:
        trainer.load_checkpoint()
        print(f"restored step {int(trainer.state.step)} "
              f"from {args.checkpoint_dir}")
    except FileNotFoundError:
        print(f"no checkpoint under {args.checkpoint_dir!r}; "
              "sampling from random init")

    prompt = jnp.asarray([tokens], jnp.int32)
    out = generate(
        trainer.state.params, trainer.model.config, prompt,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p,
        rng=jax.random.PRNGKey(args.seed),
    )
    new_ids = out[0, len(tokens):].tolist()
    if tokenizer is not None:
        print("prompt:    ", args.prompt_text)
        print("generated: ", tokenizer.decode(new_ids))
    else:
        print("prompt:    ", tokens)
        print("generated: ", new_ids)
    trainer.cleanup()
    return 0


def build_serve_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trustworthy-dl-serve",
        description="Serve a GPT-2 checkpoint with the continuous-batching "
                    "engine (slotted KV cache, iteration-level scheduling, "
                    "trust-aware output monitoring).  Drives a synthetic "
                    "heterogeneous workload and prints serving metrics — "
                    "the smoke-deployment mode; hook ServingEngine.submit "
                    "into a real frontend for production traffic.",
    )
    parser.add_argument("--model", type=str, default="gpt2")
    parser.add_argument("--checkpoint-dir", type=str, default="checkpoints",
                        help="restore the latest checkpoint from here "
                             "(falls back to fresh init with a warning)")
    parser.add_argument("--max-slots", type=int, default=8,
                        help="concurrent sequences resident in the KV pool")
    parser.add_argument("--max-seq", type=int, default=256,
                        help="KV slot depth (prompt + generated tokens)")
    parser.add_argument("--queue-limit", type=int, default=64,
                        help="admission-queue bound (backpressure beyond)")
    parser.add_argument("--num-requests", type=int, default=32,
                        help="synthetic workload size")
    parser.add_argument("--max-new-tokens", type=int, default=32)
    parser.add_argument("--prompt-len", type=int, default=16,
                        help="mean synthetic prompt length (lengths vary "
                             "around it — heterogeneity is the point)")
    parser.add_argument("--temperature", type=float, default=0.8)
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="per-request wall-clock deadline")
    parser.add_argument("--no-monitor", action="store_true",
                        help="disable the trust-aware output monitor")
    parser.add_argument("--block-size", type=int, default=16,
                        help="paged-pool token positions per KV block "
                             "(--max-seq must be a multiple)")
    parser.add_argument("--num-blocks", type=int, default=None,
                        help="usable paged-pool blocks; default sizes "
                             "the pool to --max-slots full --max-seq "
                             "sequences")
    parser.add_argument("--no-prefix-cache", action="store_true",
                        help="disable the radix prefix cache (requests "
                             "sharing a prompt prefix otherwise reuse "
                             "already-filled blocks copy-on-write)")
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="prompt positions fed per chunked-prefill "
                             "tick (multiple of --block-size; default "
                             "auto) — bounds how long one admission can "
                             "stall the fused decode step")
    parser.add_argument("--spec-k", type=int, default=0,
                        help="speculative decoding draft depth: per "
                             "decode tick draft this many tokens per "
                             "active slot with the int8 weight tier "
                             "(built automatically as the draft model) "
                             "and verify them in ONE batched "
                             "model-dtype forward — streams stay "
                             "bit-identical to spec off except where a "
                             "greedy near-tie (top-1 margin under the "
                             "int8 parity tolerance) lets a draft flip "
                             "through, counted in spec_near_tie_flips; "
                             "rejected draft KV rolls back by COW "
                             "refcount decrement. "
                             "0 disables (default).  Requires "
                             "weight-dtype 'model'; README "
                             "§Serving/'Speculative decoding'")
    parser.add_argument("--no-spec-decode", action="store_true",
                        help="force speculative decoding OFF even when "
                             "--spec-k is set (A/B escape hatch; fleet "
                             "replica restarts inherit whichever the "
                             "config resolved to)")
    parser.add_argument("--kv-dtype", type=str, default="model",
                        choices=["model", "bfloat16", "float32", "int8"],
                        help="KV slot-pool storage dtype; int8 stores "
                             "per-(head, position)-scaled int8 — about "
                             "half the KV bytes per slot, so ~2x the "
                             "slots at fixed HBM (parity-gated with "
                             "automatic fallback to the model dtype; "
                             "README §Serving/Quantization)")
    parser.add_argument("--weight-dtype", type=str, default="model",
                        choices=["model", "int8"],
                        help="decode-matmul weight tier; int8 halves "
                             "the weight bytes streamed per decode "
                             "token (embedding/lm-head stay high "
                             "precision)")
    parser.add_argument("--adapter-rank", type=int, default=0,
                        help="per-tenant low-rank adapter tier: rank of "
                             "the paged adapter deltas gathered into "
                             "the decode/prefill matmuls by a traced "
                             "per-slot page table.  0 disables "
                             "(default) — the serve programs keep their "
                             "adapter-free signatures, streams "
                             "bit-identical to today's.  >0 is "
                             "incompatible with --spec-k; README "
                             "§Serving/Adapters")
    parser.add_argument("--adapter-pool-pages", type=int, default=None,
                        help="usable pages in the adapter HBM pool "
                             "(page 0 is the pinned all-zero page; "
                             "unset sizes the pool from the HBM "
                             "headroom gate).  More distinct adapters "
                             "than pages churn by LRU eviction of cold "
                             "pages — never by recompiling")
    parser.add_argument("--adapter-dtype", type=str, default="model",
                        choices=["model", "int8"],
                        help="adapter pool storage tier; int8 stores "
                             "per-page-scaled deltas dequantized in "
                             "register inside the gathered matmul "
                             "(~1/4 the pool bytes at f32 model dtype)")
    parser.add_argument("--obs-dir", type=str, default=None,
                        help="write serving telemetry here: trace.jsonl "
                             "(request lifecycle events + spans "
                             "correlated by request id), "
                             "attribution.jsonl (per-request ledger: "
                             "slot/blocks/weight-tier/verdict), "
                             "slo_status.json, trace_events.json "
                             "(Chrome/Perfetto timeline) + metrics "
                             "snapshot/Prometheus export")
    parser.add_argument("--slo-ttft-ms", type=float, default=2000.0,
                        help="TTFT SLO target per request (needs "
                             "--obs-dir); breaches emit slo_breach "
                             "events, burn the tddl_slo_burn_rate gauge "
                             "and shed lowest-priority admissions")
    parser.add_argument("--slo-itl-ms", type=float, default=250.0,
                        help="inter-token-latency SLO target (needs "
                             "--obs-dir)")
    parser.add_argument("--fleet-replicas", type=int, default=1,
                        help="serve through a ServingFleet of N engine "
                             "replicas (replica lifecycle supervision, "
                             "trust-aware routing, request fail-over "
                             "with bounded retries, drain/quarantine; "
                             "README §Fleet).  1 = single engine "
                             "(default)")
    parser.add_argument("--pool-roles", type=str, default=None,
                        metavar="ROLE[,ROLE...]",
                        help="fleet only: disaggregate the replicas "
                             "into prefill/decode specialist pools — "
                             "one comma-separated role per replica "
                             "('prefill' or 'decode', at least one of "
                             "each; e.g. 'prefill,decode,decode').  New "
                             "requests prefill on a prefill specialist "
                             "and hand off to a decode specialist at "
                             "their first decode token as a LIVE KV "
                             "block-table migration; the autoscaler "
                             "(when on) scales each pool independently")
    parser.add_argument("--no-live-migration", action="store_true",
                        help="fleet only: disable live KV block-table "
                             "migration everywhere (drains run out, "
                             "failures replay from the prompt — the "
                             "pre-migration arcs; escape hatch)")
    parser.add_argument("--hedge-deadline-ms", type=float, default=None,
                        help="fleet only: launch a hedged duplicate on "
                             "a second replica when a request's "
                             "remaining deadline drops below this "
                             "(first completed attempt wins; the loser "
                             "is cancelled and recorded hedge_lost)")
    parser.add_argument("--vote-k", type=int, default=0,
                        help="fleet only: cross-replica verdict voting "
                             "— replay a SUSPECTED replica's completed "
                             "requests on this many other replicas and "
                             "majority-vote the streams token-for-token "
                             "(README §Fleet/'Adversarial scenarios'); "
                             "0 disables (default), >= 2 needed for "
                             "outvote quarantines")
    parser.add_argument("--vote-outvote-limit", type=int, default=2,
                        help="fleet only: outvoted verdicts before the "
                             "suspected replica enters the drain -> "
                             "quarantine ladder")
    parser.add_argument("--autoscale-min", type=int, default=None,
                        help="fleet only: autoscaler floor — enables "
                             "the closed-loop control plane (with "
                             "--autoscale-max): replica count breathes "
                             "between min and max from queue depth, "
                             "occupancy, ITL-p99 and SLO burn with "
                             "hysteresis; scale-up builds replicas "
                             "through the HBM headroom gate, "
                             "scale-down drains (in-flight runs out, "
                             "never killed).  --fleet-replicas is the "
                             "starting count and must sit inside "
                             "[min, max] (default min: --fleet-"
                             "replicas)")
    parser.add_argument("--autoscale-max", type=int, default=None,
                        help="fleet only: autoscaler ceiling (enables "
                             "autoscaling when > --fleet-replicas or "
                             "with --autoscale-min)")
    parser.add_argument("--tenant-quota", type=int, default=None,
                        help="fleet only: per-tenant token-bucket "
                             "capacity (a submission costs prompt + "
                             "max_new tokens against its tenant's "
                             "bucket; over-budget submissions are "
                             "throttled loudly — tenant_throttle "
                             "events + tddl_fleet_tenant_throttled_"
                             "total{tenant=} — so a flooding tenant "
                             "backpressures itself, not the fleet)")
    parser.add_argument("--tenant-quota-refill", type=float,
                        default=None,
                        help="fleet only: bucket refill in tokens per "
                             "fleet tick (default: capacity / 64)")
    parser.add_argument("--slo-class", action="append", default=None,
                        metavar="NAME:PRIO:TTFT_MS:ITL_MS:WEIGHT",
                        help="fleet only, repeatable: define an SLO "
                             "class (priority orders shedding — "
                             "higher sheds last; weight scales the "
                             "deficit-round-robin share; TTFT_MS/"
                             "ITL_MS are per-class targets, '-' = "
                             "untracked).  Workload tenant priorities "
                             "map onto the class ladder.  The single "
                             "value 'default' installs the built-in "
                             "batch/standard/premium ladder")
    parser.add_argument("--trace-max-bytes", type=int, default=0,
                        help="rotate trace.jsonl once it exceeds this "
                             "many bytes (trace.1.jsonl, ...; 0 = no "
                             "rotation; env TDDL_TRACE_MAX_BYTES is the "
                             "default)")
    parser.add_argument("--seed", type=int, default=0)
    return parser


def serve_main(argv: Optional[List[str]] = None,
               model_overrides: Optional[dict] = None) -> int:
    """Console entry point ``trustworthy-dl-serve``.

    Same checkpoint handling as ``trustworthy-dl-generate`` (dense GPT-2
    family; pipeline-stacked checkpoints refused with a clear message);
    ``model_overrides`` is the tests' shrink hook."""
    import jax
    import numpy as np

    from trustworthy_dl_tpu.core.config import ServeConfig, TrainingConfig
    from trustworthy_dl_tpu.engine.checkpoint import CheckpointManager
    from trustworthy_dl_tpu.engine.trainer import DistributedTrainer
    from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
    from trustworthy_dl_tpu.utils.compile_cache import configure_compile_cache

    args = build_serve_parser().parse_args(argv)
    if not args.model.startswith("gpt") or args.model.endswith("-moe"):
        print("serving supports the dense GPT-2 family")
        return 2
    spec_k = 0 if args.no_spec_decode else args.spec_k
    if spec_k > args.max_new_tokens:
        # A draft deeper than the longest possible stream can never be
        # accepted past the budget — loud operator error, not silence.
        print(f"--spec-k {spec_k} exceeds --max-new-tokens "
              f"{args.max_new_tokens}: every draft past the request "
              "budget is discarded; lower --spec-k")
        return 2
    # Construction-time validation of the serving knobs (loud, before any
    # model init) — the dtype strings fail here, never at trace time.
    serve_config = ServeConfig(
        max_slots=args.max_slots, max_seq=args.max_seq,
        queue_limit=args.queue_limit,
        kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefix_cache=not args.no_prefix_cache,
        prefill_chunk=args.prefill_chunk,
        spec_k=spec_k,
        adapter_rank=args.adapter_rank,
        adapter_pool_pages=args.adapter_pool_pages,
        adapter_dtype=args.adapter_dtype,
    )
    configure_compile_cache()
    probe = CheckpointManager(args.checkpoint_dir)
    # verified=False: this probe only reads the topology sidecar to
    # refuse pipeline checkpoints — no reason to checksum the whole
    # payload here (load_checkpoint verifies on the actual restore).
    latest = probe.latest_step(verified=False)
    if latest is not None:
        meta = probe.load_metadata(latest) or {}
        if meta.get("parallelism") == "model":
            print("checkpoint was trained with pipeline (stage) "
                  "parallelism; serving needs a data-parallel checkpoint "
                  "(params stage-stacked)")
            return 2
    config = TrainingConfig(model_name=args.model, num_nodes=1, batch_size=1,
                            checkpoint_dir=args.checkpoint_dir)
    trainer = DistributedTrainer(config, model_overrides=model_overrides)
    cfg = trainer.model.config
    if args.max_seq > cfg.n_positions:
        print(f"--max-seq {args.max_seq} exceeds the model's "
              f"n_positions={cfg.n_positions}")
        return 2
    if args.prompt_len + args.max_new_tokens > args.max_seq:
        print(f"--prompt-len + --max-new-tokens = "
              f"{args.prompt_len + args.max_new_tokens} exceeds "
              f"--max-seq {args.max_seq}")
        return 2
    trainer.initialize()
    try:
        trainer.load_checkpoint()
        print(f"restored step {int(trainer.state.step)} "
              f"from {args.checkpoint_dir}")
    except FileNotFoundError:
        print(f"no checkpoint under {args.checkpoint_dir!r}; "
              "serving from random init")

    obs_session = None
    extra = {}
    if args.obs_dir:
        from trustworthy_dl_tpu.obs import ObsSession

        obs_session = ObsSession(args.obs_dir,
                                 trace_max_bytes=args.trace_max_bytes)
        obs_session.enable_spans()
        obs_session.open_ledger()
        # Forensics: every flight-dump-grade episode gets a paired
        # incident_NNN_<reason>.json (causal timeline + blast radius)
        # and a durable VERDICTS.jsonl trust-history row — what the
        # 'trustworthy-dl-obs incident' subcommands render offline.
        obs_session.enable_forensics()
        # Performance tier: compile watcher (the decode loop's
        # compile-once pin enforced live), HBM watermark gauges + the
        # pool headroom gate, cost ledger + perf fingerprint at exit.
        obs_session.enable_compile_watch()
        obs_session.enable_hbm()
    control_knobs = (args.autoscale_min is not None
                     or args.autoscale_max is not None
                     or args.tenant_quota is not None
                     or bool(args.slo_class))
    if args.fleet_replicas > 1 or control_knobs:
        # Fleet mode builds PER-REPLICA watchers from the SLO flags (a
        # breach is a replica-local signal) — the session-level watcher
        # pair stays uninstalled rather than sitting attached-but-unfed.
        # ANY control-plane knob routes here too (quotas, classes and
        # autoscaling live in the fleet's tick loop — a 1-replica fleet
        # enforces them fine, silently ignoring them would not).
        return _serve_fleet(args, trainer, cfg, serve_config, obs_session)
    if obs_session is not None:
        from trustworthy_dl_tpu.obs.slo import default_serve_rules

        obs_session.install_watchers(slo_rules=default_serve_rules(
            ttft_target_s=args.slo_ttft_ms / 1e3,
            itl_target_s=args.slo_itl_ms / 1e3,
        ))
        extra = dict(spans=obs_session.spans, ledger=obs_session.ledger,
                     slo=obs_session.slo, anomaly=obs_session.anomaly,
                     compilewatch=obs_session.compilewatch,
                     hbm=obs_session.hbm)
    tenant_names: list = []
    adapter_map = None
    if serve_config.adapter_rank > 0:
        # The smoke loop's synthetic traffic needs tenants for the
        # adapter tier to resolve: a Zipf-skewed tenant->adapter map
        # over more adapters than pool pages, so the run exercises
        # residency churn (LRU eviction, never recompiles).
        from trustworthy_dl_tpu.serve.workload import (
            make_tenant_population, zipf_adapter_assignments)

        tenant_names = [t.name for t in make_tenant_population(8)]
        n_adapters = (args.adapter_pool_pages or 4) + 1
        adapter_map = zipf_adapter_assignments(tenant_names, n_adapters,
                                               seed=args.seed)
    engine = ServingEngine.from_config(
        trainer.state.params, cfg, serve_config,
        enable_monitor=not args.no_monitor,
        rng=jax.random.PRNGKey(args.seed),
        trace=obs_session.trace if obs_session else None,
        registry=obs_session.registry if obs_session else None,
        adapter_map=adapter_map,
        **extra,
    )
    if engine.kv_fallback_reason:
        print(f"kv_dtype={args.kv_dtype} fell back to the model dtype "
              f"({engine.kv_fallback_reason})")
    rng = np.random.default_rng(args.seed)
    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    submitted = 0
    for i in range(args.num_requests):
        plen = int(np.clip(rng.integers(max(args.prompt_len // 2, 1),
                                        args.prompt_len * 2 + 1),
                           1, args.max_seq - args.max_new_tokens))
        prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
        new = int(rng.integers(1, args.max_new_tokens + 1))
        tenant = tenant_names[i % len(tenant_names)] \
            if tenant_names else None
        rid = engine.submit(ServeRequest(
            prompt=prompt, max_new_tokens=new,
            temperature=args.temperature, deadline_s=deadline,
            tenant=tenant,
        ))
        if rid is None:
            engine.run_until_idle()  # drain, then retry the arrival
            rid = engine.submit(ServeRequest(
                prompt=prompt, max_new_tokens=new,
                temperature=args.temperature, deadline_s=deadline,
                tenant=tenant,
            ))
        if rid is not None:
            submitted += 1
    engine.run_until_idle()
    summary = engine.metrics_summary()
    print(f"served {submitted} request(s) on {args.max_slots} slot(s)")
    for key in ("requests_completed", "requests_deadline_exceeded",
                "requests_flagged", "tokens_emitted", "tokens_per_s",
                "itl_p50_ms", "itl_p99_ms", "ttft_p50_ms",
                "peak_tokens_in_flight", "blocks_in_use",
                "prefix_hits", "prefix_hit_rate",
                "spec_k", "spec_proposed", "spec_accepted",
                "accepted_rate", "spec_near_tie_flips"):
        if key in summary:
            value = summary[key]
            shown = f"{value:.3f}" if isinstance(value, float) else value
            print(f"  {key}: {shown}")
    print("  attn_kernel_paths: " + " ".join(
        f"{program}={path}"
        for program, path in summary["attn_kernel_paths"].items()))
    if summary.get("quarantined_slots"):
        print(f"  quarantined slots: {summary['quarantined_slots']}")
    adapters = summary.get("adapters")
    if adapters:
        print(f"  adapters: rank={adapters['rank']} "
              f"dtype={adapters['dtype']} pages={adapters['pages']} "
              f"resident={adapters['resident']} "
              f"hit_rate={adapters['hit_rate']:.3f} "
              f"evictions={adapters['evictions']} "
              f"uploads={adapters['uploads']}")
    if obs_session is not None:
        ok, problems = engine.verify_attribution()
        print(f"attribution: {engine.ledger.total} record(s), "
              f"block-lifecycle reconciliation "
              f"{'OK' if ok else 'FAILED'}")
        for p in problems[:5]:
            print(f"  !! {p}")
        if obs_session.slo.active:
            print(f"SLO breaches active: {obs_session.slo.active}")
        # Performance tier artifacts: per-program cost ledger into
        # obs_report.json, a final HBM sweep, and the compile-watch
        # verdict (zero storms = the compile-once pin held live).
        engine.analyze_programs(obs_session.cost_ledger)
        obs_session.hbm.sweep(emit=True)
        compiles = obs_session.compiles.summary()
        print(f"compiles: {compiles['total']} "
              f"({compiles['seconds']:.2f}s), decode storms: "
              f"{obs_session.compilewatch.storm_total}")
        obs_session.finalize()
        print(f"obs artifacts in {args.obs_dir}")
        _print_perf_verdict(obs_session)
    trainer.cleanup()
    return 0


def _parse_slo_classes(specs):
    """``--slo-class NAME:PRIO:TTFT_MS:ITL_MS:WEIGHT`` (repeatable;
    '-' leaves a latency target untracked; the single spec 'default'
    installs the built-in ladder).  Raises ValueError with the exact
    offending spec — an operator typo must fail before any model
    work."""
    if not specs:
        return None
    from trustworthy_dl_tpu.serve import DEFAULT_SLO_CLASSES, SLOClass

    if len(specs) == 1 and specs[0].strip().lower() == "default":
        return DEFAULT_SLO_CLASSES
    classes = []
    for spec in specs:
        parts = spec.split(":")
        if len(parts) != 5:
            raise ValueError(
                f"--slo-class {spec!r}: expected "
                "NAME:PRIO:TTFT_MS:ITL_MS:WEIGHT (use '-' for an "
                "untracked target), or the single value 'default'")
        name, prio, ttft, itl, weight = (p.strip() for p in parts)
        try:
            classes.append(SLOClass(
                name=name, priority=int(prio),
                ttft_target_s=(None if ttft in ("-", "")
                               else float(ttft) / 1e3),
                itl_target_s=(None if itl in ("-", "")
                              else float(itl) / 1e3),
                weight=float(weight),
            ))
        except ValueError as exc:
            raise ValueError(f"--slo-class {spec!r}: {exc}")
    return tuple(classes)


def _parse_autoscale(args):
    """--autoscale-min/--autoscale-max -> AutoscalerConfig (None when
    neither is given).  --fleet-replicas is the STARTING count and must
    sit inside the bounds."""
    if args.autoscale_min is None and args.autoscale_max is None:
        return None
    from trustworthy_dl_tpu.serve import AutoscalerConfig

    lo = (args.autoscale_min if args.autoscale_min is not None
          else args.fleet_replicas)
    hi = (args.autoscale_max if args.autoscale_max is not None
          else max(args.fleet_replicas, lo))
    if not lo <= args.fleet_replicas <= hi:
        raise ValueError(
            f"--fleet-replicas {args.fleet_replicas} must start inside "
            f"the autoscale bounds [{lo}, {hi}]")
    return AutoscalerConfig(
        min_replicas=lo, max_replicas=hi,
        scale_up_queue_per_replica=float(args.max_slots),
        scale_down_queue_per_replica=max(args.max_slots / 8.0, 0.5),
        itl_p99_target_s=(args.slo_itl_ms / 1e3
                          if args.obs_dir else None),
    )


def _serve_fleet(args, trainer, cfg, serve_config, obs_session) -> int:
    """The ``--fleet-replicas N`` serve path: a ServingFleet over the
    seeded workload generator (bursty arrivals, heavy-tailed lengths,
    tenant priority skew) — the smoke-deployment mirror of the
    single-engine loop."""
    import jax

    from trustworthy_dl_tpu.serve import (
        FleetConfig,
        ServeRequest,
        ServingFleet,
        WorkloadConfig,
        generate_workload,
    )
    from trustworthy_dl_tpu.serve.workload import replay_workload

    slo_rules = None
    if obs_session is not None:
        from trustworthy_dl_tpu.obs.slo import default_serve_rules

        # The SLO flags become PER-REPLICA watcher rules: each replica
        # sheds its own breached admissions and feeds its own
        # degraded-signal, instead of one fleet-wide watcher conflating
        # every replica's latency stream.
        slo_rules = default_serve_rules(
            ttft_target_s=args.slo_ttft_ms / 1e3,
            itl_target_s=args.slo_itl_ms / 1e3,
        )
    # Control plane knobs (serve/control.py), all opt-in.
    try:
        slo_classes = _parse_slo_classes(args.slo_class)
        autoscale = _parse_autoscale(args)
        tenant_quota = None
        if args.tenant_quota is not None:
            from trustworthy_dl_tpu.serve import TenantQuotaConfig

            refill = (args.tenant_quota_refill
                      if args.tenant_quota_refill is not None
                      else args.tenant_quota / 64.0)
            tenant_quota = TenantQuotaConfig(
                capacity_tokens=args.tenant_quota,
                refill_per_tick=refill)
        pool_roles = None
        if args.pool_roles:
            pool_roles = tuple(
                r.strip() for r in args.pool_roles.split(","))
    except ValueError as exc:
        print(f"control plane: {exc}")
        return 2
    adapter_map = None
    if serve_config.adapter_rank > 0:
        # Same adapter resolution as the single-engine path, over the
        # workload generator's own tenant population: Zipf-skewed onto
        # one more adapter than the pool holds, so the smoke run churns
        # residency (and a crashed replica's rebuilt pool re-creates
        # the same deterministic weights).
        from trustworthy_dl_tpu.serve.workload import (
            DEFAULT_TENANTS, zipf_adapter_assignments)

        n_adapters = (args.adapter_pool_pages or 4) + 1
        adapter_map = zipf_adapter_assignments(
            [t.name for t in DEFAULT_TENANTS], n_adapters,
            seed=args.seed)
    # One source of truth for the serving knobs: the SAME validated
    # ServeConfig the single-engine path uses, via from_config.
    fleet = ServingFleet.from_config(
        trainer.state.params, cfg, serve_config,
        fleet_config=FleetConfig(
            num_replicas=args.fleet_replicas,
            hedge_deadline_s=(args.hedge_deadline_ms / 1e3
                              if args.hedge_deadline_ms else None),
            vote_k=args.vote_k,
            vote_outvote_limit=args.vote_outvote_limit,
            slo_classes=slo_classes,
            tenant_quota=tenant_quota,
            autoscale=autoscale,
            pool_roles=pool_roles,
            live_migration=not args.no_live_migration,
        ),
        rng=jax.random.PRNGKey(args.seed),
        trace=obs_session.trace if obs_session else None,
        registry=obs_session.registry if obs_session else None,
        spans=obs_session.spans if obs_session else None,
        ledger=obs_session.ledger if obs_session else None,
        forensics=obs_session.forensics if obs_session else None,
        slo_rules=slo_rules,
        enable_monitor=not args.no_monitor,
        # Performance tier rides every replica build (and rebuild): the
        # decode loops share one compile watcher scope, and each
        # replica's pool allocation consults the HBM headroom gate.
        compilewatch=obs_session.compilewatch if obs_session else None,
        hbm=obs_session.hbm if obs_session else None,
        adapter_map=adapter_map,
    )
    workload = generate_workload(
        WorkloadConfig(seed=args.seed, num_requests=args.num_requests,
                       prompt_median=args.prompt_len,
                       output_median=max(args.max_new_tokens // 2, 1),
                       max_output=args.max_new_tokens),
        cfg.vocab_size, args.max_seq,
    )
    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None
    submitted = replay_workload(fleet, workload, lambda item: ServeRequest(
        prompt=list(item.prompt), max_new_tokens=item.max_new_tokens,
        temperature=args.temperature, priority=item.priority,
        deadline_s=(deadline if deadline is not None
                    else item.deadline_s),
        tenant=item.tenant,
    ))
    if fleet.autoscaler is not None:
        # Give a trailing scale-down room to land: the replay exits at
        # drain, the controller breathes a beat later.
        for _ in range(64):
            fleet.step()
    summary = fleet.metrics_summary()
    print(f"fleet served {submitted} request(s) on "
          f"{args.fleet_replicas} replica(s) x {args.max_slots} slot(s)")
    for key in ("statuses", "completed_tokens", "replica_states", "ticks",
                "fleet_failovers", "fleet_migrations", "fleet_preempts",
                "fleet_hedges", "fleet_drains",
                "fleet_quarantines", "fleet_restarts",
                "fleet_suspicions", "fleet_votes", "fleet_outvotes",
                "fleet_tenant_floods", "fleet_throttles",
                "fleet_scale_ups", "fleet_scale_downs",
                "replicas_in_service", "replica_trace",
                "per_class", "class_queue_depth",
                "replica_suspicion", "replica_slo_active"):
        if key in summary:
            print(f"  {key}: {summary[key]}")
    if obs_session is not None:
        ok, problems = fleet.verify_attribution()
        print(f"attribution: {fleet.ledger.total} record(s), "
              f"fleet block-lifecycle reconciliation "
              f"{'OK' if ok else 'FAILED'}")
        for p in problems[:5]:
            print(f"  !! {p}")
        if fleet.replicas:
            fleet.replicas[0].engine.analyze_programs(
                obs_session.cost_ledger)
        obs_session.hbm.sweep(emit=True)
        print(f"compiles: {obs_session.compiles.summary()['total']}, "
              f"decode storms: {obs_session.compilewatch.storm_total}")
        obs_session.finalize()
        print(f"obs artifacts in {args.obs_dir}")
        _print_perf_verdict(obs_session)
    trainer.cleanup()
    return 0


def build_prepare_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trustworthy-dl-prepare-data",
        description="Tokenize a raw .txt corpus into the loader's .bin "
                    "token memmap (byte-level BPE, trained on the corpus "
                    "or loaded from GPT-2-format vocab.json/merges.txt)",
    )
    parser.add_argument("txt", type=str, help="input UTF-8 text file")
    parser.add_argument("--out", type=str, default=None,
                        help="output .bin path (default: alongside input)")
    parser.add_argument("--vocab-size", type=int, default=8192)
    parser.add_argument("--tokenizer-dir", type=str, default=None,
                        help="directory holding (or to receive) "
                             "vocab.json + merges.txt")
    parser.add_argument("--val-fraction", type=float, default=0.0,
                        help="also write a *_val.bin holdout split")
    return parser


def prepare_main(argv: Optional[List[str]] = None) -> int:
    """Console entry point ``trustworthy-dl-prepare-data`` — the offline
    .txt → .bin pipeline (experiment_runner.py:100-110 parity: the
    'openwebtext' tier works from raw text with no external tooling)."""
    import os

    from trustworthy_dl_tpu.data.tokenizer import prepare_data

    args = build_prepare_parser().parse_args(argv)
    if not os.path.exists(args.txt):
        print(f"no such file: {args.txt}")
        return 2
    info = prepare_data(args.txt, out_path=args.out,
                        vocab_size=args.vocab_size,
                        tokenizer_dir=args.tokenizer_dir,
                        val_fraction=args.val_fraction)
    print(f"wrote {info['num_tokens']} tokens (vocab {info['vocab_size']}) "
          f"to {info['out_path']}"
          + (f" + val split {info['val_path']}" if info["val_path"] else ""))
    print(f"tokenizer files in {info['tokenizer_dir']}")
    return 0


def build_obs_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="trustworthy-dl-obs",
        description="Render an obs directory: tail/filter trace.jsonl by "
                    "request/step id (rotated trace.N.jsonl segments are "
                    "walked in order), convert spans to a Chrome/Perfetto "
                    "timeline, pretty-print obs_report.json and the "
                    "SLO/anomaly status.  With no action flags, prints a "
                    "summary of everything the directory holds.  The "
                    "'diff' subcommand (trustworthy-dl-obs diff A B) "
                    "renders two obs_report/perf-ledger artifacts side "
                    "by side with deltas; the 'incident' subcommand "
                    "(trustworthy-dl-obs incident list|show|blast) "
                    "renders assembled incident forensics.",
    )
    parser.add_argument("obs_dir", type=str,
                        help="directory a run wrote with --obs-dir")
    parser.add_argument("--tail", type=int, default=None, metavar="N",
                        help="print the last N trace events (after any "
                             "filters)")
    parser.add_argument("--request-id", type=int, default=None,
                        help="only events correlated to this request id")
    parser.add_argument("--step", type=int, default=None,
                        help="only events correlated to this step id")
    parser.add_argument("--type", type=str, default=None,
                        help="only events of this type (e.g. span, "
                             "anomaly, serve_retire)")
    parser.add_argument("--chrome", type=str, default=None, metavar="OUT",
                        help="convert the trace's span events to a Chrome/"
                             "Perfetto trace_events JSON at OUT (load in "
                             "chrome://tracing or ui.perfetto.dev)")
    parser.add_argument("--report", action="store_true",
                        help="pretty-print obs_report.json (step-time "
                             "breakdown + MFU)")
    parser.add_argument("--slo", action="store_true",
                        help="print SLO burn rates / anomaly status "
                             "(slo_status.json + snapshot gauges)")
    return parser


def obs_main(argv: Optional[List[str]] = None) -> int:
    """Console entry point ``trustworthy-dl-obs`` — the reader side of
    the obs directory (host-only; imports no jax)."""
    import json
    import os
    import sys as _sys

    from trustworthy_dl_tpu.obs.events import read_jsonl_rotated
    from trustworthy_dl_tpu.obs.spans import chrome_trace_from_events

    if argv is None:
        argv = _sys.argv[1:]
    if argv and argv[0] == "diff":
        return _obs_diff(argv[1:])
    if argv and argv[0] == "incident":
        return _obs_incident(argv[1:])
    args = build_obs_parser().parse_args(argv)
    if not os.path.isdir(args.obs_dir):
        print(f"no such obs directory: {args.obs_dir}")
        return 2
    trace_path = os.path.join(args.obs_dir, "trace.jsonl")
    # Rotated segments (trace.1.jsonl, ...) are walked oldest-first so a
    # size-capped long run reads exactly like an uncapped one.
    events = read_jsonl_rotated(trace_path)

    filtered = events
    if args.request_id is not None:
        filtered = [e for e in filtered
                    if e.get("request_id") == args.request_id]
    if args.step is not None:
        filtered = [e for e in filtered if e.get("step") == args.step]
    if args.type is not None:
        filtered = [e for e in filtered if e.get("type") == args.type]

    acted = False
    if args.tail is not None or args.request_id is not None \
            or args.step is not None or args.type is not None:
        acted = True
        for e in filtered[-(args.tail or 20):]:
            print(json.dumps(e))
    if args.chrome is not None:
        acted = True
        payload = chrome_trace_from_events(events, args.chrome)
        print(f"wrote {len(payload['traceEvents'])} span event(s) to "
              f"{args.chrome}")
    if args.report:
        acted = True
        path = os.path.join(args.obs_dir, "obs_report.json")
        if os.path.exists(path):
            with open(path) as f:
                print(json.dumps(json.load(f), indent=2))
        else:
            print(f"no obs_report.json under {args.obs_dir}")
    if args.slo:
        acted = True
        _print_slo_status(args.obs_dir)
    if not acted:
        _print_obs_summary(args.obs_dir, events)
    return 0


def _obs_diff(argv: List[str]) -> int:
    """``trustworthy-dl-obs diff A B`` — two obs artifact sets side by
    side (obs dirs, obs_report.json files, or PERF_LEDGER.jsonl files;
    host-only, imports no jax)."""
    import argparse as _argparse

    from trustworthy_dl_tpu.obs.sentinel import (
        load_perf_artifact,
        render_diff,
    )

    parser = _argparse.ArgumentParser(
        prog="trustworthy-dl-obs diff",
        description="Pretty-print two obs_report/perf-ledger artifacts "
                    "side by side: step time, phase fractions, MFU "
                    "(nominal + analyzed), per-program FLOPs/temp "
                    "bytes, compile counts, HBM watermark — with "
                    "relative deltas.",
    )
    parser.add_argument("a", type=str, help="first artifact (obs dir, "
                                            "obs_report.json, or "
                                            "PERF_LEDGER.jsonl)")
    parser.add_argument("b", type=str, help="second artifact")
    args = parser.parse_args(argv)
    try:
        view_a = load_perf_artifact(args.a)
        view_b = load_perf_artifact(args.b)
    except FileNotFoundError as exc:
        print(f"diff: {exc}")
        return 2
    print(render_diff(view_a, view_b))
    return 0


def _obs_incident(argv: List[str]) -> int:
    """``trustworthy-dl-obs incident list|show|blast`` — render the
    forensic incident artifacts a run assembled next to its flight
    dumps (obs/forensics.py; host-only, imports no jax)."""
    import argparse as _argparse

    from trustworthy_dl_tpu.obs.forensics import (
        find_incident,
        load_incidents,
        render_blast,
        render_incident,
    )

    parser = _argparse.ArgumentParser(
        prog="trustworthy-dl-obs incident",
        description="Offline incident forensics: 'list' the assembled "
                    "incident_NNN_<reason>.json reports in a directory, "
                    "'show' one causal timeline (trigger event -> "
                    "contributing signals -> actions taken, each with "
                    "trace seq ids), or 'blast' one blast radius (every "
                    "request that decoded off the suspect's KV blocks "
                    "or adapter page, with per-journal block sets).",
    )
    parser.add_argument("action", choices=("list", "show", "blast"))
    parser.add_argument("ident", nargs="?", default=None,
                        help="incident id, bare index, or reason "
                             "substring (show/blast)")
    parser.add_argument("--dir", dest="directory", default=".",
                        help="directory holding the incident artifacts "
                             "(an obs dir or a checkpoint dir; "
                             "default: cwd)")
    args = parser.parse_args(argv)
    if args.action == "list":
        incidents = load_incidents(args.directory)
        if not incidents:
            print(f"no incident artifacts under {args.directory}")
            return 0
        for inc in incidents:
            radius = inc.get("blast_radius") or {}
            print(f"{inc.get('incident_id'):<40} "
                  f"tick={str(inc.get('tick')):<6} "
                  f"suspects={inc.get('suspect_replicas')} "
                  f"actions={len(inc.get('actions') or [])} "
                  f"blast={len(radius.get('requests') or [])}")
        return 0
    if args.ident is None:
        print(f"incident {args.action}: an incident id (or index, or "
              f"reason substring) is required")
        return 2
    inc = find_incident(args.directory, args.ident)
    if inc is None:
        print(f"no incident matching {args.ident!r} under "
              f"{args.directory}")
        return 2
    print(render_incident(inc) if args.action == "show"
          else render_blast(inc))
    return 0


def _print_slo_status(obs_dir: str) -> None:
    import json
    import os

    path = os.path.join(obs_dir, "slo_status.json")
    if os.path.exists(path):
        with open(path) as f:
            status = json.load(f)
        for rule in status.get("slo", {}).get("rules", ()):
            flag = " BREACHED" if rule["active"] else ""
            print(f"  slo {rule['name']:<12} ({rule['signal']} <= "
                  f"{rule['target']:g}): burn {rule['burn_rate']:.2f}"
                  f"{flag}")
        anomaly = status.get("anomaly", {})
        if anomaly:
            print(f"  anomaly events: {anomaly.get('event_total', 0)}, "
                  f"active: {anomaly.get('active', [])}")
        return
    # Fall back to the burn-rate gauges in the metrics snapshot (a run
    # that died before finalize still snapshotted on cadence).
    snap_path = os.path.join(obs_dir, "metrics_snapshot.json")
    if not os.path.exists(snap_path):
        print(f"  no slo_status.json or metrics_snapshot.json under "
              f"{obs_dir}")
        return
    with open(snap_path) as f:
        snap = json.load(f)
    for name in ("tddl_slo_burn_rate", "tddl_anomaly_active"):
        metric = snap.get("metrics", {}).get(name)
        if not metric:
            continue
        for row in metric.get("series", ()):
            labels = ",".join(f"{k}={v}" for k, v in row["labels"].items())
            print(f"  {name}{{{labels}}} = {row['value']}")


def _print_obs_summary(obs_dir: str, events: list) -> None:
    import json
    import os

    print(f"obs dir: {obs_dir}")
    counts: dict = {}
    for e in events:
        counts[e.get("type", "?")] = counts.get(e.get("type", "?"), 0) + 1
    if counts:
        print(f"trace.jsonl: {len(events)} event(s)")
        for etype, n in sorted(counts.items()):
            print(f"  {etype}: {n}")
    report_path = os.path.join(obs_dir, "obs_report.json")
    if os.path.exists(report_path):
        with open(report_path) as f:
            report = json.load(f)
        line = f"obs_report.json: {report.get('num_steps', 0)} step(s)"
        mfu = report.get("mfu", {})
        if isinstance(mfu, dict) and mfu.get("mfu") is not None:
            line += f", MFU {mfu['mfu']:.1%} ({mfu['peak_flops_source']})"
        print(line)
    ledger_path = os.path.join(obs_dir, "attribution.jsonl")
    if os.path.exists(ledger_path):
        from trustworthy_dl_tpu.obs.attribution import read_ledger

        _, records = read_ledger(ledger_path)
        flagged = sum(1 for r in records if r.get("flagged"))
        print(f"attribution.jsonl: {len(records)} record(s), "
              f"{flagged} flagged")
    _print_slo_status(obs_dir)
    dumps = sorted(p for p in os.listdir(obs_dir)
                   if p.startswith("flight_") and p.endswith(".json"))
    if dumps:
        print(f"flight dumps: {', '.join(dumps)}")
    incidents = sorted(p for p in os.listdir(obs_dir)
                       if p.startswith("incident_")
                       and p.endswith(".json"))
    if incidents:
        print(f"incidents: {', '.join(incidents)} "
              f"(render with 'trustworthy-dl-obs incident "
              f"list --dir {obs_dir}')")
    verdicts_path = os.path.join(obs_dir, "VERDICTS.jsonl")
    if os.path.exists(verdicts_path):
        from trustworthy_dl_tpu.obs.verdicts import VerdictStore

        rows = VerdictStore(verdicts_path).read()
        kinds: dict = {}
        for row in rows:
            key = f"{row.get('kind')}:{row.get('outcome')}"
            kinds[key] = kinds.get(key, 0) + 1
        print(f"VERDICTS.jsonl: {len(rows)} row(s)"
              + (" — " + ", ".join(f"{k}={n}" for k, n in
                                   sorted(kinds.items()))
                 if kinds else ""))


if __name__ == "__main__":
    raise SystemExit(main())
