"""Serving engine (trustworthy_dl_tpu/serve): continuous batching over the
paged KV pool, pinned against models/generate.py numerics.

Fast tier: host-side contracts (slot allocator, backpressure,
output-monitor math, sampling-key layout) — nothing jits a model.
Slow tier (@pytest.mark.slow): jitted smoke tests, including THE acceptance
scenario — >= 8 concurrent heterogeneous requests through fewer slots with
mid-flight retirement, the decode step compiled exactly once, and streamed
tokens bit-identical to batch generate for the same params/keys."""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trustworthy_dl_tpu.detect import baseline as bl
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.serve import (
    OutputMonitor,
    ServeRequest,
    ServingEngine,
    SlotAllocator,
)
from trustworthy_dl_tpu.serve.scheduler import request_key_stream

CFG = gpt2.GPT2Config(vocab_size=97, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# Fast tier: host-side contracts
# --------------------------------------------------------------------------


def test_slot_allocator_lifecycle():
    alloc = SlotAllocator(3)
    slots = [alloc.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert alloc.alloc() is None          # exhausted, not an error
    alloc.free(slots[0])
    assert alloc.free_count == 1
    with pytest.raises(ValueError):
        alloc.free(slots[0])              # double free
    # Quarantine shrinks the serviceable pool and survives free().
    s = alloc.alloc()
    alloc.quarantine(s)
    alloc.free(s)                         # no-op on a quarantined slot
    assert s not in [alloc.alloc() for _ in range(alloc.free_count)]
    assert alloc.capacity == 2
    alloc.release(s)
    assert alloc.capacity == 3 and alloc.free_count == 1


def test_backpressure_and_validation(params):
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           queue_limit=2)
    ok = [engine.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2))
          for _ in range(3)]
    assert ok[0] is not None and ok[1] is not None
    assert ok[2] is None                  # queue full -> shed, not raise
    assert engine.rejected == 1
    with pytest.raises(ValueError):
        engine.submit(ServeRequest(prompt=[], max_new_tokens=1))
    with pytest.raises(ValueError):      # can never fit the slot depth
        engine.submit(ServeRequest(prompt=[1] * 30, max_new_tokens=10))
    # A prompt past max_seq is rejected at submit, not crashed on (and
    # slot-leaked) at admission.
    tight = ServingEngine(params, CFG, max_slots=2, max_seq=48)
    with pytest.raises(ValueError, match="max_seq"):
        tight.submit(ServeRequest(prompt=[1] * 50, max_new_tokens=2))
    assert tight.scheduler.allocator.free_count == 2  # nothing leaked
    assert tight.scheduler.blocks_in_use == 0


def test_request_key_stream_matches_generate_layout():
    """Serving key streams replicate generate's rng consumption: token 0
    from the request key, token i from split(fold_in(key, 1), n-1)[i-1]."""
    key = jax.random.PRNGKey(11)
    stream = request_key_stream(key, 5)
    assert stream.shape == (5, 2)
    np.testing.assert_array_equal(stream[0], np.asarray(key, np.uint32))
    ref = np.asarray(jax.random.split(jax.random.fold_in(key, 1), 4),
                     np.uint32)
    np.testing.assert_array_equal(stream[1:], ref)
    assert request_key_stream(key, 1).shape == (1, 2)


def test_output_monitor_flags_outlier_and_does_not_absorb():
    mon = OutputMonitor(window=64, warmup=8, z_threshold=4.0)
    rng = np.random.default_rng(0)
    for _ in range(16):
        flagged, _ = mon.observe(rng.normal(3.0, 0.05, 8),
                                 rng.normal(1.0, 0.05, 8))
        assert not flagged
    before = mon.count
    flagged, z = mon.observe([0.01] * 8, [25.0] * 8)  # collapse signature
    assert flagged and z > 4.0
    assert mon.count == before            # flagged request NOT absorbed
    # Clean requests keep absorbing afterwards.
    flagged, _ = mon.observe(rng.normal(3.0, 0.05, 8),
                             rng.normal(1.0, 0.05, 8))
    assert not flagged and mon.count == before + 1


class _DeviceRingMonitor:
    """The reference: the monitor as it scored before its baseline moved to
    the host, on ``detect.baseline``'s device ring (eager, one row)."""

    def __init__(self, window, warmup, z_threshold):
        self.warmup, self.z_threshold = warmup, z_threshold
        self.state = bl.init_baseline_state(1, window,
                                            OutputMonitor.NUM_SIGNALS)

    def observe(self, entropies, margins):
        vec = jnp.asarray(
            [[float(np.mean(entropies)), float(np.mean(margins))]],
            jnp.float32)
        mean, std, valid = bl.baseline_moments(self.state)
        z = float(jnp.max(bl.zscores(vec, mean, std)))
        flagged = int(valid[0]) >= self.warmup and z > self.z_threshold
        if not flagged:
            self.state = bl.push_stats(self.state, vec)
        return flagged, z

    @property
    def count(self):
        return int(self.state.count[0])


def _verdict_sequence(window, warmup, steps=640, seed=41):
    """(entropies, margins) of ``steps`` requests: clean draws, and among
    them an outlier one verdict BEFORE the baseline is warm and a collapse
    right at the edge, a collapse or a garbage signature every 29th
    request, and a stretch longer than the window of one exact vector
    (zero variance in both signals) followed by clean draws again."""
    rng = np.random.default_rng(seed)
    flat = range(window + 32, 2 * window + 44)
    sequence = []
    for i in range(steps):
        if i == warmup - 1:
            pair = ([3.4] * 8, [1.4] * 8)            # cold: must be absorbed
        elif i == warmup or i % 29 == 28:
            pair = (([0.01] * 8, [25.0] * 8) if i % 2 == 0
                    else ([9.0] * 8, [0.001] * 8))
        elif i in flat:
            pair = ([3.0] * 8, [1.0] * 8)
        elif i == flat.stop:
            # the first vector off the flat one is the whole deviation of
            # the next verdicts: far enough that float32 resolves it
            pair = ([3.125] * 8, [1.125] * 8)
        else:
            pair = (rng.normal(3.0, 0.05, 8), rng.normal(1.0, 0.05, 8))
        sequence.append(pair)
    return sequence


@pytest.mark.parametrize("window,warmup", [(64, 8), (64, 16), (16, 4),
                                           (256, 16)])
def test_host_monitor_gives_the_device_ring_s_verdicts(window, warmup):
    """Same work, same answers: at every step of a sequence that wraps the
    window the host baseline flags, counts and scores as the device ring
    of ``detect.baseline`` did (float64 moments against float32: ``z``
    within 1e-3 relative, and within 1e-4 where a vector lies so close to
    the mean that the float32 mean's own rounding is the difference)."""
    host = OutputMonitor(window=window, warmup=warmup, z_threshold=4.0)
    ref = _DeviceRingMonitor(window, warmup, 4.0)
    flags, zero_variance = [], 0
    for step, (ent, mar) in enumerate(_verdict_sequence(window, warmup)):
        flagged, z = host.observe(ent, mar)
        ref_flagged, ref_z = ref.observe(ent, mar)
        assert flagged == ref_flagged, step
        assert host.count == ref.count, step
        assert z == pytest.approx(ref_z, rel=1e-3, abs=1e-4), step
        flags.append(flagged)
        zero_variance += (step >= window + 32 and z == 0.0)
    assert host.count > 2 * window                   # the ring wrapped
    assert not any(flags[:warmup]) and flags[warmup]  # the warm-up edge
    assert sum(flags) >= 600 // 29                   # the signatures
    assert zero_variance >= 3                        # the flat stretch
    assert host.count + sum(flags) == len(flags)     # absorbed iff clean


def test_output_monitor_touches_no_device(caplog):
    """A verdict is host arithmetic over host floats: nothing is uploaded,
    pulled, dispatched or compiled under ``serve.monitor``.  The window is
    one no other test uses, so a device ring would have to compile."""
    mon = OutputMonitor(window=53, warmup=8, z_threshold=4.0)
    rng = np.random.default_rng(7)
    with caplog.at_level(logging.DEBUG, logger="jax"), \
            jax.log_compiles(), jax.transfer_guard("disallow"):
        for _ in range(50):
            flagged, z = mon.observe(list(rng.normal(3.0, 0.05, 8)),
                                     list(rng.normal(1.0, 0.05, 8)))
            assert type(flagged) is bool and type(z) is float
        flagged, z = mon.observe([0.01] * 8, [25.0] * 8)
    assert flagged and z > 4.0 and mon.count == 50
    assert [r.getMessage() for r in caplog.records
            if r.name.startswith("jax")] == []
    assert not any(isinstance(value, jax.Array)
                   for value in vars(mon).values())


# --------------------------------------------------------------------------
# Slow tier: jitted smoke tests
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_serving_smoke_matches_generate(params):
    """THE acceptance scenario: 9 concurrent requests with heterogeneous
    prompt/output lengths through 3 slots — continuous batching admits and
    retires mid-flight (slot count < request count forces reuse), the
    fused decode step compiles exactly once, and every request's streamed
    tokens are bit-identical to models/generate.py for the same params."""
    engine = ServingEngine(params, CFG, max_slots=3, max_seq=48,
                           queue_limit=32)
    cache_before = engine.scheduler.decode_cache_size()
    rng = np.random.default_rng(0)
    streamed = {}
    reqs = []
    for i in range(9):
        plen = int(rng.integers(3, 12))
        new = int(rng.integers(1, 9))
        prompt = rng.integers(0, CFG.vocab_size, plen).tolist()
        reqs.append((prompt, new))
        rid = engine.submit(ServeRequest(
            prompt=prompt, max_new_tokens=new,
            on_token=lambda r, t: streamed.setdefault(r, []).append(t),
        ))
        assert rid == i
    results = engine.run_until_idle()

    assert len(results) == 9
    assert all(r.status == "completed" for r in results.values())
    # One compiled decode program for the whole heterogeneous run.
    assert engine.scheduler.decode_cache_size() - cache_before == 1
    # Slot reuse actually happened: 9 sequences through a 3-slot pool.
    assert engine.scheduler.allocator.max_slots == 3

    for rid, (prompt, new) in enumerate(reqs):
        ref = generate(params, CFG, jnp.asarray([prompt], jnp.int32), new,
                       temperature=0.0)
        ref_tokens = np.asarray(ref)[0, len(prompt):].tolist()
        assert results[rid].tokens == ref_tokens, f"request {rid}"
        assert streamed[rid] == ref_tokens  # streaming saw the same tokens
        assert len(results[rid].itl_s) == new - 1
        assert results[rid].ttft_s is not None

    summary = engine.metrics_summary()
    assert summary["requests_completed"] == 9
    assert summary["tokens_emitted"] == sum(n for _, n in reqs)


@pytest.mark.slow
def test_sampled_request_matches_generate_stream(params):
    """A temperature-sampled request reproduces generate() token-for-token
    under the same key — the per-slot key stream is generate's stream."""
    prompt = [5, 17, 3, 88, 41]
    key = jax.random.PRNGKey(7)
    ref = np.asarray(generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                              8, temperature=0.8, rng=key))[0, 5:].tolist()
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48)
    rid = engine.submit(ServeRequest(prompt=prompt, max_new_tokens=8,
                                     temperature=0.8, rng=key))
    assert engine.run_until_idle()[rid].tokens == ref


@pytest.mark.slow
def test_eos_retires_mid_flight(params):
    """eos_id stops a sequence early — the slot frees before max_new."""
    prompt = [9, 4, 33]
    ref = np.asarray(generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                              6, temperature=0.0))[0, 3:].tolist()
    # First position at which the greedy stream emits ref[0] again — with a
    # repetitive random-init model that can be position 0 (stop after one
    # token); the invariant under test is stop-at-FIRST-eos, whatever the
    # stream looks like.
    eos = ref[0]
    stop = ref.index(eos) + 1
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48)
    rid = engine.submit(ServeRequest(prompt=prompt, max_new_tokens=6,
                                     eos_id=eos))
    result = engine.run_until_idle()[rid]
    assert result.status == "completed"
    assert result.tokens == ref[:stop]    # stopped AT the eos token
    assert len(result.tokens) < 6         # genuinely early
    assert engine.scheduler.allocator.free_count == 2  # slot returned


@pytest.mark.slow
def test_deadline_sheds_queued_requests(params):
    """An already-expired deadline retires the request before admission."""
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48)
    rid_ok = engine.submit(ServeRequest(prompt=[1, 2, 3],
                                        max_new_tokens=2))
    rid_late = engine.submit(ServeRequest(prompt=[4, 5, 6],
                                          max_new_tokens=2,
                                          deadline_s=0.0))
    results = engine.run_until_idle()
    assert results[rid_ok].status == "completed"
    assert results[rid_late].status == "deadline_exceeded"
    assert results[rid_late].tokens == []


@pytest.mark.slow
def test_flagged_request_quarantines_slot(params):
    """A monitor-flagged generation quarantines its slot; with every slot
    quarantined the engine sheds the queue as no_capacity instead of
    spinning."""

    class FlagAll:
        def observe(self, entropies, margins):
            return True, 99.0

    engine = ServingEngine(params, CFG, max_slots=2, max_seq=48,
                           monitor=FlagAll())
    rids = [engine.submit(ServeRequest(prompt=[i + 1, i + 2],
                                       max_new_tokens=2))
            for i in range(3)]
    results = engine.run_until_idle()
    assert results[rids[0]].flagged and results[rids[1]].flagged
    assert engine.quarantined_slots == {0, 1}
    assert results[rids[2]].status == "no_capacity"
    # Operator releases a slot -> service resumes.
    engine.release_quarantine(0)
    rid = engine.submit(ServeRequest(prompt=[7, 8], max_new_tokens=2))
    assert engine.run_until_idle()[rid].tokens  # served


# --------------------------------------------------------------------------
# Active observability plane (obs/): shed hook, bounded retention,
# full-plane bit-parity + compile-once
# --------------------------------------------------------------------------


def test_slo_shed_hook_drops_lowest_priority_newest_first(params):
    """While the attached watcher is in breach, the admission path sheds
    the LOWEST-priority queued request (ties: newest) — and only while
    the queue exceeds free capacity, so shedding relieves pressure
    instead of burning goodput."""

    class Breached:
        breached = True

        def observe(self, *a, **k):
            pass

        def quantile(self, signal, q):
            return None   # attached watcher owns the summary sketches

    engine = ServingEngine(params, CFG, max_slots=1, max_seq=32,
                           queue_limit=8, slo=Breached())
    rid_hi = engine.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                        priority=5))
    rid_a = engine.submit(ServeRequest(prompt=[3, 4], max_new_tokens=2))
    rid_b = engine.submit(ServeRequest(prompt=[5, 6], max_new_tokens=2))
    engine._shed_for_slo()
    engine._shed_for_slo()
    engine._shed_for_slo()   # queue (1) <= free (1): no further sheds
    assert engine.results[rid_b].status == "shed_slo"   # newest tie first
    assert engine.results[rid_a].status == "shed_slo"
    assert rid_hi not in engine.results                 # survivor
    assert engine.shed_slo == 2
    assert [t.request_id for t, _ in engine._queue] == [rid_hi]
    assert engine.metrics_summary()["requests_shed_slo"] == 2


def test_slo_shed_tiebreak_honours_retry_age(params):
    """Satellite regression (fleet fail-over depends on this): a shed
    request RESUBMITTED with ``first_submit_id`` keeps its original
    age in the shed tie-break.  Without the anchor the retry gets a
    fresh (newest) id and is shed again first under sustained pressure
    — a starvation loop where the same request is shed forever."""

    class Breached:
        breached = True

        def observe(self, *a, **k):
            pass

        def quantile(self, signal, q):
            return None

    engine = ServingEngine(params, CFG, max_slots=1, max_seq=32,
                           queue_limit=8, slo=Breached())
    # rid 0 was shed earlier and is now RESUBMITTED as rid 1, carrying
    # its original age; rid 2 arrives after it, same priority.
    retry = engine.submit(ServeRequest(prompt=[1, 2], max_new_tokens=2,
                                       first_submit_id=0))
    fresh = engine.submit(ServeRequest(prompt=[3, 4], max_new_tokens=2))
    engine._shed_for_slo()
    # The genuinely newest request is shed — NOT the retry.
    assert engine.results[fresh].status == "shed_slo"
    assert retry not in engine.results
    assert [t.request_id for t, _ in engine._queue] == [retry]


@pytest.mark.slow
@pytest.mark.obswatch
def test_full_obs_plane_keeps_streams_bit_identical(params, tmp_path):
    """THE acceptance pin for the active plane: spans + attribution
    ledger + SLO/anomaly watchers all attached, greedy AND sampled
    requests — streamed tokens stay bit-identical to generate(), the
    fused decode step still compiles exactly once, every request yields
    a verifiable attribution record, and the request span cascade lands
    in the trace."""
    from trustworthy_dl_tpu.obs import MetricsRegistry, ObsSession
    from trustworthy_dl_tpu.obs.events import read_jsonl
    from trustworthy_dl_tpu.obs.slo import SLORule

    session = ObsSession(str(tmp_path), registry=MetricsRegistry())
    session.enable_spans()
    # Generous targets: a healthy engine must never trip them (a breach
    # would shed, and shedding would break the parity assertion below).
    session.install_watchers(slo_rules=(
        SLORule("ttft", signal="ttft_s", target=60.0),
        SLORule("itl", signal="itl_s", target=60.0),
    ))
    session.open_ledger()
    # max_seq=64 is this file's only 64-row geometry: the strict
    # compile-once delta below must see a FRESH decode program, not a
    # process-global jit-cache hit from an earlier engine's identical
    # shapes (same trap test_quant's vocab split documents).
    engine = ServingEngine(
        params, CFG, max_slots=3, max_seq=64, queue_limit=32,
        trace=session.trace, registry=session.registry,
        spans=session.spans, ledger=session.ledger,
        slo=session.slo, anomaly=session.anomaly,
    )
    cache_before = engine.scheduler.decode_cache_size()
    key = jax.random.PRNGKey(3)
    reqs = [([5, 17, 3], 6, 0.0, None),
            ([9, 4, 33, 2], 5, 0.8, key),
            ([5, 17, 3], 4, 0.0, None)]     # shares a prefix with req 0
    rids = [engine.submit(ServeRequest(prompt=p, max_new_tokens=n,
                                       temperature=t, rng=r))
            for p, n, t, r in reqs]
    results = engine.run_until_idle()
    assert engine.scheduler.decode_cache_size() - cache_before == 1

    for rid, (prompt, new, temp, rng) in zip(rids, reqs):
        ref = generate(params, CFG, jnp.asarray([prompt], jnp.int32), new,
                       temperature=temp, rng=rng)
        assert results[rid].tokens \
            == np.asarray(ref)[0, len(prompt):].tolist(), f"request {rid}"

    # One verifiable attribution record per request.
    records = engine.ledger.records()
    assert sorted(r["request_id"] for r in records) == sorted(rids)
    ok, problems = engine.verify_attribution()
    assert ok, problems
    for r in records:
        assert r["admitted"] and r["layout"] == "paged"
        assert r["block_ids"] and r["kv_dtype"] == "model"
        assert r["token_hash"] == __import__(
            "trustworthy_dl_tpu.obs.attribution", fromlist=["token_hash"]
        ).token_hash(results[r["request_id"]].tokens)

    session.finalize()
    events = read_jsonl(str(tmp_path / "trace.jsonl"))
    spans = [e for e in events if e["type"] == "span"]
    for name in ("serve.request", "serve.queued", "serve.prefill",
                 "serve.decode", "serve.decode_tick", "serve.monitor"):
        assert any(e["name"] == name for e in spans), name
    # Attribution events correlate on request id.
    attrib = [e for e in events if e["type"] == "attribution"]
    assert sorted(e["request_id"] for e in attrib) == sorted(rids)
    # Streaming estimators took over the summary percentiles.
    summary = engine.metrics_summary()
    assert summary["itl_p50_ms"] > 0 and summary["ttft_p50_ms"] > 0
    assert not session.slo.active


@pytest.mark.slow
def test_bounded_result_retention_with_exact_rollups(params):
    """`results` retains at most retain_results finished records, while
    metrics_summary's counters/percentiles stay exact over every request
    ever retired (rollup + streaming estimators, not the ring)."""
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           queue_limit=32, retain_results=3)
    rids = [engine.submit(ServeRequest(prompt=[i + 1, i + 2],
                                       max_new_tokens=2))
            for i in range(8)]
    results = engine.run_until_idle()
    assert len(results) == 3                       # ring bound
    assert set(results) == set(rids[-3:])          # oldest evicted
    summary = engine.metrics_summary()
    assert summary["requests_completed"] == 8      # rollup is exact
    assert summary["tokens_emitted"] == 16
    assert summary["itl_p50_ms"] >= 0.0


def test_engine_feeds_its_slo_watcher_every_retirement(params):
    """The serving defaults (TTFT and ITL rules) on a watched engine:
    every completed request lands ONE TTFT observation and each gap
    between its tokens one ITL observation in the watcher's sketches,
    which ``metrics_summary`` then reads instead of its own; a healthy
    engine leaves both rules out of breach."""
    from trustworthy_dl_tpu.obs.slo import SLOWatcher, default_serve_rules

    watcher = SLOWatcher(default_serve_rules())
    engine = ServingEngine(params, CFG, max_slots=2, max_seq=32,
                           queue_limit=8, slo=watcher)
    for i in range(5):
        engine.submit(ServeRequest(prompt=[i + 1, i + 2, i + 3],
                                   max_new_tokens=2 + i % 3))
    results = engine.run_until_idle()
    assert all(r.status == "completed" for r in results.values())
    ttft = watcher.percentiles("ttft_s")
    itl = watcher.percentiles("itl_s")
    assert ttft["count"] == len(results) == 5
    assert itl["count"] == sum(len(r.tokens) - 1 for r in results.values())
    assert ttft["p50"] > 0.0 and itl["p50"] > 0.0
    status = watcher.status()
    assert {r["name"] for r in status["rules"]} == {"ttft", "itl"}
    assert all(r["burn_rate"] >= 0.0 and not r["active"]
               for r in status["rules"])
    assert status["breach_total"] == 0
    summary = engine.metrics_summary()
    assert summary["ttft_p50_ms"] == watcher.quantile("ttft_s", 0.5) * 1e3
    assert summary["itl_p99_ms"] == watcher.quantile("itl_s", 0.99) * 1e3
