"""The serving program's tick and request spans (``serve.*`` through
``utils.profiling.span`` into the engine's one timer): which spans a tick
opens and how many, whose request each works for, exact nesting on one
clock, the totals behind ``metrics_summary()``'s fractions, the
since-the-last-summary scope, and what is kept where nothing listens.  A
tiny paged engine on the CPU: structure and counts, never a time."""

import collections
import contextlib
import glob
import os
import time
import types

import pytest

import jax

from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.obs.spans import SpanTracker
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
from trustworthy_dl_tpu.serve import engine as engine_module
from trustworthy_dl_tpu.serve import scheduler as scheduler_module

# A vocabulary no other test file uses, so that the compile-once counts of
# the process-wide jit cache elsewhere do not see these programs.
CFG = gpt2.GPT2Config(vocab_size=179, n_positions=64, n_layer=2, n_embd=32,
                      n_head=4)
CHUNK = 8
#: (prompt length, reply length): prompts of two to four chunks, more
#: requests than slots, so that ticks admit, prefill, decode and retire
#: side by side.
SHAPES = ((20, 4), (11, 3), (27, 5), (9, 3), (17, 4))
#: The spans every tick opens, and those of one decode call.
TICK_SPANS = ("serve.tick", "serve.tick.expire", "serve.tick.admit",
              "serve.decode_tick", "serve.tick.emit", "serve.tick.account")
DECODE_SPANS = ("serve.decode_tick.build", "serve.decode_tick.dispatch",
                "serve.decode_tick.pull", "serve.decode_tick.record")
#: child -> the span it lies inside, for every span this drive opens.
PARENT = {
    "serve.submit.key_stream": "serve.submit",
    "serve.tick.expire": "serve.tick", "serve.tick.admit": "serve.tick",
    "serve.decode_tick": "serve.tick", "serve.tick.emit": "serve.tick",
    "serve.tick.account": "serve.tick",
    "serve.prefix_lookup": "serve.tick.admit",
    "serve.prefill_chunk": "serve.decode_tick",
    "serve.prefill_chunk.dispatch": "serve.prefill_chunk",
    # a chunk call is pulled after the decode call's dispatch
    "serve.prefill_chunk.pull": "serve.decode_tick",
    **{name: "serve.decode_tick" for name in DECODE_SPANS},
    "serve.tick.retire": "serve.tick.emit",
    "serve.monitor": "serve.tick.retire",
}
#: The spans of a request that cross ticks: the tracker's own, no phase.
REQUEST_SPANS = ("serve.request", "serve.queued", "serve.prefill",
                 "serve.decode")


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.PRNGKey(0), CFG)


def build(params, **kwargs):
    kwargs.setdefault("registry", MetricsRegistry())
    return ServingEngine(params, CFG, max_slots=3, max_seq=56, block_size=8,
                         prefill_chunk=CHUNK, queue_limit=16, **kwargs)


def submit_all(engine):
    return [engine.submit(ServeRequest(
        prompt=[(7 * i + j) % 170 + 1 for j in range(plen)],
        max_new_tokens=new)) for i, (plen, new) in enumerate(SHAPES)]


@contextlib.contextmanager
def counted_programs():
    """The scheduler's jitted programs, each call counted by name: what a
    ``*.dispatch`` span is held to, from outside the spans."""
    calls = collections.Counter()

    def counting(name, program):
        def call(*args, **kwargs):
            calls[name] += 1
            return program(*args, **kwargs)
        return call

    wrapped = {name: counting(name, program) for name, program
               in scheduler_module._programs().items()}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(scheduler_module, "_programs", lambda: wrapped)
        yield calls


@pytest.fixture(scope="module")
def drive(params):
    """One drive with a tracker attached: the engine, the ids ``submit``
    returned, and for every tick what the program calls and the results
    (not the spans) say it held."""
    engine = build(params, spans=SpanTracker())
    rids = submit_all(engine)
    ticks = []
    with counted_programs() as calls:
        while engine.busy:
            before, done = calls.copy(), len(engine.results)
            engine.step()
            ticks.append({
                "chunks": calls["paged_chunk"] - before["paged_chunk"],
                "decoded": calls["paged_decode"] - before["paged_decode"],
                "retired": len(engine.results) - done})
        assert set(calls) == {"paged_chunk", "paged_decode"}
    return engine, rids, ticks


def by_tick(engine):
    """The phase spans of each tick, in order: a list of spans a tick."""
    spans = [s for s in engine.spans.closed_spans()
             if s.name not in REQUEST_SPANS]
    roots = sorted((s for s in spans if s.name == "serve.tick"),
                   key=lambda s: s.start)
    return [[s for s in spans if root.start <= s.start and s.end <= root.end]
            for root in roots]


def test_a_tick_opens_its_phases_once_and_a_chunk_span_a_prefilling_slot(
        drive):
    engine, rids, ticks = drive
    assert sorted(rids) == list(range(len(SHAPES)))
    grouped = by_tick(engine)
    assert len(grouped) == len(ticks) == engine.metrics_summary()[
        "iterations"]
    for spans, tick, before in zip(grouped, ticks,
                                   [{"decoded": 0}] + ticks):
        names = [s.name for s in spans]
        for name in TICK_SPANS:
            assert names.count(name) == 1, name
        # a tick builds and dispatches the next tick's decode call and
        # pulls and records the one the tick before it dispatched
        for name in DECODE_SPANS[:2]:
            assert names.count(name) == tick["decoded"] <= 1, name
        for name in DECODE_SPANS[2:]:
            assert names.count(name) == before["decoded"] <= 1, name
        chunks = [s for s in spans if s.name == "serve.prefill_chunk"]
        # ONE call holds a chunk of every mid-prefill slot, padded up to
        # the call's rows
        assert len(chunks) == tick["chunks"] <= 1
        for s in chunks:
            rows = s.attrs["rows"]
            assert 0 <= s.attrs["final"] <= rows
            assert rows + s.attrs["padded"] == engine.scheduler.chunk_rows
        assert names.count("serve.prefill_chunk.dispatch") == len(chunks)
        assert names.count("serve.prefill_chunk.pull") == sum(
            s.attrs["final"] > 0 for s in chunks)
        assert names.count("serve.tick.retire") == tick["retired"]
    # every request was fed its whole prompt, a chunk a tick, and each
    # prompt ended in one call
    calls = [s for spans in grouped for s in spans
             if s.name == "serve.prefill_chunk"]
    assert sum(s.attrs["rows"] for s in calls) == sum(
        -(-plen // CHUNK) for plen, _ in SHAPES)
    assert sum(s.attrs["final"] for s in calls) == len(rids)
    assert max(s.attrs["rows"] for s in calls) == 3    # every slot at once


def test_the_spans_a_tick_opens_are_bounded_by_what_it_holds(drive):
    """The hot path's cost, pinned by count: six spans a tick, four a decode
    call, three a chunk (two where it is not a prompt's last), one an
    admission's lookup, two a retirement."""
    engine, _, ticks = drive
    for spans, tick in zip(by_tick(engine), ticks):
        admitted = next(s.attrs["admitted"] for s in spans
                        if s.name == "serve.tick.admit")
        bound = (len(TICK_SPANS) + len(DECODE_SPANS) + 3 * tick["chunks"]
                 + admitted + 2 * tick["retired"])
        assert len(spans) <= bound, sorted(s.name for s in spans)
    totals = engine.timer.span_totals()
    assert totals["serve.tick"][0] == len(ticks)
    assert set(totals) <= set(PARENT) | {"serve.tick", "serve.submit"}


def test_every_child_lies_inside_its_parent_exactly(drive):
    """One pair of clock reads a span and one clock for all: no tolerance."""
    engine, _, _ = drive
    spans = engine.spans.closed_spans()
    named = {}
    for s in spans:
        named.setdefault(s.name, []).append(s)
    children = [s for s in spans if s.name in PARENT]
    assert {s.name for s in children} == set(PARENT)
    for child in children:
        assert any(p.start <= child.start and child.end <= p.end
                   for p in named[PARENT[child.name]]), child.name


def test_a_request_s_phase_spans_carry_its_id_and_its_root(drive):
    engine, rids, _ = drive
    spans = engine.spans.closed_spans()
    roots = {s.request_id: s.span_id for s in spans
             if s.name == "serve.request"}
    assert sorted(roots) == sorted(rids)
    for name in ("serve.tick.retire", "serve.monitor"):
        mine = [s for s in spans if s.name == name]
        assert mine and all(s.parent_id == roots[s.request_id]
                            for s in mine), name
    # a chunk call works for one request only where it holds one row
    chunks = [s for s in spans if s.name == "serve.prefill_chunk"]
    assert all((s.request_id is not None) == (s.attrs["rows"] == 1)
               for s in chunks)
    ones = [s for s in chunks if s.request_id is not None]
    assert ones and all(s.parent_id == roots[s.request_id] for s in ones)
    submits = [s for s in spans if s.name == "serve.submit"]
    assert [s.request_id for s in submits] == rids
    assert all(s.kind == "serve" for s in spans)


def test_the_fractions_are_the_totals_over_elapsed(drive):
    engine, _, _ = drive
    before = time.perf_counter() - engine._t_start
    summary = engine.metrics_summary()
    after = time.perf_counter() - engine._t_start
    phases = summary["tick_phases"]
    for key, name in (("decode_tick_fraction", "serve.decode_tick"),
                      ("prefill_chunk_fraction",
                       "serve.prefill_chunk.dispatch")):
        seconds = phases[name]["seconds"]
        assert seconds == engine.timer.span_totals()[name][1] > 0.0
        assert seconds / after <= summary[key] <= seconds / before, key
    assert summary["spec_verify_fraction"] == 0.0    # no such span here
    assert phases["serve.tick"]["longest_s"] == max(
        s.duration_s for s in engine.spans.closed_spans()
        if s.name == "serve.tick")


def test_a_dispatch_span_is_one_program_call_and_a_pull_one_wait(drive):
    """What ``serve_dispatches_per_tick`` and ``serve_host_syncs_per_tick``
    count: the phases' counts ARE the program calls and the pulls."""
    engine, _, ticks = drive
    phases = engine.metrics_summary()["tick_phases"]
    decode_ticks = sum(t["decoded"] for t in ticks)
    finishing = [s for s in engine.spans.closed_spans()
                 if s.name == "serve.prefill_chunk" and s.attrs["final"]]
    assert phases["serve.tick"]["count"] == len(ticks)
    assert phases["serve.prefill_chunk.dispatch"]["count"] \
        == sum(t["chunks"] for t in ticks) \
        == sum(t["chunks"] > 0 for t in ticks)
    assert phases["serve.decode_tick.dispatch"]["count"] == decode_ticks
    # a chunk call that finishes prompts pulls their first tokens once, a
    # decode call its rows, and a submit its keys
    assert phases["serve.prefill_chunk.pull"]["count"] == len(finishing) \
        <= len(SHAPES)
    assert phases["serve.decode_tick.pull"]["count"] == decode_ticks
    assert phases["serve.submit.key_stream"]["count"] == len(SHAPES)


def test_a_monitor_span_is_one_verdict_in_the_retirement_it_judges(drive):
    """``serve.monitor`` stays round every verdict wherever the baseline
    lives: its count is the requests scored (what a reader divides its
    seconds by), and the verdict it notes is the one its retirement put
    into the result."""
    engine, rids, ticks = drive
    phases = engine.metrics_summary()["tick_phases"]
    results = [engine.results[rid] for rid in rids]
    scored = phases["serve.monitor"]["count"]
    assert scored == len(rids) == phases["serve.tick.retire"]["count"] \
        == sum(t["retired"] for t in ticks)
    assert scored == engine.monitor.count + sum(r.flagged for r in results)
    noted = {s.request_id: s.attrs for s in engine.spans.closed_spans()
             if s.name == "serve.monitor"}
    assert {rid: (a["flagged"], a["monitor_z"]) for rid, a in noted.items()} \
        == {r.request_id: (r.flagged, r.monitor_z) for r in results}


def test_a_speculative_tick_s_dispatch_spans_are_its_program_calls(params):
    """``spec_k`` drafts and one verify a tick, a ``.dispatch`` span each,
    one ``.pull`` a half: the rule holds for every engine."""
    engine = build(params, spec_k=2)
    submit_all(engine)
    with counted_programs() as calls:
        engine.run_until_idle()
    totals = engine.timer.span_totals()
    spec_ticks = engine.scheduler.spec_ticks
    assert spec_ticks > 0
    assert calls["spec_draft"] == 2 * spec_ticks
    assert calls["spec_verify"] == spec_ticks
    for name, program in (("serve.spec_draft", "spec_draft"),
                          ("serve.spec_verify", "spec_verify"),
                          ("serve.prefill_chunk", "paged_chunk")):
        assert totals[name + ".dispatch"][0] == calls[program], name
        if program != "paged_chunk":
            assert totals[name][0] == totals[name + ".pull"][0] \
                == spec_ticks, name
    decode = totals.get("serve.decode_tick.dispatch", (0,))[0]
    assert decode == calls["paged_decode"]      # the one-token fallback
    assert sum(t[0] for name, t in totals.items()
               if name.endswith(".dispatch")) == sum(calls.values())


def test_since_the_last_summary_is_the_window_between_two_summaries(params):
    """Phases and the expert counters alike, through the one helper; the
    registry holds both scopes."""
    registry = MetricsRegistry()
    engine = build(params, registry=registry)
    asked = []
    reckon = engine._since_last_summary
    engine._since_last_summary = lambda what, now: (
        asked.append(what), reckon(what, now))[1]
    submit_all(engine)
    for _ in range(3):
        engine.step()
    first = engine.metrics_summary()
    assert asked == ["phases"]
    window = engine_module.SINCE_LAST
    # the first summary's window is the whole run so far
    assert first["tick_phases"][window]["serve.tick"]["count"] == 3
    longest = []
    for _ in range(2):
        engine.step()
        longest.append(engine.timer._spans["serve.tick"][-1][0])
    second = engine.metrics_summary()
    phases = second["tick_phases"]
    assert phases["serve.tick"]["count"] == 5
    assert phases[window]["serve.tick"]["count"] == 2
    assert phases[window]["serve.tick"]["seconds"] == pytest.approx(
        phases["serve.tick"]["seconds"]
        - first["tick_phases"]["serve.tick"]["seconds"])
    assert phases[window]["serve.tick"]["longest_s"] == max(longest)
    assert phases[window]["serve.submit"] == {
        "count": 0, "seconds": 0.0, "longest_s": 0.0}
    metrics = registry.snapshot()["metrics"]
    count = {(s["labels"]["phase"], s["labels"]["scope"]): s["value"]
             for s in metrics["tddl_serve_phase_count"]["series"]}
    assert count[("serve.tick", "total")] == 5
    assert count[("serve.tick", window)] == 2
    # the expert counters take the same road (a description with routed
    # experts hands them in; the device's token counter wraps at 2**32)
    engine.cfg = types.SimpleNamespace(first_expert=4)
    engine._expert_summary({"held_expert_pairs": [5, 1],
                            "tokens_fed": (1 << 32) - 2})
    moe = engine._expert_summary({"held_expert_pairs": [9, 1],
                                  "tokens_fed": 3})
    assert asked[-2:] == ["experts", "experts"]
    assert moe[window] == {"held_expert_pairs": [4, 0], "tokens_fed": 5}


def test_the_chunk_calls_rows_are_tallied_in_both_scopes(params):
    """``serve.prefill_chunk`` carries its calls' real and padding rows in
    ``tick_phases`` and in the registry, totals and the window between two
    summaries, as the spans noted them."""
    registry = MetricsRegistry()
    engine = build(params, registry=registry, spans=SpanTracker())
    submit_all(engine)
    for _ in range(3):
        engine.step()
    first = engine.metrics_summary()["tick_phases"]
    engine.run_until_idle()
    phases = engine.metrics_summary()["tick_phases"]
    calls = [s for s in engine.spans.closed_spans()
             if s.name == "serve.prefill_chunk"]
    rows = sum(s.attrs["rows"] for s in calls)
    padded = sum(s.attrs["padded"] for s in calls)
    assert rows == sum(-(-plen // CHUNK) for plen, _ in SHAPES)
    assert phases["serve.prefill_chunk"]["rows"] == rows
    assert phases["serve.prefill_chunk"]["padded"] == padded
    assert engine.scheduler.chunk_rows == 3         # three slots
    assert padded == sum(3 - s.attrs["rows"] for s in calls) > 0
    window = phases[engine_module.SINCE_LAST]["serve.prefill_chunk"]
    assert window["rows"] == rows - first["serve.prefill_chunk"]["rows"] > 0
    assert window["count"] == len(calls) - first["serve.prefill_chunk"][
        "count"]
    tally = {(s["labels"]["kind"], s["labels"]["scope"]): s["value"]
             for s in registry.snapshot()["metrics"][
                 "tddl_serve_phase_tally"]["series"]
             if s["labels"]["phase"] == "serve.prefill_chunk"}
    assert tally == {("rows", "total"): rows, ("padded", "total"): padded,
                     ("rows", engine_module.SINCE_LAST): window["rows"],
                     ("padded", engine_module.SINCE_LAST): window["padded"]}
    # a phase that tallies nothing keeps its three fields
    assert set(phases["serve.tick"]) == set(engine_module.PHASE_FIELDS)


def test_with_nothing_attached_nothing_grows_without_bound(params):
    """The state of every timed window: no tracker, no profiler."""
    engine = build(params)
    assert engine.spans is None
    for _ in range(3):
        submit_all(engine)
        engine.run_until_idle()
        engine.drain_results()
    timer = engine.timer
    assert timer.spans is None and not timer._steps
    assert set(timer._spans) == set(timer.span_totals())
    # the newest span a name; the totals carry the rest
    assert all(ring.maxlen == len(ring) == 1
               for ring in timer._spans.values())
    assert timer.span_totals()["serve.prefill_chunk"][0] > 1
    assert not engine._req_spans and not engine._timing
    assert all(task.span_root is None for task, _ in engine._queue)


def test_the_spans_stand_on_the_host_plane_of_a_profile(params, tmp_path):
    """Under ``jax.profiler.trace`` the phases are on ``/host:CPU``, the
    device ops' clock (on the chip through the tool where the CPU profiler
    writes no host plane)."""
    engine = build(params)
    submit_all(engine)                 # compile outside the profile
    engine.run_until_idle()
    engine.metrics_summary()           # the window starts here
    with jax.profiler.trace(str(tmp_path)):
        submit_all(engine)
        engine.run_until_idle()
    xplanes = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    if not xplanes:
        return
    data = jax.profiler.ProfileData.from_file(xplanes[0])
    names = [e.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events]
    if not names:
        return
    for name in ("serve.submit", "serve.tick", "serve.prefill_chunk",
                 "serve.prefill_chunk.dispatch", "serve.decode_tick.pull",
                 "serve.tick.retire", "serve.monitor"):
        assert name in names, name
    assert names.count("serve.tick") == engine.metrics_summary()[
        "tick_phases"][engine_module.SINCE_LAST]["serve.tick"]["count"]
