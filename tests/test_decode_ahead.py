"""The decode call dispatched a tick ahead of its record
(``PagedBatchingScheduler.decode_tick``): a tick dispatches the NEXT tick's
decode call before it pulls the one the tick before it dispatched, the
call's input tokens kept on the device (the scheduler's ``carry``).

Pinned here, for a tiny GPT-2 and a tiny Falcon-H1 hybrid (Mamba-2 state
rows beside rotary attention) on the CPU:

* streams are ``generate()``'s (GPT-2) or the plain reference's first
  choices (the hybrid), and a request's tokens, entropies and margins in a
  batch are bit-identical to the same request served alone;
* every tick brings what a tick that dispatched and pulled its own decode
  call brings: a request's first token in the tick that runs its final
  chunk, then one token a tick;
* a row decoded past a stream's end (EOS, cancel, a flagged request) is
  thrown away, and the next request admitted to the slot, its blocks and
  its state row serves its solo stream;
* ``cancel``, ``release_quarantine`` and a live migration pull the call in
  flight first (``settle``) and leave every stream whole;
* the counters: ``decode_calls``, ``decode_ahead_calls``,
  ``decode_settles``, ``decode_overrun_rows``, in ``metrics_summary()``
  and the registry; each program keeps one compiled size.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_hybrid_serving import TINY as HYBRID_TINY
from trustworthy_dl_tpu.models import gpt2
from trustworthy_dl_tpu.models.generate import generate
from trustworthy_dl_tpu.obs.registry import MetricsRegistry
from trustworthy_dl_tpu.serve import ServeRequest, ServingEngine
from trustworthy_dl_tpu.serve import scheduler as sch
from trustworthy_dl_tpu.serve.engine import DECODE_COUNTERS
from trustworthy_dl_tpu.serve.migrate import migrate_request

# A vocabulary no other test file uses: the compile counts below read the
# process-wide jit cache.
GPT2 = gpt2.GPT2Config(vocab_size=181, n_positions=64, n_layer=2,
                       n_embd=32, n_head=4, dtype=jnp.float32)
MAX_SEQ, BLOCK, CHUNK = 48, 8, 8


class Model:
    """One of the two descriptions: its engine and its reference stream."""

    def __init__(self, name):
        self.name = name
        if name == "gpt2":
            self.cfg = GPT2
            self.params = gpt2.init_params(jax.random.PRNGKey(0), GPT2)
            self.vocab = GPT2.vocab_size
        else:
            from benchmark.harness.families import falcon_h1

            self.family = falcon_h1
            self.cfg = dataclasses.replace(falcon_h1.model(HYBRID_TINY),
                                           dtype=jnp.float32)
            self.params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32),
                falcon_h1.make_weights(3, HYBRID_TINY))
            self.vocab = HYBRID_TINY["vocab_size"]

    def engine(self, max_slots=2, **kwargs):
        kwargs.setdefault("registry", MetricsRegistry())
        if self.name != "gpt2":
            kwargs.update(prefix_cache=False, attn_impl="jnp")
        return ServingEngine(self.params, self.cfg, max_slots=max_slots,
                             max_seq=MAX_SEQ, block_size=BLOCK,
                             prefill_chunk=CHUNK, queue_limit=16, **kwargs)

    def reference(self, prompt, tokens, temperature=0.0, rng=None):
        """The stream ``tokens`` ought to be: generate()'s for GPT-2, the
        reference's first choices after each of them for the hybrid."""
        if self.name == "gpt2":
            out = generate(self.params, self.cfg,
                           jnp.asarray([prompt], jnp.int32), len(tokens),
                           temperature=temperature, rng=rng)
            return np.asarray(out)[0, len(prompt):].tolist()
        return [int(t) for t in self.family.chosen_tokens(
            self.family.reply_logits(self.params, np.asarray(prompt),
                                     tokens, HYBRID_TINY, 8))]


@pytest.fixture(scope="module", params=["gpt2", "hybrid"])
def model(request):
    return Model(request.param)


@pytest.fixture(scope="module", autouse=True)
def _a_registry_of_this_file_s_own():
    """Engines built without a registry of their own (the fleet's replicas)
    fill the process-wide one; leave it as this file found it."""
    from trustworthy_dl_tpu.obs import registry

    was = registry._DEFAULT_REGISTRY
    registry._DEFAULT_REGISTRY = registry.MetricsRegistry()
    yield
    registry._DEFAULT_REGISTRY = was


def prompts(seed, lengths, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lengths]


class Drive:
    """An engine stepped by the test: the tick each token streamed in, the
    tick each request was admitted in, the signals each request retired
    with, and the decode counters after every tick."""

    def __init__(self, engine):
        self.engine = engine
        self.ticks = 0
        self.streamed = {}           # rid -> [(tick, token)]
        self.admitted = {}           # rid -> tick
        self.retired = {}            # rid -> (slot, tokens, ents, margins)
        self.counters = []           # after each tick
        sched = engine.scheduler
        admit, retire = sched.admit, sched.retire

        def admitted(task):
            ok = admit(task)
            if ok:
                self.admitted[task.request_id] = self.ticks
            return ok

        def retired(task, quarantine=False):
            if sched.tasks.get(task.slot) is task:
                self.retired[task.request_id] = (
                    task.slot, list(task.emitted), list(task.entropies),
                    list(task.margins))
            retire(task, quarantine=quarantine)

        sched.admit, sched.retire = admitted, retired

    def submit(self, prompt, new, **kwargs):
        return self.engine.submit(ServeRequest(
            prompt=prompt, max_new_tokens=new,
            on_token=lambda r, t: self.streamed.setdefault(r, []).append(
                (self.ticks, t)), **kwargs))

    def step(self):
        self.ticks += 1
        self.engine.step()
        self.counters.append({name: getattr(self.engine.scheduler, name)
                              for name in DECODE_COUNTERS})

    def run(self, until=None):
        for _ in range(200):
            if until is not None and until():
                return
            if until is None and not self.engine.busy:
                return
            self.step()
        raise AssertionError("the drive did not end")

    def tokens(self, rid):
        return [t for _, t in self.streamed.get(rid, [])]

    def tick_of_each(self, rid):
        return [tick for tick, _ in self.streamed.get(rid, [])]


def solo(model, prompt, new, **kwargs):
    """``prompt`` served alone: its tokens, entropies and margins."""
    drive = Drive(model.engine())
    rid = drive.submit(prompt, new, **kwargs)
    drive.run()
    _, tokens, ents, margins = drive.retired[rid]
    return tokens, ents, margins


def program_sizes():
    return {name: (prog._cache_size() if name in sch._PROGRAMS else 0)
            for name, prog in sch._programs().items()
            if name in ("paged_prefill", "paged_chunk", "paged_decode")}


def test_streams_and_the_tokens_each_tick_brings_are_today_s(model):
    """More requests than slots, prompts of one to four chunks: every
    stream is the reference's, bit-identical in tokens, entropies and
    margins to the request served alone; each request's first token comes
    in the tick that runs its final chunk and then one a tick; every decode
    call but one after a tick that dispatched none is dispatched ahead;
    nothing is settled or thrown away; and each program has one size."""
    before = program_sizes()
    drive = Drive(model.engine())
    lengths = (5, 20, 11, 27, 8, 17)
    replies = (4, 6, 3, 5, 1, 7)
    rids = []
    for i, (prompt, new) in enumerate(zip(
            prompts(1, lengths, model.vocab), replies)):
        kwargs = {}
        if model.name == "gpt2" and i == 1:      # one sampled stream
            kwargs = dict(temperature=0.8, rng=jax.random.PRNGKey(7))
        rids.append((drive.submit(prompt, new, **kwargs), prompt, new,
                     kwargs))
    drive.run()
    for rid, prompt, new, kwargs in rids:
        tokens = drive.tokens(rid)
        assert len(tokens) == new
        assert drive.engine.results[rid].tokens == tokens
        assert tokens == model.reference(
            prompt, tokens, kwargs.get("temperature", 0.0),
            kwargs.get("rng")), rid
        assert drive.retired[rid][1:] == solo(model, prompt, new, **kwargs)
        first = drive.admitted[rid] + -(-len(prompt) // CHUNK) - 1
        assert drive.tick_of_each(rid) == list(range(first, first + new))
    summary = drive.engine.metrics_summary()
    calls = [c["decode_calls"] for c in drive.counters]
    dispatched = np.diff([0] + calls)
    assert set(dispatched) <= {0, 1}
    ahead = sum(1 for was, now in zip(dispatched, dispatched[1:])
                if was and now)
    assert summary["decode_calls"] == calls[-1] == sum(dispatched) > 0
    assert summary["decode_ahead_calls"] == ahead > 0
    assert summary["decode_settles"] == summary["decode_overrun_rows"] == 0
    series = drive.engine._decode_counters
    for name in DECODE_COUNTERS:
        assert (series[name].value() or 0) == summary[name], name
    grown = {name: n - before[name] for name, n in program_sizes().items()}
    assert grown.pop("paged_decode") <= 1 and grown.pop("paged_chunk") <= 1
    assert grown.get("paged_prefill", 0) <= 1
    assert drive.engine.scheduler.decode_cache_size() >= 1


def _eos_of(stream):
    """A token whose first place in ``stream`` is past the first token, where
    there is one, and the number of tokens up to and with it (a stream cut
    before its last token, so that a row is dispatched past the EOS)."""
    for i, token in enumerate(stream[1:], 1):
        if token not in stream[:i]:
            return token, i + 1
    return stream[0], 1


def test_a_row_past_an_eos_is_thrown_away_and_the_slot_serves_its_next(
        model):
    """A request that ends on its EOS is in the decode call already
    dispatched: that row is decoded and thrown away, and the next request,
    admitted to the same slot (and blocks, and state row) while the row is
    still in flight, serves the stream it serves alone."""
    first, other, after = prompts(2, (6, 19, 13), model.vocab)
    alone, _, _ = solo(model, first, 6)
    eos, stop = _eos_of(alone[:-1])
    drive = Drive(model.engine())
    a = drive.submit(first, 6, eos_id=eos)
    b = drive.submit(other, 7)
    c = drive.submit(after, 5)
    drive.run()
    assert drive.tokens(a) == alone[:stop] == drive.engine.results[a].tokens
    assert drive.retired[c][0] == drive.retired[a][0]       # the same slot
    assert drive.admitted[c] == drive.tick_of_each(a)[-1] + 1
    assert drive.retired[c][1:] == solo(model, after, 5)
    assert drive.retired[b][1:] == solo(model, other, 7)
    assert drive.tokens(c) == model.reference(after, drive.tokens(c))
    summary = drive.engine.metrics_summary()
    assert summary["decode_overrun_rows"] == 1
    assert summary["decode_settles"] == 0


class _FlagsTheFirst:
    """An output monitor whose verdict flags the first request it scores."""

    def __init__(self):
        self.count = 0

    def observe(self, entropies, margins):
        self.count += 1
        return self.count == 1, 9.0


@pytest.mark.parametrize("how", ["cancel", "cancel_quarantined",
                                 "flagged_then_released"])
def test_a_slot_freed_outside_a_tick_settles_first(model, how):
    """``cancel`` (freeing or impounding the slot) and ``release_quarantine``
    of a slot whose flagged request's row is still in flight pull the
    decode call first, once; the cancelled request keeps what it streamed,
    the other request's stream is whole, and the next request admitted to
    the slot serves its solo stream."""
    first, other, after = prompts(3, (6, 19, 13), model.vocab)
    kwargs = {}
    if how == "flagged_then_released":
        kwargs["monitor"] = _FlagsTheFirst()
    drive = Drive(model.engine(**kwargs))
    new_a = 6
    eos = None
    if how == "flagged_then_released":
        alone, _, _ = solo(model, first, new_a)
        eos, stop = _eos_of(alone[:-1])
    a = drive.submit(first, new_a, eos_id=eos)
    b = drive.submit(other, 7)
    sched = drive.engine.scheduler
    if how.startswith("cancel"):
        drive.run(until=lambda: len(drive.tokens(a)) >= 2)
        slot = next(s for s, t in sched.tasks.items() if t.request_id == a)
        assert slot in sched._decode_call.rows           # a row in flight
        streamed = drive.tokens(a)
        assert drive.engine.cancel(a, quarantine=how.endswith("quarantined"))
        assert drive.engine.results[a].tokens == streamed
        assert streamed == solo(model, first, new_a)[0][:len(streamed)]
        if how.endswith("quarantined"):
            assert drive.engine.quarantined_slots == {slot}
            drive.engine.release_quarantine(slot)
    else:
        drive.run(until=lambda: a in drive.engine.results)
        assert drive.engine.results[a].flagged
        slot = drive.retired[a][0]
        assert drive.engine.quarantined_slots == {slot}
        assert slot in sched._decode_call.rows           # the EOS overrun
        assert drive.engine.metrics_summary()["decode_settles"] == 0
        drive.engine.release_quarantine(slot)
        assert drive.tokens(a) == alone[:stop]
    assert drive.engine.metrics_summary()["decode_settles"] == 1
    assert not drive.engine.quarantined_slots
    c = drive.submit(after, 5)
    drive.run()
    assert drive.retired[c][0] == slot
    assert drive.retired[c][1:] == solo(model, after, 5)
    assert drive.retired[b][1:] == solo(model, other, 7)
    summary = drive.engine.metrics_summary()
    assert summary["decode_settles"] == 1
    assert summary["decode_overrun_rows"] == 1


def test_a_migration_settles_first_and_the_stream_goes_on_whole():
    """GPT-2 (a state row has no snapshot yet): the source pulls its call in
    flight before the snapshot, whose length is what was recorded; the
    destination, busy with its own stream and a call in flight, decodes
    the migrated request in a call of its own in the next tick, so the
    stream goes on a token a tick; every token streams exactly once and
    the stream is generate()'s."""
    model = Model("gpt2")
    first, other = prompts(4, (10, 15), model.vocab)
    src, dst = Drive(model.engine()), Drive(model.engine())
    streamed = []
    rid = src.engine.submit(ServeRequest(
        prompt=first, max_new_tokens=8,
        on_token=lambda r, t: streamed.append((src.ticks, t))))
    busy = dst.submit(other, 9)
    src.run(until=lambda: len(streamed) >= 3)
    dst.run(until=lambda: len(dst.tokens(busy)) >= 2)
    assert src.engine.scheduler._decode_call is not None
    assert dst.engine.scheduler._decode_call is not None
    moved = migrate_request(
        src.engine, dst.engine, rid,
        on_token=lambda r, t: streamed.append((src.ticks + dst.ticks, t)))
    assert moved is not None
    assert src.engine.metrics_summary()["decode_settles"] == 1
    assert src.engine.metrics_summary()["decode_overrun_rows"] == 1
    calls = dst.engine.scheduler.decode_calls
    at = len(streamed)
    dst.step()
    assert dst.engine.scheduler.decode_calls == calls + 2   # catch-up too
    assert len(streamed) == at + 1
    dst.run()
    tokens = [t for _, t in streamed]
    assert tokens == model.reference(first, tokens)
    assert dst.engine.results[moved["local_id"]].tokens == tokens
    assert dst.tokens(busy) == model.reference(other, dst.tokens(busy))
    assert dst.engine.metrics_summary()["decode_settles"] == 0


def test_the_carry_feeds_the_next_call_and_a_host_token_overrides_it():
    """The decode program's token input: a row's host token where it is
    not negative, the carry's otherwise; the active rows' sampled tokens
    go into the carry, the others keep theirs.  The chunk program writes
    its final rows' tokens at their slots and drops every other row's."""
    from trustworthy_dl_tpu.models import generate as gen
    from trustworthy_dl_tpu.serve.kv_slots import TRASH_BLOCK, init_paged_pool

    params = gpt2.init_params(jax.random.PRNGKey(0), GPT2)
    view = gen._decode_view(params, GPT2)
    slots, nbps = 3, MAX_SEQ // BLOCK
    kv = init_paged_pool(GPT2, slots * nbps + 1, BLOCK)
    tables = jnp.asarray(np.arange(1, slots * nbps + 1, dtype=np.int32)
                         .reshape(slots, nbps))
    keys = jnp.zeros((slots, 2), jnp.uint32)
    ones, greedy = jnp.ones(slots), jnp.ones(slots, bool)
    carry = jnp.asarray([11, 22, 33], jnp.int32)
    active = jnp.asarray([True, True, False])
    lengths = jnp.asarray([4, 4, 4], jnp.int32)

    def decode(tokens, carry):
        out = sch._paged_decode_impl(
            GPT2, kv.k, kv.v, None, None, view, jnp.asarray(tokens,
                                                            jnp.int32),
            tables, lengths, keys, ones, greedy, active=active, carry=carry)
        return np.asarray(out[0][0]).astype(int), np.asarray(out[-1])

    fed, new_carry = decode([-1, 22, 5], carry)
    plain, _ = decode([11, 22, 5], None)
    assert (fed == plain).all()
    assert new_carry.tolist() == [fed[0], fed[1], 33]
    tokens = jnp.asarray(np.arange(1, 2 * CHUNK + 1).reshape(2, CHUNK),
                         jnp.int32)
    out = sch._paged_chunk_impl(
        GPT2, kv.k, kv.v, None, None, view, tokens,
        jnp.full((2, nbps), TRASH_BLOCK, jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.full(2, CHUNK - 1, jnp.int32),
        jnp.zeros((2, 2), jnp.uint32), jnp.ones(2), jnp.ones(2, bool),
        carry=carry, carry_rows=jnp.asarray([2, slots], jnp.int32))
    first = int(np.asarray(out[4])[0, 0])
    assert np.asarray(out[-1]).tolist() == [11, 22, first]
