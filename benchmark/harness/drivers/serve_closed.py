"""Traffic kind ``serve-closed``: the paged serving engine under a closed
loop of clients, whole ticks.

Drives ``ServingEngine`` as ``cli.serve_main`` builds it, through its public
surface only: ``from_config``, ``submit``, ``step``, ``drain_results``,
``metrics_summary``, ``ServeRequest`` with ``on_token``, ``ServeResult``
(and, where the monitor flags a request, ``quarantined_slots`` and
``release_quarantine``: the driver is the operator too).
Every client has one request in flight; the moment the tick that brought a
request's last token has returned, its client submits the next, so every
slot stays taken.  The mix's file fixes the lengths and their order
(``serve_traffic``); ``--seed`` decides the weights and the token ids.

Set-up builds the engine, warms what ``submit`` compiles for each distinct
reply length, hands out the first wave client by client, ``ramp_ticks``
ticks apart (so the slots' phases are spread from the start), and runs the
loop until every client has submitted and ``warm_completed`` requests have
finished.  The window opens at the next tick boundary, counts the OUTPUT
tokens of every tick that begins while less than ``--seconds`` have passed,
and closes when that tick's tokens are on the host and its freed clients
have submitted again.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.harness import (common, correct_serve, serve_trace,
                               serve_traffic)
from benchmark.harness.window import Window


def build_engine(run: Any) -> Any:
    """The engine as ``cli.serve_main`` builds it from the configuration's
    ``ServeConfig``, monitor on, with the weights and the model description
    of the configuration's family."""
    import jax

    from trustworthy_dl_tpu.core.config import ServeConfig
    from trustworthy_dl_tpu.serve import ServingEngine

    family = run.family
    deployment = run.config["deployment"]
    serve_config = ServeConfig(**deployment["serve_config"])
    params = family.make_weights(run.seed, run.config)
    return ServingEngine.from_config(
        params, family.model(run.config), serve_config,
        enable_monitor=bool(deployment["enable_monitor"]),
        rng=jax.random.PRNGKey(int(run.seed) % (1 << 31)))


def warm_key_streams(reply_lengths: List[int]) -> int:
    """``submit`` splits a request's key into ``max_new_tokens`` parts, one
    XLA program a distinct count (PERF.md section 7): run each once, so that
    no submit of the window compiles.  A program that no longer has the
    function has nothing to warm."""
    import jax

    try:
        from trustworthy_dl_tpu.serve.scheduler import request_key_stream
    except ImportError:
        return 0
    key = jax.random.PRNGKey(0)
    for count in sorted(set(reply_lengths)):
        request_key_stream(key, count)
    return len(set(reply_lengths))


class Flight:
    """One request as its client sees it."""

    def __init__(self, index: int, prompt: np.ndarray, reply_len: int,
                 first_tick: int):
        self.index, self.prompt, self.reply_len = index, prompt, reply_len
        self.first_tick = first_tick        # the tick that admits it
        self.tokens: List[int] = []         # as ``on_token`` streamed them
        self.done_tick: Optional[int] = None
        self.status = ""
        self.result_tokens: List[int] = []


class ClosedLoop:
    """The clients, the tick counter and the driver's own record of every
    tick: its wall time, the tokens it brought, and the work it held as the
    clients can tell it (which requests were still being prefilled, chunk
    by chunk, and how long the others' contexts were)."""

    def __init__(self, run: Any, engine: Any,
                 clock: Any = time.perf_counter):
        from trustworthy_dl_tpu.serve import ServeRequest

        self.request_type = ServeRequest
        self.run, self.engine, self.clock = run, engine, clock
        mix = run.mix
        self.clients = int(mix["clients"])
        self.ramp_ticks = int(mix["ramp_ticks"])
        self.table = serve_traffic.shapes(mix)
        self.vocab = run.family.vocab(run.config)
        self.chunk = int(run.config["deployment"]["prefill_chunk_positions"])
        self.flights: Dict[int, Flight] = {}        # request id -> flight
        self.finished: List[Flight] = []
        self.ticks: List[Dict[str, Any]] = []
        self.started = 0                            # clients that have begun
        self.submitted = 0
        self.model_misses = 0
        self.flagged = 0                            # requests, slots released
        self.tick_tokens = 0

    # -- the clients -------------------------------------------------------

    def on_token(self, request_id: int, token: int) -> None:
        self.flights[request_id].tokens.append(int(token))
        self.tick_tokens += 1

    def submit_next(self) -> None:
        spec = serve_traffic.request(self.table, self.run.seed,
                                     self.submitted, self.vocab)
        request_id = self.engine.submit(self.request_type(
            prompt=spec["prompt"], max_new_tokens=spec["max_new_tokens"],
            temperature=float(self.run.mix["temperature"]),
            on_token=self.on_token))
        if request_id is None:
            raise RuntimeError("the engine shed a closed-loop request")
        self.flights[request_id] = Flight(
            spec["index"], spec["prompt"], spec["max_new_tokens"],
            len(self.ticks) + 1)
        self.submitted += 1

    def start_clients(self) -> None:
        """The first wave: client k begins ``k * ramp_ticks`` ticks in."""
        while (self.started < self.clients
               and len(self.ticks) >= self.started * self.ramp_ticks):
            self.submit_next()
            self.started += 1

    # -- one tick ----------------------------------------------------------

    def expected_work(self, tick: int) -> Dict[str, Any]:
        """What this tick holds, told from what the clients have seen: a
        request with no token yet is fed its next prompt chunk, (position,
        rows); one with tokens decodes one more over its context."""
        prefill, decode, tokens = [], [], 0
        for flight in self.flights.values():
            plen = len(flight.prompt)
            if not flight.tokens:
                pos = (tick - flight.first_tick) * self.chunk
                rows = min(self.chunk, plen - pos)
                if rows <= 0:
                    continue                # late by the model: a miss
                prefill.append((pos, rows))
                tokens += pos + rows >= plen
            elif len(flight.tokens) < flight.reply_len:
                decode.append(plen + len(flight.tokens))
                tokens += 1
        return {"prefill": prefill, "decode": decode, "expected": tokens}

    def tick(self) -> Dict[str, Any]:
        self.start_clients()
        number = len(self.ticks) + 1
        record = self.expected_work(number)
        record["in_flight"] = len(self.flights)
        self.tick_tokens = 0
        record["start"] = self.clock()
        emitted = self.engine.step()
        record["end"] = self.clock()
        record["tokens"] = self.tick_tokens
        if emitted != self.tick_tokens or emitted != record["expected"]:
            self.model_misses += 1
        record["finished"] = []
        self.ticks.append(record)           # the refills belong to the next
        for request_id, result in self.engine.drain_results().items():
            flight = self.flights.pop(request_id)
            flight.done_tick, flight.status = number, result.status
            flight.result_tokens = [int(t) for t in result.tokens]
            self.finished.append(flight)
            record["finished"].append(flight)
            if result.flagged:
                self.release_quarantined()
            self.submit_next()
        return record

    def release_quarantined(self) -> None:
        """The driver is the operator too: a slot that the output monitor
        quarantines with a flagged request goes back into service before the
        next tick.  A verdict on a clean request (one run in 25 had one, and
        read 2 % fast with a slot short for five ticks; PERF.md section 6)
        would otherwise let the seed change the work."""
        self.flagged += 1
        for slot in sorted(self.engine.quarantined_slots):
            self.engine.release_quarantine(slot)

    def warm(self) -> bool:
        return (self.started >= self.clients and len(self.finished)
                >= int(self.run.mix["warm_completed"]))


def _summary(engine: Any, since: float) -> Dict[str, Any]:
    """``metrics_summary()``, with the engine's own counter of host wall
    inside its prefill-chunk dispatches turned back into seconds: it is
    given as a share of the time since the engine's first ``step``, which
    began at ``since`` on the driver's clock."""
    summary = engine.metrics_summary()
    share = summary.get("prefill_chunk_fraction")
    if share is not None:
        summary["prefill_s"] = float(share) * (time.perf_counter() - since)
    return summary


def run(run: Any, manifest: Any) -> None:
    common.configure_jax(run)
    compiles = common.CompileCounter()
    mix = run.mix
    engine = build_engine(run)
    run.mark("weights made, engine built")
    loop = ClosedLoop(run, engine)
    run.counters["key_streams_warmed"] = warm_key_streams(
        [reply for _, reply in loop.table])

    # -- the first wave and the warm ticks ---------------------------------
    loop_start = time.perf_counter()
    while not loop.warm():
        loop.tick()
    gc.collect()
    gc.disable()
    compiles.mark_open()
    at_open = _summary(engine, loop_start)
    run.mark("first wave and warm ticks, window opens")
    run.end_to_end["setup_s"] = common.setup_seconds(run)

    # -- the window --------------------------------------------------------
    window = Window(run.seconds)
    first = len(loop.ticks)
    window.open()
    try:
        while window.admits():
            window.count(loop.tick()["tokens"])
        window.close()
    finally:
        gc.enable()
    compiles.mark_close()
    compiles.into(run)
    at_close = _summary(engine, loop_start)
    ticks = loop.ticks[first:]
    done = [f for t in ticks for f in t["finished"]]
    run.end_to_end["serve_tokens_per_s"] = window.rate()
    run.attempted = len(done)
    run.failed = sum(f.status != "completed" or f.tokens != f.result_tokens
                     for f in done)
    run.device["memory_peak_bytes"] = common.memory_peak_bytes(run)
    run.counters.update(
        window_ticks=ticks, window_s=window.elapsed, warm_ticks=first,
        tick_model_misses=loop.model_misses, max_slots=int(
            run.config["deployment"]["serve_config"]["max_slots"]),
        engine_prefill_s=at_close.get("prefill_s", 0.0)
        - at_open.get("prefill_s", 0.0),
        prefix_hit_rate=at_close.get("prefix_hit_rate"),
        quarantined_slots=len(at_close.get("quarantined_slots", ())),
        requests_flagged=at_close.get("requests_flagged"))

    print(f"serve-closed: {first} warm ticks, {len(ticks)} window ticks in "
          f"{window.elapsed:.3f} s, {window.work:.0f} tokens, "
          f"{len(done)} requests finished, tick_model_misses "
          f"{loop.model_misses}, prefix_hit_rate "
          f"{run.counters['prefix_hit_rate']}, flagged "
          f"{run.counters['requests_flagged']} (slots released "
          f"{loop.flagged}), quarantined slots "
          f"{run.counters['quarantined_slots']}", file=sys.stderr)

    # -- the traced slice: whole ticks under the profiler --------------------
    if run.trace_on:
        sliced = serve_trace.TracedTicks(run)
        before = len(loop.ticks)
        for _ in range(int(mix["trace_ticks"])):
            with sliced.tick():
                loop.tick()
        sliced.finish()
        run.counters["trace_ticks"] = loop.ticks[before:]

    # -- correct: the reference, once the engine is released ----------------
    del engine
    loop.engine = None
    gc.collect()
    served = correct_serve.sample(
        [f for f in done if f.status == "completed"], run.seed,
        int(mix["correct_sample"]))
    run.counters["served_sample"] = [(f.prompt, f.result_tokens)
                                     for f in served]
    readings = correct_serve.readings(
        run.seed, run.config, run.counters["served_sample"],
        int(mix["reply"]["max"]))
    correct_serve.judge(run, readings, manifest.limits(run.cell["name"]))
