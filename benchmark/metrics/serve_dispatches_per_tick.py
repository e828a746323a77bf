"""Program calls a tick of the untraced window: the window's spans named `*.dispatch` and `serve.tick.admit.zero_state` (each is one call) over its `serve.tick` spans, from the engine's phase counts in its obs registry (since the last summary = the window); nothing where the program keeps no such series."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.dispatches_per_tick
