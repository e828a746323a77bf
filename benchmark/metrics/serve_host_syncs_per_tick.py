"""Waits for the device a tick of the untraced window: the window's spans named `*.pull` and `serve.submit.key_stream` (each blocks on the device at least once) over its `serve.tick` spans, from the engine's phase counts in its obs registry (since the last summary = the window); nothing where the program keeps no such series."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.host_syncs_per_tick
