"""Host milliseconds a tick of the untraced window: the seconds under `serve.tick` and `serve.submit` less those under every `*.pull`, over the window's ticks, from the engine's phase totals in its obs registry (since the last summary = the window); nothing where the program keeps no such series."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.tick_host_ms
