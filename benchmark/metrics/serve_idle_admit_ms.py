"""Idle milliseconds of chip 0 a traced tick under the admission spans (`serve.tick.expire`, `serve.tick.admit*`, `serve.prefix_lookup`), by the innermost program span over each gap; nothing on a program that opens no such span."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.idle_ms("admit")
