"""Idle milliseconds of chip 0 a traced tick under `serve.submit` and its `.key_stream` (the refills between ticks), by the innermost program span over each gap; nothing on a program that opens no such span."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.idle_ms("submit")
