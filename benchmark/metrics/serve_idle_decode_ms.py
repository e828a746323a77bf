"""Idle milliseconds of chip 0 a traced tick under `serve.decode_tick` itself, its `.build`, `.dispatch`, `.pull` and `.record`, and `serve.spec_*`, by the innermost program span over each gap; nothing on a program that opens no such span."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.idle_ms("decode")
