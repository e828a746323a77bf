"""The benchmark: one command, cells as data (see BENCHMARK.json, PERF.md)."""
