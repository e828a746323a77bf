"""Seconds of XLA backend compilation before the window opened (the program's obs.compilewatch registry; a cache hit costs its load)."""

from benchmark.harness import readers

read = readers.counter("compile_s")
