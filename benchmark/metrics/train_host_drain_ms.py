"""Median 'host' lap of the program's StepTimeReporter over the traced steps: the wait on the async host queue's lagged metrics."""

from benchmark.harness import readers

read = readers.phase_lap_ms("host")
