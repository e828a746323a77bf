"""Median 'data' lap of the program's StepTimeReporter over the traced steps: loader, host assembly and placement of a batch."""

from benchmark.harness import readers

read = readers.phase_lap_ms("data")
