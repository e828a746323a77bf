"""Collective time a step during which no other op runs on that chip, mean over chips."""

from benchmark.harness import readers

read = readers.collective_exposed_ms
