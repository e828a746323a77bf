"""Idle gaps of the traced slice longer than one step's device time, summed: the epoch's end (full drain of the async host queue, host sync) with the device idle."""

from benchmark.harness import readers

read = readers.host_stall_ms
