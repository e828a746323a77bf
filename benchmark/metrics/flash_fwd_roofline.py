"""Least time for the causal forward attention the algorithm needs, over the _flash_fwd kernels' device time."""

from benchmark.harness import readers

read = readers.flash_fwd_roofline
