"""Least time for the causal backward attention the algorithm needs (five products), over the _flash_bwd kernels' device time."""

from benchmark.harness import readers

read = readers.flash_bwd_roofline
