"""Device time of the detector battery's sorts and fused-moments kernel over the step's device time, by op name: a lower bound until the program names its scopes."""

from benchmark.harness import readers

read = readers.trust_overhead_pct
