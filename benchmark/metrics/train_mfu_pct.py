"""6 N times the window's tokens/s/chip over the chip's bf16 peak (no remat, nothing recomputed; attention's own products left out)."""

from benchmark.harness import readers

read = readers.train_mfu_pct
