"""The Mamba-2 decode step's kernel (ssm_step*) against the work ssm_work.ssm_decode reckons from the traced ticks' decoding rows: each row's float32 state read and written once, its x, dt, B, C and y; over the chip's HBM bandwidth."""

from benchmark.harness import ssm_readers

read = ssm_readers.ssm_decode_roofline
