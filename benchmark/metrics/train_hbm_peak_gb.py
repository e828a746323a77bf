"""memory_stats()['peak_bytes_in_use'] of the fullest chip, read when the window closed."""

from benchmark.harness import readers

read = readers.hbm_peak_gb
