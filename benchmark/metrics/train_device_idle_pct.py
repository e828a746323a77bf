"""1 - union of device op intervals over the traced window, both without the stalls that train_host_stall_ms holds: idle while the steps run."""

from benchmark.harness import readers

read = readers.device_idle_pct
