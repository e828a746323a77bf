"""Device busy time of the traced slice over its steps."""

from benchmark.harness import readers

read = readers.train_step_device_ms
