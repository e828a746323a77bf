"""The host's own work for one step once its metrics have landed (spans `train.host_drain.records` and `train.epoch_end.drain.records`), summed over the traced slice and divided by its steps."""

from benchmark.harness import span_readers

read = span_readers.host_work_ms
