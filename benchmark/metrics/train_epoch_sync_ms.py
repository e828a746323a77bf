"""The span `train.epoch_end.host_sync`: `sync_host_state`, the trust manager and node monitor absorbing the device's state."""

from benchmark.harness import span_readers

read = span_readers.span_ms(span_readers.EPOCH_END + ".host_sync")
