"""The span `train.epoch_end` in the traced slice: from the step loop's exit to `train_epoch`'s return (full drain, host sync, thresholds, ML refit, epoch metrics), the device idle but for the steps still in flight at the drain."""

from benchmark.harness import span_readers

read = span_readers.span_ms(span_readers.EPOCH_END)
