"""The span `train.epoch_end.drain`: the epoch's mandatory full drain of the async host queue (waiting for the steps still in flight, then their host records)."""

from benchmark.harness import span_readers

read = span_readers.span_ms(span_readers.EPOCH_END + ".drain")
