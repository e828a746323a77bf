"""The spans `train.epoch_end.thresholds` and `train.epoch_end.ml_refit`: the two halves of the epoch-cadence host intelligence (adaptive trust threshold; refit and scoring of the per-node ML detectors)."""

from benchmark.harness import span_readers

read = span_readers.span_ms(span_readers.EPOCH_END + ".thresholds",
                            span_readers.EPOCH_END + ".ml_refit")
