"""Seconds building and initialising the trainer (`setup.trainer_init`, `setup.initialize`, `setup.build_steps` of the program's recorded set-up spans; a span inside another counts once)."""

from benchmark.harness import span_readers

read = span_readers.trainer_build_s
