"""The longest single interval of any phase span (`serve.*`) inside the untraced window, from the engine's obs registry; the five longest go to stderr by name; nothing where the program keeps no such series."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.tick_phase_max_ms
