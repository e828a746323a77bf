"""Share of chip 0's idle seconds in the traced ticks that lie under a span of the serving program (`serve.*`), each gap whole to the shortest such span over its middle; the rest is `_outside_`, the driver's own work between ticks; nothing on a program that opens no such span."""

from benchmark.harness import tick_span_readers

read = tick_span_readers.idle_named_pct
