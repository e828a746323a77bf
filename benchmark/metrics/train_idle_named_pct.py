"""Share of chip 0's idle seconds in the traced slice that fell under a span of the training program (`train.*`), as the harness attributes gaps."""

from benchmark.harness import span_readers

read = span_readers.idle_named_pct
