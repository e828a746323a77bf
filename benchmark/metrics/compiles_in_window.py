"""Backend compilations between the window's opening and its close (the program's obs.compilewatch registry); should be 0."""

from benchmark.harness import readers

read = readers.counter("compiles_in_window")
