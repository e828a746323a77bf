"""The fullest held expert's (token, expert) pairs over the mean held expert's, over the window and all layers, from the program's device counters through its obs registry; 1 = even, and the grouped products' longest group sets their time; nothing where the program has no such counter."""

from benchmark.harness import expert_readers

read = expert_readers.expert_load_max_x
