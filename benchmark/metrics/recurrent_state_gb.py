"""HBM held by the recurrent-state rows beside the paged pool (`state_pool_bytes` of `metrics_summary()`, through the program's obs registry): the state of every slot of every layer that keeps one, and its convolution tail; nothing where every layer keeps keys and values or the program has no such gauge."""

from benchmark.harness import expert_readers

read = expert_readers.recurrent_state_gb
