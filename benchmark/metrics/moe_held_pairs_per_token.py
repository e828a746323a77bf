"""(Token, expert) pairs routed to the experts HELD here over the tokens fed through an expert layer (a layer each), over the window, from the program's device counters through its obs registry; experts per token x held / published = 1.0 is what a balanced router sends and what `serve_mfu_pct` assumes; nothing where the program has no such counter."""

from benchmark.harness import expert_readers

read = expert_readers.held_pairs_per_token
