"""Operations and bytes that the serving kernels' ALGORITHM needs, from a
tick's live lengths alone.  (The model's own products a token are its
family's: ``families/<model_type>.py`` ``model_flops``.)

Nothing an implementation pads, gathers twice or recomputes is counted: a
decode row reads the K and V blocks that hold its live positions once and
scores each of them once; a prefill chunk scores the causal pairs of its
rows against what is cached.  So the share reads the same work whatever
implements it.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

from benchmark.harness.kernel_work import Work, causal_pairs


def _kv_bytes(positions: int, heads: int, d: int, block: int,
              itemsize: int) -> float:
    """K and V of the blocks that hold ``positions`` live positions."""
    return 2.0 * math.ceil(positions / block) * block * heads * d * itemsize


def paged_decode(lengths: Sequence[int], heads: int, d: int, block: int,
                 itemsize: int = 2, kv_heads: Optional[int] = None) -> Work:
    """One layer's paged decode attention for rows whose contexts hold
    ``lengths`` positions (the new one included): two products a
    query-key pair; reads the live K and V blocks and Q, writes O.
    ``heads`` query heads share ``kv_heads`` K and V heads (as many, where
    not given): products by query heads, K and V bytes by K and V heads."""
    kv_heads = heads if kv_heads is None else kv_heads
    flops = sum(2 * (2.0 * n * d) * heads for n in lengths)
    nbytes = sum(_kv_bytes(n, kv_heads, d, block, itemsize)
                 + 2.0 * heads * d * itemsize for n in lengths)
    return Work(flops, nbytes)


def paged_prefill(chunks: Sequence[Tuple[int, int]], heads: int, d: int,
                  block: int, itemsize: int = 2,
                  kv_heads: Optional[int] = None) -> Work:
    """One layer's chunked-prefill attention for chunks of ``rows`` queries
    that start at position ``pos``: the causal pairs of the rows against
    the ``pos + rows`` positions cached by then.  ``kv_heads`` as in
    ``paged_decode``."""
    kv_heads = heads if kv_heads is None else kv_heads
    flops = nbytes = 0.0
    for pos, rows in chunks:
        flops += 2 * (2.0 * causal_pairs(rows, pos + rows) * d) * heads
        nbytes += _kv_bytes(pos + rows, kv_heads, d, block, itemsize) \
            + 2.0 * rows * heads * d * itemsize
    return Work(flops, nbytes)
