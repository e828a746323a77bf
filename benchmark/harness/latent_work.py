"""Operations and bytes that latent attention's ALGORITHM needs over a paged
latent cache, from a tick's live lengths alone (``serve_work``'s twin for a
layer that keeps ONE shared row a position and no values).

``width`` is what a position's row holds (``kv_lora_rank + qk_rope_head_dim``,
576 at Kimi Linear: the pool's padding to whole lane columns is the
implementation's, not counted) and ``rank`` its first lanes, which are also
the values.  A decode row HAS to run the absorbed form (the cache holds no
per-head K or V), so its work is that form's.  A chunk may run either form,
so it is counted by the LEAST either needs: the expanded form's products a
causal pair, ``2 x (nope + rope + value)`` a head, the cached blocks' bytes
once, Q in and O out.  An absorbed kernel (``2 x (width + rank)`` a pair a
head) therefore cannot read over ``(nope + rope + value) / (width + rank)``
of its roofline (29 % at 192 + 128 against 576 + 512), and an expanded one
pays its expansion of the cached rows uncounted: neither can read over
100 %, and the share means the same whichever form the program keeps.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

from benchmark.harness.kernel_work import Work, causal_pairs


def _row_bytes(positions: int, width: int, block: int, itemsize: int
               ) -> float:
    """The latent rows of the blocks that hold ``positions`` live
    positions, once: no V term."""
    return float(math.ceil(positions / block) * block * width * itemsize)


def latent_decode(lengths: Sequence[int], heads: int, width: int, rank: int,
                  block: int, itemsize: int = 2) -> Work:
    """One layer's absorbed decode attention for rows whose contexts hold
    ``lengths`` positions (the new one included): a head scores a cached
    row over its ``width`` values and sums its first ``rank``; reads the
    live blocks once and the absorbed queries, writes the heads' sums."""
    flops = sum(2.0 * (width + rank) * heads * n for n in lengths)
    nbytes = sum(_row_bytes(n, width, block, itemsize)
                 + heads * (width + rank) * itemsize for n in lengths)
    return Work(flops, nbytes)


def latent_prefill(chunks: Sequence[Tuple[int, int]], heads: int,
                   qk_width: int, v_width: int, width: int, block: int,
                   itemsize: int = 2) -> Work:
    """One layer's chunked latent attention for chunks of ``rows`` queries
    that start at position ``pos``, by the least either form needs (the
    module's docstring): the causal pairs of the rows against the ``pos +
    rows`` positions cached by then at the per-head widths ``qk_width`` and
    ``v_width``; the cached blocks' rows once, Q and O."""
    flops = nbytes = 0.0
    for pos, rows in chunks:
        flops += 2.0 * (qk_width + v_width) * heads * causal_pairs(
            rows, pos + rows)
        nbytes += _row_bytes(pos + rows, width, block, itemsize) \
            + rows * heads * (qk_width + v_width) * itemsize
    return Work(flops, nbytes)
