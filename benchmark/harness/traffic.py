"""The traffic generator, driven by a mix's data file.

The file fixes the WORK: the batch shape of every step.  ``--seed`` decides
only the token ids.  So every seed offers the same tokens and the same load.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose).  numpy's seed words
    are 32 bits wide and the driver's seeds are larger, so the seed goes in
    as two words."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def train_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """Batch ``step`` (0-based) of a training cell: ``batch`` rows of
    ``seq_len + 1`` token ids drawn from the whole vocabulary, all rows
    different; ``input`` is all but the last id and ``target`` all but the
    first."""
    rng = rng_for(seed, f"train:{step}")
    tokens = rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
    return {"input": tokens[:, :-1], "target": tokens[:, 1:]}


def offered(mix: Dict[str, Any]) -> Dict[str, int]:
    """The tokens one step of the mix offers (what a test compares between
    seeds)."""
    rows = int(mix["nodes"]) * int(mix["per_node_batch"])
    return {"rows": rows, "tokens": rows * int(mix["seq_len"])}
