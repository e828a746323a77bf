"""Operations and bytes that ONE decode step of the Mamba-2 recurrence needs
for one layer, from the rows that decode: each row reads its state ``[H, P,
N]`` float32 once and writes it once, reads its ``x [H, P]``, ``dt [H]`` and
its groups' ``B, C [G, N]`` and writes ``y [H, P]``, all float32.  The gate
``z`` is applied outside the step and is not counted.  This is the USEFUL
work: the program's kernel (``ops/ssm_step.py``) streams every slot's state
through each call, an idle or mid-prefill slot's too (at ``dt`` 0, which
keeps it), but a row that does not decode needs none of those bytes, so
they are not counted and the share of the roofline reads them as time
lost.  Operations: per state
element the decay's product, the input's product and sum, and the output's
product and sum, 5; with some 0.6 operations a byte the step is bound by
its bytes at any size.
"""

from __future__ import annotations

from benchmark.harness.kernel_work import Work

F32 = 4


def ssm_decode(rows: int, heads: int, head_dim: int, state: int,
               groups: int) -> Work:
    """One layer's decode step for ``rows`` decoding rows."""
    elements = heads * head_dim * state
    flops = rows * 5.0 * elements
    nbytes = rows * F32 * (2.0 * elements + heads * head_dim + heads
                           + 2 * groups * state + heads * head_dim)
    return Work(flops, nbytes)
