"""Operations and bytes that each kernel's ALGORITHM needs, from shapes.

The masked half of causal attention is not counted, and nothing that an
implementation recomputes beyond what the algorithm needs.  A roofline share
is the least time the chip could take (the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the kernel's device time.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

from benchmark.harness.peaks import Peak


class Work(NamedTuple):
    flops: float
    bytes: float


def causal_pairs(t_q: int, t_k: int) -> float:
    """Query-key pairs that causal attention needs when ``t_q`` queries sit
    at the END of ``t_k`` keys: query i (0-based) sees t_k - t_q + i + 1."""
    return t_q * (t_k - t_q) + t_q * (t_q + 1) / 2.0


def flash_fwd(batch: int, heads: int, t: int, d: int, itemsize: int = 2
              ) -> Work:
    """Causal flash attention forward, one layer: QK^T and PV over the
    causal pairs; reads Q, K, V, writes O and the f32 log-sum-exp row."""
    pairs = causal_pairs(t, t)
    flops = batch * heads * 2 * (2.0 * pairs * d)
    nbytes = batch * heads * (4 * t * d * itemsize + t * 4)
    return Work(flops, nbytes)


def flash_bwd(batch: int, heads: int, t: int, d: int, itemsize: int = 2
              ) -> Work:
    """Causal flash attention backward, one layer.  The algorithm needs five
    matrix products over the causal pairs (S again, dP, dV, dK, dQ); a
    kernel pair that forms S and dP twice is not given credit for it.
    Reads Q, K, V, O, dO and the lse and delta rows; writes dQ, dK, dV."""
    pairs = causal_pairs(t, t)
    flops = batch * heads * 5 * (2.0 * pairs * d)
    nbytes = batch * heads * (8 * t * d * itemsize + 2 * t * 4)
    return Work(flops, nbytes)


def roofline_pct(work: Work, device_seconds: float, peak: Peak
                 ) -> Tuple[float, str]:
    """(share of the roofline in %, which bound holds)."""
    t_flops = work.flops / peak.flops_bf16
    t_bytes = work.bytes / peak.hbm_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / device_seconds, bound


def train_flops_per_token(n_params: int) -> float:
    """6 N: forward and backward of every parameter's multiply-add, nothing
    recomputed.  Attention's own products are left out, so the share of the
    peak this gives is a lower bound."""
    return 6.0 * n_params
