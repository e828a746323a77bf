"""Published peaks of one chip, keyed by ``device_kind``.

A device that is not here is an error, not a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s,
    # 16 GB of HBM a chip.  JAX reports the chip as "TPU v5 lite".
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "Google Cloud documentation, TPU v5e"),
    "TPU v5e": Peak(197e12, 819e9, 16e9,
                    "Google Cloud documentation, TPU v5e"),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source") from None
