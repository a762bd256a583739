"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

This table is the benchmark's own yardstick: every utilisation and
roofline share divides by it, so it lives with the benchmark and not
with the program.  A device that is not listed is an error, never a
default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    name: str
    flops_per_s: float          # dense bf16 matmul FLOP/s per chip
    hbm_bytes_per_s: float      # HBM bandwidth per chip
    hbm_bytes: int              # HBM capacity per chip
    source: str


#: device_kind as JAX reports it -> peaks of one chip.
PEAKS = {
    "TPU v5 lite": Peak(
        "v5e", 197e12, 819e9, 16 * 1024**3,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM2 at 819 GB/s per chip"),
    "TPU v5e": Peak(
        "v5e", 197e12, 819e9, 16 * 1024**3,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM2 at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    """Peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; add the chip to "
            f"bench/peaks.py with its published source") from None
