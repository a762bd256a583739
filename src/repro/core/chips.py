"""Per-chip constants, keyed by the ``device_kind`` JAX reports.

One table holds what the planner, the memory model and the kernel gate
need to know about a device: HBM capacity, peak dense bf16 FLOP/s and
HBM bandwidth.  A device missing from the table is an error, never a
default: a wrong budget refuses or admits the wrong plans, and a wrong
peak moves the roofline ridge that decides which kernels fuse.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax

GIB = 1024**3


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    hbm_bytes: int
    peak_flops: float           # dense bf16 FLOP/s
    hbm_bytes_per_s: float
    source: str


CHIPS: Dict[str, Chip] = {
    "v5e": Chip("v5e", 16 * GIB, 197e12, 819e9,
                "Google Cloud documentation, 'TPU v5e'"),
    "v5p": Chip("v5p", 95 * GIB, 459e12, 2765e9,
                "Google Cloud documentation, 'TPU v5p'"),
    "h100": Chip("h100", 80 * GIB, 989e12, 3.35e12,
                 "NVIDIA H100 SXM datasheet (dense bf16)"),
    # The fake-device CPU meshes of the tests: a v5e-sized budget, so a
    # CPU plan answers "would this fit a v5e?", and a nominal peak that
    # only sets the ratio against the comms cost model.  Not a CPU figure.
    "cpu": Chip("cpu", 16 * GIB, 100e12, 819e9,
                "debug stand-in for CPU test meshes, not a measured peak"),
}

# device_kind substring -> CHIPS key, first match wins ("v5p" before the
# bare "v5 lite" form; v5e reports itself as "TPU v5 lite").
_KINDS = (
    ("v5p", "v5p"),
    ("v5e", "v5e"),
    ("v5 lite", "v5e"),
    ("h100", "h100"),
    ("cpu", "cpu"),
)


def chip_key(device_kind: str) -> str:
    """The :data:`CHIPS` key of a ``device_kind``; unknown kinds raise."""
    kind = device_kind.lower()
    for sub, key in _KINDS:
        if sub in kind:
            return key
    raise ValueError(
        f"unknown device_kind {device_kind!r}: add its HBM capacity, peak "
        f"and bandwidth to repro.core.chips.CHIPS")


def chip(device=None) -> Chip:
    """Constants of ``device`` (default: the first device JAX reports)."""
    device = device if device is not None else jax.devices()[0]
    return CHIPS[chip_key(device.device_kind)]
