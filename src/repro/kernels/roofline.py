"""Roofline gate: when does a hand-fused Pallas kernel beat the reference?

The dispatch layer (:mod:`repro.kernels.ops`) only routes an op to its
fused kernel when this gate says the fusion pays.  The model is the
standard roofline argument (cuDNN's "efficient primitives" framing, and
PolyDL's measure-and-select discipline):

- an op whose arithmetic intensity (FLOPs per HBM byte of the *reference*
  composition) sits below the device ridge point is memory bound — its
  runtime is the bytes it moves, so a fusion that eliminates intermediate
  HBM round trips wins roughly ``bytes_ref / bytes_fused``;
- above the ridge the op is compute bound: XLA's own fusions already keep
  the MXU busy and the hand kernel buys little, so dispatch keeps the
  reference path.

Constants: HBM bandwidth is the device's entry in
:data:`repro.core.chips.CHIPS`; effective FLOPs/s comes from
:func:`repro.pipeline.costs.device_flops`, i.e. the *calibrated* value
whenever a fitted CalibrationTable is active and the device's peak from
the same table otherwise.  An unknown device raises.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

def ridge_intensity() -> float:
    """FLOPs/byte at which compute time equals memory time."""
    from repro.core import chips
    from repro.pipeline import costs
    return costs.device_flops() / chips.chip().hbm_bytes_per_s


@dataclasses.dataclass(frozen=True)
class GateDecision:
    """One gating verdict (kept for the BENCH_* meta / dispatch report)."""

    op: str
    fused: bool
    intensity: float            # FLOPs / reference HBM byte
    ridge: float
    bytes_ref: int
    bytes_fused: int
    reason: str

    def to_dict(self) -> Dict:
        return {"op": self.op, "fused": self.fused,
                "intensity": round(self.intensity, 3),
                "ridge": round(self.ridge, 3),
                "bytes_ref": self.bytes_ref,
                "bytes_fused": self.bytes_fused,
                "reason": self.reason}


def gate(op: str, *, flops: float, bytes_ref: int,
         bytes_fused: int) -> GateDecision:
    """Decide fused vs reference for one op instance.

    ``bytes_ref`` is the HBM traffic of the unfused composition
    (including every intermediate it materializes), ``bytes_fused`` the
    traffic of the fused kernel.  Fused wins when the op is memory bound
    AND the fusion actually removes bytes.
    """
    ridge = ridge_intensity()
    intensity = flops / max(1, bytes_ref)
    if bytes_fused >= bytes_ref:
        return GateDecision(op, False, intensity, ridge, int(bytes_ref),
                            int(bytes_fused), "fusion saves no bytes")
    if intensity >= ridge:
        return GateDecision(op, False, intensity, ridge, int(bytes_ref),
                            int(bytes_fused),
                            "compute bound: XLA reference keeps MXU busy")
    return GateDecision(op, True, intensity, ridge, int(bytes_ref),
                        int(bytes_fused),
                        f"memory bound ({intensity:.2f} < ridge "
                        f"{ridge:.0f} FLOPs/B): fusion cuts "
                        f"{bytes_ref - bytes_fused} HBM bytes")
