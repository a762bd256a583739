"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run forces 512 host devices *before*
first jax init; everything else sees the real topology).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, pp: int = 1):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    ``pp > 1`` carves a ``pipe`` axis out of the pod's chips.  The
    explicit-pipeline train step is DP x PP (the pipe axis needs manual
    ppermute placement), so the model axis collapses to 1 in that mode —
    TP x PP composition stays at the planner's cost-model level.
    """
    if pp > 1:
        chips = 256
        if chips % pp:
            raise ValueError(f"pp={pp} does not divide {chips} chips/pod")
        shape = (2, chips // pp, pp, 1) if multi_pod \
            else (chips // pp, pp, 1)
        axes = ("pod", "data", "pipe", "model") if multi_pod \
            else ("data", "pipe", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh for tests/benchmarks (same Auto axis types)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_host_mesh(pp: int = 1, devices=None):
    """``devices`` (default: every device) as (data=n, model=1) — the
    layouts always name both axes (smoke tests, examples).  ``pp > 1``
    inserts a ``pipe`` axis: (data=n/pp, pipe=pp, model=1)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if pp > 1:
        if n % pp:
            raise ValueError(f"pp={pp} does not divide {n} devices")
        return make_mesh((n // pp, pp, 1), ("data", "pipe", "model"),
                         devices)
    return make_mesh((n, 1), ("data", "model"), devices)
