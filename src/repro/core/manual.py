"""Nested manual-mesh handling: the one module that knows the rule.

Model code issues its own ``shard_map``\\ s (explicit bf16 TP collectives,
the embedding gather, MoE dispatch).  The explicit gradient-sync and
pipeline train steps (:mod:`repro.train.step`) run the whole loss inside
an outer, fully-manual ``shard_map`` over the same mesh.  Inside it the
arguments are already this device's local blocks and every mesh axis is
bound, so a model ``shard_map`` over that mesh must run its body inline:
the collectives it issues still resolve against the bound axes.  JAX
itself refuses the nesting ("the context mesh ... should match the mesh
passed to shard_map"), so every model call site goes through
:func:`shard_map` here.

The inline case is only reachable from data-parallel cells where every
non-batch axis has size 1 (enforced in ``train/step.py``), which is what
makes the local block equal to the block the inner specs would slice.
"""

from __future__ import annotations

import jax


def manual_axes() -> frozenset:
    """Mesh axis names the enclosing ``shard_map`` holds manually.

    Empty outside any ``shard_map`` body.  Read from the ambient abstract
    mesh, whose axis types mark manual axes while a body is traced.
    """
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` (``check_vma=False``) that runs ``f`` inline when
    every axis of ``mesh`` is already manual (nested inside an outer
    fully-manual shard_map)."""
    mapped = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    names = frozenset(mesh.axis_names)

    def call(*args):
        if names <= manual_axes():
            return f(*args)
        return mapped(*args)

    return call
