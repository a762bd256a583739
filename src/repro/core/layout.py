"""Layout algebra for distributed tensors.

dMath §2.1/§3.2: a distributed matrix is split into non-overlapping blocks
stored on individual workers, and *every* worker knows the layout of *every*
matrix.  In JAX the "worker table" is a ``NamedSharding``; this module gives
layouts a first-class, comparable, hashable representation plus the
divisibility solver the planner uses (JAX requires sharded dims to divide the
mesh axis size exactly).

A :class:`Layout` is a tuple of per-dimension shardings over *named* mesh
axes.  The classic dMath/ScaLAPACK layouts are special cases:

- ``Layout.replicated(ndim)``                — every block on every worker
- ``Layout.row_sharded(ndim, axis="model")`` — 1-D row decomposition
- ``Layout.col_sharded(ndim, axis="model")`` — 1-D column decomposition
- ``Layout.blocked_2d(("data", "model"))``   — 2-D block decomposition

Unlike ScaLAPACK-era libraries (paper §3.2, refs [3,4]) operations in
``core.gemm``/``core.redistribute`` accept *any* pair of layouts and insert
the communication needed to make them compatible.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisSpec = Union[None, str, Tuple[str, ...]]


def _canon_axis(a: AxisSpec) -> Union[None, str, Tuple[str, ...]]:
    """Canonicalize a per-dim axis spec: () -> None, ("x",) -> "x"."""
    if a is None:
        return None
    if isinstance(a, str):
        return a
    t = tuple(a)
    if len(t) == 0:
        return None
    if len(t) == 1:
        return t[0]
    return t


def _axis_names(a: AxisSpec) -> Tuple[str, ...]:
    if a is None:
        return ()
    if isinstance(a, str):
        return (a,)
    return tuple(a)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Per-dimension mapping of a logical tensor onto named mesh axes.

    ``dims[i]`` is the mesh axis (or axes) that shard dimension ``i``;
    ``None`` means the dimension is replicated.  Hashable and comparable so it
    can key the op cache (paper §3.3's cached metadata identifiers).
    """

    dims: Tuple[AxisSpec, ...]

    def __post_init__(self):
        object.__setattr__(self, "dims", tuple(_canon_axis(d) for d in self.dims))
        seen = set()
        for d in self.dims:
            for name in _axis_names(d):
                if name in seen:
                    raise ValueError(
                        f"mesh axis {name!r} used for two dimensions in {self.dims}"
                    )
                seen.add(name)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def replicated(ndim: int) -> "Layout":
        return Layout((None,) * ndim)

    @staticmethod
    def row_sharded(ndim: int, axis: AxisSpec = "model") -> "Layout":
        return Layout((axis,) + (None,) * (ndim - 1))

    @staticmethod
    def col_sharded(ndim: int, axis: AxisSpec = "model") -> "Layout":
        return Layout((None,) * (ndim - 1) + (_canon_axis(axis),))

    @staticmethod
    def blocked_2d(axes: Tuple[AxisSpec, AxisSpec] = ("data", "model")) -> "Layout":
        return Layout(tuple(axes))

    @staticmethod
    def from_spec(spec: PartitionSpec, ndim: Optional[int] = None) -> "Layout":
        dims = tuple(spec)
        if ndim is not None:
            dims = dims + (None,) * (ndim - len(dims))
        return Layout(dims)

    # -- views --------------------------------------------------------------
    @property
    def ndim(self) -> int:
        return len(self.dims)

    @property
    def spec(self) -> PartitionSpec:
        return PartitionSpec(*self.dims)

    def sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec)

    def is_replicated(self) -> bool:
        return all(d is None for d in self.dims)

    def sharded_dims(self) -> Tuple[int, ...]:
        return tuple(i for i, d in enumerate(self.dims) if d is not None)

    def mesh_axes_used(self) -> Tuple[str, ...]:
        out = []
        for d in self.dims:
            out.extend(_axis_names(d))
        return tuple(out)

    # -- geometry -----------------------------------------------------------
    def shard_count(self, mesh: Mesh, dim: int) -> int:
        """Number of shards along logical dimension ``dim``."""
        return math.prod(mesh.shape[name] for name in _axis_names(self.dims[dim]))

    def num_shards(self, mesh: Mesh) -> int:
        return math.prod(self.shard_count(mesh, i) for i in range(self.ndim))

    def local_shape(
        self, global_shape: Sequence[int], mesh: Mesh
    ) -> Tuple[int, ...]:
        out = []
        for i, size in enumerate(global_shape):
            n = self.shard_count(mesh, i)
            if size % n:
                raise ValueError(
                    f"dim {i} of size {size} not divisible by {n} shards "
                    f"(layout {self.dims}, mesh {dict(mesh.shape)})"
                )
            out.append(size // n)
        return tuple(out)

    def divisible(self, global_shape: Sequence[int], mesh: Mesh) -> bool:
        try:
            self.local_shape(global_shape, mesh)
            return True
        except ValueError:
            return False

    def bytes_per_device(
        self, global_shape: Sequence[int], dtype, mesh: Mesh
    ) -> int:
        local = self.local_shape(global_shape, mesh)
        return math.prod(local) * jax.dtypes.canonicalize_dtype(dtype).itemsize

    # -- transforms ---------------------------------------------------------
    def with_dim(self, dim: int, axis: AxisSpec) -> "Layout":
        dims = list(self.dims)
        dims[dim] = _canon_axis(axis)
        return Layout(tuple(dims))

    def drop_axis(self, name: str) -> "Layout":
        """Remove one mesh axis from wherever it shards (-> replicated there)."""
        new = []
        for d in self.dims:
            names = tuple(n for n in _axis_names(d) if n != name)
            new.append(_canon_axis(names))
        return Layout(tuple(new))

    def __repr__(self) -> str:  # compact, e.g. L[model, -, data]
        parts = []
        for d in self.dims:
            if d is None:
                parts.append("-")
            elif isinstance(d, str):
                parts.append(d)
            else:
                parts.append("+".join(d))
        return "L[" + ", ".join(parts) + "]"


def constrain(x: jax.Array, layout: Layout, mesh: Optional[Mesh] = None):
    """``with_sharding_constraint`` via a Layout.

    Inside ``jit`` under a mesh context the mesh argument may be omitted.

    Inside a ``shard_map`` body the constraint is rewritten for the manual
    context: axes the shard_map holds manually are dropped (the value is
    already local over them — the global annotation is meaningless there,
    and the SPMD partitioner rejects it), and if nothing remains the call
    is a no-op.  This is what lets model code that annotates layouts run
    unchanged under the explicit comms schedules in :mod:`repro.comms`.
    """
    from repro.core.manual import manual_axes

    manual = manual_axes()
    if manual:
        for name in set(layout.mesh_axes_used()) & manual:
            layout = layout.drop_axis(name)
        if layout.is_replicated():
            return x
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, layout.sharding(mesh))
    return jax.lax.with_sharding_constraint(x, layout.spec)


def best_divisor_axis(
    size: int, mesh: Mesh, candidates: Sequence[str]
) -> Optional[str]:
    """First candidate mesh axis whose size divides ``size`` (planner helper)."""
    for name in candidates:
        if name in mesh.shape and size % mesh.shape[name] == 0:
            return name
    return None
