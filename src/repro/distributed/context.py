"""Process-wide mesh context: which mesh / axis names the model runs under.

Set by the trainer / server / dry-run launcher; consulted by model code for
sharding constraints and by the MoE layer for its shard_map.  When no mesh is
active (unit tests, single-host experiments) everything degrades to plain
single-device execution.  Every shard_map in the repo goes through
:func:`shard_map` here.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None

DATA_AXES = ("pod", "data")      # batch-parallel axes (present subset used)
MODEL_AXIS = "model"


def shard_map(fn, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    old = _MESH
    set_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        set_mesh(old)


def data_axes() -> tuple:
    if _MESH is None:
        return ()
    return tuple(a for a in DATA_AXES if a in _MESH.axis_names)


def has_model_axis() -> bool:
    return _MESH is not None and MODEL_AXIS in _MESH.axis_names \
        and _MESH.shape[MODEL_AXIS] > 1


def batch_spec(ndim: int) -> Optional[NamedSharding]:
    """(batch, ...) arrays: shard batch over pod+data."""
    if _MESH is None:
        return None
    ax = data_axes()
    spec = P(ax if ax else None, *([None] * (ndim - 1)))
    return NamedSharding(_MESH, spec)


def hidden_spec(ndim: int, axis: int = -1,
                shape: Optional[tuple] = None) -> Optional[NamedSharding]:
    """Activations with a model-sharded feature axis: (batch, ..., features).
    Axes that don't divide their dim are dropped (uneven vocab etc.)."""
    if _MESH is None or not has_model_axis():
        return None
    axis = axis % ndim
    parts = [None] * ndim
    ax = data_axes()
    if ax:
        parts[0] = ax
    parts[axis] = MODEL_AXIS
    if shape is not None:
        for i, part in enumerate(parts):
            if part is None:
                continue
            axes = part if isinstance(part, tuple) else (part,)
            size = 1
            for a in axes:
                size *= _MESH.shape[a]
            if shape[i] % size:
                parts[i] = None
    return NamedSharding(_MESH, P(*parts))


def replicated_spec() -> Optional[NamedSharding]:
    if _MESH is None:
        return None
    return NamedSharding(_MESH, P())
