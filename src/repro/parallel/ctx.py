"""Mesh context + activation sharding constraints (no-op off-mesh).

Model code calls ``maybe_constrain(x, "batch", None, "seq_model", ...)``
with *logical* entries; under an active mesh (set by the launchers) these
become ``with_sharding_constraint`` placements, filtered for axis presence
and divisibility. On CPU tests (no mesh) they are identity — the same model
code runs everywhere.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CURRENT_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mesh", default=None)

# logical activation entries -> mesh axes
ACT_ENTRIES = {
    "batch": ("pod", "data"),
    "seq_model": ("model",),  # sequence parallelism over the TP axis
    "model": ("model",),
    "tokens_all": ("pod", "data", "model"),  # flat token dim, all axes
    None: (),
}


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH.get()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    token = _CURRENT_MESH.set(mesh)
    try:
        yield
    finally:
        _CURRENT_MESH.reset(token)


_SERVE_TP_AXIS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_serve_tp_axis", default=None)


def serve_tp_axis() -> Optional[str]:
    """Mesh axis name the serve step is KV-head-sharded over, or None.

    Set only *inside* the body of the engine's ``shard_map``-wrapped step
    (a trace-time signal, not a runtime one): attention's fused apply
    paths read it to learn that their K/V pools and q/k/v projections
    carry only ``KVH / mesh.shape[axis]`` local heads and that the
    kernel output must be all-gathered over this axis before the
    (replicated) output projection. Everything outside the serve step —
    training, the single-device engine, the einsum oracles — sees None
    and runs unchanged.
    """
    return _SERVE_TP_AXIS.get()


@contextlib.contextmanager
def use_serve_tp(axis_name: Optional[str]):
    token = _SERVE_TP_AXIS.set(axis_name)
    try:
        yield
    finally:
        _SERVE_TP_AXIS.reset(token)


def maybe_constrain(x, *entries):
    """Apply a logical sharding constraint if a mesh is active."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = []
    used = set()
    for dim, entry in zip(x.shape, entries):
        axes = tuple(a for a in ACT_ENTRIES.get(entry, ())
                     if a in mesh.axis_names and a not in used)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if axes and dim % size == 0 and dim >= size:
            spec.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            spec.append(None)
    # pad remaining dims
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
