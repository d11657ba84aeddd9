"""Activation sharding constraints (mesh-context aware).

Model code calls ``constrain(x, BATCH, None, ...)`` to anchor GSPMD
propagation at key activations (embedding output, logits).  Outside a mesh
context (CPU smoke tests) these are no-ops, so model code stays
mesh-agnostic.  BATCH resolves to whichever of ("pod", "data") exist in the
active mesh; MODEL to "model".
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

BATCH = "__batch__"
MODEL = "__model__"


def current_mesh() -> Optional[jax.sharding.Mesh]:
    m = jax.sharding.get_abstract_mesh()
    if not m.empty and m.axis_names:
        return m
    try:
        from jax._src import mesh as mesh_lib
        pm = mesh_lib.thread_resources.env.physical_mesh
        return None if pm.empty else pm
    except Exception:
        return None


def _resolve(axis, mesh):
    if axis == BATCH:
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return dp if dp else None
    if axis == MODEL:
        return "model" if "model" in mesh.axis_names else None
    return axis


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = P(*(_resolve(a, mesh) for a in axes))
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except (ValueError, TypeError):
        return x
