"""Mesh-aware sharding helpers usable from model code.

Model code calls ``maybe_shard(x, "dp", None, ...)`` with *logical* axis
names; under an ambient mesh (``jax.sharding.use_mesh``) they resolve to the
physical axes present — ``"dp"`` -> ("pod", "data") (whichever exist),
``"tp"`` -> ("model",). Outside a mesh the call is a no-op, so the same model
runs on a laptop and on the production mesh unchanged.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


DP = "dp"  # logical data-parallel axis -> ("pod", "data")
TP = "tp"  # logical tensor/expert-parallel axis -> ("model",)
ALL = "all"  # every mesh axis (edge-parallel GNN aggregation)

_LOGICAL = {
    DP: ("pod", "data"),
    TP: ("model",),
    ALL: ("pod", "data", "model"),
}


def physical_axes(logical: str, mesh_axis_names) -> tuple[str, ...]:
    return tuple(a for a in _LOGICAL[logical] if a in mesh_axis_names)


def resolve_spec(spec_entries, mesh_axis_names) -> P:
    out = []
    for e in spec_entries:
        if e is None:
            out.append(None)
        elif e in _LOGICAL:
            phys = physical_axes(e, mesh_axis_names)
            out.append(phys if phys else None)
        else:
            out.append(e if e in mesh_axis_names else None)
    return P(*out)


def maybe_shard(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint under an ambient mesh; identity otherwise."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, resolve_spec(spec_entries, mesh.axis_names)
    )
