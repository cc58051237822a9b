"""Production mesh definitions.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256 topology).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis extends
data parallelism across the DCN/ICI boundary (cluster/batch sharding only —
no tensor-parallel traffic crosses pods).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — run via "
            "launch/dryrun.py (sets xla_force_host_platform_device_count)"
        )
    # The single-pod mesh uses the first 256 of the dry-run's 512 devices.
    return jax.sharding.Mesh(np.asarray(devs[:need]).reshape(shape), axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def n_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
