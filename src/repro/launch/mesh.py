"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
touches no jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; real deployments get the same shapes from the TPU runtime.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes, devices) -> Mesh:
    # Auto axes: the compiler propagates shardings from the parameter and
    # activation constraints (jax.make_mesh defaults to Explicit axes)
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) = (data, model) = 256 chips.
    Multi-pod: (2, 16, 16) = (pod, data, model) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} "
            "(dry-run must set --xla_force_host_platform_device_count=512 "
            "before importing jax)")
    return _make_mesh(shape, axes, devices[:n])


def make_host_mesh(*, data: int = 1, model: int = 1) -> Mesh:
    """Small mesh for tests/examples on whatever devices exist."""
    devices = jax.devices()[: data * model]
    return _make_mesh((data, model), ("data", "model"), devices)
