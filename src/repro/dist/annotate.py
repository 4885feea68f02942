"""Activation sharding annotations bound to the ambient mesh.

Model code calls :func:`batch_activations` on residual streams and
:func:`replicate` on tiny decode activations.  Under a mesh set with
``jax.set_mesh`` (the dry-run's context) these lower to
``with_sharding_constraint``; outside any mesh they are exact no-ops, so
the same model code runs unannotated on a single host.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .sharding import activation_spec


def ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def batch_activations(x):
    """Constrain an activation to batch-over-DP plus sequence-over-model.

    Re-anchors the residual stream so feature shardings introduced by TP
    weights don't propagate layer to layer, and parks a 3-D+ activation's
    sequence dim on the otherwise-idle ``model`` axis (sequence
    parallelism -- see :func:`repro.dist.sharding.activation_spec`).
    No-op without an ambient mesh or when no dim divides its axes.
    """
    mesh = ambient_mesh()
    if mesh is None or x.ndim == 0:
        return x
    spec = activation_spec(mesh, x.shape)
    if all(ax is None for ax in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def replicate(x):
    """Constrain to fully-replicated; no-op without an ambient mesh."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec()))
