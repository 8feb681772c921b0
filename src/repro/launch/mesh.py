"""Production meshes.

Single pod: (16, 16) ("data", "model") — 256 v5e chips.
Multi-pod:  (2, 16, 16) ("pod", "data", "model") — 512 chips, the "pod" axis
crossing the inter-pod DCN/ICI link.

Functions, not module constants: importing this module must never touch jax
device state (smoke tests see 1 CPU device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the train steps place activations with sharding
    # constraints, which Explicit axes (make_mesh's default) refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has, as a 1-axis data mesh (examples/tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))


def batch_axes(mesh) -> tuple:
    """Axes the global batch shards over (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def fsdp_axes(mesh) -> tuple:
    """Axes parameters/optimizer shard over in the FSDP dimension."""
    return batch_axes(mesh)


def axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
