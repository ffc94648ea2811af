"""Mesh construction + axis conventions for the device-distributed path.

Axis vocabulary (DESIGN.md §6): ``data`` is the RapidGNN worker axis --
one mesh slot per paper "worker", holding that worker's feature-table
partition, steady cache C_s, and batch stream. On a hierarchical
multi-host topology (``repro.dist.topology.Topology``, DESIGN.md §6.7)
``data`` becomes the INTRA-host ici axis and a ``dcn`` axis sits OUTER,
so the flat worker ordinal is the row-major ``("dcn", "data")``
flattening. ``model`` (tensor/expert parallel) and ``pod`` (multi-pod
data parallel) are the transformer substrate's axes. Everything here is
a FUNCTION of an explicit shape so importing this module never touches
jax device state (device count locks at first backend init; the
dry-runs set XLA_FLAGS before importing jax).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """Build a device mesh, e.g. ``make_mesh((4,), ("data",))``.

    Axes are ``Auto``: every program here partitions through explicit
    ``shard_map`` specs, and with ``jax.make_mesh``'s ``Explicit`` default
    a jitted epoch's outputs carry mesh-typed avals that its fresh inputs
    lack, so the second epoch would trace and compile again."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def host_device_flags(n: int, flags: str = "") -> str:
    """``flags`` (an ``XLA_FLAGS`` value) plus a request for ``n`` XLA CPU
    devices; flags the caller already set survive. The device count locks
    at the first JAX backend use, so set it before that."""
    return " ".join(filter(None, (
        flags, f"--xla_force_host_platform_device_count={n}")))


def dp_axes(mesh) -> Optional[Union[str, Tuple[str, ...]]]:
    """The data-parallel axes of ``mesh`` as a PartitionSpec entry.

    Returns a tuple of the present batch-sharding axes (``pod``
    outermost, then ``dcn``, then ``data``) or None when the mesh has
    none of them -- usable directly as one entry of a
    ``PartitionSpec``.
    """
    axes = tuple(a for a in ("pod", "dcn", "data") if a in mesh.axis_names)
    return axes if axes else None
