"""Mesh construction: the production dry-run shapes and the runtime
lane meshes the service layer shards ensembles over.

``make_production_mesh`` is a function (not a module constant) so that
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain the placeholder devices.

``make_lane_mesh`` / ``resolve_placement`` are the runtime seam
(DESIGN.md §8): ``ServiceConfig.placement`` resolves here to the 1-D
``("data",)`` mesh that :mod:`repro.api.service` shards the stacked
ensemble axis over.  On a single-device host the resolution degrades to
:func:`make_host_mesh`'s single-device data axis, so placement never
changes semantics — only where lanes live.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1x1 mesh on the first local device (one chip, or the CPU)."""
    return _make_mesh((1, 1), ("data", "model"))


def data_shards(mesh: Mesh) -> int:
    n = mesh.shape.get("data", 1)
    return n * mesh.shape.get("pod", 1)


def make_lane_mesh(n_lanes: int,
                   max_shards: Optional[int] = None) -> Mesh:
    """1-D ``("data",)`` mesh for sharding an ensemble/partition axis.

    GSPMD input shardings must divide the sharded extent, so the mesh
    takes the *largest divisor* of ``n_lanes`` that fits the local
    device count (optionally capped by ``max_shards``): 63 lanes on 8
    devices shard 7-way, 504 lanes shard 8-way, and a prime lane count
    on one device degrades to :func:`make_host_mesh` — identical
    decisions either way, only the placement differs.
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    n_dev = len(jax.devices())
    if max_shards is not None:
        n_dev = min(n_dev, max_shards)
    d = max(k for k in range(1, max(n_dev, 1) + 1) if n_lanes % k == 0)
    if d == 1:
        return make_host_mesh()
    return _make_mesh((d,), ("data",))


def resolve_placement(placement: Union[None, str, int],
                      n_lanes: int) -> Optional[Mesh]:
    """``ServiceConfig.placement`` -> the mesh lanes shard over.

    ``None`` / ``"single"`` disables sharding entirely (the pre-mesh
    single-device path); ``"auto"`` shards over every local device via
    :func:`make_lane_mesh`; ``"host"`` pins the 1x1
    :func:`make_host_mesh`; an ``int`` caps the shard count.
    """
    if placement is None or placement == "single":
        return None
    if placement == "host":
        return make_host_mesh()
    if placement == "auto":
        return make_lane_mesh(n_lanes)
    if isinstance(placement, int):
        return make_lane_mesh(n_lanes, max_shards=placement)
    raise ValueError(f"unknown placement {placement!r}")
