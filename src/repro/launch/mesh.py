"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The dry-run entry
point sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before
any jax import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import List, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU tests: usually (1,1)).

    ``model_axis`` must divide the device count exactly: the old path
    floored ``data`` to 1 and let ``jax.make_mesh`` fail later with an
    opaque device-count mismatch (or silently built a mesh smaller than
    the host when the floor happened to fit)."""
    n = len(jax.devices())
    if model_axis < 1 or n % model_axis:
        raise ValueError(
            f"model_axis={model_axis} must be >= 1 and divide the "
            f"{n} available device(s) exactly; pick a divisor of {n} "
            f"(or re-launch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=<multiple of "
            f"{model_axis}>)")
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))


def replica_groups(mesh: Union[Mesh, Sequence, None], tp: int,
                   *, axis: str = "model") -> List[Mesh]:
    """Carve a device pool into per-replica tensor-parallel sub-meshes.

    Each group is a 1-D Mesh of ``tp`` consecutive devices over a single
    ``axis`` ("model") — the unit a ring node maps to in the serve plane
    (node = replica group, not device).  ``mesh`` may be a Mesh (its
    devices are taken in row-major order, so a group's devices are
    ICI-adjacent along the fastest-varying axis), an explicit device
    sequence, or None for every host device."""
    if mesh is None:
        devices = list(jax.devices())
    elif isinstance(mesh, Mesh):
        devices = list(mesh.devices.reshape(-1))
    else:
        devices = list(mesh)
    n = len(devices)
    if tp < 1 or n % tp:
        raise ValueError(
            f"tp={tp} must be >= 1 and divide the {n} pooled device(s) "
            f"exactly — a partial group cannot hold a full weight shard "
            f"set")
    return [Mesh(np.array(devices[i:i + tp]), (axis,), axis_types=_auto(1))
            for i in range(0, n, tp)]


HARDWARE = {
    # TPU v5e per chip
    "peak_bf16_flops": 197e12,       # FLOP/s
    "hbm_bandwidth": 819e9,          # B/s
    "ici_link_bandwidth": 50e9,      # B/s per link
    "hbm_bytes": 16 * 1024**3,
}
