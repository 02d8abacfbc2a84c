"""Memory-placement helpers shared by topology and feature tiers."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

__all__ = ["to_pinned_host"]


def to_pinned_host(x: np.ndarray, mesh=None) -> tuple[jax.Array, bool]:
    """Place an array in pinned host memory. Returns (array, is_host).

    With ``mesh``, the host array is replicated across the mesh's devices
    (one physical copy per host) so it composes with mesh-sharded arrays.
    On a TPU the array must land in ``pinned_host`` — a "cold tier" that
    quietly sits in HBM is an error, and a failed placement propagates.
    Only the CPU backend, when it has no such memory space, gets the
    default placement with ``is_host=False``; callers branch on the flag
    to pick direct vs staged gathers.
    """
    device = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    kind = "pinned_host"
    if device.platform == "cpu" and kind not in {
        m.kind for m in device.addressable_memories()
    }:
        if mesh is None:
            return jnp.asarray(x), False
        kind = None
    sharding = (
        SingleDeviceSharding(device, memory_kind=kind) if mesh is None
        else NamedSharding(mesh, PartitionSpec(), memory_kind=kind)
    )
    arr = jax.device_put(np.asarray(x), sharding)
    if kind is not None and arr.sharding.memory_kind != kind:
        raise RuntimeError(
            f"asked for {kind} memory on {device.platform}, got "
            f"{arr.sharding.memory_kind!r}"
        )
    return arr, kind is not None
