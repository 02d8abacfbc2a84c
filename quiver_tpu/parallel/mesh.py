"""Device-mesh topology utilities.

Capability parity with the reference's p2p-clique machinery
(torch-quiver utils.py:8-104 ``Topo``/``find_cliques`` +
``init_p2p``/``can_device_access_peer``, quiver_feature.cu:363-413): the
reference discovers which GPUs share NVLink and partitions them into
cliques; on TPU the analogous structure is *given* — every device in a slice
is connected over ICI, and distinct slices talk over DCN. ``MeshTopo``
exposes the same queries (clique of a device, device list of a clique, info
string) over a ``jax.sharding.Mesh``, treating each ICI-connected slice as
one clique (single-slice = one all-device clique).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = [
    "MeshTopo",
    "make_mesh",
    "shard_map",
    "init_p2p",
    "can_device_access_peer",
    "init_distributed",
]

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map`` with the varying-axes check left at its default
    unless ``check_vma`` says otherwise."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def make_mesh(
    n_devices: int | None = None,
    data: int | None = None,
    feature: int = 1,
    devices=None,
) -> Mesh:
    """Build a (data, feature) mesh over the available devices.

    The ``data`` axis carries batch/data parallelism (the reference's one
    process per GPU, dist_sampling_ogb_products_quiver.py:85); the
    ``feature`` axis shards the hot feature cache (the NVLink-clique role).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    if data is None:
        data = n // feature
    if data * feature != n:
        raise ValueError(f"data*feature = {data}*{feature} != {n} devices")
    arr = np.asarray(devices[:n]).reshape(data, feature)
    return Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


def _slice_index(device) -> int:
    """ICI-connected group of a device (slice index; 0 when not exposed)."""
    return getattr(device, "slice_index", 0) or 0


class MeshTopo:
    """Clique view of the device set (reference ``p2pCliqueTopo`` parity).

    Devices in the same TPU slice are one clique: any pair can reach each
    other over ICI, exactly the property ``can_device_access_peer``
    certified for NVLink pairs.
    """

    def __init__(self, devices=None):
        self.devices = list(devices if devices is not None else jax.devices())
        groups: dict[int, list[int]] = {}
        for i, d in enumerate(self.devices):
            groups.setdefault(_slice_index(d), []).append(i)
        self.cliques: list[list[int]] = [groups[k] for k in sorted(groups)]
        self.device2clique = {
            i: ci for ci, clique in enumerate(self.cliques) for i in clique
        }

    @property
    def p2p_clique(self) -> list[list[int]]:
        return self.cliques

    def get_clique_id(self, device_index: int) -> int:
        return self.device2clique[device_index]

    def p2p_clique_device_list(self, clique_id: int) -> list[int]:
        return self.cliques[clique_id]

    @property
    def info(self) -> str:
        lines = []
        for ci, clique in enumerate(self.cliques):
            lines.append(
                f"Clique {ci} (ICI-connected): devices {clique} "
                f"[{', '.join(str(self.devices[i]) for i in clique)}]"
            )
        return "\n".join(lines)

    def __repr__(self):
        return f"MeshTopo(cliques={self.cliques})"


def can_device_access_peer(a: int, b: int) -> bool:
    """True when devices a and b share an ICI domain (same slice).

    Parity with the reference binding (quiver_feature.cu:407-413).
    """
    devices = jax.devices()
    return _slice_index(devices[a]) == _slice_index(devices[b])


def init_p2p(device_list=None) -> None:
    """No-op parity shim (reference utils.py:234-240): ICI peer access needs
    no explicit enablement on TPU."""
    return None


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the multi-host job (the reference's future-work story,
    docs/Introduction_en.md:171 "Distributed Quiver").

    Thin wrapper over ``jax.distributed.initialize``: on TPU pods every
    argument is auto-discovered from the environment, so a bare
    ``init_distributed()`` at program start is enough; after it,
    ``jax.devices()`` spans all hosts and :func:`make_mesh` builds
    DCN-spanning meshes transparently (ICI collectives within a slice, DCN
    across). Call once per host process, before any other jax use.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
