"""Typed runtime configuration for quiver-tpu.

The reference scatters three string-typed knobs across modules: a byte-size
parser duplicated in two files (torch-quiver feature.py:64-81 and
shard_tensor.py:42-68), ``cache_policy`` strings (feature.py:35-37), and the
sampler ``mode`` flag (pyg/sage_sampler.py:43-44). Here they are unified into
one module with enums that still accept the reference's spellings for API
parity.
"""

from __future__ import annotations

import dataclasses
import enum
import re

__all__ = [
    "parse_size_bytes",
    "resolve_platform_strategy",
    "CachePolicy",
    "SampleMode",
    "SamplerConfig",
]


def resolve_platform_strategy(env_var: str, choices, tpu_default: str,
                              other_default: str) -> str:
    """Shared env-override-then-platform-default resolver.

    Several ops keep two bit-identical implementations whose cost model
    may flip between backends (on a v5e a scatter costs 4.4x a payload
    sort of the same lanes: PERF.md, PR 26): occurrence counts, chunked
    inference aggregation. Neither pair has a chip number (ROADMAP D4).
    Each exposes an env var that FORCES a strategy for a measurement run;
    a typo'd force must raise, not silently measure the platform default.
    """
    import os

    v = os.environ.get(env_var, "").strip().lower()
    if v:
        if v not in choices:
            raise ValueError(f"{env_var}={v!r} is not one of {tuple(choices)}")
        return v
    import jax

    return (tpu_default if jax.default_backend() == "tpu"
            else other_default)

_SIZE_RE = re.compile(r"^\s*([0-9]*\.?[0-9]+)\s*([A-Za-z]*)\s*$")

_UNITS = {
    "": 1,
    "B": 1,
    "K": 2**10,
    "KB": 2**10,
    "M": 2**20,
    "MB": 2**20,
    "G": 2**30,
    "GB": 2**30,
    "T": 2**40,
    "TB": 2**40,
}


def parse_size_bytes(size: int | float | str) -> int:
    """Parse a human byte-size like ``"0.9M"``, ``"3GB"``, ``200`` into bytes.

    Accepts every spelling the reference accepts (K/KB/M/MB/G/GB, case
    insensitive, optional fraction) plus T/TB and plain ints (bytes).
    """
    if isinstance(size, bool):
        raise TypeError("size must be a number or string, not bool")
    if isinstance(size, (int, float)):
        if size < 0:
            raise ValueError(f"size must be non-negative, got {size}")
        return int(size)
    m = _SIZE_RE.match(size)
    if not m:
        raise ValueError(f"cannot parse byte size {size!r}")
    value, unit = m.group(1), m.group(2).upper()
    if unit not in _UNITS:
        raise ValueError(f"unknown size unit {unit!r} in {size!r}")
    return int(float(value) * _UNITS[unit])


class CachePolicy(enum.Enum):
    """Hot-tier placement policy for the feature cache.

    ``DEVICE_REPLICATE`` replicates the hot rows into every device's HBM
    (reference ``device_replicate``, feature.py:120-124). ``MESH_SHARD``
    partitions the hot rows across the devices of the mesh's feature axis and
    gathers over ICI — the TPU analogue of the reference's NVLink-clique
    partitioning (``p2p_clique_replicate``, feature.py:126-166).
    """

    DEVICE_REPLICATE = "device_replicate"
    MESH_SHARD = "mesh_shard"

    @classmethod
    def parse(cls, value: "CachePolicy | str") -> "CachePolicy":
        if isinstance(value, cls):
            return value
        aliases = {
            "device_replicate": cls.DEVICE_REPLICATE,
            "p2p_clique_replicate": cls.MESH_SHARD,  # reference spelling
            "mesh_shard": cls.MESH_SHARD,
        }
        try:
            return aliases[value]
        except KeyError:
            raise ValueError(
                f"unknown cache policy {value!r}; expected one of {sorted(aliases)}"
            ) from None


class SampleMode(enum.Enum):
    """Where the graph topology lives during sampling.

    ``HBM`` keeps indptr/indices in device HBM (reference ``GPU`` mode,
    sage_sampler.py:54). ``HOST`` keeps the large ``indices`` array in pinned
    host memory and stages gathers — the TPU replacement for the reference's
    UVA zero-copy mode (quiver_sample.cu:400-408), since TPU kernels cannot
    dereference host pointers.
    """

    HBM = "hbm"
    HOST = "host"

    @classmethod
    def parse(cls, value: "SampleMode | str") -> "SampleMode":
        if isinstance(value, cls):
            return value
        aliases = {
            "gpu": cls.HBM,  # reference spelling
            "hbm": cls.HBM,
            "uva": cls.HOST,  # reference spelling
            "host": cls.HOST,
            "zero_copy": cls.HOST,
        }
        try:
            return aliases[value.lower()]
        except KeyError:
            raise ValueError(
                f"unknown sample mode {value!r}; expected one of {sorted(aliases)}"
            ) from None


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """Static-shape configuration for the multi-layer sampler.

    XLA requires static shapes, so the ragged outputs of the reference's
    sampler (quiver_sample.cu:100-119) become padded blocks. ``seed_capacity``
    is the padded batch size; ``frontier_caps`` bounds the unique-node count
    after each layer (defaults to min(worst-case growth, node_count)).
    """

    sizes: tuple[int, ...]
    seed_capacity: int
    frontier_caps: tuple[int, ...]
    mode: SampleMode = SampleMode.HBM

    def __post_init__(self):
        if len(self.frontier_caps) != len(self.sizes):
            raise ValueError("frontier_caps must have one entry per layer")
        if self.seed_capacity <= 0:
            raise ValueError("seed_capacity must be positive")
