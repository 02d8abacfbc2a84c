"""One node type, one CSR in HBM, one feature store: ``CSRTopo``,
``GraphSageSampler`` and ``Feature`` or ``ShardedFeature`` as the traffic
mix's ``feature`` says, with the configuration's ``fanout`` and pinned
``frontier_caps`` (nothing timed chooses the code, no election, no probe
program). A lane carries nothing: ``Block.lane_data`` stays empty."""

from __future__ import annotations

import types

import numpy as np

from ..reference.graph import Block

__all__ = ["build", "blocks"]


def build(cfg: dict, traffic: dict, data, mesh) -> types.SimpleNamespace:
    import quiver_tpu

    topo = quiver_tpu.CSRTopo(indptr=data.indptr, indices=data.indices)
    sampler = quiver_tpu.GraphSageSampler(
        topo, list(cfg["fanout"]), frontier_caps=list(cfg["frontier_caps"]))
    nodes, width = data.features.shape
    placement = traffic["feature"]
    shards = (int(traffic["mesh"]["feature"])
              if placement["store"] == "sharded" else 1)
    rows = -(-int(round(float(placement["cache_ratio"]) * nodes)) // shards)
    budget = rows * width * data.features.dtype.itemsize
    if placement["store"] == "sharded":
        store = quiver_tpu.ShardedFeature(
            mesh, device_cache_size=budget, csr_topo=topo)
    elif placement["store"] == "plain":
        store = quiver_tpu.Feature(device_cache_size=budget, csr_topo=topo)
    else:
        raise ValueError(f"no feature store {placement['store']!r}")
    return types.SimpleNamespace(
        sampler=sampler, feature=store.from_cpu_tensor(data.features))


def blocks(parts, cfg: dict, seeds: np.ndarray, key: np.ndarray,
           workers: int) -> list:
    """One block per worker, drawn by the sampler's own jit-composable
    entry with the key the step derives for that worker
    (``split(fold_in(key, worker))[0]``)."""
    import jax
    import jax.numpy as jnp

    out = []
    batch = int(cfg["batch"])
    for w, part in enumerate(np.array_split(np.asarray(seeds), workers)):
        padded = np.full(batch, -1, np.int32)
        padded[:len(part)] = part
        sample_key = jax.random.split(
            jax.random.fold_in(jnp.asarray(key), w))[0]
        n_id, _, adjs, overflow, _, _ = parts.sampler.sample_padded(
            parts.sampler.topo, jnp.asarray(padded), jnp.int32(len(part)),
            sample_key)
        layers = []
        for adj in adjs:
            src, dst = np.asarray(adj.edge_index)
            layers.append((src, dst, int(adj.size[1])))
        block = Block(np.asarray(n_id), layers, len(part))
        block.overflow = int(overflow)
        out.append(block)
    return out
