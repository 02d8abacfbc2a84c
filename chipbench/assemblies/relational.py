"""One CSR of typed nodes in HBM whose edges carry their relation, one
feature store of the rows as the graph file makes them (float16 for
MAG240M): ``CSRTopo`` with ``edge_relation``, ``GraphSageSampler`` with the
configuration's ``fanout`` and pinned ``frontier_caps``, ``Feature``.

The program's sampler reads each lane's relation from the edge word it
fetches and hands it on in ``Adj.relation``; ``blocks`` puts what the
sampler gave, and nothing looked up here, into ``Block.lane_data``, so the
graph file's ``lane_faults`` checks the program's own output."""

from __future__ import annotations

import types

import numpy as np

from ..reference.graph import Block

__all__ = ["build", "blocks"]


def build(cfg: dict, traffic: dict, data, mesh) -> types.SimpleNamespace:
    import quiver_tpu

    placement = traffic["feature"]
    if placement["store"] != "plain":
        raise ValueError(f"no feature store {placement['store']!r} here")
    topo = quiver_tpu.CSRTopo(indptr=data.indptr, indices=data.indices,
                              edge_relation=data.edge_data["relation"])
    sampler = quiver_tpu.GraphSageSampler(
        topo, list(cfg["fanout"]), frontier_caps=list(cfg["frontier_caps"]))
    nodes, width = data.features.shape
    rows = int(round(float(placement["cache_ratio"]) * nodes))
    store = quiver_tpu.Feature(
        device_cache_size=rows * width * data.features.dtype.itemsize,
        csr_topo=topo)
    return types.SimpleNamespace(
        sampler=sampler, feature=store.from_cpu_tensor(data.features))


def blocks(parts, cfg: dict, seeds: np.ndarray, key: np.ndarray,
           workers: int) -> list:
    """One block per worker, drawn by the sampler's own jit-composable
    entry with the key the step derives for that worker
    (``split(fold_in(key, worker))[0]``); each layer's lanes carry the
    relation the sampler read, in lane order."""
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
        block = Block(np.asarray(n_id), [], len(part))
        for adj in adjs:
            src, dst = np.asarray(adj.edge_index)
            block.layers.append((src, dst, int(adj.size[1])))
            # (fanout, targets) -> lane order, aligned with src
            block.lane_data.append(
                {"relation": np.asarray(adj.relation).T.reshape(-1)})
        block.overflow = int(overflow)
        out.append(block)
    return out
