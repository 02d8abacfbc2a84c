"""The README's worked example of an assembly file;
``tests/test_chipbench.py`` copies it to ``assemblies/typed.py`` of a copy of
the benchmark.

It builds what ``assemblies/homogeneous.py`` builds, and every lane of a
block carries its edge's relation in ``Block.lane_data``. The program's
sampler hands no payload on yet, so the relation is looked up here, in the
harness's own CSR: the first edge of the target's row that ends at the
source. An assembly over a sampler that carries the payload reads it from
the sampler's output instead."""

from __future__ import annotations

import numpy as np

from ..graphs.typed import edge_keys
from . import homogeneous

__all__ = ["build", "blocks"]


def build(cfg: dict, traffic: dict, data, mesh):
    parts = homogeneous.build(cfg, traffic, data, mesh)
    keys = edge_keys(data)
    order = np.argsort(keys, kind="stable")
    parts.keys = keys[order]
    parts.relation = data.edge_data["relation"][order]
    parts.nodes = data.indptr.shape[0] - 1
    return parts


def blocks(parts, cfg: dict, seeds, key, workers: int) -> list:
    out = homogeneous.blocks(parts, cfg, seeds, key, workers)
    for block in out:
        n_id = np.asarray(block.n_id).astype(np.int64)
        for src, dst, _ in block.layers:
            lanes = n_id[dst] * parts.nodes + n_id[np.maximum(src, 0)]
            edge = np.minimum(np.searchsorted(parts.keys, lanes),
                              parts.keys.shape[0] - 1)
            block.lane_data.append({"relation": np.where(
                src >= 0, parts.relation[edge], np.int8(-1))})
    return out
