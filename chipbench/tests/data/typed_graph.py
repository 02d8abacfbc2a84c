"""The README's worked example of a graph file; ``tests/test_chipbench.py``
copies it to ``graphs/typed.py`` of a copy of the benchmark.

``graphs/lomax.py``'s graph with two things said of it: its nodes are of
two types in contiguous id ranges (``graph.type_shares``), of which only the
first may be a seed, and every edge is of one of ``graph.relations``
relations, an ``int8`` beside its endpoint, drawn from the seed. A sampled
lane has to carry the relation of an edge of the graph between its two
nodes (the graph has parallel edges: of any of them)."""

from __future__ import annotations

import numpy as np

from . import lomax

__all__ = ["make", "describe", "lane_faults"]


def _first_type(cfg: dict) -> np.ndarray:
    g = cfg["graph"]
    return np.arange(int(float(g["type_shares"][0]) * int(g["nodes"])),
                     dtype=np.int32)


def make(cfg: dict, seed: int):
    data = lomax.make(cfg, seed)
    rng = np.random.default_rng([int(seed), 6])
    data.seed_nodes = _first_type(cfg)
    data.edge_data["relation"] = rng.integers(
        0, int(cfg["graph"]["relations"]), size=data.indices.shape[0],
        dtype=np.int8)
    return data


def describe(cfg: dict):
    data = lomax.describe(cfg)
    data.seed_nodes = _first_type(cfg)
    data.edge_data["relation"] = np.zeros(data.indices.shape[0], np.int8)
    return data


def edge_keys(data, relation=None) -> np.ndarray:
    """One int64 per edge that says (row, endpoint), and the relation too
    where one is given."""
    nodes = data.indptr.shape[0] - 1
    rows = np.repeat(np.arange(nodes, dtype=np.int64), np.diff(data.indptr))
    keys = rows * nodes + data.indices
    return keys if relation is None else keys * 128 + relation


def lane_faults(data, seeds, block) -> dict:
    """Lanes whose relation no edge of the graph between their two nodes
    has, and layers whose lanes carry none."""
    faults = {"relation_missing": 0, "wrong_relation": 0}
    nodes = data.indptr.shape[0] - 1
    have = edge_keys(data, data.edge_data["relation"])
    n_id = np.asarray(block.n_id).astype(np.int64)
    for i, (src, dst, _) in enumerate(block.layers):
        carried = (block.lane_data[i] if i < len(block.lane_data)
                   else {}).get("relation")
        if carried is None or np.shape(carried) != np.shape(src):
            faults["relation_missing"] += 1
            continue
        keep = np.asarray(src) >= 0
        lanes = (n_id[np.asarray(dst)[keep]] * nodes
                 + n_id[np.asarray(src)[keep]]) * 128 + np.asarray(
                     carried)[keep].astype(np.int64)
        faults["wrong_relation"] += int((~np.isin(lanes, have)).sum())
    return faults
