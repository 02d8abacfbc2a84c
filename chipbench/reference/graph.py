"""The sampler's contract, which every model's reference takes as its
input: what a sampled block is, and what it has to satisfy, checked against
the harness's own copy of the graph in numpy. The neighbour sampler draws
``min(deg, k)`` neighbours of every target from that target's row, and the
block's local ids are consistent. Which neighbours were drawn is the
sampler's choice; a block that passes here is a sample a reference accepts
as its input."""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Block", "block_faults"]

ROWS_CHECKED = 512  # targets per hop whose every edge is looked up in the CSR


@dataclasses.dataclass
class Block:
    """One sampled mini-batch of one worker: ``n_id`` maps local ids to
    nodes (-1 pads), seeds first; ``layers`` holds, input layer first,
    ``(src, dst, n_dst)``: an edge list in local ids (``src`` -1 on a lane
    that holds no edge) and how many local ids are targets. ``lane_data``
    holds what the lanes carry from the sampler to the model (a relation,
    a weight, a timestamp): per layer, in ``layers``' order, a dict of
    arrays aligned with that layer's ``src``; empty where lanes carry
    nothing. The graph's file checks it against the graph
    (``lane_faults``), a model's plain side reads it."""

    n_id: np.ndarray
    layers: list
    num_seeds: int
    overflow: int = 0  # lanes the sampler clipped at a frontier cap
    lane_data: list = dataclasses.field(default_factory=list)


def block_faults(indptr, indices, seeds, block, fanout, rng) -> dict:
    """Counts of what is wrong with ``block``; all zero for a sound one.
    ``fanout`` is seeds-outward, as the configuration gives it."""
    n_id = np.asarray(block.n_id)
    valid_ids = n_id[n_id >= 0]
    faults = {
        "seeds_not_first": int(
            not np.array_equal(n_id[:block.num_seeds], seeds)),
        "duplicate_nodes": int(
            valid_ids.shape[0] - np.unique(valid_ids).shape[0]
            # duplicate seeds keep their slots
            - (len(seeds) - np.unique(seeds).shape[0])),
        "pad_inside": int((n_id[:valid_ids.shape[0]] < 0).sum()),
        "wrong_counts": 0,
        "not_neighbours": 0,
    }
    degree = np.diff(indptr)
    targets = block.num_seeds  # valid targets of the hop, seeds-outward
    for (src, dst, n_dst), k in zip(block.layers[::-1], fanout):
        src, dst = np.asarray(src), np.asarray(dst)
        keep = src >= 0
        src, dst = src[keep], dst[keep]
        got = np.bincount(dst, minlength=n_dst)[:n_dst]
        want = np.zeros(n_dst, np.int64)
        want[:targets] = np.minimum(degree[n_id[:targets]], k)
        faults["wrong_counts"] += int((got != want).sum())
        faults["not_neighbours"] += int(
            ((n_id[src] < 0) | (n_id[dst] < 0)).sum())
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        starts = np.searchsorted(dst, np.arange(n_dst + 1))
        rows = rng.choice(targets, size=min(ROWS_CHECKED, targets),
                          replace=False)
        for t in rows:
            node = n_id[t]
            row = indices[indptr[node]:indptr[node + 1]]
            drawn = n_id[src[starts[t]:starts[t + 1]]]
            faults["not_neighbours"] += int((~np.isin(drawn, row)).sum())
        targets = max(targets, int(src.max(initial=-1)) + 1)
    faults["pad_inside"] += int(targets != valid_ids.shape[0])
    return faults
