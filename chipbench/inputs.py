"""What a run is fed, all from ``--seed``: the graph with its rows and labels
(drawn by the graph file the configuration names, ``graphs/<name>.py``), the
weights (drawn by the plain side of its model) and the step feed.

The seed decides values and never a shape: ``nodes``, ``edges`` and the
largest degree come from the configuration's file, so every seed drives the
programs that the checkout's first run compiled.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import spec

__all__ = ["Inputs", "make_inputs", "make_weights", "Feed"]


@dataclasses.dataclass
class Inputs:
    """What a graph file makes. Node ids are flat: typed nodes are
    contiguous id ranges of one CSR."""

    indptr: np.ndarray    # (nodes + 1,) int64
    indices: np.ndarray   # (edges,) int32
    features: np.ndarray  # (nodes, feature_dim), the configuration's dtype
    labels: np.ndarray    # (nodes,) int32
    # the ids a batch may be drawn from (the labelled nodes of one type,
    # say); None: every node
    seed_nodes: np.ndarray | None = None
    # what an edge carries beside its endpoint: name -> array aligned with
    # `indices` (an int8 relation, say)
    edge_data: dict = dataclasses.field(default_factory=dict)


def make_inputs(cfg: dict, seed: int) -> Inputs:
    """The configuration's graph, feature table and labels, drawn from the
    seed by the graph file that its ``graph.generator`` names."""
    return spec.load_graph(cfg["graph"]["generator"]).make(cfg, seed)


def make_weights(cfg: dict, seed: int) -> list[dict]:
    """Initial weights, one dict of leaves per layer (input layer first),
    drawn by the plain side of the configuration's model
    (``reference/<model>.py``) from the seed. The harness owns them: the
    program and the reference are both handed these, neither makes its
    own."""
    rng = np.random.default_rng([int(seed), 2])
    return spec.load_model(cfg["model"], "reference").make_weights(cfg, rng)


class Feed:
    """Step ``i``'s seed nodes and PRNG key: a permutation of the nodes that
    may be seeds, cut into global batches (an epoch that wraps), and raw
    ``uint32[2]`` keys. Every step's rows differ from the step before."""

    KEYS = 1 << 14

    def __init__(self, nodes: int, global_batch: int, seed: int,
                 seed_nodes: np.ndarray | None = None):
        rng = np.random.default_rng([int(seed), 3])
        self.order = rng.permutation(
            nodes if seed_nodes is None else np.asarray(seed_nodes)
        ).astype(np.int32)
        self.global_batch = int(global_batch)
        if self.global_batch > self.order.shape[0]:
            raise ValueError("a global batch larger than the graph's seeds")
        self.steps_per_epoch = self.order.shape[0] // self.global_batch
        self.keys = rng.integers(0, 1 << 32, size=(self.KEYS, 2),
                                 dtype=np.uint32)

    @classmethod
    def of(cls, data: Inputs, global_batch: int, seed: int) -> "Feed":
        """The feed over ``data``: its ``seed_nodes``, or every node."""
        return cls(data.indptr.shape[0] - 1, global_batch, seed,
                   data.seed_nodes)

    def seeds(self, i: int) -> np.ndarray:
        j = i % self.steps_per_epoch
        return self.order[j * self.global_batch:(j + 1) * self.global_batch]

    def key(self, i: int) -> np.ndarray:
        return self.keys[i % self.KEYS]
