"""The one graph the benchmark has had since PR 23: one node type, one CSR,
truncated Lomax degrees, endpoints by the configuration's
``graph.endpoints``, float32 rows with a bump on the label's column; every
node may be a seed and no edge carries anything. ``make`` is the draw that
``inputs.make_inputs`` was, draw for draw: the digests of
``tests/test_chipbench.py::test_inputs_are_the_bytes_they_were`` hold it.

Everything is made on the host by one ``numpy.random.Generator`` in one
thread: no BLAS, no sort over the edges, so the stage takes the same time
in every run.
"""

from __future__ import annotations

import mmap

import numpy as np

from ..inputs import Inputs

_CHUNK = 1 << 23  # edges drawn at a time into a buffer that is reused

__all__ = ["make", "describe", "allocate", "degree_sequence",
           "draw_endpoints"]


def allocate(shape, dtype) -> np.ndarray:
    """A host array whose pages are mapped before it is written.

    A fresh gigabyte touched page by page costs seconds of page faults on a
    virtual machine, and a different number of them from run to run;
    ``MAP_POPULATE`` maps the whole array in one call."""
    dtype = np.dtype(dtype)
    size = int(np.prod(shape)) * dtype.itemsize
    buf = mmap.mmap(-1, max(size, 1), flags=mmap.MAP_PRIVATE
                    | mmap.MAP_ANONYMOUS | getattr(mmap, "MAP_POPULATE", 0))
    return np.frombuffer(buf, dtype=dtype, count=int(np.prod(shape))).reshape(shape)


def degree_sequence(rng, nodes: int, edges: int, alpha: float,
                    max_degree: int) -> np.ndarray:
    """Truncated power-law degrees that sum to ``edges`` exactly and whose
    largest is ``max_degree`` exactly.

    The draw is the one ``generate_pareto_graph`` makes (numpy's ``pareto``,
    a Lomax tail of index ``alpha``, every node at least one edge); the
    scale is then found by bisection so that the truncated, floored degrees
    reach the published edge count, and the remainder of a few edges goes
    one each to the first nodes that have room.
    """
    if not nodes <= edges <= nodes * max_degree:
        raise ValueError(
            f"{edges} edges cannot be spread over {nodes} nodes with degrees "
            f"in [1, {max_degree}]"
        )
    tail = rng.pareto(alpha, nodes).astype(np.float32)
    top = np.float32(max_degree - 1)

    def degrees(scale):
        return np.minimum(tail * np.float32(scale), top).astype(np.int32) + 1

    def total(scale):
        return int(degrees(scale).sum(dtype=np.int64))

    lo, hi = 0.0, float(max_degree)
    while total(hi) < edges:
        hi *= 2.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        if total(mid) <= edges:
            lo = mid
        else:
            hi = mid
    deg = degrees(lo).astype(np.int64)
    deg[np.argmax(tail)] = max_degree
    rest = edges - int(deg.sum())
    if rest > 0:
        room = np.flatnonzero(deg < max_degree)[:rest]
        deg[room] += 1
    elif rest < 0:
        room = np.flatnonzero((deg > 1) & (deg < max_degree))[:-rest]
        deg[room] -= 1
    if int(deg.sum()) != edges or int(deg.max()) != max_degree:
        raise ValueError("degree sequence did not reach the configured sizes")
    return deg


def draw_endpoints(rng, law: str, indptr: np.ndarray,
                   indices: np.ndarray) -> None:
    """Fill ``indices`` with every edge's endpoint, by the configuration's
    ``graph.endpoints``.

    ``"uniform"``: any node, with equal chance. A node is then reached
    with chance 1/N whatever its degree, so ordering rows by degree orders
    them by nothing the traffic follows, and a cache of any share of the
    rows hits that share.

    ``"degree"``: node ``v`` with chance ``deg(v) / edges``, the owner of a
    uniformly drawn edge slot, ``deg`` being the out-degrees just drawn.
    In-degree then follows out-degree as on a symmetrised graph, and hubs
    are sampled in proportion to their degree. The owners of all slots are
    one more ``int32[edges]`` on the host while the edges are drawn (472
    MiB at ogbn-products' 123.7 M edges), and the draw is a random read of
    that table for every edge: there the inputs stage takes 6.7 s against
    3.0 s under ``"uniform"`` on the chip's host (PERF.md section 6, PR 27).
    """
    nodes, edges = indptr.shape[0] - 1, indices.shape[0]
    if law == "uniform":
        high, owner = nodes, None
    elif law == "degree":
        # every node has an edge, so its first slot is its own: mark the
        # first slots of nodes 1.. and sum
        high, owner = edges, np.zeros(edges, np.int32)
        owner[indptr[1:-1]] = 1
        np.cumsum(owner, dtype=np.int32, out=owner)
    else:
        raise ValueError(
            f"`graph.endpoints` is {law!r}: \"uniform\" or \"degree\"")
    for lo in range(0, edges, _CHUNK):
        hi = min(lo + _CHUNK, edges)
        drawn = rng.integers(0, high, size=hi - lo, dtype=np.int32)
        indices[lo:hi] = drawn if owner is None else owner[drawn]


def make(cfg: dict, seed: int) -> Inputs:
    """The configuration's graph as a CSR, its feature table, and labels
    that the model can learn, so that the loss falls."""
    if np.dtype(cfg["feature_dtype"]) != np.float32:
        raise ValueError("graphs/lomax.py makes float32 features")
    g = cfg["graph"]
    nodes, edges = int(g["nodes"]), int(g["edges"])
    rng = np.random.default_rng([int(seed), 1])
    deg = degree_sequence(rng, nodes, edges, float(g["degree_alpha"]),
                          int(g["max_degree"]))
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = allocate((edges,), np.int32)
    draw_endpoints(rng, g["endpoints"], indptr, indices)
    classes, width = int(cfg["classes"]), int(cfg["feature_dim"])
    # unit-variance uniform features, and on each node's label column a
    # bump that a model can learn from the node's own row; where there are
    # more classes than columns, classes `width` apart share a column
    feat = allocate((nodes, width), np.float32)
    rng.random(out=feat, dtype=np.float32)
    feat -= np.float32(0.5)
    feat *= np.float32(12 ** 0.5)
    labels = rng.integers(0, classes, size=nodes, dtype=np.int32)
    column = labels if classes <= width else labels % width
    feat[np.arange(nodes), column] += np.float32(3.0)
    return Inputs(indptr, indices, feat, labels)


def describe(cfg: dict) -> Inputs:
    """Inputs of the configured shapes with no values: the degree sequence
    is flat but for one row of ``max_degree``, which the topology records."""
    g = cfg["graph"]
    nodes, edges = int(g["nodes"]), int(g["edges"])
    deg = np.full(nodes, (edges - int(g["max_degree"])) // (nodes - 1),
                  np.int64)
    deg[0] = int(g["max_degree"])
    deg[1] += edges - int(deg.sum())
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return Inputs(
        indptr, np.zeros(edges, np.int32),
        np.zeros((nodes, int(cfg["feature_dim"])), cfg["feature_dtype"]),
        np.zeros(nodes, np.int32))
