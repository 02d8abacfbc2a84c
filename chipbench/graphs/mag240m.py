"""MAG240M-LSC's heterogeneous graph as OGB-LSC's ``rgnn.py`` feeds it to
R-GraphSAGE: one CSR over three node types, a relation on every edge,
float16 rows of every node, labelled papers as the seeds.

Node ids are contiguous by type: papers, then authors, then institutions,
in the shares of ``graph.types``. Each of the three published relations is
drawn on the rows of one type and added with its exact transpose, so that
every edge can be sampled from either end, as ``rgnn.py`` builds
``full_adj_t``. A node's CSR row lists the sources it samples from, and each
edge carries its relation (``edge_data["relation"]``, int8):

    0  paper <- paper               cites, drawn on paper rows, and its transpose
    1  author <- paper              writes, drawn on author rows (an author's papers)
    2  paper <- author              the transpose of 1
    3  author <- institution        affiliated_with, drawn on author rows
    4  institution <- author        the transpose of 3

A drawn relation's rows take truncated Lomax degrees at the relation's mean
(``graph.drawn`` edges over the rows' type), capped at ``max_over_mean``
times that mean, with endpoints uniform over the other end's type
(``graph.endpoints`` must be ``"uniform"``). ``graphs/lomax.py``'s draw where
every row has an edge (cites, writes); where the mean is under one edge a
row (affiliated_with) rows may have none.

Scale: ``nodes`` and ``edges`` are the counts that are drawn; the shares of
the types and of the relations are ``graph.types`` and ``graph.drawn``, so a
configuration cut in scale keeps its shapes' proportions. The seeds are
``graph.train_papers`` papers at that scale (never fewer than eight
batches), drawn at random; each has a label of ``classes`` and, as
``lomax.py`` does, +3 on its label's column so that the loss falls.
"""

from __future__ import annotations

import numpy as np

from ..inputs import Inputs
from .lomax import allocate, degree_sequence

__all__ = ["make", "describe", "lane_faults", "RELATIONS"]

RELATIONS = 5
_ROWS = 1 << 16  # feature rows written at a time
_POOL = 1 << 16  # distinct rows drawn


def _sizes(cfg: dict) -> dict:
    """Node and edge counts of each type and relation at the configured
    scale; a draw of the seed never changes one."""
    g = cfg["graph"]
    if g["endpoints"] != "uniform":
        raise ValueError("graphs/mag240m.py draws uniform endpoints")
    nodes, edges = int(g["nodes"]), int(g["edges"])
    if edges % 2:
        raise ValueError("`graph.edges` is even: every drawn edge has its "
                         "transpose")
    types, drawn = g["types"], g["drawn"]
    node_scale = nodes / sum(types.values())
    papers = int(round(types["paper"] * node_scale))
    institutions = max(1, int(round(types["institution"] * node_scale)))
    authors = nodes - papers - institutions
    edge_scale = edges / 2 / sum(drawn.values())
    writes = int(round(drawn["writes"] * edge_scale))
    affiliated = int(round(drawn["affiliated_with"] * edge_scale))
    cites = edges // 2 - writes - affiliated
    batches = 8 * int(cfg["batch"])
    seeds = min(papers, max(int(round(g["train_papers"] * node_scale)),
                            batches))
    return dict(papers=papers, authors=authors, institutions=institutions,
                cites=cites, writes=writes, affiliated=affiliated,
                seeds=seeds)


def _cap(edges: int, rows: int, over_mean: float) -> int:
    return max(1, int(round(over_mean * edges / rows)))


def sparse_degrees(rng, nodes: int, edges: int, alpha: float,
                   max_degree: int) -> np.ndarray:
    """Truncated Lomax degrees that sum to ``edges`` where a row may have
    none: ``lomax.degree_sequence``'s draw and bisection without its floor
    of one edge a row."""
    if not 0 <= edges <= nodes * max_degree:
        raise ValueError(f"{edges} edges do not fit {nodes} rows of at most "
                         f"{max_degree}")
    tail = rng.pareto(alpha, nodes).astype(np.float32)
    top = np.float32(max_degree)

    def degrees(scale):
        return np.minimum(tail * np.float32(scale), top).astype(np.int64)

    lo, hi = 0.0, 1.0
    while int(degrees(hi).sum()) < edges:
        hi *= 2.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if int(degrees(mid).sum()) <= edges:
            lo = mid
        else:
            hi = mid
    deg = degrees(lo)
    room = np.flatnonzero(deg < max_degree)[:edges - int(deg.sum())]
    deg[room] += 1
    if int(deg.sum()) != edges:
        raise ValueError("degree sequence did not reach the configured sizes")
    return deg


def _drawn(rng, rows: int, edges: int, sources: int, alpha: float,
           over_mean: float, floor_one: bool):
    """One drawn relation: the degree of each of its rows and each edge's
    endpoint (an offset into the source type), in row order."""
    cap = _cap(edges, rows, over_mean)
    if floor_one:
        deg = degree_sequence(rng, rows, edges, alpha, cap)
    else:
        deg = sparse_degrees(rng, rows, edges, alpha, cap)
    ends = rng.integers(0, sources, size=edges, dtype=np.int32)
    return deg, ends


def _graph(cfg: dict, rng):
    """``indptr``, ``indices``, the relation of every edge, and the sizes."""
    n = _sizes(cfg)
    alpha = float(cfg["graph"]["degree_alpha"])
    over_mean = float(cfg["graph"]["max_over_mean"])
    P, A, I = n["papers"], n["authors"], n["institutions"]
    nodes = P + A + I
    cites_deg, cites_end = _drawn(rng, P, n["cites"], P, alpha, over_mean,
                                  True)
    writes_deg, writes_end = _drawn(rng, A, n["writes"], P, alpha,
                                    over_mean, True)
    aff_deg, aff_end = _drawn(rng, A, n["affiliated"], I, alpha, over_mean,
                              False)
    # each part: the rows it fills (ascending), their endpoints, its
    # relation; a row's parts follow one another in this order
    parts = [
        (np.repeat(np.arange(P, dtype=np.int32), cites_deg), cites_end, 0),
        _transpose(np.repeat(np.arange(P, dtype=np.int32), cites_deg),
                   cites_end, 0),
        _transpose(np.repeat(np.arange(P, P + A, dtype=np.int32),
                             writes_deg), writes_end, 2),
        (np.repeat(np.arange(P, P + A, dtype=np.int32), writes_deg),
         writes_end, 1),
        (np.repeat(np.arange(P, P + A, dtype=np.int32), aff_deg),
         aff_end + np.int32(P + A), 3),
        _transpose(np.repeat(np.arange(P, P + A, dtype=np.int32), aff_deg),
                   aff_end + np.int32(P + A), 4),
    ]
    counts = [np.bincount(rows, minlength=nodes) for rows, _, _ in parts]
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(sum(counts), out=indptr[1:])
    edges = int(indptr[-1])
    indices = allocate((edges,), np.int32)
    relation = allocate((edges,), np.int8)
    cursor = indptr[:-1].copy()
    for (rows, ends, rel), count in zip(parts, counts):
        start = np.zeros(nodes, np.int64)
        np.cumsum(count[:-1], out=start[1:])
        at = cursor[rows] + (np.arange(rows.shape[0]) - start[rows])
        indices[at] = ends
        relation[at] = rel
        cursor += count
    return indptr, indices, relation, n


def _transpose(rows, ends, relation: int):
    """The transpose of a drawn relation, grouped by its new rows (the
    drawn endpoints)."""
    order = np.argsort(ends, kind="stable")
    return ends[order], rows[order], relation


def _rows(rng, nodes: int, width: int) -> np.ndarray:
    """Unit-variance uniform float16 rows: a pool of ``_POOL`` rows drawn
    from the seed, and each node's row one of them, picked at random. What
    a row holds changes nothing the step does; drawing 2.9 G values one by
    one took 70 s of the chip host's set-up, the pool and the copy of its
    rows take a few."""
    pool = rng.random((_POOL, width), dtype=np.float32)
    pool -= np.float32(0.5)
    pool *= np.float32(12 ** 0.5)
    pool = pool.astype(np.float16)
    feat = allocate((nodes, width), np.float16)
    for lo in range(0, nodes, _ROWS):
        hi = min(lo + _ROWS, nodes)
        np.take(pool, rng.integers(0, _POOL, hi - lo), axis=0,
                out=feat[lo:hi], mode="clip")
    return feat


def make(cfg: dict, seed: int) -> Inputs:
    if np.dtype(cfg["feature_dtype"]) != np.float16:
        raise ValueError("graphs/mag240m.py makes float16 rows")
    rng = np.random.default_rng([int(seed), 1])
    indptr, indices, relation, n = _graph(cfg, rng)
    nodes, width = indptr.shape[0] - 1, int(cfg["feature_dim"])
    feat = _rows(rng, nodes, width)
    classes = int(cfg["classes"])
    labels = rng.integers(0, classes, size=nodes, dtype=np.int32)
    seeds = np.sort(rng.choice(n["papers"], size=n["seeds"],
                               replace=False)).astype(np.int32)
    column = labels[seeds] % width
    feat[seeds, column] += np.float16(3.0)
    return Inputs(indptr, indices, feat, labels, seed_nodes=seeds,
                  edge_data={"relation": relation})


def describe(cfg: dict) -> Inputs:
    """The configured shapes with no values: every row of a drawn relation
    at its mean degree, the largest row at its cap."""
    n = _sizes(cfg)
    nodes = n["papers"] + n["authors"] + n["institutions"]
    edges = int(cfg["graph"]["edges"])
    deg = np.full(nodes, edges // nodes, np.int64)
    deg[0] = _cap(n["cites"], n["papers"], float(cfg["graph"]["max_over_mean"]))
    deg[1] += edges - int(deg.sum())
    indptr = np.zeros(nodes + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return Inputs(
        indptr, np.zeros(edges, np.int32),
        np.zeros((nodes, int(cfg["feature_dim"])), np.float16),
        np.zeros(nodes, np.int32),
        seed_nodes=np.arange(n["seeds"], dtype=np.int32),
        edge_data={"relation": np.zeros(edges, np.int8)})


def lane_faults(data, seeds, block) -> dict:
    """Every valid lane of every layer has to carry the relation of an edge
    of the graph between its two nodes, and every padded lane -1; a layer
    whose lanes carry nothing is a fault of its own. The lanes are checked
    against the rows of their layer's targets, all of them: a lane that no
    edge of its target's row matches, endpoint and relation, is wrong."""
    faults = {"relation_missing": 0, "wrong_relation": 0}
    nodes = data.indptr.shape[0] - 1
    edge_relation = data.edge_data["relation"]
    n_id = np.asarray(block.n_id).astype(np.int64)
    for i, (src, dst, _) in enumerate(block.layers):
        carried = (block.lane_data[i] if i < len(block.lane_data)
                   else {}).get("relation")
        if carried is None or np.shape(carried) != np.shape(src):
            faults["relation_missing"] += 1
            continue
        src, dst = np.asarray(src), np.asarray(dst).astype(np.int64)
        carried = np.asarray(carried).astype(np.int64)
        keep = src >= 0
        faults["wrong_relation"] += int((carried[~keep] != -1).sum())
        targets = np.unique(dst[keep])
        first = data.indptr[n_id[targets]]
        deg = data.indptr[n_id[targets] + 1] - first
        slots = np.repeat(first - np.cumsum(deg) + deg, deg) + np.arange(
            int(deg.sum()))
        have = ((np.repeat(targets, deg) * nodes + data.indices[slots]) * 256
                + edge_relation[slots] + 128)
        lanes = ((dst[keep] * nodes + n_id[src[keep]]) * 256
                 + carried[keep] + 128)
        faults["wrong_relation"] += int((~np.isin(lanes, have)).sum())
    return faults
