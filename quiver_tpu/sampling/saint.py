"""GraphSAINT-style subgraph sampling, TPU-native.

The reference *planned* a GraphSAINT sampler — ``qv.saint_subgraph`` appears
only as a commented-out block in tests/python/cuda/test_saint.py and never
landed (SURVEY §2.5). Here it is a real feature: node-induced subgraph
extraction with static shapes, plus the three standard GraphSAINT samplers
(node, edge, random-walk) and loss/aggregation normalization estimation
(Zeng et al., "GraphSAINT: Graph Sampling Based Inductive Learning Method").

Static-shape design: a node budget ``C`` (padded, -1 sentinel) and a
per-node degree cap ``D``; the induced edge set is emitted as a (C*D,)
padded local edge list. Membership testing is a sort + binary search over
the node set — no hash tables, no atomics (SURVEY §7.1).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.topology import CSRTopo
from ..ops.reindex import masked_unique
from ..ops.sample import sample_layer, staged_gather

__all__ = [
    "SaintSubgraph",
    "saint_subgraph",
    "SAINTNodeSampler",
    "SAINTEdgeSampler",
    "SAINTRandomWalkSampler",
    "estimate_saint_norm",
]


class SaintSubgraph(NamedTuple):
    """Induced subgraph in local ids, padded with -1.

    node_id: (C,) global node ids (the subgraph's local id i is node_id[i]).
    edge_index: (2, C*D) [src, dst] local ids, -1 where invalid.
    num_nodes: scalar valid node count.
    num_edges: scalar valid edge count.
    """

    node_id: jax.Array
    edge_index: jax.Array
    num_nodes: jax.Array
    num_edges: jax.Array


def _membership(nodes, queries):
    """Local id of each query in ``nodes`` (or -1).

    nodes: (C,) ids, -1 padded, may contain duplicates (first wins).
    queries: (...,) ids (-1 lanes return -1).
    """
    C = nodes.shape[0]
    sent = jnp.iinfo(nodes.dtype).max
    keyed = jnp.where(nodes >= 0, nodes, sent)
    order = jnp.argsort(keyed)
    sorted_nodes = keyed[order]
    pos = jnp.searchsorted(sorted_nodes, queries)
    pos = jnp.minimum(pos, C - 1)
    hit = (sorted_nodes[pos] == queries) & (queries >= 0)
    local = jnp.where(hit, order[pos], -1)
    return local.astype(jnp.int32)


def saint_subgraph(topo, nodes, num_nodes, deg_cap: int):
    """Node-induced subgraph over a device CSR topology.

    For every valid node u in ``nodes``, scans up to ``deg_cap`` of u's
    neighbors (CSR order; edges beyond the cap are dropped — pick
    ``deg_cap >= max_degree`` for exactness) and keeps edges whose endpoint
    is also in ``nodes``. Jit-composable; all shapes static.

    Args:
      topo: DeviceTopology.
      nodes: (C,) node ids, -1 padded; valid entries occupy a prefix.
        Duplicate ids keep their first occurrence as the canonical local id.
      num_nodes: scalar count of valid entries.
      deg_cap: static per-node neighbor-scan window.

    Returns: SaintSubgraph.
    """
    C = nodes.shape[0]
    valid = (jnp.arange(C) < num_nodes) & (nodes >= 0)
    s = jnp.where(valid, nodes, 0)
    base = topo.indptr[s]
    deg = (topo.indptr[s + 1] - base).astype(jnp.int32)
    deg = jnp.where(valid, deg, 0)

    j = jnp.arange(deg_cap, dtype=jnp.int32)[None, :]
    in_window = j < jnp.minimum(deg, deg_cap)[:, None]
    epos = base[:, None] + jnp.where(in_window, j, 0).astype(base.dtype)
    nbr = staged_gather(topo.indices, epos, topo.host_indices)
    nbr = jnp.where(in_window, nbr, -1)

    dst_local = _membership(nodes, nbr)  # (C, D)
    src_local = jnp.broadcast_to(
        jnp.arange(C, dtype=jnp.int32)[:, None], (C, deg_cap)
    )
    keep = (dst_local >= 0) & in_window
    src_flat = jnp.where(keep, src_local, -1).reshape(-1)
    dst_flat = jnp.where(keep, dst_local, -1).reshape(-1)
    edge_index = jnp.stack([src_flat, dst_flat])
    return SaintSubgraph(
        node_id=nodes,
        edge_index=edge_index,
        num_nodes=jnp.sum(valid.astype(jnp.int32)),
        num_edges=jnp.sum(keep.astype(jnp.int32)),
    )


def _uniform_edge_positions(key, budget: int, edge_count: int, dtype):
    """(budget,) uniform draws in [0, edge_count). ``edge_count`` is static
    (an array shape), so the wide-graph branch resolves at trace time."""
    if edge_count < 2**31:
        return jax.random.randint(
            key, (budget,), 0, edge_count, dtype=jnp.int32
        ).astype(dtype)
    # >2^31 edges: compose two 16-bit draws into a 32-bit mantissa-safe
    # uniform and scale (float32 alone loses low bits past 2^24)
    hi = jax.random.randint(key, (budget,), 0, 1 << 16, dtype=jnp.int32)
    lo = jax.random.randint(
        jax.random.fold_in(key, 1), (budget,), 0, 1 << 16, dtype=jnp.int32
    )
    u = (hi.astype(jnp.float64) * (1 << 16) + lo) / float(1 << 32)
    return jnp.minimum((u * edge_count).astype(dtype), edge_count - 1)


def _degree_proportional_nodes(topo, key, budget: int):
    """Device-side degree-proportional node draw + first-occurrence dedup.

    P(node) ∝ degree is exactly a uniform edge draw mapped to its source row:
    ``indptr`` IS the degree CDF, so one ``searchsorted`` replaces the host
    ``rng.choice(p=deg/deg.sum())`` (VERDICT r2 item 5 — no host RNG, no
    per-batch ``np.unique``). A zero-edge graph degrades to uniform node
    draws (the degree law is undefined), matching the host path's p=None
    fallback; E is a static shape, so the branch resolves at trace time.
    """
    E = topo.edge_count
    if E == 0:
        n = topo.indptr.shape[0] - 1
        src = jax.random.randint(key, (budget,), 0, max(n, 1), dtype=jnp.int32)
    else:
        r = _uniform_edge_positions(key, budget, E, topo.indptr.dtype)
        src = (
            jnp.searchsorted(topo.indptr, r, side="right").astype(jnp.int32) - 1
        )
    nodes, num, _ = masked_unique(src, jnp.ones(budget, bool), budget)
    return nodes, jnp.minimum(num, budget)


def _uniform_edge_endpoints(topo, key, budget: int):
    """Device-side uniform edge draw -> dedup'd endpoint set (cap 2*budget)."""
    E = topo.edge_count
    eids = _uniform_edge_positions(key, budget, E, topo.indptr.dtype)
    dst = staged_gather(topo.indices, eids, topo.host_indices).astype(jnp.int32)
    src = (
        jnp.searchsorted(topo.indptr, eids, side="right").astype(jnp.int32) - 1
    )
    both = jnp.concatenate([src, dst])
    nodes, num, _ = masked_unique(both, both >= 0, 2 * budget)
    return nodes, jnp.minimum(num, 2 * budget)


@functools.partial(jax.jit, static_argnames=("budget", "deg_cap"))
def _saint_node_sample(topo, key, budget: int, deg_cap: int):
    nodes, num = _degree_proportional_nodes(topo, key, budget)
    return saint_subgraph(topo, nodes, num, deg_cap)


@functools.partial(jax.jit, static_argnames=("budget", "deg_cap"))
def _saint_edge_sample(topo, key, budget: int, deg_cap: int):
    nodes, num = _uniform_edge_endpoints(topo, key, budget)
    return saint_subgraph(topo, nodes, num, deg_cap)


@functools.partial(
    jax.jit, static_argnames=("roots", "walk_length", "deg_cap")
)
def _saint_rw_sample(topo, key, roots: int, walk_length: int, deg_cap: int):
    kr, kw = jax.random.split(key)
    n_nodes = topo.indptr.shape[0] - 1
    starts = jax.random.randint(kr, (roots,), 0, n_nodes, dtype=jnp.int32)
    visited = random_walk(topo, starts, walk_length, kw).reshape(-1)
    budget = roots * (walk_length + 1)
    nodes, num, _ = masked_unique(visited, visited >= 0, budget)
    return saint_subgraph(topo, nodes, jnp.minimum(num, budget), deg_cap)


class _SaintSamplerBase:
    """Shared machinery: node-budget padding, fully-fused jitted sampling.

    Each ``sample()`` is ONE compiled program — random draw, dedup
    (ops/reindex.masked_unique), and subgraph induction all on device; the
    host only advances the PRNG key (VERDICT r2 item 5: the original
    round-1 design re-entered the host for ``np.unique`` + RNG every batch,
    fine as preprocessing but a per-batch sync in a training loop).

    ``deg_cap`` defaults to the 99th-percentile degree (not max_degree: the
    subgraph extraction materializes (budget, deg_cap) blocks, and a
    power-law hub would blow that up by orders of magnitude for edges that
    overwhelmingly fail the membership test anyway). Pass
    ``deg_cap=csr_topo.max_degree`` for exact induced subgraphs.
    """

    def __init__(self, csr_topo: CSRTopo, budget: int, deg_cap: int | None = None,
                 seed: int = 0):
        self.csr_topo = csr_topo
        self.budget = int(budget)
        if deg_cap is None:
            deg = csr_topo.degree
            p99 = int(np.percentile(deg, 99)) if deg.size else 1
            deg_cap = min(max(p99, 1), max(csr_topo.max_degree, 1))
        self.deg_cap = int(deg_cap)
        self.topo = csr_topo.to_device()
        self._key = jax.random.PRNGKey(seed)
        self._call = 0

    def _next_key(self):
        self._call += 1
        return jax.random.fold_in(self._key, self._call)

    def sample(self) -> SaintSubgraph:
        raise NotImplementedError


class SAINTNodeSampler(_SaintSamplerBase):
    """GraphSAINT-Node: sample ``budget`` nodes with probability proportional
    to degree (the paper's importance distribution), induce the subgraph."""

    def sample(self) -> SaintSubgraph:
        return _saint_node_sample(
            self.topo, self._next_key(), self.budget, self.deg_cap
        )


class SAINTEdgeSampler(_SaintSamplerBase):
    """GraphSAINT-Edge: sample ``budget`` edges uniformly, take both
    endpoints as the node set, induce the subgraph. Node budget = 2*edges."""

    def __init__(self, csr_topo, budget, deg_cap=None, seed=0):
        if csr_topo.edge_count == 0:
            raise ValueError("SAINTEdgeSampler needs a graph with edges")
        super().__init__(csr_topo, budget, deg_cap, seed)

    def sample(self) -> SaintSubgraph:
        return _saint_edge_sample(
            self.topo, self._next_key(), self.budget, self.deg_cap
        )


class SAINTRandomWalkSampler(_SaintSamplerBase):
    """GraphSAINT-RW: ``roots`` uniform random roots, each walking
    ``walk_length`` uniform steps; the visited set induces the subgraph.

    Roots, walk, dedup, and induction are a single compiled program."""

    def __init__(self, csr_topo, roots: int, walk_length: int,
                 deg_cap=None, seed=0):
        budget = roots * (walk_length + 1)
        super().__init__(csr_topo, budget, deg_cap, seed)
        self.roots = int(roots)
        self.walk_length = int(walk_length)

    def sample(self) -> SaintSubgraph:
        return _saint_rw_sample(
            self.topo, self._next_key(), self.roots, self.walk_length,
            self.deg_cap,
        )


def random_walk(topo, starts, walk_length: int, key):
    """Uniform random walks: (R,) starts -> (R, walk_length+1) visited ids.

    Dead-end nodes (deg 0) stay in place (emit their own id), so every lane
    stays valid — a padded-shape-friendly convention.
    """
    R = starts.shape[0]
    cur = starts
    out = [starts]
    n = jnp.int32(R)
    for _ in range(walk_length):
        key, sub = jax.random.split(key)
        nbr, _ = sample_layer(topo, cur, n, 1, sub)
        step = nbr[:, 0]
        cur = jnp.where(step >= 0, step, cur)
        out.append(cur)
    return jnp.stack(out, axis=1)


def estimate_saint_norm(sampler, num_iters: int = 50):
    """Estimate GraphSAINT's loss normalization by pre-sampling.

    Runs ``num_iters`` subgraph draws and counts per-node appearances;
    returns (node_norm (N,), counts (N,)) where node_norm[v] ~ 1 / P(v in
    subgraph) scaled to mean 1 over appearing nodes — multiply each node's
    loss term by node_norm to unbias the estimator (GraphSAINT eq. 2's
    lambda). Nodes never sampled get norm 0.
    """
    N = sampler.csr_topo.node_count
    counts = np.zeros(N, dtype=np.int64)
    for _ in range(num_iters):
        sub = sampler.sample()
        ids = np.asarray(sub.node_id)
        counts[ids[ids >= 0]] += 1
    freq = counts / num_iters
    norm = np.zeros(N, dtype=np.float32)
    seen = freq > 0
    norm[seen] = 1.0 / freq[seen]
    if seen.any():
        norm /= norm[seen].mean()
    return norm, counts
