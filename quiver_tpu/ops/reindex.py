"""Order-preserving deduplication with static shapes.

TPU-native replacement for the reference's GPU hash-table reindex
(torch-quiver reindex.cu.hpp:17-225 + ``FillWithDuplicates``,
quiver_sample.cu:18-63): instead of atomicCAS open addressing, a stable
sort + segment-representative scan assigns every id the position of its first
occurrence, producing the same order-preserving compaction with fully static
shapes and no atomics. Seeds are placed first in the input, so — exactly as
in the reference's ``reindex_with_seeds`` — the first ``num_seeds`` unique
ids are the seeds themselves, preserving the PyG ``n_id[:batch_size]``
contract.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..utils.trace import info_once, trace_scope

__all__ = [
    "masked_unique",
    "reindex_layer",
    "inverse_permutation",
    "inverse_permutation_gather",
    "complete_permutation",
    "resolve_dedup",
]

DEDUP_STRATEGIES = ("sort", "map", "scan")

# QUIVER_DEDUP resolution caches — ONE env read per process each.
# resolve_dedup is reachable from traced code (dist_multilayer_sample /
# multilayer_sample call it inside shard_map'd bodies), where a per-call
# env read freezes at first trace while looking like a live switch (the
# QUIVER_COUNTS bug class, graftlint env-at-trace). Set QUIVER_DEDUP
# before the first sampler construction or trace; tests reset these.
_forced_dedup: str | None = None
_auto_dedup: str | None = None


def _forced_dedup_env() -> str:
    """The ``QUIVER_DEDUP`` force, read once per process ("" = no force)."""
    global _forced_dedup
    if _forced_dedup is None:
        import os

        _forced_dedup = os.environ.get("QUIVER_DEDUP", "").strip()
    return _forced_dedup


def resolve_dedup(dedup: str) -> str:
    """Resolve a dedup strategy name, mapping ``"auto"`` to the platform
    default.

    The three strategies are bit-identical (tests/test_reindex.py); only
    their cost model differs per backend:

    * **cpu** -> ``"map"`` — on XLA's CPU backend the dense scatter-min
      map ran 4-5x the sort path at both smoke and full products scale.
    * **tpu** -> ``"scan"`` — the zero-scatter strategy. What a v5e has
      timed so far (PERF.md, PR 26) is its compaction: at ogbn-products'
      deepest hop (852,480 lanes) the payload-carrying sort it uses costs
      0.9 ms and the scatter of the other two strategies 3.9 ms, so a
      scatter with unique indices is NOT serialized there, only slower.
      The three whole strategies have not been run against each other on
      a cell: provisional until ROADMAP S9 does.

    ``QUIVER_DEDUP=sort|map|scan`` overrides the ``"auto"`` resolution
    ONLY: call sites passing an explicit strategy
    keep it — benchmark variant labels must match what actually ran — and
    the first such ignored force is logged so the mismatch is visible.
    Unknown names raise — a typo must not silently fall back to a
    strategy (the callers' dispatch treats anything non-map/scan as sort).
    Both the force and the "auto" resolution are pinned at FIRST use for
    the process (env-before-first-use contract; this function runs inside
    traced sampler bodies, where the env would freeze at first trace
    regardless — the cache makes the once-semantics explicit).
    """
    if dedup in DEDUP_STRATEGIES:
        forced = _forced_dedup_env()
        if forced and forced != dedup:
            info_once(
                f"dedup-env-ignored-{dedup}",
                "QUIVER_DEDUP=%s ignored for explicit dedup=%r (the env "
                "override applies only to dedup='auto')",
                forced, dedup,
            )
        return dedup
    if dedup != "auto":
        raise ValueError(
            f"dedup must be 'auto', 'sort', 'map', or 'scan', got {dedup!r}"
        )
    global _auto_dedup
    if _auto_dedup is None:
        from ..core.config import resolve_platform_strategy

        _auto_dedup = resolve_platform_strategy(
            "QUIVER_DEDUP", DEDUP_STRATEGIES, tpu_default="scan",
            other_default="map",
        )
    return _auto_dedup


def inverse_permutation(p):
    """q with q[p[i]] == i — the reference's ``inverse_permutation``
    (reindex.cu.hpp:304-315), as one XLA scatter instead of a thrust
    for_each."""
    n = p.shape[0]
    return jnp.zeros(n, p.dtype).at[p].set(jnp.arange(n, dtype=p.dtype))


def inverse_permutation_gather(p):
    """The zero-scatter sibling of :func:`inverse_permutation`: argsort of
    a permutation IS its inverse. Costs a sort instead of a scatter
    (shared by the dedup scan strategy and the routed feature gather)."""
    return jnp.argsort(p).astype(jnp.int32)


def complete_permutation(p, n: int):
    """Extend an injective partial map ``p`` (m distinct values < n) to a
    full permutation of {0..n-1}: p's entries first (in order), then the
    missing values ascending — the reference's ``complete_permutation``
    (reindex.cu.hpp:277-300, pair-sort construction). Static-shape rebuild:
    rank present values by position in p, absent values by value after all
    present ones, then argsort the rank vector.
    """
    m = p.shape[0]
    if m > n:
        raise ValueError(f"partial permutation longer ({m}) than n ({n})")
    # rank[v] = position in p when present, m + v when absent — absent
    # values compare after every present one yet stay value-ordered.
    # (m + v fits: m <= n and v < n, so rank < 2n < int32 max for any
    # realistic graph.)
    vals = jnp.arange(n, dtype=p.dtype)
    rank = (vals + m).at[p].set(jnp.arange(m, dtype=p.dtype))
    return jnp.argsort(rank).astype(p.dtype)


def masked_unique(ids, valid, size: int, num_forced: int = 0,
                  node_bound: int | None = None,
                  scatter_free: bool = False):
    """First-occurrence-order unique of ``ids[valid]``, padded to ``size``.

    Args:
      ids: (T,) integer ids (values < iinfo.max; padding may be anything).
      valid: (T,) bool mask.
      size: static output capacity for the unique list.
      num_forced: the first ``num_forced`` valid lanes are *unconditionally*
        kept as distinct outputs even if their values repeat. Used for seed
        lanes: PyG's contract is ``n_id[:batch_size] == seeds`` verbatim,
        duplicates included, so a batch like [7, 7, 3] must occupy three
        output slots. Later duplicates of a forced value still map to its
        first occurrence.
      node_bound: static exclusive upper bound on valid id values. When
        given, first occurrences are found with a scatter-min into a
        (node_bound,)-sized position map instead of a stable sort —
        O(node_bound + T) memset/scatter/gather vs O(T log^2 T) sort
        passes. This is the direct analogue of the reference's GPU hash
        table (reindex.cu.hpp:120-139 atomicMin keeps the first
        occurrence); the dense map plays the table, scatter-min plays
        atomicMin. Same contract either way; pick by measurement.
        WARNING — silent corruption if violated: a valid id >= node_bound
        is dropped by the scatter (mode="drop") and its gather clamps to
        the last map slot, so the output is WRONG with no error raised;
        the sort path tolerates arbitrary id values. Callers must derive
        node_bound from the id space that produced ``ids`` (the samplers
        pass topo.node_count; neighbor ids are CSR entries < node_count by
        construction).
      scatter_free: use the ZERO-SCATTER strategy (``dedup="scan"``): two
        sorts + a cumulative max + gathers find the first occurrences, and
        ONE more sort packs them: keyed by position on the representatives
        and by ``T`` elsewhere, it carries the ids along, so the frontier
        comes out in first-occurrence order with no ``.at[].set/min``
        anywhere — the other two strategies compact with a scatter. On a
        v5e at ogbn-products' deepest hop (T = 852,480, ``size`` 672,384)
        that sort costs 0.9 ms, the scatter 3.9 ms, and the binary search
        per output slot it replaced (twenty rounds of dependent gathers)
        88 ms (PERF.md, PR 26). Same contract; pick by measurement
        (ignored when ``node_bound`` is given).

    Returns:
      uniq: (size,) unique ids in first-occurrence order, -1 padded.
      num_unique: scalar — total uniques found (may exceed ``size``; the
        excess is reported, not stored).
      local: (T,) compact id of each element among the uniques, or -1 for
        invalid / overflowed elements.
    """
    T = ids.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)

    # the three phases are scopes of every strategy, so that a device
    # trace splits the reindex the same way whichever one ran
    with trace_scope("dedup"):
        rep_pos = _first_occurrence(ids, valid, pos, node_bound, scatter_free)

    with trace_scope("compact"):
        forced = (pos < num_forced) & valid
        is_rep = (valid & (rep_pos == pos)) | forced
        rank = jnp.cumsum(is_rep.astype(jnp.int32)) - 1  # first-occurrence rank
        num_unique = jnp.sum(is_rep.astype(jnp.int32))

        if scatter_free and node_bound is None:
            # compaction as ONE sort that carries the ids: a rep's key is
            # its position (distinct, ascending in first-occurrence order),
            # every other lane's is T, so the reps come out packed in front
            # and the rest is masked. No gather, no scatter, no loop; the
            # (size,) write is a contiguous slice update.
            m = min(size, T)
            _, packed = lax.sort(
                (jnp.where(is_rep, pos, T), ids), num_keys=1, is_stable=False
            )
            packed = jnp.where(jnp.arange(m) < num_unique, packed[:m], -1)
            uniq = jnp.full(size, -1, ids.dtype).at[:m].set(packed)
        else:
            uniq = (
                jnp.full(size, -1, ids.dtype)
                .at[jnp.where(is_rep & (rank < size), rank, size)]
                .set(ids, mode="drop")
            )
    with trace_scope("relabel"):
        local = rank[rep_pos]
        local = jnp.where(valid & (local < size), local, -1)
    return uniq, num_unique, local


def _first_occurrence(ids, valid, pos, node_bound, scatter_free):
    """(T,) position of the first occurrence of each lane's id — the
    ``dedup`` phase of :func:`masked_unique`, one branch per strategy."""
    T = ids.shape[0]
    if node_bound is not None:
        safe = jnp.where(valid, ids, 0)
        first_pos = (
            jnp.full((node_bound,), T, jnp.int32)
            .at[safe]
            .min(jnp.where(valid, pos, T), mode="drop")
        )
        return first_pos[safe]
    # shared sorted view: stable value sort, run starts (sentinel run
    # excluded); positions within a run ascend, so a run's first sorted
    # element IS the value's first occurrence
    sent = jnp.iinfo(ids.dtype).max
    vals = jnp.where(valid, ids, sent)
    order = jnp.argsort(vals, stable=True)
    sv = vals[order]
    pv = pos[order]
    first = jnp.concatenate(
        [jnp.ones(1, bool), sv[1:] != sv[:-1]]
    ) & (sv != sent)

    if scatter_free:
        # sorted-view index of the current run's first element: a running
        # max over first-markers (the scatter-free run-representative)
        idx_first = lax.cummax(
            jnp.where(first, jnp.arange(T, dtype=jnp.int32), -1)
        )
        rep_pos_sorted = jnp.where(
            idx_first >= 0, pv[jnp.clip(idx_first, 0)], T
        )
        # back to original positions via the inverse permutation, built by
        # sorting the permutation instead of scattering into it
        return rep_pos_sorted[inverse_permutation_gather(order)]
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    # representative position scattered per run
    by_run = (
        jnp.zeros(T, jnp.int32)
        .at[jnp.where(first, run_id, T)]
        .set(pv, mode="drop")
    )
    rep_pos_sorted = by_run[jnp.clip(run_id, 0)]
    # back to original positions
    return jnp.zeros(T, jnp.int32).at[order].set(rep_pos_sorted)


def reindex_layer(seeds, num_seeds, neighbors, frontier_cap: int,
                  node_bound: int | None = None,
                  scatter_free: bool = False):
    """Per-layer reindex: frontier = unique(seeds ∪ neighbors), seeds first.

    Mirrors the reference's ``reindex_single`` contract
    (quiver_sample.cu:294-346) in padded form.

    Args:
      seeds: (S,) seed node ids, -1 padded; valid entries occupy a prefix.
      num_seeds: scalar count of valid seeds.
      neighbors: (S, K) sampled neighbor ids, -1 where invalid.
      frontier_cap: static capacity of the output frontier.
      node_bound: optional static id upper bound enabling the sort-free
        scatter-min dedup (see masked_unique).
      scatter_free: the zero-scatter sort/scan/gather strategy
        (see masked_unique; ignored when node_bound is given).

    Returns:
      frontier: (frontier_cap,) unique node ids, seeds first, -1 padded.
      num_frontier: scalar valid count (clipped to capacity).
      col_local: (S, K) frontier-local id per neighbor, -1 where invalid.
        (Row-local ids need no lookup: seed i's local id is i.)
      overflow: scalar count of uniques dropped for exceeding frontier_cap.
    """
    S, K = neighbors.shape
    with trace_scope("dedup"):
        ids = jnp.concatenate([seeds, neighbors.reshape(-1)])
        seed_valid = (jnp.arange(S) < num_seeds) & (seeds >= 0)
        nbr_valid = neighbors.reshape(-1) >= 0
        valid = jnp.concatenate([seed_valid, nbr_valid])

    uniq, num_unique, local = masked_unique(
        ids, valid, frontier_cap, num_forced=S, node_bound=node_bound,
        scatter_free=scatter_free,
    )
    with trace_scope("relabel"):
        col_local = local[S:].reshape(S, K)
        num_frontier = jnp.minimum(num_unique, frontier_cap)
        overflow = jnp.maximum(num_unique - frontier_cap, 0)
    return uniq, num_frontier, col_local, overflow
