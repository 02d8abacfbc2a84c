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
    * **tpu** -> ``"scan"`` — the zero-scatter, zero-gather strategy:
      every value travels as the payload of a sort. On a v5e at
      ogbn-products' deepest hop (852,480 lanes) a whole ``masked_unique``
      costs 4.8 ms under ``"scan"``, 24.6 under ``"sort"`` and 21.3 under
      ``"map"`` (one probe, PERF.md, PR 29): there a T-lane 4-byte gather
      costs 6.4 ms, a scatter with unique indices 3.9 (PR 26) and a
      payload-carrying sort 1.0 - 1.5. Only ``"scan"`` has run in a cell;
      ROADMAP S9 / D1 settle what becomes of the other two.

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
    a permutation IS its inverse. Costs a sort instead of a scatter (the
    routed feature gather un-buckets through it)."""
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
      scatter_free: use the ZERO-SCATTER strategy (``dedup="scan"``), which
        is zero-gather too: every value travels as the payload of a sort
        (:func:`_unique_by_sorts`), where the other two strategies gather
        through the sort order and compact with a scatter. Same contract;
        pick by measurement (:func:`resolve_dedup` has a v5e's; ignored
        when ``node_bound`` is given).

    Returns:
      uniq: (size,) unique ids in first-occurrence order, -1 padded.
      num_unique: scalar — total uniques found (may exceed ``size``; the
        excess is reported, not stored).
      local: (T,) compact id of each element among the uniques, or -1 for
        invalid / overflowed elements.
    """
    T = ids.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    if scatter_free and node_bound is None:
        return _unique_by_sorts(ids, valid, pos, size, num_forced)

    # the three phases are scopes of every strategy, so that a device
    # trace splits the reindex the same way whichever one ran
    with trace_scope("dedup"):
        rep_pos = _first_occurrence(ids, valid, pos, node_bound)

    with trace_scope("compact"):
        forced = (pos < num_forced) & valid
        is_rep = (valid & (rep_pos == pos)) | forced
        rank = jnp.cumsum(is_rep.astype(jnp.int32)) - 1  # first-occurrence rank
        num_unique = jnp.sum(is_rep.astype(jnp.int32))
        uniq = (
            jnp.full(size, -1, ids.dtype)
            .at[jnp.where(is_rep & (rank < size), rank, size)]
            .set(ids, mode="drop")
        )
    with trace_scope("relabel"):
        local = rank[rep_pos]
        local = jnp.where(valid & (local < size), local, -1)
    return uniq, num_unique, local


def _sorted_view(ids, valid, pos):
    """Value sort that carries the positions (``pv``: the sort order
    itself) and its run starts, sentinel run excluded. The position is the
    second key, so positions within a run ascend: a run's first sorted
    element IS the value's first occurrence."""
    sent = jnp.iinfo(ids.dtype).max
    sv, pv = lax.sort(
        (jnp.where(valid, ids, sent), pos), num_keys=2, is_stable=False
    )
    live = sv != sent
    first = jnp.concatenate([jnp.ones(1, bool), sv[1:] != sv[:-1]]) & live
    return sv, pv, first, live


def _first_occurrence(ids, valid, pos, node_bound):
    """(T,) position of the first occurrence of each lane's id — the
    ``dedup`` phase of the ``"map"`` (``node_bound``) and ``"sort"``
    strategies of :func:`masked_unique`."""
    T = ids.shape[0]
    if node_bound is not None:
        safe = jnp.where(valid, ids, 0)
        first_pos = (
            jnp.full((node_bound,), T, jnp.int32)
            .at[safe]
            .min(jnp.where(valid, pos, T), mode="drop")
        )
        return first_pos[safe]
    _, pv, first, _ = _sorted_view(ids, valid, pos)
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    # representative position scattered per run
    by_run = (
        jnp.zeros(T, jnp.int32)
        .at[jnp.where(first, run_id, T)]
        .set(pv, mode="drop")
    )
    rep_pos_sorted = by_run[jnp.clip(run_id, 0)]
    # back to original positions
    return jnp.zeros(T, jnp.int32).at[pv].set(rep_pos_sorted)


def _spread_bits(T: int, size: int) -> tuple[int, int]:
    """``(bits, passes)`` of :func:`_unique_by_sorts`'s run broadcast. A
    pass packs a lane index (< T) above ``bits`` bits of a local id (<=
    ``size`` and < T) into a non-negative int32: ``bits`` is what 31
    leaves, ``passes`` the chunks a local id needs — static in the shapes
    (1 or 2 at every hop of the benchmark's cells)."""
    bits = 31 - (T - 1).bit_length()
    if bits < 1:
        raise ValueError(f"masked_unique: {T} lanes leave no bit to carry")
    return bits, max(1, -(-min(size, T - 1).bit_length() // bits))


def _unique_by_sorts(ids, valid, pos, size, num_forced):
    """:func:`masked_unique` for ``dedup="scan"``: four sorts, no scatter
    and no T-lane gather — what would travel through ``x[perm]`` is a
    sort's payload, and a run's first lane reaches the whole run as a
    running max."""
    T = ids.shape[0]
    with trace_scope("dedup"):
        sv, pv, first, live = _sorted_view(ids, valid, pos)
        is_rep = (first | (pv < num_forced)) & live
        num_unique = jnp.sum(is_rep.astype(jnp.int32))
    with trace_scope("compact"):
        # ONE sort packs the reps in front in first-occurrence order (a
        # rep's key is its position, every other lane's lies above them
        # all; all distinct) and carries the ids and each one's sorted-view
        # lane: a rep's output index IS its local id. The (size,) write is
        # a contiguous slice update.
        m = min(size, T)
        _, packed, lane = lax.sort(
            (jnp.where(is_rep, pv, T + pv), sv, pos), num_keys=1,
            is_stable=False,
        )
        packed = jnp.where(pos[:m] < num_unique, packed[:m], -1)
        uniq = jnp.full(size, -1, ids.dtype).at[:m].set(packed)
    with trace_scope("relabel"):
        # local ids back at their sorted-view lanes (``lane`` is a
        # permutation); clipped, overflow stays overflow in fewer bits
        _, local = lax.sort((lane, pos), num_keys=1, is_stable=False)
        local = jnp.minimum(local, size)
        # spread each run's first over the run: running max of (lane index
        # | chunk of the id), the lane index rising along the view
        bits, passes = _spread_bits(T, size)
        mask = (1 << bits) - 1
        spread = jnp.zeros(T, jnp.int32)
        for shift in range(0, bits * passes, bits):
            word = (pos << bits) | ((local >> shift) & mask)
            word = lax.cummax(jnp.where(first, word, 0))
            spread = spread | ((word & mask) << shift)
        # back to lane order: ``pv`` is a permutation too
        _, local = lax.sort((pv, spread), num_keys=1, is_stable=False)
        local = jnp.where(valid & (local < size), local, -1)
    return uniq, num_unique, local


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
      scatter_free: the zero-scatter, zero-gather payload-sort strategy
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
