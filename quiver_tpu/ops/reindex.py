"""Order-preserving deduplication with static shapes.

TPU-native replacement for the reference's GPU hash-table reindex
(torch-quiver reindex.cu.hpp:17-225 + ``FillWithDuplicates``,
quiver_sample.cu:18-63): instead of atomicCAS open addressing, four sorts
that carry their payloads and a packed running max give every id the slot of
its first occurrence, producing the same order-preserving compaction with
fully static shapes, no atomics, no scatter and no gather. One algorithm on
every backend (:func:`masked_unique`). Seeds are placed first in the
input, so — exactly as in the reference's ``reindex_with_seeds`` — the first ``num_seeds`` unique
ids are the seeds themselves, preserving the PyG ``n_id[:batch_size]``
contract.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..utils.trace import trace_scope

__all__ = [
    "masked_unique",
    "reindex_layer",
    "inverse_permutation",
    "complete_permutation",
]


def inverse_permutation(p):
    """q with q[p[i]] == i — the reference's ``inverse_permutation``
    (reindex.cu.hpp:304-315), as one XLA scatter instead of a thrust
    for_each."""
    n = p.shape[0]
    return jnp.zeros(n, p.dtype).at[p].set(jnp.arange(n, dtype=p.dtype))


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


def _spread_bits(T: int, size: int) -> tuple[int, int]:
    """``(bits, passes)`` of :func:`masked_unique`'s run broadcast. A
    pass packs a lane index (< T) above ``bits`` bits of a local id (<=
    ``size`` and < T) into a non-negative int32: ``bits`` is what 31
    leaves, ``passes`` the chunks a local id needs — static in the shapes
    (1 or 2 at every hop of the benchmark's cells)."""
    bits = 31 - (T - 1).bit_length()
    if bits < 1:
        raise ValueError(f"masked_unique: {T} lanes leave no bit to carry")
    return bits, max(1, -(-min(size, T - 1).bit_length() // bits))


def masked_unique(ids, valid, size: int, num_forced: int = 0):
    """First-occurrence-order unique of ``ids[valid]``, padded to ``size``.

    Four sorts, no scatter and no T-lane gather: what would travel through
    ``x[perm]`` is a sort's payload, and a run's first lane reaches the
    whole run as a running max. On a v5e at ogbn-products' deepest hop
    (852,480 lanes) the call costs 4.8 ms; the gather-and-scatter
    strategies it replaced cost 21.3 and 24.6 (one probe, PERF.md, PR 29).

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

    Returns:
      uniq: (size,) unique ids in first-occurrence order, -1 padded.
      num_unique: scalar — total uniques found (may exceed ``size``; the
        excess is reported, not stored).
      local: (T,) compact id of each element among the uniques, or -1 for
        invalid / overflowed elements.
    """
    T = ids.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    # the three phases are the scopes a device trace splits the reindex by
    with trace_scope("dedup"):
        # value sort that carries the positions (``pv``: the sort order
        # itself). The position is the second key, so positions within a
        # run ascend: a run's first sorted element IS the value's first
        # occurrence. The sentinel run (invalid lanes) is excluded.
        sent = jnp.iinfo(ids.dtype).max
        sv, pv = lax.sort(
            (jnp.where(valid, ids, sent), pos), num_keys=2, is_stable=False
        )
        live = sv != sent
        first = jnp.concatenate([jnp.ones(1, bool), sv[1:] != sv[:-1]]) & live
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


def reindex_layer(seeds, num_seeds, neighbors, frontier_cap: int):
    """Per-layer reindex: frontier = unique(seeds ∪ neighbors), seeds first.

    Mirrors the reference's ``reindex_single`` contract
    (quiver_sample.cu:294-346) in padded form.

    Args:
      seeds: (S,) seed node ids, -1 padded; valid entries occupy a prefix.
      num_seeds: scalar count of valid seeds.
      neighbors: (S, K) sampled neighbor ids, -1 where invalid.
      frontier_cap: static capacity of the output frontier.

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
        ids, valid, frontier_cap, num_forced=S
    )
    with trace_scope("relabel"):
        col_local = local[S:].reshape(S, K)
        num_frontier = jnp.minimum(num_unique, frontier_cap)
        overflow = jnp.maximum(num_unique - frontier_cap, 0)
    return uniq, num_frontier, col_local, overflow
