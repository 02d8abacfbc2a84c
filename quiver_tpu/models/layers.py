"""Message-passing primitives over padded Adj blocks.

The reference delegates all modeling to PyG (SAGEConv etc. in example
scripts, examples/pyg/reddit_quiver.py:42-65); quiver-tpu ships its own
TPU-native GNN layers because PyG/torch are out of the build. Edges arrive
as padded ``edge_index`` (2, E) with -1 sentinels (source = frontier-local
id, target = seed-local id).

Two aggregation paths, identical results:

* **dense** (``fanout`` set — every sampler-built Adj): the sampler's edge
  layout is regular (lane ``s*fanout + k`` targets seed ``s``), so a
  target's sum is a sum of its ``fanout`` lanes — zero scatters. A scatter
  is not serialized on a v5e but costs 4.6 ns a lane, 4.4x a payload sort
  of the same lanes (one unique-index scatter of 852,480 lanes: PERF.md,
  PR 26); the segment path's own cost has not been measured on the chip.
  A family that sums its sources' rows as they are (SAGE, GCN, GIN, RGCN)
  gathers and sums in ONE function, :func:`fanout_gather_sum`, which takes
  the rows **fanout-major**: the indices transposed to ``(fanout,
  num_dst)``, the gathered rows ``(fanout, num_dst, F)``, the sum over
  axis 0. With ``num_dst`` a multiple of 8 that view is the gather's own
  ``(E, F)`` output tile for tile and the sum adds ``fanout`` aligned
  slabs. Gathered in lane order and viewed ``(num_dst, fanout, F)``, a v5e
  wants the 8 x 128 tiles over ``(fanout, F)``: the view was a copy of
  every gathered row with the fanout padded to 8 or 16 sublanes, and the
  sum read the padded copy (PERF.md, PR 36).
* **segment** (``fanout=None``): ``jax.ops.segment_sum`` with an overflow
  bucket for invalid lanes — kept for hand-built/irregular Adjs and as the
  differential-test oracle.

A dense path that needs its sources' rows by lane and differentiates through
them (GAT's attention) takes them in lane order with
:func:`gather_lane_rows` and sums the messages it forms from them with
:func:`fanout_sum_aggregate`; the gather's transpose is a row gather and a
scatter-add of the repeated lanes alone: a scatter-add of 2 KB rows walks
every lane at 73 ns on a v5e, dropped or not, a row gather at 10 (PERF.md,
PR 35). :func:`gather_src` is the segment path's plain gather in lane order.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.gather import tile_pad
from ..utils.trace import info_once

__all__ = [
    "segment_mean_aggregate",
    "segment_softmax",
    "fanout_softmax",
    "fanout_sum_aggregate",
    "fanout_gather_sum",
    "fanout_relation_sums",
    "fanout_relation_softmax",
    "masked_batch_norm",
    "gather_mean_aggregate",
    "gather_src",
    "gather_lane_rows",
    "zero_scatter_counts",
    "occurrence_counts",
    "resolve_counts_strategy",
]


_counts_strategy: str | None = None


def resolve_counts_strategy() -> str:
    """The ``QUIVER_COUNTS`` histogram strategy, resolved ONCE per process.

    Resolution (env override, else platform default — see
    ``core.config.resolve_platform_strategy``) used to happen at trace time
    inside jitted model code, which implied an env var read on every
    retrace and made it look like ``QUIVER_COUNTS`` could flip a live
    model. It cannot: jit caches keep whatever strategy they were traced
    with. The first call — op construction / first model trace — pins the
    strategy for the process; set ``QUIVER_COUNTS`` BEFORE constructing or
    tracing any model that counts (chip-window forcing must precede the
    first trace)."""
    global _counts_strategy
    if _counts_strategy is None:
        from ..core.config import resolve_platform_strategy

        _counts_strategy = resolve_platform_strategy(
            "QUIVER_COUNTS", ("scan", "scatter"), tpu_default="scan",
            other_default="scatter",
        )
    return _counts_strategy


_check_cache: bool | None = None


def _check_enabled() -> bool:
    """QUIVER_CHECK=1 turns on the debug-mode layout assertions.

    Resolved ONCE per process (graftlint env-at-trace): the check gate is
    evaluated inside traced aggregation code, where a per-call env read
    would freeze at first trace anyway while looking like a live switch.
    Set QUIVER_CHECK before the first model trace; tests reset
    ``_check_cache`` to re-resolve."""
    global _check_cache
    if _check_cache is None:
        _check_cache = os.environ.get("QUIVER_CHECK", "0") not in (
            "", "0", "false", "False"
        )
    return _check_cache


def _raise_layout_violation(count):
    if int(count) > 0:
        raise AssertionError(
            f"QUIVER_CHECK: {int(count)} valid edge lanes violate the "
            "regular layout dst == repeat(arange(num_dst), fanout) that "
            "the dense aggregation path trusts; this Adj's fanout claim "
            "is wrong and the dense path would mis-aggregate"
        )


def _check_regular_layout(dst, valid, num_dst: int, fanout: int) -> None:
    """Debug-mode assertion of the regular-layout claim the dense-path
    gate trusts (ADVICE layers.py:93): lane ``s*fanout + k`` targets seed
    ``s`` on every valid lane. jit-composable via debug.callback; only
    traced when QUIVER_CHECK is set, so the default path pays nothing."""
    expected = jnp.repeat(
        jnp.arange(num_dst, dtype=dst.dtype), fanout
    )
    bad = jnp.sum(((dst != expected) & valid).astype(jnp.int32))
    jax.debug.callback(_raise_layout_violation, bad)


def gather_src(x, src):
    """Gather per-edge source features; invalid lanes (src == -1) give zeros."""
    valid = src >= 0
    h = x[jnp.clip(src, 0)]
    return jnp.where(valid[:, None], h, 0.0), valid


# lanes a trip of the repeats' loop adds (a chunk of 2 KB rows is 2 MiB; the
# loop's time follows the lanes, not the chunk: PERF.md, PR 35's probe)
_REPEAT_CHUNK = 1024


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def gather_lane_rows(h, idx, num_head: int):
    """Rows of ``h`` by lane and its head rows, transposed as a gather.

    ``h`` is (rows, ...) and ``idx`` of any shape: ``h[clip(idx, 0)]`` and
    ``h[:num_head]`` (a block's sources by lane, and its targets).

    A lane with ``idx < 0`` is padding: it reads row 0, as the clip makes it,
    and **its cotangent is dropped**, so the caller masks those lanes out of
    whatever it computes from the result (every user of a padded block does).

    The plain transpose is one scatter-add of every lane's cotangent row. On
    a v5e that op walks its lanes at 73 ns each whatever they do (544,000
    lanes of 2 KB rows: 40 ms; a lane sent out of range and dropped still
    costs 71 ns), six times a gathered row (PERF.md, PR 35). Most rows of a
    sampled block are named by one lane and need no read-modify-write, so the
    rule here turns the transpose round:

    * two sorts, over the lanes and one query per row, give every row one of
      its lanes (none for a row no lane names) and the other lanes of the
      rows named more than once, compacted (:func:`_lane_plan`);
    * ONE row gather takes each row's cotangent from its lane, zero where it
      has none;
    * the repeats alone are scatter-added, ``_REPEAT_CHUNK`` lanes a trip of
      a loop whose trip count follows their number;
    * the head's cotangent is added to its rows in place (which is why the
      head is this function's to return: sliced off ``h`` by the caller, its
      cotangent would be padded to all rows and added in a pass over all).

    The same sums in another order (a row's lanes in the sort's order). The
    rule keeps ``idx`` of the forward and nothing else, and reads the
    cotangent in place: no lane-sized copy of it is made."""
    return h[jnp.clip(idx, 0)], h[:num_head]


def _gather_lane_rows_fwd(h, idx, num_head):
    return gather_lane_rows(h, idx, num_head), (idx, h.shape[0])


def _lane_plan(flat, rows: int, chunk: int):
    """For lanes ``flat`` (L,) naming rows in [0, rows) (negative: padding):
    ``lane_of`` (rows,), one lane of each row or -1; the remaining valid
    lanes, compacted and padded to whole chunks, as ``(rep_rows, rep_lanes)``
    (behind them: row ``rows``, out of range) and their number. Two sorts
    and no scatter: a row's query sorts right behind the row's lanes, so its
    left neighbour is one of them and every lane whose right neighbour is
    another lane is a repeat; the second sort puts the queries first, in row
    order, and the repeats behind them."""
    L = flat.shape[0]
    big = jnp.iinfo(jnp.int32).max - 1          # even, behind every key
    if 2 * rows + L >= big:
        raise ValueError(
            f"{L} lanes over {rows} rows do not fit the packed int32 keys")
    one = partial(jnp.full, (1,), dtype=jnp.int32)
    key = jnp.concatenate([
        jnp.where(flat >= 0, flat.astype(jnp.int32) * 2, big),
        jnp.arange(rows, dtype=jnp.int32) * 2 + 1])
    lane = jnp.concatenate([
        jnp.arange(L, dtype=jnp.int32), jnp.full((rows,), -1, jnp.int32)])
    key, lane = lax.sort((key, lane), num_keys=1, is_stable=False)
    query = (key & 1) == 1
    left_key = jnp.concatenate([one(-1), key[:-1]])
    left_lane = jnp.concatenate([one(-1), lane[:-1]])
    right_key = jnp.concatenate([key[1:], one(big)])
    repeat = ~query & (key != big) & (right_key == key)
    pos = jnp.arange(L + rows, dtype=jnp.int32)
    order = jnp.where(query, key >> 1, jnp.where(repeat, rows + pos, big))
    _, lane, row = lax.sort(
        (order, jnp.where(query, jnp.where(left_key == key - 1, left_lane, -1),
                          lane), jnp.where(repeat, key >> 1, rows)),
        num_keys=1, is_stable=False)
    pad = (-L) % chunk + chunk      # a slice of the loop never runs off the end
    return (lane[:rows], jnp.pad(row[rows:], (0, pad), constant_values=rows),
            jnp.pad(lane[rows:], (0, pad)), repeat.sum(dtype=jnp.int32))


def _gather_lane_rows_bwd(num_head, res, cots):
    idx, rows = res
    cot, cot_head = cots
    # one barrier over the two: the lanes' plan needs ``idx`` alone and
    # would be free to run, and to keep its arrays, while the cotangent's
    # producers still hold theirs (the step's peak); and the reshape below
    # stays a view of the cotangent as its producer wrote it (moved up into
    # that producer it turns a broadcast over the lanes into an array of
    # its own, 1.11 GB at GAT's layer 0: PERF.md, PR 35)
    cot, idx = lax.optimization_barrier((cot, idx))
    L, chunk = idx.size, _REPEAT_CHUNK
    cot = cot.reshape((L,) + cot.shape[idx.ndim:])
    lane_of, rep_rows, rep_lanes, num_rep = _lane_plan(
        idx.reshape(L), rows, chunk)
    named = (lane_of >= 0).reshape((rows,) + (1,) * (cot.ndim - 1))
    d_h = jnp.where(named, cot[jnp.clip(lane_of, 0)], 0)

    def add_chunk(i, d_h):
        at = lax.dynamic_slice_in_dim(rep_rows, i * chunk, chunk)
        lanes = lax.dynamic_slice_in_dim(rep_lanes, i * chunk, chunk)
        return d_h.at[at].add(cot[lanes], mode="drop")

    d_h = lax.fori_loop(0, (num_rep + chunk - 1) // chunk, add_chunk, d_h)
    # the head's cotangent goes into its rows in place, behind the loop. The
    # rows are read through a 2-D view behind a barrier: the compiler gives
    # a plain slice the layout of the head's cotangent, which costs a copy of
    # ALL rows, before the loop or behind it (2.8 ms at GAT's layer 0:
    # PERF.md, PR 35); the reshape is a boundary that layout does not cross
    head = lax.optimization_barrier(
        lax.slice_in_dim(d_h, 0, num_head).reshape(num_head, -1))
    d_h = lax.dynamic_update_slice_in_dim(
        d_h, head.reshape(cot_head.shape) + cot_head, 0, axis=0)
    return d_h, None


gather_lane_rows.defvjp(_gather_lane_rows_fwd, _gather_lane_rows_bwd)


def zero_scatter_counts(ids, valid, n: int, dtype=jnp.float32):
    """Occurrence count of each value in [0, n) among ``ids[valid]`` —
    a histogram with no scatter: sort (invalid lanes to the sentinel n),
    then bucket edges via one vectorized binary search. The zero-scatter
    analogue of ``segment_sum(ones, ids)`` for backends where a scatter
    costs more than a sort (a v5e: 4.4x, PERF.md, PR 26; this pair itself
    has no chip number, ROADMAP D4)."""
    sv = jnp.sort(jnp.where(valid, ids, n))
    edges = jnp.searchsorted(sv, jnp.arange(n + 1, dtype=ids.dtype))
    return (edges[1:] - edges[:-1]).astype(dtype)


def occurrence_counts(ids, valid, n: int, dtype=jnp.float32):
    """Histogram of ``ids[valid]`` over [0, n), strategy picked per
    platform (``core.config.resolve_platform_strategy``):
    zero-scatter sort+searchsorted on TPU, one scalar scatter-add
    elsewhere. ``QUIVER_COUNTS=scan|scatter`` overrides — resolved once
    per process at op construction (:func:`resolve_counts_strategy`), so
    the env force must be set before the first model trace."""
    how = resolve_counts_strategy()
    if how == "scan":
        return zero_scatter_counts(ids, valid, n, dtype)
    return jax.ops.segment_sum(
        valid.astype(dtype), jnp.where(valid, ids, n), num_segments=n + 1
    )[:n]


def fanout_sum_aggregate(messages, valid, num_dst: int, fanout: int):
    """Masked dense sum over the regular sampler layout: ``messages``
    (num_dst*fanout, ...) in lane order -> (num_dst, ...), zero scatters.
    For a caller that has formed its messages by lane already (GAT's
    weighted rows); one that sums its sources' rows as they are gathers and
    sums with :func:`fanout_gather_sum`, which never holds them by lane."""
    validb = valid.reshape(valid.shape + (1,) * (messages.ndim - 1))
    m = jnp.where(validb, messages, 0)
    return m.reshape((num_dst, fanout) + messages.shape[1:]).sum(axis=1)


# targets of ``fanout`` lanes to add to a fanout-major row gather so that it
# keeps 256 rows in flight (PERF.md section 7 (3))
_target_pad = tile_pad


def _fanout_index(src, num_dst: int, fanout: int, rows: int):
    """``src`` as the index of its fanout-major row gather, ``(fanout,
    num_dst + pad)``, padded so that the gather keeps 256 rows in flight
    (PERF.md section 7 (3)): the ``pad`` targets of :func:`_target_pad`
    behind the block's, whose lanes name rows of their own (a lane's number
    in the padded gather modulo ``rows``): lanes that all name one row are
    served one after the other (PERF.md section 6, the sampler's block
    gather). The caller sums the block's targets alone
    (:func:`_block_sums`)."""
    idx = src.reshape(num_dst, fanout).T
    pad = _target_pad(num_dst, fanout)
    if not pad:
        return idx
    info_once(f"fanout-gather-pad-{num_dst}-{fanout}",
              "fanout-major row gather of %d x %d lanes: %d padded targets, "
              "so that it keeps 256 rows in flight", fanout, num_dst, pad)
    lane = (lax.broadcasted_iota(idx.dtype, (fanout, pad), 0)
            * (num_dst + pad) + num_dst
            + lax.broadcasted_iota(idx.dtype, (fanout, pad), 1))
    return jnp.concatenate([idx, lane % rows], axis=1)


def _block_sums(rows, num_dst: int, dtype=None):
    """``rows[:, :num_dst].sum(axis=0, dtype=dtype)``: the ``(fanout,
    num_dst + pad, F)`` rows of a gather from :func:`_fanout_index` summed
    over the block's targets alone."""
    if rows.shape[1] == num_dst:
        return rows.sum(axis=0, dtype=dtype)
    return _padded_block_sums(rows, num_dst, dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _padded_block_sums(rows, num_dst: int, dtype):
    """The slice is read inside the reduction: sliced off the sums, the
    ``(num_dst, F)`` result is a slice and a copy of its own in front of a
    product (``compiled.as_text()`` for a described v5e). Transposed, the
    sums' cotangent is padded to the pad targets and broadcast over the
    fanout, as the transpose of a slice of the sums would be; the plain
    transpose pads the broadcast, an array of the gathered rows' size."""
    return rows[:, :num_dst].sum(axis=0, dtype=dtype)


def _padded_block_sums_fwd(rows, num_dst, dtype):
    # a zero-size residual: the rows' shape and dtype, and no bytes
    return _padded_block_sums(rows, num_dst, dtype), rows[..., :0]


def _padded_block_sums_bwd(num_dst, dtype, like, cot):
    fanout, targets = like.shape[:2]
    cot = lax.pad(cot.astype(like.dtype), jnp.zeros((), like.dtype),
                  [(0, targets - num_dst, 0)] + [(0, 0, 0)] * (cot.ndim - 1))
    return (jnp.broadcast_to(cot, (fanout,) + cot.shape),)


_padded_block_sums.defvjp(_padded_block_sums_fwd, _padded_block_sums_bwd)


def fanout_gather_sum(x, src, num_dst: int, fanout: int):
    """Each target's sum of its sources' rows, gathered fanout-major.

    ``x`` (rows, F); ``src`` (num_dst*fanout,) in the regular sampler
    layout (lane ``t * fanout + k`` is target ``t``'s k-th source, -1 a
    padded lane) -> the sums (num_dst, F) and the number of sources of each
    target (num_dst,) int32. The gather and the reduction of every family
    that sums plain rows on the dense path.

    The rows are gathered **fanout-major**: the indices go in transposed,
    so slab ``k`` of the ``(fanout, num_dst, F)`` result holds every
    target's k-th source and the sum over axis 0 adds ``fanout`` slabs
    elementwise. With ``num_dst`` a multiple of 8 that 3-D array is the
    gather's ``(fanout * num_dst, F)`` output in the same 8 x 128 tiles (no
    copy), each row is read once, and the transpose broadcasts the
    cotangent over the leading axis straight into the scatter-add's
    update. The lane-order view ``(num_dst, fanout, F)`` tiles over
    ``(fanout, F)`` instead: a copy of every gathered row with the fanout
    padded to whole sublanes (5 -> 8, 10 -> 16), 3.1 + 1.6 ms of
    reddit-sage's 25.2 ms step (PERF.md, PR 36). Any other ``num_dst``
    gives the same sums; the view may then cost its copy again. The same
    terms as the segment path, added in another order.

    **The lanes are padded where they fill whole 1,024-word tiles** or
    fall short of them by under 128: XLA's TPU row gather then keeps 128
    rows in flight, not 256. On a v5e that is 20.9 against 13.2 ns a
    1.5 KB float16 row, and 10 against 4 ns for the sampler's 512-byte
    blocks, which are padded for the same reason (PERF.md section 6;
    ``ops/sample.py::_gather_indices``). The pad is a few targets behind
    ``num_dst`` (:func:`_target_pad`, decided from the lane count at trace
    time), so the sum still adds aligned slabs, and it reads the block's
    targets' part of them alone (:func:`_block_sums`): no copy of the
    gathered rows is made. A pad lane reads a row of its own and enters no
    sum and no count: they are those of the unpadded gather, bit for
    bit."""
    idx = _fanout_index(src, num_dst, fanout, x.shape[0])
    valid = idx >= 0
    rows = jnp.where(valid[..., None], x[jnp.clip(idx, 0)], 0)
    return (_block_sums(rows, num_dst),
            valid[:, :num_dst].sum(axis=0, dtype=jnp.int32))


def fanout_relation_sums(x, src, relation, num_dst: int, fanout: int,
                         num_relations: int):
    """Each target's sum of its sources' rows per relation, gathered
    fanout-major as :func:`fanout_gather_sum` gathers them.

    ``relation`` is ``(fanout, num_dst)``, the sampler's ``Adj.relation``
    (lane ``t * fanout + k`` at ``[k, t]``; -1 on padded lanes). Returns
    one ``(num_dst, F)`` float32 sum per relation (a tuple: each is its
    relation's operand as it is, no stacked copy of the five) and each
    target's number of lanes of each relation ``(num_relations, num_dst)``
    int32.

    The rows are gathered once, in the dtype ``x`` holds (float16 rows
    stay two bytes until they are summed), and each relation's sum adds
    the ``fanout`` slabs that its lanes select, widened to float32 inside
    the sum: one pass over the gathered rows a relation. The lanes are
    padded as :func:`fanout_gather_sum` pads them (mag240m-rsage's 26,624
    x 15 fill whole tiles: 8 targets more keep 256 rows in flight, PERF.md
    section 6); a pad lane carries no relation and enters no sum."""
    idx = _fanout_index(src, num_dst, fanout, x.shape[0])
    rows = x[jnp.clip(idx, 0)]
    kinds = jnp.arange(num_relations, dtype=relation.dtype)
    if idx.shape[1] > num_dst:
        relation = lax.pad(relation, jnp.asarray(-1, relation.dtype),
                           ((0, 0, 0), (0, idx.shape[1] - num_dst, 0)))
    picked = (relation[None] == kinds[:, None, None]) & (idx >= 0)[None]
    sums = tuple(
        _block_sums(jnp.where(picked[r][..., None], rows, 0), num_dst,
                    jnp.float32)
        for r in range(num_relations))
    return sums, picked[..., :num_dst].sum(axis=1, dtype=jnp.int32)


def fanout_relation_softmax(logits, relation, num_relations: int):
    """Softmax over the fanout lanes of each target, one group per target,
    relation and head, with no self term.

    ``logits`` is ``(heads, fanout, targets)`` (lanes-minor: a heads-minor
    ``(..., 4)`` array is padded to 128 lanes on a TPU) and
    ``relation`` ``(fanout, targets)``, each lane's relation or -1 where
    the lane is in no group (a padded lane). Returns the weights
    ``(heads, fanout, targets)``: over the lanes of relation ``r`` of target
    ``t`` they sum to 1 per head, and they are 0 on lanes in no group, so a
    group with no lane gives a zero message and not NaN or a uniform weight.

    Each group's max is taken over its own lanes and held out of the
    gradient (the weights do not depend on it); a lane's max and
    denominator are selected from the ``num_relations`` groups of its
    target by its relation. No scatter, and no array of more than
    ``relations x heads x lanes`` values."""
    kinds = jnp.arange(num_relations, dtype=relation.dtype)
    picked = relation[None] == kinds[:, None, None]      # (R, K, T)
    valid = relation >= 0
    neg = jnp.finfo(logits.dtype).min
    group_max = lax.stop_gradient(jnp.where(
        picked[:, None], logits[None], neg).max(axis=2))  # (R, H, T)

    def by_lane(per_group):                              # (R, H, T) -> (H, K, T)
        return sum(jnp.where(picked[r], per_group[r][:, None], 0)
                   for r in range(num_relations))

    expv = jnp.where(valid, jnp.exp(jnp.where(
        valid, logits - by_lane(group_max), 0)), 0)
    denom = jnp.where(picked[:, None], expv[None], 0).sum(axis=2)
    return expv / jnp.where(valid, by_lane(denom), 1)


def masked_batch_norm(x, valid, scale, bias, eps: float = 1e-5):
    """Batch normalisation in training mode over the rows where ``valid``:
    the mean and the biased variance of those rows alone (a padded block's
    empty slots would otherwise move both), then ``scale`` and ``bias``.
    Rows that are not valid come out zero."""
    v = valid[:, None]
    n = jnp.maximum(valid.sum(dtype=x.dtype), 1)
    mean = jnp.where(v, x, 0).sum(axis=0) / n
    centred = jnp.where(v, x - mean, 0)
    var = (centred * centred).sum(axis=0) / n
    return centred * (lax.rsqrt(var + eps) * scale) + jnp.where(v, bias, 0)


def gather_mean_aggregate(x, src, dst, num_dst: int,
                          fanout: int | None = None):
    """Mean of each target's sources' rows of ``x``, 0 where it has none.

    ``src`` / ``dst`` (E,) hold -1 on padded lanes.

    With ``fanout`` (regular sampler layout, ``E == num_dst * fanout``) the
    dense :func:`fanout_gather_sum`; otherwise the rows in lane order
    through :func:`segment_mean_aggregate`."""
    if fanout is not None and src.shape[0] == num_dst * fanout:
        if _check_enabled():
            _check_regular_layout(dst, src >= 0, num_dst, fanout)
        total, cnt = fanout_gather_sum(x, src, num_dst, fanout)
        return total / jnp.maximum(cnt, 1).astype(total.dtype)[:, None]
    if fanout is not None:
        # the gate failed on SHAPE: fanout promised the dense layout but
        # E != num_dst*fanout, so this aggregation silently reverts to the
        # segment-scatter path — make the perf regression visible (ADVICE
        # layers.py:93)
        info_once(
            f"dense-gate-fallback-{src.shape[0]}-{num_dst}-{fanout}",
            "Adj.fanout=%d set but E=%d != num_dst*fanout=%d; falling back "
            "to the segment-scatter aggregation path (slow on TPU)",
            fanout, src.shape[0], num_dst * fanout,
        )
    messages, valid = gather_src(x, src)
    return segment_mean_aggregate(messages, jnp.clip(dst, 0), valid, num_dst)


def segment_mean_aggregate(messages, dst, valid, num_dst: int):
    """Mean-aggregate edge messages (E, F) into target nodes by segment.

    Invalid lanes are routed to an overflow segment (index num_dst) and
    sliced off — the padded-shape analogue of skipping masked edges. Any
    edge order; the oracle of the dense path."""
    seg = jnp.where(valid, dst, num_dst)
    total = jax.ops.segment_sum(messages, seg, num_segments=num_dst + 1)[:num_dst]
    cnt = jax.ops.segment_sum(valid.astype(messages.dtype), seg, num_segments=num_dst + 1)[:num_dst]
    return total / jnp.maximum(cnt, 1.0)[:, None]


def fanout_softmax(logits, self_logits, valid):
    """Dense counterpart of ``segment_softmax`` with a self term, no scatters.

    ``logits`` is ``(heads, fanout, targets)``, lanes-minor for
    :func:`fanout_relation_softmax`'s reason (a heads-minor ``(..., 4)``
    array is padded to 128 lanes on a TPU), ``self_logits`` ``(heads,
    targets)`` and ``valid`` ``(fanout, targets)``. The softmax runs over
    ``fanout + 1`` entries per target and head: its valid lanes and its own
    (the self loop of an attention layer), which is no lane of the block.
    The self term is an operand of its own of the max and of the
    denominator, so nothing is scattered and no ``(heads, fanout + 1,
    targets)`` copy is made to append it; it also keeps every denominator
    at 1 or more, all-invalid targets included. Returns the weights
    ``(heads, fanout, targets)``, 0 on invalid lanes, and the self weights
    ``(heads, targets)``."""
    neg = jnp.finfo(logits.dtype).min
    g = jnp.where(valid, logits, neg)
    gmax = jnp.maximum(g.max(axis=1), self_logits)
    expv = jnp.where(g > neg, jnp.exp(g - gmax[:, None]), 0.0)
    exp_self = jnp.exp(self_logits - gmax)
    denom = expv.sum(axis=1) + exp_self
    return expv / denom[:, None], exp_self / denom


def segment_softmax(logits, seg, valid, num_seg: int):
    """Numerically-stable softmax over edges grouped by target segment.

    ``logits`` may be (E,) or (E, ...) — trailing dims (e.g. attention
    heads) are softmaxed independently. Exercises the pattern a GAT needs
    (BASELINE.json config 4: "attention aggregation, exercises
    segment-softmax").
    """
    validb = valid.reshape(valid.shape + (1,) * (logits.ndim - 1))
    seg_safe = jnp.where(valid, seg, num_seg)
    neg = jnp.finfo(logits.dtype).min
    masked = jnp.where(validb, logits, neg)
    seg_max = jax.ops.segment_max(masked, seg_safe, num_segments=num_seg + 1)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    shifted = jnp.where(validb, logits - seg_max[seg_safe], neg)
    expv = jnp.where(validb, jnp.exp(shifted), 0.0)
    denom = jax.ops.segment_sum(expv, seg_safe, num_segments=num_seg + 1)
    return expv / jnp.maximum(denom[seg_safe], jnp.finfo(logits.dtype).tiny)
