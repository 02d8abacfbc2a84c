"""Message-passing primitives over padded Adj blocks.

The reference delegates all modeling to PyG (SAGEConv etc. in example
scripts, examples/pyg/reddit_quiver.py:42-65); quiver-tpu ships its own
TPU-native GNN layers because PyG/torch are out of the build. Edges arrive
as padded ``edge_index`` (2, E) with -1 sentinels (source = frontier-local
id, target = seed-local id).

Two aggregation paths, identical results:

* **dense** (``fanout`` set — every sampler-built Adj): the sampler's edge
  layout is regular (lane ``s*fanout + k`` targets seed ``s``), so
  aggregation is a masked ``(num_dst, fanout, F)`` reshape + axis-1
  reduction — zero scatters. A scatter is not serialized on a v5e but
  costs 4.6 ns a lane, 4.4x a payload sort of the same lanes (one
  unique-index scatter of 852,480 lanes: PERF.md, PR 26); the segment
  path's own cost has not been measured on the chip.
* **segment** (``fanout=None``): ``jax.ops.segment_sum`` with an overflow
  bucket for invalid lanes — kept for hand-built/irregular Adjs and as the
  differential-test oracle.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = [
    "segment_mean_aggregate",
    "segment_softmax",
    "fanout_softmax",
    "fanout_sum_aggregate",
    "gather_src",
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
    (num_dst*fanout, ...) -> (num_dst, ...), zero scatters. The shared
    reduction behind every conv family's dense path."""
    validb = valid.reshape(valid.shape + (1,) * (messages.ndim - 1))
    m = jnp.where(validb, messages, 0)
    return m.reshape((num_dst, fanout) + messages.shape[1:]).sum(axis=1)


def segment_mean_aggregate(messages, dst, valid, num_dst: int,
                           fanout: int | None = None):
    """Mean-aggregate edge messages into target nodes.

    With ``fanout`` (regular sampler layout, ``E == num_dst * fanout``) the
    aggregate is a dense masked reduction; otherwise invalid lanes are
    routed to an overflow segment (index num_dst) and sliced off — the
    padded-shape analogue of skipping masked edges.
    """
    if fanout is not None and messages.shape[0] == num_dst * fanout:
        if _check_enabled():
            _check_regular_layout(dst, valid, num_dst, fanout)
        total = fanout_sum_aggregate(messages, valid, num_dst, fanout)
        cnt = valid.reshape(num_dst, fanout).sum(1).astype(messages.dtype)
        return total / jnp.maximum(cnt, 1.0)[:, None]
    if fanout is not None:
        from ..utils.trace import info_once

        # the gate failed on SHAPE: fanout promised the dense layout but
        # E != num_dst*fanout, so this aggregation silently reverts to the
        # segment-scatter path — make the perf regression visible (ADVICE
        # layers.py:93)
        info_once(
            f"dense-gate-fallback-{messages.shape[0]}-{num_dst}-{fanout}",
            "Adj.fanout=%d set but E=%d != num_dst*fanout=%d; falling back "
            "to the segment-scatter aggregation path (slow on TPU)",
            fanout, messages.shape[0], num_dst * fanout,
        )
    seg = jnp.where(valid, dst, num_dst)
    total = jax.ops.segment_sum(messages, seg, num_segments=num_dst + 1)[:num_dst]
    cnt = jax.ops.segment_sum(valid.astype(messages.dtype), seg, num_segments=num_dst + 1)[:num_dst]
    return total / jnp.maximum(cnt, 1.0)[:, None]


def fanout_softmax(logits, self_logits, valid, num_dst: int, fanout: int):
    """Dense counterpart of ``segment_softmax`` with a self term, no scatters.

    The softmax runs over ``fanout + 1`` entries per target: its ``fanout``
    sampled lanes and its own (``self_logits`` (num_dst, ...), the self loop
    of an attention layer), which is no lane of the block, in the regular
    layout (lane ``t * fanout + k`` targets ``t``). The self term is an
    operand of its own of the max and of the denominator, so nothing is
    scattered and no ``(num_dst, fanout + 1, ...)`` copy is made to append
    it; it also keeps every denominator at 1 or more, all-invalid targets
    included.
    ``logits`` (E, ...) -> weights (E, ...) and self weights (num_dst, ...)."""
    shape = logits.shape
    validb = valid.reshape(valid.shape + (1,) * (logits.ndim - 1))
    neg = jnp.finfo(logits.dtype).min
    g = jnp.where(validb, logits, neg).reshape((num_dst, fanout) + shape[1:])
    gmax = jnp.maximum(g.max(axis=1), self_logits)
    expv = jnp.where(g > neg, jnp.exp(g - gmax[:, None]), 0.0)
    exp_self = jnp.exp(self_logits - gmax)
    denom = expv.sum(axis=1) + exp_self
    return (expv / denom[:, None]).reshape(shape), exp_self / denom


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
