"""Capped-bucket owner routing over a mesh axis — the shared comm core.

Extracted from the PR 1 capped-bucket routed gather (feature/shard.py) so
the two per-hop consumers — the sharded-feature gather and the distributed
neighbor sampler (sampling/dist.py) — drive ONE audited code path:

1. give every request its slot in its owner's bucket — its running count
   among my lanes of the same owner, in lane order — and group the ids by
   owner with one sort that carries them as its payload (no gather through
   a sort order, no ``searchsorted``, no scatter);
2. cut destination buckets CAPPED at ``cap`` lanes each out of that sorted
   array as contiguous slices and exchange them with one ``all_to_all``
   over the mesh axis (``F x cap`` lanes per hop instead of the exact-safe
   worst case ``F x L``);
3. serve the received requests locally (the caller's ``serve`` closure),
   return the answers with a second ``all_to_all``, and read them once, in
   lane order, at ``owner * cap + slot``;
4. lanes past their bucket's capacity are DETECTED in-program, never
   silent: they are served exactly through a psum fallback (all_gather the
   <= L-cap overflow requests over the axis, every shard contributes the
   answers it owns, psum hands the full result to every member) gated
   behind a ``lax.cond`` whose predicate is the axis-psum of the overflow
   count — uniform across the participants, so the collective-inside-cond
   is deadlock-free, and a clean batch pays ZERO fallback comm.

Overflow budget (why the ``(L - cap,)`` fallback buffer is exact-safe): at
most ``L`` lanes are valid, and every bucket that overflows still keeps its
first ``cap`` lanes, so the total overflow across all buckets is at most
``L - cap``.

Results are bit-identical between capped and uncapped (``cap >= L``)
routing: capping changes how many lanes each hop carries, never which
answers come back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.trace import trace_scope

__all__ = ["BucketRoute"]


class BucketRoute:
    """One planned owner-routing of a per-device request vector.

    Call inside ``shard_map``. The plan (slots, bucket bounds, the ids
    grouped by owner, overflow mask) is computed once; :meth:`exchange` can
    then route any number of request/payload exchanges through the same
    buckets — the distributed sampler uses this to route ids, then per-id
    sample offsets, without planning again.

    What the plan costs: ``F`` running counts over the ``L`` lanes (a lane's
    slot is its rank among the lanes of its owner; ``F`` is static, the
    mesh axis's size, so the counts are one ``(F, L)`` ``cumsum``) and one
    sort of ``L`` lanes, keyed by ``owner * L + lane``, that carries the
    ids. Nothing is gathered through the sort order: bucket ``f`` is the
    slice of the sorted ids that starts at the number of lanes owned below
    ``f``, and only an exchange that carries a payload reads the order. In
    the four-chip step on v5e, at 672,384 lanes and ``F`` 2, ``route_plan``
    reads 0.77 ms where the argsort, ``searchsorted`` and five gathers it
    replaced read 18.79 (one traced pair, PERF.md, PR 31).

    Args:
      ids: (L,) int request keys; invalid lanes may hold anything (they are
        sanitized to 0 and never routed).
      valid: (L,) bool. Invalid lanes are assigned to a sentinel bucket past
        the real ones, occupy zero bucket capacity, and come back as zeros.
      owner: (L,) int owning-shard index in [0, F) (any value on invalid
        lanes).
      axis: mesh axis name the ``all_to_all``/``psum`` collectives run over.
      num_shards: F, the axis size.
      cap: per-destination bucket capacity. ``None`` or ``>= L`` means
        full-length buckets — the exact-safe uncapped mode; no fallback
        machinery is traced and :attr:`overflow` is a constant 0.
      tape: optional ``obs.MetricsTape`` — the plan's :attr:`overflow`
        count is fed to it as counter ``metric`` (graftscope: routing
        telemetry rides the step's metrics pytree instead of inventing a
        surfacing convention; the metric must be registered on the tape's
        registry).
      metric: tape counter name; defaults to ``obs.ROUTED_OVERFLOW``.
    """

    @trace_scope("route_plan")
    def __init__(self, ids, valid, owner, *, axis: str, num_shards: int,
                 cap: int | None = None, tape=None, metric: str | None = None):
        F = int(num_shards)
        L = int(ids.shape[0])
        if cap is None or int(cap) >= L:
            cap = L  # full-length buckets ARE the uncapped exact-safe mode
        cap = int(cap)
        if cap < 1:
            raise ValueError(f"cap must be >= 1, got {cap}")
        if (F + 1) * L > jnp.iinfo(jnp.int32).max:
            raise ValueError(
                f"BucketRoute: {L} lanes over {F} shards leave the sort key "
                "no room in 32 bits")
        self.axis = axis
        self.num_shards = F
        self.cap = cap

        self._valid = valid
        self._ids = jnp.where(valid, ids, 0)
        # invalid lanes go to a sentinel bucket F past the real ones: they
        # are never routed, eat no bucket capacity, and cannot fake overflow
        key = jnp.where(valid, jnp.clip(owner, 0, F - 1), F).astype(jnp.int32)
        # a lane's slot in its bucket: how many lanes of its owner precede
        # it (0 on invalid lanes, which are masked wherever slots are read)
        mine = key[None, :] == jnp.arange(F, dtype=jnp.int32)[:, None]
        running = lax.cumsum(mine.astype(jnp.int32), axis=1)
        self._counts = running[:, -1]
        self._start = jnp.cumsum(self._counts) - self._counts
        slot = jnp.sum(jnp.where(mine, running - 1, 0), axis=0)
        # the ids grouped by owner, lane order kept inside a group: bucket f
        # is sorted[start[f] : start[f] + count[f]]. The sort's one key
        # packs the lane under the owner, so it needs no stability and its
        # sorted key gives the order back (read by payload exchanges only)
        word, self._sorted_ids = lax.sort(
            (key * L + jnp.arange(L, dtype=jnp.int32), self._ids),
            num_keys=1, is_stable=False,
        )
        self._order = word % L
        # where each lane's answer lands in the returned (F * cap) buffer
        self._back = jnp.where(valid & (slot < cap), key * cap + slot, 0)

        # overflow bookkeeping (statically absent when cap == L)
        self.ov_budget = L - cap
        if self.ov_budget == 0:
            self._ov_mask = None
            self.overflow = jnp.zeros((), jnp.int32)
        else:
            self._ov_mask = valid & (slot >= cap)
            ov_local = jnp.sum(self._ov_mask.astype(jnp.int32))
            self._ov_local = ov_local
            # axis-psum'd: uniform across the axis group — the fallback
            # cond's deadlock-free predicate, and the count callers surface
            self.overflow = jax.lax.psum(ov_local, axis)
            # compact my overflow lanes to the static budget (overflow lanes
            # first, in lane order: False < True, stable)
            self._ov_take = jnp.argsort(~self._ov_mask, stable=True)[
                : self.ov_budget
            ]
            self._ov_rank = jnp.cumsum(self._ov_mask.astype(jnp.int32)) - 1
        # the routed request ids, cached after the first exchange: a second
        # exchange through the same plan (the sampler routes ids for the
        # degree hop, then offsets for the neighbor hop) skips re-sending
        # them. Plans live and die inside one traced body, so caching the
        # traced value is safe.
        self._recv_ids = None
        if tape is not None:
            from ..obs.registry import ROUTED_OVERFLOW

            # the psum'd overflow is uniform across the axis group, so the
            # tape value needs no further feature-axis reduction
            tape.add(metric or ROUTED_OVERFLOW, self.overflow)

    # -- internals ----------------------------------------------------------

    @trace_scope("route_plan")
    def _bucketize(self, sorted_vals, fill):
        """(L, ...) per-lane values grouped by owner -> (F, cap, ...) send
        buckets: the first ``cap`` lanes per destination, ``fill``
        elsewhere. A bucket is a contiguous slice; the padding keeps the
        last one's ``cap`` lanes inside the array."""
        F, cap = self.num_shards, self.cap
        trailing = sorted_vals.shape[1:]
        padded = jnp.pad(
            sorted_vals, ((0, cap),) + ((0, 0),) * len(trailing))
        vals = jnp.stack([
            lax.dynamic_slice_in_dim(padded, self._start[f], cap)
            for f in range(F)
        ])
        live = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                < jnp.minimum(self._counts, cap)[:, None])
        live = live.reshape(live.shape + (1,) * len(trailing))
        return jnp.where(live, vals, fill)

    @trace_scope("route_exchange")
    def _a2a(self, x):
        """Exchange (F, cap, ...) buckets: bucket f goes to shard f; the
        result's leading axis indexes the SENDING shard."""
        out = jax.lax.all_to_all(
            x, self.axis, split_axis=0, concat_axis=0, tiled=False
        )
        return out.reshape(x.shape)

    def _compact_overflow(self, vals, fill):
        """(L, ...) per-lane values -> (ov_budget, ...) overflow lanes
        first, ``fill`` past the live count."""
        take = vals[self._ov_take]
        live = jnp.arange(self.ov_budget, dtype=jnp.int32) < self._ov_local
        live = live.reshape(live.shape + (1,) * (take.ndim - 1))
        return jnp.where(live, take, fill)

    @trace_scope("route_fallback")
    def _answer_overflow(self, serve, payload, main):
        """``main`` with the lanes past their bucket's capacity answered
        through the cond-gated psum fallback."""
        F = self.num_shards
        L_ov = self.ov_budget
        ov_ids = self._compact_overflow(self._ids, fill=-1)
        ov_payload = (
            None if payload is None
            else self._compact_overflow(payload, fill=0)
        )
        trailing = main.shape[1:]
        dtype = main.dtype
        my = jax.lax.axis_index(self.axis)

        def _fallback(args):
            # psum fallback: everyone sees everyone's overflow requests
            # (cheap — id/payload lanes, no answers), each shard
            # contributes the answers it owns, the psum hands every
            # member the full result and it keeps its own slice
            ids_, pay_ = args
            allov = jax.lax.all_gather(
                ids_, self.axis, tiled=False
            ).reshape(F, L_ov)
            if pay_ is None:
                part = serve(allov.reshape(-1))
            else:
                allpay = jax.lax.all_gather(
                    pay_, self.axis, tiled=False
                ).reshape((F, L_ov) + pay_.shape[1:])
                part = serve(
                    allov.reshape(-1),
                    allpay.reshape((F * L_ov,) + pay_.shape[1:]),
                )
            part = part.reshape((F, L_ov) + trailing)
            return jax.lax.psum(part, self.axis)[my]

        def _no_overflow(args):
            return jnp.zeros((L_ov,) + trailing, dtype)

        ov_rows = jax.lax.cond(
            self.overflow > 0, _fallback, _no_overflow,
            (ov_ids, ov_payload),
        )
        mask = self._ov_mask.reshape(
            self._ov_mask.shape + (1,) * (main.ndim - 1)
        )
        return jnp.where(
            mask, ov_rows[jnp.clip(self._ov_rank, 0, L_ov - 1)], main
        )

    # -- API ----------------------------------------------------------------

    def exchange(self, serve, payload=None):
        """Route the planned ids (and optional per-lane ``payload``) to
        their owners, serve, and return the per-lane answers in original
        lane order (zeros on invalid lanes).

        ``serve(ids[, payload])`` receives flat ``(n,)`` global ids (-1 on
        dead lanes) plus the matching payload slice and must return
        ``(n, ...)`` answers that are ZERO for lanes it does not own and
        for ``ids < 0`` — the ownership masking is what makes the psum
        fallback exact, and it is harmless on the main hop (routing already
        guarantees ownership there).
        """
        F, cap = self.num_shards, self.cap
        if self._recv_ids is None:
            self._recv_ids = self._a2a(
                self._bucketize(self._sorted_ids, fill=-1)
            )
        recv_ids = self._recv_ids
        if payload is not None:
            recv_payload = self._a2a(
                self._bucketize(payload[self._order], fill=0))
            served = serve(
                recv_ids.reshape(-1),
                recv_payload.reshape((F * cap,) + recv_payload.shape[2:]),
            )
        else:
            served = serve(recv_ids.reshape(-1))
        served = served.reshape((F, cap) + served.shape[1:])
        back = self._a2a(served)
        # the one pass over the rows on this side: each lane reads its slot
        # of its owner's answers (lanes with none read row 0, masked below)
        out = back.reshape((F * cap,) + back.shape[2:])[self._back]
        if self.ov_budget != 0:
            out = self._answer_overflow(serve, payload, out)
        vmask = self._valid.reshape(self._valid.shape + (1,) * (out.ndim - 1))
        return jnp.where(vmask, out, 0)
