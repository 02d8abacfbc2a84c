"""Capped-bucket owner routing over a mesh axis — the shared comm core.

Extracted from the PR 1 capped-bucket routed gather (feature/shard.py) so
the two per-hop consumers — the sharded-feature gather and the distributed
neighbor sampler (sampling/dist.py) — drive ONE audited code path:

1. sort my per-device requests by owning shard (stable, so results can be
   unsorted with a gather through the inverse permutation — no scatter);
2. pack destination buckets CAPPED at ``cap`` lanes each and exchange them
   with one ``all_to_all`` over the mesh axis (``F x cap`` lanes per hop
   instead of the exact-safe worst case ``F x L``);
3. serve the received requests locally (the caller's ``serve`` closure) and
   return the answers with a second ``all_to_all``;
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

from ..ops.reindex import inverse_permutation_gather
from ..utils.trace import trace_scope

__all__ = ["BucketRoute"]


class BucketRoute:
    """One planned owner-routing of a per-device request vector.

    Call inside ``shard_map``. The plan (owner sort, bucket bounds, overflow
    mask) is computed once; :meth:`exchange` can then route any number of
    request/payload exchanges through the same buckets — the distributed
    sampler uses this to route ids, then per-id sample offsets, without
    re-sorting.

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
        self.axis = axis
        self.num_shards = F
        self.length = L
        self.cap = cap

        self._valid = valid
        safe = jnp.where(valid, ids, 0)
        # invalid lanes go to a sentinel bucket F past the real ones: they
        # are never routed, eat no bucket capacity, and cannot fake overflow
        owner = jnp.where(valid, jnp.clip(owner, 0, F - 1), F)
        order = jnp.argsort(owner, stable=True)
        self._order = order
        self._sorted_ids = safe[order]
        sorted_owner = owner[order]
        sorted_valid = valid[order]
        bounds = jnp.searchsorted(
            sorted_owner, jnp.arange(F + 1, dtype=sorted_owner.dtype)
        )
        self._start, ends = bounds[:F], bounds[1:]
        self._counts = ends - self._start
        self._owner_c = jnp.clip(sorted_owner, 0, F - 1)
        self._slot = jnp.arange(L, dtype=jnp.int32) - self._start[self._owner_c]

        # overflow bookkeeping (statically absent when cap == L)
        self.ov_budget = L - cap
        if self.ov_budget == 0:
            self._ov_mask = None
            self.overflow = jnp.zeros((), jnp.int32)
        else:
            self._ov_mask = sorted_valid & (self._slot >= cap)
            ov_local = jnp.sum(self._ov_mask.astype(jnp.int32))
            self._ov_local = ov_local
            # axis-psum'd: uniform across the axis group — the fallback
            # cond's deadlock-free predicate, and the count callers surface
            self.overflow = jax.lax.psum(ov_local, axis)
            # compact my overflow lanes to the static budget (overflow lanes
            # first in sorted order: False < True, stable)
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
        """(L, ...) sorted per-lane values -> (F, cap, ...) send buckets:
        the first ``cap`` lanes per destination, ``fill`` elsewhere."""
        F, cap, L = self.num_shards, self.cap, self.length
        j = jnp.arange(cap, dtype=jnp.int32)[None, :]
        pos = jnp.clip(self._start[:, None] + j, 0, L - 1)
        live = j < jnp.minimum(self._counts, cap)[:, None]
        vals = sorted_vals[pos]  # (F, cap, ...)
        live = live.reshape(live.shape + (1,) * (vals.ndim - 2))
        return jnp.where(live, vals, fill)

    @trace_scope("route_exchange")
    def _a2a(self, x):
        """Exchange (F, cap, ...) buckets: bucket f goes to shard f; the
        result's leading axis indexes the SENDING shard."""
        out = jax.lax.all_to_all(
            x, self.axis, split_axis=0, concat_axis=0, tiled=False
        )
        return out.reshape(x.shape)

    def _compact_overflow(self, sorted_vals, fill):
        """(L, ...) sorted values -> (ov_budget, ...) overflow lanes first,
        ``fill`` past the live count."""
        take = sorted_vals[self._ov_take]
        live = jnp.arange(self.ov_budget, dtype=jnp.int32) < self._ov_local
        live = live.reshape(live.shape + (1,) * (take.ndim - 1))
        return jnp.where(live, take, fill)

    @trace_scope("route_fallback")
    def _answer_overflow(self, serve, sorted_payload, main):
        """``main`` with the lanes past their bucket's capacity answered
        through the cond-gated psum fallback."""
        F = self.num_shards
        L_ov = self.ov_budget
        ov_ids = self._compact_overflow(self._sorted_ids, fill=-1)
        ov_payload = (
            None if sorted_payload is None
            else self._compact_overflow(sorted_payload, fill=0)
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
        F, cap, L = self.num_shards, self.cap, self.length
        if self._recv_ids is None:
            self._recv_ids = self._a2a(
                self._bucketize(self._sorted_ids, fill=-1)
            )
        recv_ids = self._recv_ids
        sorted_payload = None
        if payload is not None:
            sorted_payload = payload[self._order]
            recv_payload = self._a2a(self._bucketize(sorted_payload, fill=0))
            served = serve(
                recv_ids.reshape(-1),
                recv_payload.reshape((F * cap,) + recv_payload.shape[2:]),
            )
        else:
            served = serve(recv_ids.reshape(-1))
        served = served.reshape((F, cap) + served.shape[1:])
        back = self._a2a(served)
        main = back[self._owner_c, jnp.clip(self._slot, 0, cap - 1)]

        if self.ov_budget == 0:
            answered = main
        else:
            answered = self._answer_overflow(serve, sorted_payload, main)

        out = answered[inverse_permutation_gather(self._order)]
        vmask = self._valid.reshape(self._valid.shape + (1,) * (out.ndim - 1))
        return jnp.where(vmask, out, 0)
