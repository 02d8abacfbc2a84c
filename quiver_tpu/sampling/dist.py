"""Distributed neighbor sampling over a mesh-sharded topology.

The scale-out counterpart of ``GraphSageSampler``: the graph lives as a
:class:`~quiver_tpu.core.sharded_topology.ShardedTopology` (contiguous
row ranges of the CSR across the mesh's ``feature`` axis, ~1/F topology
bytes per chip) and every device is a full sampling worker over its own
seed block. Each hop runs inside ``shard_map``:

1. route every frontier vertex to its owning shard with the PR 1
   capped-bucket ``all_to_all`` (``parallel/routing.py`` — the SAME
   audited code path the sharded feature gather uses);
2. the owner answers the vertex's degree (one capped hop back);
3. the requester draws the per-vertex sample offsets with the EXACT
   stratified+rotation scheme of the replicated kernel
   (``ops/sample.py`` ``stratified_offsets``/``rotate_offsets``, same key,
   same shapes — this is what makes the distributed sampler bit-identical
   to the replicated one);
4. the offsets ride the same buckets to the owner, which gathers the
   neighbor ids from its local CSR slice and routes the ``(cap, k)``
   neighbor blocks back.

Bucket overflow is detected in-program and served EXACTLY via the
cond-gated psum fallback (never silent, never wrong), counted, and
surfaced as ``last_sample_overflow`` — the sampling sibling of
``last_routed_overflow``/``last_tier_hits``.

Comm model (L = per-device frontier width, F = shards, k = fanout,
``cap = ceil(alpha * L / F)``): the four ``all_to_all`` hops move
``F*cap``, ``F*cap``, ``F*cap*k`` and ``F*cap*k`` lanes — ``~alpha * L *
(2 + 2k)`` total vs the exact-safe full-length ``F * L * (2 + 2k)``; the
id lanes of the second exchange are not re-sent (the route plan caches
them).

Weighted and temporal draws ride the SAME route plan. The weighted hop
adds one f32 exchange (per-row total weight back) and moves the
inverse-CDF binary search to the owner, which searches its routed
prefix-weight segment — bitwise identical f32 values to the replicated
array's row, so the draw is bit-identical too (+``F*cap`` f32 lanes; the
offsets-out hop carries the (S, k) f32 uniform block instead of int32
offsets). The temporal hop answers ``(first, deg_t)`` in-window slot
ranges in place of plain degrees (one int32 exchange with trailing dim
2, +``F*cap`` lanes over uniform).

Bit-parity contract: for the same seed block, PRNG key, fanouts, frontier
caps, every per-worker ``SampleOutput`` (n_id, adjs)
is bit-identical to the replicated ``GraphSageSampler``'s on that block
with key ``fold_in(key, worker_index)`` — capping and routing change which
wires the bits cross, never the bits.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.config import SampleMode
from ..core.sharded_topology import ShardedTopology
from ..core.topology import CSRTopo
from ..obs.registry import SAMPLE_OVERFLOW, MetricsRegistry
from ..ops.reindex import reindex_layer
from ..ops.sample import rotate_offsets, stratified_offsets
from ..parallel.mesh import FEATURE_AXIS, shard_map
from ..parallel.routing import BucketRoute
from ..utils.trace import info_once, trace_scope
from .sampler import Adj, GraphSageSampler, SampleOutput, _round_up

__all__ = [
    "DistGraphSageSampler",
    "dist_sample_layer",
    "dist_multilayer_sample",
    "routed_sample_cap",
]


def routed_sample_cap(length: int, num_shards: int,
                      alpha: float | None) -> int | None:
    """Per-destination bucket capacity for a frontier of width ``length``:
    ``ceil(alpha * L / F)`` clamped to [1, L]; ``None`` (or a cap >= L)
    means the exact-safe full-length buckets."""
    if alpha is None:
        return None
    if alpha <= 0:
        raise ValueError(f"alpha must be > 0, got {alpha}")
    # graftlint: disable=host-op-on-tracer -- L is the static lane width
    cap = -(-int(alpha * length) // max(num_shards, 1))
    # graftlint: disable=host-op-on-tracer -- L is the static lane width
    cap = max(1, min(cap, int(length)))
    return None if cap >= length else cap


def _worker_index(mesh):
    """Flat worker index over every mesh axis (axis-name order) — the same
    fold-in scheme the seed_sharding="all" trainer uses."""
    idx = jnp.zeros((), jnp.int32)
    for a in mesh.axis_names:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def dist_sample_layer(local_indptr, local_indices, rows_per_shard: int,
                      seeds, num_seeds, k: int, key, *, axis: str,
                      num_shards: int, cap: int | None,
                      weighted: bool = False, local_cum_weights=None,
                      time_window=None, local_edge_time=None,
                      search_iters: int = 0, route=None,
                      kernel: str = "xla"):
    """One distributed hop (per-device body; call inside ``shard_map``).

    Args:
      local_indptr: (rows_per_shard + 1,) this shard's rebased indptr.
      local_indices: (padded_edges,) this shard's CSR indices slice.
      seeds: (S,) node ids, -1 padded (valid entries occupy a prefix).
      num_seeds: scalar count of valid seeds.
      k: static fanout.
      key: PRNG key — consumed exactly like the replicated
        ``sample_layer`` (same splits over the same (S, k) shapes; the
        weighted draw consumes it unsplit, also matching), which is what
        makes results bit-identical.
      axis / num_shards: the mesh axis the topology is sharded over.
      cap: per-destination routed-bucket capacity (None = uncapped).
      weighted: inverse-CDF weighted draw against the owner's routed
        prefix-weight segments; requires ``local_cum_weights`` (this
        shard's (padded_edges,) slice of ``CSRTopo.cum_weights``).
      time_window: optional ``(lo, hi)`` scalar timestamps; the owner
        binary-searches each routed row's in-window slot range and the
        requester draws within it (masked degrees). Requires
        ``local_edge_time``; mutually exclusive with ``weighted``.
      search_iters: static binary-search bound for the weighted/temporal
        paths — MUST derive from the GLOBAL max degree so every shard
        (and the replicated oracle) runs the same loop.
      route: an existing ``BucketRoute`` built over this hop's ``seeds``
        (the hetero sampler shares ONE route per destination type across
        every relation into it — the plan's id lanes are sent once and
        cached). ``None`` builds a fresh route.
      kernel: "xla" or "pallas" — with "pallas" the OWNER-side neighbor
        gather and weighted CDF walk run on the fused Pallas engine
        (ops/pallas/fused.py ``fused_select_hop``/``fused_weighted_hop``;
        the same audited kernel as the replicated sampler), and every bit
        crossing the wires is unchanged, so the parity contract holds.
        Callers must guarantee ``window <= E_local <= int32 max`` and that
        every row fits one DMA window (global ``max_degree <= window``) —
        ``DistGraphSageSampler._compiled`` gates this and degrades to xla;
        direct callers that break it get a loud ValueError.

    Returns (neighbors (S, k) int32 -1-masked, counts (S,), overflow
    scalar — the axis-group total of fallback-served lanes).
    """
    from ..ops.sample import _cdf_search, temporal_window_counts

    S = seeds.shape[0]
    valid = (jnp.arange(S) < num_seeds) & (seeds >= 0)
    s = jnp.where(valid, seeds, 0)
    my = jax.lax.axis_index(axis)
    E_local = local_indices.shape[0]
    base_dtype = (
        jnp.int64 if E_local > np.iinfo(np.int32).max else jnp.int32
    )
    use_pallas = kernel == "pallas"
    if use_pallas:
        from ..ops.pallas.fused import (
            DEFAULT_WINDOW,
            MIN_EDGES,
            fused_select_hop,
            fused_weighted_hop,
        )

        if (E_local < MIN_EDGES
                or E_local > np.iinfo(np.int32).max
                or k > DEFAULT_WINDOW):
            raise ValueError(
                f"kernel='pallas' needs {MIN_EDGES} <= local edge "
                f"count <= int32 max and fanout <= {DEFAULT_WINDOW} (got "
                f"E_local={E_local}, k={k}); DistGraphSageSampler gates "
                f"this at compile time — use kernel='xla' here"
            )

    def _mine_local(ids):
        # ownership-masked local row index — zero answers for lanes this
        # shard does not own make the route's psum fallback exact
        mine = (ids >= 0) & (ids // rows_per_shard == my)
        return mine, jnp.where(mine, ids - my * rows_per_shard, 0)

    def _local_row(r):
        base = local_indptr[r].astype(base_dtype)
        deg = (local_indptr[r + 1] - local_indptr[r]).astype(jnp.int32)
        return base, deg

    def serve_deg(ids):
        mine, r = _mine_local(ids)
        _, deg = _local_row(r)
        return jnp.where(mine, deg, 0)

    def serve_nbr(ids, offs):
        mine, r = _mine_local(ids)
        base, _ = _local_row(r)
        if use_pallas:
            # fused owner-side gather: one window DMA per routed lane +
            # in-kernel one-hot select. Callers guarantee every owned row
            # fits the window (global max_degree <= window) and offs <
            # deg, so start = clip(base) keeps base+offs in-window; lanes
            # this shard does not own read row 0's window (in-bounds, any
            # value) and are zero-masked below, exactly like the clipped
            # XLA gather — the bits after the mask are identical.
            start = jnp.clip(
                base, 0, E_local - DEFAULT_WINDOW).astype(jnp.int32)
            woffs = offs.astype(jnp.int32) + (
                base.astype(jnp.int32) - start)[:, None]
            (nbr,) = fused_select_hop(
                local_indices.astype(jnp.int32), start, woffs,
                window=DEFAULT_WINDOW)
        else:
            epos = base[:, None] + offs.astype(base.dtype)
            nbr = local_indices[jnp.clip(epos, 0, E_local - 1)]
        return jnp.where(mine[:, None], nbr, 0).astype(jnp.int32)

    if route is None:
        route = BucketRoute(
            s, valid, s // rows_per_shard, axis=axis, num_shards=num_shards,
            cap=cap,
        )

    if weighted:
        # weighted hop: (1) ids out / degrees back, (2) row weight totals
        # back (same buckets, f32 — one answer dtype per exchange), (3)
        # the requester's uniform block out / weight-drawn neighbor ids
        # back. The requester consumes the key UNSPLIT over the same
        # (S, k) uniform block as ops.sample.weighted_offsets, and the
        # owner's prefix slice is bitwise identical to the replicated
        # array's row segment — bit parity by construction.
        def serve_tot(ids):
            mine, r = _mine_local(ids)
            base, deg = _local_row(r)
            end = jnp.clip(base + deg.astype(base.dtype) - 1, 0, E_local - 1)
            tot = local_cum_weights[end]
            return jnp.where(mine & (deg > 0), tot, 0.0)

        def serve_wnbr(ids, u):
            mine, r = _mine_local(ids)
            base, deg = _local_row(r)
            if use_pallas:
                # the fused in-kernel CDF walk is the affine shift of
                # _cdf_search by the window start (see ops/pallas/fused.py
                # for the probe-parity proof); u arrives pre-scaled by the
                # tot exchange, so scale_u=False. The take-all override
                # (local deg equals global deg) runs in-kernel.
                start = jnp.clip(
                    base, 0, E_local - DEFAULT_WINDOW).astype(jnp.int32)
                off0 = (base - start.astype(base.dtype)).astype(jnp.int32)
                nbr, _ = fused_weighted_hop(
                    local_indices.astype(jnp.int32), local_cum_weights,
                    start, off0, deg, u, search_iters, scale_u=False,
                    window=DEFAULT_WINDOW)
            else:
                off = _cdf_search(
                    local_cum_weights, u, base, deg, search_iters)
                i = jnp.arange(k, dtype=jnp.int32)[None, :]
                degc = deg[:, None]
                # the replicated kernel's take-all override
                # (weighted_offsets): local deg equals global deg, so
                # this matches exactly
                off = jnp.where(
                    degc <= k, jnp.minimum(i, jnp.maximum(degc - 1, 0)), off
                )
                epos = base[:, None] + off.astype(base.dtype)
                nbr = local_indices[jnp.clip(epos, 0, E_local - 1)]
            return jnp.where(mine[:, None], nbr, 0).astype(jnp.int32)

        deg = route.exchange(serve_deg)
        tot = route.exchange(serve_tot)
        tot = jnp.where(deg > 0, tot, 1.0)
        u = jax.random.uniform(
            key, (S, k), dtype=local_cum_weights.dtype
        ) * tot[:, None]
        nbr = route.exchange(serve_wnbr, payload=u)
        i = jnp.arange(k, dtype=jnp.int32)[None, :]
        mask = valid[:, None] & (i < jnp.minimum(deg[:, None], k))
    elif time_window is not None:
        # temporal hop: the owner answers each routed row's in-window slot
        # range (first, deg_t) — both int32, so they ride ONE exchange —
        # and the requester draws the replicated scheme over the masked
        # degrees, rebasing offsets by `first` before the neighbor hop.
        lo_t, hi_t = time_window

        def serve_window(ids):
            mine, r = _mine_local(ids)
            base, deg = _local_row(r)
            first, deg_t = temporal_window_counts(
                local_edge_time, base, deg, lo_t, hi_t, search_iters
            )
            out = jnp.stack([first, deg_t], axis=-1)
            return jnp.where(mine[:, None], out, 0)

        win = route.exchange(serve_window)
        first, deg = win[:, 0], win[:, 1]
        kj, kr = jax.random.split(key)
        off_nr, mask_sel = stratified_offsets(kj, deg, k)
        off = rotate_offsets(kr, off_nr, deg, k)
        mask = valid[:, None] & mask_sel
        nbr = route.exchange(serve_nbr, payload=first[:, None] + off)
    else:
        # hop pair 1: ids out, degrees back — the requester needs deg to
        # draw the same offsets the replicated kernel would
        deg = route.exchange(serve_deg)
        # identical draw scheme/key discipline as ops.sample.sample_layer
        kj, kr = jax.random.split(key)
        off_nr, mask_sel = stratified_offsets(kj, deg, k)
        off = rotate_offsets(kr, off_nr, deg, k)
        mask = valid[:, None] & mask_sel
        # hop pair 2: offsets out (same buckets, ids not re-sent),
        # neighbor blocks back
        nbr = route.exchange(serve_nbr, payload=off)
    nbr = jnp.where(mask, nbr, -1).astype(jnp.int32)
    counts = jnp.where(valid, jnp.minimum(deg, k), 0)
    return nbr, counts, route.overflow


def dist_multilayer_sample(local_indptr, local_indices, rows_per_shard: int,
                           seeds, num_seeds, key, sizes, caps, *, axis: str,
                           num_shards: int, routed_alpha: float | None = 2.0,
                           weighted: bool = False, local_cum_weights=None,
                           time_window=None, local_edge_time=None,
                           search_iters: int = 0, kernel: str = "xla"):
    """Multi-layer distributed sample+reindex loop (per-device body).

    The sharded-topology twin of ``sampling.sampler.multilayer_sample`` —
    the reindex/Adj assembly is byte-for-byte the same discipline; only the
    per-hop neighbor lookup is owner-routed. Returns the same tuple plus a
    trailing ``hop_overflows``: per-hop fallback-served lane counts
    (axis-group totals, seeds-outward order) — the ``last_sample_overflow``
    telemetry source.
    """
    adjs = []
    edge_counts = []
    frontier_counts = []
    hop_overflows = []
    cur, cur_n = seeds, num_seeds
    total_overflow = jnp.zeros((), jnp.int32)
    for l, k in enumerate(sizes):
        S = cur.shape[0]
        cap = routed_sample_cap(S, num_shards, routed_alpha)
        with trace_scope(f"dist_sample_layer_{l}"):
            key, sub = jax.random.split(key)
            nbr, counts, hop_ov = dist_sample_layer(
                local_indptr, local_indices, rows_per_shard, cur, cur_n, k,
                sub, axis=axis, num_shards=num_shards, cap=cap,
                weighted=weighted, local_cum_weights=local_cum_weights,
                time_window=time_window, local_edge_time=local_edge_time,
                search_iters=search_iters, kernel=kernel,
            )
        hop_overflows.append(hop_ov)
        with trace_scope(f"reindex_layer_{l}"):
            frontier, n_frontier, col, overflow = reindex_layer(
                cur, cur_n, nbr, caps[l]
            )
            with trace_scope("assemble"):
                row = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[:, None], (S, k))
                row = jnp.where(col >= 0, row, -1)
                edge_index = jnp.stack([col.reshape(-1), row.reshape(-1)])
                del counts
                edge_counts.append(jnp.sum((col >= 0).astype(jnp.int32)))
                frontier_counts.append(n_frontier + overflow)
                total_overflow = total_overflow + overflow
        adjs.append(Adj(edge_index, None, (caps[l], S), fanout=k))
        cur, cur_n = frontier, n_frontier
    return (cur, cur_n, adjs[::-1], total_overflow,
            tuple(edge_counts[::-1]), tuple(frontier_counts[::-1]),
            tuple(hop_overflows))


class DistGraphSageSampler(GraphSageSampler):
    """K-hop sampler over a mesh-sharded topology.

    Constructed directly or via ``GraphSageSampler(...,
    topo_sharding="mesh", mesh=mesh)``. Every device of the mesh is a full
    sampling worker over its own seed block (the ``seed_sharding="all"``
    discipline); per-hop neighbor lookups route frontier vertices to the
    shard owning their CSR row (see the module docstring for the comm
    model and the bit-parity contract).

    Supports the replicated sampler's ``weighted=True`` (the shards carry
    row-local prefix-weight slices and the owner answers inverse-CDF
    draws — see ``dist_sample_layer``) and ``time_window`` (owner-answered
    in-window slot ranges) biased draws, each bit-identical to its
    replicated counterpart. The ``kernel`` knob matches the replicated
    sampler too: with "pallas" the owner-side gathers and weighted CDF walks run on the fused Pallas
    engine — bits on the wire unchanged — degrading per compile to xla
    (one INFO) when a shard's slice cannot host the window DMA.
    Constraints vs the replicated sampler: HBM mode and no ``with_eid``
    (that path stays on the replicated ``GraphSageSampler``; the sharded
    CSR slices do not carry eid).
    ``routed_alpha`` is the shared capped-bucket routing budget —
    ``cap = ceil(alpha * L / F)`` lanes per destination per hop; ``None``
    = uncapped full-length buckets. The ``DistributedTrainer`` drives this
    sampler and the sharded feature store with ONE alpha (one budget, one
    tuner).

    After an eager :meth:`sample`, ``last_sample_overflow`` holds the
    per-hop fallback-served lane counts (int32 ``(num_layers,)`` device
    vector, seeds-outward) — same telemetry discipline as
    ``last_routed_overflow``.
    """

    def __init__(
        self,
        csr_topo: CSRTopo,
        sizes,
        device=None,
        mode: str | SampleMode = SampleMode.HBM,
        seed_capacity: int | None = None,
        frontier_caps=None,
        seed: int = 0,
        weighted: bool = False,
        time_window=None,
        auto_margin: float = 1.25,
        kernel: str = "auto",
        with_eid: bool = False,
        dedup: str = "auto",
        device_topo=None,
        topo_sharding: str = "mesh",
        mesh=None,
        routed_alpha: float | None = 2.0,
        axis: str = FEATURE_AXIS,
    ):
        if topo_sharding != "mesh":
            raise ValueError(
                f"DistGraphSageSampler is the topo_sharding='mesh' sampler; "
                f"got topo_sharding={topo_sharding!r}"
            )
        if mesh is None:
            raise ValueError("topo_sharding='mesh' requires mesh=")
        if with_eid:
            raise ValueError(
                "with_eid over a sharded topology is not supported; the "
                "sharded CSR slices do not carry eid — use the replicated "
                "GraphSageSampler"
            )
        if getattr(csr_topo, "edge_relation", None) is not None:
            raise ValueError(
                "edge relations over a sharded topology are not supported; "
                "the sharded CSR slices do not carry them — use the "
                "replicated GraphSageSampler"
            )
        if SampleMode.parse(mode) is not SampleMode.HBM:
            raise ValueError(
                "topo_sharding='mesh' requires mode='HBM': each shard's CSR "
                "slice is device-resident (that is the point — per-chip "
                "bytes shrink 1/F instead of staging through host)"
            )
        if device_topo is not None:
            raise ValueError(
                "device_topo cannot be combined with topo_sharding='mesh'"
            )
        if routed_alpha is not None and routed_alpha <= 0:
            raise ValueError(
                f"routed_alpha must be > 0 or None, got {routed_alpha}"
            )
        self.mesh = mesh
        self.axis = axis
        self.routed_alpha = (
            None if routed_alpha is None else float(routed_alpha)
        )
        # graftscope registry: per-hop fallback-served lane counts of the
        # last eager sample land here (``last_sample_overflow`` is a thin
        # view; int32 (num_layers,) device vector, seeds-outward; None
        # before any)
        self.metrics = MetricsRegistry()
        self.metrics.counter(
            SAMPLE_OVERFLOW, shape=(len(tuple(sizes)),), unit="lanes",
            doc="per-hop fallback-served lanes of the last distributed "
                "sample (seeds-outward)",
        )
        super().__init__(
            csr_topo, sizes, device=device, mode=mode,
            seed_capacity=seed_capacity, frontier_caps=frontier_caps,
            seed=seed, weighted=weighted, time_window=time_window,
            auto_margin=auto_margin, kernel=kernel, with_eid=with_eid,
            dedup=dedup,
        )
        self.topo_sharding = "mesh"

    @property
    def last_sample_overflow(self):
        """Per-hop fallback-served lane counts of the last eager sample
        (thin view of the ``sample.hop_overflow`` registry metric — new
        consumers should read ``self.metrics``)."""
        return self.metrics.value(SAMPLE_OVERFLOW)

    @last_sample_overflow.setter
    def last_sample_overflow(self, value):
        self.metrics.set(SAMPLE_OVERFLOW, value)

    # -- topology placement (overrides the replicated upload) ---------------

    def _init_topo(self, device_topo):
        return ShardedTopology(
            self.mesh, self.csr_topo, axis=self.axis,
            with_weights=self.weighted,
            with_times=self.time_window is not None,
        )

    def _topo_operands(self) -> tuple:
        """Per-shard topology arrays, in the order the compiled body
        expects them: indptr, indices, then whichever edge attributes this
        sampler's draw needs (all ``(F, ...)`` with ``P(axis, None)``)."""
        ops = [self.topo.indptr, self.topo.indices]
        if self.weighted:
            ops.append(self.topo.cum_weights)
        if self.time_window is not None:
            ops.append(self.topo.edge_time)
        return tuple(ops)

    def replan(self, mesh) -> "DistGraphSageSampler":
        """Re-partition the topology onto a different mesh (elastic
        resume) and drop the compiled-program cache (programs bake in the
        old mesh). Sampling parameters, the PRNG stream, and the
        bit-parity contract are untouched: per seed block and key, the
        re-planned sampler draws exactly what the old one would — only
        the owner routing changes shape."""
        self.mesh = mesh
        self.topo = self.topo.replan(mesh, axis=self.axis)
        self._compiled_cache.clear()
        return self

    @property
    def workers(self) -> int:
        """Seed-block workers: every device of the mesh."""
        w = 1
        for a in self.mesh.axis_names:
            w *= self.mesh.shape[a]
        return w

    # -- compiled program ---------------------------------------------------

    def _compiled(self, seed_cap: int):
        caps = self._caps_for(seed_cap)
        cache_key = (seed_cap, caps, self.routed_alpha)
        if cache_key in self._compiled_cache:
            return self._compiled_cache[cache_key]
        mesh, axis = self.mesh, self.axis
        F = mesh.shape[axis]
        sizes = self.sizes
        alpha = self.routed_alpha
        rps = self.topo.rows_per_shard
        ids_axes = tuple(mesh.axis_names)
        other_axes = tuple(a for a in mesh.axis_names if a != axis)
        n_layers = len(sizes)
        weighted = self.weighted
        time_window = self.time_window
        iters = self.topo.search_iters
        n_topo = len(self._topo_operands())
        kernel = self.kernel
        if kernel == "pallas":
            from ..ops.pallas.fused import DEFAULT_WINDOW, MIN_EDGES

            # compile-time eligibility for the fused owner-side kernel:
            # every shard's slice must host a full DMA window in int32
            # range, and every row (global max_degree — offsets route to
            # whichever shard owns the row) must fit one window
            E_local = int(self.topo.indices.shape[1])
            md = int(self.csr_topo.max_degree)
            bad = None
            if E_local < MIN_EDGES:
                bad = (f"per-shard edge slices hold {E_local} edges, fewer "
                       f"than the {MIN_EDGES}-edge DMA window")
            elif E_local > np.iinfo(np.int32).max:
                bad = f"per-shard edge slices exceed int32 range ({E_local})"
            elif md > DEFAULT_WINDOW:
                bad = (f"max_degree {md} exceeds the {DEFAULT_WINDOW}-slot "
                       f"window (owner-side rows must fit one window)")
            elif any(kf > DEFAULT_WINDOW for kf in sizes):
                bad = (f"a fanout in {sizes} exceeds the "
                       f"{DEFAULT_WINDOW}-slot window")
            if bad is not None:
                info_once(
                    "dist-sample-pallas-degrade",
                    "kernel='pallas' over the sharded topology falls back "
                    "to the XLA path: %s", bad,
                )
                kernel = "xla"

        def body(*args):
            # args: indptr, indices, [cum_weights], [edge_time], seeds, key
            # — the per-shard (1, ...) blocks of self._topo_operands()
            topo_blks, (seeds, key) = args[:n_topo], args[n_topo:]
            extra = list(topo_blks[2:])
            cum_blk = extra.pop(0)[0] if weighted else None
            time_blk = extra.pop(0)[0] if time_window is not None else None
            key = jax.random.fold_in(key, _worker_index(mesh))
            num_seeds = jnp.sum((seeds >= 0).astype(jnp.int32))
            (n_id, n_count, adjs, overflow, e_cnts, f_cnts,
             hop_ovs) = dist_multilayer_sample(
                topo_blks[0][0], topo_blks[1][0], rps, seeds, num_seeds, key,
                sizes, caps, axis=axis, num_shards=F, routed_alpha=alpha,
                weighted=weighted, local_cum_weights=cum_blk,
                time_window=time_window, local_edge_time=time_blk,
                search_iters=iters, kernel=kernel,
            )
            eis = tuple(a.edge_index for a in adjs)
            # per-worker scalar row: [n_count, frontier_overflow,
            # edge_counts (deepest-first), frontier_counts (deepest-first)]
            scal = jnp.stack(
                [n_count, overflow] + list(e_cnts) + list(f_cnts)
            ).astype(jnp.int32)
            hop_ov = jnp.stack(hop_ovs)  # (L,) axis-group totals
            if other_axes:  # replicate the mesh-wide totals
                hop_ov = jax.lax.psum(hop_ov, other_axes)
            return n_id, eis, scal, hop_ov

        run = jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    (P(axis, None),) * n_topo + (P(ids_axes), P())
                ),
                out_specs=(
                    P(ids_axes),
                    tuple(P(None, ids_axes) for _ in range(n_layers)),
                    P(ids_axes),
                    P(),
                ),
                check_vma=False,
            )
        )
        self._compiled_cache[cache_key] = (run, caps)
        return run, caps

    # -- public API ---------------------------------------------------------

    def shard_seeds(self, seeds, local_cap: int) -> np.ndarray:
        """Split a global seed array into per-worker valid-prefix blocks,
        padded to (workers, local_cap) with -1 (same packing as the
        seed_sharding="all" trainer)."""
        seeds = np.asarray(seeds)
        blocks = np.array_split(seeds, self.workers)
        out = np.full((self.workers, local_cap), -1, np.int32)
        for i, b in enumerate(blocks):
            if len(b) > local_cap:
                raise ValueError(
                    f"per-worker block {len(b)} exceeds capacity {local_cap}"
                )
            out[i, : len(b)] = b
        return out

    def sample(self, input_nodes, key=None) -> SampleOutput:
        """Sample k-hop neighborhoods of a GLOBAL seed batch, split across
        every device of the mesh.

        Returns one worker-major global ``SampleOutput``: ``n_id`` is
        ``(workers * frontier_cap,)`` (each worker's block bit-identical
        to the replicated sampler's on that worker's seed block — see
        :meth:`sample_per_worker`), each ``adjs[l].edge_index`` is
        ``(2, workers * E_l)`` with per-worker ``Adj.size``/``fanout``,
        ``batch_size`` is the per-worker padded block width, ``n_count``/
        ``overflow``/``edge_counts`` are mesh totals and
        ``frontier_counts`` per-layer worker maxima. ``key`` overrides the
        sampler's own PRNG stream (each worker folds in its flat worker
        index on top).
        """
        self.check_topo_version()
        seeds = np.asarray(input_nodes)
        batch = int(seeds.shape[0])
        if batch and (seeds.min() < 0
                      or seeds.max() >= self.csr_topo.node_count):
            raise ValueError(
                f"seed ids must be in [0, {self.csr_topo.node_count}); "
                f"got range [{seeds.min()}, {seeds.max()}]"
            )
        W = self.workers
        per_worker = -(-batch // W) if batch else 1
        cap = self._seed_capacity or max(_round_up(per_worker, 128), 128)
        packed = self.shard_seeds(seeds, cap)
        if key is None:
            self._call += 1
            key = jax.random.fold_in(self._key, self._call)
        dev_seeds = jax.device_put(
            jnp.asarray(packed.reshape(-1)),
            NamedSharding(self.mesh, P(tuple(self.mesh.axis_names))),
        )
        run, used_caps = self._compiled(cap)
        n_id, eis, scal, hop_ov = run(
            *self._topo_operands(), dev_seeds, key
        )
        if self._auto_caps:
            n_layers = len(self.sizes)
            first_plan = self._frontier_caps is None
            for _ in range(n_layers + 2):
                sc = np.asarray(scal).reshape(W, 2 + 2 * n_layers)
                overflow = int(sc[:, 1].sum())
                if not first_plan and overflow == 0:
                    break
                # per-layer unclipped uniques, seeds-outward, worker max —
                # caps must cover the worst worker (one uniform program)
                observed = sc[:, 2 + n_layers:][:, ::-1].max(axis=0)
                before = self._frontier_caps
                self._plan_auto(cap, [int(o) for o in observed])
                if self._frontier_caps != before:
                    from ..utils.trace import get_logger

                    get_logger().info(
                        "dist auto caps %s: %s -> %s (recompile)",
                        "planned" if before is None else "regrown",
                        before, self._frontier_caps,
                    )
                if not first_plan and self._frontier_caps == before:
                    break  # saturated: clipped result + overflow stand
                if first_plan and overflow == 0:
                    first_plan = False
                    break
                run, used_caps = self._compiled(cap)
                n_id, eis, scal, hop_ov = run(
                    *self._topo_operands(), dev_seeds, key
                )
                first_plan = False
        self.last_sample_overflow = hop_ov
        return self._assemble(n_id, eis, scal, cap, used_caps, batch)

    def _assemble(self, n_id, eis, scal, seed_cap, caps, batch):
        W = self.workers
        n_layers = len(self.sizes)
        sc = np.asarray(scal).reshape(W, 2 + 2 * n_layers)
        # adjs deepest-first; per-layer frontier widths seeds-outward are
        # (seed_cap, caps[0], ..., caps[-2])
        widths = (seed_cap,) + tuple(caps[:-1])
        adjs = [
            Adj(ei, None, (caps[l], widths[l]), fanout=self.sizes[l])
            for l, ei in zip(range(n_layers - 1, -1, -1), eis)
        ]
        e_cnts = tuple(int(c) for c in sc[:, 2:2 + n_layers].sum(axis=0))
        f_cnts = tuple(int(c) for c in sc[:, 2 + n_layers:].max(axis=0))
        return SampleOutput(
            n_id, seed_cap, adjs,
            jnp.int32(int(sc[:, 0].sum())), jnp.int32(int(sc[:, 1].sum())),
            e_cnts, f_cnts,
        )

    def sample_per_worker(self, input_nodes, key=None) -> list[SampleOutput]:
        """:meth:`sample`, sliced into per-worker ``SampleOutput``s — each
        bit-comparable to the replicated ``GraphSageSampler``'s output on
        that worker's seed block with key
        ``fold_in(base_key, worker_index)``."""
        seeds = np.asarray(input_nodes)
        out = self.sample(seeds, key=key)
        W = self.workers
        n_layers = len(self.sizes)
        cap_last = out.n_id.shape[0] // W
        n_id = np.asarray(out.n_id).reshape(W, cap_last)
        blocks = np.array_split(seeds, W)
        per = []
        for w in range(W):
            adjs_w = []
            for a in out.adjs:
                E_l = a.edge_index.shape[1] // W
                ei = jnp.asarray(
                    np.asarray(a.edge_index).reshape(2, W, E_l)[:, w]
                )
                adjs_w.append(Adj(ei, None, a.size, fanout=a.fanout))
            per.append(SampleOutput(
                jnp.asarray(n_id[w]), len(blocks[w]), adjs_w,
                jnp.int32(0), jnp.int32(0), (), (),
            ))
        return per
