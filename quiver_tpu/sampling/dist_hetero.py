"""Distributed heterogeneous neighbor sampling over a mesh-sharded topology.

The scale-out counterpart of ``HeteroGraphSampler``: every relation's CSR
lives as a row-range partition
(:class:`~quiver_tpu.core.hetero_sharded.HeteroShardedTopology`) and every
device is a full sampling worker over its own seed block. Each hop runs
inside ``shard_map`` and reuses the homogeneous owner-routed hop
(``sampling.dist.dist_sample_layer``) per relation, with ONE twist that
makes the typed case cheap: all relations into the same destination type
share that type's row ranges, so they share ONE ``BucketRoute`` plan per
hop — the plan's id lanes are sent once and cached; every subsequent
relation's degree/offset/neighbor exchanges ride the same buckets.

Comm model per hop (S_t = per-device frontier width of dst type t, F =
shards, ``cap_t = ceil(alpha * S_t / F)``): the shared plan moves
``F*cap_t`` id lanes ONCE per (hop, dst type); each uniform relation then
adds ``F*cap_t`` (degrees back) + ``F*cap_t*k`` (offsets out) +
``F*cap_t*k`` (neighbors back) lanes, and each weighted relation adds one
more ``F*cap_t`` f32 hop (row weight totals back; its offsets-out hop
carries the f32 uniform block instead of int32 offsets).

Bit-parity contract: for the same seed block, fanouts and caps,
every per-worker output is bit-identical to the replicated
``HeteroGraphSampler``'s on that block with key ``fold_in(base_key,
worker_index)`` — the per-relation key schedule (one split per active
relation, plan order) and the per-type dedup are byte-for-byte the
replicated loop's; only the neighbor lookup is owner-routed.

Routed-bucket overflow is served exactly via the cond-gated psum fallback
and surfaced per (hop, edge type) on the graftscope registry
(``HETERO_SAMPLE_OVERFLOW``); relations sharing a destination type share
that hop's route plan, so they report the plan's overflow equally.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.config import SampleMode
from ..core.hetero import HeteroCSRTopo
from ..core.hetero_sharded import HeteroShardedTopology
from ..obs.registry import HETERO_SAMPLE_OVERFLOW, MetricsRegistry
from ..ops.reindex import masked_unique
from ..parallel.mesh import FEATURE_AXIS, shard_map
from ..parallel.routing import BucketRoute
from ..utils.trace import info_once, trace_scope
from .dist import _worker_index, dist_sample_layer, routed_sample_cap
from .hetero import HeteroGraphSampler, HeteroLayer, HeteroSampleOutput
from .sampler import Adj, _round_up, settle_sample_kernel

__all__ = ["DistHeteroSampler", "dist_hetero_multilayer_sample"]


def dist_hetero_multilayer_sample(rel_blocks, seeds, num_seeds, key,
                                  input_type, layer_plans, *, axis: str,
                                  num_shards: int, rows_per_shard: dict,
                                  routed_alpha: float | None = 2.0,
                                  weighted_rels=frozenset(),
                                  search_iters=None,
                                  pallas_rels=frozenset()):
    """The per-device distributed hetero loop (call inside ``shard_map``).

    Args:
      rel_blocks: {edge_type: (local_indptr, local_indices,
        local_cum_weights | None)} — this shard's rebased CSR blocks per
        relation (``HeteroShardedTopology`` layout).
      layer_plans: the STATIC per-hop plans of ``HeteroGraphSampler._plan``
        — sharing the replicated planner is part of the parity contract
        (same active sets, same caps, same key schedule).
      rows_per_shard: {node_type: rows per shard} owner geometry.
      search_iters: {edge_type: static binary-search bound} for weighted
        relations (from each relation's GLOBAL max degree).
      pallas_rels: relations whose owner-side hop runs on the fused
        Pallas engine (``dist_sample_layer`` ``kernel="pallas"``; bits on
        the wire unchanged). ``DistHeteroSampler._compiled`` gates each
        relation on slice size / max degree / fanout vs the DMA window.

    Returns ``(frontier, counts, ei_layers, overflow, frontier_counts,
    hop_overflows)`` where ``ei_layers`` is deepest-first, each hop a tuple
    of ``(2, S*k)`` edge_index arrays in sorted-relation order, and
    ``hop_overflows`` is seeds-outward, each hop a tuple of the shared
    route plan's fallback-served lane count per active relation (sorted
    order — the ``HETERO_SAMPLE_OVERFLOW`` slot layout).
    """
    search_iters = search_iters or {}
    frontier = {input_type: seeds}
    counts = {input_type: num_seeds}
    ei_layers = []
    frontier_counts = []
    hop_overflows = []
    overflow = jnp.zeros((), jnp.int32)

    for li, (rel_fanouts, caps_prev, caps_next) in enumerate(layer_plans):
        # 1) sample every active relation through ONE shared route per
        #    destination type; key schedule mirrors the replicated loop
        #    exactly (one split per relation, plan order)
        routes = {}
        samples = {}
        for et, k in rel_fanouts.items():
            _, _, d = et
            key, sub = jax.random.split(key)
            if d not in routes:
                S_d = frontier[d].shape[0]
                valid = (jnp.arange(S_d) < counts[d]) & (frontier[d] >= 0)
                s = jnp.where(valid, frontier[d], 0)
                routes[d] = BucketRoute(
                    s, valid, s // rows_per_shard[d], axis=axis,
                    num_shards=num_shards,
                    cap=routed_sample_cap(S_d, num_shards, routed_alpha),
                )
            ip, ix, cw = rel_blocks[et]
            with trace_scope(f"dist_hetero_layer_{li}"):
                nbr, _, _ = dist_sample_layer(
                    ip, ix, rows_per_shard[d], frontier[d], counts[d], k,
                    sub, axis=axis, num_shards=num_shards, cap=None,
                    weighted=et in weighted_rels, local_cum_weights=cw,
                    search_iters=search_iters.get(et, 0), route=routes[d],
                    kernel="pallas" if et in pallas_rels else "xla",
                )
            samples[et] = nbr
        hop_overflows.append(tuple(
            routes[et[2]].overflow for et in sorted(rel_fanouts, key=str)
        ))

        # 2) per-type dedup — byte-for-byte the replicated discipline
        #    (sampling.hetero.hetero_multilayer_sample): previous frontier
        #    forced first, then each relation's flat samples in sorted
        #    relation order
        new_frontier, new_counts, locals_per_rel = {}, {}, {}
        layer_uniques = {}
        for t, cap in caps_next.items():
            blocks, valids, spans = [], [], {}
            prev = frontier.get(t)
            n_prev = 0
            if prev is not None:
                n_prev = prev.shape[0]
                blocks.append(prev)
                valids.append(
                    (jnp.arange(n_prev) < counts[t]) & (prev >= 0)
                )
            for et in sorted(samples, key=str):
                if et[0] != t:
                    continue
                flat = samples[et].reshape(-1)
                spans[et] = (sum(b.shape[0] for b in blocks),
                             flat.shape[0])
                blocks.append(flat)
                valids.append(flat >= 0)
            ids = jnp.concatenate(blocks)
            valid = jnp.concatenate(valids)
            uniq, num_u, local = masked_unique(
                ids, valid, cap, num_forced=n_prev
            )
            new_frontier[t] = uniq
            new_counts[t] = jnp.minimum(num_u, cap)
            layer_uniques[t] = num_u
            overflow = overflow + jnp.maximum(num_u - cap, 0)
            for et, (off, ln) in spans.items():
                locals_per_rel[et] = local[off:off + ln]

        # 3) one padded edge_index per relation (col = new src-frontier
        #    local id, row = dst row position), sorted-relation order
        eis = []
        for et in sorted(rel_fanouts, key=str):
            k = rel_fanouts[et]
            d_t = et[2]
            S = frontier[d_t].shape[0]
            col = locals_per_rel[et].reshape(S, k)
            row = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[:, None], (S, k)
            )
            row = jnp.where(col >= 0, row, -1)
            eis.append(jnp.stack([col.reshape(-1), row.reshape(-1)]))
        ei_layers.append(tuple(eis))
        frontier_counts.append(layer_uniques)

        frontier, counts = new_frontier, new_counts

    return (frontier, counts, tuple(ei_layers[::-1]), overflow,
            tuple(frontier_counts), tuple(hop_overflows))


class DistHeteroSampler(HeteroGraphSampler):
    """K-hop typed sampler over a mesh-sharded heterogeneous topology.

    The typed member of the distributed sampler family
    (``DistGraphSageSampler`` is the homogeneous one): per-relation CSR
    partitions (~1/F topology bytes per chip), owner-routed hops through
    one shared ``BucketRoute`` plan per (hop, destination type), and the
    ``seed_sharding="all"`` worker discipline — every device samples its
    own seed block with key ``fold_in(key, worker_index)``, bit-identical
    to the replicated ``HeteroGraphSampler`` on that block (see the
    module docstring for the comm model and parity contract).

    Extra args over the replicated sampler: ``mesh`` (required), the
    ``routed_alpha`` capped-bucket budget (``cap = ceil(alpha * S / F)``
    lanes per destination per hop; ``None`` = uncapped), ``axis`` (the
    mesh axis the partitions live on), and ``kernel`` ("xla", which "auto"
    means, or "pallas" — with pallas, eligible relations' owner-side hops
    run on the fused Pallas engine, per-relation compile-time gating
    with one INFO per degrade; bits on the wire unchanged). Constraints:
    HBM mode and no ``with_eid`` (the sharded relation slices do not
    carry eid — that path stays on the replicated sampler).

    After an eager :meth:`sample`, ``last_sample_overflow`` holds the
    fallback-served lane count per (hop, edge type) — an int32
    ``(num_slots,)`` device vector in :attr:`overflow_slots` order,
    registered on the graftscope registry as ``HETERO_SAMPLE_OVERFLOW``.
    """

    def __init__(self, topo: HeteroCSRTopo, sizes, input_type: str,
                 mode: str | SampleMode = SampleMode.HBM,
                 seed_capacity: int | None = None,
                 frontier_caps: str | None = None, seed: int = 0,
                 auto_margin: float = 1.25, weighted=False,
                 with_eid: bool = False, *,
                 mesh=None, routed_alpha: float | None = 2.0,
                 axis: str = FEATURE_AXIS, kernel: str = "auto"):
        if mesh is None:
            raise ValueError("DistHeteroSampler requires mesh=")
        self.kernel = settle_sample_kernel(str(kernel))
        if with_eid:
            raise ValueError(
                "with_eid over a sharded topology is not supported; the "
                "sharded relation slices do not carry eid — use the "
                "replicated HeteroGraphSampler"
            )
        if SampleMode.parse(mode) is not SampleMode.HBM:
            raise ValueError(
                "DistHeteroSampler requires mode='HBM': each shard's "
                "relation slice is device-resident (that is the point — "
                "per-chip bytes shrink 1/F instead of staging through host)"
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
        super().__init__(
            topo, sizes, input_type, mode=mode,
            seed_capacity=seed_capacity, frontier_caps=frontier_caps,
            seed=seed, auto_margin=auto_margin, weighted=weighted,
            with_eid=with_eid,
        )
        # static (hop, edge_type) telemetry slot order — the active sets
        # depend only on schema reachability, never on cap values, so any
        # seed capacity plans the same slots
        self._overflow_slots = tuple(
            (li, et)
            for li, (active, _, _) in enumerate(self._plan(128))
            for et in sorted(active, key=str)
        )
        # graftscope registry: fallback-served lane counts per (hop, edge
        # type) of the last eager sample (``last_sample_overflow`` is a
        # thin view; None before any sample)
        self.metrics = MetricsRegistry()
        self.metrics.counter(
            HETERO_SAMPLE_OVERFLOW, shape=(len(self._overflow_slots),),
            unit="lanes",
            doc="fallback-served lanes per (hop, edge type) of the last "
                "distributed hetero sample (overflow_slots order)",
        )

    # -- topology placement (overrides the replicated upload) ---------------

    def _init_topo(self):
        return HeteroShardedTopology(
            self.mesh, self.topo, axis=self.axis,
            weighted_rels=self.weighted_rels,
        )

    @property
    def overflow_slots(self) -> tuple:
        """Static ``(hop, edge_type)`` order of the overflow vector."""
        return self._overflow_slots

    @property
    def last_sample_overflow(self):
        """Fallback-served lane counts of the last eager sample — int32
        ``(num_slots,)`` device vector in :attr:`overflow_slots` order
        (thin view of the ``HETERO_SAMPLE_OVERFLOW`` registry metric)."""
        return self.metrics.value(HETERO_SAMPLE_OVERFLOW)

    @property
    def last_sample_overflow_by_rel(self) -> dict | None:
        """``{(hop, edge_type): lanes}`` view of the last sample's
        overflow vector (host ints; None before any sample)."""
        v = self.metrics.value(HETERO_SAMPLE_OVERFLOW)
        if v is None:
            return None
        flat = np.asarray(v)
        return {
            slot: int(flat[i]) for i, slot in enumerate(self._overflow_slots)
        }

    @property
    def workers(self) -> int:
        """Seed-block workers: every device of the mesh."""
        w = 1
        for a in self.mesh.axis_names:
            w *= self.mesh.shape[a]
        return w

    def _topo_operands(self) -> tuple:
        """Per-shard relation arrays in the order the compiled body
        expects: for each relation (sorted), indptr, indices, then the
        prefix-weight slice if the relation draws weighted (all
        ``(F, ...)`` with ``P(axis, None)``)."""
        ops = []
        for et in sorted(self.dev_topos.rels, key=str):
            rel = self.dev_topos.rels[et]
            ops.append(rel.indptr)
            ops.append(rel.indices)
            if et in self.weighted_rels:
                ops.append(rel.cum_weights)
        return tuple(ops)

    def _scal_layout(self, plans):
        """Static layout of the per-worker scalar row: [frontier_overflow,
        final counts per type (sorted), per-hop unclipped uniques per type
        (hop-major, sorted within each hop)]."""
        out_types = tuple(sorted(plans[-1][2]))
        fc_slots = tuple(
            (li, t) for li, (_, _, caps_next) in enumerate(plans)
            for t in sorted(caps_next)
        )
        return out_types, fc_slots

    # -- compiled program ---------------------------------------------------

    def _compiled(self, seed_cap: int):
        ov = self._cap_overrides
        cache_key = (
            seed_cap,
            None if ov is None
            else tuple(tuple(sorted(layer.items())) for layer in ov),
        )
        if cache_key in self._compiled_cache:
            return self._compiled_cache[cache_key]
        plans = self._plan(
            seed_cap, self._cap_overrides if self._auto_caps else None
        )
        mesh, axis = self.mesh, self.axis
        F = int(mesh.shape[axis])
        ids_axes = tuple(mesh.axis_names)
        other_axes = tuple(a for a in mesh.axis_names if a != axis)
        rel_keys = tuple(sorted(self.dev_topos.rels, key=str))
        weighted_rels = self.weighted_rels
        rps = dict(self.dev_topos.rows_per_shard)
        iters = {
            et: self.dev_topos.rels[et].search_iters for et in rel_keys
        }
        alpha = self.routed_alpha
        input_type = self.input_type
        n_topo = len(self._topo_operands())
        out_types, fc_slots = self._scal_layout(plans)
        pallas_rels = frozenset()
        if self.kernel == "pallas":
            from ..ops.pallas.fused import DEFAULT_WINDOW, MIN_EDGES

            # per-relation compile-time eligibility for the fused
            # owner-side kernel (same gates as the homogeneous sampler,
            # applied to each relation's slice and global max degree)
            kmax = {}
            for active, _, _ in plans:
                for et, kf in active.items():
                    kmax[et] = max(kf, kmax.get(et, 0))
            ok, degraded = set(), []
            for et in rel_keys:
                E_local = int(self.dev_topos.rels[et].indices.shape[1])
                md = int(self.topo.relations[et].max_degree)
                if (MIN_EDGES <= E_local <= np.iinfo(np.int32).max
                        and md <= DEFAULT_WINDOW
                        and kmax.get(et, 0) <= DEFAULT_WINDOW):
                    ok.add(et)
                else:
                    degraded.append(et)
            if degraded:
                info_once(
                    "dist-hetero-pallas-degrade",
                    "kernel='pallas' falls back to the XLA path for "
                    "relations %s: each needs a per-shard slice of at "
                    "least %d edges (int32 range) with max_degree and "
                    "fanout within the DMA window",
                    sorted(degraded, key=str), MIN_EDGES,
                )
            pallas_rels = frozenset(ok)

        def body(*args):
            # args: per-relation (indptr, indices, [cum_weights]) blocks in
            # sorted relation order (self._topo_operands()), seeds, key
            topo_blks, (seeds, key) = args[:n_topo], args[n_topo:]
            blk = iter(topo_blks)
            rel_blocks = {}
            for et in rel_keys:
                ip = next(blk)[0]
                ix = next(blk)[0]
                cw = next(blk)[0] if et in weighted_rels else None
                rel_blocks[et] = (ip, ix, cw)
            key = jax.random.fold_in(key, _worker_index(mesh))
            num_seeds = jnp.sum((seeds >= 0).astype(jnp.int32))
            (frontier, counts, ei_layers, overflow, fcounts,
             hop_ovs) = dist_hetero_multilayer_sample(
                rel_blocks, seeds, num_seeds, key, input_type, plans,
                axis=axis, num_shards=F, rows_per_shard=rps,
                routed_alpha=alpha, weighted_rels=weighted_rels,
                search_iters=iters, pallas_rels=pallas_rels,
            )
            # per-worker scalar row in the _scal_layout order
            scal = jnp.stack(
                [overflow]
                + [counts[t] for t in out_types]
                + [fcounts[li][t] for li, t in fc_slots]
            ).astype(jnp.int32)
            hop_ov = jnp.concatenate(
                [jnp.stack(h) for h in hop_ovs]
            )  # (num_slots,) axis-group totals, overflow_slots order
            if other_axes:  # replicate the mesh-wide totals
                hop_ov = jax.lax.psum(hop_ov, other_axes)
            return frontier, ei_layers, scal, hop_ov

        run = jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    (P(axis, None),) * n_topo + (P(ids_axes), P())
                ),
                out_specs=(
                    P(ids_axes),
                    tuple(P(None, ids_axes) for _ in plans),
                    P(ids_axes),
                    P(),
                ),
                check_vma=False,
            )
        )
        self._compiled_cache[cache_key] = (run, plans)
        return run, plans

    # -- public API ---------------------------------------------------------

    def shard_seeds(self, seeds, local_cap: int) -> np.ndarray:
        """Split a global seed array into per-worker valid-prefix blocks,
        padded to (workers, local_cap) with -1 (same packing as the
        homogeneous distributed sampler)."""
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

    def sample(self, input_nodes, key=None) -> HeteroSampleOutput:
        """Sample typed k-hop neighborhoods of a GLOBAL seed batch, split
        across every device of the mesh.

        Returns one worker-major global ``HeteroSampleOutput``: each
        ``n_id[t]`` is ``(workers * cap_t,)`` (each worker's block
        bit-identical to the replicated sampler's on that worker's seed
        block — see :meth:`sample_per_worker`), each relation's
        ``edge_index`` is ``(2, workers * S*k)`` with per-worker
        ``Adj.size``, ``batch_size`` is the per-worker padded block width,
        ``n_count``/``overflow`` are mesh totals and ``frontier_counts``
        per-layer/type worker maxima. ``key`` overrides the sampler's own
        PRNG stream (each worker folds in its flat worker index on top).
        """
        seeds = np.asarray(input_nodes)
        batch = int(seeds.shape[0])
        n = self.topo.num_nodes[self.input_type]
        if batch and (seeds.min() < 0 or seeds.max() >= n):
            raise ValueError(
                f"seed ids must be in [0, {n}); got "
                f"[{seeds.min()}, {seeds.max()}]"
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
        run, plans = self._compiled(cap)
        n_id, eis, scal, hop_ov = run(
            *self._topo_operands(), dev_seeds, key
        )
        if self._auto_caps:
            # same regrow discipline as the replicated hetero sampler, fed
            # from the worker-MAX unclipped uniques (caps must cover the
            # worst worker — one uniform program across the mesh)
            first_plan = self._cap_overrides is None
            for _ in range(len(self.sizes) + 2):
                out_types, fc_slots = self._scal_layout(plans)
                sc = np.asarray(scal).reshape(
                    W, 1 + len(out_types) + len(fc_slots)
                )
                overflow = int(sc[:, 0].sum())
                if not first_plan and overflow == 0:
                    break
                off = 1 + len(out_types)
                observed = [dict() for _ in self.sizes]
                for j, (li, t) in enumerate(fc_slots):
                    observed[li][t] = int(sc[:, off + j].max())
                before = self._cap_overrides
                self._plan_auto(observed)
                if not first_plan and self._cap_overrides == before:
                    break  # saturated: clipped result + overflow stand
                if first_plan and overflow == 0:
                    first_plan = False
                    break  # worst-case first run was exact; keep it
                run, plans = self._compiled(cap)
                n_id, eis, scal, hop_ov = run(
                    *self._topo_operands(), dev_seeds, key
                )
                first_plan = False
        self.metrics.set(HETERO_SAMPLE_OVERFLOW, hop_ov)
        return self._assemble(n_id, eis, scal, cap, plans)

    def _assemble(self, n_id, eis, scal, seed_cap, plans):
        W = self.workers
        L = len(plans)
        out_types, fc_slots = self._scal_layout(plans)
        sc = np.asarray(scal).reshape(W, 1 + len(out_types) + len(fc_slots))
        n_count = {
            t: jnp.int32(int(sc[:, 1 + i].sum()))
            for i, t in enumerate(out_types)
        }
        layers = []
        for l, layer_eis in enumerate(eis):  # deepest-first
            active, caps_prev, caps_next = plans[L - 1 - l]
            adjs = {}
            for et, ei in zip(sorted(active, key=str), layer_eis):
                s_t, _, d_t = et
                adjs[et] = Adj(
                    ei, None, (caps_next[s_t], caps_prev[d_t]),
                    fanout=active[et],
                )
            layers.append(HeteroLayer(adjs, dict(caps_next), dict(caps_prev)))
        off = 1 + len(out_types)
        observed = [dict() for _ in range(L)]
        for j, (li, t) in enumerate(fc_slots):
            observed[li][t] = int(sc[:, off + j].max())
        return HeteroSampleOutput(
            n_id, n_count, seed_cap, layers,
            jnp.int32(int(sc[:, 0].sum())), tuple(observed),
        )

    def sample_per_worker(self, input_nodes, key=None):
        """:meth:`sample`, sliced into per-worker ``HeteroSampleOutput``s
        — each bit-comparable to the replicated ``HeteroGraphSampler``'s
        output on that worker's seed block with key
        ``fold_in(base_key, worker_index)``."""
        out = self.sample(np.asarray(input_nodes), key=key)
        W = self.workers
        per = []
        for w in range(W):
            n_id_w = {
                t: jnp.asarray(np.asarray(v).reshape(W, -1)[w])
                for t, v in out.n_id.items()
            }
            layers_w = []
            for layer in out.adjs:
                adjs_w = {}
                for et, a in layer.adjs.items():
                    E_l = a.edge_index.shape[1] // W
                    ei = jnp.asarray(
                        np.asarray(a.edge_index).reshape(2, W, E_l)[:, w]
                    )
                    adjs_w[et] = Adj(ei, None, a.size, fanout=a.fanout)
                layers_w.append(HeteroLayer(
                    adjs_w, dict(layer.src_caps), dict(layer.dst_caps)
                ))
            per.append(HeteroSampleOutput(
                n_id_w, {}, out.batch_size, layers_w, jnp.int32(0), ()
            ))
        return per
