"""Multi-layer graph sampler with the PyG-compatible output contract.

Capability parity with the reference's ``quiver.pyg.GraphSageSampler``
(torch-quiver pyg/sage_sampler.py:22-133): a fanout list ``sizes``, per-layer
sample + reindex, ``Adj(edge_index, e_id, size)`` records returned deepest
layer first, and ``n_id[:batch_size] == seeds``. Differences forced by XLA
(SURVEY §7.1): all shapes are static — seeds are padded to ``seed_capacity``
and each layer's frontier to a precomputed cap — and the whole multi-layer
loop is one jitted program instead of one C++ call pair per hop
(sage_sampler.py:84-112).

No IPC/lazy-child-reinit machinery is needed (reference sage_sampler.py:71-79,
114-133): under single-controller SPMD there is exactly one process.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.config import SampleMode
from ..core.topology import CSRTopo, DeviceTopology, VersionMismatchError
from ..ops.reindex import reindex_layer
from ..ops.sample import sample_layer
from ..utils.trace import info_once, trace_scope

__all__ = ["Adj", "GraphSageSampler", "SampleOutput"]


@jax.tree_util.register_pytree_node_class
class Adj:
    """PyG-shaped adjacency record (mirrors reference Adj, sage_sampler.py:12-19).

    ``edge_index`` is (2, E_cap) with [0]=source (frontier-local neighbor id)
    and [1]=target (seed-local id); invalid edges have source == -1.
    ``size`` = (num_source_nodes_cap, num_target_nodes_cap) — static, so it
    survives jit boundaries as pytree metadata (models use it for
    ``num_segments``). Supports 3-tuple unpacking like PyG's Adj.

    ``fanout`` (static, None for hand-built Adjs): when set by the sampler
    it asserts the REGULAR edge layout — lane ``s*fanout + k`` targets seed
    ``s`` (or is invalid), so ``E_cap == size[1] * fanout``. Models use it
    to aggregate with dense (num_dst, fanout) reductions instead of
    segment scatters.

    Over a topology with edge relations the sampler also sets
    ``relation``, each lane's edge relation (int8, -1 on invalid lanes)
    **fanout-major**, ``(fanout, size[1])``: entry ``[k, s]`` is lane
    ``s*fanout + k``, the layout in which the dense aggregation gathers
    its rows (``models.layers.fanout_gather_sum``); and ``dst_count``, the
    number of valid targets (a prefix of the ``size[1]`` slots), which
    PyG's unpadded ``size`` would give. Both are None otherwise.
    """

    def __init__(self, edge_index, e_id, size: tuple[int, int],
                 fanout: int | None = None, relation=None, dst_count=None):
        self.edge_index = edge_index
        self.e_id = e_id
        self.size = tuple(size)
        self.fanout = fanout
        self.relation = relation
        self.dst_count = dst_count

    def __iter__(self):
        return iter((self.edge_index, self.e_id, self.size))

    def __repr__(self):
        return f"Adj(edge_index={self.edge_index.shape}, size={self.size})"

    def to(self, device):
        put = lambda a: None if a is None else jax.device_put(a, device)
        return Adj(
            jax.device_put(self.edge_index, device), put(self.e_id),
            self.size, self.fanout, put(self.relation), put(self.dst_count),
        )

    def tree_flatten(self):
        return ((self.edge_index, self.e_id, self.relation, self.dst_count),
                (self.size, self.fanout))

    @classmethod
    def tree_unflatten(cls, aux, children):
        edge_index, e_id, relation, dst_count = children
        return cls(edge_index, e_id, *aux, relation=relation,
                   dst_count=dst_count)


class SampleOutput(NamedTuple):
    n_id: jax.Array  # (frontier_cap,) node ids, seeds first, -1 padded
    batch_size: int
    adjs: list  # deepest layer first
    n_count: jax.Array  # scalar: valid entries in n_id
    overflow: jax.Array  # scalar: uniques dropped by frontier caps (0 = exact)
    edge_counts: tuple = ()  # per-layer valid-edge scalars, deepest first
    frontier_counts: tuple = ()  # per-layer UNCLIPPED unique counts, deepest first


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def multilayer_sample(topo, seeds, num_seeds, key, sizes, caps, weighted=False,
                      kernel="xla", with_eid=False, time_window=None):
    """The multi-layer sample+reindex loop (jit- and shard_map-composable).

    One trace covers all layers — the fused analogue of the reference's
    per-hop Python loop of C++ calls (sage_sampler.py:84-112). Shapes are
    fully static: ``sizes`` and ``caps`` are tuples of ints.

    With ``with_eid`` each Adj carries per-edge global edge ids aligned with
    its edge_index columns (-1 on invalid lanes) — the reference's per-hop
    ``e_id`` output (sage_sampler.py:100-109, reindex_single eid plumbing).
    Ids are original COO edge positions when the topology tracks ``eid``,
    raw CSR slots otherwise. A topology placed with its edge relations
    (``topo.num_relations``) gives every Adj its lanes' ``relation`` and
    its ``dst_count``.

    Returns (n_id, n_count, adjs deepest-first, overflow, per-layer edge
    counts, per-layer unclipped frontier counts).
    """
    use_pallas = kernel == "pallas"
    if use_pallas:
        from ..ops.pallas.fused import (
            DEFAULT_WINDOW,
            MIN_EDGES,
            fused_sample_layer,
        )

        # trace-time eligibility for the fused kernel; every degrade is a
        # one-shot INFO (same info_once discipline as the other silent
        # fallback paths) and lands on the bitwise-identical XLA oracle
        E = topo.edge_count
        md = getattr(topo, "max_degree", None)
        if getattr(topo, "host_indices", False):
            info_once(
                "sample-pallas-host-topo",
                "kernel='pallas' needs an HBM-resident topology; this "
                "HOST-staged placement falls back to the XLA sampler",
            )
            use_pallas = False
        elif E < MIN_EDGES:
            # the kernel DMAs a full window per row; smaller graphs would
            # read past the edge array (trace-time constant)
            info_once(
                "sample-pallas-small-graph",
                "graph has %d edges, fewer than the Pallas sampler's "
                "%d-edge DMA window; kernel='pallas' falls back to the "
                "XLA path for this topology",
                E, MIN_EDGES,
            )
            use_pallas = False
        elif E - DEFAULT_WINDOW > np.iinfo(np.int32).max:
            info_once(
                "sample-pallas-int32-range",
                "edge count %d exceeds the fused kernel's int32 "
                "window-start range; falling back to the XLA sampler", E,
            )
            use_pallas = False
        elif weighted and (md is None or md > DEFAULT_WINDOW):
            # a truncated CDF segment would RE-WEIGHT the draw, not
            # attenuate it (unlike the accepted uniform hub-row policy),
            # so the weighted path refuses windowed rows outright
            info_once(
                "sample-pallas-weighted-window",
                "the fused weighted draw needs a known max_degree <= %d "
                "to keep each row's whole CDF segment in-window (got "
                "%s); falling back to the XLA draw", DEFAULT_WINDOW, md,
            )
            use_pallas = False
    with_relation = getattr(topo, "num_relations", 0) > 0
    if with_relation and use_pallas:
        raise ValueError(
            "the fused Pallas sampler does not read edge relations; use "
            "kernel='xla'")
    adjs = []
    edge_counts = []
    frontier_counts = []
    cur, cur_n = seeds, num_seeds
    total_overflow = jnp.zeros((), jnp.int32)
    for l, k in enumerate(sizes):
        eids = relation = None
        if use_pallas and k > DEFAULT_WINDOW:
            info_once(
                "sample-pallas-fanout",
                "fanout %d exceeds the %d-slot Pallas window; this hop "
                "falls back to the XLA sampler", k, DEFAULT_WINDOW,
            )
        with trace_scope(f"sample_layer_{l}"):
            key, sub = jax.random.split(key)
            if use_pallas and k <= DEFAULT_WINDOW:
                if with_eid:
                    nbr, counts, eids = fused_sample_layer(
                        topo, cur, cur_n, k, sub, weighted=weighted,
                        time_window=time_window, with_eid=True)
                else:
                    nbr, counts = fused_sample_layer(
                        topo, cur, cur_n, k, sub, weighted=weighted,
                        time_window=time_window)
            else:
                out = sample_layer(topo, cur, cur_n, k, sub,
                                   weighted=weighted, with_eid=with_eid,
                                   time_window=time_window)
                nbr, counts = out[:2]
                if with_eid:
                    eids = out[2]
                if with_relation:
                    relation = out[-1]
        with trace_scope(f"reindex_layer_{l}"):
            frontier, n_frontier, col, overflow = reindex_layer(
                cur, cur_n, nbr, caps[l]
            )
            with trace_scope("assemble"):
                S = cur.shape[0]
                row = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[:, None], (S, k))
                row = jnp.where(col >= 0, row, -1)
                edge_index = jnp.stack([col.reshape(-1), row.reshape(-1)])
                if eids is not None:
                    # re-mask with col: neighbors dropped by frontier-cap
                    # overflow must not leak their edge ids
                    eids = jnp.where(col >= 0, eids, -1).reshape(-1)
                if relation is not None:
                    relation = jnp.where(col >= 0, relation, -1).T
                # per-layer tallies in-program: the fused step's counters,
                # benchmarks and the auto-cap planner read scalars instead
                # of reducing (2, E_cap) arrays on the host path. Tallied
                # POST-reindex (col >= 0), so overflow-dropped neighbors
                # are excluded — edge_counts[i] always equals the valid
                # edges actually present in adjs[i] (BASELINE.md honesty
                # rule)
                del counts
                edge_counts.append(jnp.sum((col >= 0).astype(jnp.int32)))
                frontier_counts.append(n_frontier + overflow)
                total_overflow = total_overflow + overflow
        adjs.append(Adj(edge_index, eids, (caps[l], S), fanout=k,
                        relation=relation,
                        dst_count=cur_n if with_relation else None))
        cur, cur_n = frontier, n_frontier
    return (cur, cur_n, adjs[::-1], total_overflow, tuple(edge_counts[::-1]),
            tuple(frontier_counts[::-1]))


def settle_sample_kernel(kernel: str) -> str:
    """The sampler kernel a constructor's ``kernel=`` names: ``"auto"`` is
    ``"xla"`` on every backend (the only sampler that is exact on rows
    above the fused kernel's 2,048-neighbour window); ``"pallas"`` is taken
    by name only. Touches no backend."""
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(f"kernel must be auto|pallas|xla, got {kernel!r}")
    return "xla" if kernel == "auto" else kernel


class GraphSageSampler:
    """K-hop neighbor sampler over a device-resident CSR topology.

    Args:
      csr_topo: host CSRTopo.
      sizes: fanouts per layer, seeds outward; -1 = full neighborhood
        (capped at the graph's max degree, reference sage_sampler.py:67).
      mode: "HBM" (reference "GPU") or "HOST" (reference "UVA").
      seed_capacity: padded batch size; defaults to first sample() call's
        batch rounded up to a multiple of 128.
      frontier_caps: per-layer unique-node capacity; defaults to
        min(worst-case growth, node_count). Pass ``"auto"`` to right-size
        caps from the first batch's observed unique counts (×``auto_margin``)
        — worst-case caps vastly overshoot on power-law graphs (SURVEY
        §7.4.2), inflating every downstream gather/aggregate; auto mode
        trades one recompile (plus a rare recompile+resample when a later
        batch overflows the planned caps) for right-sized programs.
      seed: base PRNG seed (per-call keys derive from it + a call counter,
        like the reference's per-launch curand reseed, cuda_random.cu.hpp:21-23).
      time_window: optional ``(lo, hi)`` timestamp pair — every hop draws
        only from edges with ``lo <= t <= hi`` (masked degrees; expired
        edges never appear). Requires ``csr_topo.set_edge_time()``, HBM
        mode, kernel="xla", and is mutually exclusive with ``weighted``.
      auto_margin: headroom factor for "auto" caps (>= 1).
      kernel: "xla" (exact stratified sampler; "auto", the default, means
        the same on every backend) or "pallas" (the fused per-hop
        megakernel, ops/pallas/fused.py, by name only — HBM mode; every
        variant: uniform, weighted, temporal, with_eid — bitwise equal to
        the XLA oracle for rows with deg <= window, see the kernel's
        parity contract). Ineligible topologies (graphs smaller than the
        DMA window, HOST placements, weighted graphs whose max_degree
        exceeds the window) degrade per hop to the XLA path with a
        one-shot INFO.
      with_eid: populate ``Adj.e_id`` with per-edge global edge ids
        (reference sage_sampler.py:100-109) — COO positions when the
        topology tracks ``eid``, CSR slots otherwise.
        A ``csr_topo`` with edge relations (``CSRTopo.set_edge_relation``)
        needs no flag: its relations are placed with the edges and every
        Adj carries ``relation`` and ``dst_count`` (kernel "xla" only).
      dedup: "auto" or "scan", one meaning: the reindex has one
        algorithm (ops.reindex.masked_unique). Kept because the benchmark
        passes it by name (ROADMAP D14); "sort" and "map" were removed
        and raise.
      device_topo: advanced — reuse an existing DeviceTopology (built with
        compatible to_device flags) instead of uploading a fresh copy;
        lets many sampler configurations share one device-resident graph.
      device: accepted-and-INERT parity slot (the reference pins a CUDA
        ordinal, sage_sampler.py:26; under SPMD the mesh owns placement).
      topo_sharding: ``"replicated"`` (default — every chip holds the full
        CSR) or ``"mesh"`` — the graph itself is partitioned across the
        mesh's feature axis (~1/F topology bytes per chip) and sampling
        routes each frontier vertex to its owning shard over capped-bucket
        all_to_all collectives. ``"mesh"`` construction returns a
        :class:`~quiver_tpu.sampling.dist.DistGraphSageSampler` and
        requires ``mesh=``; results are bit-identical to the replicated
        sampler per worker block.
      compiled_cache_size: LRU bound on the per-instance compiled-program
        cache (keyed on (seed_cap, caps)); evictions are counted on
        ``compiled_cache_evictions``. Auto-cap replans and the serving
        ladder both grow this cache — unbounded, every superseded program
        stays pinned.
    """

    def __new__(cls, *args, **kwargs):
        # GraphSageSampler(topo_sharding="mesh", mesh=...) constructs the
        # sharded-topology sampler — one entry point, two placements
        if (cls is GraphSageSampler
                and kwargs.get("topo_sharding", "replicated") == "mesh"):
            from .dist import DistGraphSageSampler

            return super().__new__(DistGraphSageSampler)
        return super().__new__(cls)

    def __init__(
        self,
        csr_topo: CSRTopo,
        sizes: Sequence[int],
        device=None,
        mode: str | SampleMode = SampleMode.HBM,
        seed_capacity: int | None = None,
        frontier_caps: Sequence[int] | str | None = None,
        seed: int = 0,
        weighted: bool = False,
        time_window=None,
        auto_margin: float = 1.25,
        kernel: str = "auto",
        with_eid: bool = False,
        dedup: str = "auto",
        device_topo=None,
        topo_sharding: str = "replicated",
        compiled_cache_size: int = 8,
    ):
        if topo_sharding not in ("replicated", "mesh"):
            raise ValueError(
                f"topo_sharding must be 'replicated' or 'mesh', "
                f"got {topo_sharding!r}"
            )
        # "mesh" never reaches this __init__ (the __new__ dispatch hands
        # construction to DistGraphSageSampler, which overrides it)
        self.topo_sharding = "replicated"
        self.csr_topo = csr_topo
        self.mode = SampleMode.parse(mode)
        max_deg = csr_topo.max_degree
        self.sizes = tuple(int(k) if k != -1 else max_deg for k in sizes)
        if any(k < 1 for k in self.sizes):
            raise ValueError(f"fanouts must be >= 1 or -1, got {sizes}")
        self.weighted = bool(weighted)
        self.with_eid = bool(with_eid)
        if time_window is not None:
            lo_t, hi_t = time_window  # two scalars, baked into the program
            time_window = (float(lo_t), float(hi_t))
            if self.weighted:
                raise ValueError(
                    "time_window cannot be combined with weighted=True; "
                    "pick one biased draw per sampler"
                )
        self.time_window = time_window
        self.kernel = settle_sample_kernel(str(kernel))
        if dedup not in ("auto", "scan"):
            raise ValueError(
                f"dedup must be 'auto' or 'scan', got {dedup!r}" + (
                    ": 'sort' and 'map' were removed, on a v5e both cost "
                    "4-5x the one algorithm left (PERF.md, PR 29)"
                    if dedup in ("sort", "map") else "")
            )
        if self.kernel == "pallas":
            # an explicit pallas request fails loudly on the one capability
            # the fused kernel cannot provide: the HBM-resident CSR it DMAs
            # from. Every sampler VARIANT (weighted/temporal/with_eid) now
            # runs on the fused engine — the old capability-matrix raises
            # are gone (ISSUE 16).
            if SampleMode.parse(mode) is not SampleMode.HBM:
                raise ValueError("kernel='pallas' requires mode='HBM' (GPU) topology")
            if csr_topo.edge_relation is not None:
                raise ValueError(
                    "kernel='pallas' does not read edge relations; use "
                    "kernel='xla'")
        if self.weighted and csr_topo.cum_weights is None:
            raise ValueError(
                "weighted=True requires edge weights; call "
                "csr_topo.set_edge_weight() or pass edge_weight= to CSRTopo"
            )
        if self.time_window is not None and csr_topo.edge_time is None:
            raise ValueError(
                "time_window requires edge timestamps; call "
                "csr_topo.set_edge_time() or pass edge_time= to CSRTopo"
            )
        self.topo = self._init_topo(device_topo)
        # the committed mutation version the device placement reflects; a
        # streaming commit bumps csr_topo.version, after which sampling
        # raises VersionMismatchError until refresh_topology() re-places
        self._topo_version = int(getattr(csr_topo, "version", 0))
        self._seed_capacity = seed_capacity
        self._auto_caps = frontier_caps == "auto"
        self._auto_margin = float(auto_margin)
        if self._auto_margin < 1.0:
            raise ValueError(f"auto_margin must be >= 1.0, got {auto_margin}")
        if self._auto_caps:
            frontier_caps = None  # first call plans from worst case
        elif frontier_caps is not None:
            frontier_caps = tuple(int(c) for c in frontier_caps)
            if len(frontier_caps) != len(self.sizes):
                raise ValueError(
                    f"frontier_caps needs one entry per layer "
                    f"({len(self.sizes)}), got {len(frontier_caps)}"
                )
            if any(c < 1 for c in frontier_caps):
                raise ValueError(f"frontier_caps must be positive, got {frontier_caps}")
        self._frontier_caps = frontier_caps
        self._key = jax.random.PRNGKey(seed)
        self._call = 0
        self._device = device  # accepted for API parity; placement is implicit
        if device is not None:
            # reference-ported code gets a runtime signal that its CUDA
            # ordinal pinning did nothing (VERDICT r5 weak #7)
            info_once(
                "sampler-inert-device-arg",
                "GraphSageSampler(device=%r) accepted for reference API "
                "parity but INERT: under single-controller SPMD placement "
                "is implicit; nothing reads this argument",
                device,
            )
        if compiled_cache_size < 1:
            raise ValueError(
                f"compiled_cache_size must be >= 1, got {compiled_cache_size}"
            )
        self.compiled_cache_size = int(compiled_cache_size)
        self.compiled_cache_evictions = 0
        # LRU-bounded: the serving ladder and auto-cap replans key programs
        # on (seed_cap, caps), and an unbounded per-instance dict would pin
        # every superseded program (and its captured constants) forever
        self._compiled_cache = OrderedDict()

    def _init_topo(self, device_topo):
        """Build (or adopt) the device-resident topology. The mesh-sharded
        sampler overrides this to partition the CSR instead of uploading a
        full replica."""
        if device_topo is not None:
            # advanced: share one DeviceTopology across samplers (the
            # reference shares one native quiver across sampler objects
            # too); must have been built with to_device flags compatible
            # with this sampler's mode/with_eid/weighted
            if self.with_eid and getattr(device_topo, "eid", None) is None:
                raise ValueError(
                    "device_topo lacks eid but with_eid=True; rebuild with "
                    "to_device(with_eid=True)"
                )
            if self.weighted and getattr(device_topo, "cum_weights", None) is None:
                raise ValueError(
                    "device_topo lacks cum_weights but weighted=True; "
                    "rebuild with to_device(with_weights=True)"
                )
            if (self.time_window is not None
                    and getattr(device_topo, "edge_time", None) is None):
                raise ValueError(
                    "device_topo lacks edge_time but time_window is set; "
                    "rebuild with to_device(with_times=True)"
                )
            return device_topo
        return self.csr_topo.to_device(
            self.mode, with_eid=self.with_eid, with_weights=self.weighted,
            with_times=self.time_window is not None,
            with_relations=self.csr_topo.edge_relation is not None,
        )

    # -- streaming-mutation versioning --------------------------------------

    def check_topo_version(self) -> None:
        """Raise :class:`VersionMismatchError` when the host CSR has been
        mutated (a ``quiver_tpu.streaming`` commit bumped its version)
        since this sampler's device topology was placed — sampling over
        the stale placement would silently draw from the pre-commit
        graph. Call :meth:`refresh_topology` to re-place."""
        current = int(getattr(self.csr_topo, "version", 0))
        if current != self._topo_version:
            raise VersionMismatchError(
                f"sampler topology placement is at version "
                f"{self._topo_version} but the host CSR has committed "
                f"version {current}; call refresh_topology() to re-place "
                f"the device topology before sampling"
            )

    def refresh_topology(self) -> "GraphSageSampler":
        """Re-place the device topology from the (possibly mutated) host
        CSR and adopt its committed version. The compiled-program cache is
        dropped — edge-array shapes changed with the edge count, and the
        mesh-sharded override bakes partition geometry into the program."""
        self.topo = self._init_topo(None)
        self._topo_version = int(getattr(self.csr_topo, "version", 0))
        self._compiled_cache.clear()
        return self

    # -- static-shape planning ---------------------------------------------

    def _worst_caps(self, seed_cap: int) -> tuple[int, ...]:
        caps = []
        cur = seed_cap
        n = self.csr_topo.node_count
        for k in self.sizes:
            # clamp growth at node_count but never below the previous cap:
            # forced (seeds-first) lanes keep duplicate seeds as distinct
            # slots, so each frontier must hold the whole previous one
            cur = max(min(cur * (k + 1), n), cur)
            cur = _round_up(cur, 8)
            caps.append(cur)
        return tuple(caps)

    def _caps_for(self, seed_cap: int) -> tuple[int, ...]:
        if self._frontier_caps is not None:
            return self._frontier_caps
        return self._worst_caps(seed_cap)

    def _plan_auto(self, seed_cap: int, observed: Sequence[int]) -> None:
        """Set frontier caps to margin × observed unclipped unique counts
        (seeds-outward order), never shrinking below already-planned caps."""
        worst = self._worst_caps(seed_cap)
        old = self._frontier_caps or (0,) * len(worst)
        caps, prev = [], seed_cap
        for w, o, c in zip(worst, observed, old):
            cap = _round_up(int(self._auto_margin * o), 128)
            cap = max(cap, prev, c, 128)
            cap = min(cap, w)
            caps.append(cap)
            prev = cap
        self._frontier_caps = tuple(caps)

    def _compiled(self, seed_cap: int):
        # instance-level memo keyed on the full static plan (a functools.cache
        # on a method would pin the sampler and its device arrays in a
        # class-level cache forever; auto mode re-plans caps per seed_cap)
        caps = self._caps_for(seed_cap)
        cache_key = (seed_cap, caps)
        hit = self._compiled_cache.get(cache_key)
        if hit is not None:
            self._compiled_cache.move_to_end(cache_key)
            return hit
        sizes = self.sizes
        weighted = self.weighted
        kernel = self.kernel
        with_eid = self.with_eid
        time_window = self.time_window

        @jax.jit
        def run(topo, seeds, num_seeds, key):
            return multilayer_sample(topo, seeds, num_seeds, key, sizes, caps,
                                     weighted=weighted, kernel=kernel,
                                     with_eid=with_eid,
                                     time_window=time_window)

        self._compiled_cache[cache_key] = (run, caps)
        while len(self._compiled_cache) > self.compiled_cache_size:
            self._compiled_cache.popitem(last=False)
            self.compiled_cache_evictions += 1
        return run, caps

    # -- public API ----------------------------------------------------------

    def sample(self, input_nodes) -> SampleOutput:
        """Sample k-hop neighborhoods of ``input_nodes``.

        Returns a SampleOutput whose ``adjs`` is deepest-layer-first,
        matching the reference's ``adjs[::-1]`` return (sage_sampler.py:112);
        ``edge_counts``/``frontier_counts`` carry per-layer in-program tallies.
        """
        self.check_topo_version()
        seeds = np.asarray(input_nodes)
        batch = int(seeds.shape[0])
        if batch and (seeds.min() < 0 or seeds.max() >= self.csr_topo.node_count):
            raise ValueError(
                f"seed ids must be in [0, {self.csr_topo.node_count}); "
                f"got range [{seeds.min()}, {seeds.max()}]"
            )
        cap = self._seed_capacity or max(_round_up(batch, 128), 128)
        if batch > cap:
            raise ValueError(f"batch {batch} exceeds seed_capacity {cap}")
        padded = np.full(cap, -1, dtype=np.int32)
        padded[:batch] = seeds
        run, _ = self._compiled(cap)
        self._call += 1
        key = jax.random.fold_in(self._key, self._call)
        dev_seeds = jnp.asarray(padded)
        n_id, n_count, adjs, overflow, edge_counts, frontier_counts = run(
            self.topo, dev_seeds, jnp.int32(batch), key
        )
        if self._auto_caps:
            first_plan = self._frontier_caps is None
            # auto mode pays one scalar sync per call to watch for overflow.
            # Regrow converges in <= num_layers rounds (each round's caps
            # cover that round's observed counts); the bound guards the
            # saturation corner where duplicate forced seed lanes push
            # uniques past node_count and even worst-case caps overflow —
            # then the clipped result + overflow report stand, as in
            # fixed-caps mode.
            for _ in range(len(self.sizes) + 2):
                if not first_plan and int(overflow) == 0:
                    break
                observed = [int(c) for c in frontier_counts[::-1]]
                before = self._frontier_caps
                self._plan_auto(cap, observed)
                if self._frontier_caps != before:
                    from ..utils.trace import get_logger

                    get_logger().info(
                        "auto caps %s: %s -> %s (recompile)",
                        "planned" if before is None else "regrown",
                        before, self._frontier_caps,
                    )
                if not first_plan and self._frontier_caps == before:
                    # saturated: caps already at worst case and still
                    # overflowing — rerunning the identical program cannot
                    # help; return the clipped result + overflow report
                    break
                if first_plan and int(overflow) == 0:
                    # worst-case first run: result stands, later calls use
                    # the tight plan
                    first_plan = False
                    break
                run, _ = self._compiled(cap)
                n_id, n_count, adjs, overflow, edge_counts, frontier_counts = run(
                    self.topo, dev_seeds, jnp.int32(batch), key
                )
                first_plan = False
        return SampleOutput(
            n_id, batch, adjs, n_count, overflow, edge_counts, frontier_counts
        )

    def sample_padded(self, topo, seeds, num_seeds, key):
        """Jit-composable sampling on already-padded device seeds.

        For use inside larger jitted programs (e.g. a fused train step);
        shapes must match a previously planned capacity.
        """
        run, _ = self._compiled(int(seeds.shape[0]))
        return run(topo, seeds, num_seeds, key)

    # -- parity helpers ------------------------------------------------------

    def share_ipc(self):
        """Reference API parity (sage_sampler.py:114-120). Under
        single-controller SPMD there is nothing to share; returns the
        rebuild recipe for symmetry."""
        return (self.csr_topo, self.sizes, self.mode)

    @classmethod
    def lazy_from_ipc_handle(cls, handle):
        csr_topo, sizes, mode = handle
        return cls(csr_topo, sizes, mode=mode)
