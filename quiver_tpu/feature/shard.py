"""Mesh-sharded feature storage with ICI-collective gathers.

TPU-native replacement for the reference's ShardTensor + p2p_clique_replicate
stack (torch-quiver shard_tensor.py:79-241, quiver_feature.cu:56-361,
feature.py:126-166): where the reference partitions hot rows across the GPUs
of an NVLink clique and lets the gather kernel load peer HBM directly,
quiver-tpu shards rows across the mesh's ``feature`` axis and fetches remote
rows with one XLA collective inside ``shard_map``:

    partial[b] = own(id_b) ? local_rows[id_b - offset] : 0
    result     = psum(partial, axis="feature")

The psum lowers to reduce-scatter + all-gather on the ICI ring — the role
NVLink peer loads play in the reference. No IPC handles, no access_book, no
cross-clique Python fallback path (shard_tensor.py:166-208): devices that
share no ICI would sit on different meshes entirely.

``ShardedTensor`` is the generic row-sharded 2-D table (reference
ShardTensor parity); ``ShardedFeature`` layers feature_order translation,
an optional L0 *replicated super-hot tier* (``replicate_budget`` — the
top-degree rows in every chip's HBM, gathered with zero interconnect
lanes), and the cold host tier on top (reference Feature with
device_replicate + p2p_clique_replicate + UVA, as one three-tier store).

When every feature-group member requests its OWN id set (routed mode, the
seed_sharding="all" trainer), requests are routed to their owning shard
over two ``all_to_all`` hops. Buckets are CAPPED by default: capacity
``ceil(alpha * L / F)`` per destination, so each hop moves ``alpha * L``
lanes instead of the exact-safe worst case ``F * L`` — the comm volume no
longer inflates with the feature-axis width. Per-bucket overflow is
detected in-program and served through a psum fallback (never silent,
never wrong), counted, and surfaced so callers and the auto-tuner can grow
the cap across batches. See ``ShardedTensor.routed_gather``.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import CachePolicy, parse_size_bytes
from .feature import (
    _parse_storage_dtype,
    quantize_rows_int8,
    tiered_lookup,
    validate_gather_kernel,
    wrap_dequant_gathers,
)
from ..core.memory import to_pinned_host
from ..core.topology import CSRTopo
from ..obs.registry import ROUTED_OVERFLOW, TIER_HITS, MetricsRegistry
from ..ops.sample import staged_gather
from ..parallel.routing import BucketRoute
from ..utils.trace import get_logger, info_once
from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS, shard_map
from ..utils.reorder import reorder_by_degree

__all__ = ["ShardedTensor", "ShardedFeature"]


class ShardedTensor:
    """2-D table row-sharded over the mesh's feature axis.

    Rows are padded to a multiple of the axis size; shard d owns rows
    [d*rows_per_shard, (d+1)*rows_per_shard) — the same contiguous-offset
    layout the reference tracks in ``tensor_offset_device``
    (shard_tensor.py:55-76).
    """

    def __init__(self, mesh: Mesh, axis: str = FEATURE_AXIS,
                 routed_alpha: float = 2.0):
        self.mesh = mesh
        self.axis = axis
        self.num_shards = mesh.shape[axis]
        # capped-bucket routed gather: per-destination bucket capacity
        # ceil(routed_alpha * L / F). alpha=2 leaves 2x headroom over a
        # uniform owner distribution — degree-ordered hot rows concentrate
        # on shard 0 (reorder_by_degree's partial shuffle spreads them, but
        # skew survives), so 1.0 would overflow routinely. Grown by
        # _maybe_grow_routed_alpha when a batch overflows (fallback-served,
        # never wrong — just slower); alpha >= F means full-length buckets,
        # i.e. the exact-safe uncapped path.
        if routed_alpha <= 0:
            raise ValueError(f"routed_alpha must be > 0, got {routed_alpha}")
        self.routed_alpha = float(routed_alpha)
        # graftscope registry: the overflow count of the last capped routed
        # gather lands here (``last_routed_overflow`` is a thin view). Read
        # lazily — int() forces a sync, so consumers (the auto-tuner,
        # benchmarks, exporters) pull it after the batch.
        self.metrics = MetricsRegistry()
        self.metrics.counter(
            ROUTED_OVERFLOW, unit="lanes",
            doc="fallback-served lanes of the last capped routed gather",
        )
        self.table = None
        self.rows_per_shard = 0
        self.num_rows = 0
        self._gather_cache = {}

    @property
    def last_routed_overflow(self):
        """Fallback-served lane count of the last eager capped routed
        gather (device scalar; ``(steps,)`` after an epoch_scan write;
        None before any). Thin view of the ``feature.routed_overflow``
        registry metric — new consumers should read ``self.metrics``."""
        return self.metrics.value(ROUTED_OVERFLOW)

    @last_routed_overflow.setter
    def last_routed_overflow(self, value):
        self.metrics.set(ROUTED_OVERFLOW, value)

    def from_cpu_tensor(self, tensor: np.ndarray) -> "ShardedTensor":
        n, f = tensor.shape
        rps = -(-n // self.num_shards)  # ceil
        padded = rps * self.num_shards
        if padded != n:
            tensor = np.concatenate(
                [tensor, np.zeros((padded - n, f), tensor.dtype)]
            )
        sharding = NamedSharding(self.mesh, P(self.axis, None))
        self.table = jax.device_put(tensor, sharding)
        self.rows_per_shard = rps
        self.num_rows = n
        return self

    @property
    def shape(self):
        return (self.num_rows, self.table.shape[1])

    def local_gather(self, local_table, ids):
        """Per-device body: serve the ids this shard owns, zeros elsewhere.

        Call inside ``shard_map``; combine across shards with
        ``psum(..., self.axis)``. Requires every member of the feature
        group to request the SAME ids (the psum aligns rows by position).
        """
        my = jax.lax.axis_index(self.axis)
        owner = ids // self.rows_per_shard
        mine = owner == my
        local_idx = jnp.where(mine, ids - my * self.rows_per_shard, 0)
        rows = local_table[local_idx]
        return jnp.where(mine[:, None], rows, 0)

    def routed_cap(self, length: int, alpha: float | None = None) -> int:
        """Capped-bucket capacity for a per-device request length ``L``:
        ``cap = ceil(alpha * L / F)``, clamped to [1, L]. ``cap == L``
        degenerates to the exact-safe full-length buckets (no fallback
        machinery is traced then)."""
        a = self.routed_alpha if alpha is None else float(alpha)
        if a <= 0:
            raise ValueError(f"alpha must be > 0, got {a}")
        cap = math.ceil(a * length / max(self.num_shards, 1))
        # graftlint: disable=host-op-on-tracer -- L is the static lane width
        return max(1, min(int(cap), int(length)))

    # graftlint: eager -- between-batch tuner; under trace int() raises and
    def _maybe_grow_routed_alpha(self) -> None:  # the except returns early
        """Auto-tuner step for eager capped gathers: if the PREVIOUS capped
        batch overflowed its buckets, double ``routed_alpha`` (capped at F
        — full-length buckets) before planning this batch's cap. Reading
        the stashed count is cheap: the batch that produced it has long
        since completed."""
        ov = self.last_routed_overflow
        if ov is None:
            return
        self.last_routed_overflow = None
        try:
            count = int(ov)
        except Exception:  # noqa: BLE001 — a deleted/donated buffer must
            return  # not break the next gather
        if count <= 0:
            return
        old = self.routed_alpha
        self.routed_alpha = min(old * 2.0, float(self.num_shards))
        if self.routed_alpha != old:
            get_logger("feature").info(
                "routed gather: %d lanes overflowed their buckets "
                "(fallback-served); growing alpha %.2f -> %.2f",
                count, old, self.routed_alpha,
            )

    def routed_gather(self, local_table, ids, cap: int | None = None,
                      with_overflow: bool = False):
        """Per-device body: serve a DIFFERENT id set per feature-group
        member by routing requests to their owning shard and rows back —
        two ``all_to_all`` hops over the feature axis.

        This is the true analogue of the reference's NVLink-clique gather
        (shard_tensor.cu.hpp:16-58: every GPU runs its own batch and loads
        peer HBM directly): with it, the feature axis no longer forces
        redundant sampling/model work across the group — each device can be
        a full data worker over its own seed block while the table stays
        sharded (see docs/Introduction.md "Cost of redundant sampling").

        Comm model (L = per-device request length, F = feature-axis size;
        beside it each device pays the plan — F running counts and one
        sort of L lanes — and two row gathers, the owner's over F x cap
        slots and its own over L lanes):

        * ``cap=None`` — exact-safe full-length buckets: every destination
          bucket is padded to L (worst case all ids on one shard), so each
          hop moves ``F x L`` row lanes regardless of actual traffic.
        * ``cap=c`` (capped-bucket mode, ``c = ceil(alpha*L/F)`` from
          :meth:`routed_cap`) — each hop moves ``F x c ~= alpha*L`` lanes.
          Per-bucket overflow (more than ``c`` of my requests owned by one
          shard) is DETECTED in-program, never silent: overflowed lanes
          are served through a psum fallback (all_gather the <= L-c
          overflow ids over the feature axis, each shard contributes the
          rows it owns, psum returns them everywhere) gated behind a
          ``lax.cond`` whose predicate is the feature-group psum of the
          overflow count — uniform across the participants, so the
          collective-inside-cond is deadlock-free, and a non-overflowing
          batch pays ZERO fallback comm. The total overflow across all
          buckets is <= L - c (at most L valid lanes, each overflowing
          bucket keeps c of them), so the (L-c,) fallback buffer is
          exact-safe.

        Results are bit-identical between the two modes: capped routing
        moves the same table rows, just in smaller buckets, and fallback
        lanes receive exactly the rows the uncapped path would have
        fetched. Use psum ``local_gather`` instead when the feature group
        shares one id set.

        ``ids`` may contain invalid lanes as any negative value; their rows
        return zero. With ``with_overflow=True`` returns ``(rows, count)``
        where ``count`` is the feature-group total of fallback-served lanes
        (an int32 scalar, identical on every member; always 0 when
        ``cap=None``).
        """
        F = self.num_shards
        L = ids.shape[0]
        if cap is not None:
            cap = int(cap)
            if cap < 1:
                raise ValueError(f"cap must be >= 1, got {cap}")
            if cap >= L:
                cap = None  # full-length buckets ARE the uncapped path
        valid = ids >= 0
        safe = jnp.where(valid, ids, 0)

        # one audited code path for both comm modes and both consumers
        # (feature gather here, neighbor sampling in sampling/dist.py):
        # parallel.routing.BucketRoute owns the owner bucketing, the two
        # all_to_all hops, and the cond-gated psum fallback
        my = jax.lax.axis_index(self.axis)
        rps = self.rows_per_shard

        def serve(req_ids):
            # ownership-masked local gather: zero for dead (-1) lanes and
            # for ids another shard owns — required by the psum fallback,
            # harmless on the main hop (routing guarantees ownership there)
            mine = (req_ids >= 0) & (req_ids // rps == my)
            lidx = jnp.where(mine, req_ids - my * rps, 0)
            rows = local_table[lidx]
            return jnp.where(mine[:, None], rows, 0)

        route = BucketRoute(
            safe, valid, safe // rps, axis=self.axis,
            num_shards=self.num_shards, cap=cap,
        )
        rows = route.exchange(serve)
        if with_overflow:
            return rows, route.overflow
        return rows

    def _gather_fn(self, padded_len: int, dtype, routed: bool = False,
                   cap: int | None = None):
        """Memoized jitted shard_map gather (a fresh wrapper per call would
        re-trace on every eager batch).

        ``routed=False``: ids shard over the data axes, remote rows arrive
        by psum. ``routed=True``: ids shard over EVERY mesh axis and each
        device routes its own slice to the owning shards (routed_gather),
        so per-device gather work is 1/num_devices of the request instead
        of 1/data_size; ``cap`` selects the capped-bucket comm mode and
        the routed program returns ``(rows, overflow_count)`` with the
        count psum'd over the whole mesh (replicated).
        """
        cache_key = (padded_len, np.dtype(dtype).name, routed, cap)
        if cache_key in self._gather_cache:
            return self._gather_cache[cache_key]

        if routed:
            ids_axes = tuple(self.mesh.axis_names)
            other_axes = tuple(
                a for a in self.mesh.axis_names if a != self.axis
            )

            def body(local_table, local_ids):
                rows, ov = self.routed_gather(
                    local_table, local_ids, cap=cap, with_overflow=True
                )
                if other_axes:  # feature-psum'd already; replicate mesh-wide
                    ov = jax.lax.psum(ov, other_axes)
                return rows, ov

            out_specs = (P(ids_axes, None), P())
        else:
            ids_axes = tuple(
                a for a in self.mesh.axis_names if a != self.axis
            )

            def body(local_table, local_ids):
                part = self.local_gather(local_table, local_ids)
                return jax.lax.psum(part, self.axis)

            out_specs = P(ids_axes, None)

        f = jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(P(self.axis, None), P(ids_axes)),
                out_specs=out_specs,
                check_vma=False,
            )
        )
        self._gather_cache[cache_key] = f
        return f

    def delete(self) -> None:
        """Free the sharded buffers now (reference ``shard_tensor.delete``,
        SURVEY §2.5). The object is unusable after."""
        if self.table is not None:
            self.table.delete()
        self.table = None
        self.last_routed_overflow = None
        self._gather_cache.clear()

    def gather(self, ids, routed: bool = False, routed_cap="auto"):
        """Standalone sharded gather.

        ``routed=False``: ids shard over the data axes (replicated across
        the feature axis); remote rows arrive by psum. ``routed=True``: ids
        shard over EVERY axis and each device routes its slice to the
        owning shards (two all_to_alls) — per-device work drops by the
        feature-axis width; see routed_gather. Same results either way
        (bit-identical).

        ``routed_cap`` picks the routed comm mode (see routed_gather's comm
        model): ``"auto"`` (default) caps destination buckets at
        ``ceil(routed_alpha * L / F)`` lanes — ``alpha*L`` moved per hop
        instead of ``F*L`` — and auto-grows ``routed_alpha`` on the next
        call after a batch overflows (the overflowed lanes themselves are
        fallback-served, so results stay exact). ``None`` forces the
        uncapped full-length buckets; an int is an explicit per-bucket
        capacity. After a routed call ``last_routed_overflow`` holds the
        batch's fallback-served lane count (device scalar).
        """
        mult = 1
        for a in self.mesh.axis_names:
            if routed or a != self.axis:
                mult *= self.mesh.shape[a]
        n = ids.shape[0]
        pad = (-n) % mult
        if pad:
            # -1 = the documented invalid-lane sentinel. Padded lanes are
            # zeroed in the output — correct output, not skipped work.
            # (psum-path local_gather treats any non-owned id as zeros and
            # the routed paths never fetch them, so -1 is safe everywhere.)
            ids = jnp.concatenate([ids, jnp.full(pad, -1, ids.dtype)])
        if not routed:
            out = self._gather_fn(ids.shape[0], ids.dtype, False)(
                self.table, ids
            )
            return out[:n] if pad else out
        local_len = ids.shape[0] // mult
        if routed_cap == "auto":
            self._maybe_grow_routed_alpha()
            cap = self.routed_cap(local_len)
        elif routed_cap is None:
            cap = None
        else:
            cap = min(int(routed_cap), local_len)
        if cap is not None and cap >= local_len:
            cap = None  # full-length buckets: share the uncapped program
        out, ov = self._gather_fn(ids.shape[0], ids.dtype, True, cap)(
            self.table, ids
        )
        if not isinstance(ov, jax.core.Tracer):
            # eager call: stash the device scalar for the auto-tuner /
            # benchmarks. Under an outer jit trace ov is a tracer — storing
            # it would leak; in-program callers use routed_gather's
            # with_overflow return instead.
            self.last_routed_overflow = ov
        return out[:n] if pad else out

    def __getitem__(self, ids):
        """Standalone sharded gather (psum flavor); see :meth:`gather`."""
        return self.gather(ids)


class ShardedFeature:
    """Feature store with a three-tier memory hierarchy over the mesh:

    * **L0 replicated super-hot** (``replicate_budget`` bytes/device): the
      top-β rows by degree, a full copy in EVERY chip's HBM, served by a
      pure local gather — zero interconnect lanes. The reference's
      ``device_replicate`` policy, scoped to only the rows hot enough to
      earn F× the HBM.
    * **L1 mesh-sharded hot** (``device_cache_size`` bytes/device): the
      MESH_SHARD realization of ``p2p_clique_replicate``
      (feature.py:126-166) — rows sharded over the feature axis, gathers
      ride ICI collectives (psum or owner-routed all_to_all).
    * **cold**: pinned-host rows with staged host-compute gathers (the UVA
      zero-copy role).

    Both budgets are *per device*, matching the reference's per-GPU
    ``device_cache_size``; total L1 rows = budget × feature-axis size,
    while an L0 row costs its bytes on every device.

    Per-tier hit counts of the last eager gather land in
    ``last_tier_hits`` (int32 ``(3,)`` device vector,
    ``[replicated, sharded, cold]``) — the measured hit distribution the
    control plane uses to move the L0/L1 boundary between batches.
    ``auto_split=True`` is a compat shim over a default
    :class:`~quiver_tpu.control.CacheController` (see
    :meth:`_maybe_auto_split`); attach a shared controller for measured
    re-tiering (:meth:`repin`) across training AND serving traffic.
    """

    def __init__(
        self,
        mesh: Mesh,
        device_cache_size: int | str = 0,
        csr_topo: CSRTopo | None = None,
        axis: str = FEATURE_AXIS,
        hot_shuffle_seed: int = 0,
        kernel: str = "auto",
        dtype=None,
        routed_alpha: float = 2.0,
        replicate_budget: int | str = 0,
        auto_split: bool = False,
    ):
        self.mesh = mesh
        self.axis = axis
        validate_gather_kernel(kernel)
        if routed_alpha <= 0:
            raise ValueError(f"routed_alpha must be > 0, got {routed_alpha}")
        self.routed_alpha = float(routed_alpha)
        self.storage_dtype = _parse_storage_dtype(dtype)
        self.cache_policy = CachePolicy.MESH_SHARD
        self.cache_budget = parse_size_bytes(device_cache_size)
        self.replicate_budget = parse_size_bytes(replicate_budget)
        self.auto_split = bool(auto_split)
        self.csr_topo = csr_topo
        self.hot_shuffle_seed = hot_shuffle_seed
        self.rep = None  # L0: (rep_rows, F) mesh-replicated block
        self.hot: ShardedTensor | None = None
        self.cold = None
        self._cold_is_host = False
        self.feature_order = None
        self.scale = None  # (N,) dequant scales (int8 storage only)
        self.rep_rows = 0
        self.hot_rows = 0
        self.shape = None
        # graftscope registry: per-tier hit counts [replicated, sharded,
        # cold] of the last eager gather land here (``last_tier_hits`` is a
        # thin view; device int32 (3,), None before any). Trainers
        # overwrite it with their psum'd batch totals so the split tuner
        # sees the fused path's traffic too.
        self.metrics = MetricsRegistry()
        self.metrics.gauge(
            TIER_HITS, shape=(3,), unit="hits",
            doc="per-tier feature hits [replicated, sharded, cold] of the "
                "last gather",
        )
        # host copy of the device region (rows [0, rep_rows + hot_rows) in
        # storage dtype) kept iff the L0/L1 boundary may move after
        # placement (auto_split or a nonzero replicate budget) — resplit
        # rebuilds both tiers from it without touching the cold tier
        self._region_host = None
        self._rep_ceiling_rows = 0  # auto_split never grows L0 past this
        # streaming-mutation version: bumped ONCE per published
        # apply_row_updates transaction. Consumers that captured tier
        # buffers (the fused trainer's mesh-wide cold copy) compare their
        # bound version against this and raise instead of serving stale
        # rows (quiver_tpu.streaming's invalidation contract).
        self.version = 0
        # quiver-ctl seam: the attached CacheController (None = standalone).
        # auto_split=True lazily creates a default one on first tuner call;
        # DistributedTrainer(controller=...) attaches a shared one. The
        # split decision itself lives in control/controller.py — this class
        # only measures (tier hits) and actuates (resplit/repin).
        self._controller = None
        self._resplit_from_tuner = False

    def _plan_split(self, n: int, f: int, itemsize: int, quantized: bool,
                    num_shards: int) -> tuple[int, int]:
        """(rep_rows, hot_rows) from the two per-device byte budgets."""
        if quantized:
            # the (N,) f32 scale array is replicated on EVERY device (all
            # tiers dequantize on device) — charge its 4N bytes against the
            # budgets before spending on 1-byte-element rows. Sharded budget
            # pays first (the scale is its dequant state even cold-only);
            # any shortfall eats into the replicate budget.
            scale_bytes = 4 * n
            combined = self.cache_budget + self.replicate_budget
            if 0 < combined < scale_bytes:
                # budget-edge: cannot even hold the dequant scales — degrade
                # to cold-only (exact, host-served) instead of crashing or
                # silently mis-splitting
                info_once(
                    "sharded-int8-budget-below-scale",
                    "ShardedFeature(int8): combined cache budget %d B is "
                    "smaller than the replicated dequant-scale array "
                    "(4 B x %d rows = %d B); degrading to a cold-only "
                    "store (exact, host-served). Grow device_cache_size "
                    "past 4*n bytes to enable device tiers.",
                    combined, n, scale_bytes, child="feature",
                )
                return 0, 0
            c_budget = self.cache_budget - scale_bytes
            r_budget = self.replicate_budget
            if c_budget < 0:
                r_budget = max(r_budget + c_budget, 0)
                c_budget = 0
            rep_rows = min(n, r_budget // f)
            hot_rows = min(n - rep_rows, (c_budget // f) * num_shards)
            return rep_rows, hot_rows
        row_bytes = f * itemsize
        rep_rows = min(n, self.replicate_budget // row_bytes)
        hot_rows = min(
            n - rep_rows, (self.cache_budget // row_bytes) * num_shards
        )
        return rep_rows, hot_rows

    def _place_region(self, region: np.ndarray, rep_rows: int) -> None:
        """(Re)build the L0 + L1 device tiers from the device-region rows.

        ``region`` holds rows [0, rep_rows + hot_rows) of the translated
        row space in storage dtype; the boundary at ``rep_rows`` decides
        which prefix is replicated."""
        old_rep, old_hot = self.rep, self.hot
        total = region.shape[0]
        rep_rows = max(0, min(int(rep_rows), total))
        if rep_rows > 0:
            self.rep = jax.device_put(
                region[:rep_rows], NamedSharding(self.mesh, P())
            )
        else:
            self.rep = None
        if total - rep_rows > 0:
            self.hot = ShardedTensor(
                self.mesh, self.axis, routed_alpha=self.routed_alpha,
            ).from_cpu_tensor(region[rep_rows:])
        else:
            self.hot = None
        self.rep_rows = rep_rows
        self.hot_rows = total - rep_rows
        if old_rep is not None and hasattr(old_rep, "delete"):
            old_rep.delete()
        if old_hot is not None:
            old_hot.delete()

    def from_cpu_tensor(self, tensor: np.ndarray) -> "ShardedFeature":
        tensor = np.asarray(tensor)
        quantized = (
            self.storage_dtype is not None
            and self.storage_dtype == np.dtype(np.int8)
        )
        if (
            self.storage_dtype is not None
            and not quantized
            and tensor.dtype != self.storage_dtype
        ):
            tensor = tensor.astype(self.storage_dtype)
        n, f = tensor.shape
        num_shards = self.mesh.shape[self.axis]
        rep_rows, hot_rows = self._plan_split(
            n, f, tensor.dtype.itemsize, quantized, num_shards
        )
        device_rows = rep_rows + hot_rows

        # degree order matters whenever a tier boundary cuts [0, n): the
        # L0 prefix wants the literal top-degree rows (pinned, unshuffled —
        # replication needs no shard balance), the sharded span keeps the
        # balance shuffle
        if self.csr_topo is not None and 0 < device_rows and (
            device_rows < n or 0 < rep_rows < n
        ):
            tensor, order = reorder_by_degree(
                tensor,
                self.csr_topo.degree,
                device_rows / n,
                seed=self.hot_shuffle_seed,
                pin_top=rep_rows,
            )
            self.csr_topo.feature_order = order
            self.feature_order = jnp.asarray(order)

        if quantized:
            tensor, scale = quantize_rows_int8(tensor)  # AFTER the reorder
            self.scale = jnp.asarray(scale)

        self.shape = (n, f)
        self.dtype = tensor.dtype
        self._rep_ceiling_rows = rep_rows
        if device_rows > 0:
            region = tensor[:device_rows]
            if self.auto_split or self.replicate_budget > 0:
                self._region_host = np.ascontiguousarray(region)
            self._place_region(region, rep_rows)
        if device_rows < n:
            self.cold, self._cold_is_host = to_pinned_host(
                tensor[device_rows:], mesh=self.mesh
            )
        # placement report (reference shard_tensor.py:153-162 LOG>>> parity)
        get_logger("feature").info(
            "feature tiers: %d/%d rows replicated (L0, %.1f MB/device), "
            "%d sharded over %d devices on mesh axis '%s' (%.1f MB/device); "
            "cold tier: %s",
            rep_rows,
            n,
            rep_rows * f * tensor.dtype.itemsize / 2**20,
            hot_rows,
            num_shards,
            self.axis,
            hot_rows * f * tensor.dtype.itemsize / num_shards / 2**20,
            "pinned host" if self._cold_is_host
            else ("none" if device_rows == n else "device"),
        )
        return self

    @property
    def last_tier_hits(self):
        """Per-tier hit counts of the last eager gather (thin view of the
        ``feature.tier_hits`` registry metric — new consumers should read
        ``self.metrics``)."""
        return self.metrics.value(TIER_HITS)

    @last_tier_hits.setter
    def last_tier_hits(self, value):
        self.metrics.set(TIER_HITS, value)

    @property
    def cache_ratio(self) -> float:
        """Fraction of rows resident in device HBM (both L0 and L1)."""
        if not self.shape:
            return 0.0
        return (self.rep_rows + self.hot_rows) / self.shape[0]

    @property
    def replicated_ratio(self) -> float:
        return self.rep_rows / self.shape[0] if self.shape else 0.0

    def resplit(self, rep_rows: int) -> None:
        """Move the L0/L1 boundary to ``rep_rows`` (eager, between batches).

        Tier membership in the translated row space is untouched — the
        first ``rep_rows`` device rows become the replicated block, the
        rest the sharded table — so gathers stay bit-identical; only the
        comm path serving each row changes. Requires the retained host
        region (``auto_split=True`` or ``replicate_budget > 0`` at
        construction). Compiled consumers retrace on the new table shapes.
        """
        if self._region_host is None:
            if max(0, int(rep_rows)) == self.rep_rows:
                return  # no-op split (e.g. a trainer passing budget 0)
            raise ValueError(
                "resplit needs the retained host region: construct "
                "ShardedFeature with replicate_budget > 0 or auto_split=True"
            )
        total = self._region_host.shape[0]
        rep_rows = max(0, min(int(rep_rows), total))
        if rep_rows == self.rep_rows:
            return
        self._place_region(self._region_host, rep_rows)
        # stale hits describe the OLD boundary; the tuner must not act on
        # them against the new one
        self.last_tier_hits = None
        if self._controller is not None and not self._resplit_from_tuner:
            # a MANUAL move invalidates the tuner's direction history (its
            # own moves keep it — that history IS the reversal dead-band)
            self._controller.split_tuner.reset()

    def replan(self, mesh: Mesh) -> "ShardedFeature":
        """Re-place the three-tier store onto a DIFFERENT mesh shape
        (elastic resume: a run checkpointed at F=8 continuing at F=4).

        The translated row space is reused verbatim — ``feature_order``,
        the per-row dequant ``scale``, and every row's bytes are
        unchanged; only the tier boundaries are re-planned for the new
        feature-axis size (the same per-device byte budgets buy fewer
        total sharded rows on a smaller mesh, so rows spill from L1 to
        the cold tier) and the tiers are re-placed. Gathers therefore
        stay bit-identical: the same rows come back, possibly over a
        different comm path — the same exactness contract as
        :meth:`resplit`. Compiled consumers must rebuild (their mesh
        changed, not just their shapes).
        """
        if self.shape is None:
            raise ValueError("replan() before from_cpu_tensor()")
        n, f = self.shape
        num_shards = int(mesh.shape[self.axis])
        quantized = (
            self.storage_dtype is not None
            and self.storage_dtype == np.dtype(np.int8)
        )
        # reassemble the full translated row space on host: device region
        # (retained host copy when available, else read back) + cold rows
        if self._region_host is not None:
            region = self._region_host
        else:
            parts = []
            if self.rep is not None:
                parts.append(np.asarray(self.rep))
            if self.hot is not None:
                parts.append(np.asarray(self.hot.table)[: self.hot_rows])
            region = (
                np.concatenate(parts) if len(parts) > 1
                else parts[0] if parts
                else np.zeros((0, f), self.dtype)
            )
        if self.cold is not None:
            full = np.concatenate([region, np.asarray(self.cold)])
        else:
            full = region
        rep_rows, hot_rows = self._plan_split(
            n, f, np.dtype(self.dtype).itemsize, quantized, num_shards
        )
        device_rows = rep_rows + hot_rows
        old_shards = self.mesh.shape[self.axis]
        self.mesh = mesh
        if self.cold is not None and hasattr(self.cold, "delete"):
            self.cold.delete()
        self.cold = None
        self._cold_is_host = False
        self._rep_ceiling_rows = rep_rows
        self._place_region(full[:device_rows], rep_rows)
        if device_rows < n:
            self.cold, self._cold_is_host = to_pinned_host(
                full[device_rows:], mesh=mesh
            )
        self._region_host = (
            np.ascontiguousarray(full[:device_rows])
            if (self.auto_split or self.replicate_budget > 0)
            else None
        )
        # stale hits describe the OLD mesh's tiers
        self.last_tier_hits = None
        get_logger("feature").info(
            "feature replan: %d -> %d shards on mesh axis '%s'; tiers now "
            "%d replicated / %d sharded / %d cold rows (same translated "
            "order — gathers stay bit-identical)",
            old_shards, num_shards, self.axis,
            rep_rows, self.hot_rows, n - device_rows,
        )
        return self

    def resplit_budget(self, replicate_budget: int | str) -> None:
        """:meth:`resplit` with the boundary given in bytes/device (same
        parser as ``device_cache_size``). Raises the L0 ceiling the
        ``auto_split`` tuner honors."""
        budget = parse_size_bytes(replicate_budget)
        row_bytes = self.shape[1] * np.dtype(self.dtype).itemsize
        rows = budget // max(row_bytes, 1)
        self._rep_ceiling_rows = max(self._rep_ceiling_rows, rows)
        self.resplit(rows)

    def repin(self, rows) -> None:
        """Re-tier the store so ``rows`` (ORIGINAL node ids, hottest
        first) occupy the FRONT of the translated row space — a
        measured-hottest set becomes the L0 prefix, spilling into L1 when
        longer than ``rep_rows``. This is the quiver-ctl actuation seam:
        the initial placement can only pin a degree-order prefix
        (``reorder_by_degree``), whereas ``repin`` accepts ARBITRARY hot
        sets (heat measured under real traffic need not correlate with
        degree).

        Tier SIZES are untouched; rows move WITH their bytes and dequant
        scales, and ``feature_order`` is re-composed with the inverse
        permutation, so every gather stays bitwise-identical — only the
        comm path serving each row changes (the exactness contract of
        :meth:`resplit`/:meth:`replan`). Duplicate ids keep their first
        (hottest) occurrence; ids beyond ``rep_rows + hot_rows`` rows are
        ignored (nothing to pin them into). Bumps ``version`` — compiled
        consumers (the fused trainer's captured cold copy) must
        ``refresh()``; :class:`~quiver_tpu.control.CacheController`
        does this for its trainer automatically.
        """
        if self.shape is None:
            raise ValueError("repin() before from_cpu_tensor()")
        n, f = self.shape
        device_rows = self.rep_rows + self.hot_rows
        if device_rows == 0:
            return  # cold-only store: no device tier to pin into
        ids = np.asarray(rows).reshape(-1).astype(np.int64)
        if ids.size == 0:
            return
        if ids.min() < 0 or ids.max() >= n:
            raise ValueError(
                f"repin ids must be in [0, {n}); got range "
                f"[{ids.min()}, {ids.max()}]"
            )
        _, first = np.unique(ids, return_index=True)
        ids = ids[np.sort(first)][:device_rows]
        if self.feature_order is not None:
            old_order = np.asarray(self.feature_order).astype(np.int64)
            t = old_order[ids]
        else:
            old_order = None
            t = ids
        # permutation of the translated space: the pinned set first (in
        # priority order), every other row keeping its relative order
        mask = np.ones(n, bool)
        mask[t] = False
        perm = np.concatenate([t, np.nonzero(mask)[0]])
        # reassemble the full translated table on host (replan's pattern:
        # retained host region when available, else device read-back)
        if self._region_host is not None:
            region = self._region_host
        else:
            parts = []
            if self.rep is not None:
                parts.append(np.asarray(self.rep))
            if self.hot is not None:
                parts.append(np.asarray(self.hot.table)[: self.hot_rows])
            region = (
                np.concatenate(parts) if len(parts) > 1
                else parts[0] if parts
                else np.zeros((0, f), self.dtype)
            )
        full = (
            np.concatenate([region, np.asarray(self.cold)])
            if self.cold is not None else region
        )
        new_full = full[perm]
        new_pos = np.empty(n, np.int64)
        new_pos[perm] = np.arange(n, dtype=np.int64)
        # compose: node id -> old translated row -> new translated row
        new_order = new_pos if old_order is None else new_pos[old_order]
        new_scale = (
            None if self.scale is None else np.asarray(self.scale)[perm]
        )
        # --- publish: host state + ONE version bump, then re-place the
        # device tiers from it (apply_row_updates' transaction shape) ---
        self.version += 1
        order_dtype = (
            old_order.dtype if old_order is not None
            else np.int32 if n <= np.iinfo(np.int32).max else np.int64
        )
        new_order = new_order.astype(order_dtype, copy=False)
        self.feature_order = jnp.asarray(new_order)
        if self.csr_topo is not None:
            self.csr_topo.feature_order = new_order
        if new_scale is not None:
            self.scale = jnp.asarray(new_scale)
        self._place_region(new_full[:device_rows], self.rep_rows)
        if self.cold is not None:
            old_cold = self.cold
            self.cold, self._cold_is_host = to_pinned_host(
                new_full[device_rows:], mesh=self.mesh
            )
            if hasattr(old_cold, "delete"):
                old_cold.delete()
        if self._region_host is not None:
            self._region_host = np.ascontiguousarray(
                new_full[:device_rows]
            )
        # pre-repin telemetry describes the OLD row order
        self.last_tier_hits = None
        get_logger("feature").info(
            "repin v%d: %d measured-hot rows pinned to the front of the "
            "device region (%d replicated / %d sharded rows; same bytes, "
            "recomposed order — gathers stay bit-identical)",
            self.version, ids.shape[0], self.rep_rows, self.hot_rows,
        )

    # -- streaming mutation (transactional row updates) ----------------------

    def apply_row_updates(self, ids, rows) -> None:
        """Transactionally update feature rows across ALL THREE tiers.

        ``ids`` are ORIGINAL node ids (translated through
        ``feature_order`` — the same id space gathers use); ``rows`` is
        the matching ``(U, feature_dim)`` block in the logical (float)
        dtype. The update is all-or-nothing: every patched host array
        (device region, cold rows, dequant scales for int8 storage) is
        built and validated ASIDE, then published together with ONE
        version bump; a validation failure leaves the store bit-identical.

        Both device tiers re-place from the patched region, so an updated
        row pinned in L0 serves the new value on EVERY chip and its L1
        shard agrees — no stale L0 serve (the streaming layer's
        invalidation contract). Consumers that captured tier buffers (the
        fused trainer's mesh-wide cold copy) detect the bumped
        ``version`` and must refresh instead of reading stale rows.
        Quantized (int8) stores re-quantize the updated rows per-row and
        patch their scales in the same transaction.
        """
        if self.shape is None:
            raise ValueError("apply_row_updates() before from_cpu_tensor()")
        n, f = self.shape
        ids = np.asarray(ids).reshape(-1).astype(np.int64)
        rows = np.asarray(rows)
        if rows.ndim != 2 or rows.shape != (ids.shape[0], f):
            raise ValueError(
                f"rows must be ({ids.shape[0]}, {f}) to match ids/the "
                f"store's feature dim, got {rows.shape}"
            )
        if ids.size == 0:
            return
        if ids.min() < 0 or ids.max() >= n:
            raise ValueError(
                f"update ids must be in [0, {n}); got range "
                f"[{ids.min()}, {ids.max()}]"
            )
        if np.unique(ids).shape[0] != ids.shape[0]:
            raise ValueError(
                "duplicate ids in one row-update transaction are ambiguous "
                "(which value wins?); collapse duplicates upstream — the "
                "streaming layer's duplicate policy does this at admission"
            )
        if np.issubdtype(rows.dtype, np.floating) and not np.isfinite(
                rows).all():
            raise ValueError(
                "row updates contain non-finite values; a poisoned row "
                "must be rejected at the boundary, not cached"
            )
        quantized = (
            self.storage_dtype is not None
            and self.storage_dtype == np.dtype(np.int8)
        )
        # --- build every patched array ASIDE (pure numpy, no mutation) ---
        if self.feature_order is not None:
            t = np.asarray(self.feature_order).astype(np.int64)[ids]
        else:
            t = ids
        if quantized:
            new_rows, row_scale = quantize_rows_int8(
                rows.astype(np.float32, copy=False)
            )
            new_scale = np.asarray(self.scale).copy()
            new_scale[t] = row_scale
        else:
            new_rows = rows.astype(self.dtype, copy=False)
            new_scale = None
        device_rows = self.rep_rows + self.hot_rows
        in_region = t < device_rows
        new_region = None
        if device_rows > 0 and bool(in_region.any()):
            if self._region_host is not None:
                new_region = self._region_host.copy()
            else:
                parts = []
                if self.rep is not None:
                    parts.append(np.asarray(self.rep))
                if self.hot is not None:
                    parts.append(np.asarray(self.hot.table)[: self.hot_rows])
                new_region = (
                    np.concatenate(parts) if len(parts) > 1 else
                    parts[0].copy()
                )
            new_region[t[in_region]] = new_rows[in_region]
        new_cold = None
        if bool((~in_region).any()):
            new_cold = np.asarray(self.cold).copy()
            new_cold[t[~in_region] - device_rows] = new_rows[~in_region]
        # --- publish: host state + ONE version bump, then re-place the
        # device tiers from it (placements derive from the committed host
        # arrays, so a placement retry reproduces the same state) ---
        self.version += 1
        if new_scale is not None:
            self.scale = jnp.asarray(new_scale)
        if new_region is not None:
            if self._region_host is not None:
                self._region_host = new_region
            self._place_region(new_region, self.rep_rows)
        if new_cold is not None:
            old_cold = self.cold
            self.cold, self._cold_is_host = to_pinned_host(
                new_cold, mesh=self.mesh
            )
            if old_cold is not None and hasattr(old_cold, "delete"):
                old_cold.delete()
        # pre-update telemetry describes rows that no longer exist
        self.last_tier_hits = None
        get_logger("feature").info(
            "feature row update v%d: %d rows (%d device-region, %d cold)%s",
            self.version, ids.shape[0], int(in_region.sum()),
            int((~in_region).sum()),
            " + requantized scales" if quantized else "",
        )

    def note_degree_update(self, degree) -> None:
        """Feed post-mutation degrees to the existing split tuner so
        re-tiering follows mutation (ROADMAP item 3).

        A committed topology mutation changes the degree distribution the
        original L0/L1 boundary was planned from. This hands the NEW
        per-node degrees to the SAME grow/shrink/dead-band tuner that
        consumes measured tier hits (:meth:`_maybe_auto_split`), as a
        synthetic per-tier "hit mass" vector — degree-as-heat, the
        proxy the store's initial placement used. One boundary move per
        commit, at most; measured traffic keeps tuning afterwards.
        No-op unless ``auto_split=True`` (the tuner's own opt-in).

        With a :class:`~quiver_tpu.control.CacheController` attached the
        new degrees additionally seed its frequency sketch as a PRIOR
        (low weight — measured heat quickly dominates), so post-mutation
        re-tiering and measured-traffic re-tiering share one state."""
        if self.shape is None:
            return
        if self._controller is not None:
            prior = np.asarray(degree).reshape(-1)
            if prior.shape[0] == self.shape[0]:
                self._controller.observe_prior(prior)
        if not self.auto_split or self._region_host is None:
            return
        n, _ = self.shape
        degree = np.asarray(degree).reshape(-1)
        if degree.shape[0] != n:
            raise ValueError(
                f"degree must have {n} entries, got {degree.shape[0]}"
            )
        if self.feature_order is not None:
            # feature_order maps node id -> translated row; scatter the
            # new degrees into translated row order
            deg_t = np.zeros(n, dtype=np.int64)
            deg_t[np.asarray(self.feature_order).astype(np.int64)] = degree
        else:
            deg_t = degree.astype(np.int64)
        device_rows = self.rep_rows + self.hot_rows
        self.last_tier_hits = np.array(
            [deg_t[: self.rep_rows].sum(),
             deg_t[self.rep_rows: device_rows].sum(),
             deg_t[device_rows:].sum()],
        )
        self._maybe_auto_split()

    # graftlint: eager -- between-batch split tuner; under trace the hits
    def _maybe_auto_split(self) -> None:  # int() raises and except returns
        """Compat shim: feed the measured hit distribution to the
        attached :class:`~quiver_tpu.control.CacheController`'s
        :class:`~quiver_tpu.control.SplitTuner` and actuate its L0/L1
        boundary decision (``auto_split=True`` lazily creates a default
        controller on first call — the legacy opt-in keeps working with
        no code change).

        Consumes ``last_tier_hits`` (the previous eager batch — long
        completed, so the read is cheap). The tuner's signals are the
        rules this method used to hard-code — grow (double ``rep_rows``,
        up to the budget ceiling) when the hit mass sits just beyond the
        boundary, shrink (halve) when L0 is not earning its F× HBM —
        plus a reversal dead-band so a noisy batch at the ceiling cannot
        oscillate the boundary (see ``control/controller.py``).
        """
        hits = self.last_tier_hits
        if hits is None or self._region_host is None:
            return
        ctl = self._controller
        if ctl is None:
            if not self.auto_split:
                return
            from ..control import CacheController  # lazy: no import cycle
            ctl = CacheController.for_store(self)
        self.last_tier_hits = None
        try:
            h0, h1, _hc = (int(v) for v in np.asarray(hits))
        except Exception:  # noqa: BLE001 — a deleted/donated buffer must
            return  # not break the next gather
        total = self._region_host.shape[0]
        ceiling = min(self._rep_ceiling_rows, total)
        new = ctl.decide_split(h0, h1, self.rep_rows, ceiling)
        if new is None or new == self.rep_rows:
            return
        get_logger("feature").info(
            "auto-split: L0 %d vs sharded %d hits; moving "
            "replicated/sharded boundary %d -> %d rows",
            h0, h1, self.rep_rows, new,
        )
        self._resplit_from_tuner = True
        try:
            self.resplit(new)
        finally:
            self._resplit_from_tuner = False

    def delete(self) -> None:
        """Free all tier buffers now (reference ``shard_tensor.delete``)."""
        if self.hot is not None:
            self.hot.delete()
        for buf in (self.rep, self.cold, self.feature_order, self.scale):
            if buf is not None and hasattr(buf, "delete"):
                buf.delete()
        self.rep = self.hot = self.cold = None
        self.feature_order = self.scale = None
        self.rep_rows = self.hot_rows = 0
        self.last_tier_hits = None
        self._region_host = None

    def __getitem__(self, n_id):
        """Gather rows for data-axis-sharded (or replicated) node ids."""
        return self.gather(n_id)

    @property
    def last_routed_overflow(self):
        """Fallback-served lane count of the hot tier's last capped routed
        gather (device scalar; None before any routed call)."""
        return None if self.hot is None else self.hot.last_routed_overflow

    def gather(self, n_id, routed: bool = False, routed_cap="auto"):
        """Three-tier gather (replicated L0 / sharded L1 / host cold);
        ``routed=True`` uses the owner-routed L1 flavor (ids sharded over
        every mesh axis — see ShardedTensor.gather) instead of the psum
        flavor. ``routed_cap`` selects the routed comm mode ("auto" =
        capped buckets at ``ceil(routed_alpha*L/F)`` with auto-grow on
        overflow, None = uncapped full-length buckets, int = explicit
        capacity); overflow is fallback-served and counted in
        ``last_routed_overflow``.

        L0 and cold lanes enter the L1 gather as -1 (its invalid-lane
        sentinel), so they occupy zero routed-bucket capacity and
        contribute zero psum lanes — an L0 hit really does cost no
        interconnect. After an eager call ``last_tier_hits`` holds the
        batch's per-tier hit counts (int32 (3,)); with ``auto_split=True``
        the measured distribution moves the L0/L1 boundary before the next
        batch (:meth:`_maybe_auto_split`)."""
        if self.auto_split or self._controller is not None:
            self._maybe_auto_split()
        rep_gather = (
            None if self.rep is None else lambda ids: self.rep[ids]
        )
        hot_gather = (
            None if self.hot is None
            else lambda ids: self.hot.gather(
                ids, routed=routed, routed_cap=routed_cap
            )
        )
        cold_gather = (
            None
            if self.cold is None
            else lambda ids: staged_gather(self.cold, ids, self._cold_is_host)
        )
        # int8 tiers dequantize after the (local, psum'd, or routed)
        # gather; only one shard contributes non-zero int8 rows so the
        # reduction is overflow-free
        rep_gather, hot_gather, cold_gather = wrap_dequant_gathers(
            self.scale, self.hot_rows, hot_gather, cold_gather,
            rep_gather, self.rep_rows,
        )
        out, hits = tiered_lookup(
            n_id, self.feature_order, self.hot_rows, hot_gather, cold_gather,
            rep_rows=self.rep_rows, rep_gather=rep_gather, hot_miss_id=-1,
            with_hits=True,
        )
        if not isinstance(hits, jax.core.Tracer):
            # eager call: stash for the split tuner / benchmarks (an outer
            # jit's tracer must not leak; in-program callers use
            # tiered_lookup's with_hits return directly)
            self.last_tier_hits = hits
        return out
