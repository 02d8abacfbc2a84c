"""Tiered feature store: HBM hot tier + host-memory cold tier.

Capability parity with the reference's ``quiver.Feature``
(torch-quiver feature.py:29-308): byte-budget hot/cold split, optional
degree-based reorder so high-degree (hot) nodes fill the cache
(feature.py:112-116), ``feature_order`` id translation on lookup
(feature.py:184-195), and two placement policies. TPU redesign:

* ``device_replicate`` → hot rows replicated in each device's HBM (same
  policy, feature.py:120-124).
* ``p2p_clique_replicate`` → hot rows *sharded over the mesh* with gathers
  riding ICI collectives (see feature/shard.py) — ICI plays NVLink's role
  (feature.py:126-166, quiver_feature.cu gather over ``dev_ptrs``).
* UVA zero-copy cold tier → pinned-host-resident cold shard with staged
  host-compute gathers (feature.py:169-182; TPU kernels cannot dereference
  host pointers, SURVEY §2.3 mapping (3)).

No IPC machinery (share_ipc/lazy rebuild, feature.py:234-308): one process
controls the mesh. The methods exist as no-op parity shims.

Cold-lane trick: every lookup gathers both tiers at full batch width (static
shapes), but lanes belonging to the other tier are pointed at row 0, so the
host-side cost collapses to the true cold-miss count's bandwidth (repeated
row 0 stays in cache) rather than the batch width.

``tiered_lookup`` is the shared tier-merge: up to three contiguous tiers in
the translated row space (replicated super-hot / hot / cold — see
feature/shard.py for the three-tier ShardedFeature) with optional
in-program per-tier hit counting.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.config import CachePolicy, parse_size_bytes
from ..core.memory import to_pinned_host
from ..core.topology import CSRTopo
from ..ops.gather import padded_row_gather
from ..ops.sample import staged_gather
from ..utils.reorder import reorder_by_degree
from ..utils.trace import get_logger, info_once, trace_scope

__all__ = ["Feature", "HeteroFeature", "tiered_lookup"]


def _parse_storage_dtype(dtype):
    """None (keep input dtype) or a numpy dtype; "bf16"/"bfloat16" resolve
    through ml_dtypes (numpy has no native bfloat16; ml_dtypes ships with
    jax). int8 means per-row absmax quantization (scales kept alongside);
    other integer dtypes are rejected — a plain astype would truncate float
    features to garbage silently."""
    if dtype is None:
        return None
    if str(dtype) in ("bf16", "bfloat16"):
        from ml_dtypes import bfloat16

        return np.dtype(bfloat16)
    dt = np.dtype(dtype)
    if dt == np.dtype(np.int8):
        return dt
    if dt.kind != "f":
        raise ValueError(
            f"storage dtype must be a float dtype, 'bfloat16', or 'int8' "
            f"(quantized); got {dtype!r}"
        )
    return dt


def quantize_rows_int8(tensor: np.ndarray):
    """Per-row symmetric absmax int8 quantization.

    Returns (q (N, F) int8, scale (N,) float32) with
    ``row ~= q * scale[:, None]``; all-zero rows get scale 0 (and dequantize
    to exact zeros). Worst-case per-element error is scale/2 — bounded by
    0.4% of the row's absmax.
    """
    absmax = np.abs(tensor).max(axis=1).astype(np.float32)
    scale = absmax / 127.0
    safe = np.where(scale > 0, scale, 1.0)
    q = np.clip(
        np.round(tensor / safe[:, None]), -127, 127
    ).astype(np.int8)
    return q, scale


def _dequant_fn(gather, scale_for):
    """Wrap an int8 row gather with on-device dequantization."""
    return lambda ids: gather(ids).astype(jnp.float32) * scale_for(ids)[:, None]


def wrap_dequant_gathers(scale, hot_rows: int, hot_gather, cold_gather,
                         rep_gather=None, rep_rows: int = 0):
    """Shared int8-dequant wrapping for the feature stores' tiered gathers.

    Scale ids live in the translated (reordered) global row space; each
    tier's gather receives ids local to its own table, so the scale lookup
    re-offsets them: replicated rows sit at [0, rep_rows), sharded-hot rows
    at [rep_rows, rep_rows + hot_rows), cold rows above. No-op when
    ``scale`` is None (unquantized storage).

    Returns ``(rep_gather, hot_gather, cold_gather)``.
    """
    if scale is None:
        return rep_gather, hot_gather, cold_gather
    if rep_gather is not None:
        rep_gather = _dequant_fn(rep_gather, lambda ids: scale[ids])
    if hot_gather is not None:
        hot_gather = _dequant_fn(
            hot_gather, lambda ids: scale[ids + rep_rows]
        )
    if cold_gather is not None:
        cold_gather = _dequant_fn(
            cold_gather, lambda ids: scale[ids + rep_rows + hot_rows]
        )
    return rep_gather, hot_gather, cold_gather


def validate_gather_kernel(kernel: str) -> None:
    """The stores' ``kernel=`` keyword: ``"auto"`` and ``"xla"`` are the one
    gather there is (``table[ids]``). Kept because the benchmark passes it
    by name (ROADMAP D14). Touches no backend."""
    if kernel == "pallas":
        raise ValueError(
            "kernel='pallas' was removed: the Pallas row gather lost to "
            "XLA's on the v5e, 12.5 against 35.1 GB/s and 10.2 against "
            "34.6 (CHANGES.md, PR 21); pass 'auto' or 'xla'"
        )
    if kernel not in ("auto", "xla"):
        raise ValueError(f"kernel must be 'auto' or 'xla', got {kernel!r}")


@trace_scope("feature_gather")
def tiered_lookup(n_id, feature_order, hot_rows: int, hot_gather, cold_gather,
                  rep_rows: int = 0, rep_gather=None, hot_miss_id: int = 0,
                  with_hits: bool = False):
    """Shared tier-merge of every feature store, under ``feature_gather``.

    The one place that opens that scope, for ``Feature``, ``ShardedFeature``,
    ``MmapFeatureStore`` and the fused step alike; each tier's gather runs
    under ``tier_rep`` / ``tier_hot`` / ``tier_cold`` below it, with
    whatever its callable does (dequant, psum, routing, host staging).

    Three contiguous tiers in the translated (reordered) row space:

    * replicated super-hot ``[0, rep_rows)`` — ``rep_gather`` (zero-comm
      local gather, every device holds the full block);
    * hot ``[rep_rows, rep_rows + hot_rows)`` — ``hot_gather`` (HBM; sharded
      stores serve it with a psum or routed collective);
    * cold ``[rep_rows + hot_rows, n)`` — ``cold_gather`` (host-staged).

    Each gather is a callable (tier-local ids) -> rows; any may be None
    (its boundary range is then empty or covered by a neighbor). Invalid
    lanes (-1) return zero rows; lanes belonging to another tier are pointed
    at row 0 so their bandwidth collapses to one cached row — except the
    hot tier's, which carry ``hot_miss_id`` (pass -1 for the sharded
    gathers: their documented invalid-lane sentinel keeps other-tier lanes
    out of the routed buckets and the psum, so they cost zero collective
    lanes instead of a redundant row-0 fetch).

    ``with_hits=True`` additionally returns an int32 ``(3,)`` vector of
    VALID lanes per tier boundary ``[replicated, hot, cold]`` — the local
    per-tier hit counts (callers inside ``shard_map`` psum them).
    """
    n_id = jnp.asarray(n_id)
    valid = n_id >= 0
    ids = jnp.where(valid, n_id, 0)
    if feature_order is not None:
        ids = feature_order[ids]
    hot_end = rep_rows + hot_rows
    have_rep = rep_gather is not None and rep_rows > 0
    # (scope, mask, gather, row offset into the tier's table, other-tier
    # miss id); masks partition the valid id range, in tier order
    tiers = []
    if have_rep:
        tiers.append(("tier_rep", ids < rep_rows, rep_gather, 0, 0))
    if hot_gather is not None:
        m = ids < hot_end
        if have_rep:
            m = m & (ids >= rep_rows)
        tiers.append(("tier_hot", m, hot_gather, rep_rows, hot_miss_id))
    if cold_gather is not None:
        tiers.append(("tier_cold", ids >= hot_end, cold_gather, hot_end, 0))
    if len(tiers) == 1:
        scope, _, gather, off, _ = tiers[0]
        with trace_scope(scope):
            out = gather(ids - off if off else ids)
    else:
        out = None
        for scope, mask, gather, off, miss in tiers:
            with trace_scope(scope):
                part = gather(jnp.where(mask, ids - off, miss))
            out = part if out is None else jnp.where(mask[:, None], part, out)
    out = jnp.where(valid[:, None], out, 0)
    if not with_hits:
        return out
    hits = jnp.stack([
        jnp.sum((valid & (ids < rep_rows)).astype(jnp.int32)),
        jnp.sum((valid & (ids >= rep_rows) & (ids < hot_end)).astype(jnp.int32)),
        jnp.sum((valid & (ids >= hot_end)).astype(jnp.int32)),
    ])
    return out, hits


@jax.tree_util.register_pytree_node_class
class Feature:
    """Tiered node-feature table with jit-compatible lookup.

    Args mirror the reference's constructor (feature.py:29-44):
      rank, device_list: accepted-and-INERT parity slots. The reference
        pins one CUDA device per process rank; under single-controller
        SPMD the mesh owns placement, so these only survive as attributes
        for call-site compatibility — nothing reads them.
      device_cache_size: hot-tier byte budget ("0.9M", "3GB", int bytes).
      cache_policy: "device_replicate" | "p2p_clique_replicate"/"mesh_shard".
      csr_topo: enables degree-based hot ordering; sets csr_topo.feature_order.
      replicate_budget: L0 super-hot byte budget (same parser). Under
        device_replicate the whole hot tier is ALREADY a zero-comm
        per-device replica, so the L0/L1 distinction collapses: the bytes
        are folded into ``device_cache_size`` (one-shot INFO log). The
        argument exists so policy configs port unchanged between Feature
        and ShardedFeature, where L0 is a real third tier.
    """

    def __init__(
        self,
        rank: int = 0,
        device_list=None,
        device_cache_size: int | str = 0,
        cache_policy: str | CachePolicy = CachePolicy.DEVICE_REPLICATE,
        csr_topo: CSRTopo | None = None,
        hot_shuffle_seed: int = 0,
        kernel: str = "auto",
        dtype=None,
        replicate_budget: int | str = 0,
    ):
        self.rank = rank
        self.device_list = device_list or [0]
        if rank != 0 or (device_list is not None and list(device_list) != [0]):
            # reference-ported code gets a runtime signal that its device
            # pinning did nothing (VERDICT r5 weak #7)
            info_once(
                "feature-inert-parity-args",
                "Feature(rank=%r, device_list=%r) accepted for reference "
                "API parity but INERT: under single-controller SPMD the "
                "mesh owns placement; nothing reads these arguments",
                rank, device_list, child="feature",
            )
        self.cache_budget = parse_size_bytes(device_cache_size)
        self.replicate_budget = parse_size_bytes(replicate_budget)
        if self.replicate_budget:
            # device_replicate's hot tier is already replicated per device —
            # there is no cheaper tier to promote rows into, so the L0
            # budget simply buys more hot rows
            info_once(
                "feature-replicate-budget-folded",
                "Feature(device_replicate) already replicates its hot tier "
                "per device; replicate_budget=%d B folded into "
                "device_cache_size (one zero-comm tier)",
                self.replicate_budget, child="feature",
            )
            self.cache_budget += self.replicate_budget
        self.cache_policy = CachePolicy.parse(cache_policy)
        self.csr_topo = csr_topo
        self.hot_shuffle_seed = hot_shuffle_seed
        validate_gather_kernel(kernel)
        # storage dtype override: "bfloat16" halves the byte budget per row
        # (so ~2x rows fit the same HBM cache and every gather moves half
        # the bytes) — the TPU-first answer to the reference's hardcoded
        # float32 ShardTensor (quiver_feature.cu:65-74). None keeps the
        # input dtype.
        self.storage_dtype = _parse_storage_dtype(dtype)
        # populated by from_cpu_tensor
        self.hot = None
        self.cold = None
        self.feature_order = None
        self.scale = None  # (N,) per-row dequant scales (int8 storage only)
        self.hot_rows = 0
        self.shape = None
        self.dtype = None
        self._cold_is_host = False

    # -- construction -------------------------------------------------------

    def from_cpu_tensor(self, tensor) -> "Feature":
        """Split, (optionally) reorder, and place the feature table."""
        if self.cache_policy is CachePolicy.MESH_SHARD:
            raise NotImplementedError(
                "mesh_shard placement lives in quiver_tpu.feature.shard."
                "ShardedFeature; plain Feature supports device_replicate only"
            )
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
        if quantized:
            # the (N,) float32 dequant-scale array lives in HBM for BOTH
            # tiers (cold gathers dequantize on device too) — charge all
            # N*4 scale bytes to the budget up front, then spend the rest
            # on 1-byte-per-element hot rows
            row_bytes = f
            hot_rows = min(n, max(self.cache_budget - 4 * n, 0) // row_bytes)
        else:
            row_bytes = f * tensor.dtype.itemsize
            hot_rows = min(n, self.cache_budget // row_bytes)

        if self.csr_topo is not None and hot_rows < n:
            hot_ratio = hot_rows / n
            tensor, order = reorder_by_degree(
                tensor, self.csr_topo.degree, hot_ratio, seed=self.hot_shuffle_seed
            )
            self.csr_topo.feature_order = order
            self.feature_order = jnp.asarray(order)

        scale = None
        if quantized:
            tensor, scale = quantize_rows_int8(tensor)  # AFTER the reorder
            self.scale = jnp.asarray(scale)  # (N,) stays in HBM (4B/row)

        self.shape = (n, f)
        self.dtype = tensor.dtype
        self.hot_rows = int(hot_rows)
        if hot_rows > 0:
            self.hot = jnp.asarray(tensor[:hot_rows])
        if hot_rows < n:
            self.cold, self._cold_is_host = to_pinned_host(tensor[hot_rows:])
        # placement report (the reference's LOG>>> cache-% print, feature.py:109-111)
        get_logger("feature").info(
            "%.2f%% of feature (%d/%d rows, %.1f MB) cached in HBM "
            "(device_replicate); cold tier: %s",
            100.0 * hot_rows / max(n, 1),
            hot_rows,
            n,
            hot_rows * row_bytes / 2**20,
            "pinned host" if self._cold_is_host else ("none" if hot_rows == n else "device"),
        )
        return self

    @classmethod
    def from_numpy(cls, tensor, **kwargs) -> "Feature":
        return cls(**kwargs).from_cpu_tensor(tensor)

    # -- lookup -------------------------------------------------------------

    def __getitem__(self, n_id):
        """Gather rows for (possibly padded, -1 sentinel) node ids.

        Jit-composable; invalid lanes return zero rows. The hot tier's row
        gather pads its lanes so that it keeps 256 rows in flight
        (``ops.gather.padded_row_gather``; PERF.md section 7 (3)).
        """
        hot_gather = (
            None if self.hot is None
            else lambda ids: padded_row_gather(self.hot, ids)
        )
        cold_gather = (
            None
            if self.cold is None
            else lambda ids: staged_gather(self.cold, ids, self._cold_is_host)
        )
        _, hot_gather, cold_gather = wrap_dequant_gathers(
            self.scale, self.hot_rows, hot_gather, cold_gather
        )
        return tiered_lookup(
            n_id, self.feature_order, self.hot_rows, hot_gather, cold_gather
        )

    def size(self, dim: int) -> int:
        return self.shape[dim]

    @property
    def cache_ratio(self) -> float:
        return self.hot_rows / self.shape[0] if self.shape else 0.0

    # -- pytree (so Feature can be closed over / passed into jit) ----------

    def tree_flatten(self):
        children = (self.hot, self.cold, self.feature_order, self.scale)
        aux = (
            self.rank,
            tuple(self.device_list),
            self.cache_budget,
            self.cache_policy,
            self.hot_rows,
            self.shape,
            self.dtype,
            self._cold_is_host,
            self.hot_shuffle_seed,
            self.storage_dtype,
            self.replicate_budget,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        obj = cls.__new__(cls)
        obj.hot, obj.cold, obj.feature_order, obj.scale = children
        (
            obj.rank,
            device_list,
            obj.cache_budget,
            obj.cache_policy,
            obj.hot_rows,
            obj.shape,
            obj.dtype,
            obj._cold_is_host,
            obj.hot_shuffle_seed,
            obj.storage_dtype,
            obj.replicate_budget,
        ) = aux
        obj.device_list = list(device_list)
        obj.csr_topo = None
        return obj

    def delete(self) -> None:
        """Free the device/host buffers now (reference ``shard_tensor.delete``,
        SURVEY §2.5 — planned there, real here). The object is unusable after."""
        for buf in (self.hot, self.cold, self.feature_order, self.scale):
            if buf is not None and hasattr(buf, "delete"):
                buf.delete()
        self.hot = self.cold = self.feature_order = self.scale = None
        self.hot_rows = 0

    # -- reference API shims (IPC is a no-op under single-controller SPMD) --

    def share_ipc(self):
        return self

    @classmethod
    def new_from_ipc_handle(cls, rank, handle):
        return handle

    @classmethod
    def lazy_from_ipc_handle(cls, handle):
        return handle


class HeteroFeature:
    """Per-node-type feature tables for heterogeneous graphs.

    A thin dict-of-Feature: ``__getitem__`` takes the sampler's ``n_id``
    dict and returns {type: rows} — each type's table keeps its own tiering
    policy (hot/cold budget, reorder) independently.
    """

    def __init__(self, features: dict):
        self.features = dict(features)

    @classmethod
    def from_cpu_tensors(cls, tensors: dict, **feature_kwargs) -> "HeteroFeature":
        return cls({
            t: Feature(**feature_kwargs).from_cpu_tensor(arr)
            for t, arr in tensors.items()
        })

    def __getitem__(self, n_id_dict: dict) -> dict:
        return {t: self.features[t][ids] for t, ids in n_id_dict.items()}

    def size(self, node_type: str, dim: int) -> int:
        return self.features[node_type].size(dim)
