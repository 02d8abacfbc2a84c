"""MmapFeatureStore — disk-backed feature rows, bitwise-equal gathers.

The in-RAM :class:`~quiver_tpu.feature.feature.Feature` ends at host
memory: its cold tier is a pinned-host array holding EVERY beyond-budget
row. This store pushes that tail one tier down — the full post-reorder,
post-quantize row table lives on disk in the raw format
(:mod:`~quiver_tpu.ooc.format`), and a gather touches only the pages it
needs:

* **hot tier** — translated rows ``[0, hot_rows)``, materialized into
  HBM once at open (identical bytes to Feature's hot tier);
* **host cold cache** — an OPTIONAL, arbitrary set of promoted disk rows
  pinned in host RAM (``host_cache_rows`` budget; quiver-ctl restages it
  to the FreqSketch's measured-hottest rows via :meth:`restage`);
* **disk tier** — everything, window-read through an
  :class:`~quiver_tpu.ooc.stager.AsyncStager`.

Bitwise identity with Feature is by construction, not by tolerance: the
write path (:meth:`write`) runs the SAME split/reorder/quantize
decisions as ``Feature.from_cpu_tensor`` (same budget arithmetic, same
``reorder_by_degree`` seed, quantize-after-reorder), and the lookup path
reuses the SAME ``tiered_lookup`` merge with the SAME hot gather and
dequant wrapping. The only difference is where the cold tier's bytes
come from: Feature gathers them from a device-resident table inside the
program; this store assembles the lane-aligned cold block on the host
(cache + windowed disk reads) and hands it to the identical merge — the
values per lane are the same bytes, so batches, losses and telemetry
match bit-for-bit (tests/test_ooc.py differentials).

Consequence: lookups are EAGER (host staging cannot be traced), which is
exactly the unfused ``DataParallelTrainer``/``Prefetcher`` path — the
reference's flagship papers100M architecture. The fused trainer keeps
its in-RAM stores.

Address-space modes: ``access="mmap"`` (default) backs the row table
onto ``np.memmap`` — resident bytes O(touched pages), virtual bytes
O(file). ``access="pread"`` never maps the file at all — windows are
``os.pread`` into pooled buffers, so VIRTUAL address space stays
O(cache_windows * window_bytes); this is the mode the rlimit'd drill
(benchmarks/ooc_drill.py) runs under ``resource.setrlimit(RLIMIT_AS)``
to make "the graph does not fit" mechanical.
"""

from __future__ import annotations

import os

import numpy as np

import jax.numpy as jnp

from ..core.config import parse_size_bytes
from ..feature.feature import (
    _parse_storage_dtype,
    quantize_rows_int8,
    tiered_lookup,
    wrap_dequant_gathers,
)
from ..utils.reorder import reorder_by_degree
from ..utils.trace import get_logger, trace_scope
from .format import load_raw_dir, npy_data_offset, save_raw_dir
from .stager import AsyncStager

__all__ = ["MmapFeatureStore"]

_ACCESS_MODES = ("mmap", "pread")
_ROWS_KIND = "quiver-ooc-feature-rows"


class MmapFeatureStore:
    """Open a :meth:`write`-prepared raw feature directory for lookups.

    Args:
      path: raw-format directory written by :meth:`write`.
      access: "mmap" (np.memmap window slices) or "pread"
        (positioned reads, zero file mappings — the rlimit-drill mode).
      window_rows: rows per disk read (the readahead granularity).
      cache_windows: stager LRU capacity in windows.
      host_cache_rows: byte-budget-free row count for the promoted host
        cold cache ("0" disables; quiver-ctl fills it via restage()).
      retries/backoff/backoff_cap/jitter/retry_seed: stager resilience
        knobs (the Prefetcher contract).
      metrics/timeline: graftscope registry + StepTimeline for the
        ``ooc.*`` counters and stages.
    """

    def __init__(self, path: str, access: str = "mmap",
                 window_rows: int = 1024, cache_windows: int = 32, host_cache_rows: int = 0,
                 retries: int = 0, backoff: float = 0.05,
                 backoff_cap: float = 2.0, jitter: float = 0.5,
                 retry_seed: int = 0, metrics=None, timeline=None):
        if access not in _ACCESS_MODES:
            raise ValueError(
                f"access must be one of {_ACCESS_MODES}, got {access!r}"
            )
        self.path = str(path)
        self.access = access
        self.metrics = metrics
        self.timeline = timeline
        # structural checks only: the manifest CRCs were computed at
        # write time and a full sweep would page the whole table in —
        # run ooc.verify_raw_dir(path) when bytes are suspect
        arrays, meta = load_raw_dir(self.path, mmap=True, verify=False)
        if meta.get("kind") != _ROWS_KIND:
            raise ValueError(
                f"{path}: not a feature-rows raw dir "
                f"(kind={meta.get('kind')!r}); write one with "
                f"MmapFeatureStore.write()"
            )
        rows = arrays["rows"]
        n, f = rows.shape
        self.shape = (n, f)
        self.dtype = rows.dtype
        self.hot_rows = int(meta["hot_rows"])
        self.cache_budget = int(meta.get("cache_budget", 0))
        # scale/feature_order are O(N) metadata tiers, resident like
        # Feature's (the O(graph) rows are what stays on disk)
        self.scale = None
        if "scale" in arrays:
            self.scale = jnp.asarray(np.asarray(arrays["scale"]))
        self.feature_order = None
        self._order_np = None
        if "feature_order" in arrays:
            order = np.asarray(arrays["feature_order"])
            self.feature_order = jnp.asarray(order)
            self._order_np = order
        self.hot = None
        if self.hot_rows > 0:
            self.hot = jnp.asarray(np.asarray(rows[:self.hot_rows]))
        self._cold_rows = n - self.hot_rows
        self._rows_mm = None
        self._fd = -1
        self._data_offset = 0
        rows_path = os.path.join(self.path, "rows.npy")
        if access == "mmap":
            self._rows_mm = rows
        else:
            _, _, self._data_offset = npy_data_offset(rows_path)
            self._fd = os.open(rows_path, os.O_RDONLY)
        # promoted host cold cache: translated cold-local row ids
        # (sorted) + their rows, restaged by quiver-ctl between batches
        self.host_cache_rows = int(host_cache_rows)
        self._cache_ids = np.empty(0, np.int64)
        self._cache_block = None
        self.cold_cache_hits_total = 0
        self.stager = None
        if self._cold_rows > 0:
            num_windows = -(-self._cold_rows // int(window_rows))
            self.stager = AsyncStager(
                self._read_window, num_windows=num_windows,
                window_rows=int(window_rows),
                cache_windows=int(cache_windows), retries=retries,
                backoff=backoff, backoff_cap=backoff_cap, jitter=jitter,
                retry_seed=retry_seed, metrics=metrics, timeline=timeline,
            )
        get_logger("ooc").info(
            "opened %s: %d rows x %d (%s, %s), hot=%d on device, "
            "cold=%d on disk (window=%d rows, cache=%d windows), host "
            "cache budget=%d rows",
            path, n, f, self.dtype, access, self.hot_rows,
            self._cold_rows, int(window_rows), int(cache_windows),
            self.host_cache_rows,
        )

    # -- write side ----------------------------------------------------------

    @classmethod
    def write(cls, path: str, tensor, device_cache_size: int | str = 0,
              csr_topo=None, dtype=None,
              hot_shuffle_seed: int = 0) -> dict:
        """Prepare a raw feature directory from an in-RAM table.

        Runs EXACTLY ``Feature.from_cpu_tensor``'s placement decisions —
        same byte-budget arithmetic (int8 charges the (N,) scale tier
        first), same degree reorder at the same hot ratio and seed,
        quantization AFTER the reorder — then publishes the
        post-processed row table (plus scale/feature_order) atomically
        in the raw format. A Feature built from the same inputs and an
        MmapFeatureStore opened on this directory hold identical bytes
        in every tier. Sets ``csr_topo.feature_order`` like the Feature
        path does. Returns the manifest.
        """
        tensor = np.asarray(tensor)
        storage_dtype = _parse_storage_dtype(dtype)
        quantized = (
            storage_dtype is not None
            and storage_dtype == np.dtype(np.int8)
        )
        if (
            storage_dtype is not None
            and not quantized
            and tensor.dtype != storage_dtype
        ):
            tensor = tensor.astype(storage_dtype)
        n, f = tensor.shape
        cache_budget = parse_size_bytes(device_cache_size)
        if quantized:
            row_bytes = f
            hot_rows = min(n, max(cache_budget - 4 * n, 0) // row_bytes)
        else:
            row_bytes = f * tensor.dtype.itemsize
            hot_rows = min(n, cache_budget // row_bytes)

        order = None
        if csr_topo is not None and hot_rows < n:
            hot_ratio = hot_rows / n
            tensor, order = reorder_by_degree(
                tensor, csr_topo.degree, hot_ratio, seed=hot_shuffle_seed
            )
            csr_topo.feature_order = order

        scale = None
        if quantized:
            tensor, scale = quantize_rows_int8(tensor)  # AFTER the reorder

        arrays = {"rows": tensor}
        if scale is not None:
            arrays["scale"] = scale
        if order is not None:
            arrays["feature_order"] = order
        meta = {
            "kind": _ROWS_KIND,
            "shape": [int(n), int(f)],
            "storage_dtype": str(tensor.dtype),
            "hot_rows": int(hot_rows),
            "cache_budget": int(cache_budget),
            "hot_shuffle_seed": int(hot_shuffle_seed),
            "quantized": bool(quantized),
        }
        return save_raw_dir(path, arrays, meta)

    # -- disk access ---------------------------------------------------------

    def _read_window(self, window: int) -> np.ndarray:
        """One window of cold-tier rows (cold-local row space); runs on
        the stager's worker thread."""
        w = self.stager.window_rows
        lo = window * w
        hi = min(lo + w, self._cold_rows)
        if self.access == "mmap":
            return np.array(self._rows_mm[self.hot_rows + lo:
                                          self.hot_rows + hi])
        n, f = self.shape
        row_bytes = f * self.dtype.itemsize
        offset = self._data_offset + (self.hot_rows + lo) * row_bytes
        nbytes = (hi - lo) * row_bytes
        buf = b""
        while len(buf) < nbytes:  # pread may return short on some fs
            chunk = os.pread(self._fd, nbytes - len(buf), offset + len(buf))
            if not chunk:
                raise OSError(
                    f"{self.path}: short read at offset {offset} "
                    f"({len(buf)}/{nbytes} B)"
                )
            buf += chunk
        return np.frombuffer(buf, self.dtype).reshape(hi - lo, f)

    def _gather_cold(self, cold_local: np.ndarray) -> np.ndarray:
        """Lane-aligned cold block: host cache hits + staged disk reads."""
        out = None
        pending = np.ones(cold_local.shape, bool)
        if self._cache_ids.size:
            pos = np.searchsorted(self._cache_ids, cold_local)
            pos_c = np.minimum(pos, self._cache_ids.size - 1)
            hit = self._cache_ids[pos_c] == cold_local
            if hit.any():
                out = np.empty(
                    cold_local.shape + self.shape[1:2], self.dtype
                )
                out[hit] = self._cache_block[pos_c[hit]]
                pending &= ~hit
                self.cold_cache_hits_total += int(hit.sum())
        if pending.any():
            block = self.stager.fetch(cold_local[pending])
            if out is None:
                out = np.empty(
                    cold_local.shape + block.shape[1:], block.dtype
                )
            out[pending] = block
        return out

    # -- lookup --------------------------------------------------------------

    def _cold_local(self, n_id) -> np.ndarray | None:
        """Host-side mirror of tiered_lookup's cold-tier id routing:
        valid lanes translate through feature_order; other-tier and
        invalid lanes point at cold row 0 (the cold-lane trick), so the
        assembled block is lane-for-lane what Feature's device gather
        reads."""
        if self._cold_rows <= 0:
            return None
        ids = np.asarray(n_id).reshape(-1)
        ids = np.where(ids >= 0, ids, 0)
        if self._order_np is not None:
            ids = np.asarray(self._order_np[ids], np.int64)
        return np.where(ids >= self.hot_rows, ids - self.hot_rows, 0)

    def __getitem__(self, n_id):
        """Gather rows for (possibly padded, -1 sentinel) node ids.

        Eager (host-staged disk reads); bitwise-identical to the in-RAM
        Feature's lookup — same translated row space, same tier merge,
        same dequant wrapping.
        """
        cold_local = self._cold_local(n_id)
        cold_gather = None
        if cold_local is not None:
            with trace_scope("ooc_stage"):
                block = jnp.asarray(self._gather_cold(cold_local))
            # lane-aligned: tiered_lookup's traced cold ids reproduce
            # exactly the routing _cold_local ran on the host, so the
            # block IS the gather's result (the dequant wrapper still
            # consumes the traced ids for its scale lookup)
            cold_gather = lambda ids: block  # noqa: E731
        hot_gather = None if self.hot is None else lambda ids: self.hot[ids]
        _, hot_gather, cold_gather = wrap_dequant_gathers(
            self.scale, self.hot_rows, hot_gather, cold_gather
        )
        return tiered_lookup(
            n_id, self.feature_order, self.hot_rows, hot_gather,
            cold_gather,
        )

    def trace_lookup(self, batch: int):
        """AOT-trace the device-side tier merge one staged batch runs —
        the SAME ``tiered_lookup`` + dequant wrapping as
        :meth:`__getitem__`, with the host-assembled cold block as a
        program *operand* (host staging cannot be traced). No disk
        I/O, no execution: this is the graftmem audit surface for the
        out-of-core path (``mmap_tiered_gather``), so the merge's
        per-device bytes are provable without paging the table in."""
        import jax

        operands = [jax.ShapeDtypeStruct((int(batch),), jnp.int32)]
        if self._cold_rows > 0:
            operands.append(jax.ShapeDtypeStruct(
                (int(batch), self.shape[1]), self.dtype))

        def merged(n_id, *staged):
            cold_gather = None
            if staged:
                block = staged[0]
                cold_gather = lambda ids: block  # noqa: E731
            hot_gather = (
                None if self.hot is None else lambda ids: self.hot[ids]
            )
            _, hot_gather, cold_gather = wrap_dequant_gathers(
                self.scale, self.hot_rows, hot_gather, cold_gather
            )
            return tiered_lookup(
                n_id, self.feature_order, self.hot_rows, hot_gather,
                cold_gather,
            )

        return jax.jit(merged).trace(*operands)

    def prefetch(self, n_id) -> int:
        """Dispatch background disk reads for a FUTURE batch's cold rows
        (bounded; returns reads issued). The overlap seam: call with
        batch t+1's ids while batch t trains."""
        cold_local = self._cold_local(n_id)
        if cold_local is None:
            return 0
        return self.stager.prefetch(cold_local)

    # -- promoted host cold cache (quiver-ctl's seam) ------------------------

    def restage(self, cold_local_ids) -> int:
        """Replace the host cold cache with ``cold_local_ids`` (cold-tier
        row space), reading newly promoted rows through the stager.
        Capped at ``host_cache_rows``; rows not in the new set spill back
        to disk-only (their bytes were never mutated — dropping the copy
        IS the demotion). Returns the resident row count."""
        ids = np.unique(np.asarray(cold_local_ids, np.int64).reshape(-1))
        ids = ids[(ids >= 0) & (ids < self._cold_rows)]
        if self.host_cache_rows > 0:
            ids = ids[:self.host_cache_rows]
        if ids.size == 0:
            self._cache_ids = np.empty(0, np.int64)
            self._cache_block = None
            return 0
        self._cache_block = self.stager.fetch(ids)
        self._cache_ids = ids
        return int(ids.size)

    @property
    def staged_ids(self) -> np.ndarray:
        """Current host-cache membership (cold-local row ids, sorted)."""
        return self._cache_ids

    # -- Feature-parity surface ----------------------------------------------

    def size(self, dim: int) -> int:
        return self.shape[dim]

    @property
    def cache_ratio(self) -> float:
        return self.hot_rows / self.shape[0] if self.shape else 0.0

    def close(self) -> None:
        if self.stager is not None:
            self.stager.close()
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1

    def __enter__(self) -> "MmapFeatureStore":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
