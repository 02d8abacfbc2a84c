"""Graph topology containers.

``CSRTopo`` is the host-side CSR graph container, capability-parity with the
reference's ``quiver.CSRTopo`` (torch-quiver utils.py:117-210): build from COO
``edge_index`` or from ``indptr``/``indices``, expose ``degree``/``eid``/
``feature_order``. Construction is pure numpy (no scipy needed — a stable
argsort plus bincount replaces the reference's ``scipy.sparse.csr_matrix``
round-trip, utils.py:107-114).

``DeviceTopology`` is the device-side view: a pytree of jnp arrays placed in
HBM (reference "GPU" mode) or pinned host memory (the TPU stand-in for the
reference's UVA zero-copy registration, quiver_sample.cu:400-408).
"""

from __future__ import annotations

import mmap
import os

import numpy as np

import jax
import jax.numpy as jnp

from .config import SampleMode
from .memory import to_pinned_host

__all__ = ["CSRTopo", "DeviceTopology", "VersionMismatchError"]


class VersionMismatchError(RuntimeError):
    """A consumer holds a placement of graph state (device CSR partition,
    feature tiers, a trainer's captured operands) whose ``version`` no
    longer matches the committed host state — a streaming mutation
    (``quiver_tpu.streaming``) published a new version since the placement
    was built. Raised instead of serving a silently stale read; call the
    consumer's ``refresh``/``refresh_topology`` seam to re-place."""


def _boundary_checks_enabled() -> bool:
    """O(E)/O(n) construction-boundary scans (index ranges, indptr
    monotonicity) run by DEFAULT — a corrupt CSR reaching XLA's clamping
    gathers turns into silently wrong samples, which is far worse than the
    scan. ``QUIVER_CHECK=0`` opts out for huge graphs on a hot rebuild
    path. (Asymmetric with models/layers: the *debug* trace assertions
    there default OFF; these *boundary* validations default ON. Host-side
    eager code — never trace-resident, so the env read per construction is
    trace-safe.)"""
    return os.environ.get("QUIVER_CHECK", "1") not in ("0", "false", "False")


def _as_numpy(x) -> np.ndarray:
    """Coerce array-likes (numpy, lists, torch CPU tensors) to numpy."""
    if hasattr(x, "detach"):  # torch tensor without importing torch
        x = x.detach().cpu().numpy()
    return np.asarray(x)


def _index_dtype(max_value: int) -> np.dtype:
    return np.dtype(np.int32) if max_value <= np.iinfo(np.int32).max else np.dtype(np.int64)


def _build_csr(row, col, node_count: int, use_native: bool):
    """COO -> CSR. Prefers the native linear-time parallel builder
    (native/quiver_host.cpp csr_from_coo); falls back to numpy stable
    argsort. Both are stable (CSR slots within a row follow COO order), so
    the two paths — and independent builds on different hosts — produce
    identical indices/eid arrays."""
    if use_native and node_count <= np.iinfo(np.int32).max:
        try:
            from ..native import available, csr_from_coo
        except ImportError:
            available = False
        if available:
            # real failures inside the native builder must propagate, not
            # silently fall back
            return csr_from_coo(row, col, node_count)
    order = np.argsort(row, kind="stable")
    counts = np.bincount(row, minlength=node_count)
    indptr = np.zeros(node_count + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, np.ascontiguousarray(col[order]), order


def _row_prefix_weights(w: np.ndarray, indptr: np.ndarray) -> np.ndarray:
    """Row-local inclusive prefix sums of CSR-ordered edge weights.

    The device-side weighted sampler inverse-CDF-searches these per row
    (the TPU analogue of the reference's per-node normalized prefix weights,
    cuda_random.cu.hpp:160-170). Rows whose total weight is <= 0 get the
    uniform prefix 1..deg so they degrade to uniform sampling instead of NaN.
    Computed in float64 (a global cumsum over E edges), emitted float32
    (row-local magnitudes only).
    """
    E = int(w.shape[0])
    deg = np.diff(indptr).astype(np.int64)
    starts = np.repeat(indptr[:-1].astype(np.int64), deg)  # row start per edge
    cw = np.cumsum(w, dtype=np.float64)
    base = np.where(starts > 0, cw[np.maximum(starts - 1, 0)], 0.0)
    prefix = cw - base
    ends = indptr[1:].astype(np.int64) - 1
    tot = np.where(deg > 0, prefix[np.maximum(ends, 0)], 0.0)
    bad = np.repeat(tot <= 0, deg)
    if bad.any():
        local = np.arange(E, dtype=np.int64) - starts
        prefix[bad] = (local[bad] + 1).astype(np.float64)
    return prefix.astype(np.float32)


def _time_sort_order(indptr: np.ndarray, times: np.ndarray) -> np.ndarray:
    """Permutation that stably sorts each CSR row's edges by timestamp.

    The temporal sampler binary-searches a ``[lo, hi]`` window per row, so
    rows must be time-nondecreasing; stability keeps the original CSR slot
    order as the tiebreak, which is what makes independently built
    replicated and sharded placements bitwise identical."""
    deg = np.diff(indptr).astype(np.int64)
    rows = np.repeat(np.arange(deg.shape[0], dtype=np.int64), deg)
    # lexsort: last key (rows) is primary, stable on equal (row, time) pairs
    return np.lexsort((times, rows))


class CSRTopo:
    """CSR graph topology with degree and feature-order bookkeeping.

    Parameters mirror the reference: either ``edge_index`` (2, E) COO, or
    ``indptr`` + ``indices`` directly. ``eid`` maps CSR edge slots back to
    the original COO edge positions (identity when built from indptr/indices).
    """

    def __init__(self, edge_index=None, indptr=None, indices=None, eid=None,
                 edge_weight=None, edge_time=None, edge_relation=None,
                 use_native: bool = True):
        if edge_index is not None:
            if indptr is not None or indices is not None:
                raise ValueError("pass either edge_index or indptr/indices, not both")
            edge_index = _as_numpy(edge_index)
            if edge_index.ndim != 2 or edge_index.shape[0] != 2:
                raise ValueError(f"edge_index must be (2, E), got {edge_index.shape}")
            row, col = edge_index[0], edge_index[1]
            if edge_index.size and min(row.min(), col.min()) < 0:
                # the native builder indexes raw ids; a stray -1 sentinel
                # must fail loudly here, not corrupt memory there
                raise ValueError("edge_index must not contain negative node ids")
            node_count = int(max(row.max(initial=-1), col.max(initial=-1)) + 1)
            indptr, indices, eid = _build_csr(row, col, node_count, use_native)
        elif indptr is not None and indices is not None:
            indptr = _as_numpy(indptr).astype(np.int64, copy=False)
            indices = _as_numpy(indices)
            if eid is not None:
                eid = _as_numpy(eid)
            # user-supplied CSR: validate, because XLA's clamping gathers
            # would otherwise turn inconsistencies into silently wrong samples
            if indptr.ndim != 1 or indptr.shape[0] < 1 or indptr[0] != 0:
                raise ValueError("indptr must be 1-D and start at 0")
            if indices.ndim != 1:
                raise ValueError(
                    f"indices must be 1-D, got shape {indices.shape}"
                )
            if _boundary_checks_enabled() and np.any(np.diff(indptr) < 0):
                raise ValueError("indptr must be non-decreasing")
            if int(indptr[-1]) != indices.shape[0]:
                raise ValueError(
                    f"indptr[-1]={int(indptr[-1])} != len(indices)={indices.shape[0]}"
                )
        else:
            raise ValueError("need edge_index or indptr+indices")

        node_count = int(indptr.shape[0] - 1)
        if indices.size and _boundary_checks_enabled():
            lo, hi = int(indices.min()), int(indices.max())
            if lo < 0:
                raise ValueError(
                    f"indices contain negative node id {lo}; CSR neighbor "
                    f"slots must reference nodes in [0, {node_count})"
                )
            if hi >= node_count:
                raise ValueError(
                    f"indices reference node {hi} but indptr only "
                    f"defines {node_count} nodes"
                )
        edge_count = int(indptr[-1])
        self._indptr = indptr.astype(_index_dtype(edge_count), copy=False)
        self._indices = indices.astype(_index_dtype(max(node_count - 1, 0)), copy=False)
        self._eid = None if eid is None else eid.astype(_index_dtype(max(edge_count - 1, 0)), copy=False)
        self._feature_order = None  # set by Feature's degree reorder
        self._edge_weight = None
        self._cum_weights = None
        self._edge_time = None
        self._edge_relation = None
        self._max_degree = None  # lazy cache (manifest-seeded on raw loads)
        # streaming-mutation version: bumped ONCE per committed transaction
        # (quiver_tpu.streaming); device placements capture the version they
        # were built from and raise VersionMismatchError instead of serving
        # a stale partition after a commit
        self._version = 0
        if edge_weight is not None:
            self.set_edge_weight(edge_weight, coo_order=edge_index is not None)
        if edge_time is not None:
            self.set_edge_time(edge_time, coo_order=edge_index is not None)
        if edge_relation is not None:
            self.set_edge_relation(edge_relation,
                                   coo_order=edge_index is not None)

    # -- properties (parity with reference utils.py:150-210) ---------------

    @property
    def indptr(self) -> np.ndarray:
        return self._indptr

    @property
    def indices(self) -> np.ndarray:
        return self._indices

    @property
    def eid(self) -> np.ndarray | None:
        return self._eid

    @property
    def feature_order(self) -> np.ndarray | None:
        """Old-node-id -> reordered-feature-row map, shared with Feature."""
        return self._feature_order

    @feature_order.setter
    def feature_order(self, order):
        order = _as_numpy(order)
        if order.shape != (self.node_count,):
            raise ValueError(
                f"feature_order must have shape ({self.node_count},), got {order.shape}"
            )
        self._feature_order = order

    # -- edge weights (weighted sampling) -----------------------------------
    # The reference *plumbed* per-edge weights (inverse-CDF ``weight_sample``,
    # cuda_random.cu.hpp:143-186) but the weighted constructor is commented
    # out (quiver.cu.hpp:240-272), leaving the path unreachable. Here it is a
    # real, tested feature.

    def set_edge_weight(self, edge_weight, coo_order: bool = True) -> "CSRTopo":
        """Attach per-edge weights for weighted neighbor sampling.

        ``coo_order=True`` means weights align with the COO edge order this
        topology was built from (translated through ``eid``); otherwise they
        are taken to already be in CSR slot order.
        """
        w = _as_numpy(edge_weight).astype(np.float64, copy=False).reshape(-1)
        if w.shape[0] != self.edge_count:
            raise ValueError(
                f"edge_weight must have {self.edge_count} entries, got {w.shape[0]}"
            )
        if w.size and not (np.isfinite(w).all() and w.min() >= 0):
            # NaN is < 0-blind and would silently degenerate the CDF search
            raise ValueError("edge weights must be finite and non-negative")
        if coo_order and self._eid is not None:
            w = w[self._eid]
        self._edge_weight = w.astype(np.float32)
        self._cum_weights = _row_prefix_weights(w, self._indptr)
        return self

    @property
    def edge_weight(self) -> np.ndarray | None:
        """Per-edge weights in CSR slot order, or None if unweighted."""
        return self._edge_weight

    @property
    def cum_weights(self) -> np.ndarray | None:
        """Row-local inclusive prefix sums of edge weights (float32, CSR
        order); rows with non-positive total weight fall back to the uniform
        prefix 1..deg."""
        return self._cum_weights

    # -- edge timestamps (temporal sampling) ---------------------------------

    def set_edge_time(self, edge_time, coo_order: bool = True) -> "CSRTopo":
        """Attach per-edge timestamps for temporal (time-windowed) sampling.

        Each row's edges are stably re-sorted time-nondecreasing (``eid``
        and ``edge_weight`` follow the permutation; the weight prefix sums
        re-derive), so the sampler can binary-search a ``[lo, hi]`` window
        to a contiguous slot range per row. The re-sort changes CSR slot
        order — attach timestamps BEFORE building samplers or device
        placements. ``coo_order=True`` means timestamps align with the COO
        edge order this topology was built from (translated through
        ``eid``); otherwise they are taken in CSR slot order.
        """
        t = _as_numpy(edge_time).astype(np.float64, copy=False).reshape(-1)
        if t.shape[0] != self.edge_count:
            raise ValueError(
                f"edge_time must have {self.edge_count} entries, got {t.shape[0]}"
            )
        if t.size and not np.isfinite(t).all():
            # NaN compares false everywhere and would silently empty or
            # corrupt every window search
            raise ValueError("edge times must be finite")
        if coo_order and self._eid is not None:
            t = t[self._eid]
        t = t.astype(np.float32)
        order = _time_sort_order(self._indptr, t)
        self._indices = self._indices[order]
        self._edge_time = t[order]
        if self._eid is not None:
            self._eid = self._eid[order]
        if self._edge_weight is not None:
            self._edge_weight = self._edge_weight[order]
            self._cum_weights = _row_prefix_weights(
                self._edge_weight, self._indptr
            )
        if self._edge_relation is not None:
            self._edge_relation = self._edge_relation[order]
        return self

    @property
    def edge_time(self) -> np.ndarray | None:
        """Per-edge timestamps in CSR slot order (float32, rows sorted
        time-nondecreasing), or None if untimestamped."""
        return self._edge_time

    # -- edge relations (typed graphs) ---------------------------------------

    def set_edge_relation(self, edge_relation,
                          coo_order: bool = True) -> "CSRTopo":
        """Attach each edge's relation, a small non-negative integer: the
        edge type of a heterogeneous graph whose node types are contiguous
        id ranges of this one CSR. A sampler over the topology hands every
        sampled lane its edge's relation (``Adj.relation``).

        ``coo_order=True`` means the relations align with the COO edge
        order this topology was built from (translated through ``eid``);
        otherwise they are taken in CSR slot order. Stored ``int8``.
        """
        rel = _as_numpy(edge_relation).reshape(-1)
        if rel.shape[0] != self.edge_count:
            raise ValueError(
                f"edge_relation must have {self.edge_count} entries, got "
                f"{rel.shape[0]}"
            )
        if rel.dtype.kind not in "iu":
            raise ValueError(
                f"edge_relation must be integers, got dtype {rel.dtype}")
        if rel.size and not 0 <= int(rel.min()) <= int(rel.max()) <= 127:
            raise ValueError("edge relations must lie in [0, 127]")
        if coo_order and self._eid is not None:
            rel = rel[self._eid]
        self._edge_relation = rel.astype(np.int8)
        return self

    @property
    def edge_relation(self) -> np.ndarray | None:
        """Per-edge relations in CSR slot order (int8), or None."""
        return self._edge_relation

    @property
    def version(self) -> int:
        """Committed mutation version (0 for a freshly built topology;
        +1 per published ``quiver_tpu.streaming`` commit). Consumers
        compare their placed version against this to detect staleness."""
        return self._version

    def _publish_mutation(self, indptr: np.ndarray, indices: np.ndarray,
                          edge_weight: np.ndarray | None = None,
                          edge_time: np.ndarray | None = None) -> None:
        """Streaming-commit publish seam (``quiver_tpu.streaming`` only):
        swap in the merged, already-VERIFIED CSR arrays and bump the
        version — the single publication point of an atomic commit. Every
        array is built and checked aside before this runs; the method body
        is pure reference assignment plus per-row derived-array rebuilds on
        arrays no reader holds yet, so there is no window in which a reader
        can observe a half-applied merge. ``eid`` is dropped (COO
        provenance does not survive mutation); ``feature_order`` is kept
        (the node id space is invariant — streaming deltas never add or
        remove nodes). A weighted/timestamped topology must be published
        with matching merged attribute arrays (the streaming admission
        layer guarantees this by rejecting attribute-less deltas);
        timestamped rows are re-sorted time-nondecreasing, restoring the
        sampler's binary-search invariant after appends."""
        if (self._edge_weight is not None) != (edge_weight is not None):
            raise ValueError(
                "mutation publish must carry edge weights exactly when the "
                "topology is weighted (the streaming admission layer "
                "rejects mismatched deltas)"
            )
        if (self._edge_time is not None) != (edge_time is not None):
            raise ValueError(
                "mutation publish must carry edge times exactly when the "
                "topology is timestamped (the streaming admission layer "
                "rejects mismatched deltas)"
            )
        if self._edge_relation is not None:
            raise ValueError(
                "a topology with edge relations cannot be mutated: a delta "
                "carries no relation for the edges it adds")
        edge_count = int(indptr[-1])
        node_count = int(indptr.shape[0] - 1)
        indptr = indptr.astype(_index_dtype(edge_count), copy=False)
        indices = indices.astype(
            _index_dtype(max(node_count - 1, 0)), copy=False
        )
        if edge_time is not None:
            t = edge_time.astype(np.float32, copy=False)
            # appended inserts land at row ends in ingestion order; re-sort
            # each row time-nondecreasing (identity on untouched rows)
            order = _time_sort_order(indptr, t)
            indices = indices[order]
            t = t[order]
            if edge_weight is not None:
                edge_weight = edge_weight[order]
            self._edge_time = t
        if edge_weight is not None:
            self._edge_weight = edge_weight.astype(np.float32, copy=False)
            self._cum_weights = _row_prefix_weights(
                self._edge_weight.astype(np.float64), indptr
            )
        self._indptr = indptr
        self._indices = indices
        self._eid = None
        self._max_degree = None  # degrees changed; re-derive on demand
        self._version += 1

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self._indptr)

    @property
    def max_degree(self) -> int:
        # cached: samplers read this per construction, and on an mmap'd
        # raw load the O(N) degree scan would page the whole indptr in —
        # the manifest carries the value instead (invalidated on mutation)
        if self._max_degree is None:
            self._max_degree = int(self.degree.max(initial=0))
        return self._max_degree

    @property
    def node_count(self) -> int:
        return int(self._indptr.shape[0] - 1)

    @property
    def edge_count(self) -> int:
        return int(self._indptr[-1])

    def __repr__(self):
        return f"CSRTopo(nodes={self.node_count}, edges={self.edge_count})"

    # -- persistence --------------------------------------------------------

    def _persist_arrays(self) -> dict:
        """Every array worth round-tripping, keyed by canonical name.
        ``cum_weights`` rides along so a load never pays the O(E) prefix
        recompute; the raw format's mmap loads depend on that."""
        arrays = {"indptr": self._indptr, "indices": self._indices}
        for name in ("eid", "edge_weight", "cum_weights", "edge_time",
                     "edge_relation", "feature_order"):
            v = getattr(self, f"_{name}")
            if v is not None:
                arrays[name] = v
        return arrays

    def save(self, path: str, format: str = "npz") -> None:
        """Persist the topology (CSR + eid + weights + feature_order).

        ``format="npz"`` (default) writes one ``.npz`` — the reference's
        users ``torch.save`` their CSR preprocessing artifacts
        (benchmarks/ogbn-papers100M/preprocess.py); this is the same
        round-trip without a torch dependency. A ``_integrity`` member
        (JSON, per-array CRC32 via the raw-manifest helper) rides inside
        the zip so :meth:`load` can catch silent byte corruption, not
        just zip-level truncation.

        ``format="raw"`` writes the mmap-native directory layout
        (:mod:`quiver_tpu.ooc.format`): per-array uncompressed ``.npy``
        files + CRC32 manifest + COMMIT marker. This is the out-of-core
        path — :meth:`load` with ``mmap=True`` backs ``indptr``/
        ``indices``/edge attrs onto ``np.memmap`` so resident bytes stay
        O(touched pages). Derived state (``cum_weights``, ``max_degree``)
        is persisted so the load path never runs an O(E) or O(N) scan.

        Both formats publish atomically (same-filesystem temp + fsync +
        ``os.replace``): a crash mid-save can leave a stale temp behind
        but never a torn artifact at ``path``."""
        if format == "raw":
            from ..ooc.format import save_raw_dir  # lazy: ooc sits above core

            save_raw_dir(path, self._persist_arrays(), meta={
                "kind": "csr-topo",
                "node_count": self.node_count,
                "edge_count": self.edge_count,
                "max_degree": self.max_degree,
                "version": self._version,
            })
            return
        if format != "npz":
            raise ValueError(f'format must be "npz" or "raw", got {format!r}')
        from ..resilience.integrity import array_checksum  # lazy (cycle)
        import json

        arrays = self._persist_arrays()
        arrays.pop("cum_weights", None)  # npz loads re-derive (legacy shape)
        integrity = json.dumps(
            {name: array_checksum(v) for name, v in arrays.items()},
            sort_keys=True,
        )
        # JSON-as-uint8 smuggles the checksums through np.savez without
        # allow_pickle; readers that predate it just see an extra member
        arrays["_integrity"] = np.frombuffer(
            integrity.encode(), dtype=np.uint8
        )
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as fh:  # exact filename, no np suffixing
                np.savez(fh, **arrays)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    @classmethod
    def _from_raw(cls, arrays: dict, meta: dict) -> "CSRTopo":
        """Assemble a topology from raw-format arrays WITHOUT running
        ``__init__`` — its O(N)/O(E) boundary scans and int64 coercion
        would page every byte of an mmap'd load in, defeating the
        out-of-core point. Safe because the arrays were validated on the
        way INTO :func:`~quiver_tpu.ooc.format.save_raw_dir` (they came
        from a live CSRTopo) and the format's manifest pins their exact
        sizes; run ``ooc.verify_raw_dir`` for a full byte-level sweep."""
        topo = cls.__new__(cls)
        topo._indptr = arrays["indptr"]
        topo._indices = arrays["indices"]
        topo._eid = arrays.get("eid")
        topo._feature_order = arrays.get("feature_order")
        topo._edge_weight = arrays.get("edge_weight")
        topo._cum_weights = arrays.get("cum_weights")
        topo._edge_time = arrays.get("edge_time")
        topo._edge_relation = arrays.get("edge_relation")
        topo._max_degree = (
            int(meta["max_degree"]) if "max_degree" in meta else None
        )
        topo._version = int(meta.get("version", 0))
        return topo

    @classmethod
    def load(cls, path: str, mmap: bool = False) -> "CSRTopo":
        """Rebuild a :meth:`save`'d topology (either format — a directory
        at ``path`` is the raw layout, a file is the legacy ``.npz``).

        ``mmap=True`` (raw format only) backs every array onto read-only
        ``np.memmap``: resident bytes stay O(touched pages) and no
        validation scan runs (see :meth:`_from_raw`) — the papers100M
        path, where the CSR alone outgrows host RAM. Eager raw loads
        (``mmap=False``) run the full CRC32 sweep instead.

        Legacy ``.npz``: weights re-derive their per-row prefix sums
        (stored CSR-ordered, so coo_order is False on the way back in);
        when the archive carries a ``_integrity`` member the per-array
        CRC32s are verified, so silent byte corruption fails as loudly
        as zip-level truncation. A truncated, corrupt, or foreign file
        raises a clear ``ValueError`` naming the artifact — np.load's
        raw zipfile errors (or a KeyError three stack frames later) left
        the operator guessing which file was bad."""
        import zipfile

        if os.path.isdir(path):
            from ..ooc.format import load_raw_dir  # lazy: ooc sits above core

            arrays, meta = load_raw_dir(path, mmap=mmap)
            if meta.get("kind") != "csr-topo":
                raise ValueError(
                    f"{path}: raw dir holds {meta.get('kind')!r}, not a "
                    f"csr-topo artifact"
                )
            return cls._from_raw(arrays, meta)
        if mmap:
            raise ValueError(
                f"{path}: mmap loading needs the raw directory format — "
                f'save with format="raw" (a legacy .npz is a zip that '
                f"must be decompressed into RAM)"
            )
        try:
            z = np.load(path)
        except (OSError, ValueError, EOFError, zipfile.BadZipFile) as e:
            raise ValueError(
                f"{path}: not a readable topology file — truncated, "
                f"corrupt, or not an .npz ({type(e).__name__}: {e})"
            ) from None
        with z:
            cls._verify_npz_integrity(path, z)
            missing = [k for k in ("indptr", "indices") if k not in z.files]
            if missing:
                raise ValueError(
                    f"{path}: topology file lacks required array(s) "
                    f"{missing} (has {sorted(z.files)}) — truncated save "
                    f"or not a CSRTopo artifact"
                )
            try:
                topo = cls(indptr=z["indptr"], indices=z["indices"],
                           eid=z["eid"] if "eid" in z.files else None)
            except (OSError, ValueError, EOFError,
                    zipfile.BadZipFile) as e:
                raise ValueError(
                    f"{path}: topology arrays failed to load/validate "
                    f"({e})"
                ) from None
            if "edge_weight" in z.files:
                topo.set_edge_weight(z["edge_weight"], coo_order=False)
            if "edge_time" in z.files:
                # stored post-sort, so the re-sort inside is the identity
                topo.set_edge_time(z["edge_time"], coo_order=False)
            if "edge_relation" in z.files:
                topo.set_edge_relation(z["edge_relation"], coo_order=False)
            if "feature_order" in z.files:
                topo.feature_order = z["feature_order"]
        return topo

    @staticmethod
    def _verify_npz_integrity(path: str, z) -> None:
        """Check the ``_integrity`` CRC32 record an npz :meth:`save`
        embeds (absent on pre-record archives — those load unverified,
        backward compatible). Raises ``ValueError`` naming the first
        corrupt array."""
        if "_integrity" not in z.files:
            return
        import json
        import zipfile

        from ..resilience.integrity import array_checksum  # lazy (cycle)

        try:
            expected = json.loads(bytes(z["_integrity"]).decode())
        except (ValueError, UnicodeDecodeError, zipfile.BadZipFile) as e:
            raise ValueError(
                f"{path}: unreadable _integrity record ({e})"
            ) from None
        for name, crc in expected.items():
            if name not in z.files:
                raise ValueError(
                    f"{path}: _integrity covers array {name!r} but the "
                    f"archive lacks it — truncated or tampered save"
                )
            try:
                got = array_checksum(z[name])
            except (OSError, ValueError, EOFError, zipfile.BadZipFile) as e:
                # the zip's own member CRC can fire first on corrupt bytes
                raise ValueError(
                    f"{path}: array {name!r} unreadable — corrupt bytes "
                    f"({type(e).__name__}: {e})"
                ) from None
            if got != int(crc):
                raise ValueError(
                    f"{path}: checksum mismatch on array {name!r} "
                    f"(stored {crc}, computed {got}) — corrupt bytes"
                )

    # -- device placement ---------------------------------------------------

    def to_device(self, mode: SampleMode | str = SampleMode.HBM,
                  with_eid: bool = False, with_weights: bool = False,
                  with_times: bool = False,
                  with_relations: bool = False) -> "DeviceTopology":
        """Place the topology for sampling.

        HBM mode puts everything in device memory. HOST mode keeps the large
        ``indices`` (and ``eid``/``cum_weights``) arrays in pinned host memory
        where supported — on platforms without a pinned_host memory space it
        degrades to HBM with a warning-free fallback (CPU tests take this
        path). ``with_weights`` ships the prefix-weight array for weighted
        sampling (requires ``set_edge_weight`` first); ``with_times`` ships
        the timestamp array for temporal windows (requires ``set_edge_time``
        first, HBM mode only — the window search gathers timestamps inside
        the draw loop, which HOST staging cannot serve). ``with_relations``
        packs each edge's relation into the edge array's free high bits
        (requires ``set_edge_relation`` first; see ``place_csr_arrays``).
        """
        if with_weights and self._cum_weights is None:
            raise ValueError(
                "weighted sampling requires edge weights; call "
                "set_edge_weight() or pass edge_weight= to CSRTopo"
            )
        if with_relations and self._edge_relation is None:
            raise ValueError(
                "with_relations requires edge relations; call "
                "set_edge_relation() or pass edge_relation= to CSRTopo"
            )
        if with_times:
            if self._edge_time is None:
                raise ValueError(
                    "temporal sampling requires edge timestamps; call "
                    "set_edge_time() or pass edge_time= to CSRTopo"
                )
            if SampleMode.parse(mode) is not SampleMode.HBM:
                raise ValueError(
                    "temporal sampling requires mode='HBM' — the window "
                    "search gathers timestamps inside the draw loop, which "
                    "HOST-staged placement cannot serve"
                )
        return place_csr_arrays(
            self._indptr, self._indices,
            self._eid if with_eid else None,
            self._cum_weights if with_weights else None,
            self.max_degree, mode,
            edge_time=self._edge_time if with_times else None,
            edge_relation=self._edge_relation if with_relations else None,
        )


EDGE_BLOCK = 128  # words of the edge array the sampler reads a lane


def _whole_blocks(indices, relation=None, shift: int = 0) -> np.ndarray:
    """``indices`` zero-padded on the host to a whole number of blocks, in
    the dtype the device will hold; with ``relation``, each word carries
    its edge's relation above bit ``shift`` (``place_csr_arrays``).

    The padded copy is written into an anonymous mapping populated in one
    call (its tail is zero as mapped): a fresh half gigabyte touched page
    by page is seconds of page faults on a virtual machine, and placing a
    topology compiles nothing, so the padding is not done on the device."""
    indices = np.asarray(indices)
    words = indices.shape[0]
    pad = -words % EDGE_BLOCK
    if pad == 0 and relation is None:
        return indices
    dtype = np.dtype(jax.dtypes.canonicalize_dtype(indices.dtype))
    buf = mmap.mmap(-1, max((words + pad) * dtype.itemsize, 1),
                    flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
                    | getattr(mmap, "MAP_POPULATE", 0))
    out = np.frombuffer(buf, dtype)
    if relation is None:
        out[:words] = indices
        return out
    head = out[:words]
    head[:] = relation
    head <<= shift
    head |= indices
    return out


def relation_shift(node_count: int, num_relations: int) -> int:
    """The bit at which an edge's relation sits in its packed word: above
    the bits of the largest node id. Raises where the two do not fit the
    31 bits of a non-negative ``int32``."""
    shift = max(int(node_count - 1).bit_length(), 1)
    bits = max(int(num_relations - 1).bit_length(), 1)
    if shift + bits > 31:
        raise ValueError(
            f"{node_count} nodes and {num_relations} relations do not fit "
            "one int32 word per edge")
    return shift


def place_csr_arrays(indptr, indices, eid, cum_weights, max_degree: int,
                     mode: SampleMode | str,
                     edge_time=None, edge_relation=None) -> "DeviceTopology":
    """Shared CSR placement for CSRTopo and hetero RelCSR.

    HBM mode puts everything in device memory; HOST mode keeps the large
    per-edge arrays (indices/eid/cum_weights) in pinned host memory where
    supported. Pass ``eid``/``cum_weights``/``edge_time`` as None to omit
    them (``edge_time`` is HBM-only, enforced by the ``to_device`` callers);
    the weighted/temporal binary searches' static iteration bound derives
    from ``max_degree``.

    In HBM mode ``indices`` is placed as whole 128-word blocks: padded with
    zeros (which no valid lane names) up to a multiple of ``EDGE_BLOCK``
    words, so that the sampler can read it through its ``(E'/128, 128)``
    view, 512 bytes a lane (``ops.sample.sample_layer``). The array stays
    1-D and is held once; ``DeviceTopology.edge_count`` stays the CSR's
    edge count. HOST-mode arrays are not padded: a staged host gather does
    not cost by the tile.

    ``edge_relation`` (int8 per edge, CSR order) is packed into the edge
    array itself: each word holds its node id in the low
    ``relation_shift(nodes, relations)`` bits and its edge's relation
    above them, so the sampler reads an edge's endpoint and relation in
    the one block it fetches anyway (``DeviceTopology.relation_shift``,
    ``num_relations``). Where node ids and relations do not fit 31 bits
    this raises.
    """
    mode = SampleMode.parse(mode)
    indptr = jnp.asarray(indptr)
    edge_count = int(np.shape(indices)[0])
    host = False
    shift = num_relations = 0
    if edge_relation is not None:
        edge_relation = np.asarray(edge_relation)
        num_relations = int(edge_relation.max(initial=-1)) + 1
        shift = relation_shift(int(np.shape(indptr)[0]) - 1, num_relations)
        if mode == SampleMode.HOST:
            indices = _whole_blocks(indices, edge_relation, shift)[:edge_count]
    if mode == SampleMode.HOST:
        indices, host = to_pinned_host(indices)
        if eid is not None:
            eid = to_pinned_host(eid)[0] if host else jnp.asarray(eid)
        if cum_weights is not None:
            cum_weights = (
                to_pinned_host(cum_weights)[0] if host
                else jnp.asarray(cum_weights)
            )
    else:
        indices = jnp.asarray(_whole_blocks(indices, edge_relation, shift))
        if eid is not None:
            eid = jnp.asarray(eid)
        if cum_weights is not None:
            cum_weights = jnp.asarray(cum_weights)
    if edge_time is not None:
        edge_time = jnp.asarray(edge_time)
    iters = (
        max(int(np.ceil(np.log2(max_degree + 1))), 1)
        if cum_weights is not None or edge_time is not None
        else 0
    )
    return DeviceTopology(indptr=indptr, indices=indices, eid=eid,
                          cum_weights=cum_weights, edge_time=edge_time,
                          host_indices=host, search_iters=iters,
                          max_degree=int(max_degree), edge_count=edge_count,
                          relation_shift=shift, num_relations=num_relations)


@jax.tree_util.register_pytree_node_class
class DeviceTopology:
    """Device-resident CSR arrays, usable inside jit as a pytree.

    ``host_indices`` is static metadata: True when ``indices``/``eid`` live in
    pinned host memory (HOST mode) so gathers must stage through host compute.
    ``max_degree`` is static host metadata (None when unknown, e.g. a
    hand-built topology); the fused Pallas sampler uses it for trace-time
    window-coverage decisions.

    ``indices`` is 1-D. A placement made by ``place_csr_arrays`` in HBM
    mode holds it zero-padded to a whole number of 128-word blocks, so
    ``indices.shape[0]`` can exceed the number of edges: ``edge_count``
    (static metadata, the CSR's ``indptr[-1]``) is what means "edges". A
    hand-built topology may pass any ``indices``; without ``edge_count``
    its length is taken, and if that is not a whole number of blocks the
    sampler reads it a word a lane.

    ``num_relations`` (static, 0 for none) says that each word of
    ``indices`` holds its edge's relation above bit ``relation_shift``.
    """

    def __init__(self, indptr, indices, eid=None, cum_weights=None,
                 edge_time=None, host_indices: bool = False,
                 search_iters: int = 0, max_degree: int | None = None,
                 edge_count: int | None = None, relation_shift: int = 0,
                 num_relations: int = 0):
        self.indptr = indptr
        self.indices = indices
        self.eid = eid
        self.cum_weights = cum_weights
        self.edge_time = edge_time
        self.host_indices = host_indices
        self.search_iters = search_iters
        self.max_degree = max_degree
        self.edge_count = (
            int(indices.shape[0]) if edge_count is None else int(edge_count)
        )
        self.relation_shift = int(relation_shift)
        self.num_relations = int(num_relations)

    @property
    def node_count(self) -> int:
        return self.indptr.shape[0] - 1

    def tree_flatten(self):
        children = (self.indptr, self.indices, self.eid, self.cum_weights,
                    self.edge_time)
        return children, (self.host_indices, self.search_iters,
                          self.max_degree, self.edge_count,
                          self.relation_shift, self.num_relations)

    @classmethod
    def tree_unflatten(cls, aux, children):
        indptr, indices, eid, cum_weights, edge_time = children
        return cls(indptr, indices, eid, cum_weights, edge_time,
                   host_indices=aux[0], search_iters=aux[1],
                   max_degree=aux[2], edge_count=aux[3],
                   relation_shift=aux[4], num_relations=aux[5])
