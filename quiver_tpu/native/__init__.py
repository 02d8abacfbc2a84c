"""ctypes loader for the native host runtime (quiver_host.cpp).

Builds the shared library on first import (no pybind11 in this image, so
the C ABI + ctypes replaces the reference's torch-extension binding layer,
srcs/cpp/src/quiver/torch/module.cpp). The library's file name carries a
hash of the source and the build flags, so a library built from other
source — or one that rode along in a copied tree — is never loaded: a
name that is not there is rebuilt. Falls back cleanly to
``available = False`` when no toolchain exists — callers keep their numpy
paths, mirroring how the reference's CPU-only CI builds without CUDA
(HAVE_CUDA gating, setup.py:13-16).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "quiver_host.cpp")
# no -march=native: the library may be built on one machine and the tree
# copied to another
_FLAGS = ["-O3", "-fopenmp", "-shared", "-fPIC"]

available = False
_lib = None


def _lib_path() -> str:
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libquiver_host.{h.hexdigest()[:16]}.so")


def _build() -> str | None:
    """Path of the library for the current source, building it if its
    name is absent; None without a working toolchain."""
    lib = _lib_path()
    if os.path.exists(lib):
        return lib
    # compile to a temp path and atomically rename so concurrent importers
    # (one JAX process per TPU host on a shared FS) never dlopen a torn file
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, lib)
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    for stale in glob.glob(os.path.join(_DIR, "libquiver_host*.so")):
        if stale != lib:
            os.unlink(stale)
    return lib


def _load():
    global _lib, available
    path = _build()
    if path is None:
        return
    try:
        lib = ctypes.CDLL(path)

        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)

        lib.csr_from_coo_i64.argtypes = [i64p, i64p, ctypes.c_int64, ctypes.c_int64, i64p, i32p, i64p]
        lib.csr_from_coo_i32.argtypes = [i32p, i32p, ctypes.c_int64, ctypes.c_int64, i64p, i32p, i64p]
        lib.gather_rows_bytes.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64, i64p, ctypes.c_int64, u8p]
        lib.sample_neighbors_cpu.argtypes = [
            i64p, i32p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, i32p, i32p,
        ]
        lib.degrees_i64.argtypes = [i64p, ctypes.c_int64, i64p]
        lib.reindex_cpu.argtypes = [
            i32p, ctypes.c_int64, i32p, ctypes.c_int32, i32p, i32p,
        ]
        lib.reindex_cpu.restype = ctypes.c_int64
        lib.quiver_host_num_threads.restype = ctypes.c_int
    except (OSError, AttributeError):
        # a library that does not load or lacks a symbol
        return
    _lib = lib
    available = True


_load()


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def csr_from_coo(rows: np.ndarray, cols: np.ndarray, n_nodes: int, with_eid: bool = True):
    """Linear-time parallel COO->CSR. Returns (indptr i64, indices i32, eid i64|None)."""
    if not available:
        raise RuntimeError("native library unavailable")
    if n_nodes > np.iinfo(np.int32).max:
        # the native path stores indices as int32; beyond that the numpy
        # int64 fallback is the correct tool
        raise ValueError(f"native CSR builder supports < 2^31 nodes, got {n_nodes}")
    e = rows.shape[0]
    indptr = np.empty(n_nodes + 1, np.int64)
    indices = np.empty(e, np.int32)
    eid = np.empty(e, np.int64) if with_eid else None
    eid_p = _ptr(eid, ctypes.c_int64) if with_eid else None
    if rows.dtype == np.int32 and cols.dtype == np.int32:
        rows = np.ascontiguousarray(rows, np.int32)
        cols = np.ascontiguousarray(cols, np.int32)
        _lib.csr_from_coo_i32(
            _ptr(rows, ctypes.c_int32), _ptr(cols, ctypes.c_int32), e, n_nodes,
            _ptr(indptr, ctypes.c_int64), _ptr(indices, ctypes.c_int32), eid_p,
        )
    else:
        rows = np.ascontiguousarray(rows, np.int64)
        cols = np.ascontiguousarray(cols, np.int64)
        _lib.csr_from_coo_i64(
            _ptr(rows, ctypes.c_int64), _ptr(cols, ctypes.c_int64), e, n_nodes,
            _ptr(indptr, ctypes.c_int64), _ptr(indices, ctypes.c_int32), eid_p,
        )
    return indptr, indices, eid


def gather_rows(table: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Parallel host row gather; ids < 0 produce zero rows."""
    if not available:
        raise RuntimeError("native library unavailable")
    table = np.ascontiguousarray(table)
    ids = np.ascontiguousarray(ids, np.int64)
    row_bytes = table.strides[0]
    out = np.empty((ids.shape[0],) + table.shape[1:], table.dtype)
    _lib.gather_rows_bytes(
        table.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        table.shape[0], row_bytes,
        _ptr(ids, ctypes.c_int64), ids.shape[0],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out


def sample_neighbors(indptr: np.ndarray, indices: np.ndarray, seeds: np.ndarray,
                     k: int, seed: int = 0):
    """CPU reservoir sampler with the padded (S, k)/-1 output contract."""
    if not available:
        raise RuntimeError("native library unavailable")
    indptr = np.ascontiguousarray(indptr, np.int64)
    indices = np.ascontiguousarray(indices, np.int32)
    seeds = np.ascontiguousarray(seeds, np.int32)
    s = seeds.shape[0]
    out = np.empty((s, k), np.int32)
    counts = np.empty(s, np.int32)
    _lib.sample_neighbors_cpu(
        _ptr(indptr, ctypes.c_int64), _ptr(indices, ctypes.c_int32),
        _ptr(seeds, ctypes.c_int32), s, k, seed,
        _ptr(out, ctypes.c_int32), _ptr(counts, ctypes.c_int32),
    )
    return out, counts


def reindex(seeds: np.ndarray, neighbors: np.ndarray):
    """Hash-based order-preserving reindex (native CPUQuiver::reindex_group
    parity, reference quiver.cpp:39-84).

    Args:
      seeds: (S,) int32 node ids, -1 for padding; every valid seed keeps its
        own frontier slot (duplicates included — PyG contract).
      neighbors: (S, k) int32 sampled ids, -1 invalid.

    Returns:
      (frontier (M,) int32 seeds-first unique ids,
       col (S, k) int32 frontier-local ids, -1 where invalid).
    """
    if not available:
        raise RuntimeError("native library unavailable")
    seeds = np.ascontiguousarray(seeds, np.int32)
    neighbors = np.ascontiguousarray(neighbors, np.int32)
    s, k = neighbors.shape
    if seeds.shape[0] != s:
        raise ValueError(f"seeds {seeds.shape} vs neighbors {neighbors.shape}")
    frontier = np.empty(s * (k + 1), np.int32)
    col = np.empty((s, k), np.int32)
    m = _lib.reindex_cpu(
        _ptr(seeds, ctypes.c_int32), s,
        _ptr(neighbors, ctypes.c_int32), k,
        _ptr(frontier, ctypes.c_int32), _ptr(col, ctypes.c_int32),
    )
    return frontier[:m].copy(), col


def num_threads() -> int:
    return _lib.quiver_host_num_threads() if available else 0
