"""Persisted AOT serving executables — compile-free replica cold start.

Every serving replica used to pay the full ladder walk
(``jit(...).lower().compile()`` twice per bucket) before it could answer
a single request — the exact setup cost the reference amortizes by
sharing one IPC-resident ``Feature`` across worker processes, and the
whole-program capture/replay pattern PyGraph (arxiv 2503.19779) applies
to CUDA graphs. Here the captured artifact is the *backend-compiled
executable itself*: :func:`jax.experimental.serialize_executable
.serialize` flattens a ``jax.stages.Compiled`` into bytes (the
motivating public API surface is ``jax.export``, but its artifacts hold
StableHLO and recompile on load — only the compiled-executable form
replays with ZERO compiles), and this module persists those bytes in a
shared disk cache so a new replica deserializes instead of compiling.

Cache discipline:

* **Keying** — a :func:`program_fingerprint` over everything the
  compiled program closed over: the graftaudit-style target id
  (``serve.sample``/``serve.forward``), bucket size, ladder geometry
  (fanouts, lane caps), sampler config (kernel, weighted), the
  CSR's committed ``version`` *and* the topology leaf avals (a streaming
  commit that changes edge counts changes traced shapes), the
  model/param treedef + avals, feature dtype/width, and the toolchain
  (jax version, platform, device kind, device count — executables are
  backend artifacts). Any mismatch is a miss: fall back to
  compile-and-publish, never to a wrong executable.
* **Tolerant load** — a corrupt/truncated/unpicklable entry degrades to
  a miss with ONE warning per process (:func:`tolerant_cache_read`); the
  subsequent compile republishes over the bad file.
* **Atomic publish** — temp file + fsync + ``os.replace``
  (:func:`atomic_publish_bytes`), so replicas warming concurrently from
  the same directory never read a torn blob.

The entries are pickles (the executable payload rides inside one), so
the cache directory must be trusted — same threat model as the jit
compilation cache. ``QUIVER_AOT_CACHE`` overrides the default location
(``<checkout>/.quiver_cache/aot_executables``), resolved ONCE per process
like every env knob on a potentially-traced path (env-before-first-use).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle

from ..utils.backend import CHECKOUT
from ..utils.trace import get_logger, warn_once

__all__ = ["AOTExecutableCache", "program_fingerprint"]

_BLOB_FORMAT = 2  # 2: entries carry the executable's device ids

_AOT_CACHE_DIR: str | None = None


def _aot_cache_dir() -> str:
    """Default cache directory (``QUIVER_AOT_CACHE``), resolved ONCE per
    process. Tests reset ``_AOT_CACHE_DIR`` to re-resolve."""
    global _AOT_CACHE_DIR
    if _AOT_CACHE_DIR is None:
        _AOT_CACHE_DIR = os.environ.get(
            "QUIVER_AOT_CACHE",
            os.path.join(CHECKOUT, ".quiver_cache", "aot_executables"),
        )
    return _AOT_CACHE_DIR


# The cache is an *optimization*: a hit skips a recompilation. It must be
# fail-safe in both directions — a corrupt/truncated/unreadable file
# degrades to a miss with ONE process-wide warning (never a raise on the
# serve path), and a publish is atomic (readers of the shared directory
# never observe a half-written blob, even with several replicas warming
# concurrently).

def tolerant_cache_read(path: str):
    """Fail-safe read of one cache entry: the unpickled blob or ``None``.

    A missing file is a silent miss; anything else (truncation, garbage
    bytes, a permission error, an unpickle that chokes) is a miss plus ONE
    warning per (process, path) — the caller recompiles and republishes
    over the bad file, so the warning self-heals.
    """
    try:
        with open(path, "rb") as f:
            return pickle.load(f)
    except FileNotFoundError:
        return None
    except Exception as e:  # noqa: BLE001 — any corruption degrades to a
        # recompile; a cache must never be the thing that takes serving down
        warn_once(
            f"cache-unreadable:{path}",
            "AOT-executable cache %s unreadable (%s: %s); ignoring it — "
            "recompiling and republishing over it", path, type(e).__name__,
            str(e)[:200], child="serving.aot",
        )
        return None


def atomic_publish_bytes(path: str, data: bytes) -> None:
    """Atomically publish ``data`` at ``path`` (write temp + fsync +
    ``os.replace``): concurrent readers — other serving replicas warming
    from the same cache — see either the old blob or the new one, never a
    torn write. Raises ``OSError`` on failure; callers that treat the
    cache as optional catch it."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    tmp_path = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp_path, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp_path, path)
    except OSError:
        try:
            os.unlink(tmp_path)
        except OSError:
            pass
        raise


def _executable_device_ids(compiled) -> list[int]:
    """Ids of the devices ``compiled`` runs on, from its shardings (the
    serving ladder compiles one-device programs, so id order is the
    device assignment)."""
    import jax

    shardings = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings)
    )
    return sorted({d.id for s in shardings for d in s.device_set})


def program_fingerprint(components: dict) -> str:
    """Content hash of a program's compile-relevant identity.

    ``components`` must be JSON-serializable (the ladder builds it from
    shapes/dtypes/versions/config scalars); the hash is over the
    canonical (sorted-key, no-whitespace) encoding, so dict ordering
    can't fork fingerprints between replicas.
    """
    canon = json.dumps(components, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode("utf-8")).hexdigest()[:32]


class AOTExecutableCache:
    """Disk cache of serialized backend-compiled serving executables.

    One file per program, named by its :func:`program_fingerprint`; a
    hit deserializes straight to a replayable ``jax.stages.Compiled``
    with zero compilation work. Both directions are fail-safe: ``load``
    never raises (corruption/version-skew = miss + one warning), and a
    failed ``store`` only costs the *next* replica a compile.

    ``hits``/``misses``/``stores``/``rejects`` are process-local
    counters for tests and the fleet benchmark (``rejects`` counts
    unreadable or mismatched entries that fell back to compile).
    """

    def __init__(self, path: str | None = None):
        self.path = str(path) if path is not None else _aot_cache_dir()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.rejects = 0

    def entry_path(self, fingerprint: str) -> str:
        return os.path.join(self.path, f"{fingerprint}.aotx")

    # -- load ---------------------------------------------------------------

    def load(self, fingerprint: str):
        """The cached executable for ``fingerprint``, or ``None``.

        ``None`` covers every non-hit uniformly — absent entry, corrupt
        or truncated blob, format skew, a payload the current backend
        refuses to load — because the caller's fallback (compile and
        republish) is correct for all of them. Never raises.
        """
        path = self.entry_path(fingerprint)
        blob = tolerant_cache_read(path)
        if blob is None:
            self.misses += 1
            if os.path.exists(path):
                self.rejects += 1
            return None
        if (not isinstance(blob, dict)
                or blob.get("format") != _BLOB_FORMAT
                or blob.get("fingerprint") != fingerprint):
            # format/fingerprint skew: treat exactly like corruption —
            # the republish after the fallback compile self-heals it
            warn_once(
                f"cache-unreadable:{path}:skew",
                "AOT-executable cache entry %s does not match its "
                "fingerprint/format; recompiling and republishing",
                path, child="serving.aot",
            )
            self.misses += 1
            self.rejects += 1
            return None
        try:
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            import jax

            # load onto the devices the program was compiled for: the
            # default is every device of the backend, and a one-device
            # executable cannot take their shards
            by_id = {d.id: d for d in jax.devices()}
            ex = deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i] for i in blob["device_ids"]],
            )
        except Exception as e:  # noqa: BLE001 — a backend that refuses the
            # payload (driver/runtime skew the fingerprint can't see) must
            # degrade to a compile, not take the replica down
            warn_once(
                f"cache-unreadable:{path}:load",
                "AOT executable %s failed to deserialize (%s: %s); "
                "recompiling and republishing", path, type(e).__name__,
                str(e)[:200], child="serving.aot",
            )
            self.misses += 1
            self.rejects += 1
            return None
        self.hits += 1
        return ex

    # -- store --------------------------------------------------------------

    def store(self, fingerprint: str, compiled,
              components: dict | None = None) -> bool:
        """Serialize ``compiled`` and atomically publish it under
        ``fingerprint``; True on publish. Fail-safe: a backend whose
        executables don't serialize, or an unwritable cache directory,
        logs once and returns False — the replica serves from its
        in-memory executable either way."""
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps({
                "format": _BLOB_FORMAT,
                "fingerprint": fingerprint,
                "components": components,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "device_ids": _executable_device_ids(compiled),
            })
        except Exception as e:  # noqa: BLE001 — serialization support is
            # backend-dependent; its absence must not fail the serve path
            warn_once(
                f"aot-store:{self.path}:serialize",
                "AOT executable serialization unavailable (%s: %s); "
                "replicas will compile instead of warming from %s",
                type(e).__name__, str(e)[:200], self.path,
                child="serving.aot",
            )
            return False
        try:
            atomic_publish_bytes(self.entry_path(fingerprint), blob)
        except OSError as e:
            warn_once(
                f"aot-store:{self.path}:write",
                "AOT cache %s unwritable (%s: %s); replicas will compile "
                "instead of warming from it", self.path,
                type(e).__name__, str(e)[:200], child="serving.aot",
            )
            return False
        self.stores += 1
        get_logger("serving.aot").info(
            "published AOT executable %s (%d bytes)", fingerprint, len(blob)
        )
        return True

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.path)
                       if n.endswith(".aotx"))
        except OSError:
            return 0

    def stats(self) -> dict:
        return {"path": self.path, "entries": len(self), "hits": self.hits,
                "misses": self.misses, "stores": self.stores,
                "rejects": self.rejects}
