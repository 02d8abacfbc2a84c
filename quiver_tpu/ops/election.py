"""Measured kernel elections (pallas vs xla) with one shared disk cache.

The gather election (feature/feature.py, the ``quiver_tensor_gather``
precedent) and the sample election (sampling/sampler.py, the fused
megakernel) follow one contract, factored here:

1. an explicit ``kernel="pallas"|"xla"`` bypasses everything (fail loudly
   on request);
2. ``kernel="auto"`` off-TPU resolves to xla (the Pallas CPU interpret
   path is correct but slow);
3. on TPU, auto runs a one-time correctness smoke at the shapes of the
   real call. A kernel the compiler refuses, or one that diverges from
   its XLA reference, is an ERROR that propagates — a degrade to xla
   would hide a broken kernel behind a working number. Then auto ELECTS
   BY MEASURED THROUGHPUT between the two kernels — "it compiled and
   returned right rows" is not evidence it is fast (VERDICT r3 item 4);
4. the election is memoised per process and persisted in ONE disk cache
   file shared by every election (``QUIVER_ELECTION_CACHE``, default
   ``<checkout>/.quiver_cache/kernel_elections.json``), keyed by election name
   and invalidated by (rev, jax version, device kind) so a kernel or
   toolchain change forces re-election instead of trusting stale numbers.
   The file is an optimization, never a failure source: a corrupt or
   truncated cache degrades to re-election with ONE warning (fail-safe,
   see :func:`tolerant_cache_read`) and every rewrite is an atomic
   publish (:func:`atomic_publish_bytes`) — both shared with the serving
   AOT executable cache (serving/aot.py);
5. ``env_var=pallas|xla`` (e.g. ``QUIVER_GATHER_KERNEL``,
   ``QUIVER_SAMPLE_KERNEL``) overrides the measurement.

Env-before-first-use: the force knob and ``QUIVER_ELECTION_CACHE`` are
resolved ONCE per process at the first auto resolution — the election
runs behind the first ``kernel="auto"`` call, which may sit inside a
traced body, where a per-call env read would freeze at first trace while
looking live (graftlint env-at-trace). Set them before the first
gather/sample; flipping them afterwards is inert
(tests/test_kernel_election.py pins this). Tests call ``reset()`` (and
reset ``_ELECTION_CACHE_PATH``) to simulate a fresh process.
"""

from __future__ import annotations

from collections.abc import Callable

import jax

from ..utils.trace import get_logger, warn_once

__all__ = [
    "KernelElection",
    "atomic_publish_bytes",
    "tolerant_cache_read",
    "validate_kernel_arg",
]


def validate_kernel_arg(kernel: str) -> str:
    """Eager argument check only — MUST NOT touch the JAX backend (object
    construction must stay cheap and never initialize/lock backend choice)."""
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(f"kernel must be auto|pallas|xla, got {kernel!r}")
    return kernel


_ELECTION_CACHE_PATH: str | None = None


def _election_cache_path() -> str:
    """Disk-cache path shared by ALL elections (``QUIVER_ELECTION_CACHE``),
    resolved ONCE per process (env-before-first-use, see module docstring).
    Tests reset ``_ELECTION_CACHE_PATH`` to re-resolve."""
    global _ELECTION_CACHE_PATH
    if _ELECTION_CACHE_PATH is None:
        import os

        from ..utils.backend import CHECKOUT

        # a fixed path under the checkout: a file that decides behaviour
        # must not hide in the home directory
        _ELECTION_CACHE_PATH = os.environ.get(
            "QUIVER_ELECTION_CACHE",
            os.path.join(CHECKOUT, ".quiver_cache", "kernel_elections.json"),
        )
    return _ELECTION_CACHE_PATH


# -- shared disk-cache discipline (elections AND the serving AOT cache) -----
#
# Both persisted caches are pure *optimizations*: a hit skips a
# re-measurement (election) or a recompilation (serving/aot.py). They must
# therefore be fail-safe in both directions — a corrupt/truncated/
# unreadable file degrades to a miss with ONE process-wide warning (never
# a raise on the serve/train path), and a publish is atomic (readers of
# the shared file never observe a half-written blob, even with several
# replicas warming concurrently).

def tolerant_cache_read(path: str, reader, *, what: str,
                        child: str | None = None):
    """Fail-safe shared-cache read: ``reader(binary_file)`` or ``None``.

    A missing file is a silent miss; anything else (truncation, garbage
    bytes, a permission error, a reader that chokes) is a miss plus ONE
    warning per (process, path) — the caller recomputes and republishes
    over the bad file, so the warning self-heals.
    """
    try:
        with open(path, "rb") as f:
            return reader(f)
    except FileNotFoundError:
        return None
    except Exception as e:  # noqa: BLE001 — any corruption degrades to a
        # recompute; a cache must never be the thing that takes serving down
        warn_once(
            f"cache-unreadable:{path}",
            "%s cache %s unreadable (%s: %s); ignoring it — recomputing "
            "and republishing over it", what, path, type(e).__name__,
            str(e)[:200], child=child,
        )
        return None


def atomic_publish_bytes(path: str, data: bytes) -> None:
    """Atomically publish ``data`` at ``path`` (write temp + fsync +
    ``os.replace``): concurrent readers — other serving replicas warming
    from the same cache — see either the old blob or the new one, never a
    torn write. Raises ``OSError`` on failure; callers that treat the
    cache as optional catch it."""
    import os

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


class KernelElection:
    """One named pallas-vs-xla election (see module docstring for the
    contract).

    ``smoke`` is a zero-arg correctness gate (it raises when the kernel
    does not compile and returns False when it diverges; either fails
    the election loudly); ``measure`` maps ``"pallas"|"xla"`` to a
    higher-is-better score in ``unit``. Both are called lazily at first auto resolution, never at
    construction. ``result`` exposes the decided election
    (``{"kernel", "how", ...}``) for tests and telemetry; ``reset()`` is
    the test seam simulating a fresh process (forgets the memo AND the
    pinned env force — not the shared cache-path pin, which
    tests/monkeypatch reset on the module).
    """

    def __init__(self, name: str, env_var: str, rev: int,
                 smoke: Callable[[], bool],
                 measure: Callable[[str], float],
                 unit: str = "GB/s", log_child: str | None = None):
        self.name = name
        self.env_var = env_var
        self.rev = int(rev)
        self._smoke = smoke
        self._measure = measure
        self.unit = unit
        self._log_child = log_child or name
        self.result: dict | None = None
        self._forced: str | None = None

    # -- env force (pinned at first use) ----------------------------------
    def forced(self) -> str:
        """The env force ("" = none), read ONCE per process."""
        if self._forced is None:
            import os

            self._forced = os.environ.get(self.env_var, "").strip().lower()
        return self._forced

    # -- disk cache (one file, nested by election name) -------------------
    def cache_key(self) -> str:
        return (f"rev{self.rev}-jax{jax.__version__}-"
                + str(jax.devices()[0].device_kind))

    def _load_blob(self) -> dict:
        """The whole shared cache file as a dict — ``{}`` on miss, and
        ``{}`` with ONE warning on a corrupt/truncated file (fail-safe to
        re-election, never a raise; tests/test_kernel_election.py pins
        it). A non-dict JSON document counts as corrupt too."""
        import json

        blob = tolerant_cache_read(
            _election_cache_path(), json.load,
            what="kernel-election", child=self._log_child,
        )
        if blob is not None and not isinstance(blob, dict):
            warn_once(
                f"cache-unreadable:{_election_cache_path()}:shape",
                "kernel-election cache %s holds a %s, not an object; "
                "ignoring it — re-electing and republishing over it",
                _election_cache_path(), type(blob).__name__,
                child=self._log_child,
            )
            return {}
        return blob or {}

    def _load_cached(self, cache_key: str) -> dict | None:
        entry = self._load_blob().get(self.name)
        if (isinstance(entry, dict) and entry.get("key") == cache_key
                and entry.get("kernel") in ("pallas", "xla")):
            return entry
        return None

    def _store(self, entry: dict) -> None:
        import json

        path = _election_cache_path()
        # drop anything that is not a nested election entry (e.g. a
        # pre-generalization flat gather_election.json pointed at by
        # QUIVER_ELECTION_CACHE)
        blob = {k: v for k, v in self._load_blob().items()
                if isinstance(v, dict) and "kernel" in v}
        blob[self.name] = entry
        try:
            atomic_publish_bytes(path, json.dumps(blob).encode("utf-8"))
        except OSError:
            pass

    # -- resolution --------------------------------------------------------
    # The instance-attribute form of the module-global resolve-once idiom:
    # the slow path (env pin, smoke, micro-bench, one log line each) runs
    # at most once per process, at or before the first trace —
    # env-before-first-use is documented in the module docstring and
    # pinned by tests/test_kernel_election.py.
    # graftlint: eager -- resolve-once barrier memoised on self.result; the smoke/micro-bench/log slow path runs at most once per process
    def elect(self) -> str:
        """TPU kernel=auto election: measured pallas-vs-xla, not compile
        success. Cached per process and on disk so every benchmark process
        doesn't re-pay the two micro-bench compiles."""
        if self.result is not None:
            return self.result["kernel"]
        log = get_logger(self._log_child)
        forced = self.forced()
        if forced in ("pallas", "xla"):
            self.result = {"kernel": forced, "how": "env override"}
            return forced
        if not self._smoke():
            raise RuntimeError(
                f"{self.name} pallas smoke diverged from its XLA reference"
            )
        cache_key = self.cache_key()
        cached = self._load_cached(cache_key)
        if cached is not None:
            self.result = {**cached, "how": "disk cache"}
            log.info("%s kernel=auto -> %s (cached election: %s)",
                     self.name, cached["kernel"], cached.get("score"))
            return cached["kernel"]
        score = {k: round(float(self._measure(k)), 2)
                 for k in ("xla", "pallas")}
        kernel = max(score, key=score.get)
        self.result = {"kernel": kernel, "score": score,
                       "key": cache_key, "how": "measured"}
        log.info("%s kernel=auto -> %s (measured %s: %s)",
                 self.name, kernel, self.unit, score)
        self._store({"kernel": kernel, "score": score, "key": cache_key})
        return kernel

    def resolve_request(self, kernel: str) -> str:
        """Resolve a kernel request. Touches the backend, so callers defer
        this to first use (never the constructor)."""
        validate_kernel_arg(kernel)
        if kernel != "auto":
            return kernel
        if jax.default_backend() != "tpu":
            return "xla"
        return self.elect()

    def reset(self) -> None:
        """Test seam: forget the in-process decision and the pinned env
        force, as a fresh process would."""
        self.result = None
        self._forced = None
