"""quiver-serve: low-latency online inference over resident graph state.

The north-star workload is "heavy traffic from millions of users" — an
online *serving* path next to the training loop. The reference's
analogue is its IPC-shared ``Feature``: many frontends, one resident
cache. Here the resident state is richer (device CSR topology, the
three-tier feature store, compiled programs), and the serving stack is
built from three pieces:

* :class:`ServeLadder` — per-bucket AOT-compiled sample/forward
  executables in a power-of-two batch-size ladder; steady state replays
  programs, never recompiles, never re-dispatches Python per request.
* :class:`DeadlineBatcher` — deadline-aware request coalescing with
  bounded-queue backpressure and SLO priority classes (gold/bronze
  per-class deadlines; the full-queue shed policy drops bronze before
  gold), deterministic under an injectable clock.
* :class:`EmbeddingRefresher` — a background lane keeping full-graph
  layer-wise embedding tables fresh across streaming commits (PR 8
  ``VersionMismatchError`` -> ``refresh()`` discipline).

:class:`InferenceServer` composes them, attributes every batch across
six graftscope timeline stages, and lands the ``serve.*`` counters on a
:class:`~quiver_tpu.obs.registry.MetricsRegistry`.

Scale-out rides two more pieces: :class:`AOTExecutableCache` persists
every compiled ladder program (serialized backend executable, fingerprint
-keyed, shared disk cache under ``QUIVER_AOT_CACHE``) so a replica
— even in a fresh process — warms by *deserializing* instead of
compiling; :class:`ServingFleet` runs N replicas over one shared
store/controller/cache with least-depth routing and fleet-level
admission failover.
"""

from .aot import AOTExecutableCache, program_fingerprint
from .coalesce import (
    PRIORITIES,
    DeadlineBatcher,
    ServeQueueFull,
    ServeRequest,
    ladder_buckets,
)
from .fleet import ServingFleet
from .ladder import ServeLadder
from .refresh import EmbeddingRefresher
from .server import InferenceServer

__all__ = [
    "AOTExecutableCache",
    "DeadlineBatcher",
    "EmbeddingRefresher",
    "InferenceServer",
    "PRIORITIES",
    "ServeLadder",
    "ServeQueueFull",
    "ServeRequest",
    "ServingFleet",
    "ladder_buckets",
    "program_fingerprint",
]
