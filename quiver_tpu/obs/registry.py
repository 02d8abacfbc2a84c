"""graftscope metrics registry — ONE audited telemetry path for traced code.

Before this module the repo had three in-program telemetry streams
(``last_routed_overflow``, ``last_tier_hits``, ``last_sample_overflow``),
each hand-threading its device value through ``shard_map``/``lax.scan`` with
its own psum placement and its own eager surfacing attribute. The registry
generalizes the discipline those streams proved:

* traced code *registers* a named counter or gauge once (host side, before
  the program is built) and *feeds* it through a :class:`MetricsTape`
  inside the traced body;
* ``tape.finalize()`` emits one metrics pytree (a plain ``{name: array}``
  dict) that rides the program's outputs through ``shard_map``,
  ``lax.scan`` and cond-gated fallback paths like any other value — mesh
  reduction (psum) is applied exactly once per metric per step, at the
  axes the producer declared;
* the eager caller hands the returned pytree to
  :meth:`MetricsRegistry.record`, which lands it as typed
  :class:`MetricSnapshot` objects — epoch_scan-stacked ``(steps, ...)``
  values are detected by shape against the registered spec.

Collection is a real program-level switch: a disabled registry's tape
feeds nothing and finalizes to ``{}``, so the compiled step carries ZERO
metric collectives — and the loss trajectory is bit-identical either way
(tests/test_obs.py differential).

Snapshots hold the device value *lazily* (``int()``/``np.asarray`` of a
just-dispatched scalar would force a sync mid-pipeline — the same rule the
``last_*`` attributes always followed); exporters and reports materialize
on access.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "MetricSpec",
    "MetricSnapshot",
    "MetricsRegistry",
    "MetricsTape",
    "ROUTED_OVERFLOW",
    "TIER_HITS",
    "SAMPLE_OVERFLOW",
    "SAMPLE_EDGES",
    "SAMPLE_FRONTIER",
    "SAMPLE_FRONTIER_OVERFLOW",
    "SAMPLE_RELATION_LANES",
    "SAMPLE_RELATION_TARGETS",
    "HETERO_SAMPLE_OVERFLOW",
    "GUARD_SKIPPED",
    "GUARD_NONFINITE",
    "PREFETCH_RETRIES",
    "PREFETCH_SKIPS",
    "PREFETCH_QUEUE_DEPTH",
    "DEGRADED_LOOKUPS",
    "DELTAS_QUARANTINED",
    "DELTAS_COMMITTED",
    "STREAMING_COMMITS",
    "SERVE_REQUESTS",
    "SERVE_DEADLINE_MISSES",
    "SERVE_DEGRADED_LOOKUPS",
    "SERVE_RECOMPILES",
    "SERVE_AOT_LOADS",
    "SERVE_SHED",
    "SERVE_CLASS_MISSES",
    "TRAIN_OVERLAP_EFFICIENCY",
    "PIPELINE_REISSUES",
    "FEATURE_ROW_HEAT",
    "CTRL_DECISIONS",
    "CTRL_REPINS",
    "CTRL_SPLIT_MOVES",
    "CTRL_ALPHA_CHANGES",
    "CTRL_OOC_PROMOTIONS",
    "OOC_STAGE_WAIT",
    "OOC_PAGE_READS",
    "OOC_READAHEAD_HITS",
    "TRACE_SPANS",
    "RECORDER_BUNDLES",
    "RECORDER_EVENTS",
    "XLA_COMPILES",
    "XLA_COMPILE_SECONDS",
    "XLA_CACHE_HITS",
    "GATHER_PAD_LANES",
]

# well-known metric names — the three streams the registry was distilled
# from (kept as module constants so producers and consumers cannot drift
# on spelling)
ROUTED_OVERFLOW = "feature.routed_overflow"
TIER_HITS = "feature.tier_hits"
SAMPLE_OVERFLOW = "sample.hop_overflow"
# what the fused step's sampler counts in-program, mesh totals per step:
# valid sampled edges per hop, distinct nodes found per hop BEFORE the
# frontier cap (both seeds-outward, like sample.hop_overflow), and the
# uniques dropped for exceeding ``frontier_caps`` (> 0 means the caps are
# too small and the step trained on a truncated block)
SAMPLE_EDGES = "sample.edges"
SAMPLE_FRONTIER = "sample.frontier"
SAMPLE_FRONTIER_OVERFLOW = "sample.frontier_overflow"
# over a topology with edge relations: valid sampled lanes per hop
# (seeds-outward) and relation, mesh totals per step
SAMPLE_RELATION_LANES = "sample.relation_lanes"
# and the valid targets with at least one valid lane of each relation: the
# groups a softmax per target and relation runs over that are not empty
SAMPLE_RELATION_TARGETS = "sample.relation_targets"
# per-(hop, edge-type) routed-overflow lanes of the distributed hetero
# sampler (flat vector in the sampler's static slot order; relations
# sharing a destination type share that hop's route plan, so they report
# the plan's overflow equally)
HETERO_SAMPLE_OVERFLOW = "sample.hetero_hop_overflow"
# resilience layer: steps cond-skipped by the non-finite guard, and the
# mesh-total count of non-finite loss/grad values it detected
GUARD_SKIPPED = "resilience.skipped_steps"
GUARD_NONFINITE = "resilience.nonfinite_grads"
# host-side resilience counters: prefetcher batch re-dispatches and
# dropped batches (pipeline health next to resilience.skipped_steps in
# metrics_report), and feature lookups served degraded by the cold-tier
# circuit breaker's fallback instead of crashing the step
PREFETCH_RETRIES = "prefetch.retries"
PREFETCH_SKIPS = "prefetch.skipped_batches"
# in-flight prefetch dispatches at the most recent queue transition — the
# gauge that distinguishes "pipeline keeps the depth budget full" from
# "consumer is starving the worker" (lifetime counters can't)
PREFETCH_QUEUE_DEPTH = "prefetch.queue_depth"
DEGRADED_LOOKUPS = "resilience.degraded_lookups"
# out-of-core disk tier (quiver_tpu/ooc): seconds a gather spent BLOCKED
# on window reads (the exposed share of disk cost — hidden reads never
# land here), window reads issued to disk, and requested rows served
# from an already-staged window (the readahead working)
OOC_STAGE_WAIT = "ooc.stage_wait"
OOC_PAGE_READS = "ooc.page_reads"
OOC_READAHEAD_HITS = "ooc.readahead_hits"
# streaming mutation layer (quiver_tpu/streaming): delta batches rejected
# at the ingestion boundary or by a failed commit (quarantined with a
# reason, never partially applied), delta batches merged by a published
# commit, and published commits (= version bumps)
DELTAS_QUARANTINED = "streaming.deltas_quarantined"
DELTAS_COMMITTED = "streaming.deltas_committed"
STREAMING_COMMITS = "streaming.commits"
# online serving layer (quiver_tpu/serving): completed point queries,
# requests finished after their admission deadline, feature lookups a
# serve batch satisfied through the circuit breaker's degraded fallback,
# and ladder-program compilations (zero after warmup = the steady-state
# never-recompile contract of the compiled micro-batch step)
SERVE_REQUESTS = "serve.requests"
SERVE_DEADLINE_MISSES = "serve.deadline_misses"
SERVE_DEGRADED_LOOKUPS = "serve.degraded_lookups"
SERVE_RECOMPILES = "serve.recompiles"
# fleet scale-out (serving/aot.py + serving/fleet.py): ladder programs
# warmed by deserializing a persisted AOT executable instead of compiling
# (a cache-warm replica reports aot_loads == program count and
# recompiles == 0), plus the SLO-class-attributed admission outcomes —
# requests shed under a full queue and requests completed after their
# deadline, both as vectors in serving.coalesce.PRIORITIES order
# (gold, bronze)
SERVE_AOT_LOADS = "serve.aot_loads"
SERVE_SHED = "serve.shed_requests"
SERVE_CLASS_MISSES = "serve.class_deadline_misses"
# software-pipelined epoch (parallel/trainer.py pipeline_depth=1): the
# derived overlap-efficiency gauge (serial stage-sum over measured
# pipelined step time, > 1.0 = the schedule is hiding sample/gather
# latency under compute; fed by bench_epoch --pipeline from the
# StepTimeline) and the count of prologue batches re-issued at
# checkpoint-chunk/resume boundaries (the carried batch is replayed from
# the seed matrix rather than serialized — each boundary costs one extra
# sample+gather)
TRAIN_OVERLAP_EFFICIENCY = "train.overlap_efficiency"
PIPELINE_REISSUES = "train.pipeline_reissues"
# control plane (quiver_tpu/control): the in-program per-row access-heat
# histogram (positional bins over the store's translated row order, psum'd
# once per step like feature.tier_hits; opt-in — registered only when a
# controller asks for it so controller-off telemetry is untouched), and the
# host-side decision counters every CacheController audit record increments:
# total decisions emitted, L0 repins to a measured hot set, L0/L1 boundary
# moves, and routed_alpha changes (grow OR shrink)
FEATURE_ROW_HEAT = "feature.row_heat"
CTRL_DECISIONS = "ctrl.decisions"
CTRL_REPINS = "ctrl.repins"
CTRL_SPLIT_MOVES = "ctrl.split_moves"
CTRL_ALPHA_CHANGES = "ctrl.alpha_changes"
# disk->host-cold promotion/demotion decisions over an out-of-core store
# (quiver_tpu/ooc): one decision restages the whole host cold cache to
# the sketch's measured-hottest disk rows
CTRL_OOC_PROMOTIONS = "ctrl.ooc_promotions"
# grafttrace (obs/tracing.py + obs/recorder.py): finished causal spans
# recorded by the tracer (bounded ring keeps the newest), postmortem
# bundles the flight recorder has published, and decision/audit events
# noted into its ring buffer
TRACE_SPANS = "trace.spans"
RECORDER_BUNDLES = "recorder.bundles"
RECORDER_EVENTS = "recorder.events"
# what the launches of DistributedTrainer.step() compiled (host-side, from
# obs/compile_watch.py): backend compilations they paid (cache-served ones
# included), those compilations' wall seconds, and how many the persistent
# compilation cache served; compiles - cache_hits were compiled anew
XLA_COMPILES = "xla.compiles"
XLA_COMPILE_SECONDS = "xla.compile_seconds"
XLA_CACHE_HITS = "xla.cache_hits"
# host-side, set when the fused step is traced: the lanes added to its plain
# hot-tier row gather so that it keeps 256 rows in flight (ops/gather.py);
# never an output of the step
GATHER_PAD_LANES = "feature.gather_pad_lanes"

_KINDS = ("counter", "gauge")


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """Declaration of one named metric.

    ``shape`` is the per-step logical shape (``()`` for scalars); an
    epoch_scan epoch lands the metric as ``(steps,) + shape``. ``counter``
    values accumulate within a step (tape ``add``); ``gauge`` values
    overwrite (tape ``set``).
    """

    name: str
    kind: str
    shape: tuple[int, ...] = ()
    dtype: Any = jnp.int32
    doc: str = ""
    unit: str = ""

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"kind must be one of {_KINDS}, got {self.kind!r}")


@dataclasses.dataclass
class MetricSnapshot:
    """One recorded metric value (a step's, or a scanned epoch's stack).

    ``value`` may be a device array — it is materialized lazily via
    :attr:`numpy` so recording never forces a host sync. ``steps`` is
    ``None`` for a single step and the scan length for epoch_scan-shaped
    values (leading axis = step index).
    """

    name: str
    kind: str
    value: Any
    steps: int | None = None
    unit: str = ""
    doc: str = ""

    @property
    def numpy(self) -> np.ndarray:
        return np.asarray(self.value)

    @property
    def shape(self) -> tuple[int, ...]:
        """Full stored shape (includes the steps axis when present)."""
        return tuple(np.shape(self.value))

    def total(self):
        """Sum over every axis — the natural counter reduction."""
        return self.numpy.sum()

    def last(self) -> np.ndarray:
        """The most recent per-step value (the value itself when single)."""
        arr = self.numpy
        return arr[-1] if self.steps is not None else arr


class MetricsTape:
    """Per-trace builder of the step's metrics pytree.

    Create one per traced body via :meth:`MetricsRegistry.tape`; feed
    values with :meth:`add` (counters accumulate) / :meth:`set` (gauges
    overwrite); :meth:`finalize` applies each metric's declared psum axes
    once and returns the ``{name: array}`` dict to thread out of the
    program. On a disabled registry every method is a no-op and
    ``finalize`` returns ``{}`` — the compiled program carries no metric
    values at all.
    """

    def __init__(self, registry: "MetricsRegistry"):
        self._registry = registry
        self._values: dict[str, Any] = {}
        self._psum: dict[str, tuple] = {}

    def _note_psum(self, name: str, psum) -> None:
        if psum is None:
            return
        axes = (psum,) if isinstance(psum, str) else tuple(psum)
        prev = self._psum.get(name)
        if prev is not None and prev != axes:
            raise ValueError(
                f"metric {name!r} fed with conflicting psum axes "
                f"{prev} vs {axes}"
            )
        self._psum[name] = axes

    def add(self, name: str, value, psum=None) -> None:
        """Accumulate ``value`` into counter ``name`` (trace-safe ``+``)."""
        if not self._registry.enabled:
            return
        spec = self._registry.spec(name)
        if spec.kind != "counter":
            raise ValueError(f"metric {name!r} is a {spec.kind}; use set()")
        cur = self._values.get(name)
        self._values[name] = value if cur is None else cur + value
        self._note_psum(name, psum)

    def set(self, name: str, value, psum=None) -> None:
        """Overwrite gauge ``name`` with ``value``."""
        if not self._registry.enabled:
            return
        spec = self._registry.spec(name)
        if spec.kind != "gauge":
            raise ValueError(f"metric {name!r} is a {spec.kind}; use add()")
        self._values[name] = value
        self._note_psum(name, psum)

    def finalize(self, names=None) -> dict[str, Any]:
        """The step's metrics pytree: every registered metric present
        (zero-filled from its spec when unfed — the dict structure must be
        static across traces), each psum'd ONCE at its declared axes.

        ``names`` restricts the emitted dict to that subset of registered
        metrics (still zero-filled when unfed). This is what lets a step
        built from SPLIT bodies — the pipelined trainer's issue/train
        halves — emit disjoint dicts whose merge is exactly the fused
        body's pytree; without the filter each half would zero-fill the
        other half's metrics and the merge would clobber real values.
        Feeding a metric and then finalizing without it would silently
        drop the value, so that raises instead."""
        if not self._registry.enabled:
            return {}
        if names is None:
            specs = self._registry.specs()
        else:
            specs = {name: self._registry.spec(name) for name in names}
            dropped = [n for n in self._values if n not in specs]
            if dropped:
                raise ValueError(
                    f"finalize(names=...) would drop fed metrics "
                    f"{sorted(dropped)}; include them in names or don't "
                    f"feed them on this tape"
                )
        out = {}
        for name, spec in specs.items():
            v = self._values.get(name)
            if v is None:
                v = jnp.zeros(spec.shape, spec.dtype)
            else:
                axes = self._psum.get(name)
                if axes:
                    v = jax.lax.psum(v, axes if len(axes) > 1 else axes[0])
                v = jnp.asarray(v, spec.dtype)
            out[name] = v
        return out


class MetricsRegistry:
    """Named counters/gauges with trace-side tapes and eager snapshots.

    Host side: :meth:`counter`/:meth:`gauge` declare metrics (idempotent —
    re-declaring with an identical spec is a no-op, a conflicting one
    raises); :meth:`record` lands a program's metrics pytree as
    :class:`MetricSnapshot` objects; :meth:`value`/:meth:`snapshot` read
    them back. Trace side: :meth:`tape`. ``enabled=False`` turns the whole
    registry into a no-op (tapes feed nothing, record drops everything) —
    the compiled-program-level collection switch.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self._specs: dict[str, MetricSpec] = {}
        self._snaps: dict[str, MetricSnapshot] = {}

    # -- declaration --------------------------------------------------------

    def _register(self, spec: MetricSpec) -> str:
        prev = self._specs.get(spec.name)
        if prev is not None:
            if prev != spec:
                raise ValueError(
                    f"metric {spec.name!r} already registered with a "
                    f"different spec ({prev} vs {spec})"
                )
            return spec.name
        self._specs[spec.name] = spec
        return spec.name

    def counter(self, name: str, shape=(), dtype=jnp.int32, doc: str = "",
                unit: str = "") -> str:
        """Register (or re-assert) a counter; returns ``name``."""
        return self._register(
            MetricSpec(name, "counter", tuple(shape), dtype, doc, unit)
        )

    def gauge(self, name: str, shape=(), dtype=jnp.int32, doc: str = "",
              unit: str = "") -> str:
        """Register (or re-assert) a gauge; returns ``name``."""
        return self._register(
            MetricSpec(name, "gauge", tuple(shape), dtype, doc, unit)
        )

    def spec(self, name: str) -> MetricSpec:
        try:
            return self._specs[name]
        except KeyError:
            raise KeyError(
                f"metric {name!r} is not registered (known: "
                f"{sorted(self._specs)})"
            ) from None

    def specs(self) -> dict[str, MetricSpec]:
        """Registered specs, insertion-ordered (read-only copy)."""
        return dict(self._specs)

    def names(self) -> list[str]:
        return list(self._specs)

    # -- trace side ---------------------------------------------------------

    def tape(self) -> MetricsTape:
        return MetricsTape(self)

    # -- eager side ---------------------------------------------------------

    def _steps_of(self, spec: MetricSpec, value) -> int | None:
        ndim = np.ndim(value)
        if ndim == len(spec.shape):
            return None
        if ndim == len(spec.shape) + 1:
            return int(np.shape(value)[0])  # epoch_scan stack
        raise ValueError(
            f"metric {spec.name!r}: value ndim {ndim} matches neither the "
            f"spec shape {spec.shape} nor a (steps,)-stacked epoch of it"
        )

    def record(self, values: dict[str, Any]) -> None:
        """Land a program's metrics pytree as snapshots (no host sync —
        values stay device-resident until an exporter/report reads them)."""
        if not self.enabled or not values:
            return
        for name, v in values.items():
            self.set(name, v)

    def set(self, name: str, value) -> None:
        """Host-side write of one metric (``None`` clears it) — the thin
        compatibility path behind the legacy ``last_*`` attribute setters."""
        if value is None:
            self._snaps.pop(name, None)
            return
        spec = self.spec(name)
        self._snaps[name] = MetricSnapshot(
            name, spec.kind, value, self._steps_of(spec, value),
            spec.unit, spec.doc,
        )

    def value(self, name: str):
        """The raw recorded value (device array or host array), or None."""
        snap = self._snaps.get(name)
        return None if snap is None else snap.value

    def snapshot(self, name: str) -> MetricSnapshot | None:
        return self._snaps.get(name)

    def snapshots(self) -> list[MetricSnapshot]:
        """Every recorded snapshot, registration-ordered."""
        return [self._snaps[n] for n in self._specs if n in self._snaps]

    def clear(self, name: str | None = None) -> None:
        if name is None:
            self._snaps.clear()
        else:
            self._snaps.pop(name, None)
