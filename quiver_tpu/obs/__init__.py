"""graftscope — unified in-program metrics, step timeline, and export.

The observability layer the three hand-threaded ``last_*`` telemetry
streams grew into (SURVEY §0/§5: sampling throughput and cache hit rates
are the signals that drove the reference's design). One discipline, four
pieces:

* :class:`MetricsRegistry` / :class:`MetricsTape` — named counters/gauges
  that ride a single metrics pytree through ``shard_map``/``lax.scan``/
  cond-gated fallbacks, psum'd once per step, landing as typed
  :class:`MetricSnapshot` objects (``registry.py``);
* :class:`StepTimeline` — host-side per-stage wall clock with streaming
  p50/p95/p99; each stage is also a ``quiver.<name>`` slice on the host
  thread of a ``jax.profiler`` capture (``timeline.py``,
  ``utils.trace.host_span``);
* JSONL + Prometheus-style exporters, both parse-back round-trippable
  (``export.py``);
* :func:`compile_watch` — what XLA compiled in this process, from JAX's
  monitoring events; the trainer puts each compile down to the step that
  paid it (``compile_watch.py``).

``DistributedTrainer.metrics_report()`` is the one-call summary over all
of it.

grafttrace extends the layer with causal chains and crash forensics:

* :class:`Tracer` / :class:`Span` — per-request/per-step causal spans
  riding the serve, fleet, trainer, host-actor, and control seams,
  exported as Chrome trace-event JSON (``tracing.py``);
* :class:`FlightRecorder` — bounded black-box ring dumping atomic,
  integrity-checksummed postmortem bundles on fault triggers
  (``recorder.py``);
* :class:`TelemetryEndpoint` — opt-in stdlib HTTP thread serving
  ``/metrics``, ``/traces``, ``/healthz`` (``endpoint.py``).
"""

from .compile_watch import compile_watch
from .endpoint import TelemetryEndpoint
from .export import (
    from_prometheus,
    prometheus_name,
    read_jsonl,
    snapshot_from_dict,
    snapshot_to_dict,
    to_prometheus,
    write_jsonl,
)
from .recorder import (
    FlightRecorder,
    TornBundle,
    list_bundles,
    verify_bundle,
)
from .registry import (
    GUARD_NONFINITE,
    GUARD_SKIPPED,
    RECORDER_BUNDLES,
    RECORDER_EVENTS,
    ROUTED_OVERFLOW,
    SAMPLE_OVERFLOW,
    TIER_HITS,
    TRACE_SPANS,
    MetricSnapshot,
    MetricSpec,
    MetricsRegistry,
    MetricsTape,
)
from .timeline import P2Quantile, StageStats, StepTimeline
from .tracing import Span, Tracer, to_chrome_trace, write_chrome_trace

__all__ = [
    "MetricSpec",
    "MetricSnapshot",
    "MetricsRegistry",
    "MetricsTape",
    "ROUTED_OVERFLOW",
    "TIER_HITS",
    "SAMPLE_OVERFLOW",
    "GUARD_SKIPPED",
    "GUARD_NONFINITE",
    "P2Quantile",
    "StageStats",
    "StepTimeline",
    "snapshot_to_dict",
    "snapshot_from_dict",
    "write_jsonl",
    "read_jsonl",
    "to_prometheus",
    "from_prometheus",
    "prometheus_name",
    "compile_watch",
    "Span",
    "Tracer",
    "TRACE_SPANS",
    "RECORDER_BUNDLES",
    "RECORDER_EVENTS",
    "to_chrome_trace",
    "write_chrome_trace",
    "FlightRecorder",
    "TornBundle",
    "verify_bundle",
    "list_bundles",
    "TelemetryEndpoint",
]
