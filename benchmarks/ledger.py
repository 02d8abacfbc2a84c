"""The run's graftscope ``metrics.jsonl`` artifact (registry snapshots).

Benchmark results themselves go to stdout as JSON lines
(``benchmarks.common.emit``); the driver keeps the chip record in
``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import datetime
import os


def metrics_jsonl_path() -> str | None:
    """Location of the run's graftscope ``metrics.jsonl`` artifact.

    Set via ``QUIVER_METRICS_JSONL``; ``None`` (unset/empty) disables the
    artifact — bench runs must not silently grow files in the repo.
    """
    return os.environ.get("QUIVER_METRICS_JSONL") or None


def append_metrics(snapshots, extra: dict | None = None) -> int:
    """Append :class:`MetricSnapshot` rows to the metrics.jsonl artifact.

    Written from inside the measured process at emit time, best-effort (a
    full disk must not kill a measurement run). Returns the number of rows
    written (0 when the artifact is disabled)."""
    path = metrics_jsonl_path()
    snapshots = list(snapshots)
    if not path or not snapshots:
        return 0
    from quiver_tpu.obs.export import write_jsonl

    row = dict(extra or {})
    row.setdefault(
        "ts",
        datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%SZ"),
    )
    try:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        return write_jsonl(snapshots, path, extra=row)
    except OSError:
        return 0


def read_metrics(path: str | None = None):
    """Parse a metrics.jsonl artifact back into snapshots (offline
    analysis twin of :func:`append_metrics`)."""
    from quiver_tpu.obs.export import read_jsonl

    p = path or metrics_jsonl_path()
    if not p or not os.path.exists(p):
        return []
    return read_jsonl(p)
