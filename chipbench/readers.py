"""The readers a per-layer metric file may name, implemented once.

A metric file (``chipbench/metrics/<name>.json``) picks one reader and gives
its arguments as data, so a later PR adds a metric over a new scope, span,
counter or stage with a JSON file alone. A reader that finds nothing to read
returns ``None`` and the harness leaves the metric out of the line; a share
of a roofline or of a peak is never reported as 0.

``ctx`` is what one traced run collected: ``trace`` (an ``xplane.Trace``),
``steps`` (steps completed in the traced window), ``spans`` (host span name
-> list of seconds), ``counters``, ``stages``, ``work`` (the counts that
``work.py`` reads), ``peaks``, ``chips``, ``workers``, ``model_scope`` (what
fills ``{model_scope}`` in a pattern: the scope the cell's model file
declares) and ``claims`` (the filled patterns of every
``device_time_by_scope`` metric of the cell).
"""

from __future__ import annotations

from .work import WORK

__all__ = ["READERS", "read"]


def _per_step_ms(seconds: float, ctx: dict):
    if not seconds or not ctx["steps"]:
        return None
    return seconds / ctx["steps"] * 1e3


def device_time_by_scope(ctx, pattern: str):
    """Device self time per step of the ops whose scope path matches."""
    return _per_step_ms(ctx["trace"].scope_s(pattern), ctx)


def unclaimed_device_time(ctx):
    """Device self time per step that no ``device_time_by_scope`` metric of
    the cell claimed."""
    return _per_step_ms(ctx["trace"].unclaimed_s(ctx["claims"]), ctx)


def collective_exposed(ctx):
    return _per_step_ms(ctx["trace"].collective_exposed_s(), ctx)


def host_span(ctx, span: str):
    """Mean milliseconds of the benchmark's host span of that name."""
    seconds = ctx["spans"].get(span)
    if not seconds:
        return None
    return sum(seconds) / len(seconds) * 1e3


def counter(ctx, name: str):
    return ctx["counters"].get(name)


def setup_stage(ctx, stage: str):
    return ctx["stages"].get(stage)


def roofline(ctx, pattern: str, work: str, peak: str):
    """The least time the chip could take for the step's work of that kind
    (bytes over the peak bandwidth), as a share of the device time its ops
    took."""
    seconds = ctx["trace"].scope_s(pattern)
    if not seconds or not ctx["steps"] or ctx["work"] is None:
        return None
    least = WORK[work](ctx["work"]) / (ctx["peaks"][peak] * 1e9)
    return 100.0 * least / (seconds / ctx["steps"])


def idle_share(ctx):
    trace = ctx["trace"]
    if not trace.devices or not trace.window_s():
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())


def mfu(ctx, work: str, peak: str):
    """The whole step's operations, times steps per second over the traced
    window, over the chip's peak. ``work`` counts one worker's step and
    every chip is one worker, so the chips cancel."""
    trace = ctx["trace"]
    if (not trace.devices or not ctx["steps"] or ctx["work"] is None
            or not trace.window_s()):
        return None
    rate = (WORK[work](ctx["work"]) * ctx["workers"] * ctx["steps"]
            / trace.window_s())
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"][peak] * 1e12)


READERS = {
    "device_time_by_scope": device_time_by_scope,
    "unclaimed_device_time": unclaimed_device_time,
    "collective_exposed": collective_exposed,
    "host_span": host_span,
    "counter": counter,
    "setup_stage": setup_stage,
    "roofline": roofline,
    "idle_share": idle_share,
    "mfu": mfu,
}


def read(metric: dict, ctx: dict):
    """The value of one metric file's metric, or None."""
    return READERS[metric["reader"]](ctx, **metric.get("args", {}))
