"""What XLA compiled in this process, counted from JAX's own events.

``jax.monitoring`` reports every backend compilation
(``/jax/core/compile/backend_compile_duration``: the seconds of a real
compile, or of the retrieval when the persistent compilation cache served
the program) and every persistent-cache hit
(``/jax/compilation_cache/cache_hits``). :func:`compile_watch` listens to
both and keeps three running totals; a caller that wants to know what ONE
call paid reads them before and after it. ``DistributedTrainer.step`` does
so around the launch of its jitted step, which is how a re-keyed program
(an eager resplit, ``refresh()``, a grown ``routed_alpha``) is put down to
the step that compiled it.

There is one watch a process, registered at the first call and never
removed: its users (trainers) have no ``close()`` at which a listener of
their own could be taken away again, and every listener is called on every
event of the process.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

import jax.monitoring

__all__ = ["CompileTotals", "CompileWatch", "compile_watch"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileTotals(NamedTuple):
    """Running totals since the watch was registered."""

    compiles: int = 0  # backend compilations, cache-served ones included
    seconds: float = 0.0  # their wall time (a hit: the retrieval's)
    cache_hits: int = 0  # those the persistent cache served


class CompileWatch:
    """``totals`` is replaced, never mutated: a caller keeps the tuple it
    read before a call and compares by identity afterwards (``after is not
    before``: something compiled in between), which costs a step that
    compiles nothing two attribute reads. Compilations come from whichever
    thread runs them, so the replacement holds a lock."""

    def __init__(self):
        self.totals = CompileTotals()
        self._lock = threading.Lock()

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                t = self.totals
                self.totals = t._replace(
                    compiles=t.compiles + 1, seconds=t.seconds + seconds)

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                t = self.totals
                self.totals = t._replace(cache_hits=t.cache_hits + 1)


_watch: CompileWatch | None = None
_register_lock = threading.Lock()


def compile_watch() -> CompileWatch:
    """The process's one :class:`CompileWatch`, registered with
    ``jax.monitoring`` on the first call."""
    global _watch
    with _register_lock:
        if _watch is None:
            watch = CompileWatch()
            jax.monitoring.register_event_duration_secs_listener(
                watch._on_duration)
            jax.monitoring.register_event_listener(watch._on_event)
            _watch = watch
    return _watch
