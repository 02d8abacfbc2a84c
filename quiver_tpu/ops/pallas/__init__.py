"""Pallas TPU kernels, and the one switch that runs them interpreted.

The kernels compile for the chip. The CPU backend cannot compile them, so
the CPU test configuration (``tests/conftest.py``) calls
:func:`set_interpret` once; nothing else does, and on a TPU interpret mode
is refused outright — a kernel the compiler rejects must fail, not run in
the interpreter.
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret", "set_interpret"]

_INTERPRET = False


def set_interpret(on: bool) -> None:
    """Make interpret mode the default of every kernel wrapper here."""
    global _INTERPRET
    _INTERPRET = bool(on)


def resolve_interpret(interpret: bool | None) -> bool:
    """The caller's ``interpret`` (None = the :func:`set_interpret`
    default), refused on a TPU."""
    on = _INTERPRET if interpret is None else interpret
    if on and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend; the kernels "
            "run compiled there"
        )
    return on
