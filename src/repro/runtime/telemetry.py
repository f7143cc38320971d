"""Named spans around the serving path's host work, off unless enabled.

The engine and its service wrap each phase of a tick, and each wait for
the service lock, in ``span(name)``. Off (the default), ``span`` returns
one shared null context: a check of one module global, no allocation and
no clock read. ``enable()`` makes every span a
``jax.profiler.TraceAnnotation``, so a profiler trace shows the host's
phases on the clock of the device's events; ``enable(sink)`` sends them to
``sink(name)`` instead, any callable that returns a context manager.

Nothing is stored here: the profiler trace is where spans land. Callers
build their span names once, not per call.
"""
from __future__ import annotations

import contextlib
from typing import Callable, ContextManager, Optional

_NULL = contextlib.nullcontext()
_sink: Optional[Callable[[str], ContextManager]] = None


def enable(sink: Optional[Callable[[str], ContextManager]] = None):
    """Turn spans on, into ``sink`` or else the JAX profiler's trace."""
    global _sink
    if sink is None:
        import jax
        sink = jax.profiler.TraceAnnotation
    _sink = sink


def disable():
    global _sink
    _sink = None


def span(name: str) -> ContextManager:
    if _sink is None:
        return _NULL
    return _sink(name)
