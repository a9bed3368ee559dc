"""Spans of the served path in the JAX profiler's trace.

The server opens a ``packrat.*`` span at each layer boundary of its
served path: the dispatcher's batch (``packrat.dispatch``), the plane's
unit gate and runner call (``packrat.gate``, ``packrat.run``), the LM
engine's resident-cache lock, enqueue and device wait
(``packrat.lock``, ``packrat.enqueue``, ``packrat.device_wait``), the
reactor's completion delivery and timers (``packrat.deliver``,
``packrat.timer``), the controller's tick (``packrat.control``) and
Python's collector (``packrat.gc``).  Spans of one batch carry its
plane-wide ``batch`` number.

They are ``jax.profiler.TraceAnnotation`` events: they land in the
profiler's own trace, on the clock of the device's operations, and
record only while the profiler runs.  With it off, :func:`span` returns
one shared no-op context; callers whose arguments cost something to
compute test :func:`on` first.
"""

from __future__ import annotations

import contextlib
import gc
from typing import ContextManager, Optional

from jax.profiler import TraceAnnotation

NOOP: ContextManager = contextlib.nullcontext()

# whether the profiler is recording
on = TraceAnnotation.is_enabled


def span(name: str, **args) -> ContextManager:
    """A span named ``name`` carrying ``args`` while the profiler runs,
    else :data:`NOOP`.  Entered, it yields the annotation (``None`` when
    off), whose ``set_metadata`` adds arguments known only at its end."""
    if not on():
        return NOOP
    return TraceAnnotation(name, **args)


class _CollectorSpans:
    """``gc.callbacks`` hook: one ``packrat.gc`` span from a collection's
    start to its stop, with its generation and what it collected.  A
    collection runs to its end on the thread that started it, so starts
    and stops pair up."""

    def __init__(self) -> None:
        self._open: Optional[TraceAnnotation] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if on():
                self._open = TraceAnnotation("packrat.gc",
                                             gen=info["generation"])
                self._open.__enter__()
        elif self._open is not None:
            ann, self._open = self._open, None
            ann.set_metadata(collected=info["collected"])
            ann.__exit__(None, None, None)


_COLLECTOR = _CollectorSpans()


def watch_collector() -> None:
    """Register the collector's span hook; once per process."""
    if _COLLECTOR not in gc.callbacks:
        gc.callbacks.append(_COLLECTOR)


__all__ = ["NOOP", "on", "span", "watch_collector"]
