"""Spans on the profiler's clock — the program's one span mechanism.

:func:`span` returns a ``jax.profiler.TraceAnnotation`` while a profile is
being captured (``jax.profiler.start_trace`` or the profiler server), so
the span lands on the host plane of the same trace as the device's ops,
on one clock.  Otherwise it returns one shared null context: an untraced
process pays only the ``is_enabled`` check.  Keyword metadata becomes the
event's stats in the trace.

Callers build span names once, at construction, and never hold a span
across an ``await``: a span is one stretch of one task's host time.
Counters live on the stats dataclasses (``EngineStats``, ``DriverStats``).
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

__all__ = ['span']

_NULL = contextlib.nullcontext()


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta``) in the
    profile being captured, if any."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **meta)
    return _NULL
