"""Host spans of the serving path, recorded by a `jax.profiler` session.

Every span is a `jax.profiler.TraceAnnotation` named ``serve.<name>``, so
it lands in the profiler's own trace on the same clock as the device's
operations. Tracing is on exactly while a profiler session is active
(`jax.profiler.trace` / `start_trace`); with none active a span records
nothing and formats nothing. Metadata values are ints or the ids the
serving layer already holds (ticket ids, stream ids, class names).

The span tree is listed in `docs/ARCHITECTURE.md` (Tracing).
"""
from __future__ import annotations

import jax

PREFIX = "serve."


def span(name: str, **meta):
    """A context manager timing ``serve.<name>``, carrying ``meta`` as the
    event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
