"""Host spans at the program's layer boundaries, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: with a profiler session open
(``jax.profiler.trace(dir)`` or ``start_trace``/``stop_trace``) it lands in
the session's ``.xplane.pb`` beside the device's programs, on the same
clock; without one it records nothing.  Counts given as keyword arguments
become the event's stats.  Spans belong in host code only: the body of a
jitted function runs once, at trace time.  The spans the engine and the
trainer write are listed in ``docs/architecture.md`` (Observability).
"""
from __future__ import annotations

import jax


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` whose stats are ``counts``; use it as a
    context manager."""
    return jax.profiler.TraceAnnotation(name, **counts)
