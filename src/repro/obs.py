"""Program spans on the profiler's clock.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``. While a
profiler session runs (``jax.profiler.start_trace``) it records one host
event on the ``/host:CPU`` plane of that session's trace, on the same clock
as the device's events, with ``meta`` as the event's arguments; with no
session it records nothing and costs about a microsecond. Spans on one
thread nest, so a span opened inside another lies inside it in the trace.
The serving path's spans carry their query's id as ``qid``, which ties one
request's spans together across the submitting and draining threads;
``set_metadata(qid=...)`` adds it to an open span once it is known.

Counters that read the same stages are plain ``ServiceStats`` fields.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A context manager that records ``name`` with ``meta`` while traced."""
    return TraceAnnotation(name, **meta)
