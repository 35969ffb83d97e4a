"""Named host spans around the phases of host-side work.

`span(name)` marks one stretch of host work.  It always enters
`jax.profiler.TraceAnnotation(name)`, so a profile taken around the work
shows the span on the device trace's clock.  Inside a `recording()`
context it also keeps `(name, start_ns, end_ns)` on the
`time.perf_counter_ns` clock, in the list that the context yields, to be
written out once the work is done:

    with spans.recording() as recorded:
        part = DevicePartition.from_graph(graph)
    # recorded: [("gre.ingress.sort", t0, t1), ("gre.ingress.csr", ...), ...]

Outside `recording()` nothing is kept.
"""
from __future__ import annotations

import contextlib
import contextvars
import time

import jax

_recorded: contextvars.ContextVar = contextvars.ContextVar(
    "repro_spans_recorded", default=None)


@contextlib.contextmanager
def recording():
    """Keep the spans that end inside this context; yields their list."""
    recorded = []
    token = _recorded.set(recorded)
    try:
        yield recorded
    finally:
        _recorded.reset(token)


@contextlib.contextmanager
def span(name: str):
    """A profiler annotation named `name`, recorded under `recording()`."""
    with jax.profiler.TraceAnnotation(name):
        start = time.perf_counter_ns()
        yield
        end = time.perf_counter_ns()
    recorded = _recorded.get()
    if recorded is not None:
        recorded.append((name, start, end))
