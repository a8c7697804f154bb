"""Host spans on the profiler's timeline, with running totals.

``span(name, stats, "field", **args)`` opens a
``jax.profiler.TraceAnnotation`` — a host event on the same clock as the
device ops while a profiler trace is being taken (``args`` ride along as
the event's stats), next to nothing otherwise — and adds the span's wall
time to ``stats.field`` when given a stats object.  It reads no array and
waits on nothing, so a span never adds a host sync; the totals are the
same whether or not a trace is running.
"""
from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def span(name: str, stats=None, field: str | None = None, **args):
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **args):
        yield
    if stats is not None:
        setattr(stats, field, getattr(stats, field)
                + time.perf_counter() - t0)
