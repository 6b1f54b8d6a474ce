"""Program spans on the JAX profiler's clock (DESIGN.md §13).

``span(name, sink, **ids)`` marks one piece of host work.  While a
profiler is active it enters ``jax.profiler.TraceAnnotation(name,
**ids)``, so the span lands in the same ``.xplane.pb`` as the device
ops, on the same clock, with ``ids`` as its stats.  With none active it
costs one pybind call (``is_enabled``) and records nothing.  Given a
``sink`` dict, the span also adds its ``time.perf_counter()`` duration,
in ms, to ``sink[name]`` — the operator counters (``serve.phases_ms``)
come from the same spans the trace shows.

Spans on one thread are flat: none encloses another, and none covers a
wait, so whatever the device is idle under carries the name of the work
the host was doing.  The profiler splits a span's stats at ``,``: join
several ids with ``;``.  ``bench/spans.py`` reads them from a traced
run.
"""
from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

profiling = TraceAnnotation.is_enabled     # () -> bool: a profiler is on


class span:
    """Context manager: one named span, its duration added to ``sink``."""

    __slots__ = ("_name", "_sink", "_ids", "_annotation", "_t0")

    def __init__(self, name: str, sink: dict | None = None, **ids):
        self._name = name
        self._sink = sink
        self._ids = ids
        self._annotation = None

    def __enter__(self) -> "span":
        if profiling():
            self._annotation = TraceAnnotation(self._name, **self._ids)
            self._annotation.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._sink is not None:
            ms = (perf_counter() - self._t0) * 1e3
            self._sink[self._name] = self._sink.get(self._name, 0.0) + ms
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
