"""Closure timing.

The analog of the reference's MethodProfiling
(geomesa-utils/.../stats/MethodProfiling.scala — ``profile(label)``
closure timing feeding the explainer/logs).  Query phases reach
``jax.profiler`` traces through the tracer's spans (obs/trace.py),
each of which enters a ``TraceAnnotation`` of its name.
"""

from __future__ import annotations

import contextlib
import time

__all__ = ["profile", "Timings"]


class Timings:
    """Accumulates label → [elapsed_ms]; the ``complete`` sink."""

    def __init__(self):
        self.times: dict[str, list[float]] = {}

    def add(self, label: str, ms: float):
        self.times.setdefault(label, []).append(ms)

    def total_ms(self, label: str) -> float:
        return sum(self.times.get(label, ()))

    def __repr__(self):
        parts = [f"{k}={self.total_ms(k):.1f}ms" for k in sorted(self.times)]
        return f"Timings({', '.join(parts)})"


class _Span:
    """Yielded by :func:`profile`; ``.ms`` is set when the block exits."""

    ms: float = 0.0


@contextlib.contextmanager
def profile(label: str, sink: Timings | None = None, explain=None):
    """Time a block; optionally record into ``sink`` and/or an Explainer.

    Yields a span whose ``.ms`` holds the elapsed time after exit;
    timings are recorded even when the block raises (failing executions
    are exactly the ones a profiler must show).
    """
    span = _Span()
    t0 = time.perf_counter()
    try:
        yield span
    finally:
        span.ms = (time.perf_counter() - t0) * 1e3
        if sink is not None:
            sink.add(label, span.ms)
        if explain is not None:
            ms = span.ms
            explain(lambda: f"{label}: {ms:.1f}ms")
