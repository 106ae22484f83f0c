"""The program's own counters and timers, as a per-layer reader sees them.

The harness hands a reader its window readings; what the program counts
itself (``geomesa_tpu.metrics.registry``) is read here when the reader
runs, after the window, so it covers the whole process: set-up (ingest,
the first request of each kind alone, the replay of every client's own
window requests at the window's concurrency) and the window.  A name the
program does not register reads as ``None``, so a reader returns nothing
on a program that lacks it.
"""

from __future__ import annotations


def _registry(registry):
    if registry is not None:
        return registry
    from geomesa_tpu.metrics import registry as program
    return program


def counts(*names: str, registry=None) -> list | None:
    """The counters' values, or None when any is not registered."""
    reg = _registry(registry)
    known = set(reg.names())
    if not set(names) <= known:
        return None
    return [reg.counter(n).count for n in names]


def timer(name: str, registry=None):
    """The timer (``count``, ``total`` ms, ``quantile(q)``), or None
    when not registered."""
    reg = _registry(registry)
    if name not in reg.names():
        return None
    return reg.timer(name)
