"""Mean device backlog a lean dispatch met: the dispatches already inside
a ``device_span`` when it entered (``lean.device.inflight.sum`` over
``lean.device.dispatches``), over the run (``benchmark/counters.py``)."""

from benchmark.counters import counts


def read(r, registry=None):
    c = counts("lean.device.inflight.sum", "lean.device.dispatches",
               registry=registry)
    if c is None or not c[1]:
        return None
    return c[0] / c[1]
