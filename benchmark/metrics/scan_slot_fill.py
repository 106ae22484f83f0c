"""Share of the slots the lean scans gather and test that hold a row of
the covering ranges: 100 x ``lean.scan.candidates`` over
``lean.scan.slots``, over the run (``benchmark/counters.py``)."""

from benchmark.counters import counts


def read(r, registry=None):
    c = counts("lean.scan.candidates", "lean.scan.slots",
               registry=registry)
    if c is None or not c[1]:
        return None
    return 100.0 * c[0] / c[1]
