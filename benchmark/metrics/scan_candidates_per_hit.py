"""Rows the covering ranges hold per row the lean scans return:
``lean.scan.candidates`` over ``lean.scan.hits``, over the run
(``benchmark/counters.py``)."""

from benchmark.counters import counts


def read(r, registry=None):
    c = counts("lean.scan.candidates", "lean.scan.hits", registry=registry)
    if c is None or not c[1]:
        return None
    return c[0] / c[1]
