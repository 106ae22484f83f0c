"""Share of the device generations held at each lean z3 totals probe
that the probe sought: 100 x ``lean.probe.seeks`` over
``lean.probe.generations``, over the run (``benchmark/counters.py``).
Sentinel padding counts as a seek, so the share can pass 100%."""

from benchmark.counters import counts


def read(r, registry=None):
    c = counts("lean.probe.seeks", "lean.probe.generations",
               registry=registry)
    if c is None or not c[1]:
        return None
    return 100.0 * c[0] / c[1]
