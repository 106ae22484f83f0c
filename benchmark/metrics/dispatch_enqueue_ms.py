"""Host enqueue per lean dispatch: the median of
``lean.device.enqueue.ms``, from a dispatch's entry until its jitted call
returned, over the marked dispatches of the run
(``benchmark/counters.py``).  The median, as the first dispatch of each
shape compiles inside its call during set-up."""

from benchmark.counters import timer


def read(r, registry=None):
    t = timer("lean.device.enqueue.ms", registry=registry)
    if t is None or not t.count:
        return None
    return t.quantile(0.5)
