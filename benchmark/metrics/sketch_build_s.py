"""Seconds the planner spent building its estimation sketches
(``plan.sketch.build.ms``) over the run (``benchmark/counters.py``): the
first request of each kind pays them in set-up, and a cell that writes
nothing rebuilds none in the window."""

from benchmark.counters import timer


def read(r, registry=None):
    t = timer("plan.sketch.build.ms", registry=registry)
    if t is None or not t.count:
        return None
    return t.total / 1e3
