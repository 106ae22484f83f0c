"""Share of the profiled seconds in which no operation ran on the device,
in a cell that ingests while it queries (``benchmark/trace_reduce.py``)."""


def read(r):
    if r.profile is None:
        return None
    return 100.0 * r.profile["idle_share"]
