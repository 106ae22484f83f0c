"""Facade and planner time per request: exclusive ms of the ``plan`` and
``decompose`` stages of the window's ``query`` roots (the program's
tracer, ``obs.attribution``), over the client requests completed."""


def read(r):
    return r.per_request(("plan", "decompose"))
