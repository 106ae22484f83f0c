"""Materialize and Arrow per request: exclusive ms of the ``materialize``
stage of the window's ``query`` roots, plus the ``query.materialize``
spans that open roots of their own (the Arrow stream gathers payload
after its ``query`` root has closed), over the client requests
completed.  Nothing to read where neither exists."""


def read(r):
    ms = (r.query_stage_ms.get("materialize", 0.0)
          + r.other_roots.get("query.materialize", [0, 0.0])[1])
    if not r.completed or not ms:
        return None
    return ms / r.completed
