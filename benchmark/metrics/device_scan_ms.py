"""Lean index scans per request: exclusive ms of the ``device_scan``
stage of the window's ``query`` roots, over the client requests
completed.  Host wall around dispatch and block, not device time."""


def read(r):
    return r.per_request(("device_scan",))
