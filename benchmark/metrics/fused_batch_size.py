"""Requests per fused dispatch over the window: the change of
``serving.fused.requests`` over the change of ``serving.fused.batches``."""


def read(r):
    if not r.fused_batches:
        return None
    return r.fused_requests / r.fused_batches
