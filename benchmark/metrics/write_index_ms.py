"""Write path per batch: exclusive ms of the ``write.index`` and
``write.device`` spans of the window's ``write`` roots, over the batches
written."""


def read(r):
    if not r.write_batches:
        return None
    return r.write_index_ms / r.write_batches
