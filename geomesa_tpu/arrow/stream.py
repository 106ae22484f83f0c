"""Arrow-native streaming result path (ISSUE 14).

The reference serves scan results as Arrow record batches encoded NEXT
TO THE SCAN (``index/iterators/ArrowScan.scala``): per-tablet iterators
emit delta-dictionary batches and no server-side SimpleFeature ever
exists.  The same holds here: a row-wise result pays per-row feature
ids and per-row Python objects that the index scan never does.

This module is the TPU-native ArrowScan: hit positions (still sorted
global row ids straight off the device scan) flow through

1. a **column gather** — the schema's lean scale index gathers its
   device-resident payload columns (x/y/t) with one batched on-device
   take per full-tier generation (``LeanZ3Index.gather_payload``);
   everything else gathers from the column store via ONE vectorized
   numpy take per column (``LeanBatch.take_view`` — no feature ids);
2. **vectorized feature ids** — ``LeanBatch.row_ids_vec`` mints the
   implicit ids as a fixed-width unicode array inside numpy;
3. the **columnar Arrow encoder** (``schema.encode_columns``) with
   shared :class:`~geomesa_tpu.arrow.schema.DictionaryState` delta
   dictionaries across chunks (the DeltaWriter protocol).

Zero per-row Python objects exist anywhere on the path for point
schemas (pinned by an object-count probe in tests); chunks stream as
they are encoded — a client renders the first ``chunk_rows`` rows while
the store is still gathering the rest — and each chunk records a
``query.materialize`` span with rows/bytes and block-until-ready device
attribution, so ``/metrics.prom`` shows the p99 split between scan and
materialize (``query.<schema>.scan_ms`` vs
``query.<schema>.materialize_ms``).
"""

from __future__ import annotations

import time
from typing import Callable, Iterator

import numpy as np

from ..config import ArrowProperties
from ..features.feature_type import FeatureType
from ..metrics import (
    ARROW_BYTES, ARROW_CHUNKS, ARROW_ROWS, registry as _metrics,
)
from ..obs import obs_count, span as obs_span
from .schema import (
    DictionaryState, encode_columns, sft_to_arrow_schema,
)

__all__ = ["ArrowStream", "stream_batches", "ipc_chunks",
           "auto_dictionary_fields"]


class ArrowStream:
    """An iterator of ``pa.RecordBatch`` plus the stream's schema.

    The return type of ``store.query_arrow``: iterate it for chunked
    consumption (the streaming contract — batches encode lazily as you
    pull), or call :meth:`to_table` / :meth:`to_ipc_bytes` to drain it
    whole.  A stream is single-use, like any generator."""

    def __init__(self, schema, batches: Iterator, sft: FeatureType,
                 on_close=None):
        #: the pa.Schema every yielded batch conforms to (available
        #: BEFORE the first batch — empty results still have a schema)
        self.schema = schema
        self.sft = sft
        self._batches = iter(batches)
        # a generator's finally only runs once its body has been
        # ENTERED — a stream created but never iterated would leak
        # whatever the finally was meant to release (the admission
        # token).  on_close must be idempotent; close()/__del__ call it
        # even for never-started streams.
        self._on_close = on_close

    def __iter__(self):
        # returns self (not the inner generator) so a bare
        # `for rb in store.query_arrow(...)` keeps THIS object alive
        # for the whole drain — handing out self._batches would let
        # refcounting collect the wrapper mid-loop, and __del__ would
        # close the generator out from under the iteration
        return self

    def __next__(self):
        return next(self._batches)

    def close(self) -> None:
        """Release the stream without draining it: closes the
        underlying generator and fires ``on_close`` (idempotent)."""
        closer = getattr(self._batches, "close", None)
        if closer is not None:
            closer()
        if self._on_close is not None:
            cb, self._on_close = self._on_close, None
            cb()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def to_table(self):
        """Drain into one ``pa.Table`` (dictionary columns keep their
        dictionary type)."""
        from .schema import _pa
        pa = _pa()
        return pa.Table.from_batches(list(self._batches),
                                     schema=self.schema)

    def to_ipc_bytes(self, buffer_bytes: int | None = None) -> bytes:
        """Drain into one Arrow IPC stream blob (delta dictionaries —
        readable by stock ``pa.ipc.open_stream``)."""
        return b"".join(ipc_chunks(self, buffer_bytes=buffer_bytes))


def auto_dictionary_fields(sft: FeatureType, batch, positions,
                           threshold: int | None = None,
                           sample: int = 8192) -> tuple[str, ...]:
    """String attributes worth dictionary-encoding for this result:
    observed cardinality over (a sample of) the hit rows must stay
    at/below ``geomesa.arrow.dictionary.threshold`` — beyond it the
    dictionary outgrows its savings and every delta message bloats.
    The sample is one vectorized ``np.unique`` per string column; no
    per-row Python work."""
    if threshold is None:
        threshold = ArrowProperties.DICTIONARY_THRESHOLD.to_int()
    if threshold <= 0 or len(positions) == 0:
        return ()
    probe = np.asarray(positions)[:sample]
    out = []
    for attr in sft.attributes:
        if attr.is_geometry or attr.type != "string":
            continue
        col = batch.column(attr.name)[probe]
        try:
            n_distinct = len(np.unique(col))
        except TypeError:   # None mixed in — unsortable, skip encoding
            continue
        if n_distinct <= threshold:
            out.append(attr.name)
    return tuple(out)


def _schema_columns(sft: FeatureType) -> tuple[set, bool]:
    """The physical column names the Arrow schema consumes, and whether
    it needs the packed (non-point) geometry."""
    names: set = set()
    packed = False
    for attr in sft.attributes:
        if attr.is_geometry:
            if attr.type == "point":
                names.add(f"{attr.name}_x")
                names.add(f"{attr.name}_y")
            elif attr.name == sft.default_geom:
                packed = True
        else:
            names.add(attr.name)
    return names, packed


def stream_batches(sft: FeatureType, schema, batch, positions,
                   chunk_rows: int | None = None,
                   payload_gather: Callable | None = None,
                   payload_columns: tuple[str, ...] = (),
                   schema_name: str | None = None,
                   dictionaries: dict | None = None,
                   deadline=None):
    """Generator of ``pa.RecordBatch`` over the hit ``positions`` of
    one query — the streaming encode loop (module doc).

    ``batch`` is the schema's column store (LeanBatch or FeatureBatch).
    ``payload_gather(chunk_positions)`` — when given — returns a dict
    of column-name → array overriding ``payload_columns`` (the lean
    scale index's on-device gather); every other needed column gathers
    host-side via one vectorized take.  ``dictionaries`` carries the
    shared per-attribute :class:`DictionaryState` accumulations across
    chunks (the delta protocol).

    ``deadline`` is an EXPLICIT resilience CancelScope (not the ambient
    contextvar — this generator's body runs long after the creating
    call's scope exited): polled between chunks, and on expiry or
    cancellation the stream simply ENDS — ipc_chunks still closes the
    IPC writer, so the client sees a well-formed (truncated) Arrow
    stream, never a mid-message cut (ISSUE 16)."""
    if chunk_rows is None:
        chunk_rows = ArrowProperties.CHUNK_ROWS.to_int()
    chunk_rows = max(1, int(chunk_rows))
    if dictionaries is None:
        dictionaries = {}
    positions = np.asarray(positions, dtype=np.int64)
    needed, needs_packed = _schema_columns(sft)
    host_cols = needed - set(payload_columns if payload_gather else ())
    lean = hasattr(batch, "take_view")
    name = schema_name or sft.name or "unknown"
    timer = _metrics.timer(f"query.{name}.materialize_ms")
    for s in range(0, len(positions), chunk_rows):
        if deadline is not None and deadline.poll():
            break
        chunk = positions[s:s + chunk_rows]
        m = len(chunk)
        t0 = time.perf_counter()
        with obs_span("query.materialize", schema=name, rows=m) as sp:
            if lean:
                view = batch.take_view(chunk, columns=host_cols)
                cols = view.columns
                geoms = view.geoms if needs_packed else None
                if batch.id_prefix:
                    fids = batch.row_ids_vec(chunk)
                else:
                    # implicit unprefixed ids: Arrow's own int64→utf8
                    # compute cast beats numpy's per-element astype by
                    # ~10x and produces the identical strings
                    from .schema import _pa
                    pa = _pa()
                    fids = pa.array(chunk).cast(pa.utf8())
            else:
                cols = {k: v[chunk] for k, v in batch.columns.items()
                        if k in host_cols}
                geoms = (batch.geoms.take(chunk)
                         if batch.geoms is not None and needs_packed
                         else None)
                fids = batch.ids[chunk]
            if payload_gather is not None:
                cols.update(payload_gather(chunk))
            rb = encode_columns(sft, schema, cols, m, fids=fids,
                                geoms=geoms, dictionaries=dictionaries)
            obs_count(ARROW_CHUNKS)
            obs_count(ARROW_ROWS, m)
            sp.set_attr("bytes", int(rb.nbytes))
            timer.update((time.perf_counter() - t0) * 1e3)
        yield rb


class _BufferedSink:
    """Minimal file-like sink collecting the IPC writer's output so the
    streaming response can flush in ``geomesa.arrow.stream.buffer.bytes``
    sized chunks instead of one write per IPC message."""

    #: the file-object protocol bits pyarrow's PythonFile wrapper
    #: checks before writing
    closed = False

    def __init__(self) -> None:
        self._buf = bytearray()

    def writable(self) -> bool:
        return True

    def write(self, data) -> int:
        self._buf += data
        return len(data)

    @property
    def size(self) -> int:
        return len(self._buf)

    def drain(self) -> bytes:
        out = bytes(self._buf)
        del self._buf[:]
        return out

    def flush(self) -> None:   # pyarrow closes the stream politely
        pass

    def close(self) -> None:
        pass


def ipc_chunks(stream: ArrowStream,
               buffer_bytes: int | None = None) -> Iterator[bytes]:
    """Arrow IPC stream bytes over an :class:`ArrowStream`, yielded in
    ≥ ``buffer_bytes`` chunks AS BATCHES COMPLETE — the body generator
    of the ``/query?format=arrow`` chunked response.  Emits delta
    dictionary messages (DeltaWriter protocol) and always produces a
    valid stream: an empty result is a schema header + end-of-stream
    marker a stock reader opens cleanly."""
    from .schema import _pa
    pa = _pa()
    if buffer_bytes is None:
        buffer_bytes = ArrowProperties.STREAM_BUFFER_BYTES.to_int()
    sink = _BufferedSink()
    writer = pa.ipc.new_stream(
        sink, stream.schema,
        options=pa.ipc.IpcWriteOptions(emit_dictionary_deltas=True))
    from ..resilience import fault_point
    for rb in stream:
        writer.write_batch(rb)
        if sink.size >= buffer_bytes:
            fault_point("arrow.flush")
            obs_count(ARROW_BYTES, sink.size)
            yield sink.drain()
    writer.close()
    if sink.size:
        fault_point("arrow.flush")
        obs_count(ARROW_BYTES, sink.size)
        yield sink.drain()
