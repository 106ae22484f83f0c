"""Distributed stats + arrow reduction over the mesh.

The reference runs StatsScan on every data node and merges partial
sketches client-side (index/iterators/StatsScan.scala:125 + the
QueryPlan.Reducer, api/QueryPlan.scala:16-39); ArrowScan does the same
with delta-dictionary record batches (iterators/ArrowScan.scala:35).
Two mesh analogs:

* :func:`sharded_stats_scan` — numeric moments + histogram computed
  INSIDE shard_map with ``psum``/``pmin``/``pmax`` over ICI: the fully
  device-resident path (no host materialization of candidates at all).
* :func:`merged_stats` / :func:`merged_arrow` — the host-merge reduce:
  per-shard partial results fold through the Stat monoid
  (``stats/stat.py`` sketches are mergeable by design) or the delta
  Arrow writer + ``merge_deltas`` k-way merge.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..stats.stat import Stat, parse_stat

__all__ = ["sharded_stats_scan", "sharded_frequency_scan",
           "merged_stats", "merged_arrow", "allreduce_run_sketch",
           "allreduce_counts", "allreduce_metrics_snapshot"]


def allreduce_metrics_snapshot(reg=None) -> dict:
    """One metrics snapshot for the WHOLE mesh: every process's
    registry snapshot (bucket-bearing form) allgathers as JSON and
    folds through :func:`~geomesa_tpu.metrics.merge_snapshots` —
    counters sum, histogram moments and log-bucket tables merge, and
    p50/p95/p99 recompute over the union, so one ``/metrics.prom``
    scrape reflects every host (ISSUE 5).  Identity (modulo quantile
    recompute) under one process.  COLLECTIVE under multihost — every
    process must call it together, like the stat reducers above."""
    from ..metrics import merge_snapshots, registry as _registry
    local = (reg if reg is not None else _registry).snapshot(buckets=True)
    if jax.process_count() == 1:
        return merge_snapshots([local])
    import json

    from .multihost import allgather_strings
    blobs = allgather_strings(
        np.array([json.dumps(local)], dtype=object))
    return merge_snapshots([json.loads(b) for b in blobs])


def allreduce_run_sketch(part):
    """Merge one per-process :class:`~geomesa_tpu.stats.sketch.
    RunSketch` across all processes through the monoid (the multihost
    client-Reducer step of the lean sketch push-down, ISSUE 3): host-
    tier runs spill to their OWNING process's RAM, so their partials
    fold locally and allgather here.  Identity under one process."""
    if jax.process_count() == 1:
        return part
    import json

    from ..stats.sketch import RunSketch
    from .multihost import allgather_strings
    merged = None
    for blob in allgather_strings(
            np.array([json.dumps(part.to_json())], dtype=object)):
        p = RunSketch.from_json(json.loads(blob))
        merged = p if merged is None else merged + p
    return merged


def allreduce_counts(counts: np.ndarray) -> np.ndarray:
    """Element-wise sum of one per-process int64 count table across all
    processes (the Z3Histogram cell-table merge for host-tier runs).
    Identity under one process."""
    if jax.process_count() == 1:
        return counts
    from .multihost import allgather_concat
    return allgather_concat(
        np.asarray(counts, np.int64)[None, :]).sum(axis=0)


def _bbox_time_mask(xs, ys, ts, gs, bx, t_lo, t_hi):
    """Shared per-shard row mask: gid validity + any-box membership
    (inclusive edges) + inclusive time interval — the ONE definition the
    moments, frequency and density bodies must agree on."""
    in_box = (
        (xs[:, None] >= bx[None, :, 0])
        & (ys[:, None] >= bx[None, :, 1])
        & (xs[:, None] <= bx[None, :, 2])
        & (ys[:, None] <= bx[None, :, 3])
    ).any(axis=1)
    return (gs >= 0) & in_box & (ts >= t_lo) & (ts <= t_hi)


def _hist_pallas_ok(idx) -> bool:
    """Whether the f32 one-hot histogram kernel is EXACT for this index:
    per-shard rows bound any bin count, which must stay inside float32's
    integer range (the XLA scatter path is int64-exact)."""
    rows_per_shard = (int(idx.x.shape[0])
                      // max(int(idx.mesh.devices.size), 1))
    return rows_per_shard < (1 << 24)


@lru_cache(maxsize=8)
def _gather_program(mesh: Mesh):
    """Cached per-shard gather of a replicated value table by gid —
    shared by the stats and frequency scans (a per-call closure would
    retrace/recompile on every invocation)."""
    from .scan import gid_weight_lookup

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard"), P(None), P(None)), out_specs=P("shard"))
    def gather(gs, tab, bs):
        return gid_weight_lookup(gs, tab, bs)

    return jax.jit(gather)


@lru_cache(maxsize=32)
def _moments_program(mesh: Mesh, hist_bins: int, with_values: bool,
                     pallas_hist: bool = False):
    """Per-shard masked moments (+ optional fixed-bin histogram) reduced
    with psum/pmin/pmax — the StatsScan iterator as one collective.
    ``pallas_hist`` routes the histogram through the MXU one-hot kernel
    (XLA lowers the scatter-add to a serialized per-element loop)."""

    n_sharded = 5 if with_values else 4
    specs = (P("shard"),) * n_sharded + (P(None),) + (P(),) * 4
    # pallas_call outputs carry no varying-mesh-axes annotation, which
    # shard_map's vma checker rejects — disable the check on the pallas
    # variant (semantics unchanged; the XLA variant keeps it)
    extra = {"check_vma": False} if pallas_hist else {}

    @partial(shard_map, mesh=mesh, in_specs=specs,
             out_specs=(P("shard"),) * 5 + (P(None),), **extra)
    def moments(*args):
        if with_values:
            xs, ys, ts, gs, vals, bx, t_lo, t_hi, h_lo, h_hi = args
        else:
            xs, ys, ts, gs, bx, t_lo, t_hi, h_lo, h_hi = args
            vals = xs
        mask = _bbox_time_mask(xs, ys, ts, gs, bx, t_lo, t_hi)
        # per-shard scalar partials, reduced on host (one tiny vector
        # per stat): the chip backend lowers only SUM all-reduces, so
        # pmin/pmax collectives never compiled on real hardware
        cnt = jnp.sum(mask)[None].astype(jnp.int64)
        s = jnp.sum(jnp.where(mask, vals, 0.0))[None]
        s2 = jnp.sum(jnp.where(mask, vals * vals, 0.0))[None]
        vmin = jnp.min(jnp.where(mask, vals, jnp.inf))[None]
        vmax = jnp.max(jnp.where(mask, vals, -jnp.inf))[None]
        if hist_bins:
            w = (h_hi - h_lo) / hist_bins
            b = jnp.clip(((vals - h_lo) / w).astype(jnp.int32),
                         0, hist_bins - 1)
            if pallas_hist:
                from ..ops.pallas_kernels import hist1d_pallas
                hist = hist1d_pallas(
                    b, jnp.ones_like(b, jnp.float32), mask,
                    hist_bins).astype(jnp.int64)
            else:
                hist = jnp.zeros((hist_bins,), jnp.int64).at[b].add(
                    jnp.where(mask, 1, 0).astype(jnp.int64))
            hist = jax.lax.psum(hist, "shard")
        else:
            hist = jax.lax.psum(jnp.zeros((1,), jnp.int64), "shard")
        return cnt, s, s2, vmin, vmax, hist

    return jax.jit(moments)


def sharded_stats_scan(idx, boxes, t_lo_ms, t_hi_ms, values=None,
                       hist_bins: int = 0, hist_range=None) -> dict:
    """Collective stats over a :class:`ShardedZ3Index` for a bbox+time
    window: count / sum / sumsq / min / max (+ a fixed-bin histogram when
    ``hist_bins`` > 0) of ``values`` — a host table indexed by gid — or
    of the x coordinate when no values are given.  One device dispatch,
    partials merged over ICI; nothing but the scalars crosses to host."""
    t_lo_ms, t_hi_ms = idx._clamp_time(t_lo_ms, t_hi_ms)
    boxes = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
    with_values = values is not None
    h_lo, h_hi = (float(hist_range[0]), float(hist_range[1])) \
        if hist_range else (0.0, 1.0)
    from ..ops.pallas_kernels import GATES
    gate = GATES["hist1d"]
    use_pallas = bool(hist_bins) and _hist_pallas_ok(idx)
    args = [idx.x, idx.y, idx.dtg, idx.gid]
    if with_values:
        # per-shard gather from the replicated table by gid, offset by
        # per-process row bases under multihost (each process passes its
        # LOCAL rows' values; see ShardedZ3Index._weight_table)
        table, bases = idx._weight_table(values)
        args.append(_gather_program(idx.mesh)(idx.gid, table, bases))
    args.append(jnp.asarray(boxes))
    tail = (jnp.int64(t_lo_ms), jnp.int64(t_hi_ms),
            jnp.float64(h_lo), jnp.float64(h_hi))

    def _run(pallas_hist: bool):
        prog = _moments_program(idx.mesh, int(hist_bins), with_values,
                                pallas_hist=pallas_hist)
        out = prog(*args, *tail)
        # per-shard partials span processes under multihost; the
        # replicated histogram is host-addressable everywhere
        from .scan import _fetch_global
        return tuple(_fetch_global(v) for v in out[:5]) + (
            np.asarray(out[5]),)

    cnt, s, s2, vmin, vmax, hist = gate.run(
        lambda: _run(True), lambda: _run(False), enabled=use_pallas)
    # host reduce of the per-shard partials (n_shards scalars each)
    res = {"count": int(cnt.sum()), "sum": float(s.sum()),
           "sumsq": float(s2.sum()),
           "min": float(vmin.min()), "max": float(vmax.max())}
    if hist_bins:
        res["histogram"] = hist
    return res


@lru_cache(maxsize=32)
def _frequency_program(mesh: Mesh, depth: int, width: int,
                       pallas_hist: bool):
    """Per-shard count-min sketch + psum: each shard hashes its masked
    values with the SAME splitmix64 family as the host sketch
    (stats/stat._hash_col numeric path) and histograms each hash row —
    the reference's per-node StatsScan computing Frequency partials
    merged by the Reducer (utils/stats/Frequency + StatsScan.scala:125),
    fully device-resident."""

    specs = (P("shard"),) * 5 + (P(None),) + (P(), P())
    extra = {"check_vma": False} if pallas_hist else {}  # see _moments

    def splitmix(h):
        h = (h ^ (h >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        h = (h ^ (h >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        return h ^ (h >> jnp.uint64(31))

    @partial(shard_map, mesh=mesh, in_specs=specs, out_specs=P(None),
             **extra)
    def freq(xs, ys, ts, gs, vals, bx, t_lo, t_hi):
        mask = _bbox_time_mask(xs, ys, ts, gs, bx, t_lo, t_hi)
        # match _hash_col's numeric path bit-for-bit: truncate to int64,
        # reinterpret as uint64, xor the seeded constant, splitmix64.
        # XLA's float->int64 convert differs from numpy's for NaN/inf/
        # out-of-range values — canonicalize those to numpy's INT64_MIN
        # result first (int64 inputs pass through untouched)
        if jnp.issubdtype(vals.dtype, jnp.floating):
            lo = jnp.float64(np.iinfo(np.int64).min)
            ok = (jnp.isfinite(vals) & (vals >= lo)
                  & (vals < jnp.float64(2.0 ** 63)))
            vals = jnp.where(ok, vals, lo)
        v64 = vals.astype(jnp.int64).astype(jnp.uint64)
        rows = []
        for d in range(depth):
            seed = jnp.uint64((d + 1) * 0x9E3779B97F4A7C15
                              & 0xFFFFFFFFFFFFFFFF)
            h = splitmix(v64 ^ seed)
            bins = (h % jnp.uint64(width)).astype(jnp.int32)
            if pallas_hist:
                from ..ops.pallas_kernels import hist1d_pallas
                rows.append(hist1d_pallas(
                    bins, jnp.ones_like(bins, jnp.float32), mask,
                    width).astype(jnp.int64))
            else:
                rows.append(jnp.zeros((width,), jnp.int64).at[bins].add(
                    jnp.where(mask, 1, 0).astype(jnp.int64)))
        return jax.lax.psum(jnp.stack(rows), "shard")

    return jax.jit(freq)


def sharded_frequency_scan(idx, boxes, t_lo_ms, t_hi_ms, values,
                           depth: int = 4, width: int = 1024):
    """Device-resident Frequency (count-min) sketch over a bbox+time
    window of a ShardedZ3Index: per-shard hash+histogram partials merged
    with psum over ICI; only the (depth × width) table reaches the host.
    ``values`` follow the _weight_table contract (per-process local rows
    under multihost).  Returns a ``stats.stat.Frequency`` whose counts
    equal a host observe() over the matching rows."""
    from ..ops.pallas_kernels import GATES
    from ..stats.stat import Frequency

    t_lo_ms, t_hi_ms = idx._clamp_time(t_lo_ms, t_hi_ms)
    boxes = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
    # integer columns travel as EXACT int64: the float64 weight path
    # would lose bits past 2^53 and diverge from the host sketch's hash
    col = np.asarray(values)
    if col.dtype == object:
        # string columns: seed-independent host digest of the UTF-8
        # bytes, then the device's numeric seeded-splitmix path is
        # bit-identical to the host sketch (VERDICT r4 #8; Frequency's
        # primary use is strings, utils/stats/Frequency.scala)
        from ..stats.stat import _string_digest
        col = _string_digest(col).view(np.int64)
    table, bases = idx._weight_table(
        col, dtype=np.int64 if col.dtype.kind in "iu" else np.float64)
    vals = _gather_program(idx.mesh)(idx.gid, table, bases)
    args = (idx.x, idx.y, idx.dtg, idx.gid, vals, jnp.asarray(boxes),
            jnp.int64(t_lo_ms), jnp.int64(t_hi_ms))

    def _run(pallas_hist: bool):
        prog = _frequency_program(idx.mesh, int(depth), int(width),
                                  pallas_hist)
        return np.asarray(prog(*args))

    out = GATES["hist1d"].run(
        lambda: _run(True), lambda: _run(False),
        enabled=_hist_pallas_ok(idx))
    return Frequency("", int(depth), int(width),
                     out.astype(np.int64))


def _shard_groups(n: int, shards) -> list[np.ndarray]:
    """Per-shard row groups for the host-merge reducers.

    ``shards`` is either an int (contiguous block split — exactly the
    residency a fresh build would create, used when no sharded index
    exists yet) or a precomputed per-row shard-id array from
    ``shard_of_gids`` (TRUE residency, including append placements)."""
    if isinstance(shards, (int, np.integer)):
        per = -(-n // int(shards)) if n else 0
        return [np.arange(s, min(s + per, n))
                for s in range(0, n, per)] if per else []
    shards = np.asarray(shards)
    # unknown-residency rows (-1) form their own group: dropping them
    # would silently lose rows from the reduce
    return [np.flatnonzero(shards == s) for s in np.unique(shards)]


def merged_stats(batch, stat_spec: str, shards) -> Stat:
    """Per-shard observe + monoid merge (the client-side Reducer): each
    shard's RESIDENT rows fold into a fresh stat, partials merge
    pairwise.  For exact stats (count, minmax, histogram, enumeration,
    descriptive) the merge is exactly the single-pass result; sketches
    (TopK, Frequency) merge within their approximation guarantees — the
    same contract as the reference's Stat.+ (Stat.scala:31-90).
    ``shards``: shard-id-per-row array (true residency) or an int block
    split (see _shard_groups)."""
    proto = parse_stat(stat_spec)
    partials = []
    for rows in _shard_groups(len(batch), shards):
        part = proto.fresh_copy()
        part.observe(batch.take(rows))
        partials.append(part)
    if not partials:
        return proto
    merged = partials[0]
    for p in partials[1:]:
        merged = merged + p
    return merged


def merged_arrow(batch, sft, shards,
                 dictionary_fields: tuple[str, ...] = (),
                 sort_field: str | None = None, reverse: bool = False):
    """Per-shard DeltaWriter streams + merge_deltas k-way merge (the
    ArrowScan reduce): each shard's RESIDENT rows stream through an
    independent delta-dictionary writer (its dictionary accumulates only
    ITS values, as on a data node), and the client merge decodes +
    merges.  Without a sort field the merged table restores the input
    row order (single-chip parity) via a host permutation over the
    per-stream ordinals.  Returns a pyarrow Table."""
    from ..arrow.delta import DeltaWriter
    from ..arrow.reader import merge_deltas

    groups = _shard_groups(len(batch), shards)
    streams = []
    for rows in groups:
        w = DeltaWriter(sft, dictionary_fields, sort_field, reverse)
        w.write(batch.take(rows))
        streams.append(w.finish())
    merged = merge_deltas(streams, sort_field=sort_field, reverse=reverse)
    if (merged is not None and sort_field is None and len(groups) > 1
            and not isinstance(shards, (int, np.integer))):
        # concat order is stream-major; restore global row order (int
        # block splits are already contiguous-in-order — no reorder)
        ordinals = np.concatenate(groups)
        merged = merged.take(np.argsort(ordinals, kind="stable"))
    return merged
