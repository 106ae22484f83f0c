"""Z3 point index: bbox + time queries over (lon, lat, dtg) point features.

TPU-native analog of the reference's Z3 index
(geomesa-index-api/.../index/z3/Z3IndexKeySpace.scala):

* **Key layout.** The reference writes ``[1B shard][2B bin][8B z][id]``
  rows (Z3IndexKeySpace.scala:60).  Here the same order lives as two
  sorted device columns — ``bins`` (int32) and ``z`` (int64) sorted
  lexicographically — plus ``pos``, the permutation into the original
  feature columns.  No shard byte: write/scan parallelism comes from mesh
  sharding, not key-prefix salting (SURVEY.md §2.7).
* **Write path.** ``build`` = host time-binning (calendar-aware,
  BinnedTime semantics) → jitted vectorized SFC encode (the reference's
  per-feature hot loop, Z3IndexKeySpace.toIndexKey:64-96, as one fused
  device kernel) → device lexsort (the KV store's implicit sort made
  explicit).
* **Query path.** Host planning mirrors Z3IndexKeySpace.getIndexValues/
  getRanges (:98-189): bin the time interval, decompose bbox × per-bin
  time windows into covering z-ranges with the scan-ranges budget split
  across bins (:166-168).  Device scan = vectorized binary-search seeks +
  one fixed-capacity gather + a fused candidate mask combining the
  normalized-int bounds check (filters/Z3Filter.scala:19-55 semantics)
  with the exact double-precision predicate (the reference's
  FilterTransformIterator CQL re-check).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.binnedtime import TimePeriod, max_date_ms, max_offset, to_binned_time
from ..curve.sfc import Z3SFC, z3_sfc
from ..curve.zorder import deinterleave3
from ..config import DEFAULT_MAX_RANGES, QueryProperties
from ..obs import device_span
from ..ops.search import (
    coded_pos_bits, expand_ranges, gather_capacity, pack_coded,
    pack_wire, pad_boxes, pad_pow2, pad_ranges, run_packed_query,
    searchsorted2,
)


def _use_pallas_scan() -> bool:
    """Pallas candidate scan: on by default on TPU backends, off elsewhere
    (interpret mode would be slower than the fused XLA path)."""
    if not QueryProperties.PALLAS_SCAN.get():
        return False
    from ..ops.pallas_kernels import on_tpu
    return on_tpu()

__all__ = ["Z3PointIndex", "Z3QueryPlan", "plan_z3_query"]


@dataclass
class Z3QueryPlan:
    """Host-side scan plan: covering ranges + filter bounds (all numpy)."""

    # per-range arrays (R,)
    rbin: np.ndarray      # int32 time bin
    rzlo: np.ndarray      # int64 inclusive z lo
    rzhi: np.ndarray      # int64 inclusive z hi
    rtlo: np.ndarray      # int32 normalized time lo for the range's bin
    rthi: np.ndarray      # int32 normalized time hi
    # normalized-int spatial bounds (Z3Filter semantics), per box (B, 4)
    ixy: np.ndarray
    # exact double-precision bounds
    boxes: np.ndarray     # (B, 4) xmin, ymin, xmax, ymax
    t_lo_ms: int
    t_hi_ms: int

    @property
    def num_ranges(self) -> int:
        return len(self.rbin)


def _time_windows_by_bin(t_lo_ms: int, t_hi_ms: int, period: TimePeriod):
    """Split [lo, hi] ms into per-bin offset windows; mirror of the
    reference's ``timesByBin`` construction (Z3IndexKeySpace.scala:120-158):
    interior bins get the whole period, boundary bins get partial windows."""
    lo_ms = max(0, int(t_lo_ms))
    hi_ms = min(int(t_hi_ms), max_date_ms(period) - 1)
    if lo_ms > hi_ms:
        return {}
    blo_a, olo_a = to_binned_time(lo_ms, period)
    bhi_a, ohi_a = to_binned_time(hi_ms, period)
    blo, olo, bhi, ohi = int(blo_a), int(olo_a), int(bhi_a), int(ohi_a)
    whole = (0, max_offset(period))
    if blo == bhi:
        return {blo: (olo, ohi)}
    windows = {blo: (olo, whole[1]), bhi: (0, ohi)}
    for b in range(blo + 1, bhi):
        windows[b] = whole
    return windows


def plan_z3_query(
    boxes,
    t_lo_ms: int,
    t_hi_ms: int,
    period: TimePeriod | str = TimePeriod.WEEK,
    max_ranges: int = DEFAULT_MAX_RANGES,
    sfc=None,
) -> Z3QueryPlan:
    """Decompose bbox(es) + time interval into a covering-range scan plan.

    The scan-ranges budget is split across time bins as in
    Z3IndexKeySpace.getRanges (:166-168); whole-period bins share one
    decomposition, partial (boundary) bins get their own.  ``sfc``
    selects the curve (versioned index layouts: the legacy
    semi-normalized curve for v1, the current curve by default — the
    reference's Z3IndexV1..Vn read-path dispatch)."""
    period = TimePeriod.parse(period)
    sfc = sfc if sfc is not None else z3_sfc(period)
    boxes = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
    windows = _time_windows_by_bin(t_lo_ms, t_hi_ms, period)
    empty = np.empty(0, dtype=np.int64)
    if not windows:
        return Z3QueryPlan(
            rbin=empty.astype(np.int32), rzlo=empty, rzhi=empty,
            rtlo=empty.astype(np.int32), rthi=empty.astype(np.int32),
            ixy=np.empty((0, 4), np.int32), boxes=boxes,
            t_lo_ms=int(t_lo_ms), t_hi_ms=int(t_hi_ms),
        )
    target = max(1, max_ranges // max(1, len(windows)))

    # group bins by identical time window so whole-period bins share one
    # range decomposition
    by_window: dict[tuple[int, int], list[int]] = {}
    for b, w in windows.items():
        by_window.setdefault(w, []).append(b)

    rbin, rzlo, rzhi, rtlo, rthi = [], [], [], [], []
    for (wlo, whi), bs in by_window.items():
        zr = sfc.ranges(boxes, [(wlo, whi)], max_ranges=target)
        itlo = sfc.time.normalize_scalar(float(wlo))
        ithi = sfc.time.normalize_scalar(float(whi))
        for b in sorted(bs):
            rbin.append(np.full(len(zr), b, dtype=np.int32))
            rzlo.append(zr[:, 0])
            rzhi.append(zr[:, 1])
            rtlo.append(np.full(len(zr), itlo, dtype=np.int32))
            rthi.append(np.full(len(zr), ithi, dtype=np.int32))

    ixy = np.stack(
        [
            [
                sfc.lon.normalize_scalar(b[0]),
                sfc.lat.normalize_scalar(b[1]),
                sfc.lon.normalize_scalar(b[2]),
                sfc.lat.normalize_scalar(b[3]),
            ]
            for b in boxes
        ]
    ).astype(np.int32)

    return Z3QueryPlan(
        rbin=np.concatenate(rbin),
        rzlo=np.concatenate(rzlo),
        rzhi=np.concatenate(rzhi),
        rtlo=np.concatenate(rtlo),
        rthi=np.concatenate(rthi),
        ixy=ixy,
        boxes=boxes,
        t_lo_ms=int(t_lo_ms),
        t_hi_ms=int(t_hi_ms),
    )


def candidate_mask(zc, rtlo_c, rthi_c, ixy, boxes, xc, yc, tc,
                   t_lo_ms, t_hi_ms, cqid=None, bqid=None, qtlo=None,
                   qthi=None):
    """Shared fused candidate filter: z-decode int-space bounds test
    (Z3Filter.inBounds, filters/Z3Filter.scala:19-55) AND the exact
    double-precision re-check (FilterTransformIterator) — used by the
    single-query, batched, and sharded scan programs so the mask
    semantics cannot diverge.

    ``rtlo_c``/``rthi_c`` are per-CANDIDATE normalized time bounds
    (already gathered by owning range).  With ``cqid``/``bqid`` given,
    boxes only apply to candidates of the same query; exact time bounds
    then come from ``qtlo``/``qthi`` per query instead of the scalars.
    """
    ix, iy, it = deinterleave3(zc.astype(jnp.uint64))
    ix = ix.astype(jnp.int32)
    iy = iy.astype(jnp.int32)
    it = it.astype(jnp.int32)
    box_pairs = (
        (ix[:, None] >= ixy[None, :, 0])
        & (iy[:, None] >= ixy[None, :, 1])
        & (ix[:, None] <= ixy[None, :, 2])
        & (iy[:, None] <= ixy[None, :, 3])
    )
    exact_pairs = (
        (xc[:, None] >= boxes[None, :, 0])
        & (yc[:, None] >= boxes[None, :, 1])
        & (xc[:, None] <= boxes[None, :, 2])
        & (yc[:, None] <= boxes[None, :, 3])
    )
    if cqid is not None:
        same_q = cqid[:, None] == bqid[None, :]
        box_pairs &= same_q
        exact_pairs &= same_q
        in_time_exact = (tc >= qtlo[cqid]) & (tc <= qthi[cqid])
    else:
        in_time_exact = (tc >= t_lo_ms) & (tc <= t_hi_ms)
    in_time_int = (it >= rtlo_c) & (it <= rthi_c)
    return (box_pairs.any(axis=1) & in_time_int
            & exact_pairs.any(axis=1) & in_time_exact)


def _scan_core(
    bins, z, pos, x, y, dtg,
    rbin, rzlo, rzhi, rtlo, rthi,
    ixy, boxes, t_lo_ms, t_hi_ms,
    capacity: int, use_pallas: bool,
):
    """The scan body shared by every single-query program: binary-search
    seeks + fixed-capacity gather + fused candidate mask.  The mask fuses
    the reference's two server-side stages — the z-decode int-space
    bounds test (Z3Iterator/Z3Filter, filters/Z3Filter.scala:19-55) and
    the exact double-precision re-check (FilterTransformIterator).
    Returns ``(posc, mask, total_candidates)``; only the wire packing
    differs between the jitted wrappers, so the hit semantics cannot
    diverge between them."""
    starts = searchsorted2(bins, z, rbin, rzlo, side="left")
    ends = searchsorted2(bins, z, rbin, rzhi, side="right")
    counts = jnp.maximum(ends - starts, 0)
    total = jnp.sum(counts)
    idx, valid, rid = expand_ranges(starts, counts, capacity)
    zc = z[idx]
    posc = pos[idx]
    xc = x[posc]
    yc = y[posc]
    tc = dtg[posc]
    if use_pallas:
        from ..ops.pallas_kernels import z3_mask_pallas
        mask_int = z3_mask_pallas(zc, ixy, rtlo[rid], rthi[rid])
        in_box_exact = (
            (xc[:, None] >= boxes[None, :, 0])
            & (yc[:, None] >= boxes[None, :, 1])
            & (xc[:, None] <= boxes[None, :, 2])
            & (yc[:, None] <= boxes[None, :, 3])
        ).any(axis=1)
        mask = (mask_int & in_box_exact
                & (tc >= t_lo_ms) & (tc <= t_hi_ms))
    else:
        mask = candidate_mask(zc, rtlo[rid], rthi[rid], ixy, boxes,
                              xc, yc, tc, t_lo_ms, t_hi_ms)
    return posc, valid & mask, total


@partial(jax.jit, static_argnames=("capacity", "use_pallas"))
def _query_packed(*args, capacity: int, use_pallas: bool):
    """The WHOLE scan as one dispatch returning a single packed int32
    vector ``[total_hi, total_lo, pos_0|-1, pos_1|-1, …]``.

    One program + one transfer per query: the old plan (range bounds →
    host count → scan → host mask) paid three host syncs where this
    pays one.
    ``total`` lets the host detect capacity overflow and retry bigger
    (rare; capacity is adaptive).  int32 wire: positions are int32
    throughout (build sorts an int32 iota), and the link pays ~125ms/MB
    — halving the packed bytes halves the dominant cost of a
    large-capacity query."""
    posc, mask, total = _scan_core(*args, capacity=capacity,
                                   use_pallas=use_pallas)
    return pack_wire(total, posc, mask, jnp.int32)


@partial(jax.jit, static_argnames=("capacity", "use_pallas"))
def _scan_keep_device(*args, capacity: int, use_pallas: bool):
    """Two-phase variant of :func:`_query_packed`: the packed vector
    stays ON DEVICE and only ``[total_candidates, total_hits]`` crosses
    to the host, which then dispatches :func:`_compact_hits` for a
    hits-sized transfer.  Pays one extra round trip (~100ms) to avoid
    shipping a capacity-sized buffer (~125ms/MB) — the winning trade
    once capacity is large and selectivity low."""
    posc, mask, total = _scan_core(*args, capacity=capacity,
                                   use_pallas=use_pallas)
    packed = jnp.where(mask, posc.astype(jnp.int32), jnp.int32(-1))
    totals = jnp.stack([total.astype(jnp.int64),
                        jnp.sum(mask).astype(jnp.int64)])
    return packed, totals


@partial(jax.jit, static_argnames=("k",))
def _compact_hits(packed, k: int):
    """Descending sort floats the valid (>= 0) positions to the front;
    the first ``k`` slots cover all hits (k = pow2 >= total_hits, so
    compiles bucket like the capacities do)."""
    return -jnp.sort(-packed)[:k]


#: capacity at which the two-phase (device-compact) read beats the
#: single-dispatch full-buffer transfer: an extra ~100ms round trip vs
#: ~125ms/MB of padded buffer
TWO_PHASE_MIN_CAPACITY = 1 << 19


@partial(jax.jit, static_argnames=("capacity", "pos_bits"))
def _query_many_packed(
    bins, z, pos, x, y, dtg,
    rbin, rzlo, rzhi, rtlo, rthi, rqid,
    ixy, boxes, bqid, qtlo, qthi,
    capacity: int, pos_bits: int = 40,
):
    """Batched multi-window scan: Q independent bbox+time queries in ONE
    dispatch (the reference's BatchScanner over many range sets,
    accumulated per query).  Each covering range and each box carries its
    owning query id; a candidate only matches boxes/time bounds of its own
    query.  Returns ``[total, (qid << pos_bits | pos)|-1, …]`` — one
    transfer decodes into per-query hit lists; when qid and pos together
    fit 31 bits the wire vector is int32 (halving the device→host
    transfer), else int64.  This amortizes one dispatch and host sync
    across e.g. a tube-select's per-segment windows or a kNN's
    expanding rings.
    """
    starts = searchsorted2(bins, z, rbin, rzlo, side="left")
    ends = searchsorted2(bins, z, rbin, rzhi, side="right")
    counts = jnp.maximum(ends - starts, 0)
    total = jnp.sum(counts)
    idx, valid, rid = expand_ranges(starts, counts, capacity)
    zc = z[idx]
    posc = pos[idx]
    cqid = rqid[rid]
    mask = valid & candidate_mask(
        zc, rtlo[rid], rthi[rid], ixy, boxes,
        x[posc], y[posc], dtg[posc], 0, 0,
        cqid=cqid, bqid=bqid, qtlo=qtlo, qthi=qthi)
    return pack_coded(total, cqid, posc, mask, pos_bits)




#: sentinel keys for capacity-padding slots: sort after every real key
#: and can never match a query range (real bins are small)
_SENTINEL_BIN = np.int32(np.iinfo(np.int32).max)
_SENTINEL_Z = np.int64(np.iinfo(np.int64).max)


@partial(jax.jit, static_argnames=("sfc",))
def _append_step(sfc, bins_a, z_a, pos_a, x_a, y_a, dtg_a, r,
                 xs, ys, offs, bs, ts, m_valid):
    """One static-shaped incremental append: encode the (padded) new
    batch, overwrite sentinel slots at the sorted tail with its keys,
    and re-sort the capacity-padded columns in place — all device-side,
    no host transfer.  On TPU the sort network (~230M keys/s) IS the
    cheapest merge: fine-grained gather/scatter merges run orders of
    magnitude slower than one dense sort, so the LSM "memtable merge"
    becomes "write into padding + sort".  Shapes depend only on
    (capacity, m_pad), so steady-state appends reuse one compile per
    bucket; the new feature values land at ``[r, r + m_pad)`` of the
    value columns (slots past m_valid belong to invalid rows that are
    never gathered)."""
    m_pad = xs.shape[0]
    z_b = sfc.index(xs, ys, offs)
    valid_b = jnp.arange(m_pad) < m_valid
    bs = jnp.where(valid_b, bs, _SENTINEL_BIN)
    z_b = jnp.where(valid_b, z_b, _SENTINEL_Z)
    payload = jnp.where(valid_b, r.astype(jnp.int32)
                        + jnp.arange(m_pad, dtype=jnp.int32), -1)
    # sentinels occupy the sorted tail, so the write window starts at r
    bins_w = jax.lax.dynamic_update_slice(bins_a, bs, (r,))
    z_w = jax.lax.dynamic_update_slice(z_a, z_b, (r,))
    pos_w = jax.lax.dynamic_update_slice(pos_a, payload, (r,))
    bins_m, z_m, pos_m = jax.lax.sort(
        (bins_w, z_w, pos_w), dimension=0, num_keys=2)
    x_a = jax.lax.dynamic_update_slice(x_a, xs, (r,))
    y_a = jax.lax.dynamic_update_slice(y_a, ys, (r,))
    dtg_a = jax.lax.dynamic_update_slice(dtg_a, ts, (r,))
    return bins_m, z_m, pos_m, x_a, y_a, dtg_a


@partial(jax.jit, static_argnames=("sfc",))
def _encode_sort_z3(sfc, xs, ys, os_, bs):
    """Key encode + 2-key variadic sort (bin-major), permutation as
    payload.  Module-level so repeated builds share one compile (Z3SFC is
    a frozen dataclass, hence a hashable static arg)."""
    zv = sfc.index(xs, ys, os_)
    return jax.lax.sort(
        (bs, zv, jnp.arange(zv.shape[0], dtype=jnp.int32)),
        dimension=0, num_keys=2)


#: current z3 key-layout version (v1 = legacy semi-normalized curve —
#: the reference's Z3IndexV1 era; see curve/legacy.py)
Z3_INDEX_VERSION = 2


def z3_sfc_for_version(period: TimePeriod, version: int):
    """Curve for a persisted index-layout version (the read-path
    dispatch of the reference's versioned indices,
    index/index/z3/legacy/Z3IndexV1.scala)."""
    if version >= 2:
        return z3_sfc(period)
    from ..curve.legacy import legacy_z3_sfc
    return legacy_z3_sfc(period)


class Z3PointIndex:
    """Device-resident Z3 index over point features with timestamps."""

    #: initial fixed gather capacity; grows adaptively on overflow so the
    #: common case is exactly ONE device dispatch + ONE transfer per query
    DEFAULT_CAPACITY = 1 << 15

    def __init__(self, period, bins, z, pos, x, y, dtg,
                 version: int = Z3_INDEX_VERSION):
        self.period = TimePeriod.parse(period)
        self.version = version
        self.sfc = z3_sfc_for_version(self.period, version)
        self.bins = bins
        self.z = z
        self.pos = pos
        self.x = x
        self.y = y
        self.dtg = dtg
        #: valid rows; append() capacity-pads the arrays with sentinel
        #: keys past this count
        self._n_rows = int(z.shape[0])
        self._capacity = self.DEFAULT_CAPACITY
        #: data time extent; queries clamp to it so an unbounded interval
        #: plans over the data's bins, not every bin since the epoch
        self.t_min_ms: int | None = None
        self.t_max_ms: int | None = None

    @classmethod
    def build(cls, x, y, dtg_ms, period: TimePeriod | str = TimePeriod.WEEK,
              xd=None, yd=None,
              version: int = Z3_INDEX_VERSION) -> "Z3PointIndex":
        """Encode keys (device) and sort (device lexsort, bin-major).
        ``xd``/``yd`` optionally supply already-device-resident coordinate
        arrays (shared with other indexes) to skip re-upload;
        ``version`` selects the key-layout curve (legacy for v1)."""
        period = TimePeriod.parse(period)
        sfc = z3_sfc_for_version(period, version)
        x = np.asarray(x, dtype=np.float64)
        y = np.asarray(y, dtype=np.float64)
        dtg_ms = np.asarray(dtg_ms, dtype=np.int64)
        host_bins, host_offs = to_binned_time(dtg_ms, period)
        t_min = int(dtg_ms.min()) if len(dtg_ms) else 0
        t_max = int(dtg_ms.max()) if len(dtg_ms) else 0

        xd = jnp.asarray(x) if xd is None else xd
        yd = jnp.asarray(y) if yd is None else yd
        td = jnp.asarray(dtg_ms)
        bind = jnp.asarray(host_bins.astype(np.int32))
        offd = jnp.asarray(host_offs.astype(np.float64))

        bins_s, z_s, pos = _encode_sort_z3(sfc, xd, yd, offd, bind)
        idx = cls(period, bins=bins_s, z=z_s, pos=pos, x=xd, y=yd, dtg=td,
                  version=version)
        idx.t_min_ms, idx.t_max_ms = t_min, t_max
        return idx

    def __len__(self) -> int:
        return self._n_rows

    def _grow_capacity(self, cap: int) -> None:
        """Extend the resident columns to ``cap`` slots with sentinel
        keys (sort last, match nothing) — one reallocation per
        power-of-two growth step."""
        pad = cap - int(self.z.shape[0])
        if pad <= 0:
            return
        self.bins = jnp.concatenate(
            [self.bins, jnp.full((pad,), _SENTINEL_BIN, self.bins.dtype)])
        self.z = jnp.concatenate(
            [self.z, jnp.full((pad,), _SENTINEL_Z, self.z.dtype)])
        self.pos = jnp.concatenate(
            [self.pos, jnp.full((pad,), -1, self.pos.dtype)])
        self.x = jnp.concatenate([self.x, jnp.zeros((pad,), self.x.dtype)])
        self.y = jnp.concatenate([self.y, jnp.zeros((pad,), self.y.dtype)])
        self.dtg = jnp.concatenate(
            [self.dtg, jnp.zeros((pad,), self.dtg.dtype)])

    def append(self, x, y, dtg_ms) -> "Z3PointIndex":
        """Incremental ingest: encode the NEW batch, write its keys into
        the sentinel padding, and re-sort the capacity-padded columns in
        place, entirely device-resident — the win over a rebuild is
        skipping the host→device re-upload of the whole dataset, not the
        sort (on TPU the sort network IS the cheapest merge; see
        _append_step).  Shapes bucket by (capacity, pow2(m)), so
        steady-state appends reuse one compiled program (~270ms per 100k
        rows at 16M resident).  Returns self (mutated)."""
        x = np.asarray(x, dtype=np.float64)
        m = len(x)
        if m == 0:
            return self
        y = np.asarray(y, dtype=np.float64)
        dtg_ms = np.asarray(dtg_ms, dtype=np.int64)
        m_pad = gather_capacity(m, minimum=8)
        r = self._n_rows
        if r + m_pad > int(self.z.shape[0]):
            self._grow_capacity(gather_capacity(r + m_pad))
        host_bins, host_offs = to_binned_time(dtg_ms, self.period)
        pad = m_pad - m
        self.bins, self.z, self.pos, self.x, self.y, self.dtg = _append_step(
            self.sfc, self.bins, self.z, self.pos, self.x, self.y, self.dtg,
            jnp.int32(r),
            jnp.asarray(np.pad(x, (0, pad))),
            jnp.asarray(np.pad(y, (0, pad))),
            jnp.asarray(np.pad(host_offs.astype(np.float64), (0, pad))),
            jnp.asarray(np.pad(host_bins.astype(np.int32), (0, pad))),
            jnp.asarray(np.pad(dtg_ms, (0, pad))),
            jnp.int32(m))
        self._n_rows = r + m
        t_min = int(dtg_ms.min())
        t_max = int(dtg_ms.max())
        self.t_min_ms = t_min if self.t_min_ms is None else min(self.t_min_ms, t_min)
        self.t_max_ms = t_max if self.t_max_ms is None else max(self.t_max_ms, t_max)
        return self

    def _clamp_time(self, t_lo_ms, t_hi_ms) -> tuple[int, int]:
        """Clamp to the data's time extent; ``None`` bounds are open (no
        time constraint) and resolve to the extent itself."""
        t_lo_ms = self.t_min_ms if t_lo_ms is None else int(t_lo_ms)
        t_hi_ms = self.t_max_ms if t_hi_ms is None else int(t_hi_ms)
        if self.t_min_ms is not None:
            t_lo_ms = max(t_lo_ms, self.t_min_ms)
        if self.t_max_ms is not None:
            t_hi_ms = min(t_hi_ms, self.t_max_ms)
        return t_lo_ms, t_hi_ms

    def query(self, boxes, t_lo_ms: int, t_hi_ms: int,
              max_ranges: int = DEFAULT_MAX_RANGES) -> np.ndarray:
        """Return original-order positions of features matching
        bbox(es) ∧ time interval, exactly (oracle-equal hit sets)."""
        t_lo_ms, t_hi_ms = self._clamp_time(t_lo_ms, t_hi_ms)
        plan = plan_z3_query(boxes, t_lo_ms, t_hi_ms, self.period, max_ranges,
                             sfc=self.sfc)
        if plan.num_ranges == 0 or len(self) == 0:
            return np.empty(0, dtype=np.int64)
        # bucket the plan shapes so differently-shaped queries share
        # compiles (one compile per power-of-two range/box count)
        r = pad_ranges({"rbin": plan.rbin, "rzlo": plan.rzlo,
                        "rzhi": plan.rzhi, "rtlo": plan.rtlo,
                        "rthi": plan.rthi}, pad_pow2(plan.num_ranges))
        ixy, bxs = pad_boxes(plan.ixy, plan.boxes,
                             pad_pow2(len(plan.boxes), minimum=1))
        args = (
            self.bins, self.z, self.pos, self.x, self.y, self.dtg,
            jnp.asarray(r["rbin"]), jnp.asarray(r["rzlo"]),
            jnp.asarray(r["rzhi"]),
            jnp.asarray(r["rtlo"]), jnp.asarray(r["rthi"]),
            jnp.asarray(ixy), jnp.asarray(bxs),
            plan.t_lo_ms, plan.t_hi_ms,
        )
        def dispatch(capacity):
            from ..ops.pallas_kernels import GATES
            with device_span("query.scan.device", stage="packed",
                             capacity=capacity):
                # BOTH branches materialize inside the span (z2.py)
                return GATES["z3_scan"].run(
                    lambda: np.asarray(_query_packed(
                        *args, capacity=capacity, use_pallas=True)),
                    lambda: np.asarray(_query_packed(
                        *args, capacity=capacity, use_pallas=False)),
                    enabled=_use_pallas_scan())

        if self._capacity >= TWO_PHASE_MIN_CAPACITY:
            return self._query_two_phase(args)
        hits, self._capacity = run_packed_query(dispatch, self._capacity)
        return hits

    def _query_two_phase(self, args) -> np.ndarray:
        """Large-capacity scan: keep the packed vector on device, read
        the tiny totals, then transfer a device-compacted hits-sized
        slice (see _scan_keep_device).  When the hits nearly fill the
        capacity the compact dispatch buys nothing, so the packed buffer
        is read directly (same bytes as the single-phase path; only the
        totals round trip was extra)."""
        capacity = self._capacity
        while True:
            with device_span("query.scan.device", stage="two_phase",
                             capacity=capacity):
                packed, totals = _scan_keep_device(
                    *args, capacity=capacity, use_pallas=False)
                total, nhits = (int(v) for v in np.asarray(totals))
                if total > capacity:
                    capacity = gather_capacity(total)
                    continue
                # decay toward the observed candidate volume so one huge
                # query doesn't tax every later small one (re-growth
                # costs a single cheap retry dispatch)
                self._capacity = max(self.DEFAULT_CAPACITY,
                                     gather_capacity(total))
                k = gather_capacity(max(nhits, 1), minimum=8)
                if k >= capacity:  # dense result: compact can't shrink
                    out = np.asarray(packed)
                else:
                    out = np.asarray(_compact_hits(packed, k=k))
            return np.sort(out[out >= 0]).astype(np.int64)

    def query_many(self, windows,
                   max_ranges: int = DEFAULT_MAX_RANGES) -> list[np.ndarray]:
        """Batched queries: ``windows`` is a list of
        ``(boxes, t_lo_ms, t_hi_ms)``; returns one sorted position array
        per window — all windows scanned in ONE device dispatch (see
        _query_many_packed)."""
        n_q = len(windows)
        if n_q == 0 or len(self) == 0:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        rbin, rzlo, rzhi, rtlo, rthi, rqid = [], [], [], [], [], []
        ixy, boxes, bqid = [], [], []
        qtlo = np.empty(n_q, dtype=np.int64)
        qthi = np.empty(n_q, dtype=np.int64)
        from ..resilience import check_cancel
        for q, (bxs, lo, hi) in enumerate(windows):
            # deadline yield point between range decompositions (ISSUE
            # 16): a partial break leaves the remaining windows with no
            # ranges — they simply return empty hit lists
            if check_cancel("query.decompose"):
                break
            lo, hi = self._clamp_time(lo, hi)
            # the scan-ranges target applies PER window, as in the
            # reference (each window is an independent scan): finer
            # covering ranges cost a bigger searchsorted batch (cheap)
            # but shrink the candidate gather + transfer (the dominant
            # cost)
            plan = plan_z3_query(bxs, lo, hi, self.period, max_ranges,
                                 sfc=self.sfc)
            qtlo[q] = plan.t_lo_ms
            qthi[q] = plan.t_hi_ms
            if plan.num_ranges == 0:
                continue
            rbin.append(plan.rbin)
            rzlo.append(plan.rzlo)
            rzhi.append(plan.rzhi)
            rtlo.append(plan.rtlo)
            rthi.append(plan.rthi)
            rqid.append(np.full(plan.num_ranges, q, dtype=np.int32))
            ixy.append(plan.ixy)
            boxes.append(plan.boxes)
            bqid.append(np.full(len(plan.boxes), q, dtype=np.int32))
        if not rbin:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        ra = {"rbin": np.concatenate(rbin), "rzlo": np.concatenate(rzlo),
              "rzhi": np.concatenate(rzhi), "rtlo": np.concatenate(rtlo),
              "rthi": np.concatenate(rthi), "rqid": np.concatenate(rqid)}
        ra = pad_ranges(ra, pad_pow2(len(ra["rbin"])))
        ixy_c, boxes_c, bqid_c = pad_boxes(
            np.concatenate(ixy), np.concatenate(boxes),
            pad_pow2(sum(len(b) for b in boxes), minimum=1),
            np.concatenate(bqid))
        args = (
            self.bins, self.z, self.pos, self.x, self.y, self.dtg,
            jnp.asarray(ra["rbin"]), jnp.asarray(ra["rzlo"]),
            jnp.asarray(ra["rzhi"]), jnp.asarray(ra["rtlo"]),
            jnp.asarray(ra["rthi"]), jnp.asarray(ra["rqid"]),
            jnp.asarray(ixy_c), jnp.asarray(boxes_c), jnp.asarray(bqid_c),
            jnp.asarray(qtlo), jnp.asarray(qthi),
        )

        pos_bits = coded_pos_bits(len(self), n_q)

        def dispatch(capacity):
            with device_span("query.scan.device", stage="packed_many",
                             capacity=capacity):
                return np.asarray(_query_many_packed(
                    *args, capacity=capacity, pos_bits=pos_bits))

        coded, self._capacity = run_packed_query(dispatch, self._capacity)
        qids = coded >> pos_bits
        positions = coded & ((np.int64(1) << pos_bits) - 1)
        out = []
        for q in range(n_q):
            hits = positions[qids == q]
            # a feature can land in several of a query's covering ranges
            out.append(np.unique(hits))
        return out
