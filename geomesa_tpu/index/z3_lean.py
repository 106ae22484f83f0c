"""LeanZ3Index: tiered generational Z3 index for HBM-bounded scale
(the 500M–1B single-chip path, and the scale profile of the store).

The full-fat :class:`geomesa_tpu.index.z3.Z3PointIndex` keeps x/y/dtg
resident next to its keys (40 B/point) so the exact re-check fuses into
the scan — the right trade below ~150M points/chip.  Past that, HBM is
the wall: a v5e chip has 15.75 GiB usable, and the append sort's HLO
temps cost ~1× the column bytes on top of the (donated) resident set
(measured on chip; the int64 z splits into 2×u32 lanes plus payload
copies).

This index is the reference's own storage split re-expressed for TPU:
the searchable keys — ``(bins int32, z int64, pos int32)`` = 16 B/point,
the role of the tablet server's key space — live in sorted GENERATIONS
of bounded capacity (LSM-flavored: appends fill the current generation
and roll to a new one when full, so the append sort's working set is
one generation), while the payload columns stay in host RAM (the
"value" fetch; clients re-check exactly,
AccumuloIndexAdapter.scala:181-195).

**Tiers.**  Each generation has a residency tier, demoted oldest-first
as the store outgrows ``hbm_budget_bytes`` (round-4 VERDICT #2/#7):

* ``full`` — keys AND an (x, y, t) payload copy on device (40 B/pt):
  the exact bbox+time mask runs fused on device per generation and only
  survivors cross the wire — no host gather at all (the full-fat scan's
  exactness at generational scale).
* ``keys`` — keys only on device (16 B/pt): device seeks + candidate
  gather; the exact mask runs vectorized on the host payload.
* ``host`` — the sorted key run spilled to host RAM (0 B HBM): numpy
  segmented searchsorted seeks.  This is how 1B points fit one chip —
  1B × 16 B = 16 GB exceeds HBM, so cold runs live beside the payload
  in host RAM while hot runs keep device seeks.

Queries batch ALL windows of a call into one totals probe and one scan
per populated tier.  The probe skips every device generation whose time
span misses all the windows, and the scan every generation the probe
found empty.  Fewer than ``_GEN_BUCKET`` generations left dispatch one
each, unpadded; a bucket or more dispatch together, padded to the
bucket with the shared full-size sentinel generation
(``_make_sentinel_cols``), so those programs compile once per bucket.

**LSM lifecycle.**  Without maintenance, a streamed 1B build
accumulates ~60 generations and every query/density call fans out over
all of them.  Two mechanisms bound that growth:

* **Compaction** — :meth:`LeanZ3Index.compact`, a budgeted/resumable
  size-tiered K-way merge (device ``lax.sort`` for keys-tier runs,
  numpy lexsort for spilled host runs) that folds ≥ F same-tier
  same-size-class sealed runs into one, driving the run count to
  O(log N).  The reference delegates this to its key-value backend's
  periodic compaction; the lean store must run its own.
* **Sealed-generation density partials** — once a generation is sealed
  (demoted off the live slot), its contribution to a given density
  (boxes, window, env, grid) spec is immutable; the per-generation
  grids cache (LRU over specs) and warm repeat calls re-scan only the
  live generation and full-tier generations (whose value-exact edge
  masks the cache must not coarsen).

Reference mapping: Z3IndexKeySpace.scala:60 (key layout),
IndexAdapter.scala:95-106 (writers), AccumuloQueryPlan.scala:87-157
(scan plans over sorted runs), BASELINE.json GDELT-1B north star.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.binnedtime import TimePeriod, to_binned_time
from ..index.z3 import Z3_INDEX_VERSION, plan_z3_query, z3_sfc_for_version
from ..metrics import (
    LEAN_COMPACTION_MERGES, LEAN_COMPACTION_ROWS,
    LEAN_DENSITY_CACHE_HITS, LEAN_DENSITY_CACHE_MISSES,
    LEAN_PROBE_GENERATIONS, LEAN_PROBE_SEEKS, LEAN_SCAN_HITS,
    LEAN_SKETCH_CACHE_HITS, LEAN_SKETCH_CACHE_MISSES,
    PYRAMID_BUILDS, PYRAMID_BUILD_MS, PYRAMID_SERVE_HITS,
    RESILIENCE_DEGRADED, RESILIENCE_RETRIES,
    WRITE_SEALS, WRITE_SPILLS, registry as _metrics,
)
from ..obs import device_span, obs_count, scan_work, span as obs_span
from ..obs.heat import (
    heat_enabled, merge_index_generations, record_index_scan,
)
from ..ops.search import (
    coded_pos_bits, expand_ranges, gather_capacity, pad_boxes, pad_pow2,
    pad_ranges, scan_read_bytes, searchsorted2, wire_dtype,
)

__all__ = ["LeanZ3Index", "HostStack", "merge_host_runs"]

_SENTINEL_BIN = np.int32(np.iinfo(np.int32).max)
_SENTINEL_Z = np.int64(np.iinfo(np.int64).max)

#: per-slot byte widths, derived ONCE from the column dtypes (bins
#: int32 + z int64 + pos int32 — positions are generation-local int32
#: here, unlike the sharded index's int64 gids — and the full tier
#: adds x/y f64 + t int64).  Every budget computation uses these, so a
#: dtype change cannot silently skew the HBM accounting.
KEYS_BYTES = 4 + 8 + 4
PAYLOAD_BYTES = 8 + 8 + 8
FULL_BYTES = KEYS_BYTES + PAYLOAD_BYTES
#: what one binary-search probe reads (bins + z) and what a scan
#: gathers per candidate before the payload (pos): the scan's
#: ``lean.scan.bytes`` lower bound (ops/search.scan_read_bytes)
_SEEK_KEY_BYTES = 4 + 8
_POS_BYTES = 4


def _append_keys_body(sfc, bins, z, pos, r, base, xs, ys, offs, bs, m):
    """Shared append body (traced inline by both jitted wrappers so the
    two tiers cannot diverge): encode a slice's keys into the sentinel
    padding at sorted offset ``r`` and re-sort.  ``base`` is the
    generation's first global row id; positions are global."""
    z_new = sfc.index(xs, ys, offs)
    valid = jnp.arange(xs.shape[0]) < m
    b_new = jnp.where(valid, bs, _SENTINEL_BIN)
    z_new = jnp.where(valid, z_new, _SENTINEL_Z)
    p_new = jnp.where(valid, base + r
                      + jnp.arange(xs.shape[0], dtype=jnp.int32),
                      jnp.int32(-1))
    bins = jax.lax.dynamic_update_slice(bins, b_new, (r,))
    z = jax.lax.dynamic_update_slice(z, z_new, (r,))
    pos = jax.lax.dynamic_update_slice(pos, p_new, (r,))
    return jax.lax.sort((bins, z, pos), dimension=0, num_keys=2)


@partial(jax.jit, static_argnames=("sfc",), donate_argnums=(1, 2, 3))
def _lean_append(sfc, bins, z, pos, r, base, xs, ys, offs, bs, m):
    """``keys``-tier append (donated: outputs alias the resident
    columns, so peak = resident + sort temps, not 2× resident)."""
    return _append_keys_body(sfc, bins, z, pos, r, base, xs, ys, offs,
                             bs, m)


@partial(jax.jit, static_argnames=("sfc",),
         donate_argnums=(1, 2, 3, 4, 5, 6))
def _lean_append_full(sfc, bins, z, pos, xp, yp, tp, r, base,
                      xs, ys, offs, bs, ts, m):
    """The ``full``-tier append: keys via the shared body plus the
    (x, y, t) payload columns updated at ``[r, r+m_pad)`` in APPEND
    order (like the full-fat index, payload is gathered by position —
    ``pos - base`` — not sorted; _append_step, index/z3.py)."""
    bins, z, pos = _append_keys_body(sfc, bins, z, pos, r, base,
                                     xs, ys, offs, bs, m)
    xp = jax.lax.dynamic_update_slice(xp, xs, (r,))
    yp = jax.lax.dynamic_update_slice(yp, ys, (r,))
    tp = jax.lax.dynamic_update_slice(tp, ts, (r,))
    return bins, z, pos, xp, yp, tp


@jax.jit
def _lean_count_multi(rb, rlo, rhi, *cols):
    """Totals probe over EVERY device generation in ONE dispatch: a
    30-run store otherwise pays 30 dispatches and host syncs per probe
    for microseconds of seek work each."""
    outs = []
    for g in range(len(cols) // 2):
        b, z = cols[2 * g], cols[2 * g + 1]
        starts = searchsorted2(b, z, rb, rlo, side="left")
        ends = searchsorted2(b, z, rb, rhi, side="right")
        outs.append(jnp.sum(jnp.maximum(ends - starts, 0)))
    return jnp.stack(outs)


@partial(jax.jit, static_argnames=("capacity", "pos_bits"))
def _lean_scan_coded(rb, rlo, rhi, rqid, *cols,
                     capacity: int, pos_bits: int):
    """CANDIDATE gather over ``keys``-tier generations in ONE dispatch:
    per generation, seek + expand + gather global positions, coded as
    ``qid << pos_bits | pos`` (the multi-window wire layout of
    ops/search.pack_coded).  Returns (G, capacity); the exact bbox/time
    mask runs on the host payload."""
    dt = wire_dtype(pos_bits)
    outs = []
    for g in range(len(cols) // 3):
        b, z, pos = cols[3 * g], cols[3 * g + 1], cols[3 * g + 2]
        starts = searchsorted2(b, z, rb, rlo, side="left")
        ends = searchsorted2(b, z, rb, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        idx, valid, rid = expand_ranges(starts, counts, capacity)
        coded = ((rqid[rid].astype(dt) << dt(pos_bits))
                 | pos[idx].astype(dt))
        outs.append(jnp.where(valid, coded, dt(-1)))
    return jnp.stack(outs)


@partial(jax.jit, static_argnames=("capacity", "pos_bits"))
def _lean_scan_exact_keep(rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi,
                          *cols, capacity: int, pos_bits: int):
    """Two-phase sibling of :func:`_lean_scan_exact_coded`: the coded
    buffer STAYS ON DEVICE and only the hit count crosses; the host
    then dispatches :func:`_compact_coded` for a survivors-sized
    transfer.  The winning trade for candidate-heavy queries — the
    device already knows the exact survivors (full tier), so shipping
    a capacity-sized buffer at ~125ms/MB to keep 0.1%% of it is pure
    waste (the full-fat index's _scan_keep_device trade, index/z3.py)."""
    packed = _lean_scan_exact_coded(
        rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi, *cols,
        capacity=capacity, pos_bits=pos_bits)
    return packed, jnp.sum(packed >= 0)


@partial(jax.jit, static_argnames=("out_cap",))
def _lean_merge_keys(*cols, out_cap: int):
    """COMPACTION merge: fold K sorted ``keys``-tier runs into ONE
    sorted run in a single dispatch.  ``lax.sort`` over the
    concatenated columns is the same radix kernel appends use; every
    sentinel slot floats past the valid rows, so the leading
    ``out_cap`` (= total valid rows) slots ARE the merged run — the
    merged generation carries ZERO sentinel padding and releases every
    slack slot the K source runs held (the memory.py-budget visible
    effect of a merge)."""
    k = len(cols) // 3
    bins = jnp.concatenate([cols[3 * i] for i in range(k)])
    z = jnp.concatenate([cols[3 * i + 1] for i in range(k)])
    pos = jnp.concatenate([cols[3 * i + 2] for i in range(k)])
    bins, z, pos = jax.lax.sort((bins, z, pos), dimension=0, num_keys=2)
    return bins[:out_cap], z[:out_cap], pos[:out_cap]


def merge_host_runs(runs: list["HostRun"]) -> "HostRun":
    """COMPACTION merge for spilled runs: K sorted host runs fold into
    one sorted :class:`HostRun` via a composite (bin, z) lexsort —
    numpy's near-sorted merge path; the per-run bins columns are
    reconstructed from the segment tables (stacked runs hand their
    ``bins`` ownership to the :class:`HostStack`)."""
    bins = np.concatenate([
        np.repeat(r._bin_vals, np.diff(r._bin_starts)) for r in runs])
    z = np.concatenate([np.asarray(r.z) for r in runs])
    pos = np.concatenate([np.asarray(r.pos) for r in runs])
    order = np.lexsort((z, bins))
    return HostRun(np.ascontiguousarray(bins[order]),
                   np.ascontiguousarray(z[order]),
                   np.ascontiguousarray(pos[order]))


@partial(jax.jit, static_argnames=("k",))
def _compact_coded(packed, k: int):
    """Descending sort floats the valid (>= 0) coded hits to the front;
    the first ``k`` slots cover all survivors (k = pow2 >= hits)."""
    return -jnp.sort(-packed.ravel())[:k]


@jax.jit
def _lean_gather_payload(idx, xp, yp, tp):
    """Result-materialization column gather (ISSUE 14): ONE batched
    take of a full-tier generation's (x, y, t) payload for a chunk of
    hit offsets.  ``idx`` is padded to a gather_capacity bucket so warm
    repeats of the same result shape reuse the compiled program."""
    return xp[idx], yp[idx], tp[idx]


#: combined (G_pad × capacity) slot count at which the exact tier's
#: two-phase read (device compaction + survivors-sized transfer) beats
#: shipping the full coded buffer: an extra ~100ms round trip vs
#: ~125ms/MB of padded int buffer
_TWO_PHASE_MIN_SLOTS = 1 << 18


def _bins_spanned(t_lo_ms: int, t_hi_ms: int, period) -> int:
    """Time bins a clamped interval covers (per-window range budgets
    scale by it: a tiny box over 27 open-bounds bins would otherwise
    get 2000/27 ranges per bin — overcovering hundreds of thousands of
    candidates for a handful of hits)."""
    b_lo, _ = to_binned_time(np.int64(max(0, t_lo_ms)), period)
    b_hi, _ = to_binned_time(np.int64(max(0, t_hi_ms)), period)
    return max(1, int(b_hi) - int(b_lo) + 1)


#: hard per-window range cap after per-bin scaling (device seeks are
#: cheap — a 32k-range searchsorted batch is microseconds — but plan
#: assembly and upload are host work)
_MAX_RANGES_PER_WINDOW = 1 << 14


@partial(jax.jit, static_argnames=("capacity", "pos_bits"))
def _lean_scan_exact_coded(rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi,
                           *cols, capacity: int, pos_bits: int):
    """EXACT scan over ``full``-tier generations in ONE dispatch: seek +
    gather + the fused f64 bbox+time mask over the generation's DEVICE
    payload (round-4 VERDICT #7 — no host gather at all).  A candidate
    only matches boxes/time bounds of its own window (cqid/bqid, the
    _query_many_packed discipline).  Returns (G, capacity) coded hits;
    every non-negative slot is a TRUE hit."""
    dt = wire_dtype(pos_bits)
    outs = []
    for g in range(len(cols) // 7):
        b, z, pos, xp, yp, tp, base = cols[7 * g: 7 * g + 7]
        starts = searchsorted2(b, z, rb, rlo, side="left")
        ends = searchsorted2(b, z, rb, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        idx, valid, rid = expand_ranges(starts, counts, capacity)
        posc = pos[idx]
        local = jnp.maximum(posc - base, 0)
        xc = xp[local]
        yc = yp[local]
        tc = tp[local]
        cqid = rqid[rid]
        same_q = cqid[:, None] == bqid[None, :]
        in_box = (
            (xc[:, None] >= boxes[None, :, 0])
            & (yc[:, None] >= boxes[None, :, 1])
            & (xc[:, None] <= boxes[None, :, 2])
            & (yc[:, None] <= boxes[None, :, 3])
            & same_q
        ).any(axis=1)
        ok = (valid & in_box
              & (tc >= qtlo[cqid]) & (tc <= qthi[cqid]))
        coded = (cqid.astype(dt) << dt(pos_bits)) | posc.astype(dt)
        outs.append(jnp.where(ok, coded, dt(-1)))
    return jnp.stack(outs)


def _grid_accum(xc, yc, ok, env, width: int, height: int, grid):
    """Count masked points into a flat (height*width) float64 grid via
    sort + boundary differences (the ops/density.density_grid_sorted
    shape): integer counts from searchsorted bounds are EXACT at any
    magnitude (no f32 saturation at 2^24 — review r5) and the native
    int32 sort beats TPU's emulated-f64 scatter-add by ~20x at scale
    (11.8s → sub-second per 40M, measured on chip).  Masked rows sort
    to a sentinel cell past the grid."""
    fx = (xc - env[0]) / jnp.maximum(env[2] - env[0], 1e-12) * width
    fy = (yc - env[1]) / jnp.maximum(env[3] - env[1], 1e-12) * height
    gx = jnp.clip(fx.astype(jnp.int32), 0, width - 1)
    gy = jnp.clip(fy.astype(jnp.int32), 0, height - 1)
    flat = jnp.where(ok, gy * width + gx, jnp.int32(width * height))
    flat_s = jnp.sort(flat)
    bounds = jnp.searchsorted(
        flat_s, jnp.arange(width * height + 1, dtype=jnp.int32),
        side="left")
    return grid + (bounds[1:] - bounds[:-1]).astype(jnp.float64)


@partial(jax.jit, static_argnames=("sfc", "capacity", "width", "height"))
def _lean_density_full(sfc, rb, rlo, rhi, boxes, qtlo, qthi, env, *cols,
                       capacity: int, width: int, height: int):
    """DensityScan over ``full``-tier generations in ONE dispatch: seek
    + gather + the fused EXACT payload mask + grid scatter-add — only
    the (height, width) grid crosses the wire, never a candidate
    (round-4 VERDICT #2; DensityScan.scala:31-59 runs next to the data
    the same way).  The MASK runs on raw f64 payload (value-exact);
    grid BINNING goes through the z-cell midpoint (normalize →
    denormalize) so cell assignment is integer-deterministic across
    platforms — raw-f64 binning flipped boundary points by one grid
    cell between TPU and host f64 rounding (measured on chip)."""
    grid = jnp.zeros((height * width,), jnp.float64)
    for g in range(len(cols) // 7):
        b, z, pos, xp, yp, tp, base = cols[7 * g: 7 * g + 7]
        starts = searchsorted2(b, z, rb, rlo, side="left")
        ends = searchsorted2(b, z, rb, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        idx, valid, _rid = expand_ranges(starts, counts, capacity)
        local = jnp.maximum(pos[idx] - base, 0)
        xc, yc, tc = xp[local], yp[local], tp[local]
        in_box = (
            (xc[:, None] >= boxes[None, :, 0])
            & (yc[:, None] >= boxes[None, :, 1])
            & (xc[:, None] <= boxes[None, :, 2])
            & (yc[:, None] <= boxes[None, :, 3])
        ).any(axis=1)
        ok = valid & in_box & (tc >= qtlo) & (tc <= qthi)
        xd = sfc.lon.denormalize(sfc.lon.normalize(xc, xp=jnp), xp=jnp)
        yd = sfc.lat.denormalize(sfc.lat.normalize(yc, xp=jnp), xp=jnp)
        grid = _grid_accum(xd, yd, ok, env, width, height, grid)
    return grid.reshape((height, width))


@partial(jax.jit, static_argnames=("sfc", "capacity", "width", "height"))
def _lean_density_keys(sfc, rb, rlo, rhi, ixy, tb, env, *cols,
                       capacity: int, width: int, height: int):
    """DensityScan over ``keys``-tier generations: the z KEY decodes to
    CELL coordinates on device (21 bits/dim ≈ 1.7e-4°, orders finer
    than any density cell), so the grid accumulates with NO payload and
    NO host transfer.  Masks compare at CELL granularity in normalized
    space — ``ixy`` holds per-box normalized (ix0, iy0, ix1, iy1) and
    ``tb`` = (bin_lo, cell_lo, bin_hi, cell_hi) — which is EXACT for
    whole-extent scans and cell-inclusive (≤ one 1.7e-4° z cell of
    over-coverage at edges) otherwise; the cell CENTER lands each hit
    in its true grid cell whenever grid cells are coarser than z cells
    (every realistic density grid).

    Returns STACKED per-generation grids ``(G, height, width)`` — one
    dispatch either way, but per-generation partials let the caller
    CACHE each sealed generation's immutable contribution (the
    aggregate cache; the grids sum on the host)."""
    from ..curve.zorder import deinterleave3
    grids = []
    for g in range(len(cols) // 2):
        grid = jnp.zeros((height * width,), jnp.float64)
        b, z = cols[2 * g], cols[2 * g + 1]
        starts = searchsorted2(b, z, rb, rlo, side="left")
        ends = searchsorted2(b, z, rb, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        idx, valid, _rid = expand_ranges(starts, counts, capacity)
        zc = z[idx]
        bc = b[idx].astype(jnp.int64)
        ix, iy, it = deinterleave3(zc.astype(jnp.uint64))
        ix = ix.astype(jnp.int32)
        iy = iy.astype(jnp.int32)
        it = it.astype(jnp.int32)
        in_box = (
            (ix[:, None] >= ixy[None, :, 0])
            & (iy[:, None] >= ixy[None, :, 1])
            & (ix[:, None] <= ixy[None, :, 2])
            & (iy[:, None] <= ixy[None, :, 3])
        ).any(axis=1)
        after = (bc > tb[0]) | ((bc == tb[0]) & (it >= tb[1]))
        before = (bc < tb[2]) | ((bc == tb[2]) & (it <= tb[3]))
        ok = valid & in_box & after & before
        xd = sfc.lon.denormalize(ix, xp=jnp)
        yd = sfc.lat.denormalize(iy, xp=jnp)
        grid = _grid_accum(xd, yd, ok, env, width, height, grid)
        grids.append(grid.reshape((height, width)))
    return jnp.stack(grids)


@partial(jax.jit, static_argnames=("sfc", "width", "height", "world"))
def _lean_density_sweep(sfc, env, *zs, width: int, height: int,
                        world: bool):
    """WHOLE-EXTENT DensityScan: no seek, no expand — every slot of
    every generation decodes its grid cell straight from the z key and
    counts via sort + boundary differences.  With a world envelope AND
    power-of-two grid dims the binning is pure integer arithmetic
    ((cell * width) >> precision — exactly the midpoint binning when
    width divides 2^precision, which pow2 widths ≤ 2^20 do); any other
    envelope/width takes the f64 midpoint path so the fast and slow
    scan paths always bin identically (review r5).  Sentinel slots
    sort past the grid.  Returns STACKED per-generation grids
    ``(G, height, width)`` so sealed generations' partials can cache
    (see _lean_density_keys)."""
    from ..curve.zorder import deinterleave3
    grids = []
    p = sfc.lon.precision
    for z in zs:
        grid = jnp.zeros((height * width,), jnp.float64)
        ok = z != _SENTINEL_Z
        ix, iy, _it = deinterleave3(z.astype(jnp.uint64))
        if world:
            gx = ((ix.astype(jnp.int64) * width) >> p).astype(jnp.int32)
            gy = ((iy.astype(jnp.int64) * height) >> p).astype(jnp.int32)
        else:
            xd = sfc.lon.denormalize(ix.astype(jnp.int32), xp=jnp)
            yd = sfc.lat.denormalize(iy.astype(jnp.int32), xp=jnp)
            fx = ((xd - env[0]) / jnp.maximum(env[2] - env[0], 1e-12)
                  * width)
            fy = ((yd - env[1]) / jnp.maximum(env[3] - env[1], 1e-12)
                  * height)
            gx = jnp.clip(fx.astype(jnp.int32), 0, width - 1)
            gy = jnp.clip(fy.astype(jnp.int32), 0, height - 1)
        flat = jnp.where(ok, gy * width + gx,
                         jnp.int32(width * height))
        flat_s = jnp.sort(flat)
        bounds = jnp.searchsorted(
            flat_s, jnp.arange(width * height + 1, dtype=jnp.int32),
            side="left")
        grid = grid + (bounds[1:] - bounds[:-1]).astype(jnp.float64)
        grids.append(grid.reshape((height, width)))
    return jnp.stack(grids)


@partial(jax.jit, static_argnames=("bits", "nb"))
def _z3_cells_multi(b0, *cols, bits: int, nb: int):
    """Z3Histogram push-down fold over device generations in ONE
    dispatch (ISSUE 3): every slot's coarse cell is the TOP BITS of its
    z key (``z >> (63 - bits)`` — exactly Z3HistogramStat's cell
    function), so the per-generation (time-bin × cell) count tables
    accumulate with no payload and no candidate; only the tiny stacked
    tables cross the wire.  ``nb`` is the time-bin span ``[b0, b0+nb)``
    of the data extent; sentinel slots (and any out-of-span bin) fold
    into a discarded overflow slot."""
    size = nb << bits
    outs = []
    for g in range(len(cols) // 2):
        b, z = cols[2 * g], cols[2 * g + 1]
        mask = z != _SENTINEL_Z
        cell = z >> jnp.int64(63 - bits)
        flat = (b.astype(jnp.int64) - b0) * jnp.int64(1 << bits) + cell
        ok = mask & (flat >= 0) & (flat < size)
        flat = jnp.where(ok, flat, size).astype(jnp.int32)
        outs.append(jnp.zeros((size + 1,), jnp.int64)
                    .at[flat].add(1)[:size])
    return jnp.stack(outs)


_WORLD_ENV = (-180.0, -90.0, 180.0, 90.0)


#: generation-count compile bucket for the multi-generation programs
_GEN_BUCKET = 4

#: window compile bucket of the exact scans: their per-window arrays
#: (boxes with owning qids, time bounds) pad to a power of two of at
#: least this many never-matching entries, so a batch of one to eight
#: windows runs the programs every other such batch already loaded.
#: The padding costs a few masked compares per candidate.
_WINDOW_BUCKET = 8

def _make_sentinel_cols(tier: str, slots: int):
    """Empty generation columns for bucket padding: FULL-SIZE (same
    slot count as the real generations, all-sentinel keys), so every
    padded program has the uniform shape ``(slots,) × G_pad`` and
    compiles once per BUCKET, not once per real generation count — at
    60 sorted runs the difference is minutes of compile per
    checkpoint.  All-sentinel keys match zero seeks, but a padded
    generation is not free: the programs still binary-search its full
    slot count for every range, and a row scan still expands, gathers
    and masks its whole capacity.  So while the density scans pad to
    ``_GEN_BUCKET``, the totals probe and the row scan
    (``LeanZ3Index._probe_totals``, ``_scan_tier``) dispatch each of
    fewer generations than a bucket alone and pad nothing.  One shared
    buffer per index is passed for every padded slot (cached
    per-INSTANCE so its device arrays die with the index and eviction
    cannot steal another live index's padding)."""
    bins = jnp.full((slots,), _SENTINEL_BIN, jnp.int32)
    z = jnp.full((slots,), _SENTINEL_Z, jnp.int64)
    pos = jnp.full((slots,), -1, jnp.int32)
    if tier == "full":
        zero = jnp.zeros((slots,), jnp.float64)
        t0 = jnp.zeros((slots,), jnp.int64)
        return (bins, z, pos, zero, zero, t0, jnp.int32(0))
    return (bins, z, pos)


class HostRun:
    """One sorted key run spilled to host RAM (the ``host`` residency
    tier, single-chip AND per-shard on the mesh): numpy segmented
    searchsorted seeks — per distinct query bin, two vectorized
    z-searchsorted calls within the bin's segment (bins are few: the
    time-period bins of the data extent)."""

    __slots__ = ("bins", "z", "pos", "_bin_vals", "_bin_starts")

    def __init__(self, bins: np.ndarray, z: np.ndarray, pos: np.ndarray):
        self.bins, self.z, self.pos = bins, z, pos
        self._bin_vals, starts = np.unique(bins, return_index=True)
        self._bin_starts = np.append(starts, len(bins))

    def __len__(self) -> int:
        return len(self.z)

    def seek(self, rb, rlo, rhi):
        """Per-range [start, end) offsets into the run."""
        starts = np.zeros(len(rb), np.int64)
        ends = np.zeros(len(rb), np.int64)
        if len(self.z) == 0:
            return starts, ends
        for b in np.unique(rb):
            bi = np.searchsorted(self._bin_vals, b)
            if bi >= len(self._bin_vals) or self._bin_vals[bi] != b:
                continue
            s0, s1 = self._bin_starts[bi], self._bin_starts[bi + 1]
            seg = self.z[s0:s1]
            sel = rb == b
            starts[sel] = s0 + np.searchsorted(seg, rlo[sel], side="left")
            ends[sel] = s0 + np.searchsorted(seg, rhi[sel], side="right")
        return starts, ends

    def _expand(self, rb, rlo, rhi):
        """(flat z indices, owning range) for a range batch over THIS
        run — the single-run twin of :meth:`HostStack._expand`."""
        starts, ends = self.seek(rb, rlo, rhi)
        counts = np.maximum(ends - starts, 0)
        cum = np.cumsum(counts)
        total = int(cum[-1]) if len(cum) else 0
        if total == 0:
            return None, None
        j = np.arange(total)
        rid = np.searchsorted(cum, j, side="right")
        prev = np.where(rid > 0, cum[rid - 1], 0)
        return starts[rid] + (j - prev), rid

    def candidates(self, rb, rlo, rhi, rqid, pos_bits: int) -> np.ndarray:
        """Coded candidate positions ``qid << pos_bits | pos`` for a
        padded range batch (the numpy twin of the device expand)."""
        idx, rid = self._expand(rb, rlo, rhi)
        if idx is None:
            return np.empty(0, np.int64)
        return ((rqid[rid].astype(np.int64) << pos_bits)
                | self.pos[idx].astype(np.int64))

    def cell_counts(self, b0: int, nb: int, bits: int) -> np.ndarray:
        """Z3Histogram partial over THIS spilled run: flat
        ``(bin - b0) << bits | cell`` counts — the numpy twin of one
        generation's slice of :func:`_z3_cells_multi` (bins rebuild
        from the segment table; the stack owns the columns)."""
        bins = np.repeat(self._bin_vals,
                         np.diff(self._bin_starts)).astype(np.int64)
        cell = np.asarray(self.z).astype(np.int64) >> (63 - bits)
        size = nb << bits
        flat = (bins - b0) * (1 << bits) + cell
        ok = (flat >= 0) & (flat < size)
        return np.bincount(flat[ok], minlength=size)[:size] \
            .astype(np.int64)

    def sweep_partial(self, sfc, env, width: int, height: int,
                      world: bool) -> np.ndarray:
        """Whole-extent grid partial over THIS run (no seeks — every
        row decodes its cell from the z key; the numpy twin of one
        generation's slice of ``_lean_density_sweep``)."""
        from ..curve.zorder import deinterleave3
        ix, iy, _ = deinterleave3(np.asarray(self.z).astype(np.uint64),
                                  xp=np)
        p = sfc.lon.precision
        if world:
            gx = (ix.astype(np.int64) * width) >> p
            gy = (iy.astype(np.int64) * height) >> p
        else:
            xd = sfc.lon.denormalize(ix.astype(np.int64), xp=np)
            yd = sfc.lat.denormalize(iy.astype(np.int64), xp=np)
            gx = np.clip(((xd - env[0])
                          / max(env[2] - env[0], 1e-12)
                          * width).astype(np.int64), 0, width - 1)
            gy = np.clip(((yd - env[1])
                          / max(env[3] - env[1], 1e-12)
                          * height).astype(np.int64), 0, height - 1)
        return np.bincount(
            (gy * width + gx).astype(np.int64),
            minlength=width * height
        )[:width * height].reshape((height, width)).astype(np.float64)


def _bisect_segments(z: np.ndarray, vals: np.ndarray, lo: np.ndarray,
                     hi: np.ndarray, side: str) -> np.ndarray:
    """Vectorized binary search of ``vals[i]`` within the sorted
    segments ``z[lo[i]:hi[i]]`` — one numpy bisection loop serves EVERY
    (range × run-segment) pair at once, which is what makes host-tier
    seek cost flat in the number of spilled runs (round-4 VERDICT #9:
    the per-run Python loop serialized at the hundreds of host
    generations the 10B-per-pod story implies)."""
    lo = lo.astype(np.int64).copy()
    hi = hi.astype(np.int64).copy()
    while True:
        active = lo < hi
        if not active.any():
            return lo
        mid = (lo + hi) >> 1
        zm = z[np.where(active, mid, 0)]
        below = zm < vals if side == "left" else zm <= vals
        go = active & below
        lo = np.where(go, mid + 1, lo)
        hi = np.where(active & ~below, mid, hi)


class HostStack:
    """EVERY spilled run stacked into one contiguous key store with a
    global (bin → segment) table: a query batch seeks ALL host
    generations with two vectorized bisections total, instead of a
    Python loop per run per bin (round-4 VERDICT #9).

    The stack OWNS the concatenated arrays; each constituent
    :class:`HostRun`'s columns are re-pointed at views into them, so
    host RAM holds ONE copy of the spilled keys (a transient second
    copy exists only while a rebuild concatenates)."""

    __slots__ = ("z", "pos", "seg_bin", "seg_lo", "seg_hi", "seg_run",
                 "n_runs")

    def __init__(self, runs: list["HostRun"]):
        zs, ps, sb, sl, sh, sr = [], [], [], [], [], []
        off = 0
        for i, run in enumerate(runs):
            zs.append(run.z)
            ps.append(run.pos)
            sb.append(run._bin_vals)
            sl.append(off + run._bin_starts[:-1])
            sh.append(off + run._bin_starts[1:])
            sr.append(np.full(len(run._bin_vals), i, np.int32))
            off += len(run.z)
        self.n_runs = len(runs)
        self.z = (np.concatenate(zs) if zs
                  else np.empty(0, np.int64))
        self.pos = (np.concatenate(ps) if ps
                    else np.empty(0, np.int32))
        seg_bin = (np.concatenate(sb) if sb
                   else np.empty(0, np.int32))
        seg_lo = (np.concatenate(sl) if sl
                  else np.empty(0, np.int64))
        seg_hi = (np.concatenate(sh) if sh
                  else np.empty(0, np.int64))
        seg_run = (np.concatenate(sr) if sr
                   else np.empty(0, np.int32))
        order = np.argsort(seg_bin, kind="stable")
        self.seg_bin = seg_bin[order]
        self.seg_lo = seg_lo[order].astype(np.int64)
        self.seg_hi = seg_hi[order].astype(np.int64)
        self.seg_run = seg_run[order]
        # re-point the runs' columns at views of the stacked buffers so
        # the per-run copies free (the stack is now the owner)
        off = 0
        for run in runs:
            n = len(run.z)
            run.z = self.z[off:off + n]
            run.pos = self.pos[off:off + n]
            run.bins = None   # recoverable from the segment table
            off += n

    def density_partial(self, rb, rlo, rhi, sfc, ixy, tb, env,
                        width: int, height: int) -> np.ndarray:
        """Numpy DensityScan partial over every stacked host run — the
        host-tier contribution to the merged grid (same z-decoded CELL
        contract as the keys-tier device program)."""
        return self.density_partials(rb, rlo, rhi, sfc, ixy, tb, env,
                                     width, height).sum(axis=0)

    def density_partials(self, rb, rlo, rhi, sfc, ixy, tb, env,
                         width: int, height: int) -> np.ndarray:
        """PER-RUN DensityScan partials ``(n_runs, height, width)`` in
        the SAME single vectorized pass density_partial always took
        (two composite bisections total — flat in run count): each hit
        attributes to its owning run via the segment table, so every
        sealed host generation's immutable partial can cache
        individually without a per-run seek loop."""
        from ..curve.zorder import deinterleave3
        grids = np.zeros((self.n_runs, height, width), np.float64)
        idx, seg, _rid = self._expand(rb, rlo, rhi)
        if idx is None:
            return grids
        zc = self.z[idx]
        bc = self.seg_bin[seg].astype(np.int64)
        ix, iy, it = deinterleave3(zc.astype(np.uint64), xp=np)
        ix = ix.astype(np.int64)
        iy = iy.astype(np.int64)
        it = it.astype(np.int64)
        in_box = np.zeros(len(zc), bool)
        for b in np.atleast_2d(ixy):
            in_box |= ((ix >= b[0]) & (iy >= b[1])
                       & (ix <= b[2]) & (iy <= b[3]))
        ok = (in_box
              & ((bc > tb[0]) | ((bc == tb[0]) & (it >= tb[1])))
              & ((bc < tb[2]) | ((bc == tb[2]) & (it <= tb[3]))))
        if not ok.any():
            return grids
        xd = sfc.lon.denormalize(ix[ok], xp=np)
        yd = sfc.lat.denormalize(iy[ok], xp=np)
        gx = np.clip(((xd - env[0])
                      / max(env[2] - env[0], 1e-12) * width)
                     .astype(np.int64), 0, width - 1)
        gy = np.clip(((yd - env[1])
                      / max(env[3] - env[1], 1e-12) * height)
                     .astype(np.int64), 0, height - 1)
        np.add.at(grids, (self.seg_run[seg[ok]], gy, gx), 1.0)
        return grids

    def _expand(self, rb, rlo, rhi):
        """(flat z indices, owning segment, owning range) for a range
        batch — the shared expansion behind candidates() and
        density_partial().  Each range matches the [a, b) span of
        same-bin segments (one segment per run containing the bin);
        two composite bisections serve every pair."""
        if not len(self.z) or not len(rb):
            return None, None, None
        a = np.searchsorted(self.seg_bin, rb, side="left")
        b = np.searchsorted(self.seg_bin, rb, side="right")
        counts = np.maximum(b - a, 0)
        cum = np.cumsum(counts)
        total = int(cum[-1]) if len(cum) else 0
        if total == 0:
            return None, None, None
        j = np.arange(total)
        rid = np.searchsorted(cum, j, side="right")
        prev = np.where(rid > 0, cum[rid - 1], 0)
        seg = a[rid] + (j - prev)
        starts = _bisect_segments(self.z, rlo[rid], self.seg_lo[seg],
                                  self.seg_hi[seg], side="left")
        ends = _bisect_segments(self.z, rhi[rid], self.seg_lo[seg],
                                self.seg_hi[seg], side="right")
        cnt2 = np.maximum(ends - starts, 0)
        cum2 = np.cumsum(cnt2)
        tot2 = int(cum2[-1]) if len(cum2) else 0
        if tot2 == 0:
            return None, None, None
        k = np.arange(tot2)
        pid = np.searchsorted(cum2, k, side="right")
        prev2 = np.where(pid > 0, cum2[pid - 1], 0)
        return starts[pid] + (k - prev2), seg[pid], rid[pid]

    def candidates(self, rb, rlo, rhi, rqid, pos_bits: int) -> np.ndarray:
        """Coded candidate positions ``qid << pos_bits | pos`` across
        every stacked run for a padded range batch."""
        idx, _seg, rid = self._expand(rb, rlo, rhi)
        if idx is None:
            return np.empty(0, np.int64)
        return ((rqid[rid].astype(np.int64) << pos_bits)
                | self.pos[idx].astype(np.int64))


class _Generation:
    """One sorted key run.  ``tier`` ∈ {"full", "keys", "host"} (module
    doc); ``base`` is the global row id of its first row — generations
    cover contiguous global row ranges, so a ``full`` generation's
    payload is indexed by ``pos - base`` (append order).  ``gen_id`` is
    a store-lifetime-unique identity assigned by the owning index —
    compaction mints a FRESH id for each merged run, which is what
    keys (and therefore invalidates) the sealed-generation density
    partial cache.  ``t_lo``/``t_hi`` bound its rows' times (epoch ms,
    inclusive; None = unknown): the totals probe skips a generation
    whose span misses every window of a batch."""

    __slots__ = ("bins", "z", "pos", "x", "y", "t", "n", "base", "tier",
                 "run", "gen_id", "t_lo", "t_hi")

    @classmethod
    def merged_keys(cls, bins, z, pos, n: int, base: int,
                    t_span=(None, None)) -> "_Generation":
        """A compacted ``keys``-tier run from already-merged device
        columns (length == n: zero sentinel padding)."""
        gen = cls.__new__(cls)
        gen.bins, gen.z, gen.pos = bins, z, pos
        gen.x = gen.y = gen.t = None
        gen.n = int(n)
        gen.base = int(base)
        gen.tier = "keys"
        gen.run = None
        gen.gen_id = -1
        gen.t_lo, gen.t_hi = t_span
        return gen

    @classmethod
    def merged_host(cls, run: HostRun, base: int,
                    t_span=(None, None)) -> "_Generation":
        """A compacted ``host``-tier run from an already-merged
        :class:`HostRun`."""
        gen = cls.__new__(cls)
        gen.bins = gen.z = gen.pos = None
        gen.x = gen.y = gen.t = None
        gen.n = len(run)
        gen.base = int(base)
        gen.tier = "host"
        gen.run = run
        gen.gen_id = -1
        gen.t_lo, gen.t_hi = t_span
        return gen

    def __init__(self, capacity: int, base: int, tier: str):
        self.bins = jnp.full((capacity,), _SENTINEL_BIN, jnp.int32)
        self.z = jnp.full((capacity,), _SENTINEL_Z, jnp.int64)
        self.pos = jnp.full((capacity,), -1, jnp.int32)
        if tier == "full":
            self.x = jnp.zeros((capacity,), jnp.float64)
            self.y = jnp.zeros((capacity,), jnp.float64)
            self.t = jnp.zeros((capacity,), jnp.int64)
        else:
            self.x = self.y = self.t = None
        self.n = 0
        self.base = base
        self.tier = tier
        self.run: HostRun | None = None
        self.gen_id = -1   # assigned by the owning index
        self.t_lo: int | None = None   # widened by each append
        self.t_hi: int | None = None

    @property
    def capacity(self) -> int:
        return int(self.z.shape[0])

    def widen(self, t_lo: int, t_hi: int) -> None:
        """Widen the time bounds over rows at ``[t_lo, t_hi]``."""
        self.t_lo = t_lo if self.t_lo is None else min(self.t_lo, t_lo)
        self.t_hi = t_hi if self.t_hi is None else max(self.t_hi, t_hi)

    def meets(self, w_lo: np.ndarray, w_hi: np.ndarray) -> bool:
        """Whether this generation can hold a row inside any window
        ``[w_lo[i], w_hi[i]]``; unknown bounds always can."""
        if self.t_lo is None or self.t_hi is None:
            return True
        return bool(np.any((w_lo <= self.t_hi) & (w_hi >= self.t_lo)))

    def device_bytes(self) -> int:
        if self.tier == "host":
            return 0
        per = FULL_BYTES if self.tier == "full" else KEYS_BYTES
        return self.capacity * per

    def drop_payload(self) -> None:
        """full → keys: free the device payload copy (the host payload
        remains the source of truth for the exact mask)."""
        if self.tier == "full":
            self.x = self.y = self.t = None
            self.tier = "keys"

    def spill_to_host(self) -> None:
        """keys → host: fetch the sorted key run into host RAM as a
        :class:`HostRun`, freeing the HBM."""
        self.drop_payload()
        if self.tier != "keys":
            return
        bins = np.asarray(self.bins)
        z = np.asarray(self.z)
        pos = np.asarray(self.pos)
        # valid rows only: the sentinel padding sorts to the tail
        self.run = HostRun(bins[:self.n], z[:self.n], pos[:self.n])
        self.bins = self.z = self.pos = None
        self.tier = "host"


class LeanZ3Index:
    """Tiered generational keys-on-device Z3 index (see module doc)."""

    #: ``(schema, index_key)`` for access-temperature attribution
    #: (obs/heat) — stamped by the datastore; directly-built indexes
    #: record under a class-name fallback scope
    heat_scope: tuple | None = None

    #: slots per generation.  Each append re-sorts its generation, so
    #: generation size trades sort cost per slice against run count per
    #: query: slice-sized generations (the scale-proof setting) sort
    #: each slice exactly once — the LSM run-per-flush shape — while
    #: larger generations amortize query seeks.
    GENERATION_SLOTS = 1 << 24
    #: candidate-buffer floor of the density scans; the row scans
    #: (``_scan_tier``) floor at SCAN_MIN_CAPACITY
    DEFAULT_CAPACITY = 1 << 15
    #: candidate-buffer floor of a row scan dispatch: its capacity is
    #: the next power of two ≥ the candidates of its generation (the
    #: largest total, in a batched dispatch), and never below this.  Every slot up to it is expanded, gathered and
    #: masked, so a floor above the typical total is pure padding.
    #: Chosen on a TPU v5e, 30 s windows of the benchmark's dashboard
    #: and analyst cells: 2^12 read 60.5 and 40.3 queries/s (p95 1.64 s,
    #: 343 ms) against 45.2 and 34.6 (2.09 s, 419 ms) at 2^15, with 17
    #: and 7 programs loaded in the window against 5 and 1; a sweep of
    #: a batched scan read 7.2 ms a scan program at 2^12 against 10.1
    #: at 2^10 and 21.6 at 2^13, with 26, 36 and 25 in-window loads.
    SCAN_MIN_CAPACITY = 1 << 12
    #: slot budget for a batched (G × capacity) candidate buffer; beyond
    #: it queries fall back to per-generation dispatches sized by each
    #: generation's own total
    BATCH_SCAN_BUDGET = 1 << 26
    #: default HBM budget for the key/payload residency (v5e usable
    #: 15.75 GiB minus scan/transfer slack; docs/scale.md)
    HBM_BUDGET_BYTES = int(13.5 * 2**30)
    #: size-tiered compaction trigger: merge when ≥ F sealed runs share
    #: a tier AND size class (the LSM merge policy the reference's
    #: key-value backends run server-side).  This class default serves
    #: EXPLICIT compact() calls; pass ``compaction_factor=F`` to the
    #: constructor to also run the trigger OPPORTUNISTICALLY after
    #: appends/demotions (bounded: one merge group per append).
    COMPACTION_FACTOR = 4
    #: distinct density grid/query specs whose per-generation partials
    #: are retained (LRU); each spec holds ≤ one (height, width) f64
    #: grid per sealed generation
    DENSITY_CACHE_SPECS = 4
    #: host-RAM ceiling for cached partials across all specs — large
    #: grids × many generations must not silently eat the host (the
    #: check runs at spec lookup, so one call may overshoot before the
    #: oldest specs evict)
    DENSITY_CACHE_MAX_BYTES = 512 * 2**20
    #: stat-sketch partial cache bounds (cell-count folds are small:
    #: time-bins × 2^bits int64 per sealed generation)
    SKETCH_CACHE_SPECS = 8
    SKETCH_CACHE_MAX_BYTES = 64 * 2**20
    #: density-pyramid cache spec bound (ISSUE 18): one spec per base
    #: resolution — two lets a live base-resolution retune keep serving
    #: off the old stack while the new one builds behind.  The byte
    #: ceiling comes from ``geomesa.density.pyramid.cache.bytes``.
    PYRAMID_CACHE_SPECS = 2

    def __init__(self, period: TimePeriod | str = TimePeriod.WEEK,
                 version: int = Z3_INDEX_VERSION,
                 generation_slots: int | None = None,
                 hbm_budget_bytes: int | None = None,
                 payload_on_device: bool = True,
                 compaction_factor: int | None = None):
        self.period = TimePeriod.parse(period)
        self.version = version
        self.sfc = z3_sfc_for_version(self.period, version)
        self.generation_slots = generation_slots or self.GENERATION_SLOTS
        self.hbm_budget_bytes = hbm_budget_bytes or self.HBM_BUDGET_BYTES
        #: whether NEW generations carry a device payload for the fused
        #: exact mask (they demote automatically under budget pressure)
        self.payload_on_device = payload_on_device
        self.generations: list[_Generation] = []
        #: host payload slices (x, y, dtg) in append order; finalized
        #: into flat arrays lazily for the exact re-check.  A store
        #: embedding this index supplies ``payload_provider`` instead
        #: (one host copy, owned by the store).
        self._payload: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._flat: tuple | None = None
        self.payload_provider = None
        self._n_rows = 0
        self.t_min_ms: int | None = None
        self.t_max_ms: int | None = None
        #: device program dispatches issued (tests pin dispatch counts)
        self.dispatch_count = 0
        #: per-instance bucket-padding sentinel columns, keyed tier
        #: (see _make_sentinel_cols)
        self._sentinels: dict = {}
        #: stacked host-tier runs (built lazily on first query after a
        #: spill; seek cost flat in run count — see HostStack)
        self._host_stack: HostStack | None = None
        #: opportunistic size-tiered compaction factor (0 = off; the
        #: explicit compact() maintenance call works either way)
        self.compaction_factor = int(compaction_factor or 0)
        #: merge groups folded so far (observability)
        self.compactions = 0
        #: sealed-generation density partials: spec → {gen_id: grid}.
        #: A sealed (demoted keys/host) generation's contribution to a
        #: given (boxes, window, env, grid) spec is IMMUTABLE, so warm
        #: repeat density calls sum cached grids and re-scan only the
        #: live generation (+ full-tier generations, whose value-exact
        #: edge cells the cache must not coarsen).  The LRU + byte
        #: ceiling + compaction-invalidation policy is the shared
        #: :class:`~geomesa_tpu.index.partial_cache.PartialCache`.
        from .partial_cache import PartialCache
        self._density_cache = PartialCache(self.DENSITY_CACHE_SPECS,
                                           self.DENSITY_CACHE_MAX_BYTES)
        #: sealed-generation stat-sketch partials (ISSUE 3): the same
        #: policy over the z3 cell-count folds Z3Histogram pushes down
        self._sketch_cache = PartialCache(self.SKETCH_CACHE_SPECS,
                                          self.SKETCH_CACHE_MAX_BYTES)
        #: sealed-generation density pyramids (ISSUE 18): the same
        #: policy over whole-world multi-resolution grid stacks —
        #: spec is ``("pyramid", base)``, so rebuilds at a new base
        #: resolution coexist until the LRU retires the old one
        from ..config import DensityProperties
        self._pyramid_cache = PartialCache(
            self.PYRAMID_CACHE_SPECS,
            DensityProperties.PYRAMID_CACHE_BYTES.to_int())
        #: generation-lifecycle listeners (index/lsm
        #: notify_generation_event): ``listener(kind, gen_ids)`` fired
        #: on seal/merge — the build-behind hook pyramid jobs ride
        self.generation_listeners: list = []
        #: store-lifetime generation id source (see _Generation.gen_id)
        self._gen_counter = 0

    def _sentinel_cols(self, tier: str):
        if tier not in self._sentinels:
            self._sentinels[tier] = _make_sentinel_cols(
                tier, self.generation_slots)
        return self._sentinels[tier]

    def __len__(self) -> int:
        return self._n_rows

    def block(self) -> None:
        """Wait for every in-flight append (dispatches are async — honest
        ingest timing must block on the last generation's columns)."""
        for gen in reversed(self.generations):
            if gen.tier != "host":
                jax.block_until_ready(gen.pos)
                break

    def device_bytes(self) -> int:
        """Resident HBM of the key/payload columns (the budget the scale
        proof asserts against docs/scale.md)."""
        return sum(g.device_bytes() for g in self.generations)

    def host_key_bytes(self) -> int:
        """Host RAM held by spilled (``host``-tier) key runs."""
        return sum(g.n * KEYS_BYTES for g in self.generations
                   if g.tier == "host")

    def tier_counts(self) -> dict:
        out = {"full": 0, "keys": 0, "host": 0}
        for g in self.generations:
            out[g.tier] += 1
        return out

    def sentinel_bytes(self) -> int:
        """HBM charged for the lazily-allocated bucket-padding sentinel
        buffers (the budget's _budget_after_sentinels counterpart, but
        for buffers that EXIST rather than will exist)."""
        return sum(self.generation_slots
                   * (FULL_BYTES if tier == "full" else KEYS_BYTES)
                   for tier in self._sentinels)

    def storage_stats(self) -> dict:
        """Live byte accounting for the storage report (obs/resource,
        ISSUE 9): where this index's bytes sit — device key/payload
        runs vs host-spilled runs, per generation, plus the sealed-
        partial caches — from the SAME per-slot constants the HBM
        budget uses, so the report reconciling these against actual
        array nbytes is exactly a budget-accounting audit."""
        gens = [{"gen_id": g.gen_id, "tier": g.tier, "rows": int(g.n),
                 "capacity": 0 if g.tier == "host" else g.capacity,
                 "device_bytes": g.device_bytes(),
                 "host_bytes": (g.n * KEYS_BYTES
                                if g.tier == "host" else 0)}
                for g in self.generations]
        return {"kind": type(self).__name__, "rows": len(self),
                "tiers": self.tier_counts(),
                "device_bytes": self.device_bytes(),
                "host_bytes": self.host_key_bytes(),
                "sentinel_bytes": self.sentinel_bytes(),
                "hbm_budget_bytes": self.hbm_budget_bytes,
                "generations": gens,
                "caches": {"density": self._density_cache.stats(),
                           "sketch": self._sketch_cache.stats(),
                           "pyramid": self._pyramid_cache.stats()},
                "dispatches": self.dispatch_count}

    # -- write path -------------------------------------------------------
    def _new_generation(self, base: int) -> _Generation:
        tier = "full" if self.payload_on_device else "keys"
        if tier == "full":
            # would the payload survive rebalance?  The LIVE generation's
            # payload is RESERVED by the demotion policy (round-4 VERDICT
            # #5): older payloads drop first and older key runs spill to
            # host before it is touched, so the payload is doomed only if
            # the live full generation ALONE (plus the sentinel padding
            # buffers) busts the budget — don't allocate slots × 24 B of
            # HBM (and a transient spike) that _rebalance frees moments
            # later.
            floor = self.generation_slots * (FULL_BYTES
                                             + KEYS_BYTES + FULL_BYTES)
            if floor > self.hbm_budget_bytes:
                tier = "keys"
        gen = _Generation(self.generation_slots, base=base, tier=tier)
        gen.gen_id = self._next_gen_id()
        self.generations.append(gen)
        self._rebalance()
        return self.generations[-1]

    def _next_gen_id(self) -> int:
        self._gen_counter += 1
        return self._gen_counter

    def _budget_after_sentinels(self) -> int:
        """Effective budget: hbm_budget_bytes minus the shared full-size
        sentinel padding buffers queries will lazily allocate — a keys
        sentinel always, a full one only while full-tier generations
        exist (recomputed as tiers demote)."""
        per = self.generation_slots * KEYS_BYTES
        if any(g.tier == "full" for g in self.generations):
            per += self.generation_slots * FULL_BYTES
        return self.hbm_budget_bytes - per

    def _fits(self) -> bool:
        if not any(g.tier == "full" for g in self.generations):
            # the budget stops charging the full-tier sentinel once no
            # full generation exists — free the cached one so the
            # charge matches resident HBM
            self._sentinels.pop("full", None)
        return self.device_bytes() <= self._budget_after_sentinels()

    def _spill(self, gen: _Generation) -> None:
        # injected BEFORE the transfer: a faulted spill leaves the
        # generation on device, fully queryable (resilience chaos tests)
        from ..resilience import fault_point
        fault_point("host.spill")
        # the spill IS a blocking device→host transfer — a device span
        # so ingest traces carry its block-until-ready ms (ISSUE 12)
        with device_span("write.spill", gen_id=gen.gen_id,
                         rows=int(gen.n)):
            obs_count(WRITE_SPILLS)
            gen.spill_to_host()
        self._host_stack = None   # restacked lazily on the next query

    def _rebalance(self) -> None:
        """Demote oldest-first until the device residency (key/payload
        columns PLUS the shared sentinel padding buffers queries will
        allocate) fits the HBM budget: payload drops first (full →
        keys), then key runs spill to host RAM (keys → host).  The
        ACTIVE generation's keys never spill — appends sort there —
        and its PAYLOAD is reserved (round-4 VERDICT #5): the newest
        (hottest) generation keeps the fused device-exact path at any
        store size, older key runs spilling to host RAM to make room;
        it drops only as the last step before the budget is simply too
        small for one live generation."""
        if self._fits():
            return
        for gen in self.generations[:-1]:
            if gen.tier == "full":
                gen.drop_payload()
                if self._fits():
                    return
        for gen in self.generations[:-1]:
            if gen.tier == "keys":
                self._spill(gen)
                if self._fits():
                    return
        live = self.generations[-1] if self.generations else None
        if live is not None and live.tier == "full":
            # last resort: the budget cannot hold even one full live
            # generation — appends continue through the keys program
            live.drop_payload()
            if self._fits():
                return
        raise MemoryError(
            f"active generation ({self.generation_slots} slots) "
            f"exceeds hbm_budget_bytes={self.hbm_budget_bytes} "
            "minus the sentinel-padding overhead")

    def append(self, x, y, dtg_ms) -> "LeanZ3Index":
        """Stream one slice in: host payload retained by reference, keys
        encoded + merged into the current generation on device (rolling
        to a fresh generation when full)."""
        if self._n_rows + len(x) > np.iinfo(np.int32).max:
            raise ValueError("LeanZ3Index positions are int32: "
                             "2,147M rows max per index/shard")
        # injected at ENTRY, before any state mutates: a faulted append
        # loses the whole slice atomically — rows are either fully
        # indexed or absent, never half-ingested (resilience chaos tests)
        from ..resilience import fault_point
        fault_point("ingest.append")
        x = np.ascontiguousarray(x, dtype=np.float64)
        y = np.ascontiguousarray(y, dtype=np.float64)
        dtg_ms = np.ascontiguousarray(dtg_ms, dtype=np.int64)
        m_total = len(x)
        if m_total == 0:
            return self
        if self.payload_provider is None:
            self._payload.append((x, y, dtg_ms))
            self._flat = None
        host_bins, host_offs = to_binned_time(dtg_ms, self.period)
        host_bins = host_bins.astype(np.int32)
        host_offs = host_offs.astype(np.float64)
        done = 0
        while done < m_total:
            gen = (self.generations[-1] if self.generations else None)
            if gen is None or gen.tier == "host" or gen.n >= gen.capacity:
                # base = global row id of the generation's first row —
                # mid-append rollovers account for rows already consumed
                if gen is not None and gen.tier != "host":
                    # the live generation SEALS on rollover; the span
                    # covers the rebalance (demote/spill) it triggers
                    sealed_id = gen.gen_id
                    with obs_span("write.seal", gen_id=gen.gen_id,
                                  tier=gen.tier, rows=int(gen.n)):
                        obs_count(WRITE_SEALS)
                        gen = self._new_generation(self._n_rows + done)
                    # AFTER the seal span: listeners schedule optional
                    # build-behind work (density pyramids) and must
                    # never break or slow the append itself
                    from .lsm import notify_generation_event
                    notify_generation_event(self, "seal", [sealed_id])
                else:
                    gen = self._new_generation(self._n_rows + done)
            room = gen.capacity - gen.n
            take = min(room, m_total - done)
            m_pad = min(gather_capacity(take, minimum=8), room)
            sl = slice(done, done + take)
            pad = m_pad - take
            self.dispatch_count += 1
            if gen.tier == "full":
                (gen.bins, gen.z, gen.pos, gen.x, gen.y,
                 gen.t) = _lean_append_full(
                    self.sfc, gen.bins, gen.z, gen.pos,
                    gen.x, gen.y, gen.t,
                    jnp.int32(gen.n), jnp.int32(gen.base),
                    jnp.asarray(np.pad(x[sl], (0, pad))),
                    jnp.asarray(np.pad(y[sl], (0, pad))),
                    jnp.asarray(np.pad(host_offs[sl], (0, pad))),
                    jnp.asarray(np.pad(host_bins[sl], (0, pad))),
                    jnp.asarray(np.pad(dtg_ms[sl], (0, pad))),
                    jnp.int32(take))
            else:
                gen.bins, gen.z, gen.pos = _lean_append(
                    self.sfc, gen.bins, gen.z, gen.pos,
                    jnp.int32(gen.n), jnp.int32(gen.base),
                    jnp.asarray(np.pad(x[sl], (0, pad))),
                    jnp.asarray(np.pad(y[sl], (0, pad))),
                    jnp.asarray(np.pad(host_offs[sl], (0, pad))),
                    jnp.asarray(np.pad(host_bins[sl], (0, pad))),
                    jnp.int32(take))
            gen.n += take
            gen.widen(int(dtg_ms[sl].min()), int(dtg_ms[sl].max()))
            done += take
        self._n_rows += m_total
        t_min, t_max = int(dtg_ms.min()), int(dtg_ms.max())
        self.t_min_ms = (t_min if self.t_min_ms is None
                         else min(self.t_min_ms, t_min))
        self.t_max_ms = (t_max if self.t_max_ms is None
                         else max(self.t_max_ms, t_max))
        if self.compaction_factor:
            # opportunistic trigger after append/demotion: bounded to
            # ONE merge group so ingest latency stays O(generation)
            self.compact(factor=self.compaction_factor, max_groups=1)
        return self

    # -- compaction (LSM maintenance) -------------------------------------
    def _sealed(self) -> list[_Generation]:
        """Generations appends can no longer touch — everything but the
        live (last) one.  Only sealed runs merge; only sealed keys/host
        runs cache density partials."""
        return self.generations[:-1]

    def _compaction_groups(self, factor: int) -> list[list[_Generation]]:
        from .lsm import plan_size_tiered
        return plan_size_tiered(self._sealed(), ("keys", "host"),
                                lambda g: g.n, factor)

    def _merge_group(self, group: list[_Generation]) -> None:
        """Fold one same-tier group into a single sorted run placed at
        the group's oldest position (list order is demotion age).  The
        merged run gets a FRESH gen_id; the source runs' device slots /
        host buffers free with their python references and their cached
        density partials drop (stale grids must never double-count)."""
        from .lsm import merged_capacity, replace_group
        base = min(g.base for g in group)
        total = int(sum(g.n for g in group))
        t_span = (None, None)
        if all(g.t_lo is not None and g.t_hi is not None for g in group):
            t_span = (min(g.t_lo for g in group),
                      max(g.t_hi for g in group))
        if group[0].tier == "keys":
            cols: list = []
            for g in group:
                cols += [g.bins, g.z, g.pos]
            out_cap = merged_capacity(
                total, sum(g.capacity for g in group), gather_capacity)
            self.dispatch_count += 1
            bins, z, pos = _lean_merge_keys(*cols, out_cap=out_cap)
            merged = _Generation.merged_keys(bins, z, pos, n=total,
                                             base=base, t_span=t_span)
        else:
            merged = _Generation.merged_host(
                merge_host_runs([g.run for g in group]), base=base,
                t_span=t_span)
            self._host_stack = None   # restacked lazily
        merged.gen_id = self._next_gen_id()
        dead_ids = [g.gen_id for g in group]
        # the merged run inherits its sources' access temperature —
        # hot data must not read cold because maintenance renamed it.
        # Credited BEFORE the swap: a concurrent heat report prunes
        # tracker entries absent from its placement snapshot, and the
        # freshly-stamped merged entry rides the prune grace window
        # while dead ids may be long-cold
        merge_index_generations(self, dead_ids, merged.gen_id)
        # pyramid inheritance mirrors the heat inheritance above: the
        # merged run's pyramid is the exact elementwise SUM of its
        # sources' (same immutable keys, renamed), computed BEFORE the
        # stale parents drop — a merge must not send tile serving back
        # to the scan path when its inputs were already built
        self._inherit_pyramids(dead_ids, merged.gen_id)
        self.generations = replace_group(self.generations, group,
                                         merged)
        self._drop_cached_partials(dead_ids)
        self.compactions += 1
        _metrics.counter(LEAN_COMPACTION_MERGES).inc()
        _metrics.counter(LEAN_COMPACTION_ROWS).inc(total)
        from .lsm import notify_generation_event
        notify_generation_event(self, "merge", [merged.gen_id])

    def compact(self, budget_ms: float | None = None,
                factor: int | None = None,
                max_groups: int | None = None) -> dict:
        """Incremental size-tiered K-way merge compaction — the role
        the reference delegates to its key-value backend's periodic
        compaction (Accumulo/HBase major compaction), run here as an
        explicit maintenance job or opportunistically after appends.

        Merges one group at a time and re-plans (index/lsm.py), so a
        ``budget_ms`` deadline or ``max_groups`` cap interrupts cleanly
        BETWEEN merges and the next call resumes where this one
        stopped; each call makes progress (≥ 1 group when any is
        eligible) even at ``budget_ms=0``.  Query results are identical
        at every intermediate state — a merge only re-sorts the union
        of already-sealed runs.

        Returns ``{"merged_groups", "generations", "tiers"}``."""
        from .lsm import compact_incremental
        f = int(factor or self.compaction_factor
                or self.COMPACTION_FACTOR)
        merged = compact_incremental(
            lambda: self._compaction_groups(f), self._merge_group,
            budget_ms=budget_ms, max_groups=max_groups)
        if merged:
            # merged runs never out-size their sources — residency only
            # shrinks, but re-check so the budget invariant is explicit
            self._rebalance()
        return {"merged_groups": merged,
                "generations": len(self.generations),
                "tiers": self.tier_counts()}

    def _drop_cached_partials(self, gen_ids: list) -> None:
        self._density_cache.drop_generations(gen_ids)
        self._sketch_cache.drop_generations(gen_ids)
        self._pyramid_cache.drop_generations(gen_ids)

    def _inherit_pyramids(self, dead_ids: list, new_gen_id: int) -> None:
        """Compaction inheritance: when EVERY merged-away parent has a
        pyramid under a spec (same level set), the merged run gets
        their elementwise sum — bit-exact, because each parent level is
        the parent's exact count grid and the merged run is exactly the
        union of the parents' rows.  Any missing parent leaves the
        merged run pyramid-less (the next build fills it)."""
        from .pyramid import DensityPyramid
        for _spec, cache in self._pyramid_cache.items():
            parents = [cache.get(gid) for gid in dead_ids]
            if all(p is not None for p in parents):
                merged = DensityPyramid.sum(parents)
                if merged is not None:
                    self._pyramid_cache.add(cache, new_gen_id, merged)

    def _pyramid_level(self, gen_id: int, width: int):
        """The cached (width, width) pyramid grid for one sealed
        generation, or None — serving never waits on a build."""
        for _spec, cache in self._pyramid_cache.items():
            pyr = cache.get(gen_id)
            if pyr is not None:
                lvl = pyr.level(width)
                if lvl is not None:
                    return lvl
        return None

    def _cache_partial(self, cache: dict, gen_id: int, part) -> None:
        """Store one sealed-generation density partial (the shared
        PartialCache byte-ceiling policy)."""
        self._density_cache.add(cache, gen_id, part)

    def _density_spec_cache(self, spec) -> dict:
        """The per-generation partial dict for one density spec (LRU +
        byte ceiling — index/partial_cache)."""
        return self._density_cache.spec_cache(spec)

    # -- payload ----------------------------------------------------------
    def _payload_flat(self):
        if self.payload_provider is not None:
            return self.payload_provider()
        if self._flat is None:
            xs, ys, ts = zip(*self._payload) if self._payload else ((), (), ())
            self._flat = (np.concatenate(xs) if xs else np.empty(0),
                          np.concatenate(ys) if ys else np.empty(0),
                          np.concatenate(ts) if ts else np.empty(0, np.int64))
            # the per-slice references are no longer needed — drop them
            # so host RAM holds ONE copy of the payload
            self._payload = [tuple(self._flat)]
        return self._flat

    def _clamp_time(self, t_lo_ms, t_hi_ms) -> tuple[int, int]:
        t_lo_ms = self.t_min_ms if t_lo_ms is None else int(t_lo_ms)
        t_hi_ms = self.t_max_ms if t_hi_ms is None else int(t_hi_ms)
        if self.t_min_ms is not None:
            t_lo_ms = max(t_lo_ms, self.t_min_ms)
        if self.t_max_ms is not None:
            t_hi_ms = min(t_hi_ms, self.t_max_ms)
        return t_lo_ms, t_hi_ms

    # -- query path -------------------------------------------------------
    def query(self, boxes, t_lo_ms, t_hi_ms,
              max_ranges: int = 2000, progress=None) -> np.ndarray:
        """Exact original-order positions for one bbox(es)+time window."""
        return self.query_many([(boxes, t_lo_ms, t_hi_ms)],
                               max_ranges=max_ranges,
                               progress=progress)[0]

    def query_many(self, windows, max_ranges: int = 2000,
                   progress=None) -> list[np.ndarray]:
        """Batched multi-window scan: every window in one totals probe
        and one scan per populated device tier, each over the
        generations that can hold a hit (``_probe_totals``,
        ``_scan_tier``), the BatchScanner-over-many-range-sets pattern
        the analytics processes build on (round-4 VERDICT #5).
        Returns one sorted exact-position array per window."""
        n_q = len(windows)
        if n_q == 0 or self._n_rows == 0:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        # host planning per window; ranges concatenate with owning qid
        rbin, rzlo, rzhi, rqid = [], [], [], []
        # the time of each window that produced ranges: the probe
        # seeks only the generations these meet
        r_tlo: list = []
        r_thi: list = []
        w_boxes: list = []
        # never-matching (lo > hi) until planned, padded to the bucket
        w_pad = pad_pow2(n_q, minimum=_WINDOW_BUCKET)
        qtlo = np.ones(w_pad, dtype=np.int64)
        qthi = np.zeros(w_pad, dtype=np.int64)
        from ..resilience import check_cancel
        with obs_span("query.decompose", windows=n_q) as dsp:
            for q, (bxs, lo, hi) in enumerate(windows):
                # yield point between range decompositions: a window
                # not yet planned scans nothing (partial mode), so the
                # planned windows' results stay exact
                if check_cancel("query.decompose"):
                    break
                lo, hi = self._clamp_time(lo, hi)
                qtlo[q], qthi[q] = lo, hi
                bxs = np.atleast_2d(np.asarray(bxs, dtype=np.float64))
                w_boxes.append(bxs)
                # per-BIN range budget: plan_z3_query splits its target
                # across the interval's bins, so open/long intervals
                # would starve each bin into hugely overcovering ranges
                # (895k candidates for 23 hits measured) — scale by the
                # bin count and let the hard cap bound plan cost
                budget = min(max_ranges * _bins_spanned(lo, hi,
                                                        self.period),
                             _MAX_RANGES_PER_WINDOW)
                plan = plan_z3_query(bxs, lo, hi, self.period, budget,
                                     sfc=self.sfc)
                if plan.num_ranges == 0:
                    continue
                rbin.append(plan.rbin)
                rzlo.append(plan.rzlo)
                rzhi.append(plan.rzhi)
                rqid.append(np.full(plan.num_ranges, q, dtype=np.int32))
                r_tlo.append(lo)
                r_thi.append(hi)
            dsp.set_attr("ranges", int(sum(len(r) for r in rbin)))
        if not rbin:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        ra = pad_ranges(
            {"rbin": np.concatenate(rbin), "rzlo": np.concatenate(rzlo),
             "rzhi": np.concatenate(rzhi), "rqid": np.concatenate(rqid)},
            pad_pow2(sum(len(r) for r in rbin)))
        rb = jnp.asarray(ra["rbin"])
        rlo = jnp.asarray(ra["rzlo"])
        rhi = jnp.asarray(ra["rzhi"])
        rq = jnp.asarray(ra["rqid"])
        pos_bits = coded_pos_bits(self._n_rows, n_q)

        full_gens = [g for g in self.generations if g.tier == "full"]
        keys_gens = [g for g in self.generations if g.tier == "keys"]
        host_gens = [g for g in self.generations if g.tier == "host"]

        # the totals probe over the device generations (full + keys),
        # positional: totals[i] belongs to dev_gens[i]
        dev_gens = full_gens + keys_gens
        totals = self._probe_totals(dev_gens, np.asarray(r_tlo),
                                    np.asarray(r_thi), rb, rlo, rhi,
                                    progress)
        # adaptive-replan probe point (ISSUE 19): the device totals are
        # known BEFORE any gather, so aborting here discards nothing
        from ..planning.adaptive import check_replan
        dev_total = int(totals.sum()) if dev_gens else 0
        check_replan("query.scan.probe", dev_total)
        coded_parts: list = []
        # keys_cand also collects DEGRADED candidates from either
        # device tier (ISSUE 16): the recheck below restores exactness
        keys_cand: list = []
        # full tier: fused exact mask on device — survivors only
        if full_gens and not check_cancel("query.scan.full"):
            t_full = totals[:len(full_gens)]
            if int(t_full.sum()):
                boxes_c, bqid_c = self._concat_boxes(w_boxes)
                coded_parts += self._scan_tier(
                    full_gens, t_full, rb, rlo, rhi, rq, pos_bits,
                    exact_args=(jnp.asarray(boxes_c),
                                jnp.asarray(bqid_c),
                                jnp.asarray(qtlo), jnp.asarray(qthi)),
                    ra=ra, degraded_out=keys_cand)
        # keys tier: candidate gather — host exact mask below; its
        # device candidates are keys_cand's rows [keys_lo, keys_hi)
        keys_lo = keys_hi = 0
        if keys_gens and not check_cancel("query.scan.keys"):
            t_keys = totals[len(full_gens):len(dev_gens)]
            if int(t_keys.sum()):
                keys_dev = self._scan_tier(
                    keys_gens, t_keys, rb, rlo, rhi, rq, pos_bits,
                    exact_args=None, ra=ra, degraded_out=keys_cand)
                keys_lo = sum(len(c) for c in keys_cand)
                keys_hi = keys_lo + sum(len(c) for c in keys_dev)
                keys_cand += keys_dev
        # host tier: stacked numpy seeks — flat in run count, and no
        # dispatch at all (round-4 VERDICT #9)
        host_cand_n = 0
        if host_gens and not check_cancel("query.scan.host"):
            with obs_span("query.scan.host", stage="seek",
                          runs=len(host_gens)):
                if self._host_stack is None:
                    self._host_stack = HostStack(
                        [g.run for g in host_gens])
                coded = self._host_stack.candidates(
                    ra["rbin"], ra["rzlo"], ra["rzhi"], ra["rqid"],
                    pos_bits)
                host_cand_n = int(len(coded))
                if len(coded):
                    keys_cand.append(coded)
        if host_cand_n:
            # second probe point: host-tier candidates are counted
            # before the payload recheck, the expensive host step
            check_replan("query.scan.probe", dev_total + host_cand_n)
        if heat_enabled():
            # per-generation access temperature (obs/heat): device
            # generations attribute candidates exactly (the probe's
            # per-generation totals); the stacked host seek loses
            # per-run attribution, so host candidates split
            # proportionally to run size
            touches = [(g.gen_id, g.tier, int(g.n),
                        int(g.n) * (FULL_BYTES if g.tier == "full"
                                    else KEYS_BYTES),
                        int(totals[i]))
                       for i, g in enumerate(dev_gens)]
            n_host = sum(g.n for g in host_gens)
            touches += [(g.gen_id, "host", int(g.n),
                         int(g.n) * KEYS_BYTES,
                         int(round(host_cand_n * g.n / n_host)))
                        for g in host_gens]
            record_index_scan(self, touches)

        mask_bits = (np.int64(1) << pos_bits) - 1
        out = [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        exact_hits = (np.concatenate(coded_parts) if coded_parts
                      else np.empty(0, np.int64))
        cand_hits = (np.concatenate(keys_cand) if keys_cand
                     else np.empty(0, np.int64))
        if len(cand_hits):
            # host exact mask on the payload (the client-side re-check
            # of keys/host-tier candidates) — its own scan.host stage
            # so the trace separates spill seeks from verification
            with obs_span("query.scan.host", stage="recheck",
                          candidates=int(len(cand_hits))) as rsp:
                x, y, t = self._payload_flat()
                qids = (cand_hits >> pos_bits).astype(np.int64)
                cand = (cand_hits & mask_bits).astype(np.int64)
                cx, cy, ct = x[cand], y[cand], t[cand]
                keep = np.zeros(len(cand), dtype=bool)
                for q in range(n_q):
                    sel = qids == q
                    if not sel.any():
                        continue
                    in_box = np.zeros(int(sel.sum()), dtype=bool)
                    for b in w_boxes[q]:
                        in_box |= ((cx[sel] >= b[0]) & (cy[sel] >= b[1])
                                   & (cx[sel] <= b[2]) & (cy[sel] <= b[3]))
                    keep[sel] = (in_box & (ct[sel] >= qtlo[q])
                                 & (ct[sel] <= qthi[q]))
                cand_hits = cand_hits[keep]
                if keys_hi > keys_lo:
                    # the keys tier's scan hits: its device candidates
                    # that pass this recheck (lean.scan.hits)
                    n_hits = int(keep[keys_lo:keys_hi].sum())
                    _metrics.counter(LEAN_SCAN_HITS).inc(n_hits)
                    rsp.set_attr(LEAN_SCAN_HITS, n_hits)
        merged = np.concatenate([exact_hits, cand_hits])
        qids = (merged >> pos_bits).astype(np.int64)
        positions = (merged & mask_bits).astype(np.int64)
        for q in range(n_q):
            # unique: overlapping covering ranges can duplicate a row
            out[q] = np.unique(positions[qids == q])
        return out

    # -- result materialization (ISSUE 14) --------------------------------
    def gather_payload(self, positions: np.ndarray):
        """(x, y, t) columns for the given global row positions — the
        Arrow result path's column gather (arrow/stream.py).

        Rows living in a ``full``-tier generation gather ON DEVICE:
        one batched take per generation (:func:`_lean_gather_payload`
        over the payload columns the fused exact mask already keeps
        resident), so for the hot all-full store the geometry/time
        columns of a result never round-trip through the host column
        store at all.  Rows in ``keys``/``host``-tier generations
        gather from the host payload via one vectorized numpy take —
        the stacked-host-run half of the materialize contract.  Values
        are bit-identical to the host payload either way (the device
        copy was written from the same arrays), which is what makes
        the Arrow path byte-exact against the row-wise one."""
        positions = np.asarray(positions, dtype=np.int64)
        n = len(positions)
        if n == 0:
            return (np.empty(0, np.float64), np.empty(0, np.float64),
                    np.empty(0, np.int64))
        order = None
        sorted_pos = positions
        if n > 1 and not bool(np.all(positions[1:] >= positions[:-1])):
            # sorted segments per generation need sorted positions; a
            # sort-by query hands them in result order — gather sorted,
            # then scatter back through the inverse permutation
            order = np.argsort(positions, kind="stable")
            sorted_pos = positions[order]
        x = np.empty(n, np.float64)
        y = np.empty(n, np.float64)
        t = np.empty(n, np.int64)
        covered = np.zeros(n, dtype=bool)
        for gen in self.generations:
            if gen.tier != "full" or gen.n == 0:
                continue
            lo = int(np.searchsorted(sorted_pos, gen.base, side="left"))
            hi = int(np.searchsorted(sorted_pos, gen.base + gen.n,
                                     side="left"))
            if hi <= lo:
                continue
            m = hi - lo
            cap = gather_capacity(m, minimum=8)
            idx = np.zeros(cap, np.int32)
            idx[:m] = (sorted_pos[lo:hi] - gen.base).astype(np.int32)
            self.dispatch_count += 1
            with device_span("query.materialize", stage="gather",
                             runs=1, rows=m, bytes=m * PAYLOAD_BYTES) as d:
                gx, gy, gt = _lean_gather_payload(jnp.asarray(idx),
                                                  gen.x, gen.y, gen.t)
                d.dispatched()
                x[lo:hi] = np.asarray(gx)[:m]
                y[lo:hi] = np.asarray(gy)[:m]
                t[lo:hi] = np.asarray(gt)[:m]
            covered[lo:hi] = True
        if not covered.all():
            hx, hy, ht = self._payload_flat()
            rest = sorted_pos[~covered]
            x[~covered] = hx[rest]
            y[~covered] = hy[rest]
            t[~covered] = ht[rest]
        if order is not None:
            inv = np.empty(n, np.int64)
            inv[order] = np.arange(n)
            x, y, t = x[inv], y[inv], t[inv]
        return x, y, t

    # -- aggregation push-down (round-4 VERDICT #2) -----------------------
    def _plan_one(self, boxes, t_lo_ms, t_hi_ms, max_ranges: int):
        """Padded covering-range arrays for ONE window (the density /
        count scan shape)."""
        lo, hi = self._clamp_time(t_lo_ms, t_hi_ms)
        bxs = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
        budget = min(max_ranges * _bins_spanned(lo, hi, self.period),
                     _MAX_RANGES_PER_WINDOW)
        plan = plan_z3_query(bxs, lo, hi, self.period, budget,
                             sfc=self.sfc)
        if plan.num_ranges == 0:
            return None
        ra = pad_ranges(
            {"rbin": plan.rbin, "rzlo": plan.rzlo, "rzhi": plan.rzhi},
            pad_pow2(plan.num_ranges))
        return ra, bxs, lo, hi

    def density(self, boxes, t_lo_ms, t_hi_ms, env,
                width: int = 256, height: int = 256,
                max_ranges: int = 2000) -> np.ndarray:
        """DensityScan push-down: the (height, width) heatmap of
        bbox+time hits accumulated NEXT TO THE KEYS — full-tier
        generations mask exactly on their device payload, keys-tier
        generations decode cell-accurate coordinates from the z key,
        host-tier runs contribute numpy partials; the grids merge as a
        sum.  Only grids cross the wire — a whole-extent heatmap over
        1B rows ships ``height*width`` floats, not a billion hits
        (round-4 VERDICT #2; DensityScan.scala:31-59 +
        AggregatingScan.scala:80-102)."""
        with obs_span("lean.density", grid=f"{width}x{height}",
                      generations=len(self.generations)):
            return self._density_scan(boxes, t_lo_ms, t_hi_ms, env,
                                      width, height, max_ranges)

    def _density_scan(self, boxes, t_lo_ms, t_hi_ms, env,
                      width: int, height: int,
                      max_ranges: int) -> np.ndarray:
        grid = np.zeros((height, width), np.float64)
        if self._n_rows == 0:
            return grid
        # whole-extent fast path: a covering box + the full time extent
        # needs no seeks at all — sweep every generation's z column
        lo_c, hi_c = self._clamp_time(t_lo_ms, t_hi_ms)
        bxs0 = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
        covers = any(b[0] <= -180.0 and b[1] <= -90.0
                     and b[2] >= 180.0 and b[3] >= 90.0 for b in bxs0)
        if (covers and lo_c == self.t_min_ms and hi_c == self.t_max_ms):
            return self._density_sweep(env, width, height)
        planned = self._plan_one(boxes, t_lo_ms, t_hi_ms, max_ranges)
        if planned is None:
            return grid
        ra, bxs, lo, hi = planned
        rb = jnp.asarray(ra["rbin"])
        rlo = jnp.asarray(ra["rzlo"])
        rhi = jnp.asarray(ra["rzhi"])
        boxes_j = jnp.asarray(bxs)
        env_t = tuple(float(v) for v in env)
        env_j = jnp.asarray(np.asarray(env_t))
        # normalized-cell bounds for the decoded (keys/host) tiers:
        # cell-granular comparisons are exact for whole-extent scans and
        # cell-inclusive otherwise (see _lean_density_keys)
        b_lo, o_lo = to_binned_time(np.int64(max(0, lo)), self.period)
        b_hi, o_hi = to_binned_time(np.int64(max(0, hi)), self.period)
        tb = np.array([int(b_lo),
                       self.sfc.time.normalize_scalar(float(o_lo)),
                       int(b_hi),
                       self.sfc.time.normalize_scalar(float(o_hi))],
                      np.int64)
        ixy = np.stack([np.array(
            [self.sfc.lon.normalize_scalar(b[0]),
             self.sfc.lat.normalize_scalar(b[1]),
             self.sfc.lon.normalize_scalar(b[2]),
             self.sfc.lat.normalize_scalar(b[3])], np.int32)
            for b in bxs])
        live = self.generations[-1] if self.generations else None
        full_gens = [g for g in self.generations if g.tier == "full"]
        keys_gens = [g for g in self.generations if g.tier == "keys"]
        host_gens = [g for g in self.generations if g.tier == "host"]
        # sealed-generation partial cache: a demoted (keys/host)
        # generation's contribution to this exact (boxes, window, env,
        # grid) spec is IMMUTABLE — sum its cached grid and scan only
        # the rest.  Full-tier generations always re-scan: their fused
        # payload mask is value-exact at window edges and the cache
        # must not replace that with anything looser; the cached
        # keys/host partials are byte-identical to what their tier's
        # scan produces (cell-granular contract), so a warm call
        # returns exactly the cold call's grid.
        spec = ("scan", tuple(map(tuple, bxs.tolist())), int(lo),
                int(hi), env_t, width, height, int(max_ranges))
        cache = self._density_spec_cache(spec)
        # heat touches (obs/heat): density reads every generation —
        # match counts are unattributable (grids, not rows), so every
        # touch is a full-weight access; cache hits read zero bytes
        _ht: list | None = [] if heat_enabled() else None
        if _ht is not None:
            _ht += [(g.gen_id, "full", int(g.n),
                     int(g.n) * FULL_BYTES, None) for g in full_gens]
        keys_scan: list = []
        for g in keys_gens:
            part = cache.get(g.gen_id) if g is not live else None
            if part is None:
                keys_scan.append(g)
            else:
                obs_count(LEAN_DENSITY_CACHE_HITS)
                grid += part
            if _ht is not None:
                _ht.append((g.gen_id, g.tier, int(g.n),
                            0 if part is not None
                            else int(g.n) * KEYS_BYTES, None))
        dev_gens = full_gens + keys_scan
        totals = np.empty(0)
        if dev_gens:
            padded = self._pad_bucket(dev_gens)
            count_cols: list = []
            for gen in padded:
                cols = (self._sentinel_cols("keys")
                        if gen is None else (gen.bins, gen.z))
                count_cols += [cols[0], cols[1]]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="probe",
                             runs=len(dev_gens)) as d:
                res = _lean_count_multi(rb, rlo, rhi, *count_cols)
                d.dispatched()
                totals = np.asarray(res)

        def _tier_groups(gens, tier_totals):
            cap = gather_capacity(int(tier_totals.max()),
                                  minimum=self.DEFAULT_CAPACITY)
            padded = self._pad_bucket(gens)
            if len(padded) * cap <= self.BATCH_SCAN_BUDGET:
                return [padded], [cap]
            return ([[g] for g, t in zip(gens, tier_totals) if int(t)],
                    [gather_capacity(int(t),
                                     minimum=self.DEFAULT_CAPACITY)
                     for t in tier_totals if int(t)])

        if full_gens and int(totals[:len(full_gens)].sum()):
            groups, caps = _tier_groups(full_gens,
                                        totals[:len(full_gens)])
            for group, cap in zip(groups, caps):
                cols: list = []
                for gen in group:
                    cols += list(self._sentinel_cols("full")
                                 if gen is None else
                                 (gen.bins, gen.z, gen.pos, gen.x,
                                  gen.y, gen.t, jnp.int32(gen.base)))
                self.dispatch_count += 1
                with device_span("query.scan.device", tier="full",
                                 runs=len(group)) as d:
                    res = _lean_density_full(
                        self.sfc, rb, rlo, rhi, boxes_j, jnp.int64(lo),
                        jnp.int64(hi), env_j, *cols, capacity=cap,
                        width=width, height=height)
                    d.dispatched()
                    grid += np.asarray(res, np.float64)
        if keys_scan:
            t_keys = totals[len(full_gens):len(dev_gens)]
            # zero-candidate generations contribute a zero grid — still
            # a cacheable (immutable) partial, computed for free
            parts = {id(g): np.zeros((height, width), np.float64)
                     for g in keys_scan}
            if int(t_keys.sum()):
                groups, caps = _tier_groups(keys_scan, t_keys)
                for group, cap in zip(groups, caps):
                    cols = []
                    for gen in group:
                        base = (self._sentinel_cols("keys")
                                if gen is None else (gen.bins, gen.z))
                        cols += [base[0], base[1]]
                    self.dispatch_count += 1
                    with device_span("query.scan.device", tier="keys",
                                     runs=len(group)) as d:
                        res = _lean_density_keys(
                            self.sfc, rb, rlo, rhi, jnp.asarray(ixy),
                            jnp.asarray(tb), env_j, *cols, capacity=cap,
                            width=width, height=height)
                        d.dispatched()
                        stacked = np.asarray(res, np.float64)
                    for i, gen in enumerate(group):
                        if gen is not None:
                            parts[id(gen)] = stacked[i]
            for g in keys_scan:
                part = parts[id(g)]
                grid += part
                if g is not live:
                    obs_count(LEAN_DENSITY_CACHE_MISSES)
                    self._cache_partial(cache, g.gen_id, part)
        # host tier: ONE stacked vectorized pass attributes hits to
        # their owning runs (flat in run count — the HostStack
        # discipline), yielding a cacheable per-generation partial
        # each; a fully-warm call touches no run at all
        if host_gens:
            if any(g.gen_id not in cache for g in host_gens):
                if self._host_stack is None:
                    self._host_stack = HostStack(
                        [g.run for g in host_gens])
                parts = self._host_stack.density_partials(
                    ra["rbin"], ra["rzlo"], ra["rzhi"], self.sfc, ixy,
                    tb, env_t, width, height)
                for g, part in zip(host_gens, parts):
                    # already-cached runs were recomputed by the
                    # stacked pass anyway — count neither a hit (no
                    # work was saved) nor a miss (nothing new cached)
                    if g.gen_id not in cache:
                        obs_count(LEAN_DENSITY_CACHE_MISSES)
                        self._cache_partial(cache, g.gen_id, part)
                    grid += part
                if _ht is not None:
                    _ht += [(g.gen_id, "host", int(g.n),
                             int(g.n) * KEYS_BYTES, None)
                            for g in host_gens]
            else:
                for g in host_gens:
                    obs_count(LEAN_DENSITY_CACHE_HITS)
                    grid += cache[g.gen_id]
                if _ht is not None:
                    _ht += [(g.gen_id, "host", int(g.n), 0, None)
                            for g in host_gens]
        if _ht:
            record_index_scan(self, _ht)
        return grid

    def _density_sweep(self, env, width: int, height: int) -> np.ndarray:
        """Whole-extent grid: one sweep dispatch per UNCACHED generation
        bucket (device) + one numpy pass per uncached host run.  Every
        SEALED generation's sweep partial caches under the grid spec —
        a whole-extent sweep is z-only and time-independent, so the
        partial survives even the generation's own later demotions
        (full → keys → host never changes its z rows); warm repeats
        re-sweep only the live generation."""
        env_t = tuple(float(v) for v in env)
        world = (env_t == _WORLD_ENV
                 and width & (width - 1) == 0
                 and height & (height - 1) == 0)
        env_j = jnp.asarray(np.asarray(env_t))
        grid = np.zeros((height, width), np.float64)
        live = self.generations[-1] if self.generations else None
        spec = ("sweep", env_t, width, height)
        cache = self._density_spec_cache(spec)
        # pyramid serving (ISSUE 18): a sealed generation whose built
        # pyramid carries this exact (world, pow2, square) resolution
        # contributes its cached level grid — bit-identical to what
        # sweeping it produces (docs/density.md), no keys touched.
        # Generations without a pyramid sweep as before: build-behind
        # never blocks or changes results
        pyr_ok = world and width == height
        dev = [g for g in self.generations if g.tier != "host"]
        scan: list = []
        for g in dev:
            part = None
            if g is not live:
                if pyr_ok:
                    part = self._pyramid_level(g.gen_id, width)
                    if part is not None:
                        obs_count(PYRAMID_SERVE_HITS)
                        grid += part
                        continue
                part = cache.get(g.gen_id)
            else:
                # the live partial is immutable FOR A GIVEN ROW COUNT
                # (the store is append-only: existing rows never
                # change), so a repeat sweep with no interleaved
                # appends is served without any dispatch — the
                # interactive-tile warm path.  Any append bumps g.n
                # and misses
                part = cache.get(("live", g.gen_id, int(g.n)))
            if part is None:
                scan.append(g)
            else:
                obs_count(LEAN_DENSITY_CACHE_HITS)
                grid += part
        for s in range(0, len(scan), _GEN_BUCKET * 2):
            chunk = scan[s:s + _GEN_BUCKET * 2]
            group = self._pad_bucket(chunk)
            zs = [(self._sentinel_cols("keys")[1] if g is None
                   else g.z) for g in group]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="sweep",
                             runs=len(chunk)) as d:
                res = _lean_density_sweep(
                    self.sfc, env_j, *zs, width=width, height=height,
                    world=world)
                d.dispatched()
                stacked = np.asarray(res, np.float64)
            for i, g in enumerate(chunk):
                part = stacked[i]
                grid += part
                if g is not live:
                    obs_count(LEAN_DENSITY_CACHE_MISSES)
                    self._cache_partial(cache, g.gen_id, part)
                else:
                    for k in [k for k in cache
                              if isinstance(k, tuple) and k[0] == "live"
                              and k[1] == g.gen_id]:
                        cache.pop(k)   # superseded row counts
                    self._cache_partial(
                        cache, ("live", g.gen_id, int(g.n)), part)
        scanned = {id(g) for g in scan}
        for g in self.generations:
            if g.tier != "host":
                continue
            if pyr_ok:
                lvl = self._pyramid_level(g.gen_id, width)
                if lvl is not None:
                    obs_count(PYRAMID_SERVE_HITS)
                    grid += lvl
                    continue
            part = cache.get(g.gen_id)
            if part is None:
                obs_count(LEAN_DENSITY_CACHE_MISSES)
                scanned.add(id(g))
                part = g.run.sweep_partial(self.sfc, env_t, width,
                                           height, world)
                self._cache_partial(cache, g.gen_id, part)
            else:
                obs_count(LEAN_DENSITY_CACHE_HITS)
            grid += part
        if heat_enabled() and self.generations:
            record_index_scan(self, [
                (g.gen_id, g.tier, int(g.n),
                 int(g.n) * KEYS_BYTES if id(g) in scanned else 0,
                 None)
                for g in self.generations])
        return grid

    def build_pyramids(self, base: int | None = None,
                       levels: int | None = None) -> int:
        """Build the density pyramid of every sealed generation that
        lacks one (ISSUE 18): one whole-world sweep per generation at
        the pow2 ``base`` resolution (device generations through the
        jitted sweep + 2×2 reduction ladder, spilled host runs through
        their numpy twins), cached under the shared PartialCache
        policy.  Idempotent build-behind: already-built generations
        are skipped, an interrupted build leaves every result exact
        (unbuilt generations simply keep sweeping), and the next call
        resumes with the missing ones.  Returns pyramids built."""
        from ..config import DensityProperties
        from ..ops.density import pyramid_reduce
        from ..resilience import fault_point
        from .pyramid import DensityPyramid, _ladder_depth, pyramid_spec
        base = int(base if base is not None
                   else DensityProperties.PYRAMID_BASE.to_int())
        if base <= 0 or base & (base - 1):
            raise ValueError(
                f"pyramid base must be a power of two, got {base}")
        levels = int(levels if levels is not None
                     else DensityProperties.PYRAMID_LEVELS.to_int())
        depth = _ladder_depth(base, levels)
        cache = self._pyramid_cache.spec_cache(pyramid_spec(base))
        env_j = jnp.asarray(np.asarray(_WORLD_ENV))
        built = 0
        for g in self._sealed():
            if g.gen_id in cache:
                continue
            fault_point("pyramid.build")
            t0 = time.perf_counter()
            with obs_span("pyramid.build", gen_id=g.gen_id,
                          tier=g.tier, base=base):
                if g.tier == "host":
                    pyr = DensityPyramid.from_base(
                        g.run.sweep_partial(self.sfc, _WORLD_ENV,
                                            base, base, True), levels)
                else:
                    group = self._pad_bucket([g])
                    zs = [(self._sentinel_cols("keys")[1] if gg is None
                           else gg.z) for gg in group]
                    self.dispatch_count += 1
                    with device_span("query.scan.device", stage="sweep",
                                     runs=1) as d:
                        stacked = _lean_density_sweep(
                            self.sfc, env_j, *zs, width=base,
                            height=base, world=True)
                        d.dispatched()
                        base_dev = stacked[0]
                        lv = {base: np.asarray(base_dev, np.float64)}
                        if depth:
                            for arr in pyramid_reduce(base_dev, depth):
                                a = np.asarray(arr, np.float64)
                                lv[a.shape[0]] = a
                    pyr = DensityPyramid(lv)
            self._pyramid_cache.add(cache, g.gen_id, pyr)
            obs_count(PYRAMID_BUILDS)
            _metrics.timer(PYRAMID_BUILD_MS).update(
                (time.perf_counter() - t0) * 1e3)
            built += 1
        return built

    def density_tile(self, z: int, x: int, y: int, tile: int = 256,
                     max_ranges: int = 2000) -> np.ndarray:
        """One slippy map tile's density grid (index/pyramid.py):
        pyramid-served while ``tile·2^z`` stays at/below the pyramid
        base, direct bbox density scan beyond."""
        from .pyramid import density_tile as _tile
        return _tile(self, z, x, y, tile, max_ranges)

    def range_count(self, boxes, t_lo_ms, t_hi_ms,
                    max_ranges: int = 2000) -> int:
        """Exact-mask hit count with no candidate materialization (the
        StatsScan Count() push-down): a 1×1 density grid over the
        world."""
        return int(round(self.density(
            boxes, t_lo_ms, t_hi_ms, (-180.0, -90.0, 180.0, 90.0),
            1, 1, max_ranges=max_ranges).sum()))

    def z3_cell_counts(self, bits: int) -> dict:
        """WHOLE-EXTENT Z3Histogram push-down (ISSUE 3): fold every
        generation's sorted keys into coarse ``(time-bin, z-cell)``
        counts — the stat's own cell function applied to the key the
        index already stores, so no payload, no candidates, and an
        exactly-oracle-matching table (the keys were encoded by the
        same curve the stat bins with).  Sealed generations' tables
        cache under ``(bits, bin-span)`` (LRU + byte ceiling;
        compaction invalidates); warm repeats fold only the live
        generation.  Returns ``{(bin, cell): count}``."""
        out: dict = {}
        if self._n_rows == 0 or self.t_min_ms is None:
            return out
        b0, _ = to_binned_time(np.int64(max(0, self.t_min_ms)),
                               self.period)
        b1, _ = to_binned_time(np.int64(max(0, self.t_max_ms)),
                               self.period)
        b0, nb = int(b0), int(b1) - int(b0) + 1
        spec = ("z3cells", int(bits), b0, nb)
        cache = self._sketch_cache.spec_cache(spec)
        live = self.generations[-1] if self.generations else None
        total = np.zeros(nb << bits, np.int64)
        scan: list = []
        for g in self.generations:
            if g.tier == "host":
                continue
            part = cache.get(g.gen_id) if g is not live else None
            if part is None:
                scan.append(g)
            else:
                obs_count(LEAN_SKETCH_CACHE_HITS)
                total += part
        for s in range(0, len(scan), _GEN_BUCKET * 2):
            chunk = scan[s:s + _GEN_BUCKET * 2]
            group = self._pad_bucket(chunk)
            cols: list = []
            for g in group:
                c = (self._sentinel_cols("keys") if g is None
                     else (g.bins, g.z))
                cols += [c[0], c[1]]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="z3_cells",
                             runs=len(chunk)) as d:
                res = _z3_cells_multi(jnp.int64(b0), *cols, bits=int(bits),
                                      nb=nb)
                d.dispatched()
                stacked = np.asarray(res)
            for i, g in enumerate(chunk):
                # copy, not a view: a cached view would pin the WHOLE
                # stacked bucket (padding + live rows) in host RAM and
                # break the cache's byte accounting
                part = np.array(stacked[i])
                total += part
                if g is not live:
                    obs_count(LEAN_SKETCH_CACHE_MISSES)
                    self._sketch_cache.add(cache, g.gen_id, part)
        scanned = {id(g) for g in scan}
        for g in self.generations:
            if g.tier != "host":
                continue
            part = cache.get(g.gen_id)
            if part is None:
                obs_count(LEAN_SKETCH_CACHE_MISSES)
                scanned.add(id(g))
                part = g.run.cell_counts(b0, nb, int(bits))
                self._sketch_cache.add(cache, g.gen_id, part)
            else:
                obs_count(LEAN_SKETCH_CACHE_HITS)
            total += part
        if heat_enabled() and self.generations:
            record_index_scan(self, [
                (g.gen_id, g.tier, int(g.n),
                 int(g.n) * KEYS_BYTES if id(g) in scanned else 0,
                 None)
                for g in self.generations])
        c_per_bin = 1 << bits
        for i in np.flatnonzero(total):
            out[(b0 + int(i) // c_per_bin, int(i) % c_per_bin)] = \
                int(total[i])
        return out

    # -- scan helpers -----------------------------------------------------
    def _probe_totals(self, dev_gens, w_lo, w_hi, rb, rlo, rhi,
                      progress=None) -> np.ndarray:
        """Per-generation candidate totals of the covering ranges.  A
        generation whose time span misses every window ``[w_lo[i],
        w_hi[i]]`` holds no row any window could return (both tiers'
        exact masks test time), so its total is 0 without a seek.  The
        rest follow ``_scan_tier``'s dispatch rule: a bucket or more
        seek in one padded dispatch, fewer one dispatch each, with no
        sentinel; all are enqueued before any is read."""
        totals = np.zeros(len(dev_gens), dtype=np.int64)
        if not dev_gens:
            return totals
        probed = [i for i, g in enumerate(dev_gens) if g.meets(w_lo, w_hi)]
        gens = [dev_gens[i] for i in probed]
        groups = ([self._pad_bucket(gens)] if len(gens) >= _GEN_BUCKET
                  else [[g] for g in gens])
        seeks = sum(len(group) for group in groups)
        _metrics.counter(LEAN_PROBE_GENERATIONS).inc(len(dev_gens))
        _metrics.counter(LEAN_PROBE_SEEKS).inc(seeks)
        if not gens:
            return totals
        if progress is not None:
            progress(f"    probing {len(gens)} of {len(dev_gens)} "
                     "generations")
        self.dispatch_count += len(groups)
        n_dev = int(sum(g.n for g in gens))
        with device_span("query.scan.device", stage="probe", runs=seeks,
                         pruned=len(dev_gens) - len(gens), rows=n_dev,
                         bytes=n_dev * KEYS_BYTES) as d:
            res = []
            for group in groups:
                cols: list = []
                for gen in group:
                    cols += (list(self._sentinel_cols("keys")[:2])
                             if gen is None else [gen.bins, gen.z])
                res.append(_lean_count_multi(rb, rlo, rhi, *cols))
            d.dispatched()
            got = np.concatenate([np.asarray(r) for r in res])
        totals[probed] = got[:len(gens)]
        return totals

    @staticmethod
    def _pad_bucket(gens: list) -> list:
        """Pad a generation list to the compile bucket with ``None``
        (the shared empty sentinel generation: no candidates, but its
        seeks still run — see _make_sentinel_cols)."""
        n_pad = (-len(gens)) % _GEN_BUCKET
        return list(gens) + [None] * n_pad

    @staticmethod
    def _concat_boxes(w_boxes: list):
        """Concatenate per-window boxes with owning qids, padded to
        the window bucket via the shared never-matching-box convention
        (ops/search.pad_boxes — the one definition of box padding)."""
        boxes_c = np.concatenate(w_boxes)
        bqid_c = np.concatenate(
            [np.full(len(b), q, dtype=np.int32)
             for q, b in enumerate(w_boxes)])
        _, boxes_c, bqid_c = pad_boxes(
            boxes_c, boxes_c,
            pad_pow2(len(boxes_c), minimum=_WINDOW_BUCKET), bqid_c)
        return boxes_c, bqid_c

    def _scan_tier(self, gens, totals, rb, rlo, rhi, rq, pos_bits,
                   exact_args, ra=None, degraded_out=None) -> list:
        """Run one tier's scan.  Only generations with CANDIDATES scan
        at all: under time-partitioned ingest a window's bins live in a
        handful of generations, and carrying the other 50 at the shared
        capacity tripled warm queries at 1B (measured; the probe
        already knows the per-generation totals).  A bucket or more of
        live generations runs as one batched dispatch at the capacity
        of the largest total; fewer, or a batched buffer over
        BATCH_SCAN_BUDGET slots, run one dispatch per generation sized
        by its OWN total (each floored at SCAN_MIN_CAPACITY).  Returns
        flat coded arrays (padding stripped).

        Degraded execution (ISSUE 16): with ``ra`` (the HOST range
        dict) and ``degraded_out`` given, a transient device failure
        (RESOURCE_EXHAUSTED) demotes the failed group to the host tier
        and answers it via host-seek CANDIDATES appended to
        ``degraded_out`` — the caller's host recheck keeps the result
        exact.  Generations whose circuit breaker is open skip device
        dispatch the same way.  Poison failures propagate."""
        from ..resilience import breaker, check_cancel, fault_point
        tier = "full" if exact_args is not None else "keys"
        live = [(g, t) for g, t in zip(gens, totals) if int(t)]
        if not live:
            return []
        can_degrade = ra is not None and degraded_out is not None
        if can_degrade:
            tripped = [g for g, _ in live
                       if not breaker.allows((id(self), g.gen_id))]
            if tripped:
                # open circuit: this generation's device dispatch keeps
                # tripping — route it through the host tier until the
                # breaker cools down (no device attempt at all)
                coded = self._degrade_to_host(tripped, ra, pos_bits,
                                              tier, reason="breaker")
                if len(coded):
                    degraded_out.append(coded)
                skip = set(id(g) for g in tripped)
                live = [(g, t) for g, t in live if id(g) not in skip]
                if not live:
                    return []
        gens = [g for g, _ in live]
        totals = np.asarray([t for _, t in live])
        capacity = gather_capacity(int(totals.max()),
                                   minimum=self.SCAN_MIN_CAPACITY)
        padded = self._pad_bucket(gens)
        # fewer live generations than a bucket dispatch one by one: a
        # padded generation costs its seeks and a whole capacity of
        # expand, gathers and masks, and a program per live count would
        # multiply the shapes a warm server has to load
        if (len(gens) >= _GEN_BUCKET
                and len(padded) * capacity <= self.BATCH_SCAN_BUDGET):
            groups = [padded]
            caps = [capacity]
        else:
            groups = [[g] for g in gens]
            caps = [gather_capacity(int(t), minimum=self.SCAN_MIN_CAPACITY)
                    for t in totals]
        parts = []
        row_bytes = FULL_BYTES if tier == "full" else KEYS_BYTES
        cand_of = {id(g): int(t) for g, t in zip(gens, totals)}
        for group, cap in zip(groups, caps):
            # deadline yield point between group dispatches: partial
            # mode stops STARTING groups (scanned ones stay exact)
            if check_cancel("query.scan.device"):
                break
            try:
                fault_point("device.dispatch")
                rows = int(sum(g.n for g in group if g is not None))
                with device_span("query.scan.device", tier=tier,
                                 runs=sum(1 for g in group
                                          if g is not None),
                                 rows=rows, bytes=rows * row_bytes) as d:
                    cols: list = []
                    for gen in group:
                        if gen is None:
                            cols += list(self._sentinel_cols(tier))
                        elif tier == "full":
                            cols += [gen.bins, gen.z, gen.pos, gen.x,
                                     gen.y, gen.t, jnp.int32(gen.base)]
                        else:
                            cols += [gen.bins, gen.z, gen.pos]
                    self.dispatch_count += 1
                    if (tier == "full"
                            and len(group) * cap >= _TWO_PHASE_MIN_SLOTS):
                        # survivors-only transfer: keep the coded buffer
                        # on device, read the hit count, compact (full
                        # tier already masked exactly on device)
                        packed, nhits = _lean_scan_exact_keep(
                            rb, rlo, rhi, rq, *exact_args, *cols,
                            capacity=cap, pos_bits=pos_bits)
                        d.dispatched()
                        k = gather_capacity(max(int(nhits), 1), minimum=8)
                        self.dispatch_count += 1
                        flat = np.asarray(_compact_coded(packed, k=k))
                    else:
                        if tier == "full":
                            packed = _lean_scan_exact_coded(
                                rb, rlo, rhi, rq, *exact_args, *cols,
                                capacity=cap, pos_bits=pos_bits)
                        else:
                            packed = _lean_scan_coded(
                                rb, rlo, rhi, rq, *cols,
                                capacity=cap, pos_bits=pos_bits)
                        d.dispatched()
                        flat = np.asarray(packed).ravel()
            except Exception as e:  # noqa: BLE001 — classified below
                coded = self._dispatch_failed(group, e, ra, pos_bits,
                                              tier, can_degrade)
                if coded is None:
                    raise
                if len(coded):
                    degraded_out.append(coded)
                continue
            for g in group:
                if g is not None:
                    breaker.record_success((id(self), g.gen_id))
            # host-side candidate filtering is NOT device time — it
            # runs after the span so device_ms stays honest
            kept = flat[flat >= 0].astype(np.int64)
            parts.append(kept)
            # the keys tier's hits are counted after the host recheck
            cand = sum(cand_of[id(g)] for g in group if g is not None)
            scan_work(
                d, cand, len(group) * cap,
                len(kept) if tier == "full" else None,
                scan_read_bytes(
                    cand, _POS_BYTES + (PAYLOAD_BYTES if tier == "full"
                                        else 0),
                    int(rb.shape[0]),
                    [self.generation_slots if g is None else g.capacity
                     for g in group],
                    _SEEK_KEY_BYTES))
        return parts

    def _dispatch_failed(self, group, exc, ra, pos_bits, tier,
                         can_degrade):
        """Classify a failed device dispatch.  Transient (memory
        pressure) failures demote the group's generations to the host
        tier and return host-seek candidates — one bounded retry, off
        device, guaranteed not to re-OOM; returns None when the failure
        must propagate (poison input, degradation unavailable, or a
        zero retry budget)."""
        from ..resilience import (breaker, classify_device_failure,
                                  retry_budget)
        if (not can_degrade
                or classify_device_failure(exc) != "transient"):
            return None
        gens = [g for g in group if g is not None]
        for g in gens:
            breaker.record_failure((id(self), g.gen_id))
        if retry_budget() <= 0:
            return None
        obs_count(RESILIENCE_RETRIES)
        return self._degrade_to_host(gens, ra, pos_bits, tier,
                                     reason="transient")

    def _degrade_to_host(self, gens, ra, pos_bits, tier, reason):
        """Demote ``gens`` to the host tier (the PR 4 spill path) and
        answer their share of the scan as host-seek CANDIDATES — the
        caller's payload recheck restores exactness.  Recorded as a
        ``query.scan.degraded`` span with a ``resilience.degraded``
        attr, not a user-facing error."""
        with obs_span("query.scan.degraded", tier=tier, reason=reason,
                      runs=len(gens)) as sp:
            sp.set_attr("resilience.degraded", True)
            obs_count(RESILIENCE_DEGRADED, len(gens))
            for g in gens:
                if g.tier != "host":
                    self._spill(g)
            stack = HostStack([g.run for g in gens])
            return stack.candidates(ra["rbin"], ra["rzlo"], ra["rzhi"],
                                    ra["rqid"], pos_bits)
