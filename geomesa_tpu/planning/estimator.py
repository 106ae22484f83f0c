"""Sketch-fed cardinality estimation: the costing half of closing the
cost-based-planning loop (ISSUE 19, ROADMAP item 4).

The :class:`CardinalityEstimator` answers the `StrategyDecider`'s
selectivity questions from the cached per-generation sketches the lean
indexes already maintain (ISSUE 2's ``RunSketch`` count-min tables and
histograms, and the Z3 cell-count partials), instead of whole-store
stats with magic fallbacks — the ``StatsBasedEstimator`` /
``CostEvaluator`` split of the reference's planning stack, fed by
observed per-generation data:

* **z3** — ``z3_cell_counts(bits)`` gives an exact row count per
  (time-bin, z-prefix cell) over every generation (sealed partials
  cached by the index, live run re-folded).  A query estimate runs the
  SAME covering-range decomposition the scan will run
  (``plan_z3_query``), coarsens the range bounds to cell granularity,
  and sums cell counts with two ``searchsorted`` probes per range — so
  the estimate is of the scan's *candidate superset*, exactly what
  ``plan.estimate.ratio`` audits against;
* **attribute** — ``sketch_scan(SketchFold(...))`` gives one merged
  count-min table (equals / IN via min-over-depth probes hashed
  bit-identically to the fold) and, for numeric attributes with a
  min/max stat, a fixed-bin histogram (ranges via pro-rated bin
  coverage).

Both tiers cache their merged table per **generation signature** —
``tuple((gen_id, rows) per generation)`` — so a warm repeat costs two
numpy probes and zero device dispatches: appends grow the live run's
row count and compaction mints fresh gen_ids, each changing the
signature and invalidating naturally (the LSM-compaction discipline of
the index-side ``PartialCache``).

When a question is out of sketch reach (non-lean store, string ranges,
index not yet built) the decider falls back to the legacy whole-store
stats tier, then to the named heuristic constants
(``geomesa.planning.selectivity.*`` — docs/planning.md).
"""

from __future__ import annotations

import contextlib
import math
import time

import numpy as np

__all__ = ["CardinalityEstimator"]

#: z-prefix bits per cell of the z3 estimation table, adaptive to the
#: data's time-bin span: as fine as the device fold's cell-table
#: budget allows (``nb << bits <= _Z3_CELL_BUDGET``) so nearby-but-
#:  disjoint boxes land in different cells, never finer than the
#: ceiling (~6 bits/dim: ~5.6 deg lon x ~2.8 deg lat) or coarser than
#: the floor
_Z3_CELL_BITS_MIN, _Z3_CELL_BITS_MAX = 10, 18
#: per-dispatch dense cell-table budget of the estimation fold
#: (int64 slots; 4M slots = 32 MB device scratch at the extreme)
_Z3_CELL_BUDGET = 1 << 22
#: covering-range budget for the *estimation* decomposition — host-side
#: numpy recursion, so a fine budget costs ~ms; it must out-resolve the
#: cell table or every range rounds up to whole cells and a sliver box
#: charges for its neighbors' mass (the scan's own default target)
_EST_RANGES = 2048
#: count-min / histogram shape of the estimator's attribute folds
_ATTR_DEPTH, _ATTR_WIDTH, _ATTR_BINS = 4, 2048, 128
#: sketch-sized scan budget clamp: floor keeps boundary-bin splits
#: meaningful, ceiling mirrors index/z3_lean._MAX_RANGES_PER_WINDOW
_MAX_RANGES_FLOOR, _MAX_RANGES_CEIL = 512, 1 << 14

_NUMERIC_HIST_TYPES = frozenset(
    {"int", "integer", "long", "float", "double"})


@contextlib.contextmanager
def _sketch_build(index: str):
    """One (re)build of an estimation table: a ``plan.sketch.build``
    span (mapped to no attribution stage) and the ``plan.sketch.*``
    counter and timer, which the first queries after start-up or after
    a write pay."""
    from ..metrics import (PLAN_SKETCH_BUILD_MS, PLAN_SKETCH_BUILDS,
                           registry)
    from ..obs import span as obs_span
    t0 = time.perf_counter()
    with obs_span("plan.sketch.build", index=index):
        yield
    registry.counter(PLAN_SKETCH_BUILDS).inc()
    registry.timer(PLAN_SKETCH_BUILD_MS).update(
        (time.perf_counter() - t0) * 1e3)


def _gen_signature(idx) -> tuple | None:
    """Cache key over an index's generation set: compaction mints new
    gen_ids and appends grow the live run's row count, so any change
    to the data changes the signature."""
    gens = getattr(idx, "generations", None)
    if gens is None:
        return None
    return tuple(
        (int(g.gen_id), int(getattr(g, "n", None) or
                            getattr(g, "n_slots", 0) or 0))
        for g in gens)


class CardinalityEstimator:
    """Per-schema-store selectivity oracle over the lean indexes'
    cached sketches.  Constructed lazily and cached on the
    ``_SchemaStore`` — one estimator, one set of merged tables, shared
    by every query against the schema."""

    def __init__(self, store):
        self.store = store
        self._z3_cached = None    # (signature, keys, cumsum, idx, bits)
        self._attr_cached: dict = {}  # attr -> (sig, sketch, fold, idx)

    # -- z3 spatiotemporal tier --------------------------------------

    @staticmethod
    def _cell_bits(idx) -> int:
        """Finest cell resolution whose dense fold table fits the
        budget given the data's time-bin span.  Deterministic in the
        index's time extent, which only moves on writes — and writes
        change the generation signature, so a cached table never mixes
        resolutions."""
        from ..curve.binnedtime import to_binned_time
        t0 = np.int64(max(0, idx.t_min_ms or 0))
        t1 = np.int64(max(0, idx.t_max_ms or 0))
        b0, _ = to_binned_time(t0, idx.period)
        b1, _ = to_binned_time(t1, idx.period)
        nb = max(1, int(b1) - int(b0) + 1)
        bits = _Z3_CELL_BITS_MAX
        while bits > _Z3_CELL_BITS_MIN and (nb << bits) > _Z3_CELL_BUDGET:
            bits -= 1
        return bits

    def _z3_table(self):
        idx = self.store._indexes.get("z3")
        if idx is None or not hasattr(idx, "z3_cell_counts"):
            return None
        sig = _gen_signature(idx)
        cached = self._z3_cached
        if cached is not None and cached[0] == sig:
            return cached
        with _sketch_build("z3"):
            bits = self._cell_bits(idx)
            cells = idx.z3_cell_counts(bits)
            cpb = 1 << bits
            flat = np.fromiter((b * cpb + c for b, c in cells),
                               np.int64, len(cells))
            cnt = np.fromiter(cells.values(), np.int64, len(cells))
            order = np.argsort(flat)
            keys = flat[order]
            cum = np.concatenate([np.zeros(1, np.int64),
                                  np.cumsum(cnt[order])])
        cached = (sig, keys, cum, idx, bits)
        self._z3_cached = cached
        return cached

    def z3_rows(self, boxes, intervals) -> int | None:
        """Estimated candidate rows of a z3 scan over ``boxes`` ×
        ``intervals`` (each ``(lo_ms, hi_ms)``, None = open end), or
        None when the sketch tier can't answer (not a lean z3 store,
        index not built yet)."""
        table = self._z3_table()
        if table is None or not len(boxes):
            return None
        _, keys, cum, idx, bits = table
        if not len(keys):
            return 0
        from ..index.z3 import plan_z3_query
        cpb = 1 << bits
        shift = np.int64(63 - bits)
        total = 0
        for lo, hi in intervals:
            lo, hi = idx._clamp_time(lo, hi)
            if lo > hi:
                continue
            plan = plan_z3_query(boxes, int(lo), int(hi), idx.period,
                                 _EST_RANGES, sfc=idx.sfc)
            if not len(plan.rbin):
                continue
            clo = plan.rbin.astype(np.int64) * cpb + (plan.rzlo >> shift)
            chi = plan.rbin.astype(np.int64) * cpb + (plan.rzhi >> shift)
            # coarsening to cells can make adjacent ranges overlap:
            # merge before summing so no cell counts twice
            order = np.argsort(clo, kind="stable")
            clo, chi = clo[order], chi[order]
            keep_hi = np.maximum.accumulate(chi)
            starts = np.r_[True, clo[1:] > keep_hi[:-1] + 1]
            seg = np.cumsum(starts) - 1
            mlo = clo[starts]
            mhi = np.full(len(mlo), np.iinfo(np.int64).min)
            np.maximum.at(mhi, seg, chi)
            li = np.searchsorted(keys, mlo, "left")
            ri = np.searchsorted(keys, mhi, "right")
            total += int((cum[ri] - cum[li]).sum())
        return min(total, int(cum[-1]))

    # -- attribute tier ----------------------------------------------

    def _attr_sketch(self, attr: str):
        idx = self.store._indexes.get(f"attr:{attr}")
        if idx is None or not hasattr(idx, "sketch_scan"):
            return None
        sig = _gen_signature(idx)
        cached = self._attr_cached.get(attr)
        if cached is not None and cached[0] == sig:
            return cached
        with _sketch_build(f"attr:{attr}"):
            fold = self._attr_fold(attr, idx)
            sketch = idx.sketch_scan(fold)
        cached = (sig, sketch, fold, idx)
        self._attr_cached[attr] = cached
        return cached

    def _attr_fold(self, attr: str, idx):
        from ..stats.sketch import SketchFold
        bins, hlo, hhi = 0, 0.0, 1.0
        if getattr(idx, "attr_type", "string") in _NUMERIC_HIST_TYPES:
            mm = self.store.stats_map().get(f"{attr}_minmax")
            try:
                lo = float(mm.min)
                hi = float(mm.max)
            except (AttributeError, TypeError, ValueError):
                lo = hi = 0.0
            if hi > lo:
                bins, hlo, hhi = _ATTR_BINS, lo, hi
        return SketchFold(bins=bins, hlo=hlo, hhi=hhi,
                          depth=_ATTR_DEPTH, width=_ATTR_WIDTH)

    def attr_equals_rows(self, attr: str, values) -> int | None:
        """Estimated rows matching ``attr IN (values)`` from the
        merged count-min table; None when unanswerable."""
        cached = self._attr_sketch(attr)
        if cached is None:
            return None
        _, sketch, fold, idx = cached
        from ..stats.sketch import sketch_equals_count
        total = 0
        for v in values:
            est = sketch_equals_count(sketch, fold, v, idx.attr_type)
            if est is None:
                return None
            total += est
        return total

    def attr_range_rows(self, attr: str, lo, hi) -> int | None:
        """Estimated rows with ``lo <= attr <= hi`` (None bound =
        open) from the merged histogram; None when the fold carries no
        histogram (string attribute, no min/max stat yet)."""
        cached = self._attr_sketch(attr)
        if cached is None:
            return None
        _, sketch, fold, _ = cached
        from ..stats.sketch import sketch_range_count
        return sketch_range_count(sketch, fold, lo, hi)

    # -- scan-budget sizing ------------------------------------------

    @staticmethod
    def size_max_ranges(est_rows: float) -> int:
        """Covering-range budget sized from estimated candidate rows:
        sparse queries keep a coarse cheap decomposition, dense ones
        earn a finer one (less gather over-scan).  Monotone, clamped,
        and deterministic — a warm repeat gets the same budget, so
        padded scan shapes stay stable (zero warm recompiles)."""
        sized = 16.0 * math.sqrt(max(0.0, float(est_rows)) + 1.0)
        return int(min(_MAX_RANGES_CEIL, max(_MAX_RANGES_FLOOR, sized)))
