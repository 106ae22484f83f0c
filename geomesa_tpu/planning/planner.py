"""Query planner: strategy → index scan → residual filter → transforms.

The orchestration layer mirroring the reference's QueryPlanner
(geomesa-index-api/.../index/planning/QueryPlanner.scala:41-134): choose a
strategy (StrategyDecider), run the chosen index's scan to get candidate
positions, apply the full filter as a vectorized re-check (the reference's
secondary-filter / FilterTransformIterator role), then projection, sort
and max-features (configureQuery's hint handling, :157-230).

Exactness contract: whatever the index strategy returns is treated as a
*candidate superset*; the final mask is always the full filter evaluated
on candidates, so results are oracle-equal regardless of strategy.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from ..features.batch import FeatureBatch
from ..features.feature_type import FeatureType
from ..filters.ast import And, Filter, IdFilter, Include, Not, Or, _Include
from ..filters.ecql import parse_ecql
from ..filters.evaluate import evaluate_filter
from .adaptive import ReplanSignal
from .explain import Explainer, ExplainNull
from .strategy import FilterStrategy, StrategyDecider

__all__ = ["Query", "QueryPlanner", "QueryResult"]


@dataclass
class Query:
    """A query against one schema (the GeoTools Query analog)."""

    filter: Filter = Include
    properties: list | None = None       # projection; None = all
    sort_by: str | None = None           # attribute name
    sort_desc: bool = False
    max_features: int | None = None
    crs: str | None = None               # output CRS; None = storage (4326)
    hints: dict = field(default_factory=dict)

    @classmethod
    def of(cls, filter_or_ecql="INCLUDE", **kw) -> "Query":
        f = (parse_ecql(filter_or_ecql)
             if isinstance(filter_or_ecql, str) else filter_or_ecql)
        return cls(filter=f, **kw)


@dataclass
class QueryResult:
    #: materialized hit rows — ``None`` when the caller asked for
    #: positions only (``materialize=False``: the Arrow-native result
    #: path encodes columns straight from the store, ISSUE 14)
    batch: FeatureBatch | None
    positions: np.ndarray
    strategy: FilterStrategy
    plan_time_ms: float
    scan_time_ms: float
    #: this process's rows in final result order — equal to
    #: ``positions`` single-host; under multihost ``positions`` are
    #: global gids and this is the local slice
    local_rows: np.ndarray | None = None
    #: set when a ``timeout_ms`` deadline expired mid-scan and the
    #: caller asked for ``partial_results`` — the rows present are
    #: exact hits over what WAS scanned before the deadline (ISSUE 16)
    timed_out: bool = False


class QueryTimeoutError(TimeoutError):
    """Query exceeded ``geomesa.query.timeout`` (the reference's
    ThreadManagement reaper killing runaway scans,
    index/utils/ThreadManagement.scala + GeoMesaFeatureReader.scala:31)."""


class QueryPlanner:
    """Plans and runs queries against a store's in-memory index set."""

    def __init__(self, sft: FeatureType, store):
        self.sft = sft
        self.store = store  # _SchemaStore (datastore.py)

    def run(self, query: Query, explain: Explainer | None = None,
            allowed: np.ndarray | None = None,
            materialize: bool = True) -> QueryResult:
        """Plan and execute.  ``allowed`` is an optional per-feature bool
        mask (row-level security) applied before sort/limit so that
        ``max_features`` fills from authorized rows only.

        ``materialize=False`` skips the result-batch gather entirely
        (positions/local_rows only — no per-row feature ids, no column
        copies): the Arrow streaming path (ISSUE 14) encodes its
        record batches straight from the store's columns instead."""
        explain = explain or ExplainNull()
        store = self.store
        batch = store.batch
        explain.push(lambda: f"Planning query on '{self.sft.name}' "
                             f"({len(batch)} features)")
        explain(lambda: f"Filter: {query.filter!r}")

        from ..config import QueryProperties
        timeout_s = QueryProperties.QUERY_TIMEOUT.to_int()
        deadline = (time.perf_counter() + timeout_s) if timeout_s else None

        from ..resilience import check_cancel

        def check_deadline(stage: str):
            if deadline is not None and time.perf_counter() > deadline:
                raise QueryTimeoutError(
                    f"query on {self.sft.name!r} exceeded "
                    f"{timeout_s}s during {stage}")
            # the per-query ``timeout_ms`` deadline (ISSUE 16) checks at
            # the same phase boundaries the legacy reaper does: raises
            # are per-process BETWEEN collective phases, the precedent
            # this module already set for multihost safety
            check_cancel(f"planner.{stage}")

        from ..obs import span as obs_span
        from ..utils.profiling import profile
        with profile("query.plan") as plan_span, \
                obs_span("query.plan") as psp:
            # multihost: global count + merged stats — every process
            # must cost strategies identically or the collective
            # dispatches would diverge (deadlock)
            stats = store.stats_map()
            n_plan = (stats["count"].count
                      if getattr(store, "multihost", False) else len(batch))
            lean = getattr(store, "lean", False)
            est_fn = getattr(store, "estimator", None)
            decider = StrategyDecider(
                self.sft, stats, n_plan,
                allowed_indices=getattr(store, "query_indices", None),
                attr_z3_tier=not lean,
                servable_attrs=(set(store._lean_attr_names())
                                if lean else None),
                estimator=(est_fn() if callable(est_fn) else None))
            strategy, options = decider.decide_with_options(
                query.filter, explain,
                forced=query.hints.get("QUERY_INDEX"))
            psp.set_attr("strategy", strategy.index)
            # estimate audit (ISSUE 9): the chosen estimate, which
            # estimator tier produced it (ISSUE 19), and every option's
            # cost land on the plan span, so the cost model the decider
            # used is reconstructable from the trace
            psp.set_attr("plan.estimate.rows", round(float(strategy.cost), 1))
            psp.set_attr("plan.estimate.source", strategy.source)
            if psp.recording and options:
                psp.set_attr("plan.options",
                             {o.index: round(float(o.cost), 1)
                              for o in options})
        plan_ms = plan_span.ms
        check_deadline("planning")

        mh = getattr(store, "multihost", False)
        t1 = time.perf_counter()
        replanned = False
        with profile("query.scan"), \
                obs_span("query.scan", strategy=strategy.index) as ssp:
            try:
                with self._replan_scope_for(strategy, query):
                    candidates = self._scan(strategy, query, explain)
            except ReplanSignal as sig:
                # adaptive mid-query replan (ISSUE 19): the scan's probe
                # observed candidates diverging past the threshold —
                # re-decide with the actual folded in, re-scan ONCE
                strategy, candidates = self._replan(
                    sig, strategy, decider, query, explain)
                replanned = True
                ssp.set_attr("strategy", strategy.index)
            ssp.set_attr("candidates",
                         -1 if candidates is None else int(len(candidates)))
        check_deadline("index scan")
        with obs_span("query.post_filter") as fsp:
            if candidates is None:  # full scan (of this process's rows)
                mask = evaluate_filter(query.filter, batch)
                positions = np.flatnonzero(mask)
            else:
                # multihost: candidates are GLOBAL gids — each process
                # residual-filters only ITS gid-decoded rows, next to the
                # data (the server-side filter role; no global batch exists)
                cand = (store.local_rows_of(candidates) if mh
                        else candidates)
                if len(cand):
                    # lean column stores re-check through an id-free
                    # ChunkView: a full take() would mint O(candidates)
                    # feature-id strings just to throw them away — the
                    # cost class ISSUE 14 removes from the serving path.
                    # Id-predicated filters still need real ids.
                    if (hasattr(batch, "take_view")
                            and not _filter_needs_ids(query.filter)):
                        sub = batch.take_view(cand)
                    else:
                        sub = batch.take(cand)
                    mask = evaluate_filter(query.filter, sub)
                    positions = cand[mask]
                else:
                    positions = np.asarray(cand, dtype=np.int64)
            fsp.set_attr("hits", int(len(positions)))
        scan_ms = (time.perf_counter() - t1) * 1000
        check_deadline("filtering")
        explain(lambda: f"Scan: {len(positions)} hits "
                        f"(plan {plan_ms:.1f}ms, scan {scan_ms:.1f}ms)")
        # estimate-vs-actual close-out (ISSUE 9): actual rows scanned
        # (candidate superset; the whole table on a full scan) and
        # matched, plus the mispredict ratio, land on the enclosing
        # query span and feed the plan.estimate.ratio histogram — the
        # baseline the item-4 sketch-driven planner must beat.  Both
        # sides are process-local (no collective), and under multihost
        # the estimate and the candidate gids are both GLOBAL, so the
        # ratio compares like with like.
        actual_scanned = int(n_plan if candidates is None
                             else len(candidates))
        ratio = (float(strategy.cost) + 1.0) / (actual_scanned + 1.0)
        from ..metrics import PLAN_ESTIMATE_RATIO, registry as _metrics
        _metrics.histogram(PLAN_ESTIMATE_RATIO).update(ratio)
        from ..obs import current_span
        root = current_span()
        if root is not None:
            root.set_attr("plan.estimate.rows",
                          round(float(strategy.cost), 1))
            root.set_attr("plan.estimate.source", strategy.source)
            root.set_attr("plan.actual.scanned", actual_scanned)
            root.set_attr("plan.actual.matched", int(len(positions)))
            root.set_attr("plan.estimate.ratio", round(ratio, 4))
            if replanned:
                root.set_attr("plan.replanned", True)
        explain(lambda: f"Estimate audit: predicted {strategy.cost:.0f} "
                        f"rows ({strategy.source}), scanned "
                        f"{actual_scanned}, matched "
                        f"{len(positions)} (ratio {ratio:.2f}x)")

        if allowed is not None and len(positions):
            positions = positions[allowed[positions]]
        if "SAMPLING" in query.hints and len(positions):
            # 1-in-n result thinning, optionally per attribute group —
            # the reference's SAMPLING/SAMPLE_BY query hints
            # (SamplingIterator + FeatureSampler); multihost thins per
            # process (the reference samples per scan thread the same
            # way, utils/FeatureSampler)
            from ..process.sampling import sample_positions
            n_samp = int(query.hints["SAMPLING"])
            by = query.hints.get("SAMPLE_BY")
            keys = batch.column(by)[positions] if by else None
            positions = sample_positions(positions, n_samp, keys)
            explain(lambda: f"Sampled 1-in-{n_samp}"
                            + (f" per {by}" if by else ""))
        if mh:
            positions, local_rows = self._finalize_multihost(
                positions, batch, query, store)
        else:
            positions = self._sort_limit(positions, batch, query)
            local_rows = positions
        if not materialize:
            return QueryResult(None, positions, strategy, plan_ms,
                               scan_ms, local_rows=local_rows)
        properties = query.properties
        if properties is None and "COLUMN_GROUP" in query.hints:
            group = query.hints["COLUMN_GROUP"]
            groups = self.sft.column_groups
            if group not in groups:
                raise ValueError(f"no column group {group!r} on "
                                 f"{self.sft.name!r}")
            properties = groups[group]
        take_cols = None
        if properties is not None:
            # projection pushes INTO the take: only the projected
            # physical columns are gathered/copied for the hit rows —
            # a sum(score) over millions of hits must not materialize
            # the geometry columns first (_project then just rebinds
            # the schema)
            take_cols = set()
            for p in properties:
                if self.sft.attribute(p).is_geometry:
                    take_cols.update((f"{p}_x", f"{p}_y", f"{p}_bbox"))
                else:
                    take_cols.add(p)
        with obs_span("query.materialize", rows=int(len(local_rows))):
            result_batch = batch.take(local_rows, columns=take_cols)
            if properties is not None:
                result_batch = _project(result_batch, properties)
            if query.crs:
                # result-side reprojection (QueryPlanner.scala:74-81)
                from ..geometry.crs import reproject_batch
                result_batch = reproject_batch(result_batch, query.crs)
                explain(lambda: f"Reprojected to {query.crs}")
        explain.pop()
        return QueryResult(result_batch, positions, strategy, plan_ms,
                           scan_ms, local_rows=local_rows)

    # -- adaptive replanning (ISSUE 19) -----------------------------------
    def _replan_scope_for(self, strategy: FilterStrategy, query: Query):
        """A replan scope around one strategy's scan, or a null context
        when replanning can't help: disabled by config, strategy pinned
        by a QUERY_INDEX hint, no probe on the chosen path ('none' /
        'id' / 'full'), or an or-split (its per-branch probe counts
        can't re-cost the split as a whole)."""
        import contextlib
        if (query.hints.get("QUERY_INDEX") is not None
                or strategy.index in ("none", "id", "full", "or-split")):
            return contextlib.nullcontext()
        from ..config import PlanningProperties
        threshold = float(PlanningProperties.REPLAN_THRESHOLD.get())
        if threshold <= 0.0:
            return contextlib.nullcontext()
        from .adaptive import replan_scope
        return replan_scope(float(strategy.cost), threshold,
                            int(PlanningProperties.REPLAN_MIN_ROWS.get()))

    def _replan(self, sig: ReplanSignal, strategy: FilterStrategy,
                decider: StrategyDecider, query: Query,
                explain: Explainer) -> tuple[FilterStrategy, np.ndarray]:
        """One bounded mid-query replan: the aborted scan's observed
        candidate count replaces the mispredicted strategy's cost and
        the decider re-runs; the re-scan executes OUTSIDE any replan
        scope, so a query replans at most once.  Bit-exactness is
        structural — the probe-point abort happened before any gather
        (nothing collected, nothing lost), and the new strategy's
        candidate superset passes the same residual filter as always.
        Multihost-safe: probe totals are fetched GLOBAL values, so
        every process raises at the same agreed point and re-decides
        identically."""
        from ..metrics import PLAN_REPLANNED, registry as _metrics
        from ..obs import span as obs_span
        with obs_span("query.replan", from_strategy=strategy.index,
                      observed=int(sig.observed),
                      estimate=round(float(sig.estimate), 1)) as rsp:
            _metrics.counter(PLAN_REPLANNED).inc()
            explain(lambda: f"Replanning: {strategy.index} observed "
                            f"{sig.observed} candidates at {sig.point} "
                            f"vs estimate {sig.estimate:.0f}")
            try:
                new, _ = decider.decide_with_options(
                    query.filter, explain,
                    observed={strategy.index: float(sig.observed)})
            except RuntimeError:
                # blocked full-table scan surfaced by the re-decide:
                # finish under the original strategy rather than fail a
                # query that was already admitted and running
                new = strategy
            rsp.set_attr("to_strategy", new.index)
            candidates = self._scan(new, query, explain)
        return new, candidates

    # -- strategy execution ----------------------------------------------
    def _scan(self, strategy: FilterStrategy, query: Query,
              explain: Explainer) -> np.ndarray | None:
        store = self.store
        name = strategy.index
        if name == "none":
            return np.empty(0, dtype=np.int64)
        if name == "or-split":
            explain(lambda: f"OR-split across {len(strategy.branches)} "
                            "indexed branches")
            return self._scan_or_split(strategy, query, explain)
        if name == "full":
            explain("Executing full-table scan")
            return None
        explain(lambda: f"Executing {name} index scan")
        if name == "id":
            # id index is host-local; multihost lifts the per-process
            # rows into the global gid space (encode + allgather); the
            # appended tail joins BEFORE the lift (tail rows are local)
            cand = store.id_index().query(strategy.ids)
            tail = store.index_tail("id")
            if tail is not None and len(tail):
                cand = _union([cand, tail])
            return store.to_global_candidates(cand)
        if name.startswith("attr:"):
            attr = name[5:]
            idx = store.attribute_index(attr)
            (a, kind, payload) = strategy.attr_values[0]
            # covering secondary refinement for the tiers; exactness
            # comes from run()'s residual filter as always
            sec_window = None
            z3_ranges = None
            if strategy.intervals and idx.secondary is not None:
                los = [iv[0] for iv in strategy.intervals]
                his = [iv[1] for iv in strategy.intervals]
                sec_window = (None if any(v is None for v in los) else min(los),
                              None if any(v is None for v in his) else max(his))
            if (idx.sec_z is not None
                    and (strategy.geometries or strategy.intervals)):
                z3_ranges = self._attr_z3_ranges(strategy)
            if kind == "equals":
                cand = idx.query_equals(payload, sec_window, z3_ranges)
            elif kind == "in":
                cand = idx.query_in(payload, sec_window, z3_ranges)
            elif kind == "range":
                lo, hi, lo_inc, hi_inc = payload
                cand = idx.query_range(lo, hi, lo_inc, hi_inc)
            elif kind == "prefix":
                cand = idx.query_prefix(payload)
            else:
                raise ValueError(f"unknown attribute query {kind!r}")
            return self._add_tail(cand, name)
        boxes = [g.envelope.as_tuple() for g in strategy.geometries] or [
            (-180.0, -90.0, 180.0, 90.0)
        ]
        if name == "z3":
            idx = store.z3_index()
            # sketch-sized decomposition budget (ISSUE 19): only ever
            # set by the lean estimator, whose index accepts the kwarg
            mr = ({} if strategy.max_ranges is None
                  else {"max_ranges": int(strategy.max_ranges)})
            if len(strategy.intervals) > 1:
                # auto-batch disjoint time windows into ONE device
                # dispatch (the multi-window BatchScanner pattern —
                # VERDICT r1 weak #4; one dispatch and host sync per
                # query instead of one per window)
                explain(lambda: f"Auto-batched {len(strategy.intervals)} "
                                "time windows into one dispatch")
                parts = idx.query_many(
                    [(boxes, lo, hi) for lo, hi in strategy.intervals],
                    **mr)
                return _union(list(parts))
            parts = [idx.query(boxes, lo, hi, **mr)
                     for lo, hi in strategy.intervals]
            return _union(parts)
        if name == "z2":
            return store.z2_index().query(boxes)
        if name == "xz3":
            idx = store.xz3_index()
            # temporal-only: scan the whole world (a strategy with no
            # geometry used to produce ZERO scan parts and silently
            # empty results — review r5)
            from ..geometry.types import Polygon as _Poly
            geoms_q = strategy.geometries or (
                _Poly([(-180.0, -90.0), (180.0, -90.0),
                       (180.0, 90.0), (-180.0, 90.0)]),)
            parts = []
            for g in geoms_q:
                for lo, hi in strategy.intervals:
                    parts.append(idx.query(g, lo, hi, exact=False))
            return self._add_tail(_union(parts), "xz3")
        if name == "xz2":
            idx = store.xz2_index()
            parts = [idx.query(g, exact=False) for g in strategy.geometries or ()]
            return self._add_tail(_union(parts), "xz2")
        raise ValueError(f"unknown strategy {name!r}")

    def _add_tail(self, cand: np.ndarray, key: str) -> np.ndarray:
        """Union rows appended after a kept index's build into its
        candidate set (write-path incremental maintenance: kept indexes
        serve their covered rows; the tail rides as unconditional
        candidates and the residual filter keeps results exact).
        Multihost: tails are per-process local rows; the presence
        decision is AGREED so every process enters the lift
        collective."""
        store = self.store
        tail = store.index_tail(key) if hasattr(store, "index_tail") \
            else None
        n_tail = 0 if tail is None else len(tail)
        if getattr(store, "multihost", False):
            from ..parallel.multihost import agreed_int
            if agreed_int(n_tail, "max") == 0:
                return cand
            tail = (tail if tail is not None
                    else np.empty(0, dtype=np.int64))
            return _union([cand, store.to_global_candidates(tail)])
        if n_tail == 0:
            return cand
        return _union([cand, tail])

    def _scan_or_split(self, strategy: FilterStrategy, query: Query,
                       explain: Explainer) -> np.ndarray | None:
        """Execute an OR-split, auto-batching its z3/z2 branches into
        single multi-window device dispatches (FilterSplitter's
        disjunction rewrite served the BatchScanner way,
        planning/FilterSplitter.scala:294-307 — VERDICT r1 item 8).
        Branches on other indexes scan individually as before; the
        planner's full-OR residual re-check keeps the union exact."""
        store = self.store
        world = (-180.0, -90.0, 180.0, 90.0)
        z3_windows: list = []
        z2_sets: list = []
        rest: list = []
        for _, st in strategy.branches:
            bx = [g.envelope.as_tuple() for g in st.geometries] or [world]
            if st.index == "z3" and st.intervals:
                z3_windows.extend((bx, lo, hi) for lo, hi in st.intervals)
            elif st.index == "z2":
                z2_sets.append(bx)
            else:
                rest.append(st)
        parts = []
        if len(z3_windows) > 1:
            explain(lambda: f"Auto-batched {len(z3_windows)} z3 windows "
                            "into one dispatch")
            parts.extend(store.z3_index().query_many(z3_windows))
        elif z3_windows:
            bx, lo, hi = z3_windows[0]
            parts.append(store.z3_index().query(bx, lo, hi))
        if len(z2_sets) > 1:
            explain(lambda: f"Auto-batched {len(z2_sets)} z2 box sets "
                            "into one dispatch")
            parts.extend(store.z2_index().query_many(z2_sets))
        elif z2_sets:
            parts.append(store.z2_index().query(z2_sets[0]))
        for st in rest:
            cand = self._scan(st, query, explain)
            if cand is None:
                # a full-scan branch inside a split would silently lose
                # its rows from the union — degrade the whole split to
                # one full scan instead
                return None
            parts.append(cand)
        parts = [p for p in parts if len(p)]
        # candidates are per-branch supersets; run()'s single full-OR
        # re-check makes the final hit set exact
        return _union(parts) if parts else np.empty(0, dtype=np.int64)

    def _attr_z3_ranges(self, strategy: FilterStrategy):
        """Covering (bin, zlo, zhi) plan for the attribute index's z3
        tier; open time bounds clamp to the data's extent (the same
        clamping the primary z3 index applies)."""
        from ..index.z3 import plan_z3_query

        # data extent from the maintained MinMax stat (O(1)); fall back
        # to one column scan only when stats are absent
        mm = self.store.stats_map().get("dtg_minmax")
        if mm is not None and not mm.is_empty:
            data_lo, data_hi = int(mm.min), int(mm.max)
        else:
            dtg = self.store.batch.column(self.sft.dtg_field)
            if len(dtg) == 0:
                return None
            data_lo, data_hi = int(dtg.min()), int(dtg.max())
        lo, hi = data_lo, data_hi
        if strategy.intervals:
            los = [iv[0] for iv in strategy.intervals]
            his = [iv[1] for iv in strategy.intervals]
            if not any(v is None for v in los):
                lo = max(lo, min(los))
            if not any(v is None for v in his):
                hi = min(hi, max(his))
        boxes = ([g.envelope.as_tuple() for g in strategy.geometries]
                 or [(-180.0, -90.0, 180.0, 90.0)])
        plan = plan_z3_query(boxes, lo, hi, self.sft.z3_interval,
                             max_ranges=256)
        if plan.num_ranges == 0:
            return None
        return plan.rbin, plan.rzlo, plan.rzhi

    def _finalize_multihost(self, local: np.ndarray, batch: FeatureBatch,
                            query: Query, store):
        """Assemble the GLOBAL result gid list from per-process survivor
        rows (hits-bounded allgather — the client-merge Reducer role),
        applying sort/limit with global semantics.  Returns
        ``(global_gids, local_rows_in_global_order)``; each process's
        result batch is its own slice of the global order."""
        import jax

        from ..parallel.multihost import allgather_concat, allgather_strings
        from ..parallel.scan import decode_gids

        local = np.asarray(local, dtype=np.int64)
        gids = np.asarray(store.gids_of(local), dtype=np.int64)
        if query.sort_by:
            keys = batch.column(query.sort_by)[local]
            if keys.dtype == object:
                # match _sort_limit's object contract: (is_none, value)
                # ascending — numeric comparables gather as floats (str
                # would order 10 before 9), everything else as strings
                none = np.array([k is None for k in keys], dtype=bool)
                vals = [k for k in keys if k is not None]
                # agreed across processes: numeric only if EVERY
                # process's keys are numeric (divergent dtypes would
                # mismatch the gather collectives)
                import numbers

                from ..parallel.multihost import agreed_int
                numeric = bool(agreed_int(
                    int(all(isinstance(v, numbers.Real) for v in vals)),
                    "min"))
                ints = numeric and bool(agreed_int(
                    int(all(isinstance(v, numbers.Integral)
                            and -(2 ** 62) < int(v) < 2 ** 62
                            for v in vals)),
                    "min"))
                if ints:
                    # exact int64 gather: float64 would collapse values
                    # past 2^53 (e.g. nanosecond epochs), breaking order
                    # parity with _sort_limit's exact comparisons
                    safe = np.array([0 if k is None else int(k)
                                     for k in keys], dtype=np.int64)
                    all_keys = allgather_concat(safe)
                elif numeric:
                    safe = np.array([0.0 if k is None else float(k)
                                     for k in keys])
                    all_keys = allgather_concat(safe)
                else:
                    all_keys = allgather_strings(np.array(
                        ["" if k is None else str(k) for k in keys],
                        dtype=object))
                all_none = allgather_concat(none)
            else:
                all_keys = allgather_concat(keys)
                all_none = np.zeros(len(all_keys), dtype=bool)
            all_gids = allgather_concat(gids)
            # stable (is_none, value) ascending sort, then a FULL
            # reverse for descending — exactly _sort_limit's order[::-1]
            # (which puts Nones first on descending sorts)
            order = np.lexsort((np.arange(len(all_keys)),
                                all_keys, all_none))
            if query.sort_desc:
                order = order[::-1]
            positions = all_gids[order]
        else:
            positions = np.sort(allgather_concat(gids))
        if query.max_features is not None:
            positions = positions[: query.max_features]
        procs, rows = decode_gids(positions)
        return positions, rows[procs == jax.process_index()]

    def _sort_limit(self, positions: np.ndarray, batch: FeatureBatch,
                    query: Query) -> np.ndarray:
        if query.sort_by:
            keys = batch.column(query.sort_by)[positions]
            if keys.dtype == object:
                # object columns may mix None (masked/sparse values) with
                # comparables: sort Nones last, stably
                order = np.asarray(sorted(
                    range(len(keys)),
                    key=lambda i: (keys[i] is None, keys[i]
                                   if keys[i] is not None else 0)),
                    dtype=np.int64)
            else:
                order = np.argsort(keys, kind="stable")
            if query.sort_desc:
                order = order[::-1]
            positions = positions[order]
        if query.max_features is not None:
            positions = positions[: query.max_features]
        return positions


def _filter_needs_ids(f: Filter) -> bool:
    """Does any node of the filter read feature ids?  (IdFilter is the
    one evaluate_filter branch touching ``batch.ids`` — id-free filters
    may re-check over an id-less ChunkView.)"""
    if isinstance(f, IdFilter):
        return True
    if isinstance(f, (And, Or)):
        return any(_filter_needs_ids(p) for p in f.filters)
    if isinstance(f, Not):
        return _filter_needs_ids(f.filter)
    return False


def _union(parts: list[np.ndarray]) -> np.ndarray:
    parts = [p for p in parts if len(p)]
    if not parts:
        return np.empty(0, dtype=np.int64)
    return np.unique(np.concatenate(parts))


def _project(batch: FeatureBatch, properties: list) -> FeatureBatch:
    """Column projection (the reference's transform schemas,
    QueryPlanner.setQueryTransforms)."""
    keep: dict = {}
    for p in properties:
        attr = batch.sft.attribute(p)
        if attr.is_geometry:
            for suffix in ("_x", "_y", "_bbox"):
                if f"{p}{suffix}" in batch.columns:
                    keep[f"{p}{suffix}"] = batch.columns[f"{p}{suffix}"]
        else:
            keep[p] = batch.columns[p]
    sub_attrs = tuple(a for a in batch.sft.attributes if a.name in properties)
    sub_sft = FeatureType(batch.sft.name, sub_attrs,
                          batch.sft.default_geom if batch.sft.default_geom in properties else None,
                          batch.sft.user_data)
    return FeatureBatch(sub_sft, keep, batch.ids,
                        batch.geoms if sub_sft.default_geom else None)
