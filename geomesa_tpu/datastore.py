"""TpuDataStore: the user-facing store facade.

The analog of the reference's GeoMesaDataStore / MetadataBackedDataStore
(geomesa-index-api/.../index/geotools/GeoMesaDataStore.scala:48-431;
createSchema at MetadataBackedDataStore.scala:121): schema lifecycle,
ingest, query, stats and explain — but over device/host-resident columnar
storage instead of a distributed KV store.

Index maintenance model: writes append to the schema's column store and
mark indexes dirty; indexes (device sort for Z2/Z3, host sorts for
XZ/attr/id) rebuild lazily on the next query.  This is the bulk-ingest
pattern the reference optimizes for (BatchWriter + periodic compaction),
without the KV store's per-row write amplification.  Stats are observed on
write (the reference's StatsCombiner role) and serialized to the metadata
catalog (metadata/GeoMesaMetadata.scala analog, JSON on disk).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import time
from contextlib import contextmanager

import numpy as np

from .features.batch import FeatureBatch
from .features.feature_type import FeatureType, parse_spec
from .filters.ast import Filter
from .index.attribute import AttributeIndex
from .index.id import IdIndex
from .index.xz2 import XZ2Index
from .index.xz3 import XZ3Index
from .index.z2 import Z2PointIndex
from .index.z3 import Z3PointIndex
from .planning.explain import Explainer
from .planning.planner import Query, QueryPlanner, QueryResult
from .stats.stat import (
    CountStat, EnumerationStat, Histogram, MinMax, Stat, TopK, stat_from_json,
)

__all__ = ["TpuDataStore", "CatalogVersionError", "CURRENT_INDEX_VERSIONS"]

#: on-disk catalog format version; bumped on incompatible layout changes
#: (v2 added per-index layout versions; v1 catalogs read as all-current;
#: v3 changed the Frequency sketch's string hashing — pre-v3 persisted
#: frequency tables are dropped on load and rebuild on the next
#: stats_analyze rather than silently answering from the wrong buckets)
CATALOG_VERSION = 3

#: current per-index key-layout versions (the reference's Z3IndexV7-style
#: version registry, index/api/GeoMesaFeatureIndexFactory); v1 of z3/z2
#: is the legacy semi-normalized curve (curve/legacy.py)
def _current_index_versions() -> dict:
    from .index.z2 import Z2_INDEX_VERSION
    from .index.z3 import Z3_INDEX_VERSION
    return {"z3": Z3_INDEX_VERSION, "z2": Z2_INDEX_VERSION,
            "xz2": 1, "xz3": 1, "attr": 1, "id": 1}


CURRENT_INDEX_VERSIONS = _current_index_versions()


def _parse_index_versions(user_data: dict) -> dict:
    """Per-schema overrides from user data: ``geomesa.index.versions =
    "z3:1,z2:1"`` pins listed indexes to old layouts (data imported from
    a system that wrote legacy keys)."""
    versions = dict(CURRENT_INDEX_VERSIONS)
    raw = (user_data or {}).get("geomesa.index.versions", "")
    if raw and raw != "current":
        for part in raw.split(","):
            name, _, v = part.strip().partition(":")
            if name not in versions:
                raise ValueError(f"unknown index {name!r} in "
                                 "geomesa.index.versions")
            versions[name] = int(v)
    return versions


class CatalogVersionError(RuntimeError):
    """Catalog written by a NEWER framework version (the client/server
    version-mismatch handshake, GeoMesaDataStore.scala:433-500: refuse to
    run rather than corrupt data written by a newer layout)."""


def _max_numeric_id(ids: np.ndarray) -> int:
    """Largest plain-integer feature id in ``ids`` (−1 when none).

    Explicit numeric ids must advance the auto-id counter, or later
    auto-generated ids would collide with them.  isdecimal, not isdigit:
    unicode digit characters like '²' pass isdigit but fail int parsing."""
    s = np.asarray(ids).astype(str)
    if not len(s):
        return -1
    mask = np.char.isdecimal(s) & (np.char.str_len(s) <= 18)
    if not mask.any():
        return -1
    return int(s[mask].astype(np.int64).max())



class _SchemaStore:
    """Per-schema storage: the column batch + lazily-built indexes + stats.

    With ``mesh`` set, every index builds its SHARDED variant over the
    device mesh (geomesa_tpu.parallel), so the same store facade scales
    from one chip to a pod unchanged — the reference's defining
    laptop-to-cluster property (GeoMesaDataStore.scala:48-431 +
    ShardStrategy.scala:17-75 applied uniformly)."""

    def __init__(self, sft: FeatureType, mesh=None, multihost: bool = False):
        self.sft = sft
        self.mesh = mesh
        #: multihost mode: this process holds only ITS rows in ``batch``;
        #: indexes build via the build_multihost variants (gids code
        #: process << GID_PROC_SHIFT | local_row), every store operation
        #: is a collective all processes enter together (SPMD), and
        #: residual filtering runs per process on gid-decoded local
        #: candidates — no process ever materializes the full dataset
        #: (GeoMesaDataStore.scala:48 data-lives-on-the-cluster property)
        self.multihost = bool(multihost and mesh is not None)
        #: bumped on every mutation; versions the merged-stats cache
        self._mutation_version = 0
        self._merged_stats: tuple[int, dict] | None = None
        #: per-index key-layout versions (versioned indices: reads of
        #: old catalogs keep their recorded layout; see migrate_schema)
        self.index_versions: dict = _parse_index_versions(sft.user_data)
        self.batch: FeatureBatch | None = None
        #: lean profile (``geomesa.index.profile=lean`` user data, or
        #: auto-enabled by a first write past the row threshold): chunked
        #: columnar storage (features/lean.LeanBatch), implicit feature
        #: ids, deletes as tombstones, and the tiered LeanZ3Index as the
        #: only spatial index — the "tens of billions of points through
        #: one DataStore" regime (introduction.rst:24,
        #: GeoMesaDataStore.scala:48) on a single chip's terms
        self.lean = ((sft.user_data or {}).get(
            "geomesa.index.profile") == "lean")
        #: deleted-row mask (lean profile: rows are never removed, ids
        #: never reused — the delete path of IndexAdapter writers
        #: re-expressed as a mask the planner applies to every result)
        self.tombstone: np.ndarray | None = None
        self.visibilities: np.ndarray | None = None  # per-feature vis strings
        #: attr name → per-feature vis strings (attribute-level visibility,
        #: the reference's KryoVisibilityRowEncoder / vis-level=attribute)
        self.attr_visibilities: dict[str, np.ndarray] = {}
        self._vis_masks: dict = {}
        self._dirty = True
        self._indexes: dict = {}
        #: generation-lifecycle hook the owning datastore parks here
        #: BEFORE the lean scale index exists (ISSUE 18): attached to
        #: the index's generation_listeners once its (re)build streams,
        #: it triggers the build-behind pyramid job on seal
        self.pyramid_trigger = None
        #: rows covered by each cached index (indexes kept across
        #: writes serve [0, coverage) from their structure and the
        #: appended TAIL [coverage, n) as unconditional candidates)
        self._index_coverage: dict[str, int] = {}
        #: per-index-type build counter (observability + the
        #: no-full-rebuild regression tests)
        self.build_counts: dict[str, int] = {}
        self._stats: dict[str, Stat] = {}
        #: monotonic auto feature-id counter — never decremented on
        #: delete, so ids are never reused (the reference's generators
        #: never recycle ids, utils/uuid/Z3FeatureIdGenerator.scala)
        self.next_fid: int = 0
        #: lazily-built id set for O(m) explicit-id collision checks
        #: (built on the first explicit-id write, maintained after)
        self._id_set: set | None = None
        #: monotonic stats-artifact generation counter: persisted in
        #: ``__meta__`` and preferred over mtime for source arbitration,
        #: which cross-host clock/mtime-granularity skew can mis-order
        #: on shared catalog dirs (round-4 ADVICE)
        self.stats_generation: int = 0
        #: lazily-built sketch-fed cardinality estimator (ISSUE 19);
        #: one per store — it caches merged sketch tables per
        #: generation signature internally
        self._estimator = None
        self._init_stats()
        if self.lean:
            self._init_lean()

    # -- lean profile ------------------------------------------------------
    #: share of the lean HBM budget given to the attribute indexes
    #: (split evenly among them); the z3 scale index keeps the rest
    LEAN_ATTR_BUDGET_FRACTION = 0.25

    #: default opportunistic LSM compaction factor for lean indexes:
    #: merge when ≥ F sealed same-tier same-size-class runs accumulate
    #: (``geomesa.lean.compaction.factor`` user data overrides; 0
    #: disables the opportunistic trigger — explicit compact() still
    #: works).  Conservative enough that small stores never trigger it;
    #: a 60-generation 1B streamed build ends at O(log) runs.
    LEAN_COMPACTION_FACTOR = 8

    #: which generational scale index a lean schema rides ("z3" for
    #: points+dtg, "xz2" for non-point geometries); set by _init_lean
    lean_kind = "z3"

    @property
    def query_indices(self) -> set | None:
        """Indices the planner may choose for this schema (None = all
        registered): the lean profile serves z3 (the scale index), id
        (implicit-id decode), and — round-5 — the generational
        lexicoded attribute index for indexed attributes, restoring
        cost-based attr-vs-z3 selection at scale (round-4 VERDICT #1;
        AttributeFilterStrategy.scala)."""
        if not self.lean:
            return None
        out = {self.lean_kind, "id"}
        if self._lean_attr_names():
            out.add("attr")
        return out

    def _lean_attr_names(self) -> list[str]:
        """Indexed attributes the lean attribute index serves (the
        lexicode covers numerics, dates, strings — the reference's
        indexable-type set, AttributeIndexKey.scala:38-52)."""
        from .index.attr_lean import _NUMERIC_TYPES
        sft = self.sft
        return [a.name for a in sft.attributes
                if a.indexed and not a.is_geometry
                and a.name != sft.dtg_field
                and a.type in _NUMERIC_TYPES | {"string"}]

    def _init_lean(self) -> None:
        sft = self.sft
        if sft.is_points and sft.geom_field and sft.dtg_field:
            #: which generational scale index serves this schema
            self.lean_kind = "z3"
        elif sft.geom_field and not sft.is_points:
            # round-5 (VERDICT #4): non-point schemas ride the
            # generational XZ tier — XZ3 (bin, code) when the schema
            # has time, XZ2 otherwise
            self.lean_kind = "xz3" if sft.dtg_field else "xz2"
        else:
            raise ValueError(
                "geomesa.index.profile=lean requires a point geometry "
                "plus a dtg attribute (z3 scale index) or a non-point "
                "geometry (xz2 scale index)")
        from .features.lean import LeanBatch
        prefix = ""
        if self.multihost:
            import jax
            if jax.process_count() > 1:
                prefix = f"p{jax.process_index()}."
        self.lean = True
        self.batch = LeanBatch(sft, id_prefix=prefix)
        self._dirty = False

    _STATS_EXECUTOR = None

    @classmethod
    def _stats_executor(cls):
        """Shared single worker for overlapped stats observes (one per
        process: observes are joined within each write call, so a
        single thread never queues more than one task)."""
        if cls._STATS_EXECUTOR is None:
            from concurrent.futures import ThreadPoolExecutor
            cls._STATS_EXECUTOR = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="lean-stats")
        return cls._STATS_EXECUTOR

    def _lean_payload(self):
        """(x, y, t) for the lean index's exact re-check — the store's
        own finalized columns (ONE host copy, shared by reference)."""
        x, y = self.batch.geom_xy()
        t = np.asarray(self.batch.column(self.sft.dtg_field), np.int64)
        return x, y, t

    def _lean_index(self):
        """The live lean scale index (LeanZ3Index for point schemas,
        LeanXZ2Index for non-point — round-4 VERDICT #4) — maintained
        incrementally by writes; (re)built here by streaming the column
        store in bounded slices only after a layout migration or
        reload."""
        kind = self.lean_kind
        idx = self._indexes.get(kind)
        if idx is not None:
            return idx
        n = len(self.batch)
        step = 1 << 22
        n_steps = -(-n // step)
        if self.multihost:
            # multihost: stream in an AGREED number of equal steps —
            # per-process row counts differ and each append is a
            # collective (trailing steps feed empty slices)
            from .parallel.multihost import agreed_int
            n_steps = agreed_int(n_steps, "max")
        if kind == "xz2":
            if self.mesh is not None:
                from .parallel.attr_lean import ShardedLeanXZ2Index
                idx = ShardedLeanXZ2Index(
                    mesh=self.mesh, multihost=self.multihost,
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            else:
                from .index.xz2_lean import LeanXZ2Index
                idx = LeanXZ2Index(
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            if n_steps:
                bb = self.batch.geom_bbox()
                for i in range(n_steps):
                    lo = i * step
                    idx.append_bboxes(bb[lo:lo + step], base_gid=lo)
        elif kind == "xz3":
            if self.mesh is not None:
                from .parallel.attr_lean import ShardedLeanXZ3Index
                idx = ShardedLeanXZ3Index(
                    period=self.sft.z3_interval, mesh=self.mesh,
                    multihost=self.multihost,
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            else:
                from .index.xz2_lean import LeanXZ3Index
                idx = LeanXZ3Index(
                    period=self.sft.z3_interval,
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            if n_steps:
                bb = self.batch.geom_bbox()
                t = self.batch.column(self.sft.dtg_field)
                for i in range(n_steps):
                    lo = i * step
                    idx.append_bboxes(bb[lo:lo + step],
                                      np.asarray(t[lo:lo + step],
                                                 np.int64),
                                      base_gid=lo)
        else:
            if self.mesh is not None:
                from .parallel.lean import ShardedLeanZ3Index
                idx = ShardedLeanZ3Index(
                    period=self.sft.z3_interval, mesh=self.mesh,
                    version=self.index_versions["z3"],
                    multihost=self.multihost,
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            else:
                from .index.z3_lean import LeanZ3Index
                idx = LeanZ3Index(
                    period=self.sft.z3_interval,
                    version=self.index_versions["z3"],
                    generation_slots=self._lean_generation_slots(),
                    hbm_budget_bytes=self._lean_z3_budget(),
                    compaction_factor=self._lean_compaction_factor())
            idx.payload_provider = self._lean_payload
            if n_steps:
                x, y = self.batch.geom_xy()
                t = self.batch.column(self.sft.dtg_field)
                for i in range(n_steps):
                    lo = i * step
                    idx.append(x[lo:lo + step], y[lo:lo + step],
                               t[lo:lo + step])
        # access-temperature attribution scope (obs/heat): the index's
        # touches record under this schema + registry key
        idx.heat_scope = (self.sft.name, kind)
        # build-behind pyramid trigger (ISSUE 18) — registered only
        # AFTER the (re)build streamed, so seals during the initial
        # stream never recurse into the builder
        if (self.pyramid_trigger is not None
                and hasattr(idx, "build_pyramids")):
            idx.generation_listeners.append(self.pyramid_trigger)
        self._indexes[kind] = idx
        self._index_coverage[kind] = n
        self.build_counts[kind] = self.build_counts.get(kind, 0) + 1
        return idx

    def _lean_budget(self) -> int:
        """Total lean HBM budget (``geomesa.lean.hbm.budget`` user data,
        bytes; default the z3 index's class default)."""
        from .index.z3_lean import LeanZ3Index
        ud = self.sft.user_data or {}
        raw = ud.get("geomesa.lean.hbm.budget")
        return int(raw) if raw else LeanZ3Index.HBM_BUDGET_BYTES

    def _lean_compaction_factor(self) -> int:
        """Opportunistic compaction factor for the lean indexes
        (``geomesa.lean.compaction.factor`` user data; 0 disables)."""
        ud = self.sft.user_data or {}
        raw = ud.get("geomesa.lean.compaction.factor")
        return (int(raw) if raw is not None
                else self.LEAN_COMPACTION_FACTOR)

    def _lean_generation_slots(self) -> int | None:
        """Per-generation slot override
        (``geomesa.lean.generation.slots`` user data; None = the index
        class default).  Small values force the many-generation LSM
        regime at test scale."""
        ud = self.sft.user_data or {}
        raw = ud.get("geomesa.lean.generation.slots")
        return int(raw) if raw is not None else None

    def compact_lean(self, budget_ms: float | None = None) -> dict:
        """Explicit LSM maintenance over every LIVE lean index (scale
        index + attribute indexes): fold sealed same-tier runs until
        done or past ``budget_ms`` (the remaining budget carries across
        indexes; each index still makes ≥ 1 group of progress when
        eligible, so repeated calls always converge).  The role the
        reference delegates to Accumulo/HBase periodic major
        compaction."""
        import time
        out: dict = {}
        if not self.lean:
            return out
        t0 = time.perf_counter()

        def remaining():
            if budget_ms is None:
                return None
            return max(0.0, budget_ms - (time.perf_counter() - t0) * 1e3)

        for key in [self.lean_kind] + [f"attr:{a}"
                                       for a in self._lean_attr_names()]:
            idx = self._indexes.get(key)
            if idx is not None and hasattr(idx, "compact"):
                out[key] = idx.compact(budget_ms=remaining())
        return out

    def build_pyramids(self) -> int:
        """Build density pyramids over the lean scale index's sealed
        generations (ISSUE 18); returns the number built.  Schemas
        whose scale index has no pyramid support (xz2/xz3, full-fat)
        build nothing."""
        if not self.lean or self.batch is None:
            return 0
        idx = self._lean_index()
        if not hasattr(idx, "build_pyramids"):
            return 0
        return idx.build_pyramids()

    def _lean_z3_budget(self) -> int:
        """The z3 index's share: the full lean budget minus the
        attribute carve-out — applied on mesh too (per-shard budgets
        must sum within one chip's HBM; review r5)."""
        if not self._lean_attr_names():
            return self._lean_budget()
        return int(self._lean_budget()
                   * (1.0 - self.LEAN_ATTR_BUDGET_FRACTION))

    def _lean_attr_index(self, attr: str):
        """The live LeanAttrIndex for one indexed attribute — maintained
        incrementally by writes; (re)built by streaming the column store
        after a reload (round-4 VERDICT #1)."""
        names = self._lean_attr_names()
        if attr not in names:
            raise ValueError(
                f"attribute {attr!r} is not lean-indexable on "
                f"{self.sft.name!r} (indexed numerics/dates/strings "
                f"only; have: {names})")
        key = f"attr:{attr}"
        idx = self._indexes.get(key)
        if idx is None:
            a = self.sft.attribute(attr)
            if self.mesh is not None:
                from .parallel.attr_lean import ShardedLeanAttrIndex
                budget = max(
                    ShardedLeanAttrIndex.GENERATION_SLOTS * 24 * 2,
                    int(self._lean_budget()
                        * self.LEAN_ATTR_BUDGET_FRACTION
                        // max(1, len(names))))
                idx = ShardedLeanAttrIndex(
                    attr, a.type, mesh=self.mesh,
                    multihost=self.multihost, hbm_budget_bytes=budget,
                    generation_slots=self._lean_generation_slots(),
                    compaction_factor=self._lean_compaction_factor())
            else:
                from .index.attr_lean import LeanAttrIndex
                budget = max(
                    LeanAttrIndex.GENERATION_SLOTS * 20 * 2,
                    int(self._lean_budget()
                        * self.LEAN_ATTR_BUDGET_FRACTION
                        // max(1, len(names))))
                idx = LeanAttrIndex(
                    attr, a.type, hbm_budget_bytes=budget,
                    generation_slots=self._lean_generation_slots(),
                    compaction_factor=self._lean_compaction_factor())
            n = len(self.batch)
            step = 1 << 22
            n_steps = -(-n // step)
            if self.multihost:
                from .parallel.multihost import agreed_int
                n_steps = agreed_int(n_steps, "max")
            if n_steps:
                col = self.batch.column(attr)
                dtg = (self.batch.column(self.sft.dtg_field)
                       if self.sft.dtg_field
                       else np.zeros(n, np.int64))
                for i in range(n_steps):
                    lo = i * step
                    idx.append(col[lo:lo + step],
                               np.asarray(dtg[lo:lo + step], np.int64),
                               base_gid=lo)
            idx.heat_scope = (self.sft.name, key)
            self._indexes[key] = idx
            self._index_coverage[key] = n
            self.build_counts[key] = self.build_counts.get(key, 0) + 1
        return idx

    def _lean_write(self, chunk, visibility: str = "") -> None:
        """Streaming ingest: observe stats on the chunk, append its
        columns by reference, and push its keys into the live index —
        O(chunk) per write (a FeatureBatch.concat store is O(n²) over a
        streamed build)."""
        n_new = len(chunk)
        prior = len(self.batch)
        if visibility or self.visibilities is not None:
            # visibility labels materialize only once someone uses them
            # (an object-array per row is real memory at lean scale)
            if self.visibilities is None:
                self.visibilities = np.full(prior, "", dtype=object)
            self.visibilities = np.concatenate(
                [self.visibilities,
                 np.full(n_new, visibility, dtype=object)])
        from .config import ObsProperties
        from .obs import current_span, device_span, span as obs_span
        from .stats.stat import observe_shared
        # stats observe runs on a worker thread OVERLAPPING the index
        # appends' host work (pad/encode/device_put below — numpy
        # releases the GIL); joined before this call returns, so no
        # concurrent state ever escapes _lean_write (round-4 VERDICT
        # weak #3: observe-on-write dominated the facade ingest tax)
        observe_fut = self._stats_executor().submit(
            observe_shared, self._stats, chunk)
        try:
            self._mutation_version += 1
            self._vis_masks = {}
            # index BEFORE the batch grows: _lean_index streams the
            # batch's CURRENT rows when (re)building, so appending the
            # chunk first would double-index it
            idx = self._lean_index()
            attr_idx = [(a, self._lean_attr_index(a))
                        for a in self._lean_attr_names()]
            self.batch.append_batch(chunk)
            if self.tombstone is not None:
                self.tombstone = np.concatenate(
                    [self.tombstone, np.zeros(n_new, dtype=bool)])
            with obs_span("write.index", index=self.lean_kind,
                          rows=n_new):
                if self.lean_kind in ("xz2", "xz3"):
                    dtg = (np.asarray(chunk.column(self.sft.dtg_field),
                                      np.int64)
                           if self.sft.dtg_field else
                           np.zeros(n_new, np.int64))
                    if self.lean_kind == "xz3":
                        idx.append_bboxes(chunk.geoms.bbox, dtg,
                                          base_gid=prior)
                    else:
                        idx.append_bboxes(chunk.geoms.bbox,
                                          base_gid=prior)
                else:
                    x, y = chunk.geom_xy(self.sft.geom_field)
                    dtg = np.asarray(chunk.column(self.sft.dtg_field),
                                     np.int64)
                    idx.append(np.asarray(x, np.float64),
                               np.asarray(y, np.float64), dtg)
            self._index_coverage[self.lean_kind] = len(self.batch)
            for a, ai in attr_idx:
                with obs_span("write.index", index=f"attr:{a}",
                              rows=n_new):
                    ai.append(chunk.column(a), dtg, base_gid=prior)
                self._index_coverage[f"attr:{a}"] = len(self.batch)
            if (current_span() is not None
                    and ObsProperties.WRITE_BLOCK.to_bool()
                    and hasattr(idx, "block")):
                # device attribution for TRACED writes: appends are
                # async by design, so block on the live run here and
                # record honest block-until-ready ms (the scan-span
                # discipline) — only when a recording trace asked for
                # it, so untraced ingest stays fully pipelined
                with device_span("write.device",
                                 index=self.lean_kind):
                    idx.block()
        finally:
            # joined on EVERY path: stats are consistent before any
            # caller (or exception handler) can read them
            with obs_span("write.observe", rows=n_new):
                observe_fut.result()

    def _lean_observe_masked(self, proto, mask: np.ndarray | None):
        """Fold the (masked) rows into a fresh copy of ``proto`` in
        bounded slices — never materializing the full row set (the
        chunked re-observe for restricted callers / post-delete stats)."""
        fresh = proto.fresh_copy() if hasattr(proto, "fresh_copy") else proto
        n = len(self.batch)
        step = 1 << 22
        for lo in range(0, n, step):
            hi = min(lo + step, n)
            view = self.batch.slice_view(lo, hi)
            if mask is not None:
                sub = mask[lo:hi]
                if not sub.all():
                    if not sub.any():
                        continue
                    view = view.take(np.flatnonzero(sub))
            fresh.observe(view)
        return fresh

    def _lean_recompute_stats(self) -> None:
        """Chunked recompute over the LIVE rows (deletes tombstone rows
        but sketches are not invertible — the same re-observe contract
        as recompute_stats, sliced to bound host memory)."""
        self._stats = {}
        self._init_stats()
        n = len(self.batch)
        if not n:
            return
        live = None if self.tombstone is None else ~self.tombstone
        from .stats.stat import Histogram
        for a in self.sft.attributes:
            if (a.indexed and a.type in ("int", "long", "float", "double")
                    and a.name in self.batch.columns):
                col = self.batch.column(a.name)
                if len(col) and col.dtype != object:
                    sel = col if live is None else col[live]
                    if len(sel):
                        lo, hi = float(sel.min()), float(sel.max())
                        if hi > lo:
                            self._stats[f"{a.name}_histogram"] = \
                                Histogram(a.name, 32, lo, hi)
        for key, s in list(self._stats.items()):
            self._stats[key] = self._lean_observe_masked(s, live)

    def _init_stats(self):
        sft = self.sft
        self._stats["count"] = CountStat()
        if sft.dtg_field:
            self._stats["dtg_minmax"] = MinMax(sft.dtg_field)
        if sft.geom_field:
            # the spatial selectivity denominator (StatsBasedEstimator's
            # geometry MinMax analog): query boxes fraction against the
            # DATA extent, not the world
            from .stats.stat import BBoxStat
            self._stats[f"{sft.geom_field}_bbox"] = BBoxStat(
                sft.geom_field)
        for a in sft.attributes:
            if a.is_geometry or a.name == sft.dtg_field:
                continue
            if a.type in ("int", "long", "float", "double"):
                self._stats[f"{a.name}_minmax"] = MinMax(a.name)
            elif a.type == "string" and a.indexed:
                self._stats[f"{a.name}_topk"] = TopK(a.name)
                self._stats[f"{a.name}_enumeration"] = EnumerationStat(a.name)

    def write(self, batch: FeatureBatch, visibility: str = "",
              attribute_visibilities: dict | None = None):
        if self.lean:
            if attribute_visibilities:
                raise ValueError(
                    "attribute-level visibility is not supported on "
                    "lean-profile schemas (row visibility is)")
            self._lean_write(batch, visibility)
            return
        vis = np.full(len(batch), visibility, dtype=object)
        prior = 0 if self.batch is None else len(self.batch)
        if self.batch is None:
            self.batch = batch
            self.visibilities = vis
        else:
            if self.visibilities is None:  # pre-visibility data (e.g. reload)
                self.visibilities = np.full(len(self.batch), "", dtype=object)
            self.batch = self.batch.concat(batch)
            self.visibilities = np.concatenate([self.visibilities, vis])
        # per-attribute labels: pad other attrs/rows with "" (visible)
        touched = set(self.attr_visibilities) | set(
            attribute_visibilities or ())
        for attr in touched:
            col = self.attr_visibilities.get(
                attr, np.full(prior, "", dtype=object))
            label = (attribute_visibilities or {}).get(attr, "")
            col = np.concatenate(
                [col, np.full(len(batch), label, dtype=object)])
            self.attr_visibilities[attr] = col
        for s in self._stats.values():
            s.observe(batch)
        if self._id_set is not None:
            self._id_set.update(batch.ids.astype(str).tolist())
        self._mutation_version += 1
        self._vis_masks: dict = {}
        # Incremental index maintenance (IndexAdapter.IndexWriter.write
        # role, api/IndexAdapter.scala:95-106): z3 and z2 APPEND the new
        # rows into their resident sorted columns (one gather pass, no
        # full re-sort); xz/attr/id indexes are KEPT — their structure
        # serves the rows they cover and queries add the appended TAIL
        # as unconditional candidates (residual filtering keeps results
        # exact), compacting lazily when the tail grows (datastore.
        # index() accessors).  Indexes cached across a prior unprocessed
        # mutation (dirty) are stale and dropped wholesale.
        if self._dirty:
            self._indexes.clear()
            self._index_coverage.clear()
        z3 = self._indexes.get("z3")
        z2 = self._indexes.get("z2")
        # the cached attr-z3-tier keys cover only pre-append rows; a
        # fresh attribute build must recompute them
        self._indexes.pop("attr-z3-keys", None)
        self._dev_xy = None
        self._dirty = False
        n_now = len(self.batch)
        from .obs import span as obs_span
        if z3 is not None:
            if self.sft.is_points and self.sft.geom_field and self.sft.dtg_field:
                x, y = batch.geom_xy(self.sft.geom_field)
                with obs_span("write.index", index="z3",
                              rows=len(batch)):
                    self._indexes["z3"] = z3.append(
                        x, y, batch.column(self.sft.dtg_field))
                self._index_coverage["z3"] = n_now
            else:
                self._indexes.pop("z3", None)
                self._index_coverage.pop("z3", None)
        if z2 is not None:
            if self.sft.is_points and self.sft.geom_field and hasattr(
                    z2, "append"):
                x, y = batch.geom_xy(self.sft.geom_field)
                with obs_span("write.index", index="z2",
                              rows=len(batch)):
                    self._indexes["z2"] = z2.append(x, y)
                self._index_coverage["z2"] = n_now
            else:
                self._indexes.pop("z2", None)
                self._index_coverage.pop("z2", None)

    #: tail fraction that triggers a compacting rebuild of a kept index
    TAIL_COMPACT_FRACTION = 8  # tail > coverage/8 (12.5%)

    def _maybe_compact(self, key: str) -> None:
        """Drop a kept index whose appended tail outgrew the lazy-scan
        budget — the next accessor call rebuilds over all rows (the
        compaction role of the reference's periodic major compaction)."""
        cov = self._index_coverage.get(key)
        if cov is None or key not in self._indexes or self.batch is None:
            return
        tail = len(self.batch) - cov
        over = tail > max(4096, cov // self.TAIL_COMPACT_FRACTION)
        if self.multihost:
            # AGREED: any process over threshold → all compact together
            # (a one-sided rebuild would enter its collectives alone)
            from .parallel.multihost import agreed_int
            over = bool(agreed_int(int(over), "max"))
        if over:
            del self._indexes[key]
            del self._index_coverage[key]
            if key.startswith("attr:"):
                self._indexes.pop("attr-z3-keys", None)

    def index_tail(self, key: str) -> np.ndarray | None:
        """Rows appended after the cached index's build — queries union
        them into the candidate set (they are not in the index's
        structure; the residual filter keeps exactness)."""
        cov = self._index_coverage.get(key)
        if cov is None or self.batch is None:
            return None
        n = len(self.batch)
        return np.arange(cov, n, dtype=np.int64) if n > cov else None

    def masked_batch(self, auths):
        """Batch with attribute-guarded values nulled for these auths —
        used for FILTERING as well as results, so a restricted caller
        cannot probe guarded values via CQL predicates.  Cached per auth
        set; unguarded columns share the original arrays."""
        if not self.attr_visibilities or self.batch is None:
            return self.batch
        key = ("attrs", frozenset(auths))
        cache = self._vis_masks
        if key not in cache:
            # bound the per-auth-set masked copies (many distinct tenants
            # on a read-mostly store would otherwise grow without limit)
            masked_keys = [k for k in cache
                           if isinstance(k, tuple) and k[0] == "attrs"]
            if len(masked_keys) >= 16:
                cache.pop(masked_keys[0], None)
            from .security import visibility_mask
            cols = dict(self.batch.columns)
            changed = False
            for attr, labels in self.attr_visibilities.items():
                if attr not in cols:
                    continue
                mask = visibility_mask(labels, frozenset(auths))
                if mask.all():
                    continue
                col = cols[attr]
                col = col.astype(object) if col.dtype != object else col.copy()
                col[~mask] = None
                cols[attr] = col
                changed = True
            cache[key] = (FeatureBatch(
                self.batch.sft, cols, self.batch.ids, self.batch.geoms)
                if changed else self.batch)
        return cache[key]

    def vis_mask(self, auths) -> np.ndarray | None:
        """Cached per-auth-set visibility mask over all features; None when
        every label is empty (everything visible)."""
        if self.visibilities is None:
            return None
        key = frozenset(auths)
        cache = getattr(self, "_vis_masks", None)
        if cache is None:
            cache = self._vis_masks = {}
        if key not in cache:
            row_keys = [k for k in cache if isinstance(k, frozenset)]
            if len(row_keys) >= 64:  # bound per-auth-set masks (tenants)
                cache.pop(row_keys[0], None)
            from .security import visibility_mask
            mask = visibility_mask(self.visibilities, key)
            cache[key] = None if mask.all() else mask
        return cache[key]

    def estimator(self):
        """Sketch-fed cardinality estimator for the planner (ISSUE 19).

        Lean stores only: the estimator reads the generational indexes'
        run sketches (z3 cell-counts, attr histograms/count-min), which
        exist only in the lean profile.  Full-fat stores return None and
        the decider falls back to whole-store stats, then heuristics.
        Small stores return None too (``estimator.min.rows``): the cold
        per-generation sketch folds cannot amortize on a store a whole
        scan finishes in milliseconds, so sketch costing only switches
        on at the scale where misplanning actually hurts.
        Multihost: sketch tables derive from globally-fetched index
        state, so every process computes the same estimates."""
        if not self.lean:
            return None
        from .config import PlanningProperties
        rows = len(self.batch) if self.batch is not None else 0
        if rows < PlanningProperties.ESTIMATOR_MIN_ROWS.to_int():
            return None
        if self._estimator is None:
            from .planning.estimator import CardinalityEstimator
            self._estimator = CardinalityEstimator(self)
        return self._estimator

    def stats_map(self) -> dict:
        """Planning/stat sketches.  Multihost: the per-process sketches
        merge through the Stat monoid into one GLOBAL view (cached per
        mutation) — cost-based strategy decisions must be identical on
        every process or collective dispatch would diverge."""
        if not self.multihost:
            return self._stats
        import jax
        if jax.process_count() == 1:
            return self._stats
        if (self._merged_stats is not None
                and self._merged_stats[0] == self._mutation_version):
            return self._merged_stats[1]
        from .parallel.multihost import allgather_strings
        payload = json.dumps({k: s.to_json()
                              for k, s in self._stats.items()})
        merged: dict[str, Stat] = {}
        for blob in allgather_strings(np.array([payload], dtype=object)):
            for k, sj in json.loads(blob).items():
                st = stat_from_json(sj)
                merged[k] = st if k not in merged else merged[k] + st
        self._merged_stats = (self._mutation_version, merged)
        return merged

    # -- multihost row identity -------------------------------------------
    def local_rows_of(self, gids: np.ndarray) -> np.ndarray:
        """Rows of THIS process among global candidate gids (multihost:
        decode ``process << GID_PROC_SHIFT | local_row``; single
        controller: identity)."""
        if not self.multihost:
            return gids
        import jax
        from .parallel.scan import decode_gids
        procs, rows = decode_gids(gids)
        return rows[procs == jax.process_index()]

    def gids_of(self, rows: np.ndarray) -> np.ndarray:
        """Global gids of this process's rows (inverse of
        local_rows_of)."""
        if not self.multihost:
            return rows
        from .parallel.scan import encode_gids
        return encode_gids(rows)

    def to_global_candidates(self, rows: np.ndarray) -> np.ndarray:
        """Lift host-index results (id index: per-process local rows)
        into the global candidate space: encode + allgather.  Identity
        for single-controller stores."""
        if not self.multihost:
            return rows
        from .parallel.multihost import allgather_concat
        return np.sort(allgather_concat(self.gids_of(rows)))

    def find_id_clash(self, ids) -> str | None:
        """First id in ``ids`` that already exists in this store's rows
        (lazy incrementally-maintained id set — O(ids), not O(store))."""
        if self.batch is None or not len(self.batch):
            return None
        if self._id_set is None:
            self._id_set = set(self.batch.ids.astype(str).tolist())
        return next((i for i in ids if i in self._id_set), None)

    def merge_stat_global(self, s: Stat) -> Stat:
        """Merge one per-process stat through the monoid across all
        processes (used for restricted-caller re-observations, which are
        computed over local rows)."""
        import jax
        if not self.multihost or jax.process_count() == 1:
            return s
        from .parallel.multihost import allgather_strings
        merged = None
        for blob in allgather_strings(
                np.array([json.dumps(s.to_json())], dtype=object)):
            st = stat_from_json(json.loads(blob))
            merged = st if merged is None else merged + st
        return merged

    def recompute_stats(self) -> None:
        """Rebuild every sketch from the current rows (sketches are not
        invertible, so deletes/reloads re-observe).  With data present,
        numeric attributes additionally get range histograms (the
        StatsRunner/stats-analyze products the cost estimator consumes,
        stats/StatsBasedEstimator spirit) — bounds come from the data, so
        these only exist after an analyze/recompute pass."""
        if self.lean:
            self._lean_recompute_stats()
            return
        self._stats = {}
        self._init_stats()
        if self.batch is not None and len(self.batch):
            from .stats.stat import Histogram
            for a in self.sft.attributes:
                if (a.indexed
                        and a.type in ("int", "long", "float", "double")
                        and a.name in self.batch.columns):
                    col = self.batch.column(a.name)
                    if len(col) and col.dtype != object:
                        lo, hi = float(col.min()), float(col.max())
                        if hi > lo:
                            self._stats[f"{a.name}_histogram"] = Histogram(
                                a.name, 32, lo, hi)
            for s in self._stats.values():
                s.observe(self.batch)

    def _rebuild_if_dirty(self):
        if self._dirty:
            self._indexes.clear()
            self._index_coverage.clear()
            self._dev_xy = None
            self._dirty = False

    def device_xy(self):
        """The point columns uploaded once and shared by the z2 AND z3
        builders (two separate uploads would double HBM + transfer).
        After incremental appends the live z3 index already holds the
        coordinates on device, so slice those (device-side copy) rather
        than paying a full host→device re-upload."""
        if getattr(self, "_dev_xy", None) is None:
            import jax.numpy as jnp
            z3 = self._indexes.get("z3")
            if z3 is not None and len(z3) == len(self.batch):
                n = len(z3)
                self._dev_xy = (z3.x[:n], z3.y[:n])
            else:
                x, y = self.batch.geom_xy()
                self._dev_xy = (jnp.asarray(np.asarray(x, np.float64)),
                                jnp.asarray(np.asarray(y, np.float64)))
        return self._dev_xy

    # -- lazily-built indexes (via the pluggable registry) ----------------
    def index(self, name: str):
        """Generic registry-backed index accessor (the reference's
        GeoMesaFeatureIndexFactory lookup): builds lazily, honors the
        schema's enabled-index restriction and applicability."""
        from .index.registry import get_index
        if self.lean:
            self._rebuild_if_dirty()
            if name == self.lean_kind:
                return self._lean_index()
            if name == "id":
                from .index.id import LeanIdIndex
                return LeanIdIndex(len(self.batch),
                                   prefix=self.batch.id_prefix)
            raise ValueError(
                f"index {name!r} is not available on lean-profile "
                f"schema {self.sft.name!r} ({self.lean_kind}/id only)")
        self._rebuild_if_dirty()
        self._maybe_compact(name)
        if name not in self._indexes:
            desc = get_index(name)
            enabled = self.sft.enabled_indices
            if enabled is not None and name not in enabled:
                raise ValueError(
                    f"index {name!r} is disabled on schema "
                    f"{self.sft.name!r} (geomesa.indices.enabled)")
            if not desc.applicable(self.sft):
                raise ValueError(f"schema {self.sft.name!r} does not "
                                 f"support the {name!r} index")
            if self.mesh is not None and desc.build_sharded is not None:
                self._indexes[name] = desc.build_sharded(self, self.mesh)
            else:
                self._indexes[name] = desc.build(self)
            self._index_coverage[name] = len(self.batch)
            self.build_counts[name] = self.build_counts.get(name, 0) + 1
        return self._indexes[name]

    def z3_index(self) -> Z3PointIndex:
        return self.index("z3")

    def z2_index(self) -> Z2PointIndex:
        return self.index("z2")

    def xz3_index(self) -> XZ3Index:
        return self.index("xz3")

    def xz2_index(self) -> XZ2Index:
        return self.index("xz2")

    def id_index(self) -> IdIndex:
        return self.index("id")

    # registry build callbacks (each returns a fresh index; caching and
    # mesh dispatch live in index())
    def _build_z3(self):
        x, y = self.batch.geom_xy()
        dtg = self.batch.column(self.sft.dtg_field)
        if self.mesh is not None:
            from .parallel.scan import ShardedZ3Index
            builder = (ShardedZ3Index.build_multihost if self.multihost
                       else ShardedZ3Index.build)
            return builder(
                np.asarray(x), np.asarray(y), dtg,
                period=self.sft.z3_interval, mesh=self.mesh,
                version=self.index_versions["z3"])
        xd, yd = self.device_xy()
        return Z3PointIndex.build(
            x, y, dtg, period=self.sft.z3_interval, xd=xd, yd=yd,
            version=self.index_versions["z3"])

    def _build_z2(self):
        x, y = self.batch.geom_xy()
        if self.mesh is not None:
            from .parallel.z2 import ShardedZ2Index
            builder = (ShardedZ2Index.build_multihost if self.multihost
                       else ShardedZ2Index.build)
            return builder(
                np.asarray(x), np.asarray(y), mesh=self.mesh,
                version=self.index_versions["z2"])
        xd, yd = self.device_xy()
        return Z2PointIndex.build(x, y, xd=xd, yd=yd,
                                  version=self.index_versions["z2"])

    def _build_xz3(self):
        dtg = self.batch.column(self.sft.dtg_field)
        if self.mesh is not None:
            from .parallel.xz import ShardedXZ3Index
            builder = (ShardedXZ3Index.build_multihost if self.multihost
                       else ShardedXZ3Index.build)
            return builder(
                self.batch.geoms, dtg, period=self.sft.z3_interval,
                g=self.sft.xz_precision, mesh=self.mesh)
        return XZ3Index.build(self.batch.geoms, dtg,
                              period=self.sft.z3_interval,
                              g=self.sft.xz_precision)

    def _build_xz2(self):
        if self.mesh is not None:
            from .parallel.xz import ShardedXZ2Index
            builder = (ShardedXZ2Index.build_multihost if self.multihost
                       else ShardedXZ2Index.build)
            return builder(
                self.batch.geoms, g=self.sft.xz_precision, mesh=self.mesh)
        return XZ2Index.build(self.batch.geoms, g=self.sft.xz_precision)

    def _build_id(self):
        return IdIndex.build(self.batch.ids)

    def _z3_tier_keys(self):
        """Host (bins, z) Z3 keys shared by every z3-tiered attribute
        index of this schema — computed once per rebuild (cached in the
        index map so rebuilds invalidate it with everything else)."""
        if "attr-z3-keys" not in self._indexes:
            from .curve import to_binned_time
            from .curve.sfc import z3_sfc
            dtg = self.batch.column(self.sft.dtg_field)
            bins, offs = to_binned_time(
                np.asarray(dtg, np.int64), self.sft.z3_interval)
            x, y = self.batch.geom_xy(self.sft.geom_field)
            sfc = z3_sfc(self.sft.z3_interval)
            z = sfc.index(np.asarray(x), np.asarray(y),
                          offs.astype(np.float64), xp=np)
            self._indexes["attr-z3-keys"] = (bins, z)
        return self._indexes["attr-z3-keys"]

    def attribute_index(self, attr: str) -> AttributeIndex:
        if self.lean:
            # round-5: the generational lexicoded attribute index —
            # attribute predicates are index-served at scale instead of
            # degrading to full host scans (round-4 VERDICT #1)
            return self._lean_attr_index(attr)
        self._rebuild_if_dirty()
        enabled = self.sft.enabled_indices
        if enabled is not None and "attr" not in enabled:
            raise ValueError(
                f"index 'attr' is disabled on schema {self.sft.name!r} "
                "(geomesa.indices.enabled)")
        key = f"attr:{attr}"
        self._maybe_compact(key)
        if key not in self._indexes:
            self._index_coverage[key] = len(self.batch)
            self.build_counts[key] = self.build_counts.get(key, 0) + 1
            if self.mesh is not None:
                # mesh mode: tier selection mirrors the single-chip
                # index — z3 tier (fused rank|bin + z keys) for point
                # schemas with dtg, date tier when only dtg
                from .parallel.attribute import ShardedAttributeIndex
                builder = (ShardedAttributeIndex.build_multihost
                           if self.multihost
                           else ShardedAttributeIndex.build)
                if (self.sft.dtg_field and self.sft.is_points
                        and self.sft.geom_field):
                    bins, z = self._z3_tier_keys()
                    self._indexes[key] = builder(
                        attr, self.batch.column(attr), mesh=self.mesh,
                        sec_bins=bins, sec_z=z)
                else:
                    secondary = (
                        np.asarray(self.batch.column(self.sft.dtg_field),
                                   np.int64)
                        if self.sft.dtg_field else None)
                    self._indexes[key] = builder(
                        attr, self.batch.column(attr),
                        secondary=secondary, mesh=self.mesh)
                return self._indexes[key]
            # secondary tier selection mirrors the reference: Z3 keys
            # when the schema has point geometry + dtg, date keys when
            # only dtg (AttributeIndexKeySpace secondary defaults)
            if self.sft.dtg_field and self.sft.is_points and self.sft.geom_field:
                bins, z = self._z3_tier_keys()
                self._indexes[key] = AttributeIndex.build_z3(
                    attr, self.batch.column(attr), bins, z)
            else:
                secondary = (self.batch.column(self.sft.dtg_field)
                             if self.sft.dtg_field else None)
                self._indexes[key] = AttributeIndex.build(
                    attr, self.batch.column(attr), secondary=secondary)
        return self._indexes[key]


def _apply_mask_global(store: "_SchemaStore", hits: list,
                       allowed: np.ndarray) -> list:
    """Apply a per-process row mask to per-window hit lists with global
    semantics: single-controller indexes directly; multihost decodes
    gids → local rows, masks next to the data, and allgathers the
    survivors back into the global gid list (every process must enter
    the collective — call this from all processes or none)."""
    if store.multihost:
        from .parallel.multihost import allgather_concat
        return [np.sort(allgather_concat(store.gids_of(r[allowed[r]])))
                for r in (store.local_rows_of(h) for h in hits)]
    return [h[allowed[h]] for h in hits]


class _MaskedStoreView:
    """Delegates to a _SchemaStore but substitutes the attribute-masked
    batch (attribute-level visibility for restricted callers)."""

    def __init__(self, store: _SchemaStore, batch: FeatureBatch):
        self._store = store
        self.batch = batch

    def __getattr__(self, name):
        return getattr(self._store, name)


class TpuDataStore:
    """In-process spatio-temporal datastore over columnar TPU indexes."""

    #: first-write row count at which a qualifying schema auto-enables
    #: the lean profile (chunked columns + tiered LeanZ3Index)
    LEAN_AUTO_ROWS = 32_000_000

    def __init__(self, catalog_dir: str | None = None, *,
                 mesh=None, multihost: bool = False, auth_provider=None,
                 audit_writer=None, user: str = "unknown"):
        """``mesh``: an optional ``jax.sharding.Mesh``; when given, every
        index builds its sharded variant and all scans run as collectives
        over the mesh — the same facade, laptop-to-pod (the reference's
        GeoMesaDataStore property, geotools/GeoMesaDataStore.scala:48).

        ``multihost``: multi-controller mode — every process runs the
        same store program (SPMD) but feeds only its LOCAL rows to
        ``write``; no process ever holds the full dataset.  Query
        results return each process's local slice of the hits plus the
        global gid list (``QueryResult.positions`` codes
        ``process << GID_PROC_SHIFT | local_row``).  Requires ``mesh``
        (usually ``global_device_mesh()``)."""
        if multihost and mesh is None:
            raise ValueError("multihost=True requires a mesh")
        self._schemas: dict[str, _SchemaStore] = {}
        self._mesh = mesh
        self._multihost = multihost
        self._catalog_dir = catalog_dir
        self._auth_provider = auth_provider
        self._audit_writer = audit_writer
        self._user = user
        self._interceptors: dict[str, list] = {}
        self._lock_depth = 0
        # the fused serving plane (ISSUE 17): one coalescing scheduler
        # per store — compatible concurrent queries share one batched
        # device dispatch (serving/fusion.py)
        from .serving import FusionScheduler
        self._fusion = FusionScheduler()
        if catalog_dir:
            os.makedirs(catalog_dir, exist_ok=True)
            with self._catalog_lock():
                self._check_catalog_version()
                self._load_catalog()

    # -- catalog version handshake + mutation locking ---------------------
    def _version_path(self) -> str:
        return os.path.join(self._catalog_dir, "catalog.version")

    def _check_catalog_version(self) -> None:
        path = self._version_path()
        if os.path.exists(path):
            with open(path) as f:
                found = int(f.read().strip() or 0)
            if found > CATALOG_VERSION:
                raise CatalogVersionError(
                    f"catalog {self._catalog_dir!r} has version {found}, "
                    f"newer than this framework's {CATALOG_VERSION}; "
                    "upgrade before opening it")
            self._catalog_found_version = found
        else:
            with open(path, "w") as f:
                f.write(str(CATALOG_VERSION))
            self._catalog_found_version = CATALOG_VERSION

    @contextmanager
    def _catalog_lock(self):
        """File lock serializing catalog reads/mutations across processes
        sharing a catalog directory (the ZookeeperLocking/
        DistributedLocking role, index/utils/DistributedLocking.scala).
        Reentrant within this store instance (flock on a second fd of the
        same file would deadlock against ourselves)."""
        if not self._catalog_dir:
            yield
            return
        if self._lock_depth > 0:
            self._lock_depth += 1
            try:
                yield
            finally:
                self._lock_depth -= 1
            return
        import fcntl
        path = os.path.join(self._catalog_dir, ".lock")
        f = open(path, "w")
        fcntl.flock(f, fcntl.LOCK_EX)
        self._lock_depth = 1
        try:
            yield
        finally:
            self._lock_depth = 0
            fcntl.flock(f, fcntl.LOCK_UN)
            f.close()

    # -- schema lifecycle (MetadataBackedDataStore.createSchema etc.) ----
    def create_schema(self, sft_or_name, spec: str | None = None) -> FeatureType:
        if isinstance(sft_or_name, FeatureType):
            sft = sft_or_name
        else:
            sft = parse_spec(sft_or_name, spec)
        if not re.fullmatch(r"[A-Za-z0-9_-]+", sft.name):
            # catalog artifacts encode structure in filename suffixes
            # ({name}.stats.json, {name}.pN.stats.json, {name}.lean.pN)
            # — a dotted schema name would collide with another
            # schema's artifact grammar (the reference's stores
            # restrict table-backed names the same way)
            raise ValueError(
                f"invalid schema name {sft.name!r}: letters, digits, "
                "underscore and dash only")
        if sft.name in self._schemas:
            raise ValueError(f"schema {sft.name!r} already exists")
        with self._catalog_lock():
            # re-check ON DISK under the lock: another process sharing the
            # catalog may have created it since we loaded (check-then-act)
            if self._catalog_dir and os.path.exists(os.path.join(
                    self._catalog_dir, f"{sft.name}.schema.json")):
                raise ValueError(
                    f"schema {sft.name!r} already exists in the catalog "
                    "(created by another process)")
            self._schemas[sft.name] = _SchemaStore(sft, mesh=self._mesh,
                                         multihost=self._multihost)
            self._schemas[sft.name].pyramid_trigger = \
                self._pyramid_listener(sft.name)
            # interceptors resolve EAGERLY at schema load (ISSUE 16): a
            # typoed ``geomesa.query.interceptors`` dotted path fails
            # create_schema, not the first query hours later
            self._resolve_interceptors(sft)
            self._persist_schema(sft)
        return sft

    def _resolve_interceptors(self, sft: FeatureType) -> None:
        from .planning.interceptor import load_interceptors
        self._interceptors[sft.name] = load_interceptors(sft)

    def get_schema(self, name: str) -> FeatureType:
        return self._store(name).sft

    def update_schema(self, name: str, sft: FeatureType) -> None:
        """Replace schema metadata (the reference's updateSchema,
        MetadataBackedDataStore.scala:205 — rename/user-data updates)."""
        store = self._store(name)
        if [a.name for a in sft.attributes] != [a.name for a in store.sft.attributes]:
            raise ValueError("updateSchema cannot add/remove attributes")
        if sft.user_data.get("geomesa.index.versions") == "current":
            # explicit layout upgrade request piggybacking on the schema
            # update (the reference's index-migration path)
            self.migrate_schema(name)
        with self._catalog_lock():
            # validate BEFORE mutating: a raise below this point would
            # leave store.sft renamed in memory while the catalog (and
            # the old name's registration) still say otherwise
            if sft.name != name:
                if not re.fullmatch(r"[A-Za-z0-9_-]+", sft.name):
                    # same grammar create_schema enforces — a dotted
                    # rename would re-create the artifact-suffix
                    # collisions the validation exists to prevent
                    raise ValueError(
                        f"invalid schema name {sft.name!r}: letters, "
                        "digits, underscore and dash only")
                on_disk = (self._catalog_dir and os.path.exists(
                    os.path.join(self._catalog_dir,
                                 f"{sft.name}.schema.json")))
                if sft.name in self._schemas or on_disk:
                    # on-disk re-check under the lock, like
                    # create_schema: another process sharing the
                    # catalog may have created the target since we
                    # loaded — the rename path destroys target-name
                    # artifacts and must never hit a LIVE schema
                    raise ValueError(
                        f"cannot rename schema {name!r} to "
                        f"{sft.name!r}: that schema already exists")
            store.sft = sft
            self._interceptors.pop(name, None)
            if sft.name != name:
                self._schemas[sft.name] = self._schemas.pop(name)
                self._interceptors.pop(sft.name, None)
                # move the persisted artifacts: stale old-name files would
                # resurrect a phantom schema on the next catalog load
                if self._catalog_dir:
                    for suffix in (".schema.json", ".parquet",
                                   ".stats.json", ".vis.json"):
                        old = os.path.join(self._catalog_dir,
                                           f"{name}{suffix}")
                        target = os.path.join(self._catalog_dir,
                                              f"{sft.name}{suffix}")
                        if os.path.exists(old):
                            os.replace(old, target)
                        elif os.path.exists(target):
                            # stale target leftover (crashed remove of
                            # an old schema) with no source to replace
                            # it: mtime recency in load_stats would let
                            # it shadow the renamed schema's artifacts
                            os.remove(target)
                    import shutil
                    # stale target-name leftovers (crashed remove of an
                    # old schema) must not fold into the renamed one —
                    # stats files AND row snapshot dirs
                    for p in self._proc_stats_files(sft.name):
                        with contextlib.suppress(FileNotFoundError):
                            os.remove(p)
                    for d in self._lean_snapshot_dirs(sft.name):
                        shutil.rmtree(d, ignore_errors=True)
                    for p in self._proc_stats_files(name):
                        f = os.path.basename(p)
                        with contextlib.suppress(FileNotFoundError):
                            # externally deleted between listdir and
                            # rename — same tolerance persist_stats has
                            os.replace(p, os.path.join(
                                self._catalog_dir,
                                sft.name + f[len(name):]))
                    for d in self._lean_snapshot_dirs(name):
                        target = os.path.join(
                            self._catalog_dir,
                            f"{sft.name}.lean"
                            + os.path.basename(d)[len(f"{name}.lean"):])
                        # a stale non-empty target dir (crashed remove
                        # of an old schema) would make rename(2) fail
                        # ENOTEMPTY mid-rename; the live-schema
                        # collision is already rejected above
                        shutil.rmtree(target, ignore_errors=True)
                        os.replace(d, target)
            # eager re-resolution (see create_schema): a bad interceptor
            # path in the UPDATED user data fails at update time, not on
            # the first query against the new user data
            self._resolve_interceptors(sft)
            self._persist_schema(sft)

    def remove_schema(self, name: str) -> None:
        with self._catalog_lock():
            self._schemas.pop(name, None)
            self._interceptors.pop(name, None)
            if self._catalog_dir:
                for suffix in (".schema.json", ".parquet", ".stats.json",
                               ".vis.json"):
                    path = os.path.join(self._catalog_dir, f"{name}{suffix}")
                    with contextlib.suppress(FileNotFoundError):
                        os.remove(path)
                for p in self._proc_stats_files(name):
                    # a concurrent persist's prune (or an external
                    # delete) between listdir and remove must not crash
                    # the schema removal mid-cleanup
                    with contextlib.suppress(FileNotFoundError):
                        os.remove(p)
                # lean snapshot dirs too: a stale snapshot would
                # resurrect the removed schema's rows into a later
                # schema of the same name
                import shutil
                for d in self._lean_snapshot_dirs(name):
                    shutil.rmtree(d, ignore_errors=True)

    def _lean_snapshot_dirs(self, name: str) -> list[str]:
        """Every lean snapshot dir for ``name`` (``{name}.lean`` plus
        the per-process ``{name}.lean.pN`` multihost variants)."""
        if not self._catalog_dir or not os.path.isdir(self._catalog_dir):
            return []
        out = []
        for f in os.listdir(self._catalog_dir):
            if f == f"{name}.lean" or f.startswith(f"{name}.lean."):
                p = os.path.join(self._catalog_dir, f)
                if os.path.isdir(p):
                    out.append(p)
        return out

    @property
    def type_names(self) -> list[str]:
        return sorted(self._schemas)

    def _store(self, name: str) -> _SchemaStore:
        if name not in self._schemas:
            raise KeyError(f"no such schema: {name!r}")
        return self._schemas[name]

    # -- ingest -----------------------------------------------------------
    def write(self, name: str, data, ids=None, visibility: str = "",
              attribute_visibilities: dict | None = None) -> int:
        """Append features: a FeatureBatch or a dict of columns.

        ``visibility`` is an optional visibility expression (e.g.
        ``"admin&ops"``) applied to every feature in this write; queries
        made with an auth provider only see features whose expression
        their auths satisfy.  ``attribute_visibilities`` maps attribute
        names to expressions guarding just that attribute (the
        reference's attribute-level visibility / KryoVisibilityRowEncoder):
        unauthorized callers see the row but the guarded values are
        nulled.

        Every write is ONE trace (the query-span symmetry, ISSUE 12):
        a root ``write`` span over ``write.encode`` (input → columns),
        per-index ``write.index`` appends, ``write.seal``/
        ``write.spill`` lifecycle events, ``write.observe`` (the stats
        join), and — while the trace records — a ``write.device``
        block-until-ready device attribution (docs/observability.md).
        """
        from .obs import span as obs_span
        with obs_span("write", schema=name) as wsp:
            n = self._write_inner(name, data, ids, visibility,
                                  attribute_visibilities)
            wsp.set_attr("rows", int(n))
            return n

    def _write_inner(self, name: str, data, ids, visibility: str,
                     attribute_visibilities: dict | None) -> int:
        from .obs import span as obs_span
        from .security import parse_visibility
        if visibility:
            parse_visibility(visibility)  # validate eagerly
        store = self._store(name)
        if (not store.lean and store.batch is None
                and store.mesh is None
                and store.sft.is_points and store.sft.geom_field
                and store.sft.dtg_field
                and not isinstance(data, FeatureBatch)
                and ids is None and not attribute_visibilities):
            # auto-profile: a first write past the threshold flips the
            # schema to the lean profile BEFORE any full-fat state
            # exists (the reference serves every scale through one
            # facade; the threshold is where full-fat HBM residency
            # stops making sense)
            first = next(iter(data.values()), ())
            n_first = (len(first[0]) if isinstance(first, tuple)
                       else len(first))
            if n_first >= self.LEAN_AUTO_ROWS:
                store.sft.user_data["geomesa.index.profile"] = "lean"
                store._init_lean()
                self._persist_schema(store.sft)
        if store.lean:
            from .features.batch import build_columns
            from .features.lean import ChunkView
            if attribute_visibilities:
                raise ValueError(
                    "attribute-level visibility is not supported on "
                    "lean-profile schemas (row visibility is)")
            if ids is not None or (isinstance(data, FeatureBatch)
                                   and data.ids_explicit):
                raise ValueError(
                    "lean-profile schemas use implicit feature ids "
                    "(row number); explicit ids are not supported")
            with obs_span("write.encode", lean=True):
                if isinstance(data, FeatureBatch):
                    chunk = ChunkView(store.sft, dict(data.columns),
                                      len(data), geoms=data.geoms)
                else:
                    cols, geoms = build_columns(store.sft, data)
                    n_chunk = (len(next(iter(cols.values()))) if cols
                               else (len(geoms) if geoms is not None
                                     else 0))
                    chunk = ChunkView(store.sft, cols, n_chunk,
                                      geoms=geoms)
            store.write(chunk, visibility=visibility)
            store.next_fid = len(store.batch)
            from .metrics import registry as _metrics
            _metrics.counter(f"write.{name}.features").inc(len(chunk))
            return len(chunk)
        for attr, expr in (attribute_visibilities or {}).items():
            spec = store.sft.attribute(attr)   # KeyError on typos
            if spec.is_geometry or attr == store.sft.dtg_field:
                raise ValueError(
                    "cannot set attribute visibility on geometry or the "
                    f"dtg field ({attr!r}): indexes scan them unmasked")
            if expr:
                parse_visibility(expr)
        with obs_span("write.encode", lean=False):
            batch = (data if isinstance(data, FeatureBatch)
                     else FeatureBatch.from_dict(store.sft, data,
                                                 ids=ids))
        auto_ids = not batch.ids_explicit
        if auto_ids:
            # feature ids must be unique across writes: re-base auto ids on
            # a shallow copy so the caller's batch (and any prior-write
            # alias held by the store) is never mutated.  With
            # ``geomesa.fid.strategy=z3`` user data, auto ids are
            # z-prefixed UUIDs (Z3FeatureIdGenerator locality).
            if (store.sft.user_data.get("geomesa.fid.strategy") == "z3"
                    and store.sft.is_points and store.sft.dtg_field):
                from .utils.feature_id import z3_feature_ids
                x, y = batch.geom_xy()
                new_ids = z3_feature_ids(
                    x, y, batch.column(store.sft.dtg_field),
                    period=store.sft.z3_interval)
            else:
                # monotonic counter, NOT len(batch): deletes shrink the
                # batch but minted ids must never come back (delete 2 of
                # 4 then write 2 → reused ids '2','3' would make id-index
                # lookups and delete-by-id hit two rows each).  Multihost
                # processes each mint from their own prefixed sequence —
                # no cross-process coordination, no collisions.
                base = store.next_fid
                prefix = ""
                if store.multihost:
                    import jax
                    if jax.process_count() > 1:
                        prefix = f"p{jax.process_index()}."
                new_ids = np.array(
                    [f"{prefix}{base + i}" for i in range(len(batch))],
                    dtype=object)
            batch = FeatureBatch(
                batch.sft, dict(batch.columns), geoms=batch.geoms,
                ids=new_ids)
            next_fid = store.next_fid + len(batch)
        else:
            # explicit ids: reject collisions at the writer (the id
            # index enforces uniqueness too, but failing there — at lazy
            # build, deep inside a later query — would permanently break
            # the schema's id queries long after the bad write)
            ids_in = batch.ids.astype(str)
            err = ""
            uniq, counts = np.unique(ids_in, return_counts=True)
            if (counts > 1).any():
                err = (f"duplicate feature id {uniq[counts > 1][0]!r} "
                       "within the write batch")
            else:
                clash = store.find_id_clash(ids_in)
                if clash is not None:
                    err = (f"feature id {clash!r} already exists in "
                           f"schema {name!r} (delete it first, or use "
                           "auto-generated ids)")
            if store.multihost:
                # collective validation: cross-process duplicates within
                # the write, clashes against rows stored on ANY process,
                # and an AGREED raise — a one-sided exception would
                # desync the SPMD store at its next collective
                import jax
                if jax.process_count() > 1:
                    from .parallel.multihost import allgather_strings
                    g_ids = allgather_strings(ids_in)
                    if not err:
                        gu, gc = np.unique(g_ids, return_counts=True)
                        if (gc > 1).any():
                            err = (f"duplicate feature id "
                                   f"{gu[gc > 1][0]!r} across processes "
                                   "in the write batch")
                        else:
                            # every process checks the FULL incoming id
                            # set against ITS stored rows (ids written
                            # by peers live only on their process)
                            clash = store.find_id_clash(g_ids)
                            if clash is not None:
                                err = (f"feature id {clash!r} already "
                                       f"exists in schema {name!r}")
                    errs = [e for e in allgather_strings(
                        np.array([err], dtype=object)) if e]
                    if errs:
                        raise ValueError(errs[0])
                    err = ""
            if err:
                raise ValueError(err)
            # numeric-id max computed BEFORE the append so a parse issue
            # can never leave the store mutated with the counter behind
            next_fid = max(store.next_fid, _max_numeric_id(batch.ids) + 1)
        store.write(batch, visibility=visibility,
                    attribute_visibilities=attribute_visibilities)
        store.next_fid = next_fid
        from .metrics import registry as _metrics
        _metrics.counter(f"write.{name}.features").inc(len(batch))
        return len(batch)

    def delete(self, name: str, ids) -> int:
        """Remove features by id (the reference's modifying writer /
        removeFeatures path).  Stats are recomputed from the surviving
        rows — sketches are not invertible."""
        store = self._store(name)
        if store.lean:
            # tombstone, don't remove: positions stay stable (the live
            # index and payload never shuffle) and implicit ids are
            # never reused — the modifying-writer delete as a mask.
            # Multihost: each process resolves ITS prefixed ids; the
            # count and the mutation decision are agreed.
            from .index.id import LeanIdIndex
            # duplicate ids in the request cannot double-count:
            # LeanIdIndex.query returns a np.unique'd row array
            rows = LeanIdIndex(len(store.batch),
                               prefix=store.batch.id_prefix).query(
                np.atleast_1d(np.asarray(ids, dtype=object)))
            newly = rows
            if len(rows):
                if store.tombstone is None:
                    store.tombstone = np.zeros(len(store.batch),
                                               dtype=bool)
                newly = rows[~store.tombstone[rows]]
                store.tombstone[rows] = True
            n_new = int(len(newly))
            if store.multihost:
                from .parallel.multihost import agreed_int
                n_global = agreed_int(n_new, "sum")
            else:
                n_global = n_new
            if n_global:
                if store.multihost and store.tombstone is None:
                    # SPMD symmetry: the tombstone must exist on EVERY
                    # process once any process has one, or downstream
                    # mask-presence branches (get_count, query allowed)
                    # diverge into mismatched collectives
                    store.tombstone = np.zeros(len(store.batch),
                                               dtype=bool)
                store._mutation_version += 1
                store._vis_masks = {}
                store._lean_recompute_stats()
            return n_global
        n_here = 0 if store.batch is None else len(store.batch)
        if n_here == 0 and not store.multihost:
            return 0
        removed = 0
        if n_here:
            drop = set(str(i)
                       for i in np.atleast_1d(np.asarray(ids, dtype=object)))
            keep = np.array([str(i) not in drop for i in store.batch.ids])
            removed = int((~keep).sum())
            if removed:
                if store._id_set is not None:
                    store._id_set.difference_update(
                        str(i) for i in store.batch.ids[~keep])
                store.batch = store.batch.take(np.flatnonzero(keep))
                if store.visibilities is not None:
                    store.visibilities = store.visibilities[keep]
                for attr in list(store.attr_visibilities):
                    store.attr_visibilities[attr] = \
                        store.attr_visibilities[attr][keep]
                store._vis_masks = {}
                store._dirty = True
                store._mutation_version += 1
                store.recompute_stats()
        if store.multihost:
            # collective: every process drops its local matches; removal
            # anywhere invalidates gid row-order everywhere, and the
            # returned count is global
            from .parallel.multihost import agreed_int
            global_removed = agreed_int(removed, "sum")
            if global_removed and not removed:
                store._dirty = True
                store._mutation_version += 1
            return global_removed
        return removed

    # -- query ------------------------------------------------------------
    def query(self, name: str, query="INCLUDE",
              explain: Explainer | None = None) -> FeatureBatch:
        return self.query_result(name, query, explain).batch

    def query_result(self, name: str, query="INCLUDE",
                     explain: Explainer | None = None, *,
                     timeout_ms: float | None = None,
                     partial_results: bool = False) -> QueryResult:
        """Run a query.  ``timeout_ms`` arms a cooperative deadline
        (resilience/deadline.py) checked at every scan yield point:
        expiry raises :class:`~geomesa_tpu.resilience.QueryTimeout`, or
        — with ``partial_results=True`` — returns the exact hits over
        what WAS scanned, flagged ``result.timed_out`` (ISSUE 16)."""
        return self._query_result_ex(
            name, query, explain, timeout_ms=timeout_ms,
            partial_results=partial_results)[0]

    def _query_result_ex(self, name: str, query="INCLUDE",
                         explain: Explainer | None = None,
                         materialize: bool = True,
                         timeout_ms: float | None = None,
                         partial_results: bool = False,
                         _token=None):
        """The shared query executor: returns ``(result, eval_store)``
        so the Arrow streaming path (``materialize=False``) can gather
        its columns from the SAME (possibly visibility-masked) batch
        the residual filter evaluated over.

        Admission (ISSUE 16): every query holds one gate token for its
        whole execution; ``_token`` hands in a token the CALLER already
        acquired (query_arrow holds its token until the streamed drain
        completes, long past this method's return)."""
        from .resilience import admission_gate, current_scope, deadline_scope
        own_token = _token is None
        token = _token if _token is not None else admission_gate.acquire(name)
        try:
            queue_ms = getattr(token, "queue_ms", 0.0)
            if timeout_ms is not None:
                with deadline_scope(timeout_ms, partial_results) as scope:
                    result, eval_store = self._run_query(
                        name, query, explain, materialize,
                        queue_ms=queue_ms)
                result.timed_out = scope.timed_out
            else:
                result, eval_store = self._run_query(
                    name, query, explain, materialize,
                    queue_ms=queue_ms)
                ambient = current_scope()
                if ambient is not None and ambient.timed_out:
                    result.timed_out = True
            return result, eval_store
        finally:
            if own_token:
                token.release()

    def _run_query(self, name: str, query="INCLUDE",
                   explain: Explainer | None = None,
                   materialize: bool = True, queue_ms: float = 0.0):
        from .obs import span as obs_span
        store = self._store(name)
        q = query if isinstance(query, Query) else Query.of(query)
        q = self._intercept(store.sft, q)
        with obs_span("query", schema=name) as sp:
            if sp.recording:
                sp.set_attr("filter", repr(q.filter))
                sp.set_attr("lean", bool(store.lean))
                if queue_ms:
                    # the admission wait happens BEFORE this span opens
                    # — the SLO plane's queue stage rides the root attr
                    sp.set_attr("admission.queue_ms", round(queue_ms, 3))
                tenant = q.hints.get("TENANT")
                if tenant:
                    sp.set_attr("tenant", str(tenant))
            if store.batch is None or len(store.batch) == 0:
                if store.multihost:
                    # a locally-empty process must still ENTER the
                    # planner's collectives (other processes may hold
                    # rows); an empty local batch feeds zero rows to the
                    # sharded builds
                    if store.batch is None:
                        store.batch = FeatureBatch.empty(store.sft)
                else:
                    empty = FeatureBatch.empty(store.sft)
                    from .planning.strategy import FilterStrategy
                    result = QueryResult(empty, np.empty(0, dtype=np.int64),
                                         FilterStrategy("none", 0), 0.0, 0.0,
                                         local_rows=np.empty(0, np.int64))
                    self._audit(name, q, result)
                    return result, store
            allowed = None
            eval_store = store
            if self._auth_provider is not None:
                auths = self._auth_provider.get_authorizations()
                allowed = store.vis_mask(auths)
                masked = store.masked_batch(auths)
                if masked is not store.batch:
                    # guarded values must be invisible to FILTERS too, not
                    # just results — evaluate over the masked view
                    eval_store = _MaskedStoreView(store, masked)
            if store.tombstone is not None:
                # deleted rows (lean tombstones) are invisible to every
                # query, like any other row the caller cannot see
                live = ~store.tombstone
                allowed = live if allowed is None else (allowed & live)
            result = QueryPlanner(store.sft, eval_store).run(
                q, explain, allowed=allowed, materialize=materialize)
            sp.set_attr("hits", int(len(result.positions)))
            self._audit(name, q, result)
            return result, eval_store

    def _intercept(self, sft: FeatureType, q: Query) -> Query:
        from .planning.interceptor import apply_interceptors, load_interceptors

        if sft.name not in self._interceptors:
            self._interceptors[sft.name] = load_interceptors(sft)
        return apply_interceptors(self._interceptors[sft.name], sft, q)

    def _audit(self, name: str, q: Query, result: QueryResult) -> None:
        self._audit_record(name, repr(q.filter), dict(q.hints),
                           result.plan_time_ms, result.scan_time_ms,
                           len(result.positions))

    def _audit_record(self, name: str, filter_repr: str, hints: dict,
                      plan_ms: float | None, scan_ms: float,
                      hits: int) -> None:
        """The ONE audit emission path — every query shape (planner,
        batched-windows fast path) updates the same registry keys and
        writes an identically-shaped QueryEvent stamped with the active
        trace id, so readback/alerting never depends on which code path
        served the query.  ``plan_ms=None`` means the planning phase
        never ran (the fast paths plan inside the index): the event
        records 0.0 but the plan_ms timer gets NO sample — phantom
        zeros would drag its p50/min to 0 and mask real planner
        regressions."""
        from .metrics import registry as _metrics
        from .obs import current_trace_id
        _metrics.counter(f"query.{name}.count").inc()
        if plan_ms is not None:
            _metrics.timer(f"query.{name}.plan_ms").update(plan_ms)
        _metrics.timer(f"query.{name}.scan_ms").update(scan_ms)
        if self._audit_writer is not None:
            from .audit import QueryEvent
            self._audit_writer.write_event(QueryEvent(
                store="tpu", type_name=name, user=self._user,
                filter=filter_repr, hints=hints,
                plan_time_ms=plan_ms or 0.0, scan_time_ms=scan_ms,
                hits=hits, trace_id=current_trace_id()))

    def query_arrow(self, name: str, query="INCLUDE", *,
                    chunk_rows: int | None = None,
                    dictionary_fields="auto",
                    timeout_ms: float | None = None,
                    partial_results: bool = False,
                    tenant: str = ""):
        """Streaming Arrow results (ISSUE 14): run the query to hit
        POSITIONS only — no per-row feature objects ever exist — and
        return an :class:`~geomesa_tpu.arrow.stream.ArrowStream`
        generator of ``pa.RecordBatch`` chunks of ``chunk_rows`` rows
        (default ``geomesa.arrow.chunk.rows``), encoded lazily as the
        caller pulls: device hit positions → one batched on-device
        column gather per full-tier generation (the lean scale index's
        ``gather_payload``), vectorized host takes for everything else,
        vectorized feature ids, and delta-dictionary record batches
        (``dictionary_fields`` names attributes to dictionary-encode;
        the default ``"auto"`` encodes string attributes whose sampled
        cardinality stays under ``geomesa.arrow.dictionary.threshold``).

        Byte-for-byte equal to encoding the row-wise
        ``query_result().batch`` chunk-by-chunk — pinned by tests — at
        zero per-row Python object cost.  Projections/reprojections
        (``properties``/``crs``) fall back to encoding the materialized
        row-wise batch.  Under multihost each process streams ITS local
        hit slice (per-shard delta streams; clients k-way merge via
        ``arrow.reader.merge_deltas``).  For the one-shot in-process
        Table API with the mesh residency reduce, see
        :meth:`query_arrow_table`."""
        from .resilience import admission_gate
        # the admission token spans the WHOLE streamed response: it
        # releases when the last chunk drains (or the drain aborts),
        # not when this method returns the lazy stream (ISSUE 16)
        token = admission_gate.acquire(name)
        try:
            return self._query_arrow_under_token(
                name, query, chunk_rows, dictionary_fields,
                timeout_ms, partial_results, token, tenant)
        except BaseException:
            token.release()
            raise

    def _query_arrow_under_token(self, name, query, chunk_rows,
                                 dictionary_fields, timeout_ms,
                                 partial_results, token, tenant=""):
        from .arrow.schema import sft_to_arrow_schema
        from .arrow.stream import (
            ArrowStream, auto_dictionary_fields, stream_batches,
        )
        from .resilience import CancelScope

        # one scope covers scan AND drain.  The scan phase honors
        # ``partial_results`` (False -> QueryTimeout before any bytes
        # hit the wire, the 504 path); the drain NEVER raises on expiry
        # — stream_batches polls the scope between chunks and ends
        # early with a well-formed Arrow EOS (the 200 status line is
        # long gone by then)
        scope = (CancelScope(timeout_ms, partial_results)
                 if timeout_ms is not None else None)
        store = self._store(name)
        q = query if isinstance(query, Query) else Query.of(query)
        needs_rows = (q.properties is not None or bool(q.crs)
                      or "COLUMN_GROUP" in q.hints)
        if needs_rows:
            result = self._scoped_query_result(name, q, scope, token)
            source = result.batch
            sft = source.sft
            rows = np.arange(len(source), dtype=np.int64)
            eval_store = store
        else:
            from .resilience import deadline_scope
            # fused serving plane (ISSUE 17): compatible queries submit
            # through the fusion scheduler and the Arrow stream picks
            # up from the demuxed positions — the token this caller
            # already holds covers the whole drain, and the scheduler
            # itself never touches the gate
            window = self._fusible_window(name, store, q)
            if window is not None:
                from .obs import span as obs_span
                tenant = tenant or str(q.hints.get("TENANT", "") or "")
                # root span for the fused path: on the LEADER thread
                # the scheduler's serving.fuse span nests under it; a
                # RIDER's trace records no scan spans at all, so the
                # coalesce/dispatch attrs stamped below are the SLO
                # plane's only attribution source (attribution.py)
                with obs_span("query", schema=name, fused=True) as sp:
                    if sp.recording:
                        sp.set_attr("filter", repr(q.filter))
                        if tenant:
                            sp.set_attr("tenant", tenant)
                        queue_ms = getattr(token, "queue_ms", 0.0)
                        if queue_ms:
                            sp.set_attr("admission.queue_ms",
                                        round(queue_ms, 3))
                    t_sub = time.perf_counter()
                    outcome = self._fusion.submit(
                        ("fuse", name), window,
                        lambda ws: self._fused_windows_dispatch(name, ws),
                        scope=scope, partial=partial_results,
                        tenant=tenant, schema=name)
                    if sp.recording:
                        # every scheduler millisecond that was NOT the
                        # batch executing is coalesce wait: the linger
                        # window plus wake-up/demux latency
                        submit_ms = (time.perf_counter() - t_sub) * 1e3
                        sp.set_attr("coalesce.ms", round(max(
                            outcome.coalesce_ms,
                            submit_ms - outcome.dispatch_ms), 3))
                        sp.set_attr("fused.dispatch.ms",
                                    outcome.dispatch_ms)
                        sp.set_attr("hits", int(len(outcome.positions)))
                from .planning.strategy import FilterStrategy
                result = QueryResult(
                    None, outcome.positions,
                    FilterStrategy("fused",
                                   float(len(outcome.positions))),
                    0.0, 0.0, local_rows=outcome.positions,
                    timed_out=outcome.timed_out)
                eval_store = store
            elif scope is not None:
                from .metrics import SERVING_BYPASS
                from .metrics import registry as _metrics
                _metrics.counter(SERVING_BYPASS).inc()
                with deadline_scope(scope=scope):
                    result, eval_store = self._query_result_ex(
                        name, q, materialize=False, _token=token)
            else:
                from .metrics import SERVING_BYPASS
                from .metrics import registry as _metrics
                _metrics.counter(SERVING_BYPASS).inc()
                result, eval_store = self._query_result_ex(
                    name, q, materialize=False, _token=token)
            source = eval_store.batch
            sft = store.sft
            rows = (result.local_rows if result.local_rows is not None
                    else result.positions)
        if dictionary_fields == "auto":
            dictionary_fields = auto_dictionary_fields(sft, source, rows)
        schema = sft_to_arrow_schema(sft, tuple(dictionary_fields))
        payload_gather = None
        payload_cols: tuple = ()
        if not needs_rows and eval_store is store and store.lean:
            idx = store._indexes.get(store.lean_kind)
            gather = getattr(idx, "gather_payload", None)
            # the protocol probe: index families without a
            # row-addressable device payload (attr lexicodes, XZ
            # envelope codes) answer None and every column takes the
            # vectorized host path instead
            if (gather is not None and len(idx) == len(store.batch)
                    and gather(np.empty(0, np.int64)) is not None):
                g, dtg = sft.geom_field, sft.dtg_field
                payload_cols = (f"{g}_x", f"{g}_y", dtg)

                def payload_gather(chunk, _gather=gather,
                                   _cols=payload_cols):
                    x, y, t = _gather(chunk)
                    return {_cols[0]: x, _cols[1]: y, _cols[2]: t}

        batches = stream_batches(
            sft, schema, source, rows, chunk_rows=chunk_rows,
            payload_gather=payload_gather, payload_columns=payload_cols,
            schema_name=name, deadline=scope)

        def _released(gen=batches, _token=token):
            # the token's lifetime IS the drain's: normal exhaustion,
            # a mid-stream failure, and a client abort (generator
            # close) all land in this finally exactly once
            try:
                yield from gen
            finally:
                _token.release()

        # on_close covers the stream-abandoned-before-first-next case:
        # _released's finally cannot run if its body was never entered
        return ArrowStream(schema, _released(), sft,
                           on_close=token.release)

    def _scoped_query_result(self, name, q, scope, token):
        from .resilience import deadline_scope
        if scope is None:
            return self._query_result_ex(name, q, _token=token)[0]
        with deadline_scope(scope=scope):
            return self._query_result_ex(name, q, _token=token)[0]

    def query_arrow_table(self, name: str, query="INCLUDE", *,
                          dictionary_fields: tuple[str, ...] = (),
                          sort_field: str | None = None,
                          reverse: bool = False,
                          batch_size: int = 65536):
        """Run a query and return a pyarrow Table via the Arrow scan
        protocol (the reference's ArrowScan, index/iterators/
        ArrowScan.scala:35): sorted dictionary-encoded record batches of
        ``batch_size`` rows — the per-device shard chunk analog — built
        in-process (no IPC round trip; serialize with
        process.arrow_conversion_process for the wire format).  This is
        the one-shot ROW-WISE materializing form; the serving plane
        streams through :meth:`query_arrow` instead (ISSUE 14)."""
        import pyarrow as pa

        from .arrow.schema import (
            encode_record_batch, sft_to_arrow_schema,
        )

        store = self._store(name)
        sft = store.sft
        schema = sft_to_arrow_schema(sft, dictionary_fields)
        result = self.query_result(name, query)
        batch = result.batch
        # gate on the GLOBAL hit list, not the local batch: under
        # multihost a process may hold zero of the hits while peers hold
        # some — it must still enter the mesh reduce below with its
        # empty local group, like stats_process does (ADVICE r3)
        if len(result.positions) == 0:
            return schema.empty_table()
        if self._mesh is not None:
            # distributed reduce: per-shard delta-dictionary streams
            # k-way merged client-side (ArrowScan.scala:35 reduce step).
            # Rows group by TRUE device residency (shard_of_gids over
            # the placement segments), so each stream is exactly what
            # that data shard would serve — its dictionary accumulates
            # only ITS values; dictionary columns decode on merge
            # (per-shard dictionaries index different accumulations).
            # Multihost: each process reduces its local hit slice.
            from .parallel.stats import merged_arrow
            shards = self._hit_residency(store, result.positions)
            merged = merged_arrow(
                batch, sft, shards, dictionary_fields, sort_field, reverse)
            # zero LOCAL rows (all hits live on peers) → empty table of
            # the right schema rather than None
            return merged if merged is not None else schema.empty_table()
        if sort_field is not None:
            order = np.argsort(np.asarray(batch.columns[sort_field]),
                               kind="stable")
            batch = batch.take(order[::-1] if reverse else order)
        dicts: dict = {}
        rbs = [encode_record_batch(
                   batch.take(np.arange(s, min(s + batch_size, len(batch)))),
                   schema, dicts)
               for s in range(0, len(batch), batch_size)]
        return pa.Table.from_batches(rbs)

    def _residency_shards(self, store: _SchemaStore, gids):
        """Per-row shard ids for the reduce protocols: true residency
        from a built sharded index's placement segments, else the block
        split a fresh build would produce (int fallback)."""
        # a dirty store's cached indexes describe PRE-mutation placement
        # (e.g. pre-delete row ids) — drop them rather than group new
        # rows through stale segments
        store._rebuild_if_dirty()
        for nm in ("z3", "z2"):
            idx = store._indexes.get(nm)
            if idx is not None and getattr(idx, "_segments", None):
                return idx.shard_of_gids(gids)
        return int(self._mesh.devices.size)

    def _hit_residency(self, store: _SchemaStore, positions: np.ndarray):
        """Residency shard ids for this process's slice of the final hit
        positions (the grouping input of the arrow/stats reducers)."""
        if store.multihost:
            import jax
            from .parallel.scan import decode_gids
            procs, _ = decode_gids(positions)
            positions = np.asarray(positions, np.int64)[
                procs == jax.process_index()]
        return self._residency_shards(store, positions)

    def query_windows(self, name: str, windows, *,
                      timeout_ms: float | None = None,
                      partial_results: bool = False) -> list[np.ndarray]:
        """Batched bbox+time window queries: one device dispatch for ALL
        windows (``[(boxes, t_lo_ms, t_hi_ms), …]``), returning a position
        array per window — the BatchScanner-over-many-range-sets pattern
        the analytics processes (tube-select, kNN rings) are built on.
        Falls back to per-window planner queries for non-point schemas.

        ``timeout_ms`` arms a cooperative deadline (ISSUE 16): expiry
        raises QueryTimeout, or with ``partial_results=True`` the
        windows scanned before expiry keep their exact hits and the
        remainder come back empty."""
        from .resilience import admission_gate, deadline_scope
        token = admission_gate.acquire(name)
        try:
            queue_ms = getattr(token, "queue_ms", 0.0)
            if timeout_ms is not None:
                with deadline_scope(timeout_ms, partial_results):
                    return self._query_windows_body(name, windows,
                                                    queue_ms=queue_ms)
            return self._query_windows_body(name, windows,
                                            queue_ms=queue_ms)
        finally:
            token.release()

    def _query_windows_body(self, name: str, windows,
                            queue_ms: float = 0.0) -> list[np.ndarray]:
        store = self._store(name)
        if store.batch is None or len(store.batch) == 0:
            if store.multihost:
                # a zero-local-row process must still enter the window
                # collectives its peers run (see query_result)
                if store.batch is None:
                    store.batch = FeatureBatch.empty(store.sft)
            else:
                return [np.empty(0, dtype=np.int64) for _ in windows]
        sft = store.sft
        if sft.name not in self._interceptors:
            from .planning.interceptor import load_interceptors
            self._interceptors[sft.name] = load_interceptors(sft)
        # guards/rewrites must see every scan: with interceptors configured
        # take the (slower) per-window planner path, which applies them;
        # schemas restricting their index set also take the planner path
        # (it honors the restriction)
        if (store.lean and store.lean_kind == "z3"
                and not self._interceptors[sft.name]):
            # lean fast path (z3 point schemas): ALL windows (timed or
            # not — the index clamps open bounds to the data extent)
            # through the lean index's single batched multi-window
            # program; non-point (xz2) lean schemas take the per-window
            # planner path below (review r5)
            from .obs import span as obs_span
            with obs_span("query", schema=name,
                          windows=len(windows), lean=True) as sp:
                if sp.recording and queue_ms:
                    sp.set_attr("admission.queue_ms", round(queue_ms, 3))
                t0 = time.time()
                hits = store.index("z3").query_many(
                    [(boxes, lo, hi) for boxes, lo, hi in windows])
                allowed = self._effective_mask(store)
                if allowed is not None:
                    hits = _apply_mask_global(store, hits, allowed)
                from .metrics import registry as _metrics
                _metrics.counter(f"query.{name}.windows").inc(len(windows))
                n_hits = int(sum(len(h) for h in hits))
                sp.set_attr("hits", n_hits)
                self._audit_record(name, f"batched windows[{len(windows)}]",
                                   {}, None, (time.time() - t0) * 1e3,
                                   n_hits)
                return hits
        enabled = sft.enabled_indices
        use_fast = (sft.is_points and sft.dtg_field
                    and not self._interceptors[sft.name]
                    and not store.lean
                    and (enabled is None
                         or {"z2", "z3"} <= set(enabled)))
        if not use_fast:
            from .filters.ast import And, BBox, During, Or
            from .resilience import AdmissionToken, check_cancel
            out = []
            for boxes, lo, hi in windows:
                # partial expiry: remaining windows answer empty (the
                # caller flagged partial; scanned windows stay exact).
                # The inner query reuses the admission slot the
                # query_windows entry point already holds (a nested
                # acquire would self-deadlock a 1-slot gate).
                if check_cancel("query_windows"):
                    out.append(np.empty(0, dtype=np.int64))
                    continue
                parts = [BBox(sft.geom_field, *b) for b in boxes]
                f = parts[0] if len(parts) == 1 else Or(tuple(parts))
                if sft.dtg_field and not (lo is None and hi is None):
                    f = And((f, During(sft.dtg_field, lo, hi)))
                out.append(self._query_result_ex(
                    name, Query.of(f),
                    _token=AdmissionToken(None))[0].positions)
            return out
        from .obs import span as obs_span
        with obs_span("query", schema=name, windows=len(windows)) as sp:
            if sp.recording and queue_ms:
                sp.set_attr("admission.queue_ms", round(queue_ms, 3))
            t0 = time.time()
            # untimed windows (both bounds None) scan the Z2 index: with
            # the time axis unconstrained, z3 covering ranges degrade to
            # near full-bin scans, while z2 ranges stay tight
            untimed = [i for i, (_, lo, hi) in enumerate(windows)
                       if lo is None and hi is None]
            if len(untimed) == len(windows):
                hits = store.z2_index().query_many([w[0] for w in windows])
            elif not untimed:
                hits = store.z3_index().query_many(windows)
            else:
                uset = set(untimed)
                timed_idx = [i for i in range(len(windows))
                             if i not in uset]
                z2_hits = store.z2_index().query_many(
                    [windows[i][0] for i in untimed])
                z3_hits = store.z3_index().query_many(
                    [windows[i] for i in timed_idx])
                hits = [None] * len(windows)
                for j, i in enumerate(untimed):
                    hits[i] = z2_hits[j]
                for j, i in enumerate(timed_idx):
                    hits[i] = z3_hits[j]
            # _effective_mask (restricted + tombstones), not vis_mask:
            # the restricted decision is AGREED under multihost
            # (per-process vis_mask may be None on one process and set
            # on another — a divergent gate would strand peers in the
            # allgather below)
            allowed = self._effective_mask(store)
            if allowed is not None:
                hits = _apply_mask_global(store, hits, allowed)
            from .metrics import registry as _metrics
            _metrics.counter(f"query.{name}.windows").inc(len(windows))
            n_hits = int(sum(len(h) for h in hits))
            sp.set_attr("hits", n_hits)
            self._audit_record(name, f"batched windows[{len(windows)}]",
                               {}, None, (time.time() - t0) * 1e3, n_hits)
            return hits

    # -- fused serving plane (ISSUE 17) -----------------------------------
    def query_fused(self, name: str, query="INCLUDE", *,
                    timeout_ms: float | None = None,
                    partial_results: bool = False,
                    tenant: str = "") -> QueryResult:
        """Run a query through the fusion scheduler: concurrent
        compatible queries (lean z3 point schema, pure bbox(+time)
        predicate, no projections/sorts/interceptors) coalesce into ONE
        batched decompose + multi-window device scan and demux their
        per-request positions — bit-exact against
        :meth:`query_result`, pinned by tests.  Incompatible queries
        bypass to the solo path untouched.

        ``tenant`` (or a ``TENANT`` query hint, or the web ``X-Tenant``
        header) keys per-tenant deficit-weighted round-robin in batch
        assembly so a flooding tenant cannot starve the rest; each
        request still acquires its own admission token (FIFO-fair), so
        the gate's view of in-flight work stays truthful."""
        from .metrics import SERVING_BYPASS
        from .metrics import registry as _metrics
        from .resilience import CancelScope, admission_gate
        q = query if isinstance(query, Query) else Query.of(query)
        tenant = tenant or str(q.hints.get("TENANT", "") or "")
        store = self._store(name)
        window = self._fusible_window(name, store, q)
        if window is None:
            _metrics.counter(SERVING_BYPASS).inc()
            return self.query_result(name, q, timeout_ms=timeout_ms,
                                     partial_results=partial_results)
        token = admission_gate.acquire(name)
        try:
            from .obs import span as obs_span
            scope = (CancelScope(timeout_ms, partial_results)
                     if timeout_ms is not None else None)
            with obs_span("query", schema=name, fused=True) as sp:
                if sp.recording:
                    sp.set_attr("filter", repr(q.filter))
                    if tenant:
                        sp.set_attr("tenant", tenant)
                    queue_ms = getattr(token, "queue_ms", 0.0)
                    if queue_ms:
                        sp.set_attr("admission.queue_ms",
                                    round(queue_ms, 3))
                t_sub = time.perf_counter()
                outcome = self._fusion.submit(
                    ("fuse", name), window,
                    lambda ws: self._fused_windows_dispatch(name, ws),
                    scope=scope, partial=partial_results, tenant=tenant,
                    schema=name)
                positions = outcome.positions
                if sp.recording:
                    # every scheduler millisecond that was NOT the batch
                    # executing is coalesce wait: the linger window plus
                    # wake-up/demux latency
                    submit_ms = (time.perf_counter() - t_sub) * 1e3
                    sp.set_attr("coalesce.ms", round(max(
                        outcome.coalesce_ms,
                        submit_ms - outcome.dispatch_ms), 3))
                    sp.set_attr("fused.dispatch.ms", outcome.dispatch_ms)
                    sp.set_attr("hits", int(len(positions)))
                from .planning.strategy import FilterStrategy
                with obs_span("query.materialize", rows=len(positions)):
                    batch = (store.batch.take(positions)
                             if store.batch is not None
                             else FeatureBatch.empty(store.sft))
            return QueryResult(batch, positions,
                               FilterStrategy("fused",
                                              float(len(positions))),
                               0.0, 0.0, local_rows=positions,
                               timed_out=outcome.timed_out)
        finally:
            token.release()

    def _fusible_window(self, name: str, store: _SchemaStore, q: Query):
        """The fused-path compatibility gate: the ``(boxes, lo, hi)``
        window this query fuses as, or None to bypass.  Conservative
        by design — only the shapes whose fused execution is provably
        identical to solo fuse: lean z3 point schemas with no
        interceptors, no per-caller visibility (auth providers can
        carry per-thread auths; the dispatch runs on the LEADER's
        thread), single-host, and a hint/projection/sort-free query
        whose filter is a pure bbox(+time) predicate."""
        from .config import ServingProperties
        if not ServingProperties.FUSE_ENABLED.get():
            return None
        if not (store.lean and store.lean_kind == "z3"):
            return None
        if store.multihost or self._auth_provider is not None:
            return None
        sft = store.sft
        if sft.name not in self._interceptors:
            from .planning.interceptor import load_interceptors
            self._interceptors[sft.name] = load_interceptors(sft)
        if self._interceptors[sft.name]:
            return None
        if (q.properties is not None or q.sort_by is not None
                or q.max_features is not None or q.crs):
            return None
        if any(k != "TENANT" for k in q.hints):
            return None
        from .serving import extract_fused_window
        return extract_fused_window(sft, q.filter)

    def _fused_windows_dispatch(self, name: str, windows):
        """One fused device dispatch for a batch of compatible
        requests: the lean z3 ``query_many`` program over every
        member's window, capacity-bucketed so the warm path never
        recompiles — the window count pads to the next power of two by
        duplicating window 0 (bounded extra scan work, log-many
        compiled shapes; ``coded_pos_bits``/``qtlo``/``qthi`` shapes
        depend on the window count).  Padded outputs are dropped
        before demux.  No admission here: every member holds its own
        token (see :meth:`query_fused`)."""
        store = self._store(name)
        if store.batch is None or len(store.batch) == 0:
            return [np.empty(0, dtype=np.int64) for _ in windows]
        n = len(windows)
        n_pad = 1 << max(0, (n - 1).bit_length())
        padded = list(windows) + [windows[0]] * (n_pad - n)
        t0 = time.time()
        hits = store.index("z3").query_many(padded)
        allowed = self._effective_mask(store)
        if allowed is not None:
            hits = _apply_mask_global(store, hits, allowed)
        hits = hits[:n]
        from .metrics import registry as _metrics
        _metrics.counter(f"query.{name}.windows").inc(n)
        n_hits = int(sum(len(h) for h in hits))
        self._audit_record(name, f"fused windows[{n}]", {}, None,
                           (time.time() - t0) * 1e3, n_hits)
        return hits

    def explain(self, name: str, query="INCLUDE") -> str:
        from .planning.explain import ExplainString
        ex = ExplainString()
        self.query_result(name, query, ex)
        return str(ex)

    def explain_analyze(self, name: str, query="INCLUDE"):
        """EXPLAIN ANALYZE: run the query under forced trace capture
        and return the merged plan + measured actuals (strategy options
        with estimated costs, the chosen estimate, actual rows
        scanned/matched, mispredict ratio, per-phase wall/device ms) —
        the reference's ``explainQuery`` with real numbers (ISSUE 9).
        Returns an :class:`~geomesa_tpu.obs.ExplainAnalyzeResult`
        (``render()`` for text, ``to_json()`` for the web surface)."""
        from .obs.explain_analyze import explain_analyze
        return explain_analyze(self, name, query)

    def storage_report(self) -> dict:
        """Walk every schema's indexes/caches/column store, reconcile
        the accounted byte totals against actual array nbytes, publish
        the ``storage.*`` gauges, and return the report (obs/resource;
        served at ``GET /debug/storage``)."""
        from .obs.resource import publish_storage_gauges, storage_report
        rep = storage_report(self)
        publish_storage_gauges(self, rep)
        return rep

    def heat_report(self, limit: int | None = None) -> dict:
        """Access-temperature report (obs/heat, ISSUE 12): every lean
        generation ranked hot→cold by decayed touch temperature,
        joined with its current device/host placement from the storage
        accounting, plus per-(schema, index) aggregates — the workload
        picture the tier autopilot (ROADMAP item 6) consumes.  Also
        publishes the ``heat.*`` gauges.  Served at
        ``GET /debug/heat``."""
        from .obs.heat import heat_report, publish_heat_gauges
        rep = heat_report(self)
        publish_heat_gauges(self, rep)   # gauges see the FULL report
        if limit is not None:
            rep["generations"] = rep["generations"][:limit]
        return rep

    # -- stats (GeoMesaStats analog) --------------------------------------
    def _restricted_mask(self, store: _SchemaStore) -> np.ndarray | None:
        """Visibility mask when this caller cannot see every row (stats are
        observed over ALL writes, so restricted callers must not read them
        directly — that would leak counts/values/extents of hidden rows).

        Multihost: the restricted/unrestricted decision must be AGREED —
        one process's rows may all be visible while another's are not,
        and the restricted path runs collectives; a divergent decision
        would hang the store."""
        if self._auth_provider is None:
            return None
        mask = (store.vis_mask(self._auth_provider.get_authorizations())
                if store.batch is not None else None)
        if store.multihost:
            from .parallel.multihost import agreed_int
            if agreed_int(0 if mask is None else 1, "max") and mask is None:
                mask = np.ones(0 if store.batch is None
                               else len(store.batch), dtype=bool)
        return mask

    def _effective_mask(self, store: _SchemaStore,
                        only_if_restricted: bool = False) -> np.ndarray | None:
        """Restricted-visibility mask combined with lean tombstones —
        what the stats/bounds/window paths must treat as 'the rows this
        caller can see'.  With ``only_if_restricted`` the tombstones ride
        along only when a visibility restriction exists: the global
        sketches already exclude deleted rows (delete-time recompute),
        so an unrestricted caller must NOT trigger the O(n) re-observe
        path just because tombstones exist."""
        mask = self._restricted_mask(store)
        tomb = store.tombstone
        if tomb is None or (only_if_restricted and mask is None):
            return mask
        live = ~tomb
        return live if mask is None else (mask & live)

    def get_count(self, name: str, query=None) -> int:
        store = self._store(name)
        if query is not None:
            # positions, not the batch: the global hit count under
            # multihost (the local batch is just this process's slice)
            return len(self.query_result(name, query).positions)
        mask = self._effective_mask(store)
        if mask is not None:
            n = int(mask.sum())
            if store.multihost:
                from .parallel.multihost import agreed_int
                n = agreed_int(n, "sum")
            return n
        # multihost: stats_map merges per-process sketches → global count
        return store.stats_map()["count"].count

    def get_bounds(self, name: str):
        store = self._store(name)
        n_here = 0 if store.batch is None else len(store.batch)
        if n_here == 0 and not store.multihost:
            return None
        if store.lean:
            from .geometry.types import Envelope
            mask = self._effective_mask(store)
            if mask is None:
                env = store.batch.envelope
                pairs = (np.array([env]) if env is not None
                         else np.empty((0, 4)))
            else:
                # masked extent straight from the x/y columns — never
                # the O(n·4) per-feature bbox materialization
                x, y = store.batch.geom_xy()
                pairs = (np.array([[x[mask].min(), y[mask].min(),
                                    x[mask].max(), y[mask].max()]])
                         if mask.any() else np.empty((0, 4)))
            if store.multihost:
                from .parallel.multihost import allgather_concat
                pairs = allgather_concat(np.asarray(pairs, np.float64))
            if not len(pairs):
                return None
            return Envelope(float(pairs[:, 0].min()),
                            float(pairs[:, 1].min()),
                            float(pairs[:, 2].max()),
                            float(pairs[:, 3].max()))
        # the restricted-mask decision is collective under multihost —
        # it must run on EVERY process, zero-local-row ones included
        mask = self._effective_mask(store)
        if n_here:
            bb = store.batch.geom_bbox()
            if mask is not None:
                bb = bb[mask] if mask.any() else bb[:0]
        else:
            bb = np.empty((0, 4))
        if store.multihost:
            # collective min/max over the per-process local extents
            from .parallel.multihost import allgather_concat
            local = (np.array([[bb[:, 0].min(), bb[:, 1].min(),
                                bb[:, 2].max(), bb[:, 3].max()]])
                     if len(bb) else np.empty((0, 4)))
            bb = allgather_concat(local)
        if not len(bb):
            return None
        from .geometry.types import Envelope
        return Envelope(float(bb[:, 0].min()), float(bb[:, 1].min()),
                        float(bb[:, 2].max()), float(bb[:, 3].max()))

    def _attr_guarded(self, store: _SchemaStore, attr: str) -> bool:
        """True when this caller cannot see every value of the attribute.
        Multihost: agreed across processes (any process guarded → all
        treat it guarded) so downstream collectives never diverge."""
        guarded = False
        if self._auth_provider is not None and attr in store.attr_visibilities:
            from .security import visibility_mask
            guarded = not visibility_mask(
                store.attr_visibilities[attr],
                self._auth_provider.get_authorizations()).all()
        if store.multihost and self._auth_provider is not None:
            from .parallel.multihost import agreed_int
            guarded = bool(agreed_int(int(guarded), "max"))
        return guarded

    def get_attribute_bounds(self, name: str, attr: str):
        store = self._store(name)
        if self._attr_guarded(store, attr):
            return None
        mask = self._effective_mask(store, only_if_restricted=True)
        if mask is not None:
            col = store.batch.column(attr)[mask]
            if store.multihost:
                # dtype gate decided from the SCHEMA type (identical on
                # every process): string/object columns cannot ride the
                # float64 allgather — their bounds travel as strings
                # (ADVICE r3)
                a_type = store.sft.attribute(attr).type
                if a_type in ("int", "long", "float", "double", "date",
                              "bool"):
                    from .parallel.multihost import allgather_concat
                    pairs = (np.array([[col.min(), col.max()]])
                             if len(col) else np.empty((0, 2)))
                    pairs = allgather_concat(np.asarray(pairs, np.float64))
                    if not len(pairs):
                        return None
                    return pairs[:, 0].min(), pairs[:, 1].max()
                if a_type != "string":
                    # bytes/json have no collective ordering protocol —
                    # str() coercion would return repr-mangled bounds
                    # inconsistent with the single-host path
                    return None
                # each process contributes its [min, max] (or nothing);
                # the global bounds are min/max over the flat gather —
                # pairing doesn't matter since both ends are present
                from .parallel.multihost import allgather_strings
                vals = [v for v in col if v is not None]
                local = ([str(min(vals)), str(max(vals))] if vals else [])
                flat = allgather_strings(np.array(local, dtype=object))
                if not len(flat):
                    return None
                return min(flat), max(flat)
            if not len(col):
                return None
            return col.min(), col.max()
        mm = store.stats_map().get(f"{attr}_minmax")
        return None if mm is None or mm.is_empty else mm.bounds

    def stat(self, name: str, key: str) -> Stat | None:
        """Sketches for this schema.  For restricted callers the global
        sketches (observed over all rows) are recomputed over the visible
        subset so hidden values cannot leak through TopK/enumeration."""
        store = self._store(name)
        stats = store.stats_map()  # multihost: globally merged
        attr = getattr(stats.get(key), "attr", None)
        if attr and self._attr_guarded(store, attr):
            return None
        mask = self._effective_mask(store, only_if_restricted=True)
        s = stats.get(key)
        if mask is None or s is None:
            return s
        # rebuild the same stat type over the visible rows only;
        # multihost merges the per-process re-observations globally
        if store.lean:
            # chunked: never materialize the full visible row set;
            # multihost re-merges per-process re-observations
            return store.merge_stat_global(
                store._lean_observe_masked(s, mask))
        fresh = s.fresh_copy()
        fresh.observe(store.batch.take(np.flatnonzero(mask)))
        return store.merge_stat_global(fresh)

    # -- metadata catalog persistence -------------------------------------
    def _persist_schema(self, sft: FeatureType) -> None:
        if not self._catalog_dir:
            return
        store = self._schemas.get(sft.name)
        versions = (store.index_versions if store is not None
                    else dict(CURRENT_INDEX_VERSIONS))
        path = os.path.join(self._catalog_dir, f"{sft.name}.schema.json")
        with open(path, "w") as f:
            json.dump({"name": sft.name, "spec": sft.spec_string(),
                       "index_versions": versions,
                       "updated": time.time()}, f)

    def migrate_schema(self, name: str) -> dict:
        """Upgrade a schema's index layouts to the CURRENT versions (the
        reference's index-format migration on update, e.g.
        AttributeIndexV2..V7 upgrades): indexes rebuild from the column
        store with current key math on next use, and the catalog records
        the new versions.  Returns the pre-migration versions."""
        store = self._store(name)
        old = dict(store.index_versions)
        with self._catalog_lock():
            store.index_versions = dict(CURRENT_INDEX_VERSIONS)
            # stale layouts must not serve another query
            store._indexes.clear()
            store._dirty = True
            if "geomesa.index.versions" in store.sft.user_data:
                ud = dict(store.sft.user_data)
                del ud["geomesa.index.versions"]
                store.sft = FeatureType(store.sft.name, store.sft.attributes,
                                        store.sft.default_geom, ud)
            self._persist_schema(store.sft)
        return old

    def stats(self, name: str, query="INCLUDE",
              spec: str = "Count()"):
        """Evaluate a Stat DSL over the features matching ``query``
        (the reference's stats-count / stats-histogram surface,
        STATS_STRING hint).  Lean tiered schemas answer pushable specs
        from per-run sketches folded next to the index keys — sealed
        generations served from the sketch-partial cache, zero
        candidate materialization (process/stats_process, ISSUE 3);
        everything else materializes hits and folds through the Stat
        monoid."""
        from .process.stats_process import stats_process
        return stats_process(self, name, query, spec)

    def stats_analyze(self, name: str) -> int:
        """Recompute a schema's sketches from its stored rows and persist
        them (the reference's stats-analyze / StatsRunner); returns the
        observed feature count."""
        store = self._store(name)
        store.recompute_stats()
        self.persist_stats(name)
        return 0 if store.batch is None else len(store.batch)

    def compact(self, name: str,
                budget_ms: float | None = None) -> dict:
        """Explicit LSM compaction of a lean schema's generational
        indexes — the maintenance analog of the reference's
        ``compact`` tool command (Accumulo major compaction): fold
        sealed same-tier sorted runs into O(log) merged runs so query
        and density fan-out stops growing with ingest history.

        ``budget_ms`` bounds the work; interrupted compaction resumes
        on the next call (each eligible index makes ≥ 1 merge of
        progress).  Returns per-index ``{"merged_groups",
        "generations", "tiers"}`` — empty for non-lean schemas, whose
        indexes compact through their own tail-rebuild policy
        (_maybe_compact)."""
        return self._store(name).compact_lean(budget_ms=budget_ms)

    def _pyramid_listener(self, name: str):
        """The generation-lifecycle hook parked on every schema store
        (ISSUE 18): on seal — when ``geomesa.density.pyramid.build`` is
        ``seal`` at fire time — run one build-behind pyramid pass as a
        registered background job.  Best-effort by contract: a failed
        build must never fail the write that sealed the generation
        (queries stay exact through the scan fallback)."""
        def on_event(kind: str, gen_ids: list) -> None:
            if kind != "seal":
                return
            from .config import DensityProperties
            if str(DensityProperties.PYRAMID_BUILD.get() or "off") != "seal":
                return
            from .jobs import run_pyramid_build
            try:
                run_pyramid_build(self, name)
            except Exception:  # noqa: BLE001 — build-behind is best-effort
                pass
        return on_event

    def build_pyramids(self, name: str) -> int:
        """Build density pyramids for a lean schema's sealed scale-index
        generations (ISSUE 18): one whole-world multi-resolution grid
        stack per generation, cached under the compaction-invalidated
        partial-cache policy so interactive heatmap/tile requests stop
        rescanning immutable history.  Idempotent — generations that
        already have pyramids are skipped.  Returns the number built
        (0 for non-lean schemas or indexes without pyramid support)."""
        return self._store(name).build_pyramids()

    def density_tile(self, name: str, z: int, x: int, y: int, *,
                     tile: int = 256, query=None,
                     timeout_ms: float | None = None) -> np.ndarray:
        """One ``(tile, tile)`` density grid for slippy-map tile
        ``(z, x, y)`` on the plate-carrée world grid (ISSUE 18).

        Serving holds one admission token and an optional deadline like
        any query.  With no ``query``, no auth provider, and no
        tombstones, a lean point schema serves the tile from the scale
        index's density path — pyramid-cached for sealed generations
        while ``tile·2^z`` stays at/below the configured pyramid base,
        live/pyramid-less generations rescanned (exact either way).
        Otherwise the tile runs through :func:`density_process` with
        the tile envelope ANDed into the filter (CQL string)."""
        import time
        from .index.pyramid import tile_env
        from .metrics import (
            TILE_REQUEST_MS, TILE_REQUESTS, registry as _metrics,
        )
        from .obs import span as obs_span
        from .resilience import admission_gate, deadline_scope
        z, x, y = int(z), int(x), int(y)
        n = 1 << z
        if not (0 <= z <= 30) or not (0 <= x < n and 0 <= y < n):
            raise ValueError(f"tile ({z}/{x}/{y}) out of range")
        store = self._store(name)
        token = admission_gate.acquire(name)
        t0 = time.perf_counter()
        try:
            with deadline_scope(timeout_ms, False):
                with obs_span("tile.render", schema=name, z=z, x=x,
                              y=y, tile=tile) as sp:
                    queue_ms = getattr(token, "queue_ms", 0.0)
                    if sp.recording and queue_ms:
                        sp.set_attr("admission.queue_ms",
                                    round(queue_ms, 3))
                    _metrics.counter(TILE_REQUESTS).inc()
                    has_tomb = (store.tombstone is not None
                                and bool(store.tombstone.any()))
                    if (query is None and self._auth_provider is None
                            and store.lean and not has_tomb
                            and store.batch is not None):
                        idx = store._lean_index()
                        if hasattr(idx, "density_tile"):
                            return np.asarray(
                                idx.density_tile(z, x, y, tile),
                                np.float64)
                    from .process.density import density_process
                    env = tile_env(z, x, y)
                    gf = self.get_schema(name).geom_field
                    bbox = (f"BBOX({gf}, {env[0]}, {env[1]}, "
                            f"{env[2]}, {env[3]})")
                    q = bbox if query is None else f"({query}) AND {bbox}"
                    return np.asarray(
                        density_process(self, name, q, env, tile, tile),
                        np.float64)
        finally:
            _metrics.timer(TILE_REQUEST_MS).update(
                (time.perf_counter() - t0) * 1e3)
            token.release()

    def _stats_path(self, name: str, store) -> str:
        """Per-schema stats file.  Multihost (with >1 process, matching
        the lean id-prefix gating in _init_lean): sketches hold THIS
        process's local observations, so each process persists (and
        reloads) its own file — a shared path would race on write and
        answer with one arbitrary process's locals on load."""
        suffix = ""
        if store.multihost:
            import jax
            if jax.process_count() > 1:
                suffix = f".p{jax.process_index()}"
        return os.path.join(self._catalog_dir,
                            f"{name}{suffix}.stats.json")

    def _proc_stats_files(self, name: str) -> list[str]:
        """Per-process multihost stats files (``{name}.pN.stats.json``)
        in the catalog — the single definition of that naming scheme
        (rename/remove/merge all use it)."""
        if not self._catalog_dir or not os.path.isdir(self._catalog_dir):
            return []
        pat = re.compile(re.escape(name) + r"\.p\d+\.stats\.json")
        return sorted(os.path.join(self._catalog_dir, f)
                      for f in os.listdir(self._catalog_dir)
                      if pat.fullmatch(f))

    def persist_stats(self, name: str) -> None:
        if not self._catalog_dir:
            return
        store = self._store(name)
        with self._catalog_lock():
            path = self._stats_path(name, store)
            # COMMIT FIRST (tmp + atomic replace, the _flush_lean
            # discipline): a crash must never leave the catalog with
            # the old artifacts pruned and the new file missing or
            # truncated — next_fid would regress and REUSE deleted ids
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                # __meta__ rides along with the sketches: the auto-id
                # counter must survive reload, or deleting the highest
                # ids then reopening would re-derive a lower counter
                # from the surviving rows and resurrect deleted ids
                store.stats_generation += 1
                json.dump({"__meta__": {
                               "next_fid": store.next_fid,
                               "generation": store.stats_generation},
                           **{k: s.to_json()
                              for k, s in store._stats.items()}}, f)
            os.replace(tmp, path)
            # then prune superseded artifacts so a later topology-
            # boundary load cannot merge them in: a single-controller
            # persist retires the whole per-process family; a multihost
            # persist (process 0) retires files from a LARGER prior
            # topology (p >= count) — but never one whose .lean.pN row
            # snapshot still exists: its sketches were never merged
            # anywhere, and a later reopen at the old topology would
            # serve those rows with zeroed stats
            shared = os.path.join(self._catalog_dir,
                                  f"{name}.stats.json")
            if path == shared:
                victims = self._proc_stats_files(name)
            else:
                import jax
                victims = []
                if jax.process_index() == 0:
                    count = jax.process_count()
                    for p in self._proc_stats_files(name):
                        pn = int(os.path.basename(p).rsplit(
                            ".stats.json", 1)[0].rsplit(".p", 1)[1])
                        if pn >= count:
                            victims.append(p)
            for p in victims:
                pn_tag = os.path.basename(p).rsplit(
                    ".stats.json", 1)[0].rsplit(".p", 1)[1]
                if os.path.isdir(os.path.join(
                        self._catalog_dir, f"{name}.lean.p{pn_tag}")):
                    continue
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass   # concurrent persist already pruned it

    def load_stats(self, name: str) -> None:
        """Reload persisted sketches + the fid counter, across PROCESS
        TOPOLOGY boundaries: the newest artifact family wins — ordered
        by the monotonic ``__meta__`` generation counter when present,
        mtime as the pre-counter fallback (a stale shared file must not
        shadow newer per-process files or
        vice versa, or next_fid would regress and REUSE deleted ids),
        per-process files merge on a single-controller open, and a
        shared (global) file opened multihost loads its sketches on
        process 0 ONLY — every process loading global sketches as its
        'locals' would count each row process_count times through the
        global stats merge.  next_fid takes the max over EVERY stats
        artifact regardless of recency (monotone-safe)."""
        if not self._catalog_dir:
            return
        store = self._store(name)
        with self._catalog_lock():
            self._load_stats_locked(name, store)

    def _load_stats_locked(self, name: str, store) -> None:
        own = self._stats_path(name, store)
        shared = os.path.join(self._catalog_dir, f"{name}.stats.json")
        procs = self._proc_stats_files(name)

        def mtime(p):
            try:
                return os.path.getmtime(p)
            except OSError:
                return -1.0

        # every candidate artifact parses exactly ONCE (sketches are
        # large at scale; the arbitration below and the merge loop share
        # these dicts rather than re-reading files)
        parsed: dict[str, dict] = {}
        for p in {shared, own, *procs}:
            try:
                with open(p) as f:
                    parsed[p] = json.load(f)
            except (OSError, ValueError):
                pass   # absent, or pruned by a concurrent persist

        def recency(p):
            """(generation, mtime): the monotonic ``__meta__`` counter
            decides when present (an artifact carrying it is from a
            counter-writing catalog and is newer than any that doesn't);
            mtime is the fallback for pre-counter artifacts only —
            cross-host clock skew can mis-order mtimes on shared dirs
            (round-4 ADVICE)."""
            gen = ((parsed.get(p) or {}).get("__meta__")
                   or {}).get("generation", -1)
            return (int(gen), mtime(p))

        # (path, load_sketches) sources; next_fid reads every artifact
        sources: list = []
        live_procs = [p for p in procs if p in parsed]
        if own == shared:       # single-controller (or 1-proc multihost)
            if live_procs and max(map(recency, live_procs)) \
                    > recency(shared):
                sources = [(p, True) for p in live_procs]
            elif shared in parsed:
                sources = [(shared, True)]
        else:                   # multihost, >1 process
            import jax
            if own in parsed and recency(own) >= recency(shared):
                sources = [(own, True)]
            elif shared in parsed:
                sources = [(shared, jax.process_index() == 0)]
        for p in parsed:
            if p not in {s for s, _ in sources}:
                sources.append((p, False))
        if not sources:
            return
        drop_freq = getattr(self, "_catalog_found_version",
                            CATALOG_VERSION) < 3
        merged: dict = {}
        poisoned: set = set()
        for path, with_sketches in sources:
            raw = dict(parsed[path])   # parsed once above
            meta = raw.pop("__meta__", None)  # absent in older catalogs
            if meta is not None:
                store.next_fid = max(store.next_fid,
                                     int(meta.get("next_fid", 0)))
                store.stats_generation = max(
                    store.stats_generation,
                    int(meta.get("generation", 0)))
            if not with_sketches:
                continue
            if drop_freq:
                # pre-v3 Frequency tables used the old string hashing —
                # reading them with the current hash would answer from
                # the wrong buckets; drop them (rebuilt by the next
                # stats_analyze)
                raw = {k: v for k, v in raw.items()
                       if v.get("kind") != "frequency"}
            for k, v in raw.items():
                if k in poisoned:
                    continue
                s = stat_from_json(v)
                if k not in merged:
                    merged[k] = s
                    continue
                try:
                    merged[k] = merged[k].merge(s)
                except ValueError:
                    # per-process sketches can be structurally
                    # incompatible (e.g. histograms binned over each
                    # process's LOCAL bounds) — an unopenable catalog
                    # is worse than a dropped sketch; stats_analyze
                    # rebuilds it
                    merged.pop(k, None)
                    poisoned.add(k)
        if merged:
            # re-seed any default sketch the merge dropped (poisoned) or
            # an older artifact never carried — code that indexes
            # _stats["count"] unconditionally must never find the key
            # missing after a reopen (round-4 ADVICE: an unopenable
            # catalog is worse than a dropped sketch, and a dropped
            # sketch must not become an unopenable catalog either)
            for k, s in store._stats.items():
                merged.setdefault(k, s)
            store._stats = merged

    # -- data persistence (FSDS-analog: parquet files under the catalog) --
    def flush(self, name: str) -> None:
        """Persist the schema's features as parquet under the catalog dir
        (the durable-store role of the reference's FileSystemDataStore)."""
        if not self._catalog_dir:
            return
        store = self._store(name)
        if store.batch is None:
            return
        if store.lean:
            self._flush_lean(name, store)
            return
        from .io.export import to_parquet
        to_parquet(store.batch, os.path.join(self._catalog_dir, f"{name}.parquet"))
        if store.visibilities is not None or store.attr_visibilities:
            # dictionary-encoded: visibilities are low-cardinality
            payload: dict = {}
            if store.visibilities is not None:
                uniq, codes = np.unique(store.visibilities.astype(str),
                                        return_inverse=True)
                payload["labels"] = uniq.tolist()
                payload["codes"] = codes.tolist()
            if store.attr_visibilities:
                attrs = {}
                for attr, col in store.attr_visibilities.items():
                    u, c = np.unique(col.astype(str), return_inverse=True)
                    attrs[attr] = {"labels": u.tolist(),
                                   "codes": c.tolist()}
                payload["attributes"] = attrs
            with open(os.path.join(self._catalog_dir,
                                   f"{name}.vis.json"), "w") as f:
                json.dump(payload, f)
        self.persist_stats(name)

    #: rows per lean snapshot part — bounds the host working set of a
    #: flush/reload to one part's columns, never the dataset
    LEAN_PART_ROWS = 1 << 22

    def _lean_dir(self, name: str, store) -> str:
        """Snapshot directory for a lean schema.  Multihost: each
        process snapshots its LOCAL rows under its id prefix (``p0``,
        ``p1``, …) so a shared catalog dir composes."""
        # `is not None`, NOT truthiness: at reload time the batch exists
        # but is EMPTY, and dropping the multihost suffix there would
        # silently miss every flushed row
        suffix = (store.batch.id_prefix.rstrip(".")
                  if store.batch is not None else "")
        return os.path.join(self._catalog_dir,
                            f"{name}.lean" + (f".{suffix}" if suffix
                                              else ""))

    def _flush_lean(self, name: str, store) -> None:
        """Chunked parquet snapshot of a lean schema: bounded column
        parts (no id materialization — lean ids are implicit row
        numbers) plus a manifest.  The durable-store role of the
        reference's FileSystemDataStore (fs/storage) at lean scale:
        flushing 100M+ rows streams ``LEAN_PART_ROWS`` slices, so peak
        host memory is one part.  Per-ROW state (tombstones,
        visibility codes) rides inside the parts as reserved columns —
        a JSON list of 100M codes would be gigabytes of host string.

        Crash-safe: parts carry a per-flush stamp, the manifest is
        swapped in atomically (tmp + ``os.replace``) LAST, and only
        then are prior-flush parts deleted — a crash at any point
        leaves the previous manifest referencing its intact parts."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        d = self._lean_dir(name, store)
        os.makedirs(d, exist_ok=True)
        mpath = os.path.join(d, "manifest.json")
        stamp = 0
        if os.path.exists(mpath):
            with open(mpath) as f:
                stamp = int(json.load(f).get("stamp", 0)) + 1
        n = len(store.batch)
        vis_labels = None
        if store.visibilities is not None:
            # label set built per slice: an astype(str) of the WHOLE
            # column would copy gigabytes at 100M rows, breaking the
            # one-part memory bound
            slice_labels = [
                np.unique(store.visibilities[lo:min(
                    lo + self.LEAN_PART_ROWS, n)].astype(str))
                for lo in range(0, n, self.LEAN_PART_ROWS)]
            vis_labels = (np.unique(np.concatenate(slice_labels))
                          if slice_labels else np.empty(0, dtype=str))
        parts = []
        for i, lo in enumerate(range(0, n, self.LEAN_PART_ROWS)):
            hi = min(lo + self.LEAN_PART_ROWS, n)
            view = store.batch.slice_view(lo, hi)
            # the (n, 4) per-feature bbox column is derived state —
            # reconstructed from the packed geometries at reload
            bbox_col = (f"{store.sft.geom_field}_bbox"
                        if store.batch.geoms is not None else None)
            cols = {k: pa.array(np.asarray(v))
                    for k, v in view.columns.items() if k != bbox_col}
            if store.batch.geoms is not None:
                # non-point lean schemas (round-5): per-part WKB keeps
                # the one-part memory bound; reload re-packs per part
                from .geometry.wkb import wkb_encode
                gpart = store.batch.geoms.take(np.arange(lo, hi))
                cols["__wkb__"] = pa.array(
                    [wkb_encode(gpart.geometry(j))
                     for j in range(hi - lo)], type=pa.binary())
            if store.tombstone is not None:
                cols["__tombstone__"] = pa.array(store.tombstone[lo:hi])
            if vis_labels is not None:
                cols["__vis__"] = pa.array(np.searchsorted(
                    vis_labels,
                    store.visibilities[lo:hi].astype(str)).astype(
                    np.int32))
            fname = f"part-{stamp:06d}-{i:05d}.parquet"
            pq.write_table(pa.table(cols), os.path.join(d, fname))
            parts.append(fname)
        manifest: dict = {
            "n": n, "parts": parts, "stamp": stamp,
            "envelope": list(store.batch.envelope)
            if store.batch.envelope else None,
            "id_prefix": store.batch.id_prefix,
            "has_tombstones": store.tombstone is not None,
        }
        if vis_labels is not None:
            manifest["vis_labels"] = vis_labels.tolist()
        tmp = mpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
        os.replace(tmp, mpath)        # the commit point
        live = set(parts)
        for f in os.listdir(d):       # prior-flush parts, now orphaned
            if f.startswith("part-") and f not in live:
                os.remove(os.path.join(d, f))
        self.persist_stats(name)

    def _load_lean(self, name: str) -> None:
        """Restore a lean snapshot: append each part's columns by
        reference (O(part) per step), restore tombstones/visibilities,
        and leave the index to the lazy streaming rebuild in
        ``_lean_index`` (bounded slices through the same append path
        the live store uses)."""
        import pyarrow.parquet as pq
        store = self._schemas[name]
        d = self._lean_dir(name, store)
        mpath = os.path.join(d, "manifest.json")
        if not os.path.exists(mpath):
            return
        with open(mpath) as f:
            manifest = json.load(f)
        from .features.lean import ChunkView
        tomb_parts: list = []
        vis_parts: list = []
        vis_labels = (np.asarray(manifest["vis_labels"], dtype=object)
                      if manifest.get("vis_labels") is not None else None)
        for fname in manifest["parts"]:
            table = pq.read_table(os.path.join(d, fname))
            cols = {c: table.column(c).to_numpy(zero_copy_only=False)
                    for c in table.column_names}
            if manifest.get("has_tombstones"):
                tomb_parts.append(
                    cols.pop("__tombstone__").astype(bool))
            if vis_labels is not None:
                vis_parts.append(
                    vis_labels[cols.pop("__vis__").astype(np.int64)])
            geoms = None
            if "__wkb__" in cols:
                from .geometry.packed import pack_geometries
                from .geometry.wkb import wkb_decode
                geoms = pack_geometries(
                    [wkb_decode(b) for b in cols.pop("__wkb__")])
                # restore the derived per-feature bbox column (flush
                # skipped it; later writes carry it, and the chunk
                # column sets must agree)
                cols[f"{store.sft.geom_field}_bbox"] = geoms.bbox
            n_part = table.num_rows
            if n_part:
                store.batch.append_batch(
                    ChunkView(store.sft, cols, n_part, geoms=geoms))
        if len(store.batch) != manifest["n"]:
            raise CatalogVersionError(
                f"lean snapshot {d} is inconsistent: manifest says "
                f"{manifest['n']} rows, parts hold {len(store.batch)}")
        if manifest.get("envelope"):
            store.batch.envelope = tuple(manifest["envelope"])
        if tomb_parts:
            store.tombstone = np.concatenate(tomb_parts)
        if vis_parts:
            store.visibilities = np.concatenate(vis_parts)
        store._dirty = True
        store._mutation_version += 1

    def _load_data(self, name: str) -> None:
        if self._schemas[name].lean:
            # sketches + fid counter from stats.json; row data from the
            # chunked parquet snapshot when one was flushed
            self.load_stats(name)
            self._load_lean(name)
            return
        path = os.path.join(self._catalog_dir, f"{name}.parquet")
        if os.path.exists(path):
            from .io.export import from_parquet
            store = self._schemas[name]
            store.batch = from_parquet(path, store.sft)
            store._id_set = None  # rebuilt lazily from the loaded rows
            store.next_fid = _max_numeric_id(store.batch.ids) + 1
            store._dirty = True
            vis_path = os.path.join(self._catalog_dir, f"{name}.vis.json")
            if os.path.exists(vis_path):
                with open(vis_path) as f:
                    enc = json.load(f)
                if "labels" in enc:
                    labels = np.asarray(enc["labels"], dtype=object)
                    store.visibilities = labels[np.asarray(enc["codes"], int)]
                else:
                    store.visibilities = np.full(len(store.batch), "",
                                                 dtype=object)
                for attr, e in enc.get("attributes", {}).items():
                    lbl = np.asarray(e["labels"], dtype=object)
                    store.attr_visibilities[attr] = lbl[
                        np.asarray(e["codes"], int)]
            else:
                store.visibilities = np.full(len(store.batch), "",
                                             dtype=object)
        # persisted sketches + the fid counter load whether or not rows
        # were ever flushed (stats_analyze without flush must survive a
        # reopen, and so must next_fid — ids are never reused)
        store = self._schemas[name]
        self.load_stats(name)
        # rebuild stats if none were persisted
        if (store.batch is not None and len(store.batch)
                and store._stats["count"].count == 0):
            for s in store._stats.values():
                s.observe(store.batch)

    def _load_catalog(self) -> None:
        for fn in os.listdir(self._catalog_dir):
            if fn.endswith(".schema.json"):
                try:
                    with open(os.path.join(self._catalog_dir, fn)) as f:
                        meta = json.load(f)
                except FileNotFoundError:
                    continue  # removed by a concurrent process mid-listing
                sft = parse_spec(meta["name"], meta["spec"])
                store = _SchemaStore(sft, mesh=self._mesh,
                                         multihost=self._multihost)
                store.pyramid_trigger = self._pyramid_listener(sft.name)
                # recorded layout versions win over spec defaults; v1
                # (pre-versioning) catalogs were written with the then-
                # current layouts, which match today's defaults
                if "index_versions" in meta:
                    store.index_versions = {
                        **CURRENT_INDEX_VERSIONS,
                        **{k: int(v) for k, v in
                           meta["index_versions"].items()}}
                self._schemas[sft.name] = store
                # same eager resolution create_schema does: a catalog
                # whose interceptor chain no longer imports fails at
                # open, where the operator is looking, not mid-query
                self._resolve_interceptors(sft)
                self._load_data(sft.name)
