"""Sharded XZ2/XZ3 indexes: intersects scans over non-point geometries
on a device mesh.

The reference serves XZ through exactly the same distributed scan as Z
(.../index/z2/XZ2IndexKeySpace.scala:44 feeding BatchScanPlan); here the
sorted code column plus per-feature bbox columns live sharded over the
mesh, and the candidate stage (seeks + bbox prefilter) runs as one
collective — replacing the host-only path of
:class:`geomesa_tpu.index.xz2.XZ2Index` for large geometry sets.  The
exact geometry predicate (`geometry_intersects`) stays on the host over
the candidate gids, mirroring the reference's client-side CQL re-check;
the device stage is the server-side filter analog.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..curve.binnedtime import TimePeriod, to_binned_time
from ..curve.xz2 import xz2_sfc
from ..curve.xz3 import xz3_sfc
from ..geometry.packed import PackedGeometry, pack_geometries
from ..geometry.predicates import geometry_intersects
from ..geometry.types import Geometry
from ..index.xz2 import _is_envelope
from ..index.z3 import _time_windows_by_bin
from ..ops.search import (
    expand_ranges, gather_capacity, pad_pow2, pad_ranges, searchsorted2,
)
from .mesh import device_mesh, shard_batch
from .scan import GID_PROC_SHIFT, _fetch_global

__all__ = ["ShardedXZ2Index", "ShardedXZ3Index"]


def _exact_recheck(cand: np.ndarray, geoms: PackedGeometry,
                   geometry: Geometry, multihost: bool) -> np.ndarray:
    """Exact geometry predicate over candidate gids.

    Single-controller: ``geoms`` holds every geometry, indexed by gid.
    Multihost: ``geoms`` holds only THIS process's geometries — each
    process re-checks its own candidates (the filter runs next to the
    data, AccumuloIndexAdapter.scala:181-195 role) and the survivors
    allgather; no process ever touches another's geometry payload."""
    from ..geometry.predicates import packed_intersects
    if not multihost:
        return np.asarray(cand, dtype=np.int64)[
            packed_intersects(geoms, geometry, cand)]
    import jax
    from .multihost import allgather_concat
    from .scan import decode_gids
    me = jax.process_index()
    procs, rows = decode_gids(cand)
    mine = cand[procs == me]
    keep = mine[packed_intersects(geoms, geometry, rows[procs == me])]
    return allgather_concat(np.asarray(keep, dtype=np.int64))

_SENTINEL_BIN = np.int32(np.iinfo(np.int32).max)
_SENTINEL_CODE = np.int64(np.iinfo(np.int64).max)


@lru_cache(maxsize=32)
def _xz_build_program(mesh: Mesh, with_bins: bool):
    """Per-shard sort of (code[, bin]) keys with gid + bbox (+dtg) payload."""
    n_in = 8 if with_bins else 6

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard"),) * (n_in + 1),
             out_specs=(P("shard"),) * n_in)
    def sort(*cols):
        *cols, vs = cols
        if with_bins:
            bs, cs, gs, *rest = cols
            bs = jnp.where(vs, bs, _SENTINEL_BIN)
            cs = jnp.where(vs, cs, _SENTINEL_CODE)
            gs = jnp.where(vs, gs, gs.dtype.type(-1))
            return jax.lax.sort((bs, cs, gs, *rest), dimension=0, num_keys=2)
        cs, gs, *rest = cols
        cs = jnp.where(vs, cs, _SENTINEL_CODE)
        gs = jnp.where(vs, gs, gs.dtype.type(-1))
        return jax.lax.sort((cs, gs, *rest), dimension=0, num_keys=1)

    return jax.jit(sort)


@lru_cache(maxsize=64)
def _xz2_scan_program(mesh: Mesh, capacity: int):
    """Collective candidate scan: per-shard seeks over the sorted code
    column + bbox-intersects prefilter against the query envelope."""

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 6 + (P(None),) * 2 + (P(),) * 4,
        out_specs=(P("shard"), P("shard")),
    )
    def scan(lc, lg, bx0, by0, bx1, by1, rlo, rhi, ex0, ey0, ex1, ey1):
        starts = jnp.searchsorted(lc, rlo, side="left")
        ends = jnp.searchsorted(lc, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        total = jnp.sum(counts)
        idx, valid_slot, _ = expand_ranges(starts, counts, capacity)
        gc = lg[idx]
        # bbox intersects: feature bbox vs query envelope
        inter = ((bx0[idx] <= ex1) & (bx1[idx] >= ex0)
                 & (by0[idx] <= ey1) & (by1[idx] >= ey0))
        mask = valid_slot & (gc >= 0) & inter
        packed = jnp.where(mask, gc, gc.dtype.type(-1))
        return packed, total[None].astype(jnp.int64)

    return jax.jit(scan)


@lru_cache(maxsize=64)
def _xz3_scan_program(mesh: Mesh, capacity: int):
    """As _xz2_scan_program with (bin, code) keys + a dtg interval mask."""

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 8 + (P(None),) * 3 + (P(),) * 6,
        out_specs=(P("shard"), P("shard")),
    )
    def scan(lb, lc, lg, bx0, by0, bx1, by1, lt,
             rb, rlo, rhi, ex0, ey0, ex1, ey1, t_lo, t_hi):
        starts = searchsorted2(lb, lc, rb, rlo, side="left")
        ends = searchsorted2(lb, lc, rb, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        total = jnp.sum(counts)
        idx, valid_slot, _ = expand_ranges(starts, counts, capacity)
        gc = lg[idx]
        inter = ((bx0[idx] <= ex1) & (bx1[idx] >= ex0)
                 & (by0[idx] <= ey1) & (by1[idx] >= ey0)
                 & (lt[idx] >= t_lo) & (lt[idx] <= t_hi))
        mask = valid_slot & (gc >= 0) & inter
        packed = jnp.where(mask, gc, gc.dtype.type(-1))
        return packed, total[None].astype(jnp.int64)

    return jax.jit(scan)


class ShardedXZ2Index:
    """XZ2 intersects index sharded over the feature axis of a mesh.

    Device state: sorted code column + gid payload + bbox columns, all
    sharded; host state: the packed geometries (original global order,
    indexed directly by gid) for the exact re-check.
    """

    DEFAULT_CAPACITY = 1 << 14

    def __init__(self, mesh: Mesh, g: int, codes, gid, bbox_cols,
                 geoms: PackedGeometry | None, n_total: int,
                 multihost: bool = False):
        self.mesh = mesh
        self.sfc = xz2_sfc(g)
        self.codes = codes
        self.gid = gid
        self.bbox_cols = bbox_cols  # (bx0, by0, bx1, by1) sharded
        #: exact-predicate payload: ALL geometries (single-controller,
        #: indexed by gid) or only THIS process's (multihost, indexed by
        #: the gid's local_row field)
        self.geoms = geoms
        self._n_total = n_total
        self._multihost = multihost
        self._capacity = self.DEFAULT_CAPACITY

    @classmethod
    def build(cls, geoms, g: int = 12,
              mesh: Mesh | None = None) -> "ShardedXZ2Index":
        mesh = mesh or device_mesh()
        packed = (geoms if isinstance(geoms, PackedGeometry)
                  else pack_geometries(geoms))
        bb = packed.bbox
        codes = xz2_sfc(g).index(bb[:, 0], bb[:, 1], bb[:, 2], bb[:, 3],
                                 xp=np).astype(np.int64)
        n = len(codes)
        gids = np.arange(n, dtype=np.int32)
        sharded, valid = shard_batch(
            mesh, codes, gids, bb[:, 0].copy(), bb[:, 1].copy(),
            bb[:, 2].copy(), bb[:, 3].copy())
        out = _xz_build_program(mesh, False)(*sharded, valid)
        cs, gs, bx0, by0, bx1, by1 = out
        return cls(mesh, g, cs, gs, (bx0, by0, bx1, by1), packed, n)

    @classmethod
    def build_multihost(cls, geoms, g: int = 12,
                        mesh: Mesh | None = None) -> "ShardedXZ2Index":
        """Multi-controller build from per-process LOCAL geometries; the
        exact-predicate payload stays local to each process (see
        _exact_recheck)."""
        import jax
        from .multihost import (
            agreed_int, global_device_mesh, process_local_shard,
        )
        mesh = mesh or global_device_mesh()
        packed = (geoms if isinstance(geoms, PackedGeometry)
                  else pack_geometries(geoms))
        bb = packed.bbox
        codes = xz2_sfc(g).index(bb[:, 0], bb[:, 1], bb[:, 2], bb[:, 3],
                                 xp=np).astype(np.int64)
        n_local = len(codes)
        from .scan import encode_gids
        gids = encode_gids(np.arange(n_local, dtype=np.int64))
        sharded, valid = process_local_shard(
            mesh, codes, gids, bb[:, 0].copy(), bb[:, 1].copy(),
            bb[:, 2].copy(), bb[:, 3].copy())
        out = _xz_build_program(mesh, False)(*sharded, valid)
        cs, gs, bx0, by0, bx1, by1 = out
        return cls(mesh, g, cs, gs, (bx0, by0, bx1, by1), packed,
                   agreed_int(n_local, "sum"), multihost=True)

    def __len__(self) -> int:
        return self._n_total

    def query(self, geometry: Geometry, max_ranges: int = 2000,
              exact: bool = True) -> np.ndarray:
        """Global gids of geometries intersecting ``geometry``: collective
        candidate scan + host exact predicate."""
        env = geometry.envelope
        ranges = self.sfc.ranges([env.as_tuple()], max_ranges=max_ranges)
        if not len(ranges) or self._n_total == 0:
            return np.empty(0, dtype=np.int64)
        r = pad_ranges({"rzlo": ranges[:, 0].astype(np.int64),
                        "rzhi": ranges[:, 1].astype(np.int64)},
                       pad_pow2(len(ranges)))
        capacity = self._capacity
        from ..resilience import breaker, classify_device_failure
        while True:
            # ISSUE 16: collective dispatch — classify-only, no local
            # retry/degrade (parallel/lean.py precedent)
            try:
                scan = _xz2_scan_program(self.mesh, capacity)
                packed, totals = scan(
                    self.codes, self.gid, *self.bbox_cols,
                    jnp.asarray(r["rzlo"]), jnp.asarray(r["rzhi"]),
                    jnp.float64(env.xmin), jnp.float64(env.ymin),
                    jnp.float64(env.xmax), jnp.float64(env.ymax))
            except Exception as e:  # noqa: BLE001 — classify + rethrow
                if classify_device_failure(e) == "transient":
                    breaker.record_failure((id(self), "xz2"))
                raise
            totals = _fetch_global(totals)
            if int(totals.max(initial=0)) <= capacity:
                self._capacity = capacity
                flat = _fetch_global(packed).ravel()
                cand = np.unique(flat[flat >= 0]).astype(np.int64)
                break
            capacity = gather_capacity(int(totals.max()))
        if exact and self.geoms is not None and not _is_envelope(geometry, env):
            cand = _exact_recheck(cand, self.geoms, geometry,
                                  self._multihost)
        return np.sort(cand).astype(np.int64)


class ShardedXZ3Index:
    """XZ3 intersects+time index sharded over the feature axis of a mesh."""

    DEFAULT_CAPACITY = 1 << 14

    def __init__(self, mesh: Mesh, period, g: int, bins, codes, gid,
                 bbox_cols, dtg, geoms: PackedGeometry | None, n_total: int,
                 multihost: bool = False):
        self.mesh = mesh
        self.period = TimePeriod.parse(period)
        self.sfc = xz3_sfc(self.period, g)
        self.bins = bins
        self.codes = codes
        self.gid = gid
        self.bbox_cols = bbox_cols
        self.dtg = dtg
        self.geoms = geoms
        self._n_total = n_total
        self._multihost = multihost
        self._capacity = self.DEFAULT_CAPACITY

    @classmethod
    def build(cls, geoms, dtg_ms, period: TimePeriod | str = TimePeriod.WEEK,
              g: int = 12, mesh: Mesh | None = None) -> "ShardedXZ3Index":
        mesh = mesh or device_mesh()
        packed = (geoms if isinstance(geoms, PackedGeometry)
                  else pack_geometries(geoms))
        period = TimePeriod.parse(period)
        sfc = xz3_sfc(period, g)
        dtg_ms = np.asarray(dtg_ms, dtype=np.int64)
        bins, offs = to_binned_time(dtg_ms, period)
        bb = packed.bbox
        offs_f = offs.astype(np.float64)
        codes = sfc.index(bb[:, 0], bb[:, 1], offs_f, bb[:, 2], bb[:, 3],
                          offs_f, xp=np).astype(np.int64)
        n = len(codes)
        gids = np.arange(n, dtype=np.int32)
        sharded, valid = shard_batch(
            mesh, bins.astype(np.int32), codes, gids,
            bb[:, 0].copy(), bb[:, 1].copy(), bb[:, 2].copy(),
            bb[:, 3].copy(), dtg_ms)
        out = _xz_build_program(mesh, True)(*sharded, valid)
        bs, cs, gs, bx0, by0, bx1, by1, td = out
        return cls(mesh, period, g, bs, cs, gs, (bx0, by0, bx1, by1),
                   td, packed, n)

    @classmethod
    def build_multihost(cls, geoms, dtg_ms,
                        period: TimePeriod | str = TimePeriod.WEEK,
                        g: int = 12,
                        mesh: Mesh | None = None) -> "ShardedXZ3Index":
        """Multi-controller build from per-process LOCAL geometries (see
        ShardedXZ2Index.build_multihost)."""
        import jax
        from .multihost import (
            agreed_int, global_device_mesh, process_local_shard,
        )
        mesh = mesh or global_device_mesh()
        packed = (geoms if isinstance(geoms, PackedGeometry)
                  else pack_geometries(geoms))
        period = TimePeriod.parse(period)
        sfc = xz3_sfc(period, g)
        dtg_ms = np.asarray(dtg_ms, dtype=np.int64)
        bins, offs = to_binned_time(dtg_ms, period)
        bb = packed.bbox
        offs_f = offs.astype(np.float64)
        codes = sfc.index(bb[:, 0], bb[:, 1], offs_f, bb[:, 2], bb[:, 3],
                          offs_f, xp=np).astype(np.int64)
        n_local = len(codes)
        from .scan import encode_gids
        gids = encode_gids(np.arange(n_local, dtype=np.int64))
        sharded, valid = process_local_shard(
            mesh, bins.astype(np.int32), codes, gids,
            bb[:, 0].copy(), bb[:, 1].copy(), bb[:, 2].copy(),
            bb[:, 3].copy(), dtg_ms)
        out = _xz_build_program(mesh, True)(*sharded, valid)
        bs, cs, gs, bx0, by0, bx1, by1, td = out
        return cls(mesh, period, g, bs, cs, gs, (bx0, by0, bx1, by1),
                   td, packed, agreed_int(n_local, "sum"), multihost=True)

    def __len__(self) -> int:
        return self._n_total

    def query(self, geometry: Geometry, t_lo_ms: int, t_hi_ms: int,
              max_ranges: int = 2000, exact: bool = True) -> np.ndarray:
        env = geometry.envelope
        windows = _time_windows_by_bin(t_lo_ms, t_hi_ms, self.period)
        if not windows or self._n_total == 0:
            return np.empty(0, dtype=np.int64)
        target = max(1, max_ranges // max(1, len(windows)))
        by_window: dict[tuple, list[int]] = {}
        for b, w in windows.items():
            by_window.setdefault(w, []).append(b)
        rbin, rlo, rhi = [], [], []
        for (wlo, whi), bs in by_window.items():
            ranges = self.sfc.ranges(
                [(env.xmin, env.ymin, float(wlo),
                  env.xmax, env.ymax, float(whi))], max_ranges=target)
            if not len(ranges):
                continue
            for b in bs:
                rbin.append(np.full(len(ranges), b, dtype=np.int32))
                rlo.append(ranges[:, 0].astype(np.int64))
                rhi.append(ranges[:, 1].astype(np.int64))
        if not rbin:
            return np.empty(0, dtype=np.int64)
        r = pad_ranges({"rbin": np.concatenate(rbin),
                        "rzlo": np.concatenate(rlo),
                        "rzhi": np.concatenate(rhi)},
                       pad_pow2(sum(len(a) for a in rbin)))
        capacity = self._capacity
        from ..resilience import breaker, classify_device_failure
        while True:
            try:
                scan = _xz3_scan_program(self.mesh, capacity)
                packed, totals = scan(
                    self.bins, self.codes, self.gid, *self.bbox_cols,
                    self.dtg,
                    jnp.asarray(r["rbin"]), jnp.asarray(r["rzlo"]),
                    jnp.asarray(r["rzhi"]),
                    jnp.float64(env.xmin), jnp.float64(env.ymin),
                    jnp.float64(env.xmax), jnp.float64(env.ymax),
                    jnp.int64(t_lo_ms), jnp.int64(t_hi_ms))
            except Exception as e:  # noqa: BLE001 — classify + rethrow
                if classify_device_failure(e) == "transient":
                    breaker.record_failure((id(self), "xz3"))
                raise
            totals = _fetch_global(totals)
            if int(totals.max(initial=0)) <= capacity:
                self._capacity = capacity
                flat = _fetch_global(packed).ravel()
                cand = np.unique(flat[flat >= 0]).astype(np.int64)
                break
            capacity = gather_capacity(int(totals.max()))
        if exact and self.geoms is not None and not _is_envelope(geometry, env):
            cand = _exact_recheck(cand, self.geoms, geometry,
                                  self._multihost)
        return np.sort(cand).astype(np.int64)
