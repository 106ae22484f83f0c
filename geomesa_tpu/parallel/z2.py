"""ShardedZ2Index: spatial-only bbox scans over a device mesh.

The mesh analog of the reference's Z2 index served through the same
distributed scan machinery as Z3 (AccumuloQueryPlan.BatchScanPlan serves
every index's ranges identically, .../data/AccumuloQueryPlan.scala:87-157).
Structure mirrors :class:`geomesa_tpu.parallel.scan.ShardedZ3Index`: one
sorted int64 z column per shard with the global-id payload, collective
packed scans, distributed append into sentinel padding.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..curve.sfc import z2_sfc
from ..curve.zorder import deinterleave2
from ..index.z2 import plan_z2_query
from ..ops.search import (
    coded_pos_bits, expand_ranges, gather_capacity, pad_boxes, pad_pow2,
    pad_ranges,
)
from .mesh import device_mesh, shard_batch
from .scan import _fetch_global

__all__ = ["ShardedZ2Index"]

_SENTINEL_Z = np.int64(np.iinfo(np.int64).max)


@lru_cache(maxsize=32)
def _z2_build_program(mesh: Mesh, sfc):
    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard"),) * 4, out_specs=(P("shard"),) * 4)
    def encode_sort(xs, ys, gs, vs):
        z = sfc.index(xs, ys)
        z = jnp.where(vs, z, _SENTINEL_Z)
        gs = jnp.where(vs, gs, gs.dtype.type(-1))
        return jax.lax.sort((z, gs, xs, ys), dimension=0, num_keys=1)

    return jax.jit(encode_sort)


def _z2_mask(zc, gc, xc, yc, ixy, bxs, same_q=None):
    """Fused Z2 candidate filter: z-decode int-space bounds test + exact
    double-precision re-check (shared by the single and batched scans)."""
    ix, iy = deinterleave2(zc.astype(jnp.uint64))
    ix = ix.astype(jnp.int64)
    iy = iy.astype(jnp.int64)
    box_pairs = (
        (ix[:, None] >= ixy[None, :, 0])
        & (iy[:, None] >= ixy[None, :, 1])
        & (ix[:, None] <= ixy[None, :, 2])
        & (iy[:, None] <= ixy[None, :, 3])
    )
    exact_pairs = (
        (xc[:, None] >= bxs[None, :, 0])
        & (yc[:, None] >= bxs[None, :, 1])
        & (xc[:, None] <= bxs[None, :, 2])
        & (yc[:, None] <= bxs[None, :, 3])
    )
    if same_q is not None:
        box_pairs &= same_q
        exact_pairs &= same_q
    return (gc >= 0) & box_pairs.any(axis=1) & exact_pairs.any(axis=1)


@lru_cache(maxsize=64)
def _z2_scan_program(mesh: Mesh, capacity: int):
    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 4 + (P(None),) * 4,
        out_specs=(P("shard"), P("shard")),
    )
    def scan(lz, lg, xs, ys, rlo, rhi, ixy, bxs):
        starts = jnp.searchsorted(lz, rlo, side="left")
        ends = jnp.searchsorted(lz, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        total = jnp.sum(counts)
        idx, valid_slot, _ = expand_ranges(starts, counts, capacity)
        gc = lg[idx]
        mask = valid_slot & _z2_mask(lz[idx], gc, xs[idx], ys[idx], ixy, bxs)
        packed = jnp.where(mask, gc, gc.dtype.type(-1))
        return packed, total[None].astype(jnp.int64)

    return jax.jit(scan)


@lru_cache(maxsize=64)
def _z2_many_program(mesh: Mesh, capacity: int, pos_bits: int):
    dt = jnp.int32 if pos_bits < 31 else jnp.int64

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 4 + (P(None),) * 6,
        out_specs=(P("shard"), P("shard")),
    )
    def scan(lz, lg, xs, ys, rlo, rhi, rqid, ixy, bxs, bqid):
        starts = jnp.searchsorted(lz, rlo, side="left")
        ends = jnp.searchsorted(lz, rhi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        total = jnp.sum(counts)
        idx, valid_slot, rid = expand_ranges(starts, counts, capacity)
        gc = lg[idx]
        cqid = rqid[rid]
        same_q = cqid[:, None] == bqid[None, :]
        mask = valid_slot & _z2_mask(
            lz[idx], gc, xs[idx], ys[idx], ixy, bxs, same_q)
        coded = (cqid.astype(dt) << dt(pos_bits)) | gc.astype(dt)
        packed = jnp.where(mask, coded, dt(-1))
        return packed, total[None].astype(jnp.int64)

    return jax.jit(scan)


@lru_cache(maxsize=32)
def _z2_append_program(mesh: Mesh, sfc):
    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 4 + (P("shard"),) * 3 + (P("shard"),),
        out_specs=(P("shard"),) * 4,
    )
    def app(lz, lg, lx, ly, xs, ys, gs, r):
        z_new = sfc.index(xs, ys)
        z_new = jnp.where(gs < 0, _SENTINEL_Z, z_new)
        r0 = r[0]
        lz = jax.lax.dynamic_update_slice(lz, z_new, (r0,))
        lg = jax.lax.dynamic_update_slice(lg, gs, (r0,))
        lx = jax.lax.dynamic_update_slice(lx, xs, (r0,))
        ly = jax.lax.dynamic_update_slice(ly, ys, (r0,))
        return jax.lax.sort((lz, lg, lx, ly), dimension=0, num_keys=1)

    return jax.jit(app)


@lru_cache(maxsize=32)
def _z2_grow_program(mesh: Mesh, pad: int):
    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard"),) * 4, out_specs=(P("shard"),) * 4)
    def grow(lz, lg, lx, ly):
        def ext(a, fill):
            return jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])
        return ext(lz, _SENTINEL_Z), ext(lg, -1), ext(lx, 0), ext(ly, 0)

    return jax.jit(grow)


class ShardedZ2Index:
    """Z2 point index sharded over the feature axis of a device mesh."""

    DEFAULT_CAPACITY = 1 << 15

    def __init__(self, mesh: Mesh, z, gid, x, y, n_total: int,
                 shard_counts: np.ndarray | None,
                 version: int | None = None,
                 multihost: bool = False, n_local: int | None = None):
        from ..index.z2 import Z2_INDEX_VERSION, z2_sfc_for_version
        self.mesh = mesh
        self.version = Z2_INDEX_VERSION if version is None else version
        self.sfc = z2_sfc_for_version(self.version)
        self.z = z
        self.gid = gid
        self.x = x
        self.y = y
        self._n_total = n_total
        self._shard_counts = shard_counts
        self._multihost = multihost
        self._n_local = n_total if n_local is None else n_local
        self._capacity = self.DEFAULT_CAPACITY
        #: gid-residency segments (see ShardedZ3Index)
        self._segments: list[tuple[int, int, int]] = []

    def shard_of_gids(self, gids: np.ndarray) -> np.ndarray:
        """Device shard holding each gid (see ShardedZ3Index)."""
        from .scan import segments_shard_of
        return segments_shard_of(self._segments, gids)

    @classmethod
    def build(cls, x, y, mesh: Mesh | None = None,
              version: int | None = None) -> "ShardedZ2Index":
        from ..index.z2 import Z2_INDEX_VERSION, z2_sfc_for_version
        mesh = mesh or device_mesh()
        version = Z2_INDEX_VERSION if version is None else version
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        n = len(x)
        gids = np.arange(n, dtype=np.int32)
        sharded, valid = shard_batch(mesh, x, y, gids)
        xd, yd, gidd = sharded
        z_s, gid_s, x_s, y_s = _z2_build_program(
            mesh, z2_sfc_for_version(version))(xd, yd, gidd, valid)
        n_shards = int(mesh.devices.size)
        per = int(z_s.shape[0]) // n_shards
        shard_counts = np.clip(n - np.arange(n_shards) * per, 0, per)
        idx = cls(mesh, z_s, gid_s, x_s, y_s, n_total=n,
                  shard_counts=shard_counts.astype(np.int64),
                  version=version)
        from .scan import _block_segments
        idx._segments = _block_segments(n, per, n_shards)
        return idx

    @classmethod
    def build_multihost(cls, x, y, mesh: Mesh | None = None,
                        version: int | None = None) -> "ShardedZ2Index":
        """Multi-controller build: each process feeds only its LOCAL
        rows; gids code ``process << GID_PROC_SHIFT | local_row`` (see
        ShardedZ3Index.build_multihost)."""
        from ..index.z2 import Z2_INDEX_VERSION, z2_sfc_for_version
        from .multihost import (
            agreed_int, global_device_mesh, global_shard_counts,
            process_local_shard,
        )
        from .scan import encode_gids

        mesh = mesh or global_device_mesh()
        version = Z2_INDEX_VERSION if version is None else version
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        n_local = len(x)
        gids = encode_gids(np.arange(n_local, dtype=np.int64))
        sharded, valid = process_local_shard(mesh, x, y, gids)
        xd, yd, gidd = sharded
        z_s, gid_s, x_s, y_s = _z2_build_program(
            mesh, z2_sfc_for_version(version))(xd, yd, gidd, valid)
        idx = cls(mesh, z_s, gid_s, x_s, y_s,
                  n_total=agreed_int(n_local, "sum"),
                  shard_counts=global_shard_counts(n_local, mesh),
                  version=version, multihost=True, n_local=n_local)
        from .scan import _multihost_segments
        idx._segments = _multihost_segments(mesh, n_local, gid_start=0)
        return idx

    def total(self) -> int:
        return self._n_total

    def __len__(self) -> int:
        return self._n_total

    def append(self, x, y) -> "ShardedZ2Index":
        """Distributed append (see ShardedZ3Index.append).  Collective
        under multihost: every process passes only its local new rows."""
        if self._multihost:
            return self._append_multihost(x, y)
        x = np.asarray(x, dtype=np.float64)
        m = len(x)
        if m == 0:
            return self
        y = np.asarray(y, dtype=np.float64)
        n_shards = int(self.mesh.devices.size)
        m_per = gather_capacity(-(-m // n_shards), minimum=8)
        slots = m_per * n_shards
        pad = slots - m
        gids = np.concatenate([
            np.arange(self._n_total, self._n_total + m, dtype=np.int32),
            np.full(pad, -1, np.int32)])
        cap = int(self.z.shape[0]) // n_shards
        need = int(self._shard_counts.max()) + m_per
        if need > cap:
            grow = _z2_grow_program(self.mesh, gather_capacity(need) - cap)
            self.z, self.gid, self.x, self.y = grow(
                self.z, self.gid, self.x, self.y)
        spec = NamedSharding(self.mesh, P("shard"))
        put = lambda a: jax.device_put(jnp.asarray(a), spec)
        self.z, self.gid, self.x, self.y = _z2_append_program(
            self.mesh, self.sfc)(
            self.z, self.gid, self.x, self.y,
            put(np.pad(x, (0, pad))), put(np.pad(y, (0, pad))), put(gids),
            put(self._shard_counts.astype(np.int32)))
        self._shard_counts = self._shard_counts + np.clip(
            m - np.arange(n_shards) * m_per, 0, m_per)
        from .scan import _block_segments
        self._segments.extend(
            _block_segments(m, m_per, n_shards, gid_base=self._n_total))
        self._n_total += m
        self._n_local += m
        return self

    def _append_multihost(self, x, y) -> "ShardedZ2Index":
        """Each process feeds only its local new rows (see
        ShardedZ3Index._append_multihost for the agreed-slot design)."""
        from .multihost import (
            agree_append_layout, agreed_int, global_shard_counts,
            process_local_shard, sharded_counts_array,
        )
        from .scan import encode_gids
        x = np.asarray(x, dtype=np.float64)
        m_local = len(x)
        m_global = agreed_int(m_local, "sum")
        if m_global == 0:
            return self
        y = np.asarray(y, dtype=np.float64)
        n_shards = int(self.mesh.devices.size)
        m_per, slots_local, _ = agree_append_layout(self.mesh, m_local)
        gids = np.full(slots_local, -1, dtype=np.int64)
        gids[:m_local] = encode_gids(
            self._n_local + np.arange(m_local, dtype=np.int64))
        cap = int(self.z.shape[0]) // n_shards
        need = int(self._shard_counts.max()) + m_per
        if need > cap:
            grow = _z2_grow_program(self.mesh, gather_capacity(need) - cap)
            self.z, self.gid, self.x, self.y = grow(
                self.z, self.gid, self.x, self.y)
        sharded, _ = process_local_shard(self.mesh, x, y, gids,
                                         padded_local=slots_local)
        xd, yd, gidd = sharded
        rd = sharded_counts_array(self.mesh, self._shard_counts)
        self.z, self.gid, self.x, self.y = _z2_append_program(
            self.mesh, self.sfc)(
            self.z, self.gid, self.x, self.y, xd, yd, gidd, rd)
        self._shard_counts = self._shard_counts + global_shard_counts(
            m_local, self.mesh, m_per=m_per)
        from .scan import _multihost_segments
        self._segments.extend(_multihost_segments(
            self.mesh, m_local, gid_start=self._n_local, m_per=m_per))
        self._n_total += m_global
        self._n_local += m_local
        return self

    def query(self, boxes, max_ranges: int = 2000,
              capacity: int | None = None) -> np.ndarray:
        """Exact global hit gids matching any of the bboxes."""
        plan = plan_z2_query(boxes, max_ranges, sfc=self.sfc)
        if plan.num_ranges == 0 or self._n_total == 0:
            return np.empty(0, dtype=np.int64)
        capacity = capacity or self._capacity
        r = pad_ranges({"rzlo": plan.rzlo, "rzhi": plan.rzhi},
                       pad_pow2(plan.num_ranges))
        ixy, bxs = pad_boxes(plan.ixy, plan.boxes,
                             pad_pow2(len(plan.boxes), minimum=1))
        while True:
            scan = _z2_scan_program(self.mesh, capacity)
            packed, totals = scan(
                self.z, self.gid, self.x, self.y,
                jnp.asarray(r["rzlo"]), jnp.asarray(r["rzhi"]),
                jnp.asarray(ixy), jnp.asarray(bxs))
            totals = _fetch_global(totals)
            if int(totals.max(initial=0)) <= capacity:
                self._capacity = capacity
                flat = _fetch_global(packed).ravel()
                return np.sort(flat[flat >= 0]).astype(np.int64)
            capacity = gather_capacity(int(totals.max()))

    def query_many(self, boxes_list,
                   max_ranges: int = 2000) -> list[np.ndarray]:
        """Batched collective spatial queries: one dispatch for ALL the
        box sets; returns a sorted gid array per entry."""
        n_q = len(boxes_list)
        if n_q == 0 or self._n_total == 0:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        rzlo, rzhi, rqid, ixy, bxs, bqid = [], [], [], [], [], []
        for q, boxes in enumerate(boxes_list):
            plan = plan_z2_query(boxes, max_ranges, sfc=self.sfc)
            if plan.num_ranges == 0:
                continue
            rzlo.append(plan.rzlo)
            rzhi.append(plan.rzhi)
            rqid.append(np.full(plan.num_ranges, q, dtype=np.int32))
            ixy.append(plan.ixy)
            bxs.append(plan.boxes)
            bqid.append(np.full(len(plan.boxes), q, dtype=np.int32))
        if not rzlo:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        r = pad_ranges({"rzlo": np.concatenate(rzlo),
                        "rzhi": np.concatenate(rzhi),
                        "rqid": np.concatenate(rqid)},
                       pad_pow2(sum(len(a) for a in rzlo)))
        ixy_c, boxes_c, bqid_c = pad_boxes(
            np.concatenate(ixy), np.concatenate(bxs),
            pad_pow2(sum(len(b) for b in bxs), minimum=1),
            np.concatenate(bqid))
        from .scan import multihost_gid_span
        pos_bits = coded_pos_bits(
            multihost_gid_span() if self._multihost else self._n_total, n_q)
        capacity = self._capacity
        while True:
            scan = _z2_many_program(self.mesh, capacity, pos_bits)
            packed, totals = scan(
                self.z, self.gid, self.x, self.y,
                jnp.asarray(r["rzlo"]), jnp.asarray(r["rzhi"]),
                jnp.asarray(r["rqid"]), jnp.asarray(ixy_c),
                jnp.asarray(boxes_c), jnp.asarray(bqid_c))
            totals = _fetch_global(totals)
            if int(totals.max(initial=0)) <= capacity:
                self._capacity = capacity
                flat = _fetch_global(packed).ravel()
                coded = flat[flat >= 0].astype(np.int64)
                break
            capacity = gather_capacity(int(totals.max()))
        qids = coded >> pos_bits
        gids = coded & ((np.int64(1) << pos_bits) - 1)
        return [np.unique(gids[qids == q]) for q in range(n_q)]
