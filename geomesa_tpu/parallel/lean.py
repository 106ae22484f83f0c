"""ShardedLeanZ3Index: the tiered lean generational index over a mesh.

Round-4 VERDICT #4: the cluster IS the reference's scale story
(AccumuloQueryPlan.scala:87-157 — scan plans fan out over tablet
servers), so the lean generational index must shard too.  Layout: every
generation's columns are STACKED per shard — ``(n_shards, slots)``
arrays with ``P("shard", None)`` sharding — and the probe/scan programs
run under ``shard_map``: each device seeks its own sorted runs, all
generations in one dispatch, with per-shard fixed-capacity coded
outputs.

Positions are GLOBAL gids (``process << GID_PROC_SHIFT | local_row``
under multihost, plain row ids single-controller), minted host-side at
append time and carried as an int64 sort payload.

**Residency tiers** (the single-chip ``index/z3_lean`` design composed
with the mesh — each generation demotes oldest-first under a PER-SHARD
HBM budget):

* ``full`` — keys AND an (x, y, t) payload per shard: the exact
  bbox+time mask runs fused INSIDE the shard_map scan and only true
  hits leave the device.  Unlike the single-chip full tier (payload in
  append order, gathered by ``pos - base``), the sharded payload is
  carried THROUGH the per-shard sort (gathered by its permutation):
  a shard's rows are block-split slices of many appends, so gids are
  not generation-contiguous per shard and a ``pos - base`` gather
  cannot work — sorted payload lets the expand index it directly.
* ``keys`` — 20 B/pt per shard (bins int32 + z int64 + gid int64):
  device seeks + candidate gather; the exact mask runs on each
  process's host payload (the client-side re-check) and survivors
  allgather.
* ``host`` — the per-shard sorted runs spilled to the OWNING process's
  host RAM (each process materializes only its addressable shards —
  which hold exactly its local rows) and seeked with the shared numpy
  :class:`~geomesa_tpu.index.z3_lean.HostRun`.  This is the 1B
  single-chip spill story composed with the mesh: per-chip reach is no
  longer bounded by HBM at all.

Demotion decisions are process-invariant (agreed byte counts over
identical global metadata), so multihost processes always pick the
same tiers — the agreed-gating discipline of the store.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..curve.binnedtime import TimePeriod, to_binned_time
from ..index.z3 import Z3_INDEX_VERSION, plan_z3_query, z3_sfc_for_version
from ..index.z3_lean import HostRun
from ..metrics import PYRAMID_SERVE_HITS, WRITE_SEALS, WRITE_SPILLS
from ..obs import device_span, obs_count, span as obs_span
from ..obs.heat import (
    heat_enabled, merge_index_generations, record_index_scan,
)
from ..ops.search import (
    expand_ranges, gather_capacity, pad_boxes, pad_pow2, pad_ranges,
    searchsorted2, sort_lex2,
)
from .scan import _fetch_global, encode_gids

__all__ = ["ShardedLeanZ3Index"]

_SENTINEL_BIN = np.int32(np.iinfo(np.int32).max)
_SENTINEL_Z = np.int64(np.iinfo(np.int64).max)

#: the world extent pyramids align to (index/pyramid._WORLD; matches
#: the single-chip sweep's _WORLD_ENV)
_PYRAMID_WORLD = (-180.0, -90.0, 180.0, 90.0)

#: per-slot byte widths, derived ONCE from the column dtypes (bins
#: int32 + z int64 + pos int64 — pos is an int64 gid here, unlike the
#: single-chip index's int32 — and the full tier adds x/y f64 + t
#: int64).  Every budget computation uses these, so a dtype change
#: cannot silently skew the HBM accounting.
KEYS_BYTES = 4 + 8 + 8
PAYLOAD_BYTES = 8 + 8 + 8
FULL_BYTES = KEYS_BYTES + PAYLOAD_BYTES

#: generation-count compile bucket (one compile per bucket: sentinel
#: padding is full-size, as in index/z3_lean)
_GEN_BUCKET = 4


@lru_cache(maxsize=8)
def _append_program(mesh: Mesh, sfc):
    """Per-shard ``keys``-tier append under shard_map: encode the
    shard's slice, write into its sentinel padding at slot offset ``r``
    and re-sort — the z3_lean append body, one run per device."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None),) * 3 + (P(),)
             + (P("shard", None),) * 6,
             out_specs=(P("shard", None),) * 3)
    def app(bins, z, pos, r, xs, ys, offs, bs, ps, m):
        b0, z0, p0 = bins[0], z[0], pos[0]
        m_pad = xs.shape[1]
        z_new = sfc.index(xs[0], ys[0], offs[0])
        valid = jnp.arange(m_pad) < m[0, 0]
        b_new = jnp.where(valid, bs[0], _SENTINEL_BIN)
        z_new = jnp.where(valid, z_new, _SENTINEL_Z)
        p_new = jnp.where(valid, ps[0], jnp.int64(-1))
        b0 = jax.lax.dynamic_update_slice(b0, b_new, (r,))
        z0 = jax.lax.dynamic_update_slice(z0, z_new, (r,))
        p0 = jax.lax.dynamic_update_slice(p0, p_new, (r,))
        b0, z0, p0 = sort_lex2(b0, z0, p0)
        return b0[None], z0[None], p0[None]

    return jax.jit(app, donate_argnums=(0, 1, 2))


@lru_cache(maxsize=8)
def _append_program_full(mesh: Mesh, sfc):
    """``full``-tier append: the keys body plus the (x, y, t) payload
    columns carried THROUGH the sort (module doc — sorted payload is
    what makes the fused exact mask possible per shard)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None),) * 6 + (P(),)
             + (P("shard", None),) * 7,
             out_specs=(P("shard", None),) * 6)
    def app(bins, z, pos, xp, yp, tp, r, xs, ys, offs, bs, ps, ts, m):
        b0, z0, p0 = bins[0], z[0], pos[0]
        x0, y0, t0 = xp[0], yp[0], tp[0]
        m_pad = xs.shape[1]
        z_new = sfc.index(xs[0], ys[0], offs[0])
        valid = jnp.arange(m_pad) < m[0, 0]
        b_new = jnp.where(valid, bs[0], _SENTINEL_BIN)
        z_new = jnp.where(valid, z_new, _SENTINEL_Z)
        p_new = jnp.where(valid, ps[0], jnp.int64(-1))
        b0 = jax.lax.dynamic_update_slice(b0, b_new, (r,))
        z0 = jax.lax.dynamic_update_slice(z0, z_new, (r,))
        p0 = jax.lax.dynamic_update_slice(p0, p_new, (r,))
        x0 = jax.lax.dynamic_update_slice(x0, xs[0], (r,))
        y0 = jax.lax.dynamic_update_slice(y0, ys[0], (r,))
        t0 = jax.lax.dynamic_update_slice(t0, ts[0], (r,))
        b0, z0, p0, x0, y0, t0 = sort_lex2(b0, z0, p0, x0, y0, t0)
        return (b0[None], z0[None], p0[None], x0[None], y0[None],
                t0[None])

    return jax.jit(app, donate_argnums=(0, 1, 2, 3, 4, 5))


@lru_cache(maxsize=8)
def _merge_program(mesh: Mesh, n_gens: int, out_slots: int):
    """COMPACTION merge under shard_map: each device concatenates its
    rows of the K sorted runs and re-sorts — sentinels float past the
    valid rows, and the leading ``out_slots`` (= the group's consumed
    slot count, an upper bound on any shard's valid rows) slots are the
    merged per-shard run.  One dispatch folds K runs into one across
    every shard (the index/z3_lean._lean_merge_keys shape on the
    mesh)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None),) * (3 * n_gens),
             out_specs=(P("shard", None),) * 3)
    def merge(*cols):
        b = jnp.concatenate([cols[3 * i][0] for i in range(n_gens)])
        z = jnp.concatenate([cols[3 * i + 1][0] for i in range(n_gens)])
        p = jnp.concatenate([cols[3 * i + 2][0] for i in range(n_gens)])
        b, z, p = sort_lex2(b, z, p)
        return (b[None, :out_slots], z[None, :out_slots],
                p[None, :out_slots])

    return jax.jit(merge)


@lru_cache(maxsize=8)
def _count_program(mesh: Mesh, n_gens: int):
    """Totals probe: per (shard, generation) candidate counts in ONE
    dispatch — out ``(n_shards, n_gens)``.  Tier-agnostic: both device
    tiers probe on (bins, z)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 3 + (P("shard", None),) * (2 * n_gens),
             out_specs=P("shard", None))
    def count(rb, rlo, rhi, *cols):
        outs = []
        for g in range(n_gens):
            b, z = cols[2 * g][0], cols[2 * g + 1][0]
            starts = searchsorted2(b, z, rb, rlo, side="left")
            ends = searchsorted2(b, z, rb, rhi, side="right")
            outs.append(jnp.sum(jnp.maximum(ends - starts, 0)))
        return jnp.stack(outs)[None]

    return jax.jit(count)


@lru_cache(maxsize=8)
def _scan_program(mesh: Mesh, n_gens: int, capacity: int, pos_bits: int):
    """``keys``-tier candidate gather: per-shard coded
    ``qid << pos_bits | gid`` buffers over every generation — out
    ``(n_shards, capacity)`` int64 (gids span the multihost process
    field)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 4 + (P("shard", None),) * (3 * n_gens),
             out_specs=P("shard", None))
    def scan(rb, rlo, rhi, rqid, *cols):
        per_gen = capacity // max(1, n_gens)
        outs = []
        for g in range(n_gens):
            b, z, pos = (cols[3 * g][0], cols[3 * g + 1][0],
                         cols[3 * g + 2][0])
            starts = searchsorted2(b, z, rb, rlo, side="left")
            ends = searchsorted2(b, z, rb, rhi, side="right")
            counts = jnp.maximum(ends - starts, 0)
            idx, valid, rid = expand_ranges(starts, counts, per_gen)
            coded = ((rqid[rid].astype(jnp.int64) << pos_bits)
                     | pos[idx])
            outs.append(jnp.where(valid, coded, jnp.int64(-1)))
        return jnp.concatenate(outs)[None]

    return jax.jit(scan)


@lru_cache(maxsize=8)
def _scan_program_exact(mesh: Mesh, n_gens: int, capacity: int,
                        pos_bits: int):
    """``full``-tier EXACT scan: seek + expand + the fused f64
    bbox+time mask over the shard's SORTED payload columns — every
    non-negative output slot is a TRUE hit; no host re-check, no
    survivors allgather (the output is already a global array).  A
    candidate only matches boxes/time of its own window (bqid/qtlo/
    qthi, the _query_many_packed discipline of index/z3)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 4 + (P(None, None), P(None), P(None),
                                        P(None))
             + (P("shard", None),) * (6 * n_gens),
             out_specs=P("shard", None))
    def scan(rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi, *cols):
        per_gen = capacity // max(1, n_gens)
        outs = []
        for g in range(n_gens):
            b, z, pos, xp, yp, tp = (c[0] for c in
                                     cols[6 * g: 6 * g + 6])
            starts = searchsorted2(b, z, rb, rlo, side="left")
            ends = searchsorted2(b, z, rb, rhi, side="right")
            counts = jnp.maximum(ends - starts, 0)
            idx, valid, rid = expand_ranges(starts, counts, per_gen)
            xc, yc, tc = xp[idx], yp[idx], tp[idx]
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
            coded = ((cqid.astype(jnp.int64) << pos_bits) | pos[idx])
            outs.append(jnp.where(ok, coded, jnp.int64(-1)))
        return jnp.concatenate(outs)[None]

    return jax.jit(scan)


@lru_cache(maxsize=8)
def _density_program_full(mesh: Mesh, n_gens: int, capacity: int,
                          width: int, height: int, sfc=None):
    """``full``-tier DensityScan under shard_map: per-shard seek +
    exact payload mask + grid scatter-add, grids merged with psum over
    ICI — only the (height, width) grid leaves the devices (round-4
    VERDICT #2; DensityScan.scala:31-59 next-to-the-data split).  The
    mask is value-exact on raw payload; binning goes through the z-cell
    midpoint for cross-platform determinism (see
    index/z3_lean._lean_density_full)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 3 + (P(None, None), P(None))
             + (P("shard", None),) * (6 * n_gens),
             out_specs=P(None, None))
    def dens(rb, rlo, rhi, boxes, tenv, *cols):
        from ..index.z3_lean import _grid_accum
        per_gen = capacity // max(1, n_gens)
        grid = jnp.zeros((height * width,), jnp.float64)
        env = tenv[:4]
        qtlo, qthi = tenv[4].astype(jnp.int64), tenv[5].astype(jnp.int64)
        for g in range(n_gens):
            b, z, pos, xp, yp, tp = (c[0] for c in
                                     cols[6 * g: 6 * g + 6])
            starts = searchsorted2(b, z, rb, rlo, side="left")
            ends = searchsorted2(b, z, rb, rhi, side="right")
            counts = jnp.maximum(ends - starts, 0)
            idx, valid, _rid = expand_ranges(starts, counts, per_gen)
            xc, yc, tc = xp[idx], yp[idx], tp[idx]
            in_box = (
                (xc[:, None] >= boxes[None, :, 0])
                & (yc[:, None] >= boxes[None, :, 1])
                & (xc[:, None] <= boxes[None, :, 2])
                & (yc[:, None] <= boxes[None, :, 3])
            ).any(axis=1)
            ok = valid & in_box & (tc >= qtlo) & (tc <= qthi)
            xd = sfc.lon.denormalize(sfc.lon.normalize(xc, xp=jnp),
                                     xp=jnp)
            yd = sfc.lat.denormalize(sfc.lat.normalize(yc, xp=jnp),
                                     xp=jnp)
            grid = _grid_accum(xd, yd, ok, env, width, height, grid)
        return jax.lax.psum(grid.reshape((height, width)), "shard")

    return jax.jit(dens)


@lru_cache(maxsize=8)
def _density_program_keys(mesh: Mesh, n_gens: int, capacity: int,
                          width: int, height: int, sfc):
    """``keys``-tier DensityScan: cell-granular masks decoded from the
    z key (the single-chip _lean_density_keys contract: exact for
    whole-extent scans, cell-inclusive at edges), psum-merged."""
    from ..curve.zorder import deinterleave3

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 3 + (P(None, None), P(None), P(None))
             + (P("shard", None),) * (2 * n_gens),
             out_specs=P(None, None))
    def dens(rb, rlo, rhi, ixy, tb, env, *cols):
        from ..index.z3_lean import _grid_accum
        per_gen = capacity // max(1, n_gens)
        grid = jnp.zeros((height * width,), jnp.float64)
        for g in range(n_gens):
            b, z = cols[2 * g][0], cols[2 * g + 1][0]
            starts = searchsorted2(b, z, rb, rlo, side="left")
            ends = searchsorted2(b, z, rb, rhi, side="right")
            counts = jnp.maximum(ends - starts, 0)
            idx, valid, _rid = expand_ranges(starts, counts, per_gen)
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
        return jax.lax.psum(grid.reshape((height, width)), "shard")

    return jax.jit(dens)


@lru_cache(maxsize=8)
def _cells_program(mesh: Mesh, n_gens: int, bits: int, nb: int):
    """Z3Histogram cell-count fold under shard_map (ISSUE 3): each
    shard folds its own sorted runs' coarse ``(bin, cell)`` keys into a
    flat table, psum-merged over ICI — the sharded twin of
    index/z3_lean._z3_cells_multi (same cell function, same overflow
    slot for sentinels)."""
    size = nb << bits

    @partial(shard_map, mesh=mesh,
             in_specs=(P(),) + (P("shard", None),) * (2 * n_gens),
             out_specs=P(None, None))
    def cells(b0, *cols):
        outs = []
        for g in range(n_gens):
            b, z = cols[2 * g][0], cols[2 * g + 1][0]
            mask = z != _SENTINEL_Z
            cell = z >> jnp.int64(63 - bits)
            flat = ((b.astype(jnp.int64) - b0) * jnp.int64(1 << bits)
                    + cell)
            ok = mask & (flat >= 0) & (flat < size)
            flat = jnp.where(ok, flat, size).astype(jnp.int32)
            outs.append(jnp.zeros((size + 1,), jnp.int64)
                        .at[flat].add(1)[:size])
        return jax.lax.psum(jnp.stack(outs), "shard")

    return jax.jit(cells)


class _ShardedGen:
    """One generation: stacked per-shard sorted runs.  ``tier`` ∈
    {"full", "keys", "host"} (module doc)."""

    __slots__ = ("bins", "z", "pos", "x", "y", "t", "n_slots", "tier",
                 "runs", "gen_id")

    @classmethod
    def merged_keys(cls, bins, z, pos, n_slots: int) -> "_ShardedGen":
        """A compacted ``keys``-tier generation from already-merged
        per-shard columns (``(n_shards, n_slots)``: zero slack)."""
        gen = cls.__new__(cls)
        gen.bins, gen.z, gen.pos = bins, z, pos
        gen.x = gen.y = gen.t = None
        gen.n_slots = int(n_slots)
        gen.tier = "keys"
        gen.runs = None
        gen.gen_id = -1
        return gen

    @classmethod
    def merged_host(cls, runs: list, n_slots: int) -> "_ShardedGen":
        """A compacted ``host``-tier generation from already-merged
        runs (this process's local rows)."""
        gen = cls.__new__(cls)
        gen.bins = gen.z = gen.pos = None
        gen.x = gen.y = gen.t = None
        gen.n_slots = int(n_slots)
        gen.tier = "host"
        gen.runs = runs
        gen.gen_id = -1
        return gen

    def __init__(self, mesh: Mesh, slots: int, tier: str = "keys"):
        shards = int(mesh.devices.size)
        sh = NamedSharding(mesh, P("shard", None))
        self.bins = jax.device_put(
            np.full((shards, slots), _SENTINEL_BIN, np.int32), sh)
        self.z = jax.device_put(
            np.full((shards, slots), _SENTINEL_Z, np.int64), sh)
        self.pos = jax.device_put(
            np.full((shards, slots), -1, np.int64), sh)
        if tier == "full":
            self.x = jax.device_put(np.zeros((shards, slots)), sh)
            self.y = jax.device_put(np.zeros((shards, slots)), sh)
            self.t = jax.device_put(
                np.zeros((shards, slots), np.int64), sh)
        else:
            self.x = self.y = self.t = None
        #: slot offset consumed so far (identical on every shard — each
        #: append writes the same agreed m_pad per shard)
        self.n_slots = 0
        self.tier = tier
        #: host-tier: this process's spilled per-shard runs
        self.runs: list[HostRun] | None = None
        #: store-lifetime-unique run identity, minted from agreed
        #: (process-invariant) appends/merges — the sketch-partial
        #: cache invalidation key (index/z3_lean._Generation.gen_id)
        self.gen_id = -1

    @property
    def slots(self) -> int:
        return 0 if self.tier == "host" else int(self.z.shape[1])

    def per_shard_bytes(self) -> int:
        """Device bytes ONE shard holds for this generation (the unit
        the per-chip HBM budget governs)."""
        if self.tier == "host":
            return 0
        per = FULL_BYTES if self.tier == "full" else KEYS_BYTES
        return int(self.z.shape[1]) * per

    def device_bytes(self) -> int:
        if self.tier == "host":
            return 0
        return int(self.z.shape[0]) * self.per_shard_bytes()

    def drop_payload(self) -> None:
        """full → keys: free the per-shard device payload (each
        process's host payload remains the re-check truth)."""
        if self.tier == "full":
            self.x = self.y = self.t = None
            self.tier = "keys"

    def spill_to_host(self) -> None:
        """keys → host: each process fetches its ADDRESSABLE shards'
        sorted runs into host RAM (those shards hold exactly its local
        rows) and frees the HBM on all of them."""
        self.drop_payload()
        if self.tier != "keys":
            return
        local = {}
        for name, arr in (("bins", self.bins), ("z", self.z),
                          ("pos", self.pos)):
            for s in arr.addressable_shards:
                row = s.index[0].start or 0
                local.setdefault(row, {})[name] = np.asarray(s.data)[0]
        self.runs = []
        for row in sorted(local):
            cols = local[row]
            valid = cols["pos"] >= 0
            self.runs.append(HostRun(cols["bins"][valid],
                                     cols["z"][valid],
                                     cols["pos"][valid]))
        self.bins = self.z = self.pos = None
        self.tier = "host"

    def host_key_bytes(self) -> int:
        if self.tier != "host":
            return 0
        return sum(len(r) * KEYS_BYTES for r in self.runs)




class ShardedLeanZ3Index:
    """Tiered lean generational Z3 index over a mesh (module doc)."""

    #: ``(schema, index_key)`` for access-temperature attribution
    #: (obs/heat) — stamped by the datastore
    heat_scope: tuple | None = None

    #: slots per generation PER SHARD
    GENERATION_SLOTS = 1 << 22
    DEFAULT_CAPACITY = 1 << 15
    #: per-shard slot budget for one batched scan output
    BATCH_SCAN_BUDGET = 1 << 26
    #: default PER-SHARD HBM budget for key/payload residency (the
    #: single-chip default: v5e usable minus scan slack, docs/scale.md)
    HBM_BUDGET_BYTES = int(13.5 * 2**30)
    #: size-tiered compaction trigger (see index/z3_lean.LeanZ3Index)
    COMPACTION_FACTOR = 4

    def __init__(self, period: TimePeriod | str = TimePeriod.WEEK,
                 mesh: Mesh | None = None,
                 version: int = Z3_INDEX_VERSION,
                 generation_slots: int | None = None,
                 multihost: bool = False,
                 hbm_budget_bytes: int | None = None,
                 payload_on_device: bool = True,
                 compaction_factor: int | None = None):
        assert mesh is not None
        self.period = TimePeriod.parse(period)
        self.version = version
        self.sfc = z3_sfc_for_version(self.period, version)
        self.mesh = mesh
        self.generation_slots = generation_slots or self.GENERATION_SLOTS
        self._multihost = bool(multihost)
        self.hbm_budget_bytes = hbm_budget_bytes or self.HBM_BUDGET_BYTES
        #: whether NEW generations carry per-shard payload for the
        #: fused exact mask (they demote under budget pressure)
        self.payload_on_device = payload_on_device
        self.generations: list[_ShardedGen] = []
        #: host payload provider: () -> (x, y, t) of THIS process's
        #: local rows (the store's columns)
        self.payload_provider = None
        self._payload: list = []
        self._flat = None
        self._n_local = 0      # this process's rows
        self._n_total = 0      # agreed global rows
        self.t_min_ms: int | None = None
        self.t_max_ms: int | None = None
        self.dispatch_count = 0
        #: stacked host-tier runs (lazy; seek cost flat in run count —
        #: round-4 VERDICT #9, same as the single-chip index)
        self._host_stack = None
        #: per-INSTANCE bucket-padding sentinels, keyed tier — instance
        #: scope (not a module cache) ties their device arrays to this
        #: index's lifetime, keeps eviction from stealing a sentinel
        #: another live index is padding with, and lets the budget
        #: accounting free the full-tier one when its charge ends
        self._sentinels: dict = {}
        #: opportunistic compaction factor (0 = off); under multihost
        #: the merge plan derives from process-invariant metadata so
        #: every process folds the same groups
        self.compaction_factor = int(compaction_factor or 0)
        self.compactions = 0
        #: sealed-run stat-sketch partials (ISSUE 3): GLOBAL z3
        #: cell-count tables keyed by agreed gen_ids, so multihost
        #: cache hits stay process-invariant
        from ..index.partial_cache import PartialCache
        from ..index.z3_lean import LeanZ3Index as _L
        self._sketch_cache = PartialCache(_L.SKETCH_CACHE_SPECS,
                                          _L.SKETCH_CACHE_MAX_BYTES)
        #: sealed-generation density pyramids (ISSUE 18): GLOBAL
        #: whole-world grid stacks keyed by agreed gen_ids — the
        #: allgathered per-gen density is process-invariant, so
        #: pyramid-served grids stay identical on every process
        from ..config import DensityProperties
        self._pyramid_cache = PartialCache(
            _L.PYRAMID_CACHE_SPECS,
            DensityProperties.PYRAMID_CACHE_BYTES.to_int())
        #: generation-lifecycle hooks: callables ``(kind, gen_ids)``
        #: invoked on seal/merge (index/lsm.notify_generation_event) —
        #: the datastore registers build-behind pyramid jobs here
        self.generation_listeners: list = []
        self._gen_counter = 0

    def _next_gen_id(self) -> int:
        self._gen_counter += 1
        return self._gen_counter

    def _sentinel(self, tier: str) -> _ShardedGen:
        """Shared empty full-size generation for bucket padding
        (uniform program shapes → one compile per bucket; all-sentinel
        keys match zero seeks)."""
        if tier not in self._sentinels:
            self._sentinels[tier] = _ShardedGen(
                self.mesh, self.generation_slots, tier=tier)
        return self._sentinels[tier]

    def __len__(self) -> int:
        return self._n_total

    def total(self) -> int:
        return self._n_total

    def device_bytes(self) -> int:
        return sum(g.device_bytes() for g in self.generations)

    def host_key_bytes(self) -> int:
        """Host RAM this process holds in spilled per-shard runs."""
        return sum(g.host_key_bytes() for g in self.generations)

    def tier_counts(self) -> dict:
        out = {"full": 0, "keys": 0, "host": 0}
        for g in self.generations:
            out[g.tier] += 1
        return out

    def sentinel_bytes(self) -> int:
        """HBM (across every shard) of the allocated padding-sentinel
        generations."""
        return sum(g.device_bytes() for g in self._sentinels.values())

    def storage_stats(self) -> dict:
        """Live byte accounting for the storage report (obs/resource,
        ISSUE 9) — the sharded twin of LeanZ3Index.storage_stats.
        ``device_bytes`` spans every shard; ``host_bytes`` is THIS
        process's spilled runs (host residency is per-process under
        multihost, so the mesh-wide view is the gauge SUM across
        processes — metrics.merge_snapshots)."""
        gens = [{"gen_id": g.gen_id, "tier": g.tier,
                 "slots": int(g.n_slots),
                 "capacity": g.slots,
                 "device_bytes": g.device_bytes(),
                 "host_bytes": g.host_key_bytes()}
                for g in self.generations]
        return {"kind": type(self).__name__, "rows": len(self),
                "tiers": self.tier_counts(),
                "device_bytes": self.device_bytes(),
                "host_bytes": self.host_key_bytes(),
                "sentinel_bytes": self.sentinel_bytes(),
                "hbm_budget_bytes": self.hbm_budget_bytes,
                "generations": gens,
                "caches": {"sketch": self._sketch_cache.stats(),
                           "pyramid": self._pyramid_cache.stats()},
                "dispatches": self.dispatch_count}

    def block(self) -> None:
        for gen in reversed(self.generations):
            if gen.tier != "host":
                jax.block_until_ready(gen.pos)
                break

    # -- write path -------------------------------------------------------
    def _agreed(self, value: int, op: str) -> int:
        if not self._multihost:
            return int(value)
        from .multihost import agreed_int
        return agreed_int(int(value), op)

    def _per_shard_resident(self) -> int:
        """Per-shard device bytes incl. the full-size sentinel padding
        buffers queries will lazily allocate (a keys sentinel always, a
        full one only while full-tier generations exist)."""
        per = sum(g.per_shard_bytes() for g in self.generations)
        per += self.generation_slots * KEYS_BYTES
        if any(g.tier == "full" for g in self.generations):
            per += self.generation_slots * FULL_BYTES
        return per

    def _rebalance(self) -> None:
        """Demote oldest-first until each shard's residency fits the
        per-shard HBM budget: payload drops first (full → keys), then
        runs spill to the owning processes (keys → host).  The ACTIVE
        generation's keys never spill — appends sort there.  All
        decisions derive from process-invariant global metadata, so
        multihost processes demote identically."""
        if self._per_shard_resident() <= self.hbm_budget_bytes:
            return
        for gen in self.generations:
            if gen.tier == "full":
                gen.drop_payload()
                if not any(g.tier == "full" for g in self.generations):
                    # the budget stops charging the full-tier sentinel
                    # the moment no full generation exists — free the
                    # cached one so the charge matches resident HBM
                    self._sentinels.pop("full", None)
                if self._per_shard_resident() <= self.hbm_budget_bytes:
                    return
        for gen in self.generations[:-1]:
            if gen.tier == "keys":
                # blocking device→host fetch of the run's shards —
                # traced with honest block-until-ready ms
                with device_span("write.spill", gen_id=gen.gen_id,
                                 slots=int(gen.n_slots)):
                    obs_count(WRITE_SPILLS)
                    gen.spill_to_host()
                self._host_stack = None   # restacked on the next query
                if self._per_shard_resident() <= self.hbm_budget_bytes:
                    return
        if self._per_shard_resident() > self.hbm_budget_bytes:
            raise MemoryError(
                f"active generation ({self.generation_slots} slots/"
                f"shard) exceeds hbm_budget_bytes="
                f"{self.hbm_budget_bytes} minus sentinel overhead")

    def _new_generation(self) -> _ShardedGen:
        tier = "full" if self.payload_on_device else "keys"
        if tier == "full":
            # would the payload survive rebalance?  The drop loop runs
            # oldest→newest BEFORE any spill, so if demoting every
            # existing payload still busts the budget, this
            # generation's payload is doomed — don't allocate (and
            # transiently spike) shards × slots × 24 B it would free
            # moments later.
            floor = (sum(min(g.per_shard_bytes(),
                             self.generation_slots * KEYS_BYTES)
                         for g in self.generations)
                     + self.generation_slots
                     * (FULL_BYTES + KEYS_BYTES + FULL_BYTES))
            if floor > self.hbm_budget_bytes:
                tier = "keys"
        gen = _ShardedGen(self.mesh, self.generation_slots, tier=tier)
        gen.gen_id = self._next_gen_id()
        self.generations.append(gen)
        self._rebalance()
        return self.generations[-1]

    def append(self, x, y, dtg_ms) -> "ShardedLeanZ3Index":
        """Distribute this process's rows across its local shards and
        merge into the current generation (rolling when full).  Under
        multihost every process enters with its LOCAL rows; the slot
        layout (m_pad) is agreed so the generation stays rectangular."""
        x = np.ascontiguousarray(x, dtype=np.float64)
        y = np.ascontiguousarray(y, dtype=np.float64)
        dtg_ms = np.ascontiguousarray(dtg_ms, dtype=np.int64)
        m_local = len(x)
        # ONE agreement for the whole append (each _agreed call is a
        # fleet-wide host allgather under multihost)
        m_max = self._agreed(m_local, "max")
        if m_max == 0:
            return self
        if self.payload_provider is None:
            self._payload.append((x, y, dtg_ms))
            self._flat = None
        n_shards = int(self.mesh.devices.size)
        from .multihost import local_device_count
        local_shards = (local_device_count(self.mesh)
                        if self._multihost else n_shards)
        # rows → this process's local shards, block-split; m_pad agreed
        # via m_max and clamped to the generation size (oversized
        # appends loop — the single-chip append's take=min(room,…))
        per = -(-max(1, m_max) // local_shards)
        m_pad = min(gather_capacity(per, minimum=8),
                    self.generation_slots)
        done = 0
        while done < m_max:
            gen = self.generations[-1] if self.generations else None
            if gen is None or gen.tier == "host" \
                    or gen.n_slots + m_pad > gen.slots:
                if gen is not None and gen.tier != "host":
                    # live generation seals on rollover (write-span
                    # taxonomy; the span covers the rebalance)
                    sealed_id = gen.gen_id
                    with obs_span("write.seal", gen_id=gen.gen_id,
                                  tier=gen.tier,
                                  slots=int(gen.n_slots)):
                        obs_count(WRITE_SEALS)
                        gen = self._new_generation()
                    from ..index.lsm import notify_generation_event
                    notify_generation_event(self, "seal", [sealed_id])
                else:
                    gen = self._new_generation()
            take_all = min(m_pad * local_shards, max(0, m_local - done))
            xs = np.zeros((local_shards, m_pad))
            ys = np.zeros((local_shards, m_pad))
            offs = np.zeros((local_shards, m_pad))
            bs = np.zeros((local_shards, m_pad), np.int32)
            ps = np.full((local_shards, m_pad), -1, np.int64)
            # only the full-tier program consumes timestamps — don't
            # allocate/copy shards × m_pad × 8 B the keys path discards
            ts = (np.zeros((local_shards, m_pad), np.int64)
                  if gen.tier == "full" else None)
            ms = np.zeros((local_shards, 1), np.int32)
            if take_all > 0:
                sl = slice(done, done + take_all)
                hb, ho = to_binned_time(dtg_ms[sl], self.period)
                rows = np.arange(done, done + take_all, dtype=np.int64)
                gids = (encode_gids(self._n_local + rows)
                        if self._multihost else self._n_local + rows)
                for s in range(local_shards):
                    lo, hi = s * m_pad, min(take_all, (s + 1) * m_pad)
                    if hi <= lo:
                        break
                    k = hi - lo
                    xs[s, :k] = x[sl][lo:hi]
                    ys[s, :k] = y[sl][lo:hi]
                    offs[s, :k] = ho[lo:hi].astype(np.float64)
                    bs[s, :k] = hb[lo:hi].astype(np.int32)
                    ps[s, :k] = gids[lo:hi]
                    if ts is not None:
                        ts[s, :k] = dtg_ms[sl][lo:hi]
                    ms[s, 0] = k
            if gen.tier == "full":
                arrs = self._shard_put([xs, ys, offs, bs, ps, ts, ms])
                prog = _append_program_full(self.mesh, self.sfc)
                self.dispatch_count += 1
                (gen.bins, gen.z, gen.pos, gen.x, gen.y,
                 gen.t) = prog(gen.bins, gen.z, gen.pos, gen.x, gen.y,
                               gen.t, jnp.int32(gen.n_slots), *arrs)
            else:
                arrs = self._shard_put([xs, ys, offs, bs, ps, ms])
                prog = _append_program(self.mesh, self.sfc)
                self.dispatch_count += 1
                gen.bins, gen.z, gen.pos = prog(
                    gen.bins, gen.z, gen.pos, jnp.int32(gen.n_slots),
                    *arrs)
            gen.n_slots += m_pad
            done += m_pad * local_shards
        self._n_local += m_local
        # one vector allgather agrees sum/extent together (each agreed
        # call is a fleet-wide host barrier — the ingest path pays it
        # once per append, not three times)
        t_min = int(dtg_ms.min()) if m_local else np.iinfo(np.int64).max
        t_max = int(dtg_ms.max()) if m_local else np.iinfo(np.int64).min
        if self._multihost:
            from .multihost import allgather_concat
            trip = allgather_concat(np.array(
                [[m_local, t_min, t_max]], dtype=np.int64))
            m_sum = int(trip[:, 0].sum())
            t_min = int(trip[:, 1].min())
            t_max = int(trip[:, 2].max())
        else:
            m_sum = m_local
        self._n_total += m_sum
        self.t_min_ms = (t_min if self.t_min_ms is None
                         else min(self.t_min_ms, t_min))
        self.t_max_ms = (t_max if self.t_max_ms is None
                         else max(self.t_max_ms, t_max))
        if self.compaction_factor:
            # bounded opportunistic trigger — max_groups is a
            # DETERMINISTIC cap, so every multihost process folds
            # exactly one group per append (a wall-clock budget could
            # stop processes after different merges and strand the
            # next collective)
            self.compact(factor=self.compaction_factor, max_groups=1)
        return self

    # -- compaction (LSM maintenance) -------------------------------------
    def _compaction_groups(self, factor: int) -> list[list]:
        """Size-tiered merge plan over SEALED generations, bucketed by
        CONSUMED SLOT COUNT — n_slots is agreed at append time and
        retained through spills, so multihost processes always plan
        identical groups (per-process host row counts are NOT
        invariant and must not drive the plan)."""
        from ..index.lsm import plan_size_tiered
        return plan_size_tiered(self.generations[:-1],
                                ("keys", "host"),
                                lambda g: g.n_slots, factor)

    def _merge_group(self, group: list) -> None:
        from ..index.lsm import merged_capacity, replace_group
        from ..index.z3_lean import merge_host_runs
        n_slots = int(sum(g.n_slots for g in group))
        if group[0].tier == "keys":
            cols: list = []
            for g in group:
                cols += [g.bins, g.z, g.pos]
            out_slots = merged_capacity(
                n_slots, sum(g.slots for g in group), gather_capacity)
            self.dispatch_count += 1
            bins, z, pos = _merge_program(
                self.mesh, len(group), out_slots)(*cols)
            merged = _ShardedGen.merged_keys(bins, z, pos,
                                             n_slots=n_slots)
        else:
            merged = _ShardedGen.merged_host(
                [merge_host_runs([r for g in group for r in g.runs])],
                n_slots=n_slots)
            self._host_stack = None
        merged.gen_id = self._next_gen_id()
        dead_ids = [g.gen_id for g in group]
        self._sketch_cache.drop_generations(dead_ids)
        # merged run inherits its sources' access temperature —
        # BEFORE the swap, so a racing heat report's stale-entry
        # prune sees the fresh merged entry (grace window), never
        # the long-cold dead ids
        merge_index_generations(self, dead_ids, merged.gen_id)
        # pyramid inheritance mirrors the heat merge: when every
        # parent has a pyramid the merged generation's is the exact
        # elementwise sum (density is additive over generations)
        self._inherit_pyramids(dead_ids, merged.gen_id)
        self._pyramid_cache.drop_generations(dead_ids)
        self.generations = replace_group(self.generations, group,
                                         merged)
        self.compactions += 1
        from ..metrics import (
            LEAN_COMPACTION_MERGES, LEAN_COMPACTION_ROWS,
            registry as _metrics,
        )
        _metrics.counter(LEAN_COMPACTION_MERGES).inc()
        # consumed-slot upper bound × shards: per-shard VALID counts
        # live on device, so exact rows would cost a fetch per merge
        _metrics.counter(LEAN_COMPACTION_ROWS).inc(
            n_slots * int(self.mesh.devices.size))
        from ..index.lsm import notify_generation_event
        notify_generation_event(self, "merge", [merged.gen_id])

    def compact(self, budget_ms: float | None = None,
                factor: int | None = None,
                max_groups: int | None = None) -> dict:
        """Incremental size-tiered merge compaction over the sharded
        runs (see index/z3_lean.LeanZ3Index.compact).  Under multihost
        ``budget_ms`` is IGNORED — a wall-clock cut could stop
        different processes after different merges and strand the next
        collective; ``max_groups`` (deterministic) and the invariant
        plan are the agreed stopping points."""
        from ..index.lsm import compact_incremental
        f = int(factor or self.compaction_factor
                or self.COMPACTION_FACTOR)
        merged = compact_incremental(
            lambda: self._compaction_groups(f), self._merge_group,
            budget_ms=None if self._multihost else budget_ms,
            max_groups=max_groups)
        if merged:
            self._rebalance()
        return {"merged_groups": merged,
                "generations": len(self.generations),
                "tiers": self.tier_counts()}

    def _shard_put(self, arrs: list):
        """Host (local_shards, …) arrays → global sharded arrays."""
        sh = NamedSharding(self.mesh, P("shard", None))
        if not self._multihost:
            return [jax.device_put(a, sh) for a in arrs]
        return [jax.make_array_from_process_local_data(sh, a)
                for a in arrs]

    # -- payload ----------------------------------------------------------
    def _payload_flat(self):
        if self.payload_provider is not None:
            return self.payload_provider()
        if self._flat is None:
            xs, ys, ts = (zip(*self._payload) if self._payload
                          else ((), (), ()))
            self._flat = (
                np.concatenate(xs) if xs else np.empty(0),
                np.concatenate(ys) if ys else np.empty(0),
                np.concatenate(ts) if ts else np.empty(0, np.int64))
            self._payload = [tuple(self._flat)]
        return self._flat

    def _clamp_time(self, t_lo_ms, t_hi_ms):
        t_lo_ms = self.t_min_ms if t_lo_ms is None else int(t_lo_ms)
        t_hi_ms = self.t_max_ms if t_hi_ms is None else int(t_hi_ms)
        if self.t_min_ms is not None:
            t_lo_ms = max(t_lo_ms, self.t_min_ms)
        if self.t_max_ms is not None:
            t_hi_ms = min(t_hi_ms, self.t_max_ms)
        return t_lo_ms, t_hi_ms

    # -- result materialization (ISSUE 14) --------------------------------
    def gather_payload(self, positions: np.ndarray):
        """(x, y, t) for the given LOCAL row positions — the sharded
        twin of :meth:`LeanZ3Index.gather_payload`.

        The sharded full tier stores its payload KEY-SORTED per shard
        (appends sort payload alongside keys under shard_map), so a
        row-id-addressed device take would need a per-row key search;
        rows gather instead from this process's host payload in ONE
        vectorized numpy take — the stacked-host-run half of the
        materialize contract.  Under multihost the caller decodes gids
        to local rows first (each process streams its own slice, the
        per-shard delta-stream protocol of ``parallel/stats.
        merged_arrow``)."""
        positions = np.asarray(positions, dtype=np.int64)
        x, y, t = self._payload_flat()
        return (np.asarray(x)[positions], np.asarray(y)[positions],
                np.asarray(t, np.int64)[positions])

    # -- query path -------------------------------------------------------
    def query(self, boxes, t_lo_ms, t_hi_ms,
              max_ranges: int = 2000) -> np.ndarray:
        return self.query_many([(boxes, t_lo_ms, t_hi_ms)],
                               max_ranges=max_ranges)[0]

    def query_many(self, windows,
                   max_ranges: int = 2000) -> list[np.ndarray]:
        """Batched multi-window scan over every shard × generation:
        probe + one scan per populated device tier + numpy seeks over
        spilled runs.  Full-tier hits are exact on device; keys/host
        candidates get the host exact mask on each process's payload
        with survivors allgathered — every process returns the same
        sorted GLOBAL gid list per window."""
        n_q = len(windows)
        if n_q == 0 or self._n_total == 0:
            return [np.empty(0, dtype=np.int64) for _ in range(n_q)]
        rbin, rzlo, rzhi, rqid = [], [], [], []
        w_boxes: list = []
        qtlo = np.empty(n_q, dtype=np.int64)
        qthi = np.empty(n_q, dtype=np.int64)
        from ..index.z3_lean import _MAX_RANGES_PER_WINDOW, _bins_spanned
        from ..resilience import check_cancel
        with obs_span("query.decompose", windows=n_q) as dsp:
            for q, (bxs, lo, hi) in enumerate(windows):
                # per-process raise BETWEEN collective phases — the
                # planner's QueryTimeoutError precedent.  The PARTIAL
                # break is single-controller only: under multihost a
                # wall-clock break could plan fewer ranges than peers
                # and diverge the collective shapes (a raise at least
                # fails loudly, like the legacy reaper)
                if not self._multihost and check_cancel("query.decompose"):
                    break
                lo, hi = self._clamp_time(lo, hi)
                qtlo[q], qthi[q] = lo, hi
                bxs = np.atleast_2d(np.asarray(bxs, dtype=np.float64))
                w_boxes.append(bxs)
                # per-BIN range budget (see index/z3_lean.query_many):
                # open/long intervals must not starve each bin into
                # overcovering ranges
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
        from .scan import multihost_gid_span
        span = (multihost_gid_span() if self._multihost
                else max(2, self._n_total))
        pos_bits = max(1, int(np.ceil(np.log2(span))))

        full_gens = [g for g in self.generations if g.tier == "full"]
        keys_gens = [g for g in self.generations if g.tier == "keys"]
        host_gens = [g for g in self.generations if g.tier == "host"]

        # ONE totals probe across every device generation (full + keys)
        dev_gens = full_gens + keys_gens
        totals = np.empty((0, 0))
        if dev_gens:
            padded = self._pad_bucket(dev_gens, "keys")
            count_cols: list = []
            for gen in padded:
                count_cols += [gen.bins, gen.z]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="probe",
                             runs=len(dev_gens)):
                totals = _fetch_global(
                    _count_program(self.mesh, len(padded))(
                        rb, rlo, rhi, *count_cols))    # (n_shards, G_pad)
            # adaptive-replan probe point (ISSUE 19): the fetched totals
            # are GLOBAL (process-invariant), so a ReplanSignal raised
            # here is multihost-agreed; host-tier candidate counts are
            # process-local and therefore get no probe
            from ..planning.adaptive import check_replan
            check_replan("query.scan.probe", int(totals.sum()))

        # deadline yield points between tier phases: single-controller
        # only (see the decompose note — a lone process skipping a
        # collective tier dispatch would strand its peers)
        def _yield_point(point: str) -> bool:
            return (not self._multihost) and check_cancel(point)

        exact_parts: list = []      # full tier — true hits already
        cand_parts: list = []       # keys/host — need the host mask
        if full_gens and not _yield_point("query.scan.full"):
            t_full = totals[:, :len(full_gens)]
            if int(t_full.sum()):
                boxes_c, bqid_c = self._concat_boxes(w_boxes)
                exact_parts += self._scan_tier(
                    full_gens, t_full, rb, rlo, rhi, rq, pos_bits,
                    exact_args=(jnp.asarray(boxes_c),
                                jnp.asarray(bqid_c),
                                jnp.asarray(qtlo), jnp.asarray(qthi)))
        if keys_gens and not _yield_point("query.scan.keys"):
            t_keys = totals[:, len(full_gens):len(dev_gens)]
            if int(t_keys.sum()):
                cand_parts += self._scan_tier(
                    keys_gens, t_keys, rb, rlo, rhi, rq, pos_bits,
                    exact_args=None)
        # host tier: stacked numpy seeks over this process's spilled
        # runs (its local rows) — flat in run count, no dispatch at all
        # (round-4 VERDICT #9)
        host_cand_n = 0
        if host_gens and not _yield_point("query.scan.host"):
            with obs_span("query.scan.host", stage="seek",
                          runs=len(host_gens)):
                coded = self._host_runs_stack(host_gens).candidates(
                    ra["rbin"], ra["rzlo"], ra["rzhi"], ra["rqid"],
                    pos_bits)
                host_cand_n = int(len(coded))
                if len(coded):
                    cand_parts.append(coded)
        if heat_enabled():
            # per-generation heat (obs/heat; process-local — never a
            # collective): device generations attribute candidates
            # exactly via the probe's per-shard totals summed; host
            # candidates split proportionally to consumed slots
            touches = [(g.gen_id, g.tier, int(g.n_slots),
                        g.device_bytes(), int(totals[:, i].sum()))
                       for i, g in enumerate(dev_gens)]
            n_host = sum(g.n_slots for g in host_gens)
            touches += [(g.gen_id, "host", int(g.n_slots),
                         g.host_key_bytes(),
                         int(round(host_cand_n * g.n_slots / n_host)))
                        for g in host_gens]
            record_index_scan(self, touches)

        mask_bits = (np.int64(1) << pos_bits) - 1
        flat = (np.concatenate(cand_parts) if cand_parts
                else np.empty(0, np.int64))
        qids = (flat >> pos_bits).astype(np.int64)
        gids = (flat & mask_bits).astype(np.int64)
        # exact host mask on THIS process's rows, survivors allgathered
        from ..parallel.scan import decode_gids
        if self._multihost:
            procs, rows = decode_gids(gids)
            mine = procs == jax.process_index()
        else:
            rows = gids
            mine = np.ones(len(gids), dtype=bool)
        with obs_span("query.scan.host", stage="recheck",
                      candidates=int(len(gids))):
            x, yv, t = self._payload_flat()
            keep = np.zeros(len(gids), dtype=bool)
            lrows = rows[mine]
            cx, cy, ct = x[lrows], yv[lrows], t[lrows]
            lq = qids[mine]
            k_local = np.zeros(len(lrows), dtype=bool)
            for q in range(n_q):
                sel = lq == q
                if not sel.any():
                    continue
                in_box = np.zeros(int(sel.sum()), dtype=bool)
                for b in w_boxes[q]:
                    in_box |= ((cx[sel] >= b[0]) & (cy[sel] >= b[1])
                               & (cx[sel] <= b[2]) & (cy[sel] <= b[3]))
                k_local[sel] = (in_box & (ct[sel] >= qtlo[q])
                                & (ct[sel] <= qthi[q]))
            keep[mine] = k_local
        coded_hits = flat[keep]
        if self._multihost:
            from .multihost import allgather_concat
            coded_hits = allgather_concat(coded_hits)
        if exact_parts:
            coded_hits = np.concatenate([coded_hits, *exact_parts])
        out = []
        hq = (coded_hits >> pos_bits).astype(np.int64)
        hg = (coded_hits & mask_bits).astype(np.int64)
        for q in range(n_q):
            out.append(np.unique(hg[hq == q]))
        return out

    # -- aggregation push-down (round-4 VERDICT #2) -----------------------
    def density(self, boxes, t_lo_ms, t_hi_ms, env,
                width: int = 256, height: int = 256,
                max_ranges: int = 2000, _gens: list | None = None,
                _record_heat: bool = True) -> np.ndarray:
        """DensityScan push-down over the mesh: per-shard grids
        accumulated inside shard_map and merged with psum over ICI —
        full tier masks exactly on its sorted payload, keys tier
        decodes cell-granular coordinates from the z key, host-tier
        runs contribute numpy partials summed across processes.  Only
        grids ever leave the devices (DensityScan.scala:31-59).

        Whole-world whole-time square requests at a cached pyramid
        resolution serve sealed generations from their density
        pyramids (ISSUE 18) and scan ONLY the live generation plus any
        pyramid-less stragglers — exact, since each pyramid level is
        the generation's own sweep at that width.  ``_gens`` /
        ``_record_heat`` are the private restriction hooks the pyramid
        builder and fast path recurse through."""
        grid = np.zeros((height, width), np.float64)
        if self._n_total == 0:
            return grid
        lo, hi = self._clamp_time(t_lo_ms, t_hi_ms)
        bxs = np.atleast_2d(np.asarray(boxes, dtype=np.float64))
        env_t = tuple(float(v) for v in env)
        pyr_ok = (
            _gens is None and width == height
            and len(self.generations) > 1
            and env_t == _PYRAMID_WORLD
            and lo == self.t_min_ms and hi == self.t_max_ms
            and bool(np.any((bxs[:, 0] <= -180.0) & (bxs[:, 1] <= -90.0)
                            & (bxs[:, 2] >= 180.0) & (bxs[:, 3] >= 90.0))))
        if pyr_ok:
            served: set = set()
            rest: list = []
            for g in self.generations[:-1]:
                lvl = self._pyramid_level(g.gen_id, width)
                if lvl is not None:
                    obs_count(PYRAMID_SERVE_HITS)
                    grid += lvl
                    served.add(id(g))
                else:
                    rest.append(g)
            if served:
                rest.append(self.generations[-1])
                grid += self.density(boxes, t_lo_ms, t_hi_ms, env,
                                     width, height, max_ranges,
                                     _gens=rest, _record_heat=False)
                if heat_enabled():
                    # pyramid-served generations record ZERO-byte
                    # touches (the PR 5 cache-hit convention)
                    record_index_scan(self, [
                        (g.gen_id, g.tier, int(g.n_slots),
                         (0 if id(g) in served
                          else g.device_bytes() if g.tier != "host"
                          else g.host_key_bytes()), None)
                        for g in self.generations])
                return grid
        from ..index.z3_lean import _MAX_RANGES_PER_WINDOW, _bins_spanned
        budget = min(max_ranges * _bins_spanned(lo, hi, self.period),
                     _MAX_RANGES_PER_WINDOW)
        plan = plan_z3_query(bxs, lo, hi, self.period, budget,
                             sfc=self.sfc)
        if plan.num_ranges == 0:
            return grid
        ra = pad_ranges(
            {"rbin": plan.rbin, "rzlo": plan.rzlo, "rzhi": plan.rzhi},
            pad_pow2(plan.num_ranges))
        rb = jnp.asarray(ra["rbin"])
        rlo = jnp.asarray(ra["rzlo"])
        rhi = jnp.asarray(ra["rzhi"])
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
        gens = self.generations if _gens is None else _gens
        full_gens = [g for g in gens if g.tier == "full"]
        keys_gens = [g for g in gens if g.tier == "keys"]
        host_gens = [g for g in gens if g.tier == "host"]
        dev_gens = full_gens + keys_gens
        totals = np.empty((0, 0))
        if dev_gens:
            padded = self._pad_bucket(dev_gens, "keys")
            count_cols: list = []
            for gen in padded:
                count_cols += [gen.bins, gen.z]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="probe",
                             runs=len(dev_gens)):
                totals = _fetch_global(_count_program(
                    self.mesh, len(padded))(rb, rlo, rhi, *count_cols))

        def _cap(tier_totals, n_padded):
            per_gen = gather_capacity(int(tier_totals.max()),
                                      minimum=self.DEFAULT_CAPACITY)
            return per_gen * n_padded

        if full_gens and int(totals[:, :len(full_gens)].sum()):
            padded = self._pad_bucket(full_gens, "full")
            cap = _cap(totals[:, :len(full_gens)], len(padded))
            cols: list = []
            for gen in padded:
                cols += [gen.bins, gen.z, gen.pos, gen.x, gen.y, gen.t]
            tenv = jnp.asarray(np.array(list(env_t) + [lo, hi],
                                        np.float64))
            self.dispatch_count += 1
            with device_span("query.scan.device", tier="full",
                             runs=len(full_gens)):
                grid += np.asarray(_density_program_full(
                    self.mesh, len(padded), cap, width, height,
                    self.sfc)(
                    rb, rlo, rhi, jnp.asarray(bxs), tenv, *cols),
                    np.float64)
        if keys_gens and int(totals[:, len(full_gens):len(dev_gens)]
                             .sum()):
            padded = self._pad_bucket(keys_gens, "keys")
            cap = _cap(totals[:, len(full_gens):len(dev_gens)],
                       len(padded))
            cols = []
            for gen in padded:
                cols += [gen.bins, gen.z]
            self.dispatch_count += 1
            with device_span("query.scan.device", tier="keys",
                             runs=len(keys_gens)):
                grid += np.asarray(_density_program_keys(
                    self.mesh, len(padded), cap, width, height,
                    self.sfc)(
                    rb, rlo, rhi, jnp.asarray(ixy), jnp.asarray(tb),
                    jnp.asarray(np.asarray(env_t)), *cols), np.float64)
        host_part = np.zeros((height, width), np.float64)
        if host_gens:
            if _gens is None:
                stack = self._host_runs_stack(host_gens)
            else:
                # restricted scans build a throwaway stack — the
                # cached one spans ALL host generations
                from ..index.z3_lean import HostStack
                stack = HostStack(
                    [run for gen in host_gens for run in gen.runs])
            host_part = stack.density_partial(
                ra["rbin"], ra["rzlo"], ra["rzhi"], self.sfc, ixy, tb,
                env_t, width, height)
        if self._multihost:
            from .multihost import allgather_concat
            host_part = allgather_concat(
                host_part[None]).sum(axis=0)
        grid += host_part
        if _record_heat and heat_enabled() and self.generations:
            # density reads every generation; matches are grids, not
            # rows — full-weight accesses (obs/heat module doc)
            record_index_scan(self, [
                (g.gen_id, g.tier, int(g.n_slots),
                 g.device_bytes() if g.tier != "host"
                 else g.host_key_bytes(), None)
                for g in self.generations])
        return grid

    def range_count(self, boxes, t_lo_ms, t_hi_ms,
                    max_ranges: int = 2000) -> int:
        """Masked hit count with no candidate materialization (exact on
        full tiers / whole-extent scans; cell-inclusive otherwise)."""
        return int(round(self.density(
            boxes, t_lo_ms, t_hi_ms, (-180.0, -90.0, 180.0, 90.0),
            1, 1, max_ranges=max_ranges).sum()))

    def z3_cell_counts(self, bits: int) -> dict:
        """WHOLE-EXTENT Z3Histogram push-down over the mesh (ISSUE 3):
        per-shard (time-bin × z-cell) tables fold inside shard_map and
        merge with psum over ICI; host-tier runs fold on their owning
        process and allreduce.  Sealed generations' GLOBAL tables cache
        identically on every process (agreed gen_ids), so warm repeats
        fold only the live generation.  Returns ``{(bin, cell):
        count}`` — the single-chip LeanZ3Index.z3_cell_counts
        contract."""
        from ..metrics import (
            LEAN_SKETCH_CACHE_HITS, LEAN_SKETCH_CACHE_MISSES,
            registry as _metrics,
        )
        from .stats import allreduce_counts
        out: dict = {}
        if self._n_total == 0 or self.t_min_ms is None:
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
        host_scan: list = []
        for g in self.generations:
            part = cache.get(g.gen_id) if g is not live else None
            if part is not None:
                obs_count(LEAN_SKETCH_CACHE_HITS)
                total += part
            elif g.tier == "host":
                host_scan.append(g)
            else:
                scan.append(g)
        if scan:
            n_b = (-len(scan)) % _GEN_BUCKET
            padded = list(scan) + [self._sentinel("keys")] * n_b
            cols: list = []
            for g in padded:
                cols += [g.bins, g.z]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="z3_cells",
                             runs=len(scan)):
                stacked = np.asarray(_cells_program(
                    self.mesh, len(padded), int(bits), nb)(
                    jnp.int64(b0), *cols))
            for i, g in enumerate(scan):
                # copy, not a view: a cached view would pin the whole
                # stacked bucket and break the byte accounting
                part = np.array(stacked[i])
                total += part
                if g is not live:
                    obs_count(LEAN_SKETCH_CACHE_MISSES)
                    self._sketch_cache.add(cache, g.gen_id, part)
        for g in host_scan:
            obs_count(LEAN_SKETCH_CACHE_MISSES)
            local = np.zeros(nb << bits, np.int64)
            for run in g.runs:
                local += run.cell_counts(b0, nb, int(bits))
            part = (allreduce_counts(local) if self._multihost
                    else local)
            self._sketch_cache.add(cache, g.gen_id, part)
            total += part
        if heat_enabled() and self.generations:
            scanned = ({id(g) for g in scan}
                       | {id(g) for g in host_scan})
            record_index_scan(self, [
                (g.gen_id, g.tier, int(g.n_slots),
                 (0 if id(g) not in scanned
                  else g.device_bytes() if g.tier != "host"
                  else g.host_key_bytes()), None)
                for g in self.generations])
        c_per_bin = 1 << bits
        for i in np.flatnonzero(total):
            out[(b0 + int(i) // c_per_bin, int(i) % c_per_bin)] = \
                int(total[i])
        return out

    # -- density pyramids (ISSUE 18) --------------------------------------
    def build_pyramids(self, base: int | None = None,
                       levels: int | None = None) -> int:
        """Build whole-world density pyramids for sealed generations
        that don't have one yet — the sharded twin of
        :meth:`LeanZ3Index.build_pyramids`.  Each generation's base
        grid comes from ONE single-generation density push-down (the
        allgathered grid is process-invariant, so cached pyramids
        agree on every process), then reduces on host through the
        exact 2×2 ladder.  Returns the number of pyramids built."""
        import time
        from ..config import DensityProperties
        from ..index.pyramid import DensityPyramid, pyramid_spec
        from ..metrics import (
            PYRAMID_BUILD_MS, PYRAMID_BUILDS, registry as _metrics,
        )
        from ..resilience.faults import fault_point
        base = int(base if base is not None
                   else DensityProperties.PYRAMID_BASE.to_int())
        if base < 1 or base & (base - 1):
            raise ValueError(
                f"pyramid base must be a power of two, got {base}")
        levels = int(levels if levels is not None
                     else DensityProperties.PYRAMID_LEVELS.to_int())
        cache = self._pyramid_cache.spec_cache(pyramid_spec(base))
        built = 0
        for g in list(self.generations[:-1]):
            if g.gen_id in cache:
                continue
            fault_point("pyramid.build")
            t0 = time.perf_counter()
            with obs_span("pyramid.build", gen_id=g.gen_id,
                          tier=g.tier, base=base):
                part = self.density(
                    [_PYRAMID_WORLD], None, None, _PYRAMID_WORLD,
                    base, base, _gens=[g], _record_heat=False)
                pyr = DensityPyramid.from_base(part, levels)
            self._pyramid_cache.add(cache, g.gen_id, pyr)
            obs_count(PYRAMID_BUILDS)
            _metrics.timer(PYRAMID_BUILD_MS).update(
                (time.perf_counter() - t0) * 1e3)
            built += 1
        return built

    def density_tile(self, z: int, x: int, y: int, tile: int = 256,
                     max_ranges: int = 2000) -> np.ndarray:
        """One (tile, tile) slippy-tile density grid — see
        :func:`geomesa_tpu.index.pyramid.density_tile`."""
        from ..index.pyramid import density_tile as _density_tile
        return _density_tile(self, z, x, y, tile, max_ranges)

    def _inherit_pyramids(self, dead_ids: list, new_gen_id: int) -> None:
        """Compaction inheritance: the merged generation's pyramid is
        the elementwise SUM of its parents' — exact, because density
        is additive over generations.  Any parent missing a pyramid
        leaves the merged generation pyramid-less (the next build pass
        fills it; queries fall back to scanning it meanwhile)."""
        from ..index.pyramid import DensityPyramid
        for _spec, cache in self._pyramid_cache.items():
            parents = [cache.get(gid) for gid in dead_ids]
            if all(p is not None for p in parents):
                merged = DensityPyramid.sum(parents)
                if merged is not None:
                    self._pyramid_cache.add(cache, new_gen_id, merged)

    def _pyramid_level(self, gen_id: int, width: int):
        """The (width, width) pyramid grid for a sealed generation, or
        None when no cached pyramid carries that resolution."""
        for _spec, cache in self._pyramid_cache.items():
            pyr = cache.get(gen_id)
            if pyr is not None:
                lvl = pyr.level(width)
                if lvl is not None:
                    return lvl
        return None

    # -- scan helpers -----------------------------------------------------
    def _host_runs_stack(self, host_gens: list):
        """This process's spilled runs stacked into one
        :class:`~geomesa_tpu.index.z3_lean.HostStack` (cached until the
        next spill)."""
        if self._host_stack is None:
            from ..index.z3_lean import HostStack
            self._host_stack = HostStack(
                [run for gen in host_gens for run in gen.runs])
        return self._host_stack

    def _pad_bucket(self, gens: list, tier: str) -> list:
        """Pad a generation list to the compile bucket with this
        index's shared full-size sentinel generation (zero seeks
        match)."""
        n_pad = (-len(gens)) % _GEN_BUCKET
        return list(gens) + [self._sentinel(tier)] * n_pad

    @staticmethod
    def _concat_boxes(w_boxes: list):
        """Concatenate per-window boxes with owning qids, padded to a
        compile bucket via the shared never-matching-box convention
        (ops/search.pad_boxes)."""
        boxes_c = np.concatenate(w_boxes)
        bqid_c = np.concatenate(
            [np.full(len(b), q, dtype=np.int32)
             for q, b in enumerate(w_boxes)])
        _, boxes_c, bqid_c = pad_boxes(
            boxes_c, boxes_c, pad_pow2(len(boxes_c), minimum=1), bqid_c)
        return boxes_c, bqid_c

    def _scan_tier(self, gens, totals, rb, rlo, rhi, rq, pos_bits,
                   exact_args) -> list:
        """Run one tier's batched scan, falling back to per-generation
        dispatches (each sized by its OWN max-shard total) when the
        shared-capacity batched buffer would exceed the per-shard
        budget — matching rows must never silently truncate
        (expand_ranges masks out everything past capacity).  Returns
        flat int64 coded arrays (padding stripped); full-tier outputs
        are TRUE hits, keys-tier outputs are candidates."""
        tier = "full" if exact_args is not None else "keys"
        # scan only generations with candidates anywhere on the mesh
        # (process-invariant: totals is the fetched global probe) —
        # time-partitioned ingest leaves most generations empty for a
        # window and the shared capacity must not be spent on them
        live = [i for i in range(len(gens))
                if int(totals[:, i].max())]
        if not live:
            return []
        gens = [gens[i] for i in live]
        totals = totals[:, live]
        per_gen_cap = gather_capacity(
            int(totals.max()), minimum=self.DEFAULT_CAPACITY)
        padded = self._pad_bucket(gens, tier)
        if per_gen_cap * len(padded) <= self.BATCH_SCAN_BUDGET:
            groups = [padded]
            caps = [per_gen_cap * len(padded)]
        else:
            gen_tot = totals.max(axis=0)     # per-gen max over shards
            groups = [[gens[g]] for g in range(len(gens))
                      if int(gen_tot[g])]
            caps = [gather_capacity(int(gen_tot[g]),
                                    minimum=self.DEFAULT_CAPACITY)
                    for g in range(len(gens)) if int(gen_tot[g])]
        parts = []
        from ..resilience import breaker, classify_device_failure
        for group, cap in zip(groups, caps):
            # NOTE (ISSUE 16): no per-process deadline break and no
            # demote-and-retry INSIDE this loop — these dispatches are
            # mesh collectives, and a process bailing or retrying alone
            # would strand its peers (deadline checks live at the
            # phase boundaries in query_many, the planner precedent).
            # Failures still classify, so the breaker/metrics see
            # device pressure even where degraded routing cannot run.
            try:
                with device_span("query.scan.device", tier=tier,
                                 runs=len(group)):
                    scan_cols: list = []
                    for gen in group:
                        if tier == "full":
                            scan_cols += [gen.bins, gen.z, gen.pos,
                                          gen.x, gen.y, gen.t]
                        else:
                            scan_cols += [gen.bins, gen.z, gen.pos]
                    self.dispatch_count += 1
                    if tier == "full":
                        packed = _fetch_global(_scan_program_exact(
                            self.mesh, len(group), cap, pos_bits)(
                            rb, rlo, rhi, rq, *exact_args, *scan_cols))
                    else:
                        packed = _fetch_global(_scan_program(
                            self.mesh, len(group), cap, pos_bits)(
                            rb, rlo, rhi, rq, *scan_cols))
            except Exception as e:  # noqa: BLE001 — classify + rethrow
                if classify_device_failure(e) == "transient":
                    for gen in group:
                        breaker.record_failure((id(self), gen.gen_id))
                raise
            # host-side filtering after the span — device_ms must not
            # absorb numpy post-processing (see z3_lean._scan_tier)
            part = packed.ravel()
            parts.append(part[part >= 0])
        return parts
