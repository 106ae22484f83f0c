"""ShardedLeanAttrIndex: the lean attribute tier over a device mesh.

The single-chip :class:`~geomesa_tpu.index.attr_lean.LeanAttrIndex`
composed with the mesh, the way
:class:`~geomesa_tpu.parallel.lean.ShardedLeanZ3Index` composes the z3
tier (round-4 VERDICT #1: "two-process CI covers the multihost
variant").  Layout: every generation's ``(key int64, sec int64,
gid int64)`` columns are stacked per shard — ``(n_shards, slots)``
arrays under ``P("shard", None)`` — and the probe/scan programs run
under ``shard_map``: each device seeks its own sorted runs, all
generations in one dispatch.

Gids are GLOBAL (``process << GID_PROC_SHIFT | local_row`` multihost,
plain row ids single-controller).  Query results are CANDIDATE gids,
fetched globally on every process; the planner residual-filters each
process's local rows and allgathers survivors (its normal multihost
discipline), so exactness needs nothing index-specific.

Residency: ``device`` ↔ ``host`` under a PER-SHARD HBM budget,
demotions oldest-first from process-invariant metadata (multihost
processes always pick the same tiers).  Host-tier runs spill to the
OWNING process's RAM (its addressable shards hold exactly its rows) and
seek through the stacked composite bisection — flat in run count.

Reference: AttributeIndexKey.scala:38-52 + AttributeFilterStrategy
(the lexicoded attribute index the cluster serves at any scale).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..index.attr_lean import (
    _SENTINEL_KEY, _HostAttrStack, _I64_MAX, _I64_MIN, SLOT_BYTES,
    encode_attr_value, encode_attr_values, string_prefix_bounds,
)
from ..metrics import WRITE_SEALS, WRITE_SPILLS
from ..obs import device_span, obs_count, span as obs_span
from ..obs.heat import (
    heat_enabled, merge_index_generations, record_index_scan,
)
from ..ops.search import (
    expand_ranges, gather_capacity, pad_pow2, searchsorted2, sort_lex2,
)
from .scan import _fetch_global, encode_gids
from ..index.xz2_lean import (
    LeanXZ3Index as _LeanXZ3Facade, XZ2Facade as _XZ2Facade,
)

__all__ = ["ShardedLeanAttrIndex", "ShardedLeanXZ2Index",
           "ShardedLeanXZ3Index"]

_GEN_BUCKET = 4


@lru_cache(maxsize=8)
def _append_program(mesh: Mesh):
    """Per-shard append at PER-SHARD offsets: ``r`` is a
    ``(n_shards, 1)`` fill vector, so each shard merges its slice into
    its OWN unused padded region — collective steps whose slices are
    smaller than ``m_pad`` no longer burn the padding gap on every
    shard (each shard's valid rows sort to the front, so its fill IS
    its next write offset)."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard", None),) * 8,
             out_specs=(P("shard", None),) * 3)
    def app(keys, sec, gid, r, ks, ss, gs, m):
        k0, s0, g0 = keys[0], sec[0], gid[0]
        valid = jnp.arange(ks.shape[1]) < m[0, 0]
        k_new = jnp.where(valid, ks[0], _SENTINEL_KEY)
        s_new = jnp.where(valid, ss[0], jnp.int64(_I64_MAX))
        g_new = jnp.where(valid, gs[0], jnp.int64(-1))
        k0 = jax.lax.dynamic_update_slice(k0, k_new, (r[0, 0],))
        s0 = jax.lax.dynamic_update_slice(s0, s_new, (r[0, 0],))
        g0 = jax.lax.dynamic_update_slice(g0, g_new, (r[0, 0],))
        k0, s0, g0 = sort_lex2(k0, s0, g0)
        return k0[None], s0[None], g0[None]

    return jax.jit(app, donate_argnums=(0, 1, 2))


@lru_cache(maxsize=8)
def _count_program(mesh: Mesh, n_gens: int):
    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 4 + (P("shard", None),) * (2 * n_gens),
             out_specs=P("shard", None))
    def count(qklo, qkhi, qslo, qshi, *cols):
        outs = []
        for g in range(n_gens):
            k, s = cols[2 * g][0], cols[2 * g + 1][0]
            starts = searchsorted2(k, s, qklo, qslo, side="left")
            ends = searchsorted2(k, s, qkhi, qshi, side="right")
            outs.append(jnp.sum(jnp.maximum(ends - starts, 0)))
        return jnp.stack(outs)[None]

    return jax.jit(count)


@lru_cache(maxsize=8)
def _scan_program(mesh: Mesh, n_gens: int, capacity: int, pos_bits: int):
    @partial(shard_map, mesh=mesh,
             in_specs=(P(None),) * 5 + (P("shard", None),) * (3 * n_gens),
             out_specs=P("shard", None))
    def scan(qklo, qkhi, qslo, qshi, qqid, *cols):
        per_gen = capacity // max(1, n_gens)
        outs = []
        for g in range(n_gens):
            k, s, gid = (cols[3 * g][0], cols[3 * g + 1][0],
                         cols[3 * g + 2][0])
            starts = searchsorted2(k, s, qklo, qslo, side="left")
            ends = searchsorted2(k, s, qkhi, qshi, side="right")
            counts = jnp.maximum(ends - starts, 0)
            idx, valid, rid = expand_ranges(starts, counts, per_gen)
            coded = ((qqid[rid].astype(jnp.int64) << pos_bits)
                     | gid[idx])
            outs.append(jnp.where(valid, coded, jnp.int64(-1)))
        return jnp.concatenate(outs)[None]

    return jax.jit(scan)


@lru_cache(maxsize=16)
def _sketch_program(mesh: Mesh, n_gens: int, bins: int, depth: int,
                    width: int, is_float: bool):
    """Per-shard stat-sketch fold under shard_map (ISSUE 3): each
    device folds its own sorted runs through the SHARED
    :func:`~geomesa_tpu.stats.sketch.device_fold_body` (one definition
    with the single-chip kernel — no drift); hist/count-min tables
    merge with ``psum`` over ICI, the five scalar partials come back
    per-shard (the chip backend lowers only SUM all-reduces, so
    min/max reduce on the host — the parallel.stats._moments_program
    discipline)."""
    from ..stats.sketch import device_fold_body

    specs_in = (P(),) * 4 + (P("shard", None),) * (2 * n_gens)
    out_specs = (P("shard", None),) * 5 + (P(None, None), P(None, None, None))

    @partial(shard_map, mesh=mesh, in_specs=specs_in,
             out_specs=out_specs)
    def fold(slo, shi, hlo, hhi, *cols):
        cnts, kmins, kmaxs, sums, sumsqs, hists, cmss = \
            [], [], [], [], [], [], []
        for g in range(n_gens):
            k, s = cols[2 * g][0], cols[2 * g + 1][0]
            cnt, kmin, kmax, vsum, vsumsq, hist, cms = device_fold_body(
                k, s, slo, shi, hlo, hhi, bins=bins, depth=depth,
                width=width, is_float=is_float)
            cnts.append(cnt)
            kmins.append(kmin)
            kmaxs.append(kmax)
            sums.append(vsum)
            sumsqs.append(vsumsq)
            hists.append(hist)
            cmss.append(cms)
        return (jnp.stack(cnts)[None], jnp.stack(kmins)[None],
                jnp.stack(kmaxs)[None], jnp.stack(sums)[None],
                jnp.stack(sumsqs)[None],
                jax.lax.psum(jnp.stack(hists), "shard"),
                jax.lax.psum(jnp.stack(cmss), "shard"))

    return jax.jit(fold)


class _ShardedAttrGen:
    __slots__ = ("keys", "sec", "gid", "n_slots", "tier", "spilled",
                 "fill", "gen_id")

    @classmethod
    def merged_device(cls, keys, sec, gid,
                      n_slots: int) -> "_ShardedAttrGen":
        """A compacted device generation from already-merged per-shard
        columns (zero slack slots)."""
        gen = cls.__new__(cls)
        gen.keys, gen.sec, gen.gid = keys, sec, gid
        gen.n_slots = int(n_slots)
        gen.tier = "device"
        gen.spilled = None
        gen.fill = None
        gen.gen_id = -1
        return gen

    @classmethod
    def merged_host(cls, parts: list,
                    n_slots: int) -> "_ShardedAttrGen":
        """A compacted host generation from already-merged spilled
        parts (this process's local rows)."""
        gen = cls.__new__(cls)
        gen.keys = gen.sec = gen.gid = None
        gen.n_slots = int(n_slots)
        gen.tier = "host"
        gen.spilled = parts
        gen.fill = None
        gen.gen_id = -1
        return gen

    def __init__(self, mesh: Mesh, slots: int):
        shards = int(mesh.devices.size)
        sh = NamedSharding(mesh, P("shard", None))
        self.keys = jax.device_put(
            np.full((shards, slots), _SENTINEL_KEY, np.int64), sh)
        self.sec = jax.device_put(
            np.full((shards, slots), _I64_MAX, np.int64), sh)
        self.gid = jax.device_put(
            np.full((shards, slots), -1, np.int64), sh)
        self.n_slots = 0
        self.tier = "device"
        self.spilled: list[tuple] | None = None
        #: per-LOCAL-shard valid-row counts (write offsets): appends
        #: merge each slice into the shard's own unused padded region
        #: instead of burning ``m_pad`` sentinel slots fleet-wide on
        #: every collective step.  ``n_slots`` remains the agreed
        #: (process-invariant) upper bound any shard's fill can reach.
        self.fill: np.ndarray | None = None
        #: store-lifetime-unique run identity — minted by the owning
        #: index from agreed (process-invariant) appends/merges, so
        #: every multihost process keys the sketch-partial cache
        #: identically (index/attr_lean._AttrGeneration.gen_id)
        self.gen_id = -1

    @property
    def slots(self) -> int:
        return 0 if self.tier == "host" else int(self.keys.shape[1])

    def per_shard_bytes(self) -> int:
        if self.tier == "host":
            return 0
        return int(self.keys.shape[1]) * (8 + 8 + 8)

    def spill_to_host(self) -> None:
        """device → host: each process fetches its ADDRESSABLE shards'
        sorted runs (exactly its local rows) and frees the HBM."""
        if self.tier != "device":
            return
        local: dict = {}
        for name, arr in (("k", self.keys), ("s", self.sec),
                          ("g", self.gid)):
            for sh in arr.addressable_shards:
                row = sh.index[0].start or 0
                local.setdefault(row, {})[name] = np.asarray(sh.data)[0]
        self.spilled = []
        for row in sorted(local):
            cols = local[row]
            valid = cols["g"] >= 0
            # mutable: the host stack re-points these at views so one
            # copy survives (see _HostAttrStack)
            self.spilled.append([cols["k"][valid], cols["s"][valid],
                                 cols["g"][valid]])
        self.keys = self.sec = self.gid = None
        self.tier = "host"


class ShardedLeanAttrIndex:
    """Sharded tiered generational attribute index (module doc)."""

    #: ``(schema, index_key)`` for access-temperature attribution
    #: (obs/heat) — stamped by the datastore / the owning XZ facade
    heat_scope: tuple | None = None

    @staticmethod
    def gather_payload(positions):
        """Result-materialization protocol hook (ISSUE 14): sharded
        attribute runs key lexicodes, not a row-addressable payload —
        ``None`` routes the Arrow result path to the host column
        store's vectorized take (index/attr_lean.LeanAttrIndex)."""
        return None

    #: slots per generation PER SHARD
    GENERATION_SLOTS = 1 << 22
    DEFAULT_CAPACITY = 1 << 15
    BATCH_SCAN_BUDGET = 1 << 26
    #: default PER-SHARD HBM budget (the store splits its lean budget)
    HBM_BUDGET_BYTES = int(2.0 * 2 ** 30)
    #: size-tiered compaction trigger (see index/attr_lean)
    COMPACTION_FACTOR = 4

    def __init__(self, attr: str, attr_type: str, mesh: Mesh,
                 generation_slots: int | None = None,
                 multihost: bool = False,
                 hbm_budget_bytes: int | None = None,
                 compaction_factor: int | None = None):
        self.attr = attr
        self.attr_type = attr_type.lower()
        self.mesh = mesh
        self._multihost = bool(multihost)
        self.generation_slots = generation_slots or self.GENERATION_SLOTS
        self.hbm_budget_bytes = hbm_budget_bytes or self.HBM_BUDGET_BYTES
        self.generations: list[_ShardedAttrGen] = []
        self._host_stack: _HostAttrStack | None = None
        self._n_local = 0
        self._n_total = 0
        self.dispatch_count = 0
        self._sentinel_gen: _ShardedAttrGen | None = None
        #: opportunistic compaction factor (0 = off)
        self.compaction_factor = int(compaction_factor or 0)
        self.compactions = 0
        #: sealed-run sketch partials: fold spec → {gen_id: RunSketch}
        #: — GLOBAL (post-collective) partials, so every multihost
        #: process caches identical values and cache hits stay agreed
        from ..index.attr_lean import LeanAttrIndex
        from ..index.partial_cache import PartialCache
        self._sketch_cache = PartialCache(
            LeanAttrIndex.SKETCH_CACHE_SPECS,
            LeanAttrIndex.SKETCH_CACHE_MAX_BYTES)
        #: generation-lifecycle hooks ``(kind, gen_ids)`` fired on
        #: seal/merge (index/lsm.notify_generation_event)
        self.generation_listeners: list = []
        self._gen_counter = 0

    def _next_gen_id(self) -> int:
        self._gen_counter += 1
        return self._gen_counter

    def __len__(self) -> int:
        return self._n_total

    def tier_counts(self) -> dict:
        out = {"device": 0, "host": 0}
        for g in self.generations:
            out[g.tier] += 1
        return out

    #: per-slot device bytes (keys int64 + sec int64 + gid int64 — the
    #: sharded gid column is int64, unlike the single-chip int32)
    SLOT_BYTES = 8 + 8 + 8

    def device_bytes(self) -> int:
        """Total HBM across every shard's device generations."""
        shards = int(self.mesh.devices.size)
        return sum(g.per_shard_bytes() * shards
                   for g in self.generations)

    def host_key_bytes(self) -> int:
        """Host RAM THIS process holds in spilled (key, sec, gid)
        runs (per-process residency; mesh-wide = sum over processes)."""
        return sum(len(p[0]) * self.SLOT_BYTES
                   for g in self.generations if g.spilled
                   for p in g.spilled)

    def sentinel_bytes(self) -> int:
        return (0 if self._sentinel_gen is None
                else self._sentinel_gen.per_shard_bytes()
                * int(self.mesh.devices.size))

    def storage_stats(self) -> dict:
        """Live byte accounting for the storage report (obs/resource,
        ISSUE 9) — the sharded twin of LeanAttrIndex.storage_stats."""
        gens = [{"gen_id": g.gen_id, "tier": g.tier,
                 "slots": int(g.n_slots), "capacity": g.slots,
                 "device_bytes": (g.per_shard_bytes()
                                  * int(self.mesh.devices.size)),
                 "host_bytes": (sum(len(p[0]) * self.SLOT_BYTES
                                    for p in g.spilled)
                                if g.spilled else 0)}
                for g in self.generations]
        return {"kind": type(self).__name__, "rows": len(self),
                "attr": self.attr,
                "tiers": self.tier_counts(),
                "device_bytes": self.device_bytes(),
                "host_bytes": self.host_key_bytes(),
                "sentinel_bytes": self.sentinel_bytes(),
                "hbm_budget_bytes": self.hbm_budget_bytes,
                "generations": gens,
                "caches": {"sketch": self._sketch_cache.stats()},
                "dispatches": self.dispatch_count}

    def block(self) -> None:
        for gen in reversed(self.generations):
            if gen.tier == "device":
                jax.block_until_ready(gen.gid)
                break

    # -- write path -------------------------------------------------------
    def _agreed(self, value: int, op: str) -> int:
        if not self._multihost:
            return int(value)
        from .multihost import agreed_int
        return agreed_int(int(value), op)

    def _sentinel(self) -> _ShardedAttrGen:
        if self._sentinel_gen is None:
            self._sentinel_gen = _ShardedAttrGen(self.mesh,
                                                 self.generation_slots)
        return self._sentinel_gen

    def _roll_generation(self) -> "_ShardedAttrGen":
        """Open a fresh live generation and rebalance (the append
        rollover body, factored so the seal span wraps it once)."""
        gen = _ShardedAttrGen(self.mesh, self.generation_slots)
        gen.gen_id = self._next_gen_id()
        self.generations.append(gen)
        self._rebalance()
        return self.generations[-1]

    def _per_shard_resident(self) -> int:
        per = sum(g.per_shard_bytes() for g in self.generations)
        return per + self.generation_slots * (8 + 8 + 8)  # sentinel

    def _rebalance(self) -> None:
        for gen in self.generations[:-1]:
            if self._per_shard_resident() <= self.hbm_budget_bytes:
                return
            if gen.tier == "device":
                # blocking device→host fetch (write-span taxonomy)
                with device_span("write.spill", gen_id=gen.gen_id,
                                 slots=int(gen.n_slots)):
                    obs_count(WRITE_SPILLS)
                    gen.spill_to_host()
                self._host_stack = None
        if self._per_shard_resident() > self.hbm_budget_bytes:
            raise MemoryError(
                f"active attr generation ({self.generation_slots} "
                f"slots/shard) exceeds hbm_budget_bytes="
                f"{self.hbm_budget_bytes}")

    def append(self, values, dtg_ms,
               base_gid: int | None = None) -> "ShardedLeanAttrIndex":
        """Distribute this process's rows across its local shards and
        merge collectively (the ShardedLeanZ3Index append discipline:
        one agreement for the whole append; trailing processes feed
        empty slices)."""
        keys = encode_attr_values(values, self.attr_type)
        sec = np.ascontiguousarray(dtg_ms, np.int64)
        m_local = len(keys)
        m_max = self._agreed(m_local, "max")
        if m_max == 0:
            return self
        n_shards = int(self.mesh.devices.size)
        from .multihost import local_device_count
        local_shards = (local_device_count(self.mesh)
                        if self._multihost else n_shards)
        per = -(-max(1, m_max) // local_shards)
        m_pad = min(gather_capacity(per, minimum=8),
                    self.generation_slots)
        base = self._n_local if base_gid is None else int(base_gid)
        done = 0
        while done < m_max:
            gen = self.generations[-1] if self.generations else None
            if gen is None or gen.tier == "host" \
                    or gen.n_slots + m_pad > gen.slots:
                if gen is not None and gen.tier != "host":
                    # live run seals on rollover (write-span taxonomy)
                    sealed_id = gen.gen_id
                    with obs_span("write.seal", gen_id=gen.gen_id,
                                  tier=gen.tier,
                                  slots=int(gen.n_slots)):
                        obs_count(WRITE_SEALS)
                        gen = self._roll_generation()
                    from ..index.lsm import notify_generation_event
                    notify_generation_event(self, "seal", [sealed_id])
                else:
                    gen = self._roll_generation()
            if gen.fill is None:
                gen.fill = np.zeros(local_shards, np.int64)
            take_all = min(m_pad * local_shards, max(0, m_local - done))
            ks = np.full((local_shards, m_pad), _SENTINEL_KEY, np.int64)
            ss = np.full((local_shards, m_pad), _I64_MAX, np.int64)
            gs = np.full((local_shards, m_pad), -1, np.int64)
            ms = np.zeros((local_shards, 1), np.int32)
            if take_all > 0:
                sl = slice(done, done + take_all)
                rows = np.arange(base + done, base + done + take_all,
                                 dtype=np.int64)
                gids = (encode_gids(rows) if self._multihost else rows)
                for s in range(local_shards):
                    lo, hi = s * m_pad, min(take_all, (s + 1) * m_pad)
                    if hi <= lo:
                        break
                    k = hi - lo
                    ks[s, :k] = keys[sl][lo:hi]
                    ss[s, :k] = sec[sl][lo:hi]
                    gs[s, :k] = gids[lo:hi]
                    ms[s, 0] = k
            # per-shard write offsets: each shard's valid rows sort to
            # the front, so its fill is exactly where its sentinel
            # padding begins
            rs = gen.fill.reshape((local_shards, 1)).astype(np.int32)
            sh = NamedSharding(self.mesh, P("shard", None))
            if self._multihost:
                arrs = [jax.make_array_from_process_local_data(sh, a)
                        for a in (rs, ks, ss, gs, ms)]
            else:
                arrs = [jax.device_put(a, sh)
                        for a in (rs, ks, ss, gs, ms)]
            self.dispatch_count += 1
            gen.keys, gen.sec, gen.gid = _append_program(self.mesh)(
                gen.keys, gen.sec, gen.gid, *arrs)
            gen.fill += ms[:, 0]
            # the agreed bound: the busiest shard anywhere gained at
            # most min(m_pad, rows remaining) valid rows this step —
            # NOT m_pad unconditionally (the old slot burn)
            gen.n_slots += int(min(m_pad, m_max - done))
            done += m_pad * local_shards
        self._n_local += m_local
        self._n_total += self._agreed(m_local, "sum")
        if self.compaction_factor:
            # deterministic one-group cap per append (multihost-safe)
            self.compact(factor=self.compaction_factor, max_groups=1)
        return self

    # -- compaction (LSM maintenance) -------------------------------------
    def _compaction_groups(self, factor: int) -> list[list]:
        """Size-tiered merge plan over SEALED generations, bucketed by
        consumed slot count (agreed metadata — identical on every
        multihost process)."""
        from ..index.lsm import plan_size_tiered
        return plan_size_tiered(self.generations[:-1],
                                ("device", "host"),
                                lambda g: g.n_slots, factor)

    def _merge_group(self, group: list) -> None:
        from ..index.attr_lean import merge_spilled_parts
        from ..index.lsm import merged_capacity, replace_group
        from .lean import _merge_program
        n_slots = int(sum(g.n_slots for g in group))
        if group[0].tier == "device":
            cols: list = []
            for g in group:
                cols += [g.keys, g.sec, g.gid]
            out_slots = merged_capacity(
                n_slots, sum(g.slots for g in group), gather_capacity)
            self.dispatch_count += 1
            keys, sec, gid = _merge_program(
                self.mesh, len(group), out_slots)(*cols)
            merged = _ShardedAttrGen.merged_device(keys, sec, gid,
                                                   n_slots=n_slots)
        else:
            merged = _ShardedAttrGen.merged_host(
                [merge_spilled_parts(
                    [p for g in group for p in g.spilled])],
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
        self.generations = replace_group(self.generations, group,
                                         merged)
        self.compactions += 1
        from ..metrics import (
            LEAN_COMPACTION_MERGES, LEAN_COMPACTION_ROWS,
            registry as _metrics,
        )
        _metrics.counter(LEAN_COMPACTION_MERGES).inc()
        # consumed-slot upper bound × shards (exact per-shard valid
        # counts live on device)
        _metrics.counter(LEAN_COMPACTION_ROWS).inc(
            n_slots * int(self.mesh.devices.size))
        from ..index.lsm import notify_generation_event
        notify_generation_event(self, "merge", [merged.gen_id])

    def compact(self, budget_ms: float | None = None,
                factor: int | None = None,
                max_groups: int | None = None) -> dict:
        """Incremental size-tiered merge compaction of the sharded
        attribute runs.  ``budget_ms`` is ignored under multihost
        (``max_groups`` and the invariant plan are the agreed stopping
        points — see ShardedLeanZ3Index.compact)."""
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

    # -- stat-sketch push-down (ISSUE 3) ----------------------------------
    def _local_runs(self, gen) -> list:
        """(keys, sec) arrays of THIS process's addressable shards for
        one device generation (valid rows sort to each shard's
        front)."""
        local: dict = {}
        for name, arr in (("k", gen.keys), ("s", gen.sec),
                          ("g", gen.gid)):
            for sh in arr.addressable_shards:
                row = sh.index[0].start or 0
                local.setdefault(row, {})[name] = np.asarray(sh.data)[0]
        runs = []
        for row in sorted(local):
            c = local[row]
            valid = c["g"] >= 0
            runs.append((c["k"][valid], c["s"][valid]))
        return runs

    def sketch_scan(self, fold):
        """Fold every run's rows matching ``fold``'s sec window into
        ONE merged RunSketch across the whole mesh — the sharded twin
        of :meth:`~geomesa_tpu.index.attr_lean.LeanAttrIndex.
        sketch_scan`: device runs fold per shard under shard_map with
        hist/count-min tables psum-merged over ICI; host-tier runs
        fold on their owning process and allgather through the monoid;
        sealed runs' GLOBAL partials cache identically on every
        process (agreed cache hits — no process strands a
        collective)."""
        with obs_span("lean.sketch", attr=self.attr, sharded=True,
                      generations=len(self.generations)):
            return self._sketch_scan(fold)

    def _sketch_scan(self, fold):
        from ..metrics import (
            LEAN_SKETCH_CACHE_HITS, LEAN_SKETCH_CACHE_MISSES,
        )
        from ..stats.sketch import RunSketch, fold_attr_runs
        from .stats import allreduce_run_sketch
        merged = RunSketch()
        if not self.generations:
            return merged
        live = self.generations[-1]
        cache = self._sketch_cache.spec_cache(fold)
        dev_scan: list = []
        host_scan: list = []
        _ht: list | None = [] if heat_enabled() else None
        for g in self.generations:
            part = cache.get(g.gen_id) if g is not live else None
            if part is not None:
                obs_count(LEAN_SKETCH_CACHE_HITS)
                merged = merged + part
            elif g.tier == "device":
                dev_scan.append(g)
            else:
                host_scan.append(g)
            if _ht is not None:
                _ht.append((g.gen_id, g.tier, int(g.n_slots),
                            0 if part is not None
                            else g.per_shard_bytes()
                            * int(self.mesh.devices.size), None))
        if _ht:
            record_index_scan(self, _ht)
        is_float = self.attr_type in ("float", "double")
        new_parts: dict[int, object] = {}
        if dev_scan and not fold.want_values:
            n_b = (-len(dev_scan)) % _GEN_BUCKET
            padded = list(dev_scan) + [self._sentinel()] * n_b
            cols: list = []
            for g in padded:
                cols += [g.keys, g.sec]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="sketch",
                             runs=len(dev_scan)):
                prog = _sketch_program(self.mesh, len(padded),
                                       int(fold.bins), int(fold.depth),
                                       int(fold.width), is_float)
                outs = prog(jnp.int64(fold.slo), jnp.int64(fold.shi),
                            jnp.float64(fold.hlo),
                            jnp.float64(fold.hhi), *cols)
                cnt = _fetch_global(outs[0]).sum(axis=0)
                kmin = _fetch_global(outs[1]).min(axis=0)
                kmax = _fetch_global(outs[2]).max(axis=0)
                vsum = _fetch_global(outs[3]).sum(axis=0)
                vsumsq = _fetch_global(outs[4]).sum(axis=0)
                hist = np.asarray(outs[5])
                cms = np.asarray(outs[6])
            for i, g in enumerate(dev_scan):
                n = int(cnt[i])
                new_parts[id(g)] = RunSketch(
                    n, int(kmin[i]) if n else None,
                    int(kmax[i]) if n else None,
                    float(vsum[i]), float(vsumsq[i]),
                    np.array(hist[i]) if fold.bins else None,
                    np.array(cms[i]) if fold.depth else None)
        elif dev_scan:
            # exact value→count folds: each process folds its
            # addressable shards, partials allgather through the monoid
            for g in dev_scan:
                local = RunSketch()
                for p in fold_attr_runs(self._local_runs(g), fold,
                                        self.attr_type):
                    local = local + p
                new_parts[id(g)] = allreduce_run_sketch(local) \
                    if self._multihost else local
        for g in host_scan:
            local = RunSketch()
            for p in fold_attr_runs([(p[0], p[1]) for p in g.spilled],
                                    fold, self.attr_type):
                local = local + p
            new_parts[id(g)] = allreduce_run_sketch(local) \
                if self._multihost else local
        for g in dev_scan + host_scan:
            p = new_parts[id(g)]
            merged = merged + p
            if g is not live:
                obs_count(LEAN_SKETCH_CACHE_MISSES)
                self._sketch_cache.add(cache, g.gen_id, p)
        return merged

    # -- query path -------------------------------------------------------
    def query_ranges(self, ranges: list, n_windows: int = 1,
                     total_rows: int | None = None) -> np.ndarray:
        """GLOBAL candidate gids for inclusive composite ranges
        ``(klo, khi, slo, shi, qid)`` — identical on every process
        (device candidates fetch globally; host-tier locals
        allgather)."""
        if not ranges or self._n_total == 0:
            return np.empty(0, np.int64)
        n_pad = pad_pow2(len(ranges))
        qklo = np.full(n_pad, 1, np.int64)
        qkhi = np.full(n_pad, 0, np.int64)
        qslo = np.full(n_pad, 1, np.int64)
        qshi = np.full(n_pad, 0, np.int64)
        qqid = np.zeros(n_pad, np.int32)
        for i, (klo, khi, slo, shi, qid) in enumerate(ranges):
            qklo[i] = klo
            qkhi[i] = khi
            qslo[i] = _I64_MIN if slo is None else slo
            qshi[i] = _I64_MAX if shi is None else shi
            qqid[i] = qid
        from .scan import multihost_gid_span
        span = (multihost_gid_span() if self._multihost
                else max(2, self._n_total))
        pos_bits = max(1, int(np.ceil(np.log2(span))))
        jk = (jnp.asarray(qklo), jnp.asarray(qkhi),
              jnp.asarray(qslo), jnp.asarray(qshi))
        dev_gens = [g for g in self.generations if g.tier == "device"]
        host_gens = [g for g in self.generations if g.tier == "host"]
        parts: list = []
        if dev_gens:
            n_b = (-len(dev_gens)) % _GEN_BUCKET
            padded = list(dev_gens) + [self._sentinel()] * n_b
            count_cols: list = []
            for gen in padded:
                count_cols += [gen.keys, gen.sec]
            self.dispatch_count += 1
            totals = _fetch_global(
                _count_program(self.mesh, len(padded))(*jk, *count_cols))
            # adaptive-replan probe point (ISSUE 19): fetched totals are
            # GLOBAL (process-invariant) so the signal is multihost-
            # agreed; host-tier counts are process-local — no probe
            from ..planning.adaptive import check_replan
            check_replan("query.scan.probe", int(totals.sum()))
            if int(totals.sum()):
                per_gen_cap = gather_capacity(
                    int(totals.max()), minimum=self.DEFAULT_CAPACITY)
                if per_gen_cap * len(padded) <= self.BATCH_SCAN_BUDGET:
                    groups = [padded]
                    caps = [per_gen_cap * len(padded)]
                else:
                    gen_tot = totals.max(axis=0)
                    groups = [[dev_gens[g]] for g in range(len(dev_gens))
                              if int(gen_tot[g])]
                    caps = [gather_capacity(int(gen_tot[g]),
                                            minimum=self.DEFAULT_CAPACITY)
                            for g in range(len(dev_gens))
                            if int(gen_tot[g])]
                from ..resilience import breaker, classify_device_failure
                for group, cap in zip(groups, caps):
                    # ISSUE 16: these dispatches are mesh collectives —
                    # no per-process deadline break and no local
                    # demote-and-retry (a lone process bailing would
                    # strand its peers).  Failures still classify so the
                    # breaker/metrics see device pressure even where
                    # degraded routing cannot run (parallel/lean.py
                    # precedent).
                    try:
                        cols: list = []
                        for gen in group:
                            cols += [gen.keys, gen.sec, gen.gid]
                        self.dispatch_count += 1
                        packed = _fetch_global(_scan_program(
                            self.mesh, len(group), cap, pos_bits)(
                            *jk, jnp.asarray(qqid), *cols))
                    except Exception as e:  # noqa: BLE001 — classify
                        if classify_device_failure(e) == "transient":
                            for gen in group:
                                breaker.record_failure(
                                    (id(self), gen.gen_id))
                        raise
                    flat = packed.ravel()
                    parts.append(flat[flat >= 0])
        host_cand_n = 0
        if host_gens:
            if self._host_stack is None:
                runs: list = []
                for g in host_gens:
                    runs.extend(g.spilled)
                self._host_stack = _HostAttrStack(runs)
            coded = self._host_stack.candidates(
                qklo, qkhi, qslo, qshi, qqid, pos_bits)
            if self._multihost:
                from .multihost import allgather_concat
                coded = allgather_concat(coded)
            host_cand_n = int(len(coded))
            if len(coded):
                parts.append(coded)
        if heat_enabled():
            # per-generation heat (obs/heat; process-local): device
            # runs attribute candidates exactly from the probe totals;
            # host candidates split proportionally to consumed slots
            touches = []
            if dev_gens:
                touches += [(g.gen_id, g.tier, int(g.n_slots),
                             g.per_shard_bytes()
                             * int(self.mesh.devices.size),
                             int(totals[:, i].sum()))
                            for i, g in enumerate(dev_gens)]
            n_host = sum(g.n_slots for g in host_gens)
            touches += [(g.gen_id, "host", int(g.n_slots),
                         (sum(int(a.nbytes) for p in g.spilled
                              for a in p) if g.spilled else 0),
                         int(round(host_cand_n * g.n_slots / n_host)))
                        for g in host_gens]
            record_index_scan(self, touches)
        if not parts:
            return np.empty(0, np.int64)
        merged = np.concatenate(parts)
        if n_windows > 1:
            return merged
        mask = (np.int64(1) << pos_bits) - 1
        return np.unique(merged & mask)

    # planner-facing surface (mirrors index/attr_lean.LeanAttrIndex) --
    secondary = True
    sec_z = None

    def _sec(self, sec_window):
        if sec_window is None:
            return None, None
        return sec_window

    def query_equals(self, value, sec_window=None,
                     z3_ranges=None) -> np.ndarray:
        k = encode_attr_value(value, self.attr_type)
        slo, shi = self._sec(sec_window)
        return self.query_ranges([(k, k, slo, shi, 0)])

    def query_in(self, values, sec_window=None,
                 z3_ranges=None) -> np.ndarray:
        if not len(values):
            return np.empty(0, np.int64)
        slo, shi = self._sec(sec_window)
        return self.query_ranges(
            [(encode_attr_value(v, self.attr_type),
              encode_attr_value(v, self.attr_type), slo, shi, 0)
             for v in values])

    def query_range(self, lo=None, hi=None, lo_inclusive=True,
                    hi_inclusive=True) -> np.ndarray:
        klo = (_I64_MIN if lo is None
               else encode_attr_value(lo, self.attr_type))
        khi = (_SENTINEL_KEY - 1 if hi is None
               else encode_attr_value(hi, self.attr_type))
        return self.query_ranges([(klo, khi, None, None, 0)])

    def query_prefix(self, prefix: str) -> np.ndarray:
        if self.attr_type != "string":
            raise TypeError("prefix queries require a string attribute")
        klo, khi = string_prefix_bounds(prefix)
        return self.query_ranges([(klo, khi, None, None, 0)])


class ShardedLeanXZ2Index(_XZ2Facade):
    """The lean XZ2 index over a mesh: the XZ2 sequence code rides the
    sharded (key, sec, gid) generational machinery verbatim (key =
    code, secondary unused) — non-point schemas at cluster scale
    (round-4 VERDICT #4; XZ2IndexKeySpace.scala:44).  The query/append
    surface is the shared XZ2Facade — one definition, no drift
    (review r5)."""

    def __init__(self, mesh: Mesh, g: int = 12, multihost: bool = False,
                 generation_slots: int | None = None,
                 hbm_budget_bytes: int | None = None,
                 compaction_factor: int | None = None):
        super().__init__(ShardedLeanAttrIndex(
            "__xz2__", "long", mesh=mesh, multihost=multihost,
            generation_slots=generation_slots,
            hbm_budget_bytes=hbm_budget_bytes,
            compaction_factor=compaction_factor), g=g)


class ShardedLeanXZ3Index(_LeanXZ3Facade):
    """The lean XZ3 tier over a mesh: (bin, code) keys on the sharded
    attribute core (XZ3IndexKeySpace.scala's ``[2B bin][8B code]`` at
    cluster scale)."""

    def __init__(self, period="week", mesh: Mesh = None, g: int = 12,
                 multihost: bool = False,
                 generation_slots: int | None = None,
                 hbm_budget_bytes: int | None = None,
                 compaction_factor: int | None = None):
        super().__init__(period=period, g=g,
                         core=ShardedLeanAttrIndex(
                             "__xz3__", "long", mesh=mesh,
                             multihost=multihost,
                             generation_slots=generation_slots,
                             hbm_budget_bytes=hbm_budget_bytes,
                             compaction_factor=compaction_factor))
