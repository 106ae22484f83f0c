"""LeanAttrIndex: tiered generational attribute index for lean schemas.

The round-4 lean profile served ``{z3, id}`` only, so an
attribute-only ECQL on a 1B-row store degraded to a full host scan and
an attribute-selective + spatially-wide query gathered every spatial
candidate first.  The reference serves these from the lexicoded
attribute index with cost-based selection at any scale
(geomesa-index-api/.../index/attribute/AttributeIndexKey.scala:38-52,
.../strategies/AttributeFilterStrategy.scala); this module is that
index re-expressed in the lean profile's terms (round-4 VERDICT #1).

**Key layout.**  Sorted GENERATIONS (LSM runs, exactly the
:class:`~geomesa_tpu.index.z3_lean.LeanZ3Index` shape) of

    ``(key int64, sec int64, gid int32)``  — 20 B/row

where ``key`` is an ORDER-PRESERVING int64 encoding of the attribute
value (the lexicode analog of ``AttributeIndexKey.typeRegistry``):

* ints/longs/dates — the value itself (exact);
* floats/doubles — the IEEE-754 order-preserving bit transform (exact);
* strings — the first 8 UTF-8 bytes big-endian (a PREFIX code: ties
  share a key and the planner's residual filter disambiguates — the
  same candidate-superset contract every index here honors).

``sec`` is the epoch-millis dtg — the reference's date secondary tier
(``DateIndexKeySpace``): because runs sort by ``(key, sec)``, an
equality/IN lookup with a time window seeks the sub-range directly
(two-key :func:`~geomesa_tpu.ops.search.searchsorted2` — the same
kernel the z3 index seeks with).  Range/prefix scans span many value
runs and pass an open ``sec`` window, as in the reference.

**Tiers.**  ``device`` generations hold the three columns in HBM
(demoted oldest-first under ``hbm_budget_bytes``); ``host`` generations
spill to RAM and seek through one stacked vectorized bisection, flat in
run count (the :class:`~geomesa_tpu.index.z3_lean.HostStack`
discipline).  There is no ``full`` tier: the encoded key IS the
payload, so the device seek is already as exact as the encoding allows.

Queries batch every (window × generation) into a fixed number of
dispatches: one totals probe + one gather over all device generations,
bucket-padded with a shared empty sentinel generation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..metrics import (RESILIENCE_DEGRADED, RESILIENCE_RETRIES,
                       WRITE_SEALS, WRITE_SPILLS)
from ..obs import device_span, obs_count, scan_work, span as obs_span
from ..obs.heat import (
    heat_enabled, merge_index_generations, record_index_scan,
)
from ..ops.search import (
    coded_pos_bits, expand_ranges, gather_capacity, pad_pow2,
    scan_read_bytes, searchsorted2, wire_dtype,
)

__all__ = ["LeanAttrIndex", "encode_attr_values", "encode_attr_value"]

_SENTINEL_KEY = np.int64(np.iinfo(np.int64).max)
_I64_MIN = np.int64(np.iinfo(np.int64).min)
_I64_MAX = np.int64(np.iinfo(np.int64).max)

#: per-slot bytes: key int64 + sec int64 + gid int32
SLOT_BYTES = 8 + 8 + 4
#: what one binary-search probe reads (key + sec) and what the gather
#: reads per candidate (gid): the ``lean.scan.bytes`` lower bound
_SEEK_KEY_BYTES = 8 + 8
_GID_BYTES = 4

#: generation-count compile bucket for the multi-generation programs
#: (the z3_lean._GEN_BUCKET discipline)
_GEN_BUCKET = 4

#: attribute types served by the int64 lexicode (AttributeIndexKey's
#: typeRegistry analog); geometry/bytes/json are not indexable here,
#: matching the reference's indexable-type set
_NUMERIC_TYPES = {"int", "integer", "long", "float", "double", "date"}


def _encode_float64(vals: np.ndarray) -> np.ndarray:
    """IEEE-754 double → order-preserving signed int64 (NaNs sort
    last)."""
    v = np.ascontiguousarray(vals, np.float64) + 0.0   # -0.0 → +0.0
    bits = v.view(np.int64)
    # negative floats (sign bit set): map reversed into [-2^63, -1];
    # positives keep their bits — order-preserving in the signed view
    return np.where(bits < 0, np.int64(-1) - (bits ^ _I64_MIN), bits)


def _encode_strings(vals: np.ndarray) -> np.ndarray:
    """First 8 UTF-8 bytes, big-endian, as signed int64 — a prefix code
    (lexicographic byte order == unsigned integer order; shifting by
    2^63 makes it signed-comparable).  ``None`` encodes as the EMPTY
    key on both paths: the fast ``astype('S8')`` path would stringify
    it to ``b'None'`` while the unicode fallback yields ``b''`` — the
    candidate set of an equality query must not depend on which path a
    batch happened to take."""
    arr = np.asarray(vals)
    if arr.dtype == object:
        none_mask = arr == np.array(None)
        if none_mask.any():
            arr = arr.copy()
            arr[none_mask] = ""
    try:
        raw = arr.astype("S8")           # ASCII fast path (truncating)
    except UnicodeEncodeError:
        raw = np.array([("" if v is None else str(v)).encode("utf-8")[:8]
                        for v in arr], dtype="S8")
    u = np.ascontiguousarray(raw).view(">u8").astype(np.uint64).ravel()
    return (u ^ np.uint64(1 << 63)).view(np.int64)


def encode_attr_values(vals: np.ndarray, attr_type: str) -> np.ndarray:
    """Vectorized order-preserving int64 encoding for one column.

    Keys clamp to ``int64 max - 1``: the sentinel padding key is int64
    max, and a real key equal to it would let open-ended range seeks
    sweep every generation's padding into the candidate buffer.  The
    clamp aliases only the two topmost encodable values — a candidate
    superset the residual filter resolves, like string prefix ties."""
    t = attr_type.lower()
    if t in ("int", "integer", "long", "date"):
        keys = np.ascontiguousarray(vals, np.int64)
    elif t in ("float", "double"):
        keys = _encode_float64(np.asarray(vals, np.float64))
    elif t == "string":
        keys = _encode_strings(vals)
    else:
        raise TypeError(f"attribute type {attr_type!r} is not indexable "
                        "on a lean schema (indexable: numerics, dates, "
                        "strings)")
    return np.minimum(keys, _SENTINEL_KEY - 1)


def encode_attr_value(v, attr_type: str) -> np.int64:
    """Scalar twin of :func:`encode_attr_values` (query planning)."""
    return np.int64(encode_attr_values(np.array([v]), attr_type)[0])


def string_prefix_bounds(prefix: str) -> tuple[np.int64, np.int64]:
    """Inclusive key bounds covering every string starting with
    ``prefix`` (for LIKE 'abc%': [code(prefix·00…), code(prefix·ff…)])."""
    b = prefix.encode("utf-8")[:8]
    lo = int.from_bytes(b.ljust(8, b"\x00"), "big")
    hi = int.from_bytes(b.ljust(8, b"\xff"), "big")
    u = np.array([lo, hi], dtype=np.uint64) ^ np.uint64(1 << 63)
    s = u.view(np.int64)
    return np.int64(s[0]), np.int64(min(s[1], _SENTINEL_KEY - 1))


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _attr_append(keys, sec, gid, r, new_k, new_s, new_g, m):
    """Merge one encoded slice into the generation's sentinel padding at
    sorted offset ``r`` and re-sort (donated: peak = resident + sort
    temps)."""
    valid = jnp.arange(new_k.shape[0]) < m
    k_new = jnp.where(valid, new_k, _SENTINEL_KEY)
    s_new = jnp.where(valid, new_s, jnp.int64(_I64_MAX))
    g_new = jnp.where(valid, new_g, jnp.int32(-1))
    keys = jax.lax.dynamic_update_slice(keys, k_new, (r,))
    sec = jax.lax.dynamic_update_slice(sec, s_new, (r,))
    gid = jax.lax.dynamic_update_slice(gid, g_new, (r,))
    return jax.lax.sort((keys, sec, gid), dimension=0, num_keys=2)


@jax.jit
def _attr_count_multi(qklo, qkhi, qslo, qshi, *cols):
    """Totals probe over every device generation in ONE dispatch."""
    outs = []
    for g in range(len(cols) // 2):
        k, s = cols[2 * g], cols[2 * g + 1]
        starts = searchsorted2(k, s, qklo, qslo, side="left")
        ends = searchsorted2(k, s, qkhi, qshi, side="right")
        outs.append(jnp.sum(jnp.maximum(ends - starts, 0)))
    return jnp.stack(outs)


@partial(jax.jit, static_argnames=("capacity", "pos_bits"))
def _attr_scan_coded(qklo, qkhi, qslo, qshi, qqid, *cols,
                     capacity: int, pos_bits: int):
    """Candidate gather over device generations in ONE dispatch,
    coded ``qid << pos_bits | gid``."""
    dt = wire_dtype(pos_bits)
    outs = []
    for g in range(len(cols) // 3):
        k, s, gid = cols[3 * g], cols[3 * g + 1], cols[3 * g + 2]
        starts = searchsorted2(k, s, qklo, qslo, side="left")
        ends = searchsorted2(k, s, qkhi, qshi, side="right")
        counts = jnp.maximum(ends - starts, 0)
        idx, valid, rid = expand_ranges(starts, counts, capacity)
        coded = ((qqid[rid].astype(dt) << dt(pos_bits))
                 | gid[idx].astype(dt))
        outs.append(jnp.where(valid, coded, dt(-1)))
    return jnp.stack(outs)


@partial(jax.jit, static_argnames=("out_cap",))
def _attr_merge(*cols, out_cap: int):
    """COMPACTION merge: fold K sorted (key, sec, gid) runs into ONE
    sorted run in a single dispatch — lax.sort over the concatenation
    floats every sentinel slot past the ``out_cap`` (= total valid)
    leading rows, so the merged run carries zero padding and releases
    the source runs' slack slots (the z3_lean._lean_merge_keys shape)."""
    k = len(cols) // 3
    keys = jnp.concatenate([cols[3 * i] for i in range(k)])
    sec = jnp.concatenate([cols[3 * i + 1] for i in range(k)])
    gid = jnp.concatenate([cols[3 * i + 2] for i in range(k)])
    keys, sec, gid = jax.lax.sort((keys, sec, gid), dimension=0,
                                  num_keys=2)
    return keys[:out_cap], sec[:out_cap], gid[:out_cap]


def merge_spilled_parts(parts: list[list]) -> list:
    """COMPACTION merge for spilled (key, sec, gid) runs: composite
    lexsort over the concatenation — the host twin of
    :func:`_attr_merge`.  Returns a fresh mutable part list (the
    _HostAttrStack re-pointing contract)."""
    k = np.concatenate([np.asarray(p[0]) for p in parts])
    s = np.concatenate([np.asarray(p[1]) for p in parts])
    g = np.concatenate([np.asarray(p[2]) for p in parts])
    order = np.lexsort((s, k))
    return [np.ascontiguousarray(k[order]),
            np.ascontiguousarray(s[order]),
            np.ascontiguousarray(g[order])]


@partial(jax.jit, static_argnames=("bins", "depth", "width", "is_float"))
def _attr_sketch_multi(slo, shi, hlo, hhi, *cols, bins: int, depth: int,
                       width: int, is_float: bool):
    """Stat-sketch fold over EVERY device generation in ONE dispatch
    (ISSUE 3): per run, the shared :func:`stats.sketch.device_fold_body`
    decodes the sorted keys and folds masked moments / histogram /
    count-min partials — only the tiny stacked partials cross the wire,
    never a key or candidate."""
    from ..stats.sketch import device_fold_body
    outs: list[list] = [[], [], [], [], [], [], []]
    for g in range(len(cols) // 2):
        res = device_fold_body(cols[2 * g], cols[2 * g + 1], slo, shi,
                               hlo, hhi, bins=bins, depth=depth,
                               width=width, is_float=is_float)
        for acc, r in zip(outs, res):
            acc.append(r)
    return tuple(jnp.stack(a) for a in outs)


def _bisect2(k: np.ndarray, s: np.ndarray, qk: np.ndarray,
             qs: np.ndarray, lo: np.ndarray, hi: np.ndarray,
             side: str) -> np.ndarray:
    """Vectorized composite-key binary search of ``(qk, qs)[i]`` within
    the (key, sec)-sorted segments ``[lo[i], hi[i])`` — the host-tier
    twin of :func:`~geomesa_tpu.ops.search.searchsorted2`, one bisection
    pass for every (range × run) pair (flat in run count)."""
    lo = lo.astype(np.int64).copy()
    hi = hi.astype(np.int64).copy()
    while True:
        active = lo < hi
        if not active.any():
            return lo
        mid = (lo + hi) >> 1
        safe = np.where(active, mid, 0)
        km, sm = k[safe], s[safe]
        if side == "left":
            below = (km < qk) | ((km == qk) & (sm < qs))
        else:
            below = (km < qk) | ((km == qk) & (sm <= qs))
        go = active & below
        lo = np.where(go, mid + 1, lo)
        hi = np.where(active & ~below, mid, hi)


class _HostAttrStack:
    """Spilled (key, sec, gid) runs stacked contiguously: each run is
    one segment; one composite bisection pass per query batch serves
    every host generation.  The stack OWNS the concatenated arrays —
    each constituent part (a mutable ``[k, s, g]`` list) is re-pointed
    at views into them so host RAM holds ONE copy of the spilled runs
    (the HostStack discipline; review r5)."""

    __slots__ = ("k", "s", "gid", "seg_lo", "seg_hi")

    def __init__(self, parts: list[list]):
        ks, ss, gs, lo, hi = [], [], [], [], []
        off = 0
        for k, s, g in parts:
            ks.append(k)
            ss.append(s)
            gs.append(g)
            lo.append(off)
            hi.append(off + len(k))
            off += len(k)
        self.k = np.concatenate(ks) if ks else np.empty(0, np.int64)
        self.s = np.concatenate(ss) if ss else np.empty(0, np.int64)
        self.gid = np.concatenate(gs) if gs else np.empty(0, np.int64)
        self.seg_lo = np.asarray(lo, np.int64)
        self.seg_hi = np.asarray(hi, np.int64)
        off = 0
        for part in parts:
            n = len(part[0])
            part[0] = self.k[off:off + n]
            part[1] = self.s[off:off + n]
            part[2] = self.gid[off:off + n]
            off += n

    def candidates(self, qklo, qkhi, qslo, qshi, qqid,
                   pos_bits: int) -> np.ndarray:
        if not len(self.k) or not len(qklo):
            return np.empty(0, np.int64)
        n_seg = len(self.seg_lo)
        n_q = len(qklo)
        # every (range × run) pair — runs are few (spilled generations)
        rid = np.repeat(np.arange(n_q), n_seg)
        seg = np.tile(np.arange(n_seg), n_q)
        lo0, hi0 = self.seg_lo[seg], self.seg_hi[seg]
        starts = _bisect2(self.k, self.s, qklo[rid], qslo[rid],
                          lo0, hi0, side="left")
        ends = _bisect2(self.k, self.s, qkhi[rid], qshi[rid],
                        lo0, hi0, side="right")
        cnt = np.maximum(ends - starts, 0)
        cum = np.cumsum(cnt)
        total = int(cum[-1]) if len(cum) else 0
        if total == 0:
            return np.empty(0, np.int64)
        j = np.arange(total)
        pid = np.searchsorted(cum, j, side="right")
        prev = np.where(pid > 0, cum[pid - 1], 0)
        idx = starts[pid] + (j - prev)
        return ((qqid[rid[pid]].astype(np.int64) << pos_bits)
                | self.gid[idx].astype(np.int64))


class _AttrGeneration:
    __slots__ = ("keys", "sec", "gid", "n", "tier", "spilled", "gen_id")

    @classmethod
    def merged_device(cls, keys, sec, gid, n: int) -> "_AttrGeneration":
        """A compacted device run from already-merged columns (length
        == n: zero sentinel padding)."""
        gen = cls.__new__(cls)
        gen.keys, gen.sec, gen.gid = keys, sec, gid
        gen.n = int(n)
        gen.tier = "device"
        gen.spilled = None
        gen.gen_id = -1
        return gen

    @classmethod
    def merged_host(cls, part: list) -> "_AttrGeneration":
        """A compacted host run from an already-merged spilled part."""
        gen = cls.__new__(cls)
        gen.keys = gen.sec = gen.gid = None
        gen.n = len(part[0])
        gen.tier = "host"
        gen.spilled = part
        gen.gen_id = -1
        return gen

    def __init__(self, capacity: int):
        self.keys = jnp.full((capacity,), _SENTINEL_KEY, jnp.int64)
        self.sec = jnp.full((capacity,), _I64_MAX, jnp.int64)
        self.gid = jnp.full((capacity,), -1, jnp.int32)
        self.n = 0
        self.tier = "device"
        self.spilled: tuple | None = None
        #: store-lifetime-unique run identity (assigned by the owning
        #: index; compaction mints fresh ids for merged runs — the
        #: sketch-partial cache invalidation key, like z3_lean)
        self.gen_id = -1

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])

    def device_bytes(self) -> int:
        return 0 if self.tier == "host" else self.capacity * SLOT_BYTES

    def spill_to_host(self) -> None:
        if self.tier != "device":
            return
        # a mutable list: _HostAttrStack re-points it at views of the
        # stacked buffers so only one host copy survives
        self.spilled = [np.asarray(self.keys)[:self.n],
                        np.asarray(self.sec)[:self.n],
                        np.asarray(self.gid)[:self.n]]
        self.keys = self.sec = self.gid = None
        self.tier = "host"


class LeanAttrIndex:
    """Tiered generational attribute index (see module doc).

    ``queries`` take lists of inclusive int64 key ranges with optional
    per-range sec windows; results are CANDIDATE gids (the planner's
    residual filter makes them exact, as for every index here)."""

    #: ``(schema, index_key)`` for access-temperature attribution
    #: (obs/heat) — stamped by the datastore / the owning XZ facade
    heat_scope: tuple | None = None

    @staticmethod
    def gather_payload(positions):
        """Result-materialization protocol hook (ISSUE 14, uniform
        across the lean index families): the attribute runs hold
        LEXICODED keys — not a row-addressable payload — so there is
        nothing to gather on device; ``None`` tells the Arrow result
        path to take every column from the host column store (one
        vectorized numpy take per column).  The schema's SCALE index
        (z3) still device-gathers x/y/t for attr-strategy queries."""
        return None

    GENERATION_SLOTS = 1 << 24
    DEFAULT_CAPACITY = 1 << 15
    BATCH_SCAN_BUDGET = 1 << 26
    #: default HBM budget — the store splits its lean budget between
    #: the z3 index and the attribute indexes (docs/scale.md)
    HBM_BUDGET_BYTES = int(2.0 * 2 ** 30)
    #: size-tiered compaction trigger (explicit compact() default; pass
    #: compaction_factor=F to run it opportunistically after appends) —
    #: the index/z3_lean.LeanZ3Index policy on the attribute runs
    COMPACTION_FACTOR = 4
    #: distinct sketch-fold specs whose per-sealed-run partials are
    #: retained (LRU; the density-cache policy on sketch partials —
    #: each partial is a handful of scalars + small hist/cms tables)
    SKETCH_CACHE_SPECS = 8
    #: host-RAM ceiling across all cached sketch specs
    SKETCH_CACHE_MAX_BYTES = 64 * 2 ** 20

    def __init__(self, attr: str, attr_type: str,
                 generation_slots: int | None = None,
                 hbm_budget_bytes: int | None = None,
                 compaction_factor: int | None = None):
        self.attr = attr
        self.attr_type = attr_type.lower()
        if self.attr_type not in _NUMERIC_TYPES | {"string"}:
            raise TypeError(
                f"attribute {attr!r}: type {attr_type!r} is not "
                "indexable on a lean schema")
        self.generation_slots = generation_slots or self.GENERATION_SLOTS
        self.hbm_budget_bytes = hbm_budget_bytes or self.HBM_BUDGET_BYTES
        self.generations: list[_AttrGeneration] = []
        self._host_stack: _HostAttrStack | None = None
        self._n_rows = 0
        self.dispatch_count = 0
        self._sentinel: tuple | None = None
        #: opportunistic compaction factor (0 = off)
        self.compaction_factor = int(compaction_factor or 0)
        self.compactions = 0
        #: sealed-run sketch partials: fold spec → {gen_id: RunSketch}
        #: (the z3_lean density-cache policy — index/partial_cache)
        from .partial_cache import PartialCache
        self._sketch_cache = PartialCache(self.SKETCH_CACHE_SPECS,
                                          self.SKETCH_CACHE_MAX_BYTES)
        #: generation-lifecycle hooks ``(kind, gen_ids)`` fired on
        #: seal/merge (index/lsm.notify_generation_event)
        self.generation_listeners: list = []
        #: store-lifetime run-id source (see _AttrGeneration.gen_id)
        self._gen_counter = 0

    def _next_gen_id(self) -> int:
        self._gen_counter += 1
        return self._gen_counter

    def _roll_generation(self) -> "_AttrGeneration":
        """Open a fresh live generation and rebalance (the append
        rollover body, factored so the seal span wraps it once)."""
        gen = _AttrGeneration(self.generation_slots)
        gen.gen_id = self._next_gen_id()
        self.generations.append(gen)
        self._rebalance()
        return self.generations[-1]

    def __len__(self) -> int:
        return self._n_rows

    def device_bytes(self) -> int:
        return sum(g.device_bytes() for g in self.generations)

    def host_key_bytes(self) -> int:
        """Host RAM held by spilled (``host``-tier) runs — key + sec +
        gid per valid row (no padding survives a spill)."""
        return sum(g.n * SLOT_BYTES for g in self.generations
                   if g.tier == "host")

    def sentinel_bytes(self) -> int:
        """HBM of the lazily-allocated padding sentinel columns."""
        return (0 if self._sentinel is None
                else self.generation_slots * SLOT_BYTES)

    def tier_counts(self) -> dict:
        out = {"device": 0, "host": 0}
        for g in self.generations:
            out[g.tier] += 1
        return out

    def storage_stats(self) -> dict:
        """Live byte accounting for the storage report (obs/resource,
        ISSUE 9) — see LeanZ3Index.storage_stats; same contract over
        the (key, sec, gid) runs."""
        gens = [{"gen_id": g.gen_id, "tier": g.tier, "rows": int(g.n),
                 "capacity": 0 if g.tier == "host" else g.capacity,
                 "device_bytes": g.device_bytes(),
                 "host_bytes": (g.n * SLOT_BYTES
                                if g.tier == "host" else 0)}
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
    def _sentinel_cols(self):
        if self._sentinel is None:
            slots = self.generation_slots
            self._sentinel = (
                jnp.full((slots,), _SENTINEL_KEY, jnp.int64),
                jnp.full((slots,), _I64_MAX, jnp.int64),
                jnp.full((slots,), -1, jnp.int32))
        return self._sentinel

    def _budget_after_sentinels(self) -> int:
        return (self.hbm_budget_bytes
                - self.generation_slots * SLOT_BYTES)

    def _rebalance(self) -> None:
        """Spill oldest-first until device residency (plus the sentinel
        padding buffer) fits the budget; the ACTIVE generation never
        spills (appends sort there)."""
        for gen in self.generations[:-1]:
            if self.device_bytes() <= self._budget_after_sentinels():
                return
            if gen.tier == "device":
                # blocking device→host transfer — traced with honest
                # block-until-ready ms (the write-span taxonomy)
                with device_span("write.spill", gen_id=gen.gen_id,
                                 rows=int(gen.n)):
                    obs_count(WRITE_SPILLS)
                    gen.spill_to_host()
                self._host_stack = None
        if self.device_bytes() > self._budget_after_sentinels():
            raise MemoryError(
                f"active attr generation ({self.generation_slots} "
                f"slots) exceeds hbm_budget_bytes="
                f"{self.hbm_budget_bytes}")

    def append(self, values, dtg_ms, base_gid: int | None = None
               ) -> "LeanAttrIndex":
        """Stream one column slice in: encode keys, merge into the
        current generation (rolling on full).  ``base_gid`` defaults to
        the running row count (the lean store's implicit ids)."""
        keys = encode_attr_values(values, self.attr_type)
        sec = np.ascontiguousarray(dtg_ms, np.int64)
        base = self._n_rows if base_gid is None else int(base_gid)
        if base + len(keys) > np.iinfo(np.int32).max:
            raise ValueError("LeanAttrIndex gids are int32: 2,147M rows "
                             "max per index/shard")
        m_total = len(keys)
        done = 0
        while done < m_total:
            gen = (self.generations[-1] if self.generations else None)
            if gen is None or gen.tier == "host" or gen.n >= gen.capacity:
                if gen is not None and gen.tier != "host":
                    # live run seals on rollover (write-span taxonomy)
                    sealed_id = gen.gen_id
                    with obs_span("write.seal", gen_id=gen.gen_id,
                                  tier=gen.tier, rows=int(gen.n)):
                        obs_count(WRITE_SEALS)
                        gen = self._roll_generation()
                    from .lsm import notify_generation_event
                    notify_generation_event(self, "seal", [sealed_id])
                else:
                    gen = self._roll_generation()
            room = gen.capacity - gen.n
            take = min(room, m_total - done)
            m_pad = min(gather_capacity(take, minimum=8), room)
            sl = slice(done, done + take)
            pad = m_pad - take
            gids = (base + done
                    + np.arange(take, dtype=np.int32)).astype(np.int32)
            self.dispatch_count += 1
            gen.keys, gen.sec, gen.gid = _attr_append(
                gen.keys, gen.sec, gen.gid, jnp.int32(gen.n),
                jnp.asarray(np.pad(keys[sl], (0, pad))),
                jnp.asarray(np.pad(sec[sl], (0, pad))),
                jnp.asarray(np.pad(gids, (0, pad))),
                jnp.int32(take))
            gen.n += take
            done += take
        self._n_rows += m_total
        if self.compaction_factor:
            # bounded opportunistic trigger: one merge group per append
            self.compact(factor=self.compaction_factor, max_groups=1)
        return self

    # -- compaction (LSM maintenance) -------------------------------------
    def _compaction_groups(self, factor: int) -> list[list]:
        from .lsm import plan_size_tiered
        return plan_size_tiered(self.generations[:-1],
                                ("device", "host"), lambda g: g.n,
                                factor)

    def _merge_group(self, group: list) -> None:
        from .lsm import merged_capacity, replace_group
        total = int(sum(g.n for g in group))
        if group[0].tier == "device":
            cols: list = []
            for g in group:
                cols += [g.keys, g.sec, g.gid]
            out_cap = merged_capacity(
                total, sum(g.capacity for g in group), gather_capacity)
            self.dispatch_count += 1
            keys, sec, gid = _attr_merge(*cols, out_cap=out_cap)
            merged = _AttrGeneration.merged_device(keys, sec, gid,
                                                   n=total)
        else:
            merged = _AttrGeneration.merged_host(
                merge_spilled_parts([g.spilled for g in group]))
            self._host_stack = None   # restacked lazily
        merged.gen_id = self._next_gen_id()
        # stale sketch partials must never double-count (the density
        # cache's compaction-mints-new-generation invalidation)
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
        _metrics.counter(LEAN_COMPACTION_ROWS).inc(total)
        from .lsm import notify_generation_event
        notify_generation_event(self, "merge", [merged.gen_id])

    def compact(self, budget_ms: float | None = None,
                factor: int | None = None,
                max_groups: int | None = None) -> dict:
        """Incremental size-tiered merge compaction over the attribute
        runs — merge one group, re-plan, stop past ``budget_ms`` or
        ``max_groups`` (≥ 1 group of progress per call; resumes on the
        next — index/lsm.py).  Candidate sets are identical at every
        intermediate state."""
        from .lsm import compact_incremental
        f = int(factor or self.compaction_factor
                or self.COMPACTION_FACTOR)
        merged = compact_incremental(
            lambda: self._compaction_groups(f), self._merge_group,
            budget_ms=budget_ms, max_groups=max_groups)
        if merged:
            self._rebalance()
        return {"merged_groups": merged,
                "generations": len(self.generations),
                "tiers": self.tier_counts()}

    # -- stat-sketch push-down (ISSUE 3) ----------------------------------
    def sketch_scan(self, fold) -> "RunSketch":
        """Fold every run's rows matching ``fold``'s sec window into ONE
        merged :class:`~geomesa_tpu.stats.sketch.RunSketch` — the
        StatsScan push-down re-expressed over the sorted key runs: the
        encoded key IS the value, so MinMax/Histogram/DescriptiveStats/
        Frequency (and Count) fold on DEVICE for device runs, host runs
        fold in one stacked numpy pass with per-run attribution, and no
        candidate row ever materializes.  Sealed runs' partials cache
        under ``fold`` (LRU + byte ceiling; compaction mints new
        gen_ids), so a warm repeat folds only the live run.

        ``want_values`` folds (TopK/Enumeration's exact value→count
        maps) are dict-valued and run host-side over the runs' key
        columns (device runs fetch once; the partial caches like any
        other)."""
        with obs_span("lean.sketch", attr=self.attr,
                      generations=len(self.generations)):
            return self._sketch_scan(fold)

    def _sketch_scan(self, fold) -> "RunSketch":
        from ..metrics import (
            LEAN_SKETCH_CACHE_HITS, LEAN_SKETCH_CACHE_MISSES,
        )
        from ..stats.sketch import RunSketch, fold_attr_runs
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
                _ht.append((g.gen_id, g.tier, int(g.n),
                            0 if part is not None
                            else int(g.n) * SLOT_BYTES, None))
        if _ht:
            record_index_scan(self, _ht)
        is_float = self.attr_type in ("float", "double")
        new_parts: dict[int, object] = {}
        if dev_scan and not fold.want_values:
            # every uncached device run in ONE dispatch (bucket-padded:
            # all-sentinel padding folds to an empty partial)
            padded = (list(dev_scan)
                      + [None] * ((-len(dev_scan)) % _GEN_BUCKET))
            cols: list = []
            for g in padded:
                c = (self._sentinel_cols() if g is None
                     else (g.keys, g.sec))
                cols += [c[0], c[1]]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="sketch",
                             runs=len(dev_scan)) as d:
                res = _attr_sketch_multi(
                    jnp.int64(fold.slo), jnp.int64(fold.shi),
                    jnp.float64(fold.hlo), jnp.float64(fold.hhi),
                    *cols, bins=int(fold.bins), depth=int(fold.depth),
                    width=int(fold.width), is_float=is_float)
                d.dispatched()
                cnt, kmin, kmax, vsum, vsumsq, hist, cms = [
                    np.asarray(a) for a in res]
            for i, g in enumerate(dev_scan):
                n = int(cnt[i])
                new_parts[id(g)] = RunSketch(
                    n, int(kmin[i]) if n else None,
                    int(kmax[i]) if n else None,
                    float(vsum[i]), float(vsumsq[i]),
                    np.array(hist[i]) if fold.bins else None,
                    np.array(cms[i]) if fold.depth else None)
        elif dev_scan:
            # exact value→count folds are dict-valued — host fold over
            # the fetched sorted key runs (valid rows sort to the front)
            runs = [(np.asarray(g.keys[:g.n]), np.asarray(g.sec[:g.n]))
                    for g in dev_scan]
            for g, p in zip(dev_scan,
                            fold_attr_runs(runs, fold, self.attr_type)):
                new_parts[id(g)] = p
        if host_scan:
            runs = [(g.spilled[0], g.spilled[1]) for g in host_scan]
            for g, p in zip(host_scan,
                            fold_attr_runs(runs, fold, self.attr_type)):
                new_parts[id(g)] = p
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
        """Candidate gids for inclusive composite ranges
        ``(klo, khi, slo, shi, qid)`` — equality narrows by sec, value
        ranges pass open sec bounds (module doc).  Returns coded
        ``qid << pos_bits | gid`` when ``n_windows > 1``, else plain
        sorted unique gids."""
        if not ranges or self._n_rows == 0:
            return np.empty(0, np.int64)
        n_pad = pad_pow2(len(ranges))
        qklo = np.full(n_pad, 1, np.int64)    # never-matching padding
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
        pos_bits = coded_pos_bits(
            total_rows if total_rows is not None else self._n_rows,
            max(1, n_windows))
        jklo, jkhi = jnp.asarray(qklo), jnp.asarray(qkhi)
        jslo, jshi = jnp.asarray(qslo), jnp.asarray(qshi)
        dev_gens = [g for g in self.generations if g.tier == "device"]
        host_gens = [g for g in self.generations if g.tier == "host"]
        parts: list = []
        if dev_gens:
            padded = list(dev_gens)
            n_b = (-len(padded)) % _GEN_BUCKET
            padded += [None] * n_b
            count_cols: list = []
            for gen in padded:
                cols = (self._sentinel_cols() if gen is None
                        else (gen.keys, gen.sec, gen.gid))
                count_cols += [cols[0], cols[1]]
            self.dispatch_count += 1
            with device_span("query.scan.device", stage="probe",
                             runs=len(dev_gens),
                             rows=int(sum(g.n for g in dev_gens))) as d:
                res = _attr_count_multi(jklo, jkhi, jslo, jshi,
                                        *count_cols)
                d.dispatched()
                totals = np.asarray(res)
            # adaptive-replan probe point (ISSUE 19): device totals are
            # known BEFORE any gather, so aborting here discards nothing
            from ..planning.adaptive import check_replan
            dev_total = int(totals.sum())
            check_replan("query.scan.probe", dev_total)
            if int(totals.sum()):
                capacity = gather_capacity(int(totals.max()),
                                           minimum=self.DEFAULT_CAPACITY)
                if len(padded) * capacity <= self.BATCH_SCAN_BUDGET:
                    groups = [padded]
                    caps = [capacity]
                else:
                    groups = [[g] for g, t in zip(dev_gens, totals)
                              if int(t)]
                    caps = [gather_capacity(int(t),
                                            minimum=self.DEFAULT_CAPACITY)
                            for t in totals if int(t)]
                from ..resilience import check_cancel, fault_point
                cand_of = {id(g): int(t) for g, t in zip(dev_gens, totals)}
                for group, cap in zip(groups, caps):
                    # deadline yield point between group dispatches
                    # (partial mode: unscanned groups' rows are simply
                    # absent — candidates are a subset either way)
                    if check_cancel("query.scan.device"):
                        break
                    try:
                        fault_point("device.dispatch")
                        cols = []
                        for gen in group:
                            cols += list(self._sentinel_cols()
                                         if gen is None
                                         else (gen.keys, gen.sec,
                                               gen.gid))
                        self.dispatch_count += 1
                        with device_span("query.scan.device",
                                         stage="gather",
                                         runs=len(group)) as d:
                            packed = _attr_scan_coded(
                                jklo, jkhi, jslo, jshi,
                                jnp.asarray(qqid),
                                *cols, capacity=cap, pos_bits=pos_bits)
                            d.dispatched()
                            # the blocking device->host read belongs to
                            # the dispatch; host-side filtering does not
                            flat = np.asarray(packed).ravel()
                    except Exception as e:  # noqa: BLE001
                        coded = self._dispatch_failed(
                            group, e, qklo, qkhi, qslo, qshi, qqid,
                            pos_bits)
                        if coded is None:
                            raise
                        if len(coded):
                            parts.append(coded)
                        continue
                    kept = flat[flat >= 0].astype(np.int64)
                    parts.append(kept)
                    # the key ranges are exact for the encoded key: the
                    # rows returned are the scan's hits (the planner's
                    # residual filter runs outside the index)
                    cand = sum(cand_of[id(g)] for g in group
                               if g is not None)
                    scan_work(d, cand, len(group) * cap, len(kept),
                              scan_read_bytes(
                                  cand, _GID_BYTES, n_pad,
                                  [self.generation_slots if g is None
                                   else int(g.keys.shape[0])
                                   for g in group], _SEEK_KEY_BYTES))
        host_cand_n = 0
        if host_gens:
            with obs_span("query.scan.host", runs=len(host_gens)):
                if self._host_stack is None:
                    self._host_stack = _HostAttrStack(
                        [g.spilled for g in host_gens])
                coded = self._host_stack.candidates(
                    qklo, qkhi, qslo, qshi, qqid, pos_bits)
                host_cand_n = int(len(coded))
                if len(coded):
                    parts.append(coded)
        if host_cand_n:
            from ..planning.adaptive import check_replan
            check_replan("query.scan.probe",
                         (dev_total if dev_gens else 0) + host_cand_n)
        if heat_enabled():
            # heat touches: device runs attribute candidates exactly
            # from the probe totals; host candidates split
            # proportionally to run size (obs/heat module doc)
            touches = [(g.gen_id, g.tier, int(g.n),
                        int(g.n) * SLOT_BYTES,
                        int(totals[i]) if len(totals) else 0)
                       for i, g in enumerate(dev_gens)]
            n_host = sum(g.n for g in host_gens)
            touches += [(g.gen_id, "host", int(g.n),
                         int(g.n) * SLOT_BYTES,
                         int(round(host_cand_n * g.n / n_host)))
                        for g in host_gens]
            record_index_scan(self, touches)
        if not parts:
            return np.empty(0, np.int64)
        merged = np.concatenate(parts)
        if n_windows > 1:
            return merged
        mask = (np.int64(1) << pos_bits) - 1
        return np.unique(merged & mask)

    def _dispatch_failed(self, group, exc, qklo, qkhi, qslo, qshi, qqid,
                         pos_bits):
        """Degraded execution at the dispatch boundary (ISSUE 16):
        transient (memory-pressure) failures spill the failed group to
        host and answer via host-seek candidates — the planner's
        residual filter restores exactness; poison propagates (returns
        None).  Mirrors z3_lean's contract."""
        from ..resilience import (breaker, classify_device_failure,
                                  retry_budget)
        if classify_device_failure(exc) != "transient":
            return None
        gens = [g for g in group if g is not None]
        for g in gens:
            breaker.record_failure((id(self), g.gen_id))
        if retry_budget() <= 0:
            return None
        with obs_span("query.scan.degraded", tier="attr",
                      reason="transient", runs=len(gens)) as sp:
            sp.set_attr("resilience.degraded", True)
            obs_count(RESILIENCE_DEGRADED, len(gens))
            obs_count(RESILIENCE_RETRIES)
            for g in gens:
                if g.tier == "device":
                    with device_span("write.spill", gen_id=g.gen_id,
                                     rows=int(g.n)):
                        obs_count(WRITE_SPILLS)
                        g.spill_to_host()
            self._host_stack = None
            stack = _HostAttrStack([g.spilled for g in gens])
            return stack.candidates(qklo, qkhi, qslo, qshi, qqid,
                                    pos_bits)

    # planner-facing surface (mirrors index/attribute.AttributeIndex) --
    #: date-tier marker: equality/IN narrow by a dtg window
    secondary = True
    #: no z3 secondary on the lean attribute index (date tier only)
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
        ranges = []
        for v in values:
            k = encode_attr_value(v, self.attr_type)
            ranges.append((k, k, slo, shi, 0))
        return self.query_ranges(ranges)

    def query_range(self, lo=None, hi=None, lo_inclusive=True,
                    hi_inclusive=True) -> np.ndarray:
        """Candidate gids for a value range.  Bounds are conservatively
        INCLUSIVE at the key level (string prefix codes alias; numeric
        exclusive endpoints survive as candidates) — the residual filter
        applies the exact operator."""
        klo = (_I64_MIN if lo is None
               else encode_attr_value(lo, self.attr_type))
        # open hi stops just short of the sentinel key (encoded keys
        # clamp below it, so no real row is missed)
        khi = (_SENTINEL_KEY - 1 if hi is None
               else encode_attr_value(hi, self.attr_type))
        return self.query_ranges([(klo, khi, None, None, 0)])

    def query_prefix(self, prefix: str) -> np.ndarray:
        if self.attr_type != "string":
            raise TypeError("prefix queries require a string attribute")
        klo, khi = string_prefix_bounds(prefix)
        return self.query_ranges([(klo, khi, None, None, 0)])
