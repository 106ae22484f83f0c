"""ShardedAttributeIndex: attribute equality/range/prefix scans on a mesh.

The reference serves attribute queries through the same distributed scan
as the spatial indexes (lexicoded value keys + tablet seeks,
.../index/attribute/AttributeIndexKey.scala:38).  Lexicoding is replaced
by **rank encoding**: the host keeps the sorted unique values (the
dictionary) and each row carries its value's rank as an int64 device key —
numpy sort order equals lexicoder order for numerics and strings, so rank
order IS key order.  Per-shard state: sorted ``(rank, secondary)`` key
columns + the gid payload; queries map value predicates to rank ranges on
the host and run one collective seek+gather scan.

**Tiers** mirror the single-chip index
(:class:`geomesa_tpu.index.attribute.AttributeIndex`):

* **date tier** — rows sort by ``(rank, dtg)``; equality lookups refine
  by a time window inside the value run via the lexicographic 2-key
  seek.
* **z3 tier** — rows sort by ``((rank << 16) | time_bin, z)``: the rank
  and the Z3 time bin FUSE into the first key (bins are small ints), so
  the same 2-key collective scan serves per-``(value, bin)`` z-range
  seeks — the tiered-range assembly of
  GeoMesaFeatureIndex.getQueryStrategy (:248-338) with no third sort
  key needed.  Restores single-chip candidate-set parity on the mesh
  (round-3 next #6).

As in the reference, tiers apply only to point lookups (equality / IN);
range and prefix scans span many value runs and rely on the planner's
residual filter.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops.search import (
    expand_ranges, gather_capacity, pad_pow2, pad_ranges, searchsorted2,
)
from .mesh import device_mesh, shard_batch
from .scan import _fetch_global

__all__ = ["ShardedAttributeIndex"]

_SENTINEL_RANK = np.int64(np.iinfo(np.int64).max)
_SEC_LO = np.int64(np.iinfo(np.int64).min)
_SEC_HI = np.int64(np.iinfo(np.int64).max)


@lru_cache(maxsize=32)
def _attr_build_program(mesh: Mesh):
    @partial(shard_map, mesh=mesh,
             in_specs=(P("shard"),) * 4, out_specs=(P("shard"),) * 3)
    def sort(rk, sec, gs, vs):
        rk = jnp.where(vs, rk, _SENTINEL_RANK)
        gs = jnp.where(vs, gs, gs.dtype.type(-1))
        return jax.lax.sort((rk, sec, gs), dimension=0, num_keys=2)

    return jax.jit(sort)


@lru_cache(maxsize=64)
def _attr_scan_program(mesh: Mesh, capacity: int):
    """Collective seek+gather over the sorted (rank, secondary) columns.
    Ranges are lexicographic [(rank_lo, sec_lo), (rank_hi, sec_hi)]
    pairs; hits are exact at index-key granularity (the planner's
    residual filter guarantees final exactness, as everywhere)."""

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"),) * 3 + (P(None),) * 4,
        out_specs=(P("shard"), P("shard")),
    )
    def scan(lr, ls, lg, rlo_r, rlo_s, rhi_r, rhi_s):
        starts = searchsorted2(lr, ls, rlo_r, rlo_s, side="left")
        ends = searchsorted2(lr, ls, rhi_r, rhi_s, side="right")
        counts = jnp.maximum(ends - starts, 0)
        total = jnp.sum(counts)
        idx, valid_slot, _ = expand_ranges(starts, counts, capacity)
        gc = lg[idx]
        mask = valid_slot & (gc >= 0)
        packed = jnp.where(mask, gc, gc.dtype.type(-1))
        return packed, total[None].astype(jnp.int64)

    return jax.jit(scan)


#: bits of the first sort key reserved for the Z3 time bin (z3 tier:
#: key1 = rank << _BIN_BITS | bin); week bins stay far below 2^16
_BIN_BITS = 16


def _tier_keys(ranks: np.ndarray, secondary, sec_bins, sec_z, n: int):
    """(key1, key2, tier) for the build: z3 tier fuses rank+bin into
    key1 with z as key2; date tier is (rank, dtg); untired (rank, 0)."""
    if sec_z is not None:
        bins = np.asarray(sec_bins, dtype=np.int64)
        if bins.size and (bins.min() < 0 or bins.max() >= 1 << _BIN_BITS):
            raise ValueError("time bin exceeds the fused-key budget")
        return ((ranks << _BIN_BITS) | bins,
                np.asarray(sec_z, dtype=np.int64), "z3")
    if secondary is not None:
        return ranks, np.asarray(secondary, dtype=np.int64), "date"
    return ranks, np.zeros(n, dtype=np.int64), "none"


class ShardedAttributeIndex:
    """Rank-encoded attribute index sharded over a device mesh."""

    DEFAULT_CAPACITY = 1 << 14

    def __init__(self, mesh: Mesh, attr: str, uniques: np.ndarray,
                 ranks, sec, gid, n_total: int, tier: str = "none",
                 multihost: bool = False):
        self.mesh = mesh
        self.attr = attr
        self.uniques = uniques      # host dictionary, sorted
        self.ranks = ranks          # sharded sorted int64 key1
        self.sec = sec              # sharded int64 key2 (dtg / z / 0)
        self.gid = gid
        self._n_total = n_total
        self.tier = tier
        self._multihost = multihost
        self._capacity = self.DEFAULT_CAPACITY
        #: the single-chip AttributeIndex attributes the planner probes
        self.has_secondary = tier == "date"
        self.secondary = sec if tier == "date" else None
        self.sec_z = True if tier == "z3" else None

    @classmethod
    def build(cls, attr: str, column: np.ndarray, secondary=None,
              mesh: Mesh | None = None, sec_bins=None,
              sec_z=None) -> "ShardedAttributeIndex":
        """``secondary`` (dtg) selects the date tier; ``sec_bins`` +
        ``sec_z`` (host-computed Z3 key parts) select the z3 tier."""
        mesh = mesh or device_mesh()
        col = np.asarray(column)
        if col.dtype == object:
            col = col.astype(str)
        uniques, inv = np.unique(col, return_inverse=True)
        ranks = inv.astype(np.int64)
        n = len(col)
        k1, k2, tier = _tier_keys(ranks, secondary, sec_bins, sec_z, n)
        gids = np.arange(n, dtype=np.int32)
        sharded, valid = shard_batch(mesh, k1, k2, gids)
        rk_s, sec_s, gid_s = _attr_build_program(mesh)(*sharded, valid)
        return cls(mesh, attr, uniques, rk_s, sec_s, gid_s, n, tier=tier)

    @classmethod
    def build_multihost(cls, attr: str, column: np.ndarray, secondary=None,
                        mesh: Mesh | None = None, sec_bins=None,
                        sec_z=None) -> "ShardedAttributeIndex":
        """Multi-controller build from per-process LOCAL columns.

        The rank dictionary must be GLOBAL (the same value must map to
        the same rank everywhere), so local unique values allgather and
        re-unique — bounded by value cardinality, never row count; rows
        themselves feed only locally (process_local_shard), gids code
        ``process << GID_PROC_SHIFT | local_row``."""
        from .multihost import (
            agreed_int, allgather_concat, allgather_strings,
            global_device_mesh, process_local_shard,
        )
        from .scan import encode_gids
        mesh = mesh or global_device_mesh()
        col = np.asarray(column)
        if col.dtype == object:
            col = col.astype(str)
        local_uniques = np.unique(col)
        gathered = (allgather_strings(local_uniques)
                    if local_uniques.dtype.kind in ("U", "S")
                    else allgather_concat(local_uniques))
        uniques = np.unique(gathered)
        ranks = np.searchsorted(uniques, col).astype(np.int64)
        n_local = len(col)
        k1, k2, tier = _tier_keys(ranks, secondary, sec_bins, sec_z,
                                  n_local)
        gids = encode_gids(np.arange(n_local, dtype=np.int64))
        sharded, valid = process_local_shard(mesh, k1, k2, gids)
        rk_s, sec_s, gid_s = _attr_build_program(mesh)(*sharded, valid)
        return cls(mesh, attr, uniques, rk_s, sec_s, gid_s,
                   agreed_int(n_local, "sum"), tier=tier, multihost=True)

    def __len__(self) -> int:
        return self._n_total

    def _cast(self, v):
        if self.uniques.dtype.kind in ("U", "S"):
            return str(v)
        return v

    def _scan(self, ranges: list[tuple[int, int, int, int]]) -> np.ndarray:
        """Run lexicographic (rank, sec) ranges as one collective scan."""
        if not ranges or self._n_total == 0:
            return np.empty(0, dtype=np.int64)
        arr = np.asarray(ranges, dtype=np.int64)
        r = pad_ranges({"rzlo": arr[:, 0], "rtlo": arr[:, 1],
                        "rzhi": arr[:, 2], "rthi": arr[:, 3]},
                       pad_pow2(len(arr)))
        # padding must be non-matching in LEX order: (1,0) > (0,0) works
        # because pad_ranges fills rzlo=1 > rzhi=0 with equal sec fills
        capacity = self._capacity
        while True:
            scan = _attr_scan_program(self.mesh, capacity)
            packed, totals = scan(
                self.ranks, self.sec, self.gid,
                jnp.asarray(r["rzlo"]), jnp.asarray(r["rtlo"]),
                jnp.asarray(r["rzhi"]), jnp.asarray(r["rthi"]))
            totals = _fetch_global(totals)
            if int(totals.max(initial=0)) <= capacity:
                self._capacity = capacity
                flat = _fetch_global(packed).ravel()
                return np.unique(flat[flat >= 0]).astype(np.int64)
            capacity = gather_capacity(int(totals.max()))

    def _sec_bounds(self, sec_window) -> tuple[int, int]:
        if sec_window is None or not self.has_secondary:
            return int(_SEC_LO), int(_SEC_HI)
        lo, hi = sec_window
        return (int(_SEC_LO) if lo is None else int(lo),
                int(_SEC_HI) if hi is None else int(hi))

    def _k1(self, rank: int, bin_: int | None = None,
            hi: bool = False) -> int:
        """First sort key for a rank: plain rank for date/untired; the
        fused ``rank << 16 | bin`` for the z3 tier (bin None spans every
        bin of the rank's run — lo/hi chosen by ``hi``)."""
        if self.tier != "z3":
            return int(rank)
        if bin_ is not None:
            return (int(rank) << _BIN_BITS) | int(bin_)
        return ((int(rank) << _BIN_BITS)
                | ((1 << _BIN_BITS) - 1 if hi else 0))

    def _value_ranges(self, rank: int, s_lo: int, s_hi: int,
                      z3_ranges) -> list[tuple[int, int, int, int]]:
        """Lex ranges for one value's run: z3-tiered point lookups seek
        per-(bin, z-range) sub-runs (tiered-range assembly,
        GeoMesaFeatureIndex.scala:248-338); otherwise one run-wide range
        refined by the date window."""
        if self.tier == "z3" and z3_ranges is not None:
            rbin, rzlo, rzhi = z3_ranges
            return [(self._k1(rank, int(b)), int(zl),
                     self._k1(rank, int(b)), int(zh))
                    for b, zl, zh in zip(rbin, rzlo, rzhi)]
        return [(self._k1(rank), s_lo, self._k1(rank, hi=True), s_hi)]

    def query_equals(self, value, sec_window=None,
                     z3_ranges=None) -> np.ndarray:
        """Gids where attr == value, tier-refined: by a dtg window (date
        tier) or a covering ``(rbin, rzlo, rzhi)`` plan (z3 tier)."""
        value = self._cast(value)
        i = np.searchsorted(self.uniques, value)
        if i >= len(self.uniques) or self.uniques[i] != value:
            return np.empty(0, dtype=np.int64)
        s_lo, s_hi = self._sec_bounds(sec_window)
        return self._scan(self._value_ranges(int(i), s_lo, s_hi,
                                             z3_ranges))

    def query_in(self, values, sec_window=None,
                 z3_ranges=None) -> np.ndarray:
        """Gids where attr IN values — all values in ONE collective scan."""
        s_lo, s_hi = self._sec_bounds(sec_window)
        ranges = []
        for v in values:
            v = self._cast(v)
            i = np.searchsorted(self.uniques, v)
            if i < len(self.uniques) and self.uniques[i] == v:
                ranges.extend(self._value_ranges(int(i), s_lo, s_hi,
                                                 z3_ranges))
        return self._scan(ranges)

    def query_range(self, lo=None, hi=None, lo_inclusive=True,
                    hi_inclusive=True) -> np.ndarray:
        i0 = 0
        i1 = len(self.uniques) - 1
        if lo is not None:
            i0 = int(np.searchsorted(
                self.uniques, self._cast(lo),
                side="left" if lo_inclusive else "right"))
        if hi is not None:
            i1 = int(np.searchsorted(
                self.uniques, self._cast(hi),
                side="right" if hi_inclusive else "left")) - 1
        if i1 < i0:
            return np.empty(0, dtype=np.int64)
        return self._scan([(self._k1(i0), int(_SEC_LO),
                            self._k1(i1, hi=True), int(_SEC_HI))])

    def query_prefix(self, prefix: str) -> np.ndarray:
        """String prefix scan — serves LIKE 'abc%'."""
        if self.uniques.dtype.kind not in ("U", "S"):
            raise TypeError("prefix queries require a string attribute")
        i0 = int(np.searchsorted(self.uniques, prefix, side="left"))
        i1 = int(np.searchsorted(self.uniques, prefix + "￿",
                                 side="right")) - 1
        if i1 < i0:
            return np.empty(0, dtype=np.int64)
        return self._scan([(self._k1(i0), int(_SEC_LO),
                            self._k1(i1, hi=True), int(_SEC_HI))])
