"""Tiered stat-sketch push-down with sealed-generation sketch caching
(ISSUE 3).

Covers the acceptance surface: sketch merge algebra property tests
(``observe(a)+observe(b) == observe(a‖b)`` per stat type vs a numpy
oracle, associativity/commutativity, Frequency/TopK bounded-error
contracts under merge), the ``Count();MinMax;Histogram`` bbox+time
push-down on a multi-generation lean store returning oracle-identical
results with ZERO host candidate materialization (asserted via the
``lean.sketch.materialized_fallbacks`` counter), the ≥5x warm repeat
via the sealed-generation sketch cache on a ≥20-run store,
compaction-mints-new-generation cache invalidation, the per-tier
fallback contract (strings / selective bbox / GroupBy), Z3Histogram
cell push-down, and the sharded variants.

Named ``test_zz_*`` deliberately: this is a heavyweight lifecycle
suite (multi-generation store builds, device folds), so it runs at the
END of the alphabetical tier-1 order, after the fast unit suites (the
test_zz_lean_compaction convention).
"""

import numpy as np
import pytest

from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.metrics import (
    LEAN_SKETCH_CACHE_HITS, LEAN_SKETCH_CACHE_MISSES, LEAN_SKETCH_SCANS,
    LEAN_STATS_MATERIALIZED, registry,
)
from geomesa_tpu.stats.sketch import (
    RunSketch, SketchFold, decode_attr_key, fold_attr_runs,
)
from geomesa_tpu.stats.stat import (
    CountStat, DescriptiveStats, EnumerationStat, Frequency, Histogram,
    MinMax, TopK, Z3HistogramStat, parse_stat,
)

MS = 1514764800000
DAY = 86_400_000
WORLD = "BBOX(geom,-180,-90,180,90)"
DURING = ("dtg DURING 2018-01-03T00:00:00Z/2018-01-10T00:00:00Z")
T_LO, T_HI = MS + 2 * DAY, MS + 9 * DAY


def _counter(name):
    return registry.counter(name).count


# -- sketch merge algebra: observe(a)+observe(b) == observe(a‖b) --------

class _DictBatch(dict):
    """Column dict with row semantics (len = rows, like FeatureBatch)."""

    def __len__(self):
        return len(next(iter(self.values())))


def _split_cases():
    rng = np.random.default_rng(17)
    n = 5_000
    cols = {
        "f": rng.normal(10.0, 4.0, n),
        "i": rng.integers(-50, 50, n).astype(np.int64),
        "s": rng.choice(np.array(["a", "b", "c", "dd"], object), n),
    }
    cut = n // 3
    return cols, cut


STAT_FACTORIES = [
    lambda: CountStat(),
    lambda: MinMax("f"),
    lambda: MinMax("i"),
    lambda: Histogram("f", 24, -5.0, 25.0),
    lambda: Frequency("i", 4, 128),
    lambda: Frequency("s", 4, 128),
    lambda: TopK("s", 3),
    lambda: EnumerationStat("i"),
    lambda: DescriptiveStats("f"),
]


@pytest.mark.parametrize("factory", STAT_FACTORIES,
                         ids=lambda f: type(f()).__name__ + "_" +
                         (getattr(f(), "attr", "") or "n"))
def test_observe_split_equals_observe_whole(factory):
    cols, cut = _split_cases()
    whole = factory()
    whole.observe(_DictBatch(cols))
    a, b = factory(), factory()
    a.observe(_DictBatch({k: v[:cut] for k, v in cols.items()}))
    b.observe(_DictBatch({k: v[cut:] for k, v in cols.items()}))
    merged = a + b
    if isinstance(whole, DescriptiveStats):
        assert merged.n == whole.n
        assert np.isclose(merged.mean, whole.mean)
        assert np.isclose(merged.variance, whole.variance)
        assert merged.min == whole.min and merged.max == whole.max
    elif isinstance(whole, TopK):
        # space-saving contract under merge: capacity bounded, and
        # reported counts never UNDER-estimate the true counts of the
        # values they report (bounded-error, not exact)
        assert len(merged.counters) <= merged._capacity
        u, c = np.unique(cols["s"].astype(str), return_counts=True)
        true = dict(zip(u.tolist(), c.tolist()))
        for v, cnt in merged.counters.items():
            assert cnt >= true.get(v, 0)
        # the true top-1 value must survive the merge at its true rank
        top1 = max(true, key=true.get)
        assert merged.topk(1)[0][0] == top1
    else:
        assert merged.to_json() == whole.to_json()


def test_merge_associative_commutative():
    cols, _ = _split_cases()
    thirds = np.array_split(np.arange(len(cols["f"])), 3)
    for factory in STAT_FACTORIES:
        parts = []
        for idx in thirds:
            s = factory()
            s.observe(_DictBatch({k: v[idx] for k, v in cols.items()}))
            parts.append(s)
        a, b, c = parts
        if isinstance(a, DescriptiveStats):
            # Welford merges associate/commute up to fp rounding
            x1, x2 = (a + b) + c, a + (b + c)
            y1, y2 = a + b, b + a
            for u, v in ((x1, x2), (y1, y2)):
                assert u.n == v.n
                assert np.isclose(u.mean, v.mean)
                assert np.isclose(u.m2, v.m2)
            continue
        assert ((a + b) + c).to_json() == (a + (b + c)).to_json()
        if not isinstance(a, TopK):   # space-saving eviction is
            assert (a + b).to_json() == (b + a).to_json()  # order-dep

    # Frequency bounded-error contract under merge: the count-min
    # estimate never under-counts, over-counts by at most the total
    f_parts = []
    for idx in thirds:
        f = Frequency("i", 4, 64)
        f.observe({"i": cols["i"][idx]})
        f_parts.append(f)
    merged = f_parts[0] + f_parts[1] + f_parts[2]
    u, c = np.unique(cols["i"], return_counts=True)
    for v, cnt in zip(u.tolist(), c.tolist()):
        est = merged.count(int(v))
        assert cnt <= est <= len(cols["i"])


def test_run_sketch_monoid_and_fold_split():
    rng = np.random.default_rng(3)
    k = np.sort(rng.integers(0, 1000, 900))
    s = rng.integers(0, 100, 900)
    fold = SketchFold(slo=10, shi=80, bins=8, hlo=0.0, hhi=1000.0,
                      depth=2, width=32, want_values=True)
    whole = fold_attr_runs([(k, s)], fold, "long")[0]
    a, b = fold_attr_runs([(k[:400], s[:400]), (k[400:], s[400:])],
                          fold, "long")
    merged = a + b
    assert merged.to_json() == whole.to_json()
    # associativity + identity
    c = fold_attr_runs([(k[:100], s[:100])], fold, "long")[0]
    assert ((a + b) + c).to_json() == (a + (b + c)).to_json()
    assert (RunSketch() + whole).to_json() == whole.to_json()
    # the fold matches the numpy oracle
    m = (s >= 10) & (s <= 80)
    assert whole.count == int(m.sum())
    assert decode_attr_key(whole.kmin, "long") == int(k[m].min())
    u, cnt = np.unique(k[m], return_counts=True)
    assert whole.values == dict(zip(u.tolist(), cnt.tolist()))


# -- the acceptance push-down on a multi-generation lean store ----------

#: enough sealed runs that the cold fold's work dwarfs per-call
#: overhead — the 5x warm assertion must not ride a ~15ms measurement
#: (cold folds N_RUNS runs; warm folds one 4-run padded bucket)
N_RUNS = 40
SLOTS = 1 << 12


@pytest.fixture(scope="module")
def lean_store():
    rng = np.random.default_rng(11)
    n = N_RUNS * SLOTS
    ds = TpuDataStore()
    ds.create_schema(
        "evt", "name:String:index=true,score:Double:index=true,"
               "k:Int:index=true,dtg:Date,*geom:Point;"
               "geomesa.index.profile=lean,"
               f"geomesa.lean.generation.slots={SLOTS},"
               "geomesa.lean.compaction.factor=0")
    data = {
        "x": rng.uniform(-75, -73, n), "y": rng.uniform(40, 42, n),
        "t": rng.integers(MS, MS + 14 * DAY, n),
        "score": rng.normal(50.0, 20.0, n),
        "k": rng.integers(0, 40, n),
        "name": rng.choice(np.array(["a", "b", "c"], object), n),
    }
    for lo in range(0, n, SLOTS):
        sl = slice(lo, lo + SLOTS)
        ds.write("evt", {"name": data["name"][sl],
                         "score": data["score"][sl],
                         "k": data["k"][sl], "dtg": data["t"][sl],
                         "geom": (data["x"][sl], data["y"][sl])})
    return ds, data


def test_pushdown_oracle_exact_zero_materialization(lean_store):
    ds, d = lean_store
    st = ds._store("evt")
    assert len(st._lean_attr_index("score").generations) >= 20
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    s0 = _counter(LEAN_SKETCH_SCANS)
    got = ds.stats("evt", f"{WORLD} AND {DURING}",
                   "Count();MinMax(score);Histogram(score,20,0,100)")
    m = (d["t"] >= T_LO) & (d["t"] <= T_HI)
    col = d["score"][m]
    assert got.stats[0].count == int(m.sum())
    assert got.stats[1].min == col.min()
    assert got.stats[1].max == col.max()
    oracle = Histogram("score", 20, 0.0, 100.0)
    oracle.observe({"score": col})
    np.testing.assert_array_equal(got.stats[2].counts, oracle.counts)
    # ZERO host candidate materialization (the acceptance counter)
    assert _counter(LEAN_STATS_MATERIALIZED) == m0
    assert _counter(LEAN_SKETCH_SCANS) == s0 + 1


def test_pushdown_more_stat_kinds_oracle_exact(lean_store):
    ds, d = lean_store
    m = (d["t"] >= T_LO) & (d["t"] <= T_HI)
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats(
        "evt", f"{WORLD} AND {DURING}",
        "DescriptiveStats(score);Frequency(k,4,256);"
        "Enumeration(k);TopK(k)")
    desc, freq, enum, topk = got.stats
    col = d["score"][m]
    assert desc.n == int(m.sum())
    assert np.isclose(desc.mean, col.mean())
    assert np.isclose(desc.stddev, col.std(ddof=1))
    oracle_f = Frequency("k", 4, 256)
    oracle_f.observe({"k": d["k"][m]})
    np.testing.assert_array_equal(freq.table, oracle_f.table)
    u, c = np.unique(d["k"][m], return_counts=True)
    true = dict(zip(u.tolist(), c.tolist()))
    assert enum.counts == true
    for v, cnt in topk.topk():
        assert true[v] == cnt
    assert _counter(LEAN_STATS_MATERIALIZED) == m0


def test_warm_repeat_5x_via_sealed_generation_cache(lean_store):
    """The cold fold runs every device run; the warm repeat serves each
    sealed run from the sketch cache and folds only the live run on the
    device.  Asserted on the cache counters and the sketch dispatch's
    ``runs``, not on the clock."""
    from geomesa_tpu import obs
    ds, _ = lean_store
    st = ds._store("evt")
    idx = st._lean_attr_index("score")
    assert len(idx.generations) >= 20
    assert all(g.tier == "device" for g in idx.generations)
    n_sealed = len(idx.generations) - 1
    spec = "Count();MinMax(score);Histogram(score,20,0,100)"
    q = f"{WORLD} AND {DURING}"

    def fold(runs):
        h0 = _counter(LEAN_SKETCH_CACHE_HITS)
        m0 = _counter(LEAN_SKETCH_CACHE_MISSES)
        with obs.tracer.capture() as cap:
            got = ds.stats("evt", q, spec)
        folded = [s.attributes["runs"] for t in cap.traces()
                  for s in t.spans if s.name == "query.scan.device"
                  and s.attributes.get("stage") == "sketch"]
        assert folded == [runs]
        return (got, _counter(LEAN_SKETCH_CACHE_HITS) - h0,
                _counter(LEAN_SKETCH_CACHE_MISSES) - m0)

    idx._sketch_cache.clear()
    cold, hits, misses = fold(len(idx.generations))
    assert (hits, misses) == (0, n_sealed)
    warm, hits, misses = fold(1)
    assert (hits, misses) == (n_sealed, 0)
    assert cold.to_json() == warm.to_json()


def test_fallbacks_materialize_and_are_counted(lean_store):
    ds, d = lean_store
    q = f"{WORLD} AND {DURING}"
    m = (d["t"] >= T_LO) & (d["t"] <= T_HI)
    # string-valued stat: prefix keys alias — must materialize (and
    # still be correct through the fallback)
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats("evt", q, "Enumeration(name)")
    u, c = np.unique(d["name"][m].astype(str), return_counts=True)
    assert got.counts == dict(zip(u.tolist(), c.tolist()))
    assert _counter(LEAN_STATS_MATERIALIZED) == m0 + 1
    # selective bbox: attr keys carry no geometry — fallback
    got = ds.stats("evt",
                   f"BBOX(geom,-74.5,40.5,-73.5,41.5) AND {DURING}",
                   "MinMax(score)")
    sel = (m & (d["x"] >= -74.5) & (d["x"] <= -73.5)
           & (d["y"] >= 40.5) & (d["y"] <= 41.5))
    assert got.min == d["score"][sel].min()
    assert _counter(LEAN_STATS_MATERIALIZED) == m0 + 2
    # GroupBy is never pushable
    ds.stats("evt", q, "GroupBy(k,Count())")
    assert _counter(LEAN_STATS_MATERIALIZED) == m0 + 3


def test_count_rides_attr_fold_for_selective_time(lean_store):
    """Pure Count() with a selective time window on a NON-full-tier
    store was previously unanswerable without materialization (the z3
    count gate needs t_open); it now rides any indexed numeric
    attribute's fold — sec is the raw dtg, exact at any window."""
    ds, d = lean_store
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats("evt", f"{WORLD} AND {DURING}", "Count()")
    m = (d["t"] >= T_LO) & (d["t"] <= T_HI)
    assert got.count == int(m.sum())
    assert _counter(LEAN_STATS_MATERIALIZED) == m0


def test_z3histogram_pushdown_whole_extent(lean_store):
    ds, d = lean_store
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats("evt", "INCLUDE", "Z3Histogram(geom,dtg,week,10)")
    oracle = Z3HistogramStat("geom", "dtg", "week", 10)

    class _B:
        def geom_xy(self, g):
            return d["x"], d["y"]

        def column(self, c):
            return d["t"]

    oracle.observe(_B())
    assert got.counts == oracle.counts
    assert _counter(LEAN_STATS_MATERIALIZED) == m0
    # selective TIME window: z3 cells are time-cell-granular — fallback
    ds.stats("evt", f"{WORLD} AND {DURING}",
             "Z3Histogram(geom,dtg,week,10)")
    assert _counter(LEAN_STATS_MATERIALIZED) == m0 + 1


def test_compaction_mints_new_generations_and_invalidates(lean_store):
    """Compaction folds sealed runs into fresh gen_ids; their cached
    sketch partials must drop (stale grids double-count) and the next
    scan must re-fold + re-cache with results unchanged."""
    ds, d = lean_store
    st = ds._store("evt")
    idx = st._lean_attr_index("k")
    fold = SketchFold(slo=T_LO, shi=T_HI, bins=8, hlo=0.0, hhi=40.0)
    before = idx.sketch_scan(fold)
    cache = idx._sketch_cache.spec_cache(fold)
    dead = [g.gen_id for g in idx.generations[:-1]]
    assert any(gid in cache for gid in dead)
    stats = idx.compact(factor=4)
    assert stats["merged_groups"] >= 1
    assert all(gid not in cache for gid in dead
               if gid not in {g.gen_id for g in idx.generations})
    after = idx.sketch_scan(fold)
    assert before.to_json() == after.to_json()
    m = (d["t"] >= T_LO) & (d["t"] <= T_HI)
    assert after.count == int(m.sum())
    np.testing.assert_array_equal(
        after.hist,
        np.bincount(np.clip((d["k"][m] * 8 // 40), 0, 7),
                    minlength=8))


def test_sketch_cache_lru_and_byte_ceiling():
    from geomesa_tpu.index.partial_cache import PartialCache
    pc = PartialCache(max_specs=2, max_bytes=10_000)
    a = pc.spec_cache("a")
    pc.add(a, 1, np.zeros(500, np.int64))   # 4000 B
    b = pc.spec_cache("b")
    pc.add(b, 1, np.zeros(500, np.int64))
    assert len(pc) == 2
    pc.spec_cache("c")                       # LRU evicts "a"
    assert len(pc) == 2 and "a" not in set(iter(pc))
    # ceiling: an insert that would bust max_bytes is refused
    c = pc.spec_cache("c")
    pc.add(c, 1, np.zeros(2_000, np.int64))  # 16000 B > ceiling
    assert 1 not in c
    pc.drop_generations([1])
    assert all(1 not in d for d in pc.values())


def test_xz2_facade_sketch_scan_counts():
    """The XZ facades expose the core's fold surface: a whole-window
    Count over the generational runs, sealed partials cached."""
    from geomesa_tpu.index.xz2_lean import LeanXZ2Index
    rng = np.random.default_rng(31)
    n = 3_000
    cx = rng.uniform(-170, 170, n)
    cy = rng.uniform(-80, 80, n)
    bb = np.column_stack([cx - .01, cy - .01, cx + .01, cy + .01])
    idx = LeanXZ2Index(generation_slots=1 << 10)
    for lo in range(0, n, 1 << 10):
        idx.append_bboxes(bb[lo:lo + (1 << 10)], base_gid=lo)
    part = idx.sketch_scan(SketchFold())
    assert part.count == n
    assert idx.sketch_scan(SketchFold()).count == n   # warm/cached


def test_xz2_store_attr_stats_pushdown():
    """Non-point lean stores (the xz2 kind) push attribute stats
    through the same pipeline — covered-extent spatial no-op + exact
    numeric folds."""
    rng = np.random.default_rng(33)
    n = 4_000
    ds = TpuDataStore()
    ds.create_schema("polys", "v:Int:index=true,*poly:Polygon;"
                              "geomesa.index.profile=lean")
    from geomesa_tpu.geometry.types import Polygon
    cx = rng.uniform(-170, 170, n)
    cy = rng.uniform(-80, 80, n)
    v = rng.integers(0, 25, n)
    polys = [Polygon([(a - .01, b - .01), (a + .01, b - .01),
                      (a + .01, b + .01), (a - .01, b + .01)])
             for a, b in zip(cx, cy)]
    ds.write("polys", {"v": v, "poly": polys})
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats("polys", "INCLUDE", "Count();MinMax(v)")
    assert got.stats[0].count == n
    assert got.stats[1].min == v.min() and got.stats[1].max == v.max()
    assert _counter(LEAN_STATS_MATERIALIZED) == m0


# -- sharded variants ---------------------------------------------------

def test_sharded_store_pushdown_oracle_exact():
    from geomesa_tpu.parallel import device_mesh
    rng = np.random.default_rng(21)
    n = 24_000
    ds = TpuDataStore(mesh=device_mesh())
    ds.create_schema(
        "mevt", "score:Double:index=true,dtg:Date,*geom:Point;"
                "geomesa.index.profile=lean,"
                "geomesa.lean.generation.slots=1024,"
                "geomesa.lean.compaction.factor=0")
    x = rng.uniform(-75, -73, n)
    y = rng.uniform(40, 42, n)
    t = rng.integers(MS, MS + 14 * DAY, n)
    sc = rng.normal(50.0, 20.0, n)
    step = 6000
    for lo in range(0, n, step):
        sl = slice(lo, lo + step)
        ds.write("mevt", {"score": sc[sl], "dtg": t[sl],
                          "geom": (x[sl], y[sl])})
    st = ds._store("mevt")
    assert len(st._lean_attr_index("score").generations) > 1
    m0 = _counter(LEAN_STATS_MATERIALIZED)
    got = ds.stats("mevt", f"{WORLD} AND {DURING}",
                   "Count();MinMax(score);Histogram(score,20,0,100)")
    m = (t >= T_LO) & (t <= T_HI)
    assert got.stats[0].count == int(m.sum())
    assert got.stats[1].min == sc[m].min()
    oracle = Histogram("score", 20, 0.0, 100.0)
    oracle.observe({"score": sc[m]})
    np.testing.assert_array_equal(got.stats[2].counts, oracle.counts)
    assert _counter(LEAN_STATS_MATERIALIZED) == m0
    # warm repeat serves sealed runs from the (global-partial) cache
    h0 = _counter(LEAN_SKETCH_CACHE_HITS)
    again = ds.stats("mevt", f"{WORLD} AND {DURING}",
                     "Count();MinMax(score);Histogram(score,20,0,100)")
    assert again.to_json() == got.to_json()
    assert _counter(LEAN_SKETCH_CACHE_HITS) > h0
