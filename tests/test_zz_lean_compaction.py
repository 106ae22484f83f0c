"""LSM generation compaction + sealed-generation density partial
caching (the lean tiered store's maintenance lifecycle).

Covers the ISSUE-2 acceptance surface: merge correctness vs oracle
(hit sets identical pre/mid/post compact), the 1B-shaped scaled-down
ingest (≥ 20 appends forcing ≥ 15 demotions) ending at ≤ 8 generations,
budget-exhausted compaction resuming cleanly, memory accounting
releasing merged runs' slack slots, cached density partials
invalidating when sealed generations compact away, the ≥ 5× warm
repeat density speedup, and the satellite regressions (sql_join
multihost gate, string-None encoding, sharded attr slot burn,
partial-window density divergence bound).

Named ``test_zz_*`` deliberately: this is the heavyweight lifecycle
suite (many-generation builds, device merges), so it runs at the END of
the alphabetical tier-1 order, after the fast unit suites.
"""

import time

import numpy as np
import pytest

from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.index.attr_lean import LeanAttrIndex, encode_attr_values
from geomesa_tpu.index.z3_lean import LeanZ3Index

MS = 1514764800000
DAY = 86_400_000
WORLD = (-180.0, -90.0, 180.0, 90.0)
SLOTS = 1 << 12
BOX = (-74.5, 40.5, -73.5, 41.5)
T_LO, T_HI = MS + 2 * DAY, MS + 9 * DAY


def _data(n, seed=7):
    rng = np.random.default_rng(seed)
    return (rng.uniform(-75, -73, n), rng.uniform(40, 42, n),
            rng.integers(MS, MS + 14 * DAY, n))


def _oracle(x, y, t, box, lo, hi):
    m = ((x >= box[0]) & (x <= box[2])
         & (y >= box[1]) & (y <= box[3]))
    if lo is not None:
        m = m & (t >= lo)
    if hi is not None:
        m = m & (t <= hi)
    return np.flatnonzero(m)


def _brute_grid(x, y, sel, env, w, h):
    g = np.zeros((h, w))
    gx = np.clip(((x[sel] - env[0]) / (env[2] - env[0]) * w).astype(int),
                 0, w - 1)
    gy = np.clip(((y[sel] - env[1]) / (env[3] - env[1]) * h).astype(int),
                 0, h - 1)
    np.add.at(g, (gy, gx), 1.0)
    return g


def _clone(src_idx):
    """Structural clone of a built index: generations share the SOURCE's
    immutable jnp columns / host runs (merges always allocate fresh
    arrays, never mutate), so compaction tests can reuse one expensive
    streamed build."""
    from geomesa_tpu.index.z3_lean import _Generation
    idx = LeanZ3Index(period="week", generation_slots=SLOTS,
                      payload_on_device=False,
                      hbm_budget_bytes=src_idx.hbm_budget_bytes)
    for g in src_idx.generations:
        ng = _Generation.__new__(_Generation)
        for slot in _Generation.__slots__:
            setattr(ng, slot, getattr(g, slot))
        idx.generations.append(ng)
    idx._payload = list(src_idx._payload)
    idx._flat = src_idx._flat
    idx._n_rows = src_idx._n_rows
    idx.t_min_ms = src_idx.t_min_ms
    idx.t_max_ms = src_idx.t_max_ms
    idx._gen_counter = src_idx._gen_counter
    return idx


@pytest.fixture(scope="module")
def built20():
    """One 20-generation streamed build shared (via _clone) by every
    test that only compacts/queries it."""
    return _streamed(20)


def _streamed(n_gens, payload=False, budget=None, factor=None,
              seed=7):
    x, y, t = _data(n_gens * SLOTS, seed=seed)
    idx = LeanZ3Index(period="week", generation_slots=SLOTS,
                      payload_on_device=payload,
                      hbm_budget_bytes=budget,
                      compaction_factor=factor)
    for lo in range(0, len(x), SLOTS):
        sl = slice(lo, lo + SLOTS)
        idx.append(x[sl], y[sl], t[sl])
    return idx, x, y, t


# -- compaction correctness -----------------------------------------------
def test_compact_keys_tier_oracle_exact_and_log_generations(built20):
    src_idx, x, y, t = built20
    idx = _clone(src_idx)
    assert len(idx.generations) == 20
    before = idx.query([BOX], T_LO, T_HI)
    stats = idx.compact()
    assert stats["merged_groups"] >= 4
    assert len(idx.generations) <= 8
    after = idx.query([BOX], T_LO, T_HI)
    np.testing.assert_array_equal(before, after)
    np.testing.assert_array_equal(after,
                                  _oracle(x, y, t, BOX, T_LO, T_HI))


def test_demotion_heavy_ingest_compacts_host_runs():
    """The 1B-shaped scaled-down analogue: ≥ 20 appends under a budget
    forcing ≥ 15 demotions to host RAM; compaction folds the spilled
    runs and every query/density result stays oracle-exact."""
    budget = 6 * SLOTS * 16   # sentinel + ~5 device keys generations
    idx, x, y, t = _streamed(21, budget=budget)
    tiers = idx.tier_counts()
    assert tiers["host"] >= 15
    before_q = idx.query([BOX], T_LO, T_HI)
    before_d = idx.density([BOX], T_LO, T_HI, WORLD, 64, 32)
    stats = idx.compact()
    assert len(idx.generations) <= 8
    assert idx.tier_counts()["host"] <= 2
    np.testing.assert_array_equal(idx.query([BOX], T_LO, T_HI),
                                  before_q)
    np.testing.assert_array_equal(
        idx.query([BOX], T_LO, T_HI),
        _oracle(x, y, t, BOX, T_LO, T_HI))
    np.testing.assert_array_equal(
        idx.density([BOX], T_LO, T_HI, WORLD, 64, 32), before_d)
    # whole-extent density stays exact over the merged runs
    np.testing.assert_array_equal(
        idx.density([WORLD], None, None, WORLD, 64, 32),
        _brute_grid(x, y, np.ones(len(x), bool), WORLD, 64, 32))
    assert stats["generations"] == len(idx.generations)


def test_budget_exhausted_compaction_resumes(built20):
    src_idx, x, y, t = built20
    idx = _clone(src_idx)
    want = _oracle(x, y, t, BOX, T_LO, T_HI)
    gens0 = len(idx.generations)
    stats = idx.compact(budget_ms=0.0)
    # progress is guaranteed (≥ 1 group) but the deadline stops it
    assert stats["merged_groups"] == 1
    assert len(idx.generations) < gens0
    # mid-compaction state serves exact results
    np.testing.assert_array_equal(idx.query([BOX], T_LO, T_HI), want)
    rounds = 0
    while idx.compact(budget_ms=0.0)["merged_groups"]:
        rounds += 1
        assert rounds < 50
    assert len(idx.generations) <= 8
    np.testing.assert_array_equal(idx.query([BOX], T_LO, T_HI), want)


def test_compact_factor_one_clamps_and_terminates(built20):
    """factor=1 would re-merge a run into its own size class forever;
    the shared planner clamps to 2 (index/lsm.py)."""
    idx = _clone(built20[0])
    stats = idx.compact(factor=1)
    assert stats["merged_groups"] >= 1
    assert len(idx.generations) <= 8


def test_opportunistic_compaction_bounds_generations():
    """With the trigger enabled, a 24-flush stream never accumulates
    24 runs — the post-append merges keep the count O(log)."""
    idx, x, y, t = _streamed(24, factor=4)
    assert idx.compactions >= 4
    assert len(idx.generations) <= 8
    np.testing.assert_array_equal(
        idx.query([BOX], T_LO, T_HI),
        _oracle(x, y, t, BOX, T_LO, T_HI))


def test_attr_index_compaction_oracle():
    vals = np.random.default_rng(3).integers(0, 50, 40_000)
    sec = np.random.default_rng(4).integers(MS, MS + DAY, 40_000)
    idx = LeanAttrIndex("v", "int", generation_slots=1 << 12)
    for lo in range(0, len(vals), 1 << 12):
        idx.append(vals[lo:lo + (1 << 12)], sec[lo:lo + (1 << 12)],
                   base_gid=lo)
    assert len(idx.generations) == 10
    want = np.flatnonzero(vals == 7)
    np.testing.assert_array_equal(idx.query_equals(7), want)
    stats = idx.compact()
    assert stats["merged_groups"] >= 2
    assert len(idx.generations) <= 4
    np.testing.assert_array_equal(idx.query_equals(7), want)
    np.testing.assert_array_equal(
        idx.query_range(10, 20), np.flatnonzero((vals >= 10)
                                                & (vals <= 20)))


# -- memory accounting ----------------------------------------------------
def test_sharded_compaction_releases_slack_slots():
    """Sharded generations seal with slack (rollover keeps m_pad
    headroom); the merged run is sized to the consumed slots only, so
    device residency DROPS by exactly the released slack."""
    import jax
    from geomesa_tpu.parallel.lean import ShardedLeanZ3Index
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("shard",))
    # 200-row appends consume 32 slots/step (m_pad), so a 120-slot
    # generation seals at 96 consumed slots with 24 slots of slack
    slots = 120
    idx = ShardedLeanZ3Index(period="week", mesh=mesh,
                             generation_slots=slots,
                             payload_on_device=False)
    x, y, t = _data(30 * 200, seed=5)
    for lo in range(0, len(x), 200):
        sl = slice(lo, lo + 200)
        idx.append(x[sl], y[sl], t[sl])
    sealed = idx.generations[:-1]
    assert len(sealed) >= 4
    slack = sum(g.slots - g.n_slots for g in sealed[:4])
    assert slack > 0       # rollover sealed them below capacity
    before = idx.device_bytes()
    hits0 = idx.query([BOX], T_LO, T_HI)
    stats = idx.compact()
    assert stats["merged_groups"] >= 1
    assert idx.device_bytes() < before
    np.testing.assert_array_equal(idx.query([BOX], T_LO, T_HI), hits0)
    np.testing.assert_array_equal(
        hits0, _oracle(x, y, t, BOX, T_LO, T_HI))


def test_single_chip_accounting_consistent_after_compact(built20):
    src_idx, x, y, t = built20
    idx = _clone(src_idx)
    from geomesa_tpu.index.z3_lean import KEYS_BYTES
    before = idx.device_bytes()
    idx.compact()
    # merged runs carry zero padding: residency never grows, and the
    # accounting equals the live structure exactly
    assert idx.device_bytes() <= before
    assert idx.device_bytes() == sum(
        g.capacity * KEYS_BYTES for g in idx.generations
        if g.tier == "keys")
    assert idx._fits()


# -- sealed-generation density partial cache ------------------------------
def test_warm_repeat_density_5x_faster_and_exact():
    # 48 generations: the regime the cache targets — cold cost scales
    # with generation count while warm stays at the live-only floor
    # (48 keeps the measured ratio ~13x on an idle host, so the 5x
    # assertion holds through CI contention)
    idx, x, y, t = _streamed(48)
    want = _brute_grid(x, y, _oracle(x, y, t, BOX, T_LO, T_HI),
                       WORLD, 256, 128)
    # compile both the all-generations (cold) and live-only (warm)
    # program shapes first, so the timed ratio compares WORK, not
    # first-call compiles
    idx.density([BOX], T_LO, T_HI, WORLD, 256, 128)
    idx.density([BOX], T_LO, T_HI, WORLD, 256, 128)
    idx._density_cache.clear()
    t0 = time.perf_counter()
    cold = idx.density([BOX], T_LO, T_HI, WORLD, 256, 128)
    cold_dt = time.perf_counter() - t0
    d0 = idx.dispatch_count
    warm_dt = float("inf")
    for _ in range(3):     # best-of-3 damps shared-CI timer noise
        t0 = time.perf_counter()
        warm = idx.density([BOX], T_LO, T_HI, WORLD, 256, 128)
        warm_dt = min(warm_dt, time.perf_counter() - t0)
    np.testing.assert_array_equal(warm, cold)
    # BOX×window is cell-inclusive on keys tiers: mass may exceed the
    # value-exact oracle only by boundary-cell points
    assert warm.sum() >= want.sum()
    # each warm call re-scans ONLY the live generation: one probe +
    # one scan per repeat
    assert idx.dispatch_count - d0 <= 6
    assert cold_dt >= 5 * warm_dt, (cold_dt, warm_dt)


def test_density_cache_hits_and_misses_counted(built20):
    from geomesa_tpu.metrics import (
        LEAN_DENSITY_CACHE_HITS, LEAN_DENSITY_CACHE_MISSES,
        registry,
    )
    idx = _clone(built20[0])
    h0 = registry.counter(LEAN_DENSITY_CACHE_HITS).count
    m0 = registry.counter(LEAN_DENSITY_CACHE_MISSES).count
    idx.density([BOX], T_LO, T_HI, WORLD, 32, 16)
    assert registry.counter(LEAN_DENSITY_CACHE_MISSES).count - m0 == 19
    idx.density([BOX], T_LO, T_HI, WORLD, 32, 16)
    assert registry.counter(LEAN_DENSITY_CACHE_HITS).count - h0 == 19


def test_cached_partials_invalidate_when_generations_compact_away(
        built20):
    src_idx, x, y, t = built20
    idx = _clone(src_idx)
    g1 = idx.density([BOX], T_LO, T_HI, WORLD, 64, 32)
    spec_caches = list(idx._density_cache.values())
    assert spec_caches and len(spec_caches[0]) == 19
    idx.compact()
    live_ids = {g.gen_id for g in idx.generations}
    for cache in idx._density_cache.values():
        assert set(cache) <= live_ids   # no stale partials survive
    g2 = idx.density([BOX], T_LO, T_HI, WORLD, 64, 32)
    np.testing.assert_array_equal(g1, g2)
    # and the re-seeded cache serves the compacted shape
    np.testing.assert_array_equal(
        idx.density([BOX], T_LO, T_HI, WORLD, 64, 32), g1)


def test_density_cache_survives_demotion_and_lru_bounds_specs(
        built20):
    from geomesa_tpu.metrics import (
        LEAN_DENSITY_CACHE_MISSES, registry,
    )
    idx = _clone(built20[0])
    g1 = idx.density([BOX], T_LO, T_HI, WORLD, 16, 8)
    # demotion does not change a sealed generation's rows: its cached
    # partial keeps serving after the spill (keys-tier and host-tier
    # scans share the cell-granular contract)
    for g in idx.generations[:-1]:
        g.spill_to_host()
    idx._host_stack = None
    m0 = registry.counter(LEAN_DENSITY_CACHE_MISSES).count
    g2 = idx.density([BOX], T_LO, T_HI, WORLD, 16, 8)
    np.testing.assert_array_equal(g1, g2)
    assert registry.counter(LEAN_DENSITY_CACHE_MISSES).count == m0
    # the spec LRU stays bounded
    for i in range(LeanZ3Index.DENSITY_CACHE_SPECS + 2):
        idx.density([BOX], T_LO + i * 1000, T_HI, WORLD, 16, 8)
    assert len(idx._density_cache) <= LeanZ3Index.DENSITY_CACHE_SPECS


# -- store-level lifecycle ------------------------------------------------
def _lean_store(n=80_000, factor=0, budget=None):
    """A lean store in the many-generation regime.  ``budget`` forces
    the 1B-shaped tiering: sealed generations demote to keys/host —
    the runs compaction targets (full-tier runs never merge; under
    pressure they demote first, exactly the 1B profile)."""
    rng = np.random.default_rng(17)
    ds = TpuDataStore()
    budget = budget if budget is not None else 16 * SLOTS * 16
    ds.create_schema(
        "evt", "name:String:index=true,score:Double,dtg:Date,"
               "*geom:Point;geomesa.index.profile=lean,"
               f"geomesa.lean.generation.slots={SLOTS},"
               f"geomesa.lean.hbm.budget={budget},"
               f"geomesa.lean.compaction.factor={factor}")
    for s in range(0, n, SLOTS):
        m = min(SLOTS, n - s)
        ds.write("evt", {
            "name": rng.choice(["a", "b", "c"], m).astype(object),
            "score": rng.uniform(0, 100, m),
            "dtg": rng.integers(MS, MS + 14 * DAY, m),
            "geom": (rng.uniform(-75, -73, m),
                     rng.uniform(40, 42, m))})
    return ds


def test_store_compact_api_and_job_oracle_exact():
    from geomesa_tpu.jobs import run_compaction
    from geomesa_tpu.process.knn import knn_process

    ds = _lean_store()
    st = ds._store("evt")
    assert len(st.index("z3").generations) >= 19
    ecql = (f"BBOX(geom,{BOX[0]},{BOX[1]},{BOX[2]},{BOX[3]}) AND "
            "dtg DURING 2018-01-03T00:00:00Z/2018-01-10T00:00:00Z")
    before = ds.query_result("evt", ecql).positions
    knn_before = knn_process(ds, "evt", -74.0, 41.0, 10)[0]
    stats = run_compaction(ds, "evt")
    assert stats["z3"]["merged_groups"] >= 1
    assert len(st.index("z3").generations) <= 8
    np.testing.assert_array_equal(
        ds.query_result("evt", ecql).positions, before)
    np.testing.assert_array_equal(
        knn_process(ds, "evt", -74.0, 41.0, 10)[0], knn_before)
    # attribute index compacted through the same call
    assert "attr:name" in stats
    # a second call converges to a no-op
    assert ds.compact("evt")["z3"]["merged_groups"] == 0


def test_store_opportunistic_compaction_via_option():
    ds = _lean_store(factor=4)
    st = ds._store("evt")
    assert st.index("z3").compactions >= 1
    assert len(st.index("z3").generations) <= 8


# -- satellite regressions ------------------------------------------------
def test_sql_join_multihost_gated(monkeypatch):
    import jax

    from geomesa_tpu.sql.join import sql_join

    rng = np.random.default_rng(1)
    ds = TpuDataStore()
    for name in ("a", "b"):
        ds.create_schema(name, "site:String,score:Double,dtg:Date,"
                               "*geom:Point")
        ds.write(name, {
            "site": rng.choice(["x", "y"], 100).astype(object),
            "score": rng.uniform(0, 100, 100),
            "dtg": rng.integers(MS, MS + DAY, 100),
            "geom": (rng.uniform(-75, -73, 100),
                     rng.uniform(40, 42, 100))})
    sql = ("SELECT a.site, b.score FROM a a JOIN b b "
           "ON a.site = b.site LIMIT 5")
    assert sql_join(ds, sql)   # single-process joins still work
    # multihost MODE on one process holds all rows locally — allowed
    ds._store("b").multihost = True
    assert sql_join(ds, sql)
    # ...but with real peer processes the pairing would silently drop
    # cross-process rows — gated loudly
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(NotImplementedError, match="multihost"):
        sql_join(ds, sql)


def test_encode_strings_none_path_independent():
    # fast astype('S8') path (plain ASCII + None)
    fast = encode_attr_values(
        np.array(["abc", None, ""], dtype=object), "string")
    # forced fallback path (non-ASCII entry)
    slow = encode_attr_values(
        np.array(["abc", None, "", "é"], dtype=object), "string")
    np.testing.assert_array_equal(fast, slow[:3])
    # None encodes as the EMPTY key, not as the string "None"
    assert fast[1] == fast[2]
    assert fast[1] != encode_attr_values(np.array(["None"]),
                                         "string")[0]


def test_sharded_attr_append_reuses_padded_region():
    import jax
    from geomesa_tpu.parallel.attr_lean import ShardedLeanAttrIndex
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("shard",))
    idx = ShardedLeanAttrIndex("v", "int", mesh=mesh,
                               generation_slots=64)
    vals = np.arange(30, dtype=np.int64)
    # ten 3-row collective steps: the old append burned m_pad (= 8)
    # slots per step — 80 slots, spilling into a second generation;
    # fill-tracking consumes 3 per step and packs all 30 rows into one
    for i in range(10):
        sl = slice(3 * i, 3 * i + 3)
        idx.append(vals[sl], np.full(3, MS), base_gid=3 * i)
    assert len(idx.generations) == 1
    assert idx.generations[-1].n_slots == 30
    for probe in (0, 13, 29):
        np.testing.assert_array_equal(idx.query_equals(probe),
                                      np.array([probe]))


def test_partial_window_density_divergence_pinned():
    """Pin the cell-granular over-inclusion bound documented at the
    density_process API: on a DEMOTED (keys/host) store, a
    partial-window grid may exceed the materializing fallback only by
    points within one z cell of the window boundary — and only
    upward (no true hit is ever excluded)."""
    from geomesa_tpu.process.density import density_process

    ds = _lean_store(n=60_000)
    st = ds._store("evt")
    idx = st.index("z3")
    # demote everything sealed: partial-window masks now run at cell
    # granularity on every sealed generation
    for g in idx.generations[:-1]:
        g.spill_to_host()
    idx._host_stack = None
    x, y = st.batch.geom_xy()
    t = np.asarray(st.batch.column("dtg"), np.int64)
    ecql = (f"BBOX(geom,{BOX[0]},{BOX[1]},{BOX[2]},{BOX[3]}) AND "
            "dtg DURING 2018-01-03T00:00:00Z/2018-01-10T00:00:00Z")
    w, h = 256, 128
    grid = density_process(ds, "evt", ecql, WORLD, w, h)
    lo = MS + 2 * DAY
    hi = MS + 9 * DAY
    true_hits = _oracle(x, y, t, BOX, lo, hi)
    exact = _brute_grid(x, y, true_hits, WORLD, w, h)
    # one z cell in each dimension (21-bit lon/lat; time cell within
    # the week bin)
    eps_x = 360.0 / (1 << 21)
    eps_y = 180.0 / (1 << 21)
    eps_t = 7 * DAY / (1 << 21)
    expanded = _oracle(x, y, t,
                       (BOX[0] - eps_x, BOX[1] - eps_y,
                        BOX[2] + eps_x, BOX[3] + eps_y),
                       lo - eps_t, hi + eps_t)
    bound = len(expanded) - len(true_hits)
    diff = grid.sum() - exact.sum()
    assert 0 <= diff <= bound
    # world-aligned pow2 grid: binning is exact, so over-inclusion is
    # the ONLY divergence — per-cell the push-down never undercounts
    assert (grid - exact >= -1e-9).all()
