"""Store-level scale proof (round-4 VERDICT #1): TpuDataStore itself —
not a standalone index artifact — holds ≥100M rows under the lean
profile and serves ECQL (spatial AND attribute residuals), stats,
density, arrow export and kNN with oracle-verified results on the real
chip.

The reference's defining property is FULL query semantics at scale
through one DataStore (docs/user/introduction.rst:24,
GeoMesaDataStore.scala:48); this drives that property end-to-end:
chunked writes stream through `TpuDataStore.write` (stats observed on
write, keys appended to the tiered LeanZ3Index), then every query runs
through the planner facade.

Run directly (``STORE_SCALE_N`` overrides the row count) or through
``bench.py``'s scale stanza.  Results record to STORE_SCALE_r04.json
(monotonic: a smaller rerun never replaces a larger verified record).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

MS_2021 = 1609459200000  # 2021-01-01
DAY = 86_400_000
#: round-5 adds a needle value (~1e-4) so attribute-INDEXED access has
#: a selective target at 1B (round-4 VERDICT #1)
NAMES = np.array(["alpha", "beta", "gamma", "delta", "rare"],
                 dtype=object)
NAME_P = [0.55, 0.3, 0.0999, 0.05, 0.0001]


def _improves(record_path: str, rows: int) -> bool:
    try:
        with open(record_path) as f:
            return rows >= int(json.load(f).get("rows", 0))
    except Exception:
        return True


def _write_record(record_path: str, out: dict) -> None:
    """Atomic record update that PRESERVES evidence keys the new dict
    doesn't carry yet (a mid-build checkpoint must not delete the prior
    record's kNN measurements — they re-record at completion)."""
    merged = dict(out)
    try:
        with open(record_path) as f:
            prior = json.load(f)
    except Exception:       # missing OR corrupt — overwrite either way
        prior = {}
    carried = [k for k in prior if k not in merged]
    for k in carried:
        merged[k] = prior[k]
    if any(k.startswith("knn") for k in carried):
        # provenance: carried kNN numbers were measured at the PRIOR
        # record's row count, not this checkpoint's
        merged["knn_measured_at_rows"] = prior.get(
            "knn_measured_at_rows", prior.get("rows"))
    with open(record_path + ".tmp", "w") as f:
        json.dump(merged, f, indent=1)
    os.replace(record_path + ".tmp", record_path)


def _slice_data(i: int, m: int):
    """Slice ``i`` of a GDELT-shaped stream with an attribute column:
    population hotspots, six months of timestamps, skewed names."""
    rng = np.random.default_rng(40_000 + i)
    hot = rng.integers(0, 4, m)
    cx = np.array([-74.0, 2.3, 116.4, 28.0])[hot]
    cy = np.array([40.7, 48.8, 39.9, -26.2])[hot]
    x = np.clip(cx + rng.normal(0, 20.0, m), -179.9, 179.9)
    y = np.clip(cy + rng.normal(0, 12.0, m), -89.9, 89.9)
    t = rng.integers(MS_2021, MS_2021 + 180 * DAY, m)
    name = NAMES[rng.choice(len(NAMES), m, p=NAME_P)]
    score = rng.uniform(0, 100, m)
    return x, y, t, name, score


def run(n: int = 100_000_000, slice_rows: int = 8_388_608,
        progress=print, record: bool = True) -> dict:
    import jax

    import geomesa_tpu  # noqa: F401  (x64)
    from geomesa_tpu.datastore import TpuDataStore

    ds = TpuDataStore()
    ds.create_schema(
        "gdelt", "name:String:index=true,score:Double:index=true,dtg:Date,"
                 "*geom:Point;geomesa.index.profile=lean")
    st = ds._store("gdelt")
    assert st.lean

    nyc = (-75.0, 40.0, -73.0, 42.0)
    paris = (1.0, 47.5, 3.5, 50.0)
    w_nyc = (MS_2021 + 30 * DAY, MS_2021 + 44 * DAY)
    w_paris = (MS_2021 + 90 * DAY, MS_2021 + 97 * DAY)
    ecqls = [
        # pure spatio-temporal
        (f"BBOX(geom,{nyc[0]},{nyc[1]},{nyc[2]},{nyc[3]}) AND dtg "
         "DURING 2021-01-31T00:00:00Z/2021-02-14T00:00:00Z",
         lambda x, y, t, nm, sc: ((x >= nyc[0]) & (x <= nyc[2])
                                  & (y >= nyc[1]) & (y <= nyc[3])
                                  & (t >= w_nyc[0]) & (t <= w_nyc[1]))),
        # attribute residual on gid-decoded candidates
        (f"BBOX(geom,{paris[0]},{paris[1]},{paris[2]},{paris[3]}) AND "
         "dtg DURING 2021-04-01T00:00:00Z/2021-04-08T00:00:00Z AND "
         "name = 'beta' AND score > 50",
         lambda x, y, t, nm, sc: ((x >= paris[0]) & (x <= paris[2])
                                  & (y >= paris[1]) & (y <= paris[3])
                                  & (t >= w_paris[0]) & (t <= w_paris[1])
                                  & (nm == "beta") & (sc > 50))),
    ]

    # prewarm the lean query programs on a tiny same-shaped store
    # while the device is near-empty (docs/scale.md)
    warm = TpuDataStore()
    warm.create_schema(
        "w", "name:String:index=true,score:Double:index=true,dtg:Date,"
             "*geom:Point;geomesa.index.profile=lean")
    wx, wy, wt, wn, wsc = _slice_data(0, 4096)
    warm.write("w", {"name": wn, "score": wsc, "dtg": wt,
                     "geom": (wx, wy)})
    for ecql, _ in ecqls:
        warm.query_result("w", ecql)
    warm.query_windows("w", [([nyc], *w_nyc), ([paris], *w_paris)])
    # round-5 surfaces: attr index scans, density push-down, Count()
    warm.query_result("w", "name = 'rare'")
    warm.query_result("w", "name = 'rare' AND dtg DURING "
                           "2021-02-01T00:00:00Z/2021-04-01T00:00:00Z")
    from geomesa_tpu.process.density import density_process
    from geomesa_tpu.process.stats_process import stats_process
    world_env = (-180.0, -90.0, 180.0, 90.0)
    density_process(warm, "w", "INCLUDE", world_env, 256, 128)
    stats_process(warm, "w", "INCLUDE", "Count()")
    del warm
    progress("  store-scale: programs prewarmed")

    # raw-index rate measured in the SAME run (round-4 VERDICT #7's
    # denominator): a throwaway LeanZ3Index + LeanAttrIndex pair takes
    # the same slices the facade will, discarded before the real build
    from geomesa_tpu.index.attr_lean import LeanAttrIndex
    from geomesa_tpu.index.z3_lean import LeanZ3Index
    raw_z3 = LeanZ3Index(period="week")
    raw_at = LeanAttrIndex("name", "string")
    rx, ry, rt, rn, _ = _slice_data(0, slice_rows)
    raw_z3.append(rx, ry, rt)   # warm the append programs
    raw_at.append(rn, rt)
    raw_times = []
    for w in range(1, 4):
        rx, ry, rt, rn, _ = _slice_data(10_000 + w, slice_rows)
        tq = time.perf_counter()
        raw_z3.append(rx, ry, rt)
        raw_z3.block()
        raw_at.append(rn, rt)
        raw_at.block()
        raw_times.append(time.perf_counter() - tq)
    raw_rate = int(slice_rows / sorted(raw_times)[1])
    del raw_z3, raw_at
    progress(f"  store-scale: raw index rate {raw_rate} rows/s "
             "(z3 + attr, same slices)")

    record_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "STORE_SCALE_r05.json")

    def verify(label: str) -> dict:
        x, yv = st.batch.geom_xy()
        t = st.batch.column("dtg")
        nm = st.batch.column("name")
        sc = st.batch.column("score")
        q_warm, q_hits = [], []
        for ecql, oracle in ecqls:
            got = ds.query_result("gdelt", ecql)
            tq = time.perf_counter()
            got = ds.query_result("gdelt", ecql)   # steady-state
            q_warm.append(time.perf_counter() - tq)
            want = np.flatnonzero(oracle(x, yv, t, nm, sc))
            assert np.array_equal(np.sort(got.positions), want), (
                f"{label}: {len(got.positions)} vs {len(want)}")
            q_hits.append(int(len(want)))
        # round-5: attribute-INDEXED access at scale (VERDICT #1) —
        # attr-only, attr + wide bbox (the round-4 full-host-scan
        # degradations), and attr + time window (the date tier)
        a_warm, a_hits = [], []
        attr_ecqls = [
            ("name = 'rare'",
             lambda: nm == "rare"),
            ("name = 'rare' AND BBOX(geom,-180,-90,180,90)",
             lambda: nm == "rare"),
            ("name = 'rare' AND dtg DURING "
             "2021-02-01T00:00:00Z/2021-04-01T00:00:00Z",
             lambda: ((nm == "rare")
                      & (t >= MS_2021 + 31 * DAY)
                      & (t <= MS_2021 + 90 * DAY))),
        ]
        for ecql, oracle in attr_ecqls:
            got = ds.query_result("gdelt", ecql)
            assert got.strategy.index == "attr:name", got.strategy
            tq = time.perf_counter()
            got = ds.query_result("gdelt", ecql)
            a_warm.append(time.perf_counter() - tq)
            want = np.flatnonzero(oracle())
            assert np.array_equal(np.sort(got.positions), want), (
                f"{label} attr: {len(got.positions)} vs {len(want)}")
            a_hits.append(int(len(want)))
        progress(f"  store-scale: {label} attr-indexed verified — "
                 f"hits {a_hits}, warm "
                 f"{[round(v * 1e3) for v in a_warm]}ms")
        # stats through the facade vs exact aggregation
        cnt = ds.get_count("gdelt")
        assert cnt == len(st.batch), (cnt, len(st.batch))
        mm = ds.stat("gdelt", "score_minmax")
        assert abs(mm.bounds[0] - sc.min()) < 1e-9
        assert abs(mm.bounds[1] - sc.max()) < 1e-9
        topk = ds.stat("gdelt", "name_topk").topk(1)[0][0]
        assert topk == "alpha", topk
        # arrow export of a selective window
        tbl = ds.query_arrow("gdelt", ecqls[1][0],
                             dictionary_fields=("name",))
        assert tbl.num_rows == q_hits[1]
        progress(f"  store-scale: {label} verified — hits {q_hits}, "
                 f"warm {[round(v * 1e3) for v in q_warm]}ms "
                 "(oracle-exact, ECQL+stats+arrow)")
        return {"query_warm_ms": [round(v * 1e3, 1) for v in q_warm],
                "query_hits": q_hits, "oracle_exact": True,
                "attr_query_warm_ms": [round(v * 1e3, 1)
                                       for v in a_warm],
                "attr_query_hits": a_hits, "attr_oracle_exact": True}

    t0 = time.perf_counter()
    done = 0
    i = 1   # slice 0 seeds the prewarm store
    out: dict = {}
    while done < n:
        m = min(slice_rows, n - done)
        x, y, t, name, score = _slice_data(i, m)
        ds.write("gdelt", {"name": name, "score": score, "dtg": t,
                           "geom": (x, y)})
        st.index("z3").block()   # serialize slices
        done += m
        i += 1
        if i % 6 == 0 or done >= n:
            build_s = time.perf_counter() - t0
            idx = st.index("z3")
            stats = jax.local_devices()[0].memory_stats() or {}
            rate = int(len(st.batch) / build_s)
            out = {
                "rows": int(len(st.batch)),
                "generations": len(idx.generations),
                "tiers": idx.tier_counts(),
                "attr_tiers": st.attribute_index("name").tier_counts(),
                "device_bytes": int(idx.device_bytes()),
                "hbm_bytes_in_use": int(stats.get(
                    "bytes_in_use", idx.device_bytes())),
                "build_s": round(build_s, 1),
                "ingest_rows_per_sec": rate,
                "raw_index_rows_per_sec": raw_rate,
                "facade_fraction_of_raw": round(rate / raw_rate, 3),
                **verify(f"{done / 1e6:.0f}M"),
            }
            if record and _improves(record_path, out["rows"]):
                _write_record(record_path, out)
    # kNN process against the full store (round-4 VERDICT #5).  Cold
    # includes the first-time compiles of the generation-count-shaped
    # scan programs (cached on disk afterwards); warm is the steady
    # state an interactive workload sees.
    from geomesa_tpu.process import knn_process
    t0 = time.perf_counter()
    kpos, kdist = knn_process(ds, "gdelt", -74.0, 40.7, 25)
    knn_cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    kpos, kdist = knn_process(ds, "gdelt", -74.0, 40.7, 25)
    knn_s = time.perf_counter() - t0
    from geomesa_tpu.process.knn import haversine_m
    x, yv = st.batch.geom_xy()
    # chunked brute-force oracle: a whole-array haversine over 1B rows
    # allocates several 8 GB temporaries on top of the ~40 GB column
    # store and OOM-killed the 1B run (dmesg: 130 GB RSS) — per-chunk
    # partition keeps the working set at one chunk
    k = 25
    best = np.empty(0)
    step = 1 << 26
    for lo in range(0, len(x), step):
        d = haversine_m(-74.0, 40.7, x[lo:lo + step], yv[lo:lo + step])
        top = np.partition(d, min(k - 1, len(d) - 1))[:k]
        best = np.sort(np.concatenate([best, top]))[:k]
    assert np.allclose(np.sort(kdist), best, rtol=1e-12)
    out["knn25_cold_ms"] = round(knn_cold_s * 1e3, 1)
    out["knn25_warm_ms"] = round(knn_s * 1e3, 1)
    out["knn_oracle_exact"] = True
    out["knn_measured_at_rows"] = int(len(st.batch))
    progress(f"  store-scale: kNN k=25 over {len(st.batch) / 1e6:.0f}M "
             f"rows cold {knn_cold_s * 1e3:.0f}ms / warm "
             f"{knn_s * 1e3:.0f}ms, exact vs brute force")
    # round-5: whole-extent heatmap + Count() push-down at full scale
    # (VERDICT #2) — grids/sketches accumulate next to the keys; only
    # the grid crosses; verified against a CHUNKED numpy oracle
    from geomesa_tpu.process.density import density_process
    from geomesa_tpu.process.stats_process import stats_process
    world_env = (-180.0, -90.0, 180.0, 90.0)
    grid = density_process(ds, "gdelt", "INCLUDE", world_env, 256, 128)
    tq = time.perf_counter()
    grid = density_process(ds, "gdelt", "INCLUDE", world_env, 256, 128)
    dens_s = time.perf_counter() - tq
    xall, yall = st.batch.geom_xy()
    want_grid = np.zeros((128, 256))
    step = 1 << 26
    for lo in range(0, len(xall), step):
        gx = np.clip(((xall[lo:lo + step] + 180.0) / 360.0 * 256)
                     .astype(np.int64), 0, 255)
        gy = np.clip(((yall[lo:lo + step] + 90.0) / 180.0 * 128)
                     .astype(np.int64), 0, 127)
        np.add.at(want_grid, (gy, gx), 1.0)
    assert grid.sum() == len(st.batch), (grid.sum(), len(st.batch))
    dens_exact = bool(np.array_equal(grid, want_grid))
    out["density_1b_ms"] = round(dens_s * 1e3, 1)
    out["density_oracle_exact"] = dens_exact
    if not dens_exact:
        diff = np.abs(grid - want_grid)
        out["density_cells_differing"] = int((diff > 0).sum())
        out["density_max_cell_diff"] = float(diff.max())
    tq = time.perf_counter()
    cstat = stats_process(ds, "gdelt", "INCLUDE", "Count()")
    count_s = time.perf_counter() - tq
    assert cstat.count == len(st.batch), (cstat.count, len(st.batch))
    out["count_pushdown_ms"] = round(count_s * 1e3, 1)
    progress(f"  store-scale: whole-extent heatmap {dens_s*1e3:.0f}ms "
             f"(per-cell exact={dens_exact}), Count() push-down "
             f"{count_s*1e3:.0f}ms — both over "
             f"{len(st.batch)/1e6:.0f}M rows, no hit materialized")
    # ISSUE 3: full stat-sketch push-down at scale — Count/MinMax/
    # Histogram over a bbox+time window fold per sealed run next to
    # the attr keys; the warm repeat serves sealed runs from the
    # sketch-partial cache (the 1B cold/warm stat latency the bench's
    # stats_pushdown stanza points at)
    try:
        from geomesa_tpu.metrics import (
            LEAN_STATS_MATERIALIZED, registry as _reg,
        )
        sspec = "Count();MinMax(score);Histogram(score,20,0,100)"
        sq = ("BBOX(geom,-180,-90,180,90) AND dtg DURING "
              "2021-01-31T00:00:00Z/2021-02-14T00:00:00Z")
        m0 = _reg.counter(LEAN_STATS_MATERIALIZED).count
        tq = time.perf_counter()
        s_cold = stats_process(ds, "gdelt", sq, sspec)
        out["stats_pushdown_cold_ms"] = round(
            (time.perf_counter() - tq) * 1e3, 1)
        stats_process(ds, "gdelt", sq, sspec)   # live-only compile
        tq = time.perf_counter()
        s_warm = stats_process(ds, "gdelt", sq, sspec)
        out["stats_pushdown_warm_ms"] = round(
            (time.perf_counter() - tq) * 1e3, 1)
        out["stats_pushdown_speedup"] = round(
            out["stats_pushdown_cold_ms"]
            / max(out["stats_pushdown_warm_ms"], 1e-3), 1)
        out["stats_materialized_fallbacks"] = int(
            _reg.counter(LEAN_STATS_MATERIALIZED).count - m0)
        assert s_cold.to_json() == s_warm.to_json()
        progress("  store-scale: stat-sketch push-down cold "
                 f"{out['stats_pushdown_cold_ms']:.0f}ms / warm "
                 f"{out['stats_pushdown_warm_ms']:.0f}ms, "
                 f"{out['stats_materialized_fallbacks']} "
                 "materialized fallbacks")
    except Exception as e:  # the proof must not die over the stanza
        out["stats_pushdown_error"] = repr(e)
    if record and _improves(record_path, out["rows"]):
        _write_record(record_path, out)
    progress(f"  store-scale: COMPLETE at {len(st.batch) / 1e6:.0f}M "
             f"rows through the store facade")
    return out


if __name__ == "__main__":
    from geomesa_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = int(os.environ.get("STORE_SCALE_N", 100_000_000))
    out = run(n)
    print(json.dumps({"metric": "store_scale_proof", **out}))
