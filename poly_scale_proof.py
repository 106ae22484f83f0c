"""Polygon (non-point) store-scale proof — round-4 VERDICT #4: the
lean XZ2 tier holds ≥200M polygons in ONE TpuDataStore on the chip and
serves INTERSECTS/BBOX ECQL, the attribute tier, deletes and id
lookups, oracle-verified at checkpoints.

The reference's XZ indexes are first-class at cluster scale
(XZ2SFC.scala:54-77, XZ2IndexKeySpace.scala:44); round 4 capped
non-point schemas at the full-fat ~150M/chip tier.  The stream is
OBJECT-FREE: axis-aligned footprint rectangles arrive as envelope
arrays and pack vectorized (`packed_from_boxes`) — 200M Python
geometry objects would dominate the build.

Records to STORE_SCALE_POLY_r05.json (monotonic).  ``POLY_SCALE_N``
overrides the row count.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

KINDS = np.array(["road", "building", "park", "water", "rare"],
                 dtype=object)
KIND_P = [0.4, 0.4, 0.1, 0.0999, 0.0001]


def _improves(record_path: str, rows: int) -> bool:
    try:
        with open(record_path) as f:
            return rows >= int(json.load(f).get("rows", 0))
    except Exception:
        return True


def _slice_data(i: int, m: int):
    """Slice ``i`` of an OSM-buildings-shaped stream: small axis-aligned
    rectangles clustered around city hotspots."""
    rng = np.random.default_rng(70_000 + i)
    hot = rng.integers(0, 4, m)
    cx = np.array([-74.0, 2.3, 116.4, 28.0])[hot]
    cy = np.array([40.7, 48.8, 39.9, -26.2])[hot]
    x = np.clip(cx + rng.normal(0, 15.0, m), -179.8, 179.8)
    y = np.clip(cy + rng.normal(0, 10.0, m), -84.8, 84.8)
    w = rng.uniform(0.0005, 0.01, m)
    h = rng.uniform(0.0005, 0.01, m)
    bbox = np.stack([x - w, y - h, x + w, y + h], axis=1)
    kind = KINDS[rng.choice(len(KINDS), m, p=KIND_P)]
    return bbox, kind


def run(n: int = 200_000_000, slice_rows: int = 4_194_304,
        progress=print, record: bool = True) -> dict:
    import jax

    import geomesa_tpu  # noqa: F401
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.geometry.packed import packed_from_boxes

    ds = TpuDataStore()
    ds.create_schema(
        "osm", "kind:String:index=true,*geom:Polygon;"
               "geomesa.index.profile=lean")
    st = ds._store("osm")
    assert st.lean and st.lean_kind == "xz2"

    qbox = (-75.0, 40.0, -73.0, 42.0)      # NYC hotspot window
    q_ecql = (f"INTERSECTS(geom, POLYGON(({qbox[0]} {qbox[1]}, "
              f"{qbox[2]} {qbox[1]}, {qbox[2]} {qbox[3]}, "
              f"{qbox[0]} {qbox[3]}, {qbox[0]} {qbox[1]})))")

    # prewarm the xz2/attr scan programs on a tiny same-shaped store
    warm = TpuDataStore()
    warm.create_schema("w", "kind:String:index=true,*geom:Polygon;"
                            "geomesa.index.profile=lean")
    wb, wk = _slice_data(0, 4096)
    warm.write("w", {"kind": wk, "geom": packed_from_boxes(wb)})
    warm.query_result("w", q_ecql)
    warm.query_result("w", "kind = 'rare'")
    del warm
    progress("  poly-scale: programs prewarmed")

    record_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "STORE_SCALE_POLY_r05.json")

    def verify(label: str) -> dict:
        bb = st.batch.geoms.bbox
        kd = st.batch.column("kind")
        got = ds.query_result("osm", q_ecql)
        tq = time.perf_counter()
        got = ds.query_result("osm", q_ecql)
        q_warm = time.perf_counter() - tq
        # axis-aligned rectangles: INTERSECTS == bbox overlap (exact)
        want = np.flatnonzero((bb[:, 0] <= qbox[2])
                              & (bb[:, 2] >= qbox[0])
                              & (bb[:, 1] <= qbox[3])
                              & (bb[:, 3] >= qbox[1]))
        assert np.array_equal(np.sort(got.positions), want), (
            f"{label}: {len(got.positions)} vs {len(want)}")
        a_got = ds.query_result("osm", "kind = 'rare'")
        assert a_got.strategy.index == "attr:kind"
        tq = time.perf_counter()
        a_got = ds.query_result("osm", "kind = 'rare'")
        a_warm = time.perf_counter() - tq
        a_want = np.flatnonzero(kd == "rare")
        assert np.array_equal(np.sort(a_got.positions), a_want), (
            f"{label} attr: {len(a_got.positions)} vs {len(a_want)}")
        progress(f"  poly-scale: {label} verified — intersects "
                 f"{len(want)} hits {q_warm*1e3:.0f}ms, attr "
                 f"{len(a_want)} hits {a_warm*1e3:.0f}ms "
                 "(oracle-exact)")
        return {"query_warm_ms": [round(q_warm * 1e3, 1)],
                "query_hits": [int(len(want))],
                "attr_query_warm_ms": [round(a_warm * 1e3, 1)],
                "attr_query_hits": [int(len(a_want))],
                "oracle_exact": True, "attr_oracle_exact": True}

    t0 = time.perf_counter()
    done = 0
    i = 1
    out: dict = {}
    while done < n:
        m = min(slice_rows, n - done)
        bbox, kind = _slice_data(i, m)
        ds.write("osm", {"kind": kind, "geom": packed_from_boxes(bbox)})
        st.index("xz2").block()
        done += m
        i += 1
        if i % 12 == 0 or done >= n:
            build_s = time.perf_counter() - t0
            idx = st.index("xz2")
            stats = jax.local_devices()[0].memory_stats() or {}
            out = {
                "rows": int(len(st.batch)),
                "generations": len(idx.generations),
                "tiers": idx.tier_counts(),
                "device_bytes": int(idx.device_bytes()),
                "hbm_bytes_in_use": int(stats.get(
                    "bytes_in_use", idx.device_bytes())),
                "build_s": round(build_s, 1),
                "ingest_rows_per_sec": int(len(st.batch) / build_s),
                **verify(f"{done / 1e6:.0f}M"),
            }
            if record and _improves(record_path, out["rows"]):
                with open(record_path + ".tmp", "w") as f:
                    json.dump(out, f, indent=1)
                os.replace(record_path + ".tmp", record_path)
    # deletes + id lookup at full capacity
    bb = st.batch.geoms.bbox
    hit0 = int(np.flatnonzero((bb[:, 0] <= qbox[2])
                              & (bb[:, 2] >= qbox[0])
                              & (bb[:, 1] <= qbox[3])
                              & (bb[:, 3] >= qbox[1]))[0])
    assert ds.delete("osm", [str(hit0)]) == 1
    got = ds.query_result("osm", q_ecql)
    assert hit0 not in set(got.positions.tolist())
    one = ds.query_result("osm", f"IN ('{hit0 + 1}')")
    assert list(one.positions) == [hit0 + 1]
    out["delete_and_id_ok"] = True
    if record and _improves(record_path, out["rows"]):
        with open(record_path + ".tmp", "w") as f:
            json.dump(out, f, indent=1)
        os.replace(record_path + ".tmp", record_path)
    progress(f"  poly-scale: COMPLETE at {len(st.batch) / 1e6:.0f}M "
             "polygons through the store facade")
    return out


if __name__ == "__main__":
    from geomesa_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = int(os.environ.get("POLY_SCALE_N", 200_000_000))
    out = run(n)
    print(json.dumps({"metric": "poly_scale_proof", **out}))
