"""End-to-end walkthrough of geomesa-tpu.

Run: ``python examples/demo.py``  (any JAX backend; TPU when available)

Covers the core workflow a GeoMesa user would recognize: define a
schema, ingest through a converter, query with ECQL, run analytics
(density / kNN / tube-select), inspect the query plan, and export —
plus the live streaming layer.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from geomesa_tpu.datastore import TpuDataStore  # noqa: E402
from geomesa_tpu.io.converters import converter_from_config

MS_2018 = 1514764800000
DAY = 86_400_000


def main():
    rng = np.random.default_rng(42)
    ds = TpuDataStore()

    # 1. schema (spec-string DSL; user data tunes the z3 interval)
    ds.create_schema(
        "gdelt", "actor:String:index=true,score:Double,dtg:Date,"
                 "*geom:Point;geomesa.z3.interval=week")

    # 2. converter ingest (CSV → transform expressions → columns)
    n = 200_000
    csv = "\n".join(
        f"actor{i % 50},{rng.uniform():.3f},{MS_2018 + int(rng.integers(14 * DAY))},"
        f"{rng.uniform(-75, -73):.5f},{rng.uniform(40, 42):.5f}"
        for i in range(n))
    conv = converter_from_config(ds.get_schema("gdelt"), {
        "type": "csv",
        "fields": [
            {"name": "actor", "transform": "$0"},
            {"name": "score", "transform": "toDouble($1)"},
            {"name": "dtg", "transform": "toLong($2)"},
            {"name": "geom", "transform": "point($3,$4)"},
        ],
    })
    ds.write("gdelt", conv.convert(csv))
    print(f"ingested {ds.get_count('gdelt'):,} features")

    # 3. ECQL query (planner picks the z3 index; hit set is exact)
    q = ("BBOX(geom,-74.5,40.5,-73.5,41.5) AND dtg DURING "
         "2018-01-03T00:00:00Z/2018-01-10T00:00:00Z AND score > 0.5")
    t0 = time.perf_counter()
    hits = ds.query("gdelt", q)
    print(f"query: {len(hits):,} hits in "
          f"{(time.perf_counter() - t0) * 1e3:.0f}ms")
    print(ds.explain("gdelt", q))

    # 4. analytics
    from geomesa_tpu.process.density import density_process
    grid = density_process(ds, "gdelt", q, (-75, 40, -73, 42), 256, 256)
    print(f"density grid: {grid.shape}, total weight {grid.sum():.0f}")

    from geomesa_tpu.process.knn import knn_process
    pos, dist = knn_process(ds, "gdelt", -74.0, 41.0, k=5)
    print(f"kNN: nearest 5 within {dist.max():.0f} m")

    from geomesa_tpu.process.tube import tube_select
    track = np.stack([np.linspace(-74.8, -73.2, 9),
                      np.linspace(40.2, 41.8, 9)], axis=1)
    times = MS_2018 + np.linspace(0, 7 * DAY, 9).astype(np.int64)
    sel = tube_select(ds, "gdelt", track, times,
                      buffer_m=5_000, time_buffer_ms=12 * 3_600_000)
    print(f"tube-select: {len(sel):,} features along the track")

    # 5. export (GeoJSON / Arrow)
    from geomesa_tpu.io.export import to_geojson
    fc = to_geojson(ds.query("gdelt", q, ))
    print(f"geojson export: {len(fc):,} bytes")
    table = ds.query_arrow_table("gdelt", q, dictionary_fields=("actor",))
    print(f"arrow export: {table.num_rows:,} rows, "
          f"{len(table.column_names)} columns")

    # 6. streaming layer (Kafka-analog live cache)
    from geomesa_tpu.stream import StreamDataStore
    live = StreamDataStore()
    live.create_schema("ships", "mmsi:String,dtg:Date,*geom:Point")
    for i in range(1_000):
        live.write("ships", f"v{i % 100}", {
            "mmsi": f"v{i % 100}", "dtg": MS_2018 + i,
            "geom": (float(rng.uniform(-74.5, -73.5)),
                     float(rng.uniform(40.5, 41.5)))})
    live.consume("ships")
    print(f"live cache: {len(live.query('ships', 'INCLUDE')):,} current "
          "vessels")

    # 7. multi-chip: the SAME facade over a device mesh — every index
    # builds sharded, scans run as collectives (psum/ppermute over ICI)
    import jax
    from geomesa_tpu.parallel import device_mesh
    if len(jax.devices()) > 1:
        dsm = TpuDataStore(mesh=device_mesh())
        dsm.create_schema(
            "gdelt", "actor:String:index=true,score:Double,dtg:Date,"
                     "*geom:Point;geomesa.z3.interval=week")
        dsm.write("gdelt", conv.convert(csv))
        hits_mesh = dsm.query("gdelt", q)
        print(f"mesh store ({len(jax.devices())} devices): "
              f"{len(hits_mesh):,} hits (single-chip store found "
              f"{len(ds.query('gdelt', q)):,})")

        # 8. SQL text front-end (the Spark-SQL user surface): st_* calls
        # rewrite to ECQL push-down predicates, aggregates vectorize
        from geomesa_tpu.sql import sql_query
        agg = sql_query(dsm, "SELECT actor, count(*) AS n, avg(score) "
                             "AS avg_s FROM gdelt GROUP BY actor "
                             "ORDER BY n DESC LIMIT 3")
        print("sql top actors:", list(zip(agg["actor"], agg["n"])))

        # 9. device-resident sketches: count-min Frequency over a
        # bbox+time window (per-shard partials psum-merged)
        from geomesa_tpu.process import stats_process
        f = stats_process(dsm, "gdelt", q, "Frequency(score)")
        print("frequency sketch non-zero cells:",
              int((f.table > 0).sum()))
    else:
        print("mesh store: single device visible — run under "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "JAX_PLATFORMS=cpu to demo the collectives")


if __name__ == "__main__":
    main()
