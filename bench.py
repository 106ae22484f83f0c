"""Benchmark: BASELINE configs 1 (Z3), 2 (Z2 OR), 3 (XZ2), 5 (kNN/tube)
+ Pallas health, all recurring so regressions anywhere are visible in
BENCH_r*.json (VERDICT r1 items 4/6).

Measured on one chip, GDELT/OSM/AIS-shaped synthetic data:

* **config 1 ingest**: vectorized Z3 SFC encode + device key sort,
  keys/sec/chip (the reference's write-path hot loop,
  Z3IndexKeySpace.toIndexKey — it claims >10k records/sec/node;
  docs/user/introduction.rst:26), plus chunked append-per-slice
  sustained ingest (the 1B-path streaming shape, docs/scale.md).
* **config 1 scan**: bbox+week query (plan + device seeks + fused
  candidate filter) single and 32-window batched.
* **config 2**: Z2 multi-bbox OR query (FilterSplitter disjunctions).
* **config 3**: XZ2 polygon intersects over 200k polygons.
* **config 5**: kNN and tube-select over 500k AIS-shaped points through
  the store facade (batched expanding rings / per-segment windows).
* **pallas**: Pallas-vs-XLA kernel timings + kernel health (a Mosaic
  failure raises).

Prints ONE JSON line with the primary metric (ingest keys/sec/chip);
vs_baseline is the ratio to the reference's 10k records/sec/node claim.
"""

import json
import os
import time

import numpy as np


N = 16_000_000
SCAN_N = 4_000_000
MS_2018 = 1514764800000



def _median_time(fn, iters=5):
    """Median per-iteration wall time — robust to host stalls that
    would skew a mean."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    mid = len(times) // 2
    if len(times) % 2:
        return times[mid]
    return (times[mid - 1] + times[mid]) / 2


def _mem_probe() -> dict:
    """Memory footprint at stanza completion (ISSUE 9): the process
    peak host RSS (a cumulative high-water mark — stanzas run in a
    fixed order, so same-stanza comparisons across rounds are
    apples-to-apples) and total live device-resident bytes.  Both feed
    the regression gate's storage direction (lower is better), so a
    memory regression fails as loudly as a perf one."""
    out: dict = {}
    try:
        import resource
        out["peak_rss_mb"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
            1)   # linux ru_maxrss is KiB
    except Exception:
        pass
    try:
        import jax
        out["device_resident_bytes"] = int(sum(
            int(getattr(a, "nbytes", 0)) for a in jax.live_arrays()))
    except Exception:
        pass
    return out


def main():
    from geomesa_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    import geomesa_tpu  # noqa: F401  (enables x64)
    from geomesa_tpu.curve import TimePeriod, to_binned_time, z3_sfc
    from geomesa_tpu.index import Z3PointIndex

    rng = np.random.default_rng(42)
    # GDELT-shaped: world-wide events over two weeks
    x = rng.uniform(-180.0, 180.0, N)
    y = rng.uniform(-56.0, 72.0, N)
    t = rng.integers(MS_2018, MS_2018 + 14 * 86_400_000, N)

    sfc = z3_sfc(TimePeriod.WEEK)
    bins, offs = to_binned_time(t, TimePeriod.WEEK)

    xd = jax.device_put(jnp.asarray(x))
    yd = jax.device_put(jnp.asarray(y))
    od = jax.device_put(jnp.asarray(offs.astype(np.float64)))
    bd = jax.device_put(jnp.asarray(bins.astype(np.int32)))

    @jax.jit
    def ingest(xs, ys, os_, bs):
        z = sfc.index(xs, ys, os_)
        # variadic 2-key sort with the permutation as payload: ~7x faster
        # than lexsort+gather on TPU
        return jax.lax.sort(
            (bs, z, jnp.arange(z.shape[0], dtype=jnp.int32)),
            dimension=0, num_keys=2)

    # warmup/compile; completion is forced via a tiny device→host read
    _ = np.asarray(ingest(xd, yd, od, bd)[0][:1])

    ingest_dt = _median_time(
        lambda: np.asarray(ingest(xd, yd, od, bd)[0][:1]))
    ingest_rate = N / ingest_dt

    # scan: selective bbox + 5-day window
    index = Z3PointIndex.build(x[:SCAN_N], y[:SCAN_N], t[:SCAN_N],
                               period=TimePeriod.WEEK)
    box = (-80.0, 30.0, -60.0, 50.0)
    tlo, thi = MS_2018 + 2 * 86_400_000, MS_2018 + 7 * 86_400_000
    hits = index.query([box], tlo, thi)  # warm (compiles both phases)
    q_dt = _median_time(lambda: index.query([box], tlo, thi), iters=10)
    scan_rate = len(hits) / q_dt
    # index-resident points covered per second of query wall time (the
    # reference's "tens of millions of points in seconds" claim scale)
    scanned_rate = SCAN_N / q_dt

    # batched windows: 32 independent bbox+time queries in ONE dispatch
    # (the tube-select / kNN scan pattern; amortizes dispatch latency)
    qrng = np.random.default_rng(7)
    windows = []
    for _ in range(32):
        cx = float(qrng.uniform(-150, 150))
        cy = float(qrng.uniform(-40, 60))
        lo = MS_2018 + int(qrng.integers(0, 9)) * 86_400_000
        windows.append(([(cx - 3, cy - 3, cx + 3, cy + 3)],
                        lo, lo + 3 * 86_400_000))
    batched = index.query_many(windows)  # warm
    batched_dt = _median_time(lambda: index.query_many(windows))
    batched_hits = int(sum(len(b) for b in batched))

    # density histogram (auto: sorted-segment at this N; Pallas MXU
    # one-hot for small batches)
    from geomesa_tpu.ops.density import density_grid_auto
    import jax.numpy as jnp
    dmask = jnp.ones(N, dtype=bool)
    dw = jnp.ones(N, dtype=jnp.float32)
    grid = density_grid_auto(xd, yd, dw, dmask,
                             (-180.0, -90.0, 180.0, 90.0), 256, 128)
    _ = np.asarray(grid)  # warm

    def one_density():
        g = density_grid_auto(xd, yd, dw, dmask,
                              (-180.0, -90.0, 180.0, 90.0), 256, 128)
        _ = np.asarray(g[:1, :1])

    density_dt = _median_time(one_density)

    # -- chunked sustained ingest (the 1B-path streaming shape): seed
    # with the already-compiled 4M build shape, then append host slices
    # into sentinel padding — the host→device stream a 1B build uses
    # (docs/scale.md HBM budget).  First append warms the (capacity,
    # slice) compile bucket; the measured appends reuse it.
    CH = 2_000_000
    from geomesa_tpu.ops.search import gather_capacity
    chunk_idx = Z3PointIndex.build(x[:SCAN_N], y[:SCAN_N], t[:SCAN_N],
                                   period=TimePeriod.WEEK)
    a0 = SCAN_N
    # pre-size capacity for the whole stream so no growth (and no fresh
    # compile bucket) lands inside the measured region — a production 1B
    # build sizes its slices the same way (docs/scale.md)
    chunk_idx._grow_capacity(gather_capacity(a0 + 6 * CH))
    chunk_idx.append(x[a0:a0 + CH], y[a0:a0 + CH], t[a0:a0 + CH])  # warm
    # median of >=3 measured appends: single-shot captures conflate
    # host stalls with real regressions
    append_times = []
    for s in range(1, 5):
        lo, hi = a0 + s * CH, a0 + (s + 1) * CH
        t0 = time.perf_counter()
        chunk_idx.append(x[lo:hi], y[lo:hi], t[lo:hi])
        _ = np.asarray(chunk_idx.z[:1])  # force completion
        append_times.append(time.perf_counter() - t0)
    append_times.sort()
    chunked_dt = append_times[len(append_times) // 2]
    chunked_rate = CH / chunked_dt

    # -- config 2: Z2 multi-bbox OR (OSM traces / FilterSplitter ORs)
    from geomesa_tpu.index.z2 import Z2PointIndex
    z2 = Z2PointIndex.build(x[:SCAN_N], y[:SCAN_N])
    boxes2 = [(-80.0, 30.0, -70.0, 40.0), (0.0, 40.0, 10.0, 50.0),
              (110.0, -40.0, 125.0, -25.0)]
    z2_hits = z2.query(boxes2)  # warm
    z2_dt = _median_time(lambda: z2.query(boxes2), iters=10)
    # world heatmap straight from the sorted column (z-prefix boundary
    # seeks, one dispatch)
    _ = z2.density_world(256, 128)  # warm
    dw_dt = _median_time(lambda: z2.density_world(256, 128), iters=5)

    # -- config 3: XZ2 polygon intersects (OSM buildings)
    from geomesa_tpu.geometry.types import Polygon
    from geomesa_tpu.index.xz2 import XZ2Index
    prng = np.random.default_rng(11)
    NP_ = 100_000
    pcx = prng.uniform(-170, 170, NP_)
    pcy = prng.uniform(-80, 80, NP_)
    pw = prng.uniform(0.001, 0.05, NP_)
    t0 = time.perf_counter()
    polys = [Polygon([(a - d, b - d), (a + d, b - d),
                      (a + d, b + d), (a - d, b + d)])
             for a, b, d in zip(pcx, pcy, pw)]
    xz2 = XZ2Index.build(polys, g=12)
    xz2_build_s = time.perf_counter() - t0
    qpoly = Polygon([(-80.0, 30.0), (-60.0, 30.0), (-60.0, 50.0),
                     (-80.0, 50.0)])
    xz2_hits = xz2.query(qpoly, exact=False)  # warm
    xz2_dt = _median_time(lambda: xz2.query(qpoly, exact=False), iters=10)

    # -- config 5: kNN + tube-select through the store facade (AIS)
    from geomesa_tpu.datastore import TpuDataStore
    from geomesa_tpu.process.knn import knn_process
    from geomesa_tpu.process.tube import tube_select
    arng = np.random.default_rng(13)
    # same row count as the scan index so the store's z3/z2 builds reuse
    # the compiled 4M shapes (TPU compiles dominate bench wall time)
    NA = SCAN_N
    ds = TpuDataStore()
    ds.create_schema("ais", "dtg:Date,*geom:Point")
    ds.write("ais", {
        "dtg": arng.integers(MS_2018, MS_2018 + 7 * 86_400_000, NA),
        "geom": (arng.uniform(-75.0, -70.0, NA),
                 arng.uniform(38.0, 42.0, NA)),
    })
    knn_process(ds, "ais", -73.0, 40.0, 25)  # warm
    knn_dt = _median_time(
        lambda: knn_process(ds, "ais", -73.0, 40.0, 25), iters=3)
    tk = np.linspace(0, 1, 41)
    track = np.column_stack([-75.0 + 4.0 * tk, 38.5 + 3.0 * tk])
    track_t = (MS_2018 + (tk * 5 * 86_400_000)).astype(np.int64)
    tube_select(ds, "ais", track, track_t, 5_000.0, 3_600_000)  # warm
    tube_dt = _median_time(
        lambda: tube_select(ds, "ais", track, track_t, 5_000.0,
                            3_600_000), iters=3)

    # -- pallas: compiled-kernel timings vs XLA + health (loud Mosaic
    # regressions; VERDICT r1 weak #1/#2)
    from geomesa_tpu.ops.pallas_kernels import on_tpu, pallas_health
    pallas = dict(pallas_health())
    raw_ms: dict = {}   # unrounded medians — the tuning decision
    # must not quantize at 0.1ms (sub-ms kernels would all tie)

    def _rec(key, seconds):
        raw_ms[key] = seconds * 1e3
        pallas[key] = round(seconds * 1e3, 1)
    if on_tpu():
        from geomesa_tpu.ops.density import density_grid
        from geomesa_tpu.ops.pallas_kernels import density_grid_pallas
        NSMALL = 1_000_000
        xs, ys = xd[:NSMALL], yd[:NSMALL]
        ws = jnp.ones(NSMALL, jnp.float32)
        ms = jnp.ones(NSMALL, bool)
        env = (-180.0, -90.0, 180.0, 90.0)
        try:
            _ = np.asarray(density_grid_pallas(xs, ys, ws, ms, env,
                                               256, 128)[:1, :1])
            _rec("density_pallas_1m_ms", _median_time(
                lambda: np.asarray(density_grid_pallas(
                    xs, ys, ws, ms, env, 256, 128)[:1, :1])))
        except Exception as e:  # Mosaic failure must be visible
            pallas["density_pallas_error"] = repr(e)
        _ = np.asarray(density_grid(xs, ys, ws, ms, env, 256, 128)[:1, :1])
        _rec("density_xla_1m_ms", _median_time(
            lambda: np.asarray(density_grid(
                xs, ys, ws, ms, env, 256, 128)[:1, :1])))

        # z2 int-space mask: fused Pallas decode+box kernel vs the XLA
        # deinterleave + (N × R) broadcast (round-3 next #8 kernel #1)
        from geomesa_tpu.curve.zorder import deinterleave2
        from geomesa_tpu.ops.pallas_kernels import z2_mask_pallas
        from geomesa_tpu.curve.sfc import z2_sfc
        z2v = z2_sfc().index(xs, ys)
        ixy8 = np.stack([np.array([i << 27, i << 26, (i + 8) << 27,
                                   (i + 8) << 26], dtype=np.int32)
                         for i in range(8)])

        @jax.jit
        def _z2_mask_xla(zz, bx):
            ix, iy = deinterleave2(zz.astype(jnp.uint64))
            ix = ix.astype(jnp.int64)
            iy = iy.astype(jnp.int64)
            return ((ix[:, None] >= bx[None, :, 0])
                    & (iy[:, None] >= bx[None, :, 1])
                    & (ix[:, None] <= bx[None, :, 2])
                    & (iy[:, None] <= bx[None, :, 3])).any(axis=1)

        try:
            _ = np.asarray(z2_mask_pallas(z2v, ixy8)[:1])
            _rec("z2_mask_pallas_1m_ms", _median_time(
                lambda: np.asarray(z2_mask_pallas(z2v, ixy8)[:1])))
        except Exception as e:
            pallas["z2_mask_pallas_error"] = repr(e)
        _ = np.asarray(_z2_mask_xla(z2v, jnp.asarray(ixy8))[:1])
        _rec("z2_mask_xla_1m_ms", _median_time(
            lambda: np.asarray(_z2_mask_xla(
                z2v, jnp.asarray(ixy8))[:1])))

        # z3 int-space mask: fused Pallas decode+box+time kernel vs the
        # XLA deinterleave3 path — measured so the z3_scan gate's claim
        # is uniform with the others (round-4 VERDICT #6)
        from geomesa_tpu.curve.zorder import deinterleave3
        from geomesa_tpu.ops.pallas_kernels import z3_mask_pallas
        z3v = sfc.index(xs, ys, od[:NSMALL])
        tlo3 = jnp.zeros(NSMALL, jnp.int32)
        thi3 = jnp.full(NSMALL, (1 << 21) - 1, jnp.int32)
        ixy3 = np.stack([np.array([i << 17, i << 16, (i + 8) << 17,
                                   (i + 8) << 16], dtype=np.int32)
                         for i in range(8)])

        @jax.jit
        def _z3_mask_xla(zz, bx, lo, hi):
            ix, iy, it = deinterleave3(zz.astype(jnp.uint64))
            ix = ix.astype(jnp.int32)
            iy = iy.astype(jnp.int32)
            it = it.astype(jnp.int32)
            hit = ((ix[:, None] >= bx[None, :, 0])
                   & (iy[:, None] >= bx[None, :, 1])
                   & (ix[:, None] <= bx[None, :, 2])
                   & (iy[:, None] <= bx[None, :, 3])).any(axis=1)
            return hit & (it >= lo) & (it <= hi)

        try:
            _ = np.asarray(z3_mask_pallas(z3v, ixy3, tlo3, thi3)[:1])
            _rec("z3_mask_pallas_1m_ms", _median_time(
                lambda: np.asarray(z3_mask_pallas(
                    z3v, ixy3, tlo3, thi3)[:1])))
        except Exception as e:
            pallas["z3_mask_pallas_error"] = repr(e)
        _ = np.asarray(_z3_mask_xla(z3v, jnp.asarray(ixy3), tlo3,
                                    thi3)[:1])
        _rec("z3_mask_xla_1m_ms", _median_time(
            lambda: np.asarray(_z3_mask_xla(
                z3v, jnp.asarray(ixy3), tlo3, thi3)[:1])))

        # 1-D histogram: MXU one-hot kernel vs XLA scatter-add (kernel #2)
        from geomesa_tpu.ops.pallas_kernels import hist1d_pallas
        hb = jnp.clip(((xs + 180.0) / 360.0 * 256).astype(jnp.int32),
                      0, 255)

        @jax.jit
        def _hist_xla(b, m):
            return jnp.zeros((256,), jnp.int64).at[b].add(
                jnp.where(m, 1, 0).astype(jnp.int64))

        try:
            _ = np.asarray(hist1d_pallas(hb, ws, ms, 256)[:1])
            _rec("hist1d_pallas_1m_ms", _median_time(
                lambda: np.asarray(hist1d_pallas(hb, ws, ms,
                                                 256)[:1])))
            # the kernel just ran successfully — record it on the gate
            # (its integrations would otherwise report 'untried' here)
            from geomesa_tpu.ops.pallas_kernels import GATES
            GATES["hist1d"].ok = True
        except Exception as e:
            pallas["hist1d_pallas_error"] = repr(e)
        _ = np.asarray(_hist_xla(hb, ms)[:1])
        _rec("hist1d_xla_1m_ms", _median_time(
            lambda: np.asarray(_hist_xla(hb, ms)[:1])))

        # measured wins govern the gates of THIS process from here on
        # (.pallas_tuning.json is read again only by explicit
        # apply_tuning callers, never at import)
        from geomesa_tpu.ops.pallas_kernels import record_tuning

        def _win(p_key, x_key):
            # RAW medians, not the 0.1ms-rounded report values: the
            # disable decision must not quantize (sub-ms kernels would
            # all tie at 1.0)
            p, q = raw_ms.get(p_key), raw_ms.get(x_key)
            if p is None or q is None or p <= 0:
                return None
            return round(q / p, 3)

        wins = {
            "density": _win("density_pallas_1m_ms", "density_xla_1m_ms"),
            "z2_scan": _win("z2_mask_pallas_1m_ms", "z2_mask_xla_1m_ms"),
            "z3_scan": _win("z3_mask_pallas_1m_ms", "z3_mask_xla_1m_ms"),
            "hist1d": _win("hist1d_pallas_1m_ms", "hist1d_xla_1m_ms"),
        }
        record_tuning({k: v for k, v in wins.items() if v is not None})
        pallas["measured_wins"] = wins
        # refresh health after the compiled runs above
        pallas.update(pallas_health())
    pallas["active"] = bool(pallas.get("z3_scan_ok") is not False
                            and pallas.get("z2_scan_ok") is not False
                            and pallas.get("hist1d_ok") is not False
                            and pallas["on_tpu"])

    scale = _guarded_stanza(_scale_stanza)
    compaction = _guarded_stanza(_compaction_stanza)
    stats_pd = _guarded_stanza(_stats_pushdown_stanza)
    xz3_scale = _guarded_stanza(_xz3_scale_stanza)
    obs_stanza = _guarded_stanza(_obs_stanza)
    heat_stanza = _guarded_stanza(_heat_stanza)
    arrow_stanza = _guarded_stanza(_arrow_stanza)
    lint_stanza = _guarded_stanza(_lint_stanza)
    resilience_stanza = _guarded_stanza(_resilience_stanza)
    serving_stanza = _guarded_stanza(_serving_stanza)
    pyramid_stanza = _guarded_stanza(_pyramid_stanza)
    planning_stanza = _guarded_stanza(_planning_stanza)
    slo_stanza = _guarded_stanza(_slo_stanza)
    full = {
        "metric": "z3_ingest_keys_per_sec_per_chip",
        "value": round(ingest_rate),
        "unit": "keys/sec",
        "vs_baseline": round(ingest_rate / 10_000.0, 2),
        "extra": {
            "n_points": N,
            "bbox_time_scan_features_per_sec": round(scan_rate),
            "scan_points_covered_per_sec": round(scanned_rate),
            "scan_hits": int(len(hits)),
            "batched_windows_per_sec": round(32 / batched_dt, 1),
            "batched_window_hits": batched_hits,
            "density_256x128_ms": round(density_dt * 1e3, 1),
            "chunked_append_keys_per_sec": round(chunked_rate),
            "chunked_total_rows": int(chunk_idx._n_rows
                                      if hasattr(chunk_idx, "_n_rows")
                                      else 8 * CH),
            "z2_or3_ms": round(z2_dt * 1e3, 1),
            "z2_or3_hits": int(len(z2_hits)),
            "density_world_zprefix_ms": round(dw_dt * 1e3, 1),
            "xz2_build_s": round(xz2_build_s, 2),
            "xz2_query_ms": round(xz2_dt * 1e3, 2),
            "xz2_candidates": int(len(xz2_hits)),
            "knn25_4m_ms": round(knn_dt * 1e3, 1),
            "tube40_4m_ms": round(tube_dt * 1e3, 1),
            "pallas": pallas,
            "scale": scale,
            "compaction": compaction,
            "stats_pushdown": stats_pd,
            "xz3_scale": xz3_scale,
            "obs": obs_stanza,
            "heat": heat_stanza,
            "arrow": arrow_stanza,
            "lint": lint_stanza,
            "resilience": resilience_stanza,
            "serving": serving_stanza,
            "pyramid": pyramid_stanza,
            "planning": planning_stanza,
            "slo": slo_stanza,
            "device": str(jax.devices()[0]),
        },
    }
    # Full detail survives in a FILE; the driver only retains the last
    # ~2,000 chars of stdout, which the round-4 full blob outgrew
    # (BENCH_r04 parsed: null — round-4 VERDICT weak #1).  The LAST
    # stdout line is therefore a compact summary, bounded well under the
    # tail window, carrying the primary metric plus per-config medians,
    # pallas wins, and scale POINTERS (record file + headline rows/rates
    # only — never the nested records themselves).
    compact = _compact_summary(full)
    # regression gate (round-5 VERDICT: silent median dips): compare
    # the compact record — the schema every BENCH_r*.json captures —
    # against the newest prior round, log loudly, and RECORD the list
    regressions = _regression_gate(compact)
    # arrow acceptance-gate failures (byte-exactness / 50x) count as
    # regressions too — the stanza records them without killing the
    # run, and here they become part of the failure signal
    for f in (arrow_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "arrow.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    # resilience acceptance-gate failures (deadline-overshoot pin,
    # shed behavior) fail the run the same way (ISSUE 16)
    for f in (resilience_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "resilience.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    # serving acceptance-gate failures (fused >= 3x serial, zero warm
    # recompiles, real fan-in) fail the run the same way (ISSUE 17)
    for f in (serving_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "serving.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    # pyramid acceptance-gate failures (>= 20x warm speedup, <50ms
    # warm tile p99, zero recompiles, bit-exactness) likewise
    # (ISSUE 18)
    for f in (pyramid_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "pyramid.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    # planning acceptance-gate failures (sketch-fed mispredict p95,
    # exactly-once bit-exact replans, zero warm recompiles) likewise
    # (ISSUE 19)
    for f in (planning_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "planning.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    # SLO-plane acceptance-gate failures (>= 90% attributed wall,
    # <= 5% hook overhead, zero warm recompiles, resolvable exemplar)
    # likewise (ISSUE 20)
    for f in (slo_stanza or {}).get("gate_failures", ()):
        regressions.append({"metric": "slo.gate", "prior": None,
                            "current": None, "ratio": None,
                            "detail": f})
    full["regressions"] = regressions
    compact["extra"]["regressions"] = len(regressions)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_FULL.json"), "w") as f:
        json.dump(full, f, indent=1)
    print(json.dumps(compact, separators=(",", ":")))


def _compact_summary(full: dict) -> dict:
    """The driver-facing last line: same top-level schema as the full
    record, `extra` reduced to scalars + scale pointers.  Must stay
    under ~1,800 chars serialized; past that it hard-trims to a 3-field
    core (pinned by tests/test_review_fixes.py) so a future field can
    never re-break the driver capture."""
    ex = full["extra"]
    scale = ex.get("scale", {})

    def _scale_ptr(key: str) -> dict:
        rec = scale.get(key)
        if not isinstance(rec, dict):
            return {"absent": True}
        out = {}
        for k in ("rows", "ingest_rows_per_sec", "generations", "tiers",
                  "oracle_exact", "knn_measured_at_rows", "knn25_warm_ms",
                  "query_warm_ms", "density_1b_ms", "attr_query_warm_ms",
                  "density_oracle_exact", "attr_oracle_exact",
                  "stats_pushdown_cold_ms", "stats_pushdown_warm_ms",
                  "stats_pushdown_speedup",
                  "stats_materialized_fallbacks"):
            if k in rec:
                v = rec[k]
                if isinstance(v, list):
                    v = v[:3]
                out[k] = v
        return out

    compact = {
        "metric": full["metric"],
        "value": full["value"],
        "unit": full["unit"],
        "vs_baseline": full["vs_baseline"],
        "extra": {
            "bbox_scan_feats_per_sec": ex["bbox_time_scan_features_per_sec"],
            "batched_windows_per_sec": ex["batched_windows_per_sec"],
            "chunked_append_keys_per_sec": ex["chunked_append_keys_per_sec"],
            "density_256x128_ms": ex["density_256x128_ms"],
            "z2_or3_ms": ex["z2_or3_ms"],
            "xz2_query_ms": ex["xz2_query_ms"],
            "knn25_4m_ms": ex["knn25_4m_ms"],
            "tube40_4m_ms": ex["tube40_4m_ms"],
            "pallas_wins": (ex.get("pallas") or {}).get("measured_wins"),
            "pallas_active": (ex.get("pallas") or {}).get("active"),
            "compaction": {
                k: (ex.get("compaction") or {}).get(k)
                for k in ("generations_before", "generations_after",
                          "warm_speedup", "density_warm_ms",
                          "recompiles")
                if k in (ex.get("compaction") or {})},
            "stats_pushdown": {
                k: (ex.get("stats_pushdown") or {}).get(k)
                for k in ("cold_ms", "warm_ms", "warm_speedup",
                          "materialized_fallbacks", "recompiles")
                if k in (ex.get("stats_pushdown") or {})},
            "xz3_scale": {
                k: (ex.get("xz3_scale") or {}).get(k)
                for k in ("ingest_rows_per_sec", "query_warm_ms",
                          "oracle_exact", "recompiles")
                if k in (ex.get("xz3_scale") or {})},
            "obs": {
                k: (ex.get("obs") or {}).get(k)
                for k in ("overhead_pct", "warm_recompiles",
                          "trace_spans")
                if k in (ex.get("obs") or {})},
            "heat": {
                k: (ex.get("heat") or {}).get(k)
                for k in ("ingest_overhead_pct", "query_overhead_pct",
                          "tracked_entries")
                if k in (ex.get("heat") or {})},
            "arrow": {
                k: (ex.get("arrow") or {}).get(k)
                for k in ("arrow_feats_per_sec",
                          "materialize_feats_per_sec", "lift_vs_r05",
                          "byte_exact", "warm_recompiles")
                if k in (ex.get("arrow") or {})},
            "resilience": {
                k: (ex.get("resilience") or {}).get(k)
                for k in ("overshoot_p99", "shed_ms",
                          "timeout_gate_ok", "warm_recompiles")
                if k in (ex.get("resilience") or {})},
            "serving": {
                k: (ex.get("serving") or {}).get(k)
                for k in ("serving_qps", "serial_qps", "fused_speedup",
                          "fanin", "warm_recompiles")
                if k in (ex.get("serving") or {})},
            "pyramid": {
                k: (ex.get("pyramid") or {}).get(k)
                for k in ("pyramid_speedup", "tile_warm_p99_ms",
                          "bit_exact", "fault_exact",
                          "warm_recompiles")
                if k in (ex.get("pyramid") or {})},
            "planning": {
                k: (ex.get("planning") or {}).get(k)
                for k in ("sketch_p95_ratio_dist",
                          "heuristic_p95_ratio_dist",
                          "replan_count", "warm_recompiles")
                if k in (ex.get("planning") or {})},
            "slo": {
                k: (ex.get("slo") or {}).get(k)
                for k in ("residual_pct", "overhead_pct",
                          "exemplar_resolves", "warm_recompiles")
                if k in (ex.get("slo") or {})},
            "scale_1b": _scale_ptr("recorded_1b"),
            "store_1b": _scale_ptr("store_recorded"),
            "store_live": _scale_ptr("store_live"),
            # storage direction (ISSUE 9): peak RSS is a process
            # high-water mark so the final probe covers every stanza,
            # but device residency is a point sample — take the MAX
            # across the per-stanza probes so a stanza that ballooned
            # HBM and freed it before the end still gates; the FULL
            # record keeps the per-stanza values for attribution
            "mem": _mem_highwater(ex),
            "full_record": "BENCH_FULL.json",
            "device": ex["device"],
        },
    }
    blob = json.dumps(compact, separators=(",", ":"))
    if len(blob) > 1800:  # hard-trim rather than re-break the capture
        compact["extra"] = {
            "chunked_append_keys_per_sec": ex["chunked_append_keys_per_sec"],
            "pallas_wins": (ex.get("pallas") or {}).get("measured_wins"),
            "full_record": "BENCH_FULL.json",
        }
    return compact


def _scale_stanza() -> dict:
    """Scale-proof evidence (round-3 next #7): the RECORDED 500M
    single-chip run (SCALE_r03.json, produced by scale_proof.py — too
    long to rerun inside every bench) plus a LIVE smaller streaming
    build each round so the lean generational path has a recurring
    regression number.  ``SCALE_LIVE_N=0`` skips the live run."""
    out: dict = {}
    here = os.path.dirname(os.path.abspath(__file__))
    for key, fns in (
            ("recorded_500m", ["SCALE_r03.json"]),
            ("store_recorded", ["STORE_SCALE_r05.json",
                                "STORE_SCALE_r04.json"]),
            ("recorded_1b", ["SCALE_1B_r05.json",
                             "SCALE_1B_r04.json"])):
        for fn in fns:   # newest PARSEABLE round's record wins
            rec = os.path.join(here, fn)
            if os.path.exists(rec):
                try:
                    with open(rec) as f:
                        out[key] = json.load(f)
                except Exception as e:
                    # a truncated/corrupt newer record must not mask an
                    # older round's good one — keep looking; the error
                    # survives only if every candidate fails
                    out[f"{key}_error"] = repr(e)
                    continue
                out.pop(f"{key}_error", None)
                break
    n_live = int(os.environ.get("SCALE_LIVE_N", 32_000_000))
    if n_live:
        try:
            import scale_proof
            out["live"] = scale_proof.run(n_live, progress=lambda *_: None,
                                          record=False)
        except Exception as e:  # never kill the bench over the stanza
            out["live_error"] = repr(e)
    n_store = int(os.environ.get("STORE_SCALE_LIVE_N", 8_000_000))
    if n_store:
        try:
            import store_scale_proof
            out["store_live"] = store_scale_proof.run(
                n_store, slice_rows=1 << 22,
                progress=lambda *_: None, record=False)
        except Exception as e:
            out["store_live_error"] = repr(e)
    out.update(_mem_probe())
    return out


def _compaction_stanza() -> dict:
    """LSM lifecycle regression numbers: stream a many-generation lean
    build, measure cold density, compact, measure post-compaction
    density, then the WARM repeat (sealed-generation partial cache) —
    the generation-count and warm-speedup trends every future
    BENCH_*.json tracks.  ``COMPACT_BENCH_N=0`` skips."""
    import time

    import numpy as np

    from geomesa_tpu.index.z3_lean import LeanZ3Index

    n = int(os.environ.get("COMPACT_BENCH_N", 4_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu.obs import compile_count
        _c0 = compile_count()
        rng = np.random.default_rng(11)
        slots = 1 << 17
        ms0 = 1_514_764_800_000
        idx = LeanZ3Index(period="week", generation_slots=slots,
                          payload_on_device=False)
        t0 = time.perf_counter()
        step = slots  # one generation per slice — the LSM flush shape
        for lo in range(0, n, step):
            m = min(step, n - lo)
            idx.append(rng.uniform(-180, 180, m),
                       rng.uniform(-90, 90, m),
                       rng.integers(ms0, ms0 + 14 * 86_400_000, m))
        idx.block()
        out["rows"] = n
        out["ingest_s"] = round(time.perf_counter() - t0, 2)
        out["generations_before"] = len(idx.generations)
        box = [(-60.0, -30.0, 60.0, 30.0)]
        lo_t, hi_t = ms0 + 86_400_000, ms0 + 9 * 86_400_000
        t0 = time.perf_counter()
        cold = idx.density(box, lo_t, hi_t, (-180, -90, 180, 90),
                           256, 128)
        out["density_cold_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        t0 = time.perf_counter()
        stats = idx.compact()
        out["compact_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["merged_groups"] = stats["merged_groups"]
        out["generations_after"] = stats["generations"]
        # compaction invalidated the merged runs' partials — this call
        # re-seeds the cache over the compacted shape...
        t0 = time.perf_counter()
        seeded = idx.density(box, lo_t, hi_t, (-180, -90, 180, 90),
                             256, 128)
        out["density_compacted_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        # ...and the warm repeat re-scans only the live generation
        # (first warm call compiles the live-only shapes; time the
        # steady state)
        warm = idx.density(box, lo_t, hi_t, (-180, -90, 180, 90),
                           256, 128)
        t0 = time.perf_counter()
        warm = idx.density(box, lo_t, hi_t, (-180, -90, 180, 90),
                           256, 128)
        out["density_warm_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        out["warm_speedup"] = round(
            out["density_compacted_ms"]
            / max(out["density_warm_ms"], 1e-3), 1)
        out["grids_equal"] = bool(
            np.array_equal(cold, seeded) and np.array_equal(cold, warm))
        out["recompiles"] = int(compile_count() - _c0)
    except Exception as e:  # never kill the bench over the stanza
        out["error"] = repr(e)
    out.update(_mem_probe())
    return out


def _obs_stanza() -> dict:
    """Observability overhead + retrace budget (ISSUE 5): the batched-
    window query stanza measured with the default always-on sampler vs
    tracing disabled — the tracing tax must stay in low single-digit
    percent — plus the warm-repeat recompile count (must be 0: a warm
    lean query that recompiles is the silent TPU perf cliff the
    recompile tracker exists to catch).  ``OBS_BENCH_N=0`` skips."""
    import time

    import numpy as np

    n = int(os.environ.get("OBS_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu.config import clear_property, set_property
        from geomesa_tpu.index.z3_lean import LeanZ3Index
        from geomesa_tpu.obs import compile_count, recompile, tracer
        # a warm_recompiles of 0 is only meaningful when the listener
        # covers every compile (the counting_jit fallback is opt-in)
        out["recompile_listener"] = bool(recompile.installed())

        rng = np.random.default_rng(17)
        ms0 = 1_514_764_800_000
        slots = 1 << 18
        idx = LeanZ3Index(period="week", generation_slots=slots,
                          payload_on_device=False)
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            idx.append(rng.uniform(-180, 180, m),
                       rng.uniform(-90, 90, m),
                       rng.integers(ms0, ms0 + 14 * 86_400_000, m))
        idx.block()
        windows = []
        for i in range(8):
            cx, cy = -150.0 + 40.0 * (i % 8), -30.0 + 8.0 * i
            lo_t = ms0 + (i % 9) * 86_400_000
            windows.append(([(cx - 3, cy - 3, cx + 3, cy + 3)],
                            lo_t, lo_t + 3 * 86_400_000))
        idx.query_many(windows)          # warm/compile
        # warm-repeat recompile budget: repeated identical lean queries
        # must hit every executable cache
        c0 = compile_count()
        for _ in range(3):
            idx.query_many(windows)
        out["warm_recompiles"] = int(compile_count() - c0)
        traced_dt = _median_time(lambda: idx.query_many(windows),
                                 iters=7)
        # one query under an explicit root so the recorded trace shows
        # the full span tree (decompose / device / host under "query")
        from geomesa_tpu.obs import span as obs_span
        with obs_span("query", bench=True):
            idx.query_many(windows)
        ring = tracer.ring
        if ring is not None:
            last = ring.traces()[-1] if len(ring) else None
            out["trace_spans"] = len(last.spans) if last else 0
        set_property("geomesa.obs.enabled", False)
        try:
            idx.query_many(windows)      # settle
            untraced_dt = _median_time(lambda: idx.query_many(windows),
                                       iters=7)
        finally:
            clear_property("geomesa.obs.enabled")
        out["query_traced_ms"] = round(traced_dt * 1e3, 2)
        out["query_untraced_ms"] = round(untraced_dt * 1e3, 2)
        out["overhead_pct"] = round(
            (traced_dt / max(untraced_dt, 1e-9) - 1.0) * 100.0, 2)
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    out.update(_mem_probe())
    return out


def _heat_stanza() -> dict:
    """Heat-tracking + write-span overhead (ISSUE 12): the warm lean
    STORE ingest path (datastore writes — the full write-span tree:
    encode / index append / seal / spill / observe) and the warm query
    path, each measured with the workload instrumentation at defaults
    (heat tracking + tracing on) vs fully off.  The acceptance budget
    is ≤ 5% on both; the regression gate treats the ``*_overhead_pct``
    leaves as lower-is-better.  ``HEAT_BENCH_N=0`` skips."""
    import time

    import numpy as np

    n = int(os.environ.get("HEAT_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu.config import clear_property, set_property
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.obs import heat_tracker

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 18
        spec = ("dtg:Date,*geom:Point;geomesa.index.profile=lean,"
                f"geomesa.lean.generation.slots={slots},"
                "geomesa.lean.compaction.factor=0")
        q = [(-60.0, -30.0, 60.0, 30.0)]
        windows = [(q, ms0 + i * day, ms0 + (i + 3) * day)
                   for i in range(8)]

        def build_and_query(name: str, rows: int):
            rng = np.random.default_rng(23)
            ds = TpuDataStore(user="heat-bench")
            ds.create_schema(name, spec)
            t0 = time.perf_counter()
            for lo in range(0, rows, slots):
                m = min(slots, rows - lo)
                ds.write(name, {
                    "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                    "geom": (rng.uniform(-180, 180, m),
                             rng.uniform(-90, 90, m))})
            idx = ds._store(name)._indexes["z3"]
            idx.block()
            ingest_s = time.perf_counter() - t0
            idx.query_many(windows)         # warm/compile
            q_ms = _median_time(lambda: idx.query_many(windows),
                                iters=7) * 1e3
            return ingest_s, q_ms, len(idx.generations)

        # untimed warm-up: compile the append/scan programs once, so
        # the on-vs-off comparison measures the instrumentation tax,
        # not which run happened to pay the compiles
        build_and_query("hb_warm", min(n, 2 * slots))
        on_s, on_q_ms, gens = build_and_query("hb_on", n)
        set_property("geomesa.obs.heat.enabled", False)
        set_property("geomesa.obs.enabled", False)
        try:
            off_s, off_q_ms, _ = build_and_query("hb_off", n)
        finally:
            clear_property("geomesa.obs.heat.enabled")
            clear_property("geomesa.obs.enabled")
        out["rows"] = n
        out["generations"] = gens
        out["tracked_entries"] = len(heat_tracker)
        out["ingest_on_s"] = round(on_s, 3)
        out["ingest_off_s"] = round(off_s, 3)
        out["ingest_overhead_pct"] = round(
            (on_s / max(off_s, 1e-9) - 1.0) * 100.0, 2)
        out["query_on_ms"] = round(on_q_ms, 2)
        out["query_off_ms"] = round(off_q_ms, 2)
        out["query_overhead_pct"] = round(
            (on_q_ms / max(off_q_ms, 1e-9) - 1.0) * 100.0, 2)
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    out.update(_mem_probe())
    return out


#: BENCH_r05's recorded bbox_scan_feats_per_sec — the row-wise
#: materialization wall the Arrow-native result path (ISSUE 14) is
#: gated against: the warm streamed query must clear >= 50x this
_R05_MATERIALIZE_FEATS_PER_SEC = 88_763.0


def _arrow_stanza() -> dict:
    """Arrow-native materialization gate (ISSUE 14).

    BENCH_r05's 88,763 feats/sec (``bbox_scan_feats_per_sec``) was
    MATERIALIZE-bound — per-row feature ids and Python objects, not
    the scan, set the rate.  The stanza measures a warm wide-bbox
    query streamed through ``store.query_arrow`` and splits its wall
    time against the same query run positions-only, so the
    materialization throughput (rows through gather+encode per second)
    is measured apples-to-apples against the r05 wall:

    * ``arrow_feats_per_sec`` — end-to-end (scan + stream) serving
      rate, the recurring trend line in the regression gate
      (higher-better);
    * ``materialize_feats_per_sec`` — hits over (stream − scan) time;
      the gate asserts >= 50x the r05 baseline, i.e. result
      construction is no longer the bottleneck (the scan is again —
      exactly what ROADMAP item 2 asked for);
    * plus a BYTE-EXACT check of the streamed IPC blob against the
      row-wise ``query_result().batch`` encoded chunk-by-chunk with
      the same schema and shared delta dictionaries (a selective
      bbox+time query with a dictionary-encoded attribute), and a
      zero-recompile warm-repeat budget (the device payload gather
      pads into compile buckets).

    ``ARROW_BENCH_N=0`` skips."""
    import io

    import numpy as np

    n = int(os.environ.get("ARROW_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        import pyarrow as pa

        from geomesa_tpu.arrow.schema import encode_record_batch
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.obs import compile_count

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 18
        rng = np.random.default_rng(29)
        spec = ("name:String,score:Double,dtg:Date,*geom:Point;"
                "geomesa.index.profile=lean,"
                f"geomesa.lean.generation.slots={slots},"
                "geomesa.lean.compaction.factor=0")
        ds = TpuDataStore(user="arrow-bench")
        ds.create_schema("ab", spec)
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("ab", {
                "name": np.array(["ais", "gdelt", "osm"], dtype=object)[
                    rng.integers(0, 3, m)],
                "score": rng.uniform(0, 100, m),
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m))})
        ds._store("ab")._indexes["z3"].block()
        chunk = 262_144
        wide = "BBOX(geom,-175,-85,175,85)"

        def drain():
            return sum(rb.num_rows
                       for rb in ds.query_arrow("ab", wide,
                                                chunk_rows=chunk,
                                                dictionary_fields=()))

        def scan_only():
            ds._query_result_ex("ab", wide, materialize=False)

        def _min_time(fn, iters=5):
            # best-of-N, not median: the materialize rate is a
            # DIFFERENCE of two timings, and box contention inflates
            # both sides asymmetrically — min is the standard
            # de-noised microbenchmark estimator for each half
            best = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        hits = drain()                       # warm/compile both halves
        scan_only()
        out["rows"] = n
        out["hits"] = int(hits)
        c0 = compile_count()
        arrow_dt = _min_time(drain, iters=5)
        scan_dt = _min_time(scan_only, iters=5)
        out["warm_recompiles"] = int(compile_count() - c0)
        out["arrow_feats_per_sec"] = round(hits / arrow_dt)
        out["scan_ms"] = round(scan_dt * 1e3, 1)
        out["stream_ms"] = round(arrow_dt * 1e3, 1)
        mat_dt = max(arrow_dt - scan_dt, 1e-9)
        out["materialize_feats_per_sec"] = round(hits / mat_dt)
        out["lift_vs_r05"] = round(
            out["materialize_feats_per_sec"]
            / _R05_MATERIALIZE_FEATS_PER_SEC, 1)
        out["target_50x"] = bool(out["lift_vs_r05"] >= 50.0)
        out["scan_bound_again"] = bool(scan_dt > mat_dt)

        # row-wise reference rate: the old materializing path
        # (positions → LeanBatch.take per chunk → per-row feature ids)
        def rowwise():
            res = ds.query_result("ab", wide)
            st = ds._store("ab")
            total = 0
            for s in range(0, len(res.positions), chunk):
                total += len(st.batch.take(res.positions[s:s + chunk]))
            return total

        rowwise()                            # warm
        row_dt = _median_time(rowwise, iters=3)
        out["rowwise_feats_per_sec"] = round(hits / row_dt)
        out["speedup_vs_rowwise_e2e"] = round(
            row_dt / max(arrow_dt, 1e-9), 2)

        # byte-exact parity on a selective bbox+time query WITH a
        # delta-dictionary attribute: streamed IPC blob vs the
        # row-wise batch encoded chunk-by-chunk, same schema + shared
        # DictionaryState accumulations
        sel = ("BBOX(geom,-60,-30,60,30) AND dtg DURING "
               "2018-01-02T00:00:00Z/2018-01-09T00:00:00Z")
        stream = ds.query_arrow("ab", sel, chunk_rows=65_536,
                                dictionary_fields=("name",))
        schema = stream.schema
        got = stream.to_ipc_bytes()
        res = ds.query_result("ab", sel)
        st = ds._store("ab")
        sink = io.BytesIO()
        writer = pa.ipc.new_stream(
            sink, schema,
            options=pa.ipc.IpcWriteOptions(emit_dictionary_deltas=True))
        dicts: dict = {}
        for s in range(0, len(res.positions), 65_536):
            fb = st.batch.take(res.positions[s:s + 65_536])
            writer.write_batch(encode_record_batch(fb, schema, dicts))
        writer.close()
        out["parity_hits"] = int(len(res.positions))
        out["byte_exact"] = bool(got == sink.getvalue())
        out["ipc_bytes"] = len(got)
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # the acceptance gate runs OUTSIDE the try (review: an assert
    # swallowed by the stanza's blanket except could never fail a run)
    # and fails the bench the way this bench fails things — a loud
    # line plus a recorded entry main() folds into `regressions`
    failures = []
    if not out.get("byte_exact", False):
        failures.append("arrow stream is not byte-exact vs the "
                        "row-wise encoding")
    if not out.get("target_50x", False):
        failures.append(
            f"materialize_feats_per_sec "
            f"{out.get('materialize_feats_per_sec')} < 50x the r05 "
            f"baseline {_R05_MATERIALIZE_FEATS_PER_SEC}")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH ARROW GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


def _guarded_stanza(fn) -> dict:
    """Every stanza RECORDS its failure rather than killing the bench:
    the stanzas' inner try/excepts cover their measured sections, but
    an exception before them (import, setup, env parsing) previously
    propagated and took the whole record with it (ISSUE 16
    satellite)."""
    try:
        out = fn()
    except Exception as e:  # noqa: BLE001 — the record IS the signal
        return {"error": repr(e)}
    if not isinstance(out, dict):
        return {"error": f"stanza returned {type(out).__name__}"}
    return out


def _resilience_stanza() -> dict:
    """Deadline + admission acceptance gate (ISSUE 16): a warm lean
    query given a timeout below its runtime must terminate within
    1.25x the deadline (the cooperative-cancellation pin documented in
    docs/resilience.md — yield points between generation scans bound
    the overshoot to one dispatch), and an over-budget request must
    shed as Backpressure after about the configured queue wait, never
    hang.  ``RESILIENCE_BENCH_N=0`` skips."""
    import numpy as np

    n = int(os.environ.get("RESILIENCE_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu import config as gm_config
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.obs import compile_count
        from geomesa_tpu.resilience import Backpressure, admission_gate

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 16
        rng = np.random.default_rng(31)
        spec = ("dtg:Date,*geom:Point;"
                "geomesa.index.profile=lean,"
                f"geomesa.lean.generation.slots={slots},"
                "geomesa.lean.compaction.factor=0")
        ds = TpuDataStore(user="resilience-bench")
        ds.create_schema("rb", spec)
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("rb", {
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m))})
        idx = ds._store("rb")._indexes["z3"]
        idx.block()
        # per-generation dispatch granularity: production fuses the
        # same-size generations into one batched program (good for
        # throughput, but then the whole scan is a single
        # uninterruptible dispatch and the cooperative pin is
        # unmeasurable); the gate measures the cancellation machinery,
        # so force one dispatch per generation (~31 yield points)
        idx.BATCH_SCAN_BUDGET = 1
        # a SELECTIVE query keeps the scan the long pole: the host
        # recheck over already-gathered candidates must finish for
        # exactness (docs/resilience.md), so a low-selectivity query's
        # overshoot is dominated by that unskippable post-work, not by
        # the dispatch granularity the pin is about
        sel = "BBOX(geom,-170,-80,-150,-60)"
        ds.query_result("rb", sel)          # warm the scan
        warm_ms = _median_time(
            lambda: ds.query_result("rb", sel), iters=3) * 1e3
        out["query_warm_ms"] = round(warm_ms, 2)
        # deadline at half the warm runtime: the query WILL expire
        # mid-scan, and every iteration must still return (partial)
        # within the overshoot pin
        deadline_ms = max(1.0, warm_ms / 2.0)
        out["deadline_ms"] = round(deadline_ms, 2)
        overshoots = []
        c0 = compile_count()
        for _ in range(20):
            t0 = time.perf_counter()
            res = ds.query_result("rb", sel, timeout_ms=deadline_ms,
                                  partial_results=True)
            dt_ms = (time.perf_counter() - t0) * 1e3
            if res.timed_out:
                overshoots.append(dt_ms / deadline_ms)
        out["warm_recompiles"] = int(compile_count() - c0)
        out["timed_out_runs"] = len(overshoots)
        if overshoots:
            overshoots.sort()
            out["overshoot_p99"] = round(
                overshoots[min(len(overshoots) - 1,
                               int(0.99 * len(overshoots)))], 3)
        # shed latency: with the single admission slot held and a
        # short queue wait, the next query must come back Backpressure
        # in roughly queue_ms — a shed that takes seconds is a hang
        # with extra steps
        gm_config.set_property(
            "geomesa.resilience.admission.max.concurrent", 1)
        gm_config.set_property(
            "geomesa.resilience.admission.queue.ms", 20.0)
        try:
            tok = admission_gate.acquire("rb")
            t0 = time.perf_counter()
            try:
                ds.query_result("rb", sel)
                out["shed_error"] = "no Backpressure under overload"
            except Backpressure:
                out["shed_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 2)
            finally:
                tok.release()
        finally:
            gm_config.clear_property(
                "geomesa.resilience.admission.max.concurrent")
            gm_config.clear_property(
                "geomesa.resilience.admission.queue.ms")
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # the acceptance gate runs OUTSIDE the try (arrow-stanza
    # precedent: an assert swallowed by the stanza's blanket except
    # could never fail a run)
    failures = []
    if "error" not in out and not out.get("skipped"):
        p99 = out.get("overshoot_p99")
        ok = (p99 is not None and p99 <= 1.25
              and out.get("timed_out_runs", 0) > 0)
        out["timeout_gate_ok"] = bool(ok)
        if not ok:
            failures.append(
                f"deadline overshoot p99 {p99} exceeds the 1.25x pin "
                f"(timed_out_runs={out.get('timed_out_runs')})")
        if "shed_ms" not in out:
            failures.append(out.get("shed_error",
                                    "admission shed did not happen"))
        elif out["shed_ms"] > 1000.0:
            failures.append(
                f"shed latency {out['shed_ms']}ms — the queue wait is "
                "not bounded")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH RESILIENCE GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


def _serving_stanza() -> dict:
    """Fused serving plane acceptance gate (ISSUE 17): 64 concurrent
    clients of warm bbox/window queries submitted through the fusion
    scheduler must beat a serial solo baseline of the same workload by
    >= 3x throughput, with ZERO warm recompiles — the power-of-two
    capacity bucketing pins the compiled-shape set (docs/serving.md).
    ``SERVING_BENCH_N=0`` skips."""
    import numpy as np

    n = int(os.environ.get("SERVING_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    clients = int(os.environ.get("SERVING_BENCH_CLIENTS", 64))
    rounds = int(os.environ.get("SERVING_BENCH_ROUNDS", 4))
    out: dict = {}
    try:
        import threading
        from geomesa_tpu import config as gm_config
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.metrics import (SERVING_FUSED_BATCHES,
                                         SERVING_FUSED_REQUESTS, registry)
        from geomesa_tpu.obs import compile_count

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 16
        rng = np.random.default_rng(47)
        ds = TpuDataStore(user="serving-bench")
        ds.create_schema("sb", (
            "dtg:Date,*geom:Point;geomesa.index.profile=lean,"
            f"geomesa.lean.generation.slots={slots},"
            "geomesa.lean.compaction.factor=0"))
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("sb", {
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m))})
        ds._store("sb")._indexes["z3"].block()
        # the concurrent-dashboard workload: selective bbox+window
        # filters, distinct per client, all ONE compatibility key
        queries, windows = [], []
        for i in range(16):
            x = -170.0 + i * 1.5
            d = 1 + (i % 5)          # 2018-01-02 .. 2018-01-06 starts
            queries.append(
                f"BBOX(geom,{x},-60,{x + 3},-57) AND dtg DURING "
                f"2018-01-{d:02d}T00:00:00Z/2018-01-{d + 3:02d}"
                "T00:00:00Z")
            windows.append((((x, -60.0, x + 3.0, -57.0),),
                            ms0 + (d - 1) * day, ms0 + (d + 2) * day))
        # a wide coalesce window + full-size batches for the measured
        # phase: on a loaded CI box 2ms of linger can miss riders that
        # a real server's steady-state arrival stream would catch
        gm_config.set_property("geomesa.serving.fuse.window.ms", 10.0)
        gm_config.set_property("geomesa.serving.fuse.max.batch", clients)
        try:
            # warm EVERY pow2 capacity bucket the fused path can hit,
            # then the solo path, then one unrecorded concurrent round
            k = 1
            while k <= clients:
                ds._fused_windows_dispatch(
                    "sb", [windows[j % len(windows)] for j in range(k)])
                k <<= 1
            for q in queries:
                ds.query_result("sb", q)
            errors: list = []
            barrier = threading.Barrier(clients + 1)

            def client(i: int) -> None:
                try:
                    barrier.wait(timeout=60)
                    for r in range(rounds):
                        ds.query_fused(
                            "sb", queries[(i + r) % len(queries)],
                            tenant=f"t{i % 8}")
                except Exception as e:  # surfaced via the gate below
                    errors.append(repr(e))

            def fused_round() -> float:
                barrier.reset()
                threads = [threading.Thread(target=client, args=(i,),
                                            daemon=True)
                           for i in range(clients)]
                for t in threads:
                    t.start()
                barrier.wait(timeout=60)   # releases all clients at once
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                return time.perf_counter() - t0

            fused_round()                  # unrecorded warm round
            # serial solo baseline: the SAME total query count, one at
            # a time down the unfused path
            total = clients * rounds
            t0 = time.perf_counter()
            for j in range(total):
                ds.query_result("sb", queries[j % len(queries)])
            serial_dt = time.perf_counter() - t0
            c0 = compile_count()
            req0 = registry.counter(SERVING_FUSED_REQUESTS).count
            bat0 = registry.counter(SERVING_FUSED_BATCHES).count
            fused_dt = fused_round()
            out["warm_recompiles"] = int(compile_count() - c0)
            reqs = registry.counter(SERVING_FUSED_REQUESTS).count - req0
            bats = registry.counter(SERVING_FUSED_BATCHES).count - bat0
            out["serial_qps"] = round(total / serial_dt, 1)
            out["serving_qps"] = round(total / fused_dt, 1)
            out["fused_speedup"] = round(serial_dt / fused_dt, 2)
            out["fanin"] = round(reqs / bats, 2) if bats else 0.0
            out["fused_requests"] = int(reqs)
            out["fused_batches"] = int(bats)
            out["clients"] = clients
            if errors:
                out["client_errors"] = errors[:4]
        finally:
            gm_config.clear_property("geomesa.serving.fuse.window.ms")
            gm_config.clear_property("geomesa.serving.fuse.max.batch")
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # the acceptance gate runs OUTSIDE the try (resilience/arrow
    # precedent: an assert swallowed by the stanza's blanket except
    # could never fail a run)
    failures = []
    if "error" not in out and not out.get("skipped"):
        if out.get("client_errors"):
            failures.append(
                f"fused clients errored: {out['client_errors']}")
        speedup = out.get("fused_speedup")
        if speedup is None or speedup < 3.0:
            failures.append(
                f"fused throughput {out.get('serving_qps')} qps is not "
                f">= 3x the serial baseline {out.get('serial_qps')} qps "
                f"(speedup {speedup})")
        if out.get("warm_recompiles", 1) != 0:
            failures.append(
                f"warm fused path recompiled "
                f"{out.get('warm_recompiles')} time(s) — the capacity "
                "bucketing is leaking shapes")
        if out.get("fanin", 0) < 2.0:
            failures.append(
                f"fan-in {out.get('fanin')} — requests are not "
                "coalescing into shared batches")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH SERVING GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


def _pyramid_stanza() -> dict:
    """Density-pyramid acceptance gate (ISSUE 18): a warm whole-extent
    heatmap served off the sealed generations' cached pyramids must
    beat the cold direct sweep by >= 20x, warm single-tile p99 must
    stay under 50 ms with ZERO warm recompiles, and an interrupted
    build (``pyramid.build`` fault point) must leave results exact
    through the sweep fallback.  Bit-exactness of the pyramid-served
    grid vs the direct scan is asserted OUTSIDE the stanza's blanket
    except (the arrow-stanza precedent).  ``PYRAMID_BENCH_N=0``
    skips."""
    import numpy as np

    n = int(os.environ.get("PYRAMID_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    grids: dict = {}
    try:
        from geomesa_tpu import config as gm_config
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.metrics import PYRAMID_SERVE_HITS, registry
        from geomesa_tpu.obs import compile_count
        from geomesa_tpu.resilience import FaultInjected

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 16
        base = 512
        world = (-180.0, -90.0, 180.0, 90.0)
        rng = np.random.default_rng(53)
        ds = TpuDataStore(user="pyramid-bench")
        ds.create_schema("pyr", (
            "dtg:Date,*geom:Point;geomesa.index.profile=lean,"
            f"geomesa.lean.generation.slots={slots},"
            "geomesa.lean.compaction.factor=0"))
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("pyr", {
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m))})
        idx = ds._store("pyr")._indexes["z3"]
        idx.block()
        out["generations"] = len(idx.generations)

        def whole_extent():
            return idx.density([world], None, None, world, base, base)

        def cold():
            # the density-partial AND pyramid caches both short-circuit
            # repeat sweeps — drop them so every iteration pays the
            # full direct scan the cold path costs
            idx._density_cache.clear()
            idx._pyramid_cache.clear()
            return whole_extent()

        grids["direct"] = np.asarray(cold())
        cold_ms = _median_time(cold, iters=3) * 1e3
        out["cold_direct_ms"] = round(cold_ms, 2)
        idx._pyramid_cache.clear()
        t0 = time.perf_counter()
        out["builds"] = int(idx.build_pyramids(base=base))
        out["build_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        idx._density_cache.clear()
        h0 = registry.counter(PYRAMID_SERVE_HITS).count
        grids["pyramid"] = np.asarray(whole_extent())
        out["serve_hits"] = int(
            registry.counter(PYRAMID_SERVE_HITS).count - h0)
        warm_ms = _median_time(whole_extent, iters=5) * 1e3
        out["warm_pyramid_ms"] = round(warm_ms, 3)
        out["pyramid_speedup"] = round(cold_ms / max(warm_ms, 1e-3), 1)

        # warm single-tile latency at the finest pyramid-served zoom
        tiles = [(1, tx, ty) for tx in (0, 1) for ty in (0, 1)]
        for z, tx, ty in tiles:
            ds.density_tile("pyr", z, tx, ty)         # warm-up
        c0 = compile_count()
        lat = []
        for i in range(40):
            z, tx, ty = tiles[i % len(tiles)]
            t0 = time.perf_counter()
            ds.density_tile("pyr", z, tx, ty)
            lat.append((time.perf_counter() - t0) * 1e3)
        out["warm_recompiles"] = int(compile_count() - c0)
        lat.sort()
        out["tile_warm_p99_ms"] = round(
            lat[min(len(lat) - 1, int(0.99 * len(lat)))], 2)

        # interrupted build: exact through the fallback, then resumes
        idx._pyramid_cache.clear()
        gm_config.set_property("geomesa.resilience.fault.points",
                               "pyramid.build:2")
        try:
            try:
                idx.build_pyramids(base=base)
                out["fault_error"] = "fault point did not fire"
            except FaultInjected:
                idx._density_cache.clear()
                grids["interrupted"] = np.asarray(whole_extent())
        finally:
            gm_config.clear_property("geomesa.resilience.fault.points")
        out["resumed_builds"] = int(idx.build_pyramids(base=base))
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # acceptance gates OUTSIDE the try: a swallowed assert could never
    # fail a run
    failures = []
    if "error" not in out and not out.get("skipped"):
        out["bit_exact"] = bool(
            "pyramid" in grids
            and np.array_equal(grids["direct"], grids["pyramid"]))
        if not out["bit_exact"]:
            failures.append("pyramid-served grid != direct scan grid")
        out["fault_exact"] = bool(
            "interrupted" in grids
            and np.array_equal(grids["direct"], grids["interrupted"]))
        if not out["fault_exact"]:
            failures.append(
                out.get("fault_error",
                        "interrupted-build grid != direct scan grid"))
        if out.get("serve_hits", 0) <= 0:
            failures.append("warm heatmap never touched a pyramid")
        if out.get("pyramid_speedup", 0.0) < 20.0:
            failures.append(
                f"pyramid_speedup {out.get('pyramid_speedup')} < 20x "
                f"(cold {out.get('cold_direct_ms')}ms, warm "
                f"{out.get('warm_pyramid_ms')}ms)")
        if out.get("tile_warm_p99_ms", float("inf")) >= 50.0:
            failures.append(
                f"tile_warm_p99_ms {out.get('tile_warm_p99_ms')} "
                "breaches the 50ms interactive pin")
        if out.get("warm_recompiles", 1) != 0:
            failures.append(
                f"{out.get('warm_recompiles')} recompiles while "
                "serving warm tiles")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH PYRAMID GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


def _planning_stanza() -> dict:
    """Sketch-driven planning acceptance gate (ISSUE 19): on a SKEWED
    multi-generation lean store, sketch-fed estimates must pull the
    per-query ``plan.estimate.ratio`` distance-from-1 p95 at or below
    the heuristic baseline's (docs/planning.md); a skew-constructed
    mispredict must replan exactly once with bit-exact results; a
    well-predicted query must never replan; warm queries stay
    recompile-free through the adaptive machinery.
    ``PLANNING_BENCH_N=0`` skips."""
    import numpy as np

    n = int(os.environ.get("PLANNING_BENCH_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu import config as gm_config
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.metrics import PLAN_REPLANNED, registry
        from geomesa_tpu.obs import compile_count

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 16
        rng = np.random.default_rng(41)
        ds = TpuDataStore(user="planning-bench")
        ds.create_schema(
            "pb", "name:String:index=true,dtg:Date,*geom:Point;"
                  "geomesa.index.profile=lean,"
                  f"geomesa.lean.generation.slots={slots},"
                  "geomesa.lean.compaction.factor=0")
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            dense = int(m * 0.85)     # skew: hot cluster + sparse tail
            ds.write("pb", {
                "name": np.where(rng.uniform(size=m) < 0.9, "hot",
                                 "cold").astype(object),
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (np.concatenate(
                             [rng.uniform(-74.05, -74.0, dense),
                              rng.uniform(-80, -70, m - dense)]),
                         np.concatenate(
                             [rng.uniform(40.0, 40.05, dense),
                              rng.uniform(35, 45, m - dense)]))})
        ds._store("pb")._indexes["z3"].block()
        # the ratio workload: the hot cluster (heuristics underestimate
        # badly), a same-size cold box (over), a wide box, and a
        # time-restricted cluster slice
        queries = [
            "BBOX(geom,-74.06,39.99,-73.99,40.06)",
            "BBOX(geom,-77.06,42.99,-76.99,43.06)",
            "BBOX(geom,-79,36,-71,44)",
            ("BBOX(geom,-74.06,39.99,-73.99,40.06) AND dtg DURING "
             "2018-01-01T00:00:00Z/2018-01-04T00:00:00Z"),
        ]

        def _ratio_dists() -> list:
            dists = []
            for q in queries:
                r = ds.explain_analyze("pb", q).summary.get(
                    "estimate_ratio")
                if r and r > 0:
                    dists.append(max(float(r), 1.0 / float(r)))
            return sorted(dists)

        def _p(dists: list, q: float) -> float:
            return round(dists[min(len(dists) - 1,
                                   int(q * len(dists)))], 3)

        # A/B the estimate ladder with replanning OFF so the ratios
        # measure pure estimate quality, not the correction; pin the
        # size gate open so a reduced PLANNING_BENCH_N can't silently
        # turn the sketch arm into a second heuristic arm
        gm_config.set_property("geomesa.planning.estimator.min.rows", 0)
        gm_config.set_property("geomesa.planning.replan.threshold", 0.0)
        gm_config.set_property("geomesa.planning.estimator.enabled",
                               False)
        try:
            d = _ratio_dists()
            out["heuristic_p50_ratio_dist"] = _p(d, 0.5)
            out["heuristic_p95_ratio_dist"] = _p(d, 0.95)
            gm_config.set_property("geomesa.planning.estimator.enabled",
                                   True)
            d = _ratio_dists()
            out["sketch_p50_ratio_dist"] = _p(d, 0.5)
            out["sketch_p95_ratio_dist"] = _p(d, 0.95)
        finally:
            gm_config.clear_property("geomesa.planning.replan.threshold")
            gm_config.clear_property(
                "geomesa.planning.estimator.enabled")

        # warm latency + recompile discipline with the adaptive
        # machinery at its DEFAULTS (replan armed, estimator on; the
        # 2M store clears the size gate, so min.rows stays pinned at 0
        # only for reduced-N runs)
        hot = queries[0]
        for q in queries:
            ds.query_result("pb", q)        # warm every shape
        c0 = compile_count()
        times = sorted(_median_time(
            lambda: ds.query_result("pb", hot), iters=3)
            for _ in range(5))
        out["query_warm_p99_ms"] = round(times[-1] * 1e3, 2)
        out["warm_recompiles"] = int(compile_count() - c0)

        # mispredict drill: heuristics under the skew MUST replan
        # exactly once, bit-exact against the non-adaptive path; the
        # sketch-fed plan of the same query must never replan
        oracle = np.sort(ds.query_result("pb", hot).positions)
        gm_config.set_property("geomesa.planning.estimator.enabled",
                               False)
        gm_config.set_property("geomesa.planning.replan.threshold", 2.0)
        gm_config.set_property("geomesa.planning.replan.min.rows", 64)
        try:
            before = registry.counter(PLAN_REPLANNED).count
            adaptive = np.sort(ds.query_result("pb", hot).positions)
            out["replan_count"] = int(
                registry.counter(PLAN_REPLANNED).count - before)
            out["replan_exact"] = bool(np.array_equal(adaptive, oracle))
            gm_config.set_property("geomesa.planning.estimator.enabled",
                                   True)
            before = registry.counter(PLAN_REPLANNED).count
            ds.query_result("pb", hot)
            out["well_predicted_replans"] = int(
                registry.counter(PLAN_REPLANNED).count - before)
        finally:
            gm_config.clear_property(
                "geomesa.planning.estimator.enabled")
            gm_config.clear_property(
                "geomesa.planning.estimator.min.rows")
            gm_config.clear_property("geomesa.planning.replan.threshold")
            gm_config.clear_property("geomesa.planning.replan.min.rows")
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # acceptance gates OUTSIDE the try (arrow-stanza precedent)
    failures = []
    if "error" not in out and not out.get("skipped"):
        sp, hp = (out.get("sketch_p95_ratio_dist"),
                  out.get("heuristic_p95_ratio_dist"))
        if sp is None or hp is None or sp > hp * 1.05:
            failures.append(
                f"sketch-fed ratio-dist p95 {sp} not <= heuristic "
                f"baseline {hp}")
        if out.get("replan_count") != 1:
            failures.append(
                f"skew mispredict replanned {out.get('replan_count')} "
                "times, expected exactly 1")
        if not out.get("replan_exact"):
            failures.append("replanned results diverged from the "
                            "non-adaptive oracle")
        if out.get("well_predicted_replans", 1) != 0:
            failures.append(
                f"well-predicted query replanned "
                f"{out.get('well_predicted_replans')} times")
        if out.get("warm_recompiles", 1) != 0:
            failures.append(
                f"{out.get('warm_recompiles')} recompiles across warm "
                "adaptive queries")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH PLANNING GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


def _lint_stanza() -> dict:
    """gm-lint no-op guard (ISSUE 13 satellite): the static-analysis
    gate must pass on the benched tree AND stay importable with NO jax
    in the interpreter (cold CI shards run it without the accelerator
    stack) — verified in a subprocess so neither property can perturb
    the bench process, and cheap enough (~3 s, pure AST) to run every
    round."""
    import subprocess
    import sys
    out: dict = {}
    code = ("import sys\n"
            "from geomesa_tpu.analysis.__main__ import main\n"
            "rc = main(['--fail-on-new'])\n"
            "assert 'jax' not in sys.modules, 'analyzer imported jax'\n"
            "print('JAXFREE_OK')\n"
            "sys.exit(rc)\n")
    try:
        t0 = time.perf_counter()
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=120)
        out["clean"] = proc.returncode == 0
        # positive sentinel: a crash BEFORE the assert must not read
        # as the property having been verified
        out["jax_free"] = "JAXFREE_OK" in proc.stdout
        out["wall_s"] = round(time.perf_counter() - t0, 2)
        if proc.returncode != 0:
            out["tail"] = (proc.stdout + proc.stderr)[-500:]
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    return out


def _mem_highwater(extra: dict) -> dict:
    """The gated memory leaves: a fresh end-of-run probe, with
    ``device_resident_bytes`` raised to the max across every stanza's
    recorded probe (compact-summary comment)."""
    mem = _mem_probe()
    stanza_dev = [v.get("device_resident_bytes")
                  for v in extra.values() if isinstance(v, dict)]
    candidates = [int(x) for x in stanza_dev if x] + \
        [int(mem.get("device_resident_bytes", 0))]
    if any(candidates):
        mem["device_resident_bytes"] = max(candidates)
    return mem


#: relative tolerance band for the regression gate — run-to-run
#: wiggle is not a regression; beyond 20% in the BAD direction is
REGRESSION_TOLERANCE = 0.20

#: metric-name direction conventions: timings regress UP, rates/speedups
#: regress DOWN; the STORAGE direction (ISSUE 9) treats the per-stanza
#: memory leaves (`peak_rss_mb` host high-water mark,
#: `device_resident_bytes` live HBM) as lower-better too, so a memory
#: regression fails as loudly as a perf one; the OVERHEAD direction
#: (ISSUE 12) does the same for the `*_overhead_pct` instrumentation-
#: tax leaves (heat tracking + write spans must stay cheap); anything
#: else (hit counts, row totals, booleans) is not a direction and is
#: never flagged
#: the PLANNING direction (ISSUE 19): mispredict distance
#: (max(ratio, 1/ratio), 1.0 = perfect estimate) regresses UP
_LOWER_BETTER_SUFFIXES = ("_ms", "_s", "_rss_mb", "_resident_bytes",
                          "_overhead_pct", "_ratio_dist")
#: the SERVING direction (ISSUE 17) adds the fused-plane leaves: qps
#: and batch fan-in regress DOWN like any other rate
_HIGHER_BETTER_MARKS = ("per_sec", "speedup", "wins", "value",
                        "_qps", "fanin")


def _flat_scalars(rec, prefix: str = "", depth: int = 0) -> dict:
    """Dotted-key numeric leaves of a (possibly nested) record —
    booleans excluded, two levels deep (the compact-summary shape)."""
    out: dict = {}
    if not isinstance(rec, dict):
        return out
    for k, v in rec.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict) and depth < 2:
            out.update(_flat_scalars(v, f"{key}.", depth + 1))
    return out


def compare_bench_records(current: dict, prior: dict,
                          tolerance: float = REGRESSION_TOLERANCE
                          ) -> list:
    """Regression gate (round-5 VERDICT: two silent median dips with
    no tracking): every directional scalar metric shared by the
    current record and the most recent prior one is compared; a move
    beyond ``tolerance`` in the bad direction yields an entry
    ``{"metric", "prior", "current", "ratio"}`` (ratio > 1 = that many
    times worse).  Pure on its inputs so tests can drive it with
    synthetic records."""
    cur = _flat_scalars(current)
    old = _flat_scalars(prior)
    regs = []
    for name, prev in old.items():
        now = cur.get(name)
        if now is None:
            continue
        leaf = name.rsplit(".", 1)[-1]
        if leaf == "recompiles" or leaf.endswith("_recompiles"):
            # retrace budget: a stanza that compiled NOTHING last round
            # and compiles now is exactly the silent recompile cliff
            # (ISSUE 5) — prev == 0 flags at a finite sentinel ratio so
            # the record stays JSON-serializable
            if now <= prev:
                continue
            ratio = now / prev if prev > 0 else 999.0
        elif prev <= 0:
            continue
        elif leaf.endswith(_LOWER_BETTER_SUFFIXES):
            ratio = now / prev
        elif any(m in name for m in _HIGHER_BETTER_MARKS):
            # matched against the FULL dotted name: pallas win leaves
            # are kernel names under "pallas_wins." — leaf-only
            # matching would silently skip exactly those regressions
            ratio = prev / now if now > 0 else 999.0
        else:
            continue
        if ratio > 1.0 + tolerance:
            regs.append({"metric": name, "prior": prev, "current": now,
                         "ratio": round(ratio, 3)})
    regs.sort(key=lambda r: -r["ratio"])
    return regs


def _latest_prior_record() -> tuple[dict | None, str | None]:
    """The newest prior round's parsed compact record
    (``BENCH_r*.json`` is the driver's capture: ``{"n", "tail",
    "parsed"}``) — the regression gate's baseline."""
    import glob
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    best, best_n = None, -1
    for fn in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", fn)
        if not m:
            continue
        n = int(m.group(1))
        if n > best_n:
            best, best_n = fn, n
    if best is None:
        return None, None
    try:
        with open(best) as f:
            rec = json.load(f)
        parsed = rec.get("parsed")
        return (parsed if isinstance(parsed, dict) else None,
                os.path.basename(best))
    except Exception:
        return None, os.path.basename(best)


def _regression_gate(compact: dict) -> list:
    """Compare this run's compact record against the most recent
    BENCH_r*.json and LOG LOUDLY — silent dips are the failure mode
    this exists to kill."""
    prior, src = _latest_prior_record()
    if prior is None:
        return []
    regs = compare_bench_records(compact, prior)
    for r in regs:
        print(f"BENCH REGRESSION vs {src}: {r['metric']} "
              f"{r['prior']} -> {r['current']} "
              f"({r['ratio']}x worse)", flush=True)
    return regs


def _xz3_scale_stanza() -> dict:
    """Lean XZ3 (non-point WITH time) scale record — round-5 VERDICT:
    'lean XZ3 has no scale record'.  Streams envelope+timestamp slices
    through the generational (bin, code) runs, then measures a warm
    INTERSECTS-with-time query whose residual-filtered result is
    asserted ORACLE-EXACT (candidates must cover the oracle; the
    residual makes them exact — the planner's normal split).
    ``XZ3_SCALE_N=0`` skips."""
    import time

    import numpy as np

    n = int(os.environ.get("XZ3_SCALE_N", 2_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu.obs import compile_count
        _c0 = compile_count()
        from geomesa_tpu.geometry.types import Polygon
        from geomesa_tpu.index.xz2_lean import LeanXZ3Index

        rng = np.random.default_rng(23)
        cx = rng.uniform(-170, 170, n)
        cy = rng.uniform(-75, 75, n)
        hw = rng.uniform(0.001, 0.05, n)
        bbox = np.column_stack([cx - hw, cy - hw, cx + hw, cy + hw])
        t = rng.integers(MS_2018, MS_2018 + 28 * 86_400_000, n)
        idx = LeanXZ3Index(period="week",
                           generation_slots=1 << 20)
        step = 1 << 20
        t0 = time.perf_counter()
        for lo in range(0, n, step):
            sl = slice(lo, lo + step)
            idx.append_bboxes(bbox[sl], t[sl])
        idx.block()
        out["rows"] = n
        out["ingest_s"] = round(time.perf_counter() - t0, 2)
        out["ingest_rows_per_sec"] = round(n / max(
            time.perf_counter() - t0, 1e-9))
        out["generations"] = len(idx.generations)
        out["tiers"] = idx.tier_counts()
        qx0, qy0, qx1, qy1 = -80.0, 30.0, -60.0, 50.0
        t_lo = MS_2018 + 7 * 86_400_000
        t_hi = MS_2018 + 14 * 86_400_000
        poly = Polygon([(qx0, qy0), (qx1, qy0), (qx1, qy1),
                        (qx0, qy1)])
        cand = idx.query(poly, t_lo, t_hi)   # warm/compile
        t0 = time.perf_counter()
        cand = idx.query(poly, t_lo, t_hi)
        out["query_warm_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        # residual exactness: envelope-intersects ∧ time window (axis-
        # aligned rects, so envelope-intersect IS intersects)
        hit = ((bbox[:, 0] <= qx1) & (bbox[:, 2] >= qx0)
               & (bbox[:, 1] <= qy1) & (bbox[:, 3] >= qy0)
               & (t >= t_lo) & (t <= t_hi))
        oracle = np.flatnonzero(hit)
        cand = np.asarray(cand, np.int64)
        got = np.unique(cand[hit[cand]])
        covered = bool(np.isin(oracle, cand).all())
        out["candidates"] = int(len(cand))
        out["hits"] = int(len(oracle))
        out["oracle_exact"] = bool(covered
                                   and np.array_equal(got, oracle))
        out["recompiles"] = int(compile_count() - _c0)
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    out.update(_mem_probe())
    return out


def _stats_pushdown_stanza() -> dict:
    """Stat-sketch push-down regression numbers (ISSUE 3): a
    many-generation lean store answers ``Count();MinMax;Histogram``
    over a bbox+time window from per-run sketches — cold folds every
    run, the warm repeat serves sealed runs from the sketch-partial
    cache and folds only the live one; zero candidate materialization
    asserted via the ``lean.sketch.materialized_fallbacks`` counter.
    The recorded 1B twin lives in STORE_SCALE records
    (store_scale_proof.run's stats_pushdown_* fields).
    ``STATS_BENCH_N=0`` skips."""
    import time

    import numpy as np

    n = int(os.environ.get("STATS_BENCH_N", 4_000_000))
    if not n:
        return {"skipped": True}
    out: dict = {}
    try:
        from geomesa_tpu.obs import compile_count
        _c0 = compile_count()
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.metrics import (
            LEAN_STATS_MATERIALIZED, registry,
        )

        rng = np.random.default_rng(29)
        slots = 1 << 17
        ds = TpuDataStore()
        ds.create_schema(
            "sbench", "score:Double:index=true,dtg:Date,*geom:Point;"
                      "geomesa.index.profile=lean,"
                      f"geomesa.lean.generation.slots={slots},"
                      "geomesa.lean.compaction.factor=0")
        t0 = time.perf_counter()
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("sbench", {
                "score": rng.normal(50.0, 20.0, m),
                "dtg": rng.integers(MS_2018,
                                    MS_2018 + 14 * 86_400_000, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m)),
            })
        out["rows"] = n
        out["ingest_s"] = round(time.perf_counter() - t0, 2)
        st = ds._store("sbench")
        out["attr_runs"] = len(st._lean_attr_index("score").generations)
        spec = "Count();MinMax(score);Histogram(score,20,0,100)"
        q = ("BBOX(geom,-180,-90,180,90) AND dtg DURING "
             "2018-01-02T00:00:00Z/2018-01-10T00:00:00Z")
        m0 = registry.counter(LEAN_STATS_MATERIALIZED).count
        t0 = time.perf_counter()
        cold = ds.stats("sbench", q, spec)
        out["cold_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        ds.stats("sbench", q, spec)   # compiles the live-only shape
        t0 = time.perf_counter()
        warm = ds.stats("sbench", q, spec)
        out["warm_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["warm_speedup"] = round(
            out["cold_ms"] / max(out["warm_ms"], 1e-3), 1)
        out["materialized_fallbacks"] = int(
            registry.counter(LEAN_STATS_MATERIALIZED).count - m0)
        out["results_equal"] = bool(
            cold.to_json() == warm.to_json())
        out["recompiles"] = int(compile_count() - _c0)
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    out.update(_mem_probe())
    return out


def _slo_stanza() -> dict:
    """SLO-plane acceptance gate (ISSUE 20): on the warm fused
    64-client workload >= 90% of each root query's wall must land in
    named ledger stages (mean residual < 10%), the per-tenant
    quantiles and burn gauges must appear in the Prometheus
    exposition with at least one parseable exemplar whose trace_id
    resolves in the tracer, and the finish-hook attribution must cost
    <= 5% wall overhead vs ``geomesa.slo.enabled=false`` with ZERO
    warm recompiles.  ``SLO_BENCH_N=0`` skips."""
    import numpy as np

    n = int(os.environ.get("SLO_BENCH_N", 1_000_000))
    if not n:
        return {"skipped": True}
    clients = int(os.environ.get("SLO_BENCH_CLIENTS", 64))
    rounds = int(os.environ.get("SLO_BENCH_ROUNDS", 3))
    out: dict = {}
    try:
        import re as _re
        import threading
        from geomesa_tpu import config as gm_config
        from geomesa_tpu.datastore import TpuDataStore
        from geomesa_tpu.metrics import registry
        from geomesa_tpu.obs import (compile_count, prometheus_text,
                                     slo_plane, tracer)

        ms0 = 1_514_764_800_000
        day = 86_400_000
        slots = 1 << 16
        rng = np.random.default_rng(53)
        ds = TpuDataStore(user="slo-bench")
        ds.create_schema("slob", (
            "dtg:Date,*geom:Point;geomesa.index.profile=lean,"
            f"geomesa.lean.generation.slots={slots},"
            "geomesa.lean.compaction.factor=0"))
        for lo in range(0, n, slots):
            m = min(slots, n - lo)
            ds.write("slob", {
                "dtg": rng.integers(ms0, ms0 + 14 * day, m),
                "geom": (rng.uniform(-180, 180, m),
                         rng.uniform(-90, 90, m))})
        ds._store("slob")._indexes["z3"].block()
        # the serving stanza's dashboard workload: selective
        # bbox+window filters, one compatibility key, 8 tenants
        queries, windows = [], []
        for i in range(16):
            x = -170.0 + i * 1.5
            d = 1 + (i % 5)
            queries.append(
                f"BBOX(geom,{x},-60,{x + 3},-57) AND dtg DURING "
                f"2018-01-{d:02d}T00:00:00Z/2018-01-{d + 3:02d}"
                "T00:00:00Z")
            windows.append((((x, -60.0, x + 3.0, -57.0),),
                            ms0 + (d - 1) * day, ms0 + (d + 2) * day))
        gm_config.set_property("geomesa.serving.fuse.window.ms", 10.0)
        gm_config.set_property("geomesa.serving.fuse.max.batch", clients)
        try:
            # warm every pow2 capacity bucket so the measured rounds
            # see a pinned compiled-shape set (serving-stanza recipe)
            k = 1
            while k <= clients:
                ds._fused_windows_dispatch(
                    "slob", [windows[j % len(windows)] for j in range(k)])
                k <<= 1
            errors: list = []
            barrier = threading.Barrier(clients + 1)

            def client(i: int) -> None:
                try:
                    barrier.wait(timeout=60)
                    for r in range(rounds):
                        ds.query_fused(
                            "slob", queries[(i + r) % len(queries)],
                            tenant=f"t{i % 8}")
                except Exception as e:  # surfaced via the gate below
                    errors.append(repr(e))

            def fused_round() -> float:
                barrier.reset()
                threads = [threading.Thread(target=client, args=(i,),
                                            daemon=True)
                           for i in range(clients)]
                for t in threads:
                    t.start()
                barrier.wait(timeout=60)   # releases all clients at once
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                return time.perf_counter() - t0

            fused_round()                  # unrecorded warm round
            # A/B overhead: the SAME warm workload with the plane off
            # then on, best-of-2 per mode so one scheduler hiccup
            # cannot fake (or mask) an overhead
            gm_config.set_property("geomesa.slo.enabled", False)
            off_dt = min(fused_round() for _ in range(2))
            gm_config.set_property("geomesa.slo.enabled", True)
            slo_plane.reset()              # only warm traces attribute
            c0 = compile_count()
            on_dt = min(fused_round() for _ in range(2))
            out["warm_recompiles"] = int(compile_count() - c0)
            out["slo_off_s"] = round(off_dt, 3)
            out["slo_on_s"] = round(on_dt, 3)
            out["overhead_pct"] = round(
                (on_dt - off_dt) / off_dt * 100.0, 2)
            if errors:
                out["client_errors"] = errors[:4]
            # attributed coverage of the warm fused root query wall
            report = slo_plane.report()
            qcls = report.get("classes", {}).get("query", {})
            out["residual_pct"] = qcls.get("residual_pct")
            out["burn_5m"] = qcls.get("burn_5m")
            # the exposition must carry >= 1 exemplar whose trace_id
            # the tracer can still resolve (the /traces/<id> join)
            expo = slo_plane.exposition()
            m = _re.search(r' # \{trace_id="([0-9a-f]+)"\}', expo)
            out["exemplar_found"] = bool(m)
            out["exemplar_resolves"] = bool(
                m and tracer.find(m.group(1)) is not None)
            # per-tenant p99 + burn gauges on the scrape surface
            slo_plane.publish()
            body = prometheus_text(registry.snapshot())
            out["tenant_p99_exposed"] = (
                "geomesa_slo_tenant_" in body and 'quantile="0.99"' in body)
            out["burn_gauges_exposed"] = (
                "geomesa_slo_query_burn_5m" in body
                and "geomesa_slo_query_burn_1h" in body)
            out["clients"] = clients
        finally:
            gm_config.clear_property("geomesa.serving.fuse.window.ms")
            gm_config.clear_property("geomesa.serving.fuse.max.batch")
            gm_config.clear_property("geomesa.slo.enabled")
    except Exception as e:  # never kill the bench over a stanza
        out["error"] = repr(e)
    # acceptance gates run OUTSIDE the try (resilience/arrow
    # precedent: an assert swallowed by the stanza's blanket except
    # could never fail a run)
    failures = []
    if "error" not in out and not out.get("skipped"):
        if out.get("client_errors"):
            failures.append(f"fused clients errored: {out['client_errors']}")
        residual = out.get("residual_pct")
        if residual is None or residual >= 10.0:
            failures.append(
                f"unattributed residual {residual}% of warm fused root "
                "wall — the stage ledger must cover >= 90%")
        if out.get("overhead_pct", 100.0) > 5.0:
            failures.append(
                f"SLO attribution costs {out.get('overhead_pct')}% wall "
                "vs slo.enabled=false (budget 5%)")
        if out.get("warm_recompiles", 1) != 0:
            failures.append(
                f"warm fused path recompiled {out.get('warm_recompiles')} "
                "time(s) with the SLO plane on")
        if not out.get("exemplar_resolves"):
            failures.append(
                "no exposition exemplar resolves in the tracer "
                f"(found={out.get('exemplar_found')}) — the "
                "/metrics.prom → /traces/<id> join is broken")
        if not out.get("tenant_p99_exposed"):
            failures.append("slo.tenant.* p99 missing from exposition")
        if not out.get("burn_gauges_exposed"):
            failures.append("slo.query.burn.{5m,1h} gauges missing "
                            "from exposition")
    if failures:
        out["gate_failures"] = failures
        for f in failures:
            print(f"BENCH SLO GATE FAILED: {f}", flush=True)
    out.update(_mem_probe())
    return out


if __name__ == "__main__":
    main()
