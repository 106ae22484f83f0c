"""Chip smoke: drive the store's main path once on the TPU, every answer
checked against a plain NumPy oracle built from the same seeded arrays.

    python chip_smoke.py              # one chip, 64M rows
    python chip_smoke.py --chips 4    # the sharded store on four chips

One process: the web server and the fused-query clients run as threads.
The path is the one users call — ``TpuDataStore`` → ``create_schema`` →
``write`` → query / stats / density / kNN / fused serving / ``GET
/query`` — on a GDELT-shaped lean schema (indexed string attribute,
``dtg:Date``, ``*geom:Point``) at 64M rows: above the store's own lean
threshold (``TpuDataStore.LEAN_AUTO_ROWS``), four 16M-slot generations,
keys and payload resident in HBM.

Each phase prints one line with its wall time (smoke timings, not
benchmark metrics).  The run fails — non-zero exit, no result line — if
the platform is not ``tpu``, any oracle differs (the four Pallas kernels
run directly on 4M of the rows: the lean path takes none of them), a
Pallas kernel is disabled, the resilience layer degraded or opened a breaker, a
generation sits in the host tier, or the HBM budget exceeds the chip's
``bytes_limit``.  No phase catches its own failure.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
import threading
import time
import urllib.parse
import urllib.request

import numpy as np

MS0 = 1514764800000          # 2018-01-01T00:00:00Z
DAY = 86_400_000
SPAN_DAYS = 14               # two weeks of events
NAMES = np.array(["alpha", "beta", "gamma", "delta", "rare"], dtype=object)
NAME_P = [0.55, 0.3, 0.0999, 0.05, 0.0001]
SCHEMA = "gdelt"
SPEC = ("name:String:index=true,dtg:Date,*geom:Point;"
        "geomesa.index.profile=lean")
WORLD = (-180.0, -90.0, 180.0, 90.0)
DENSITY_W, DENSITY_H = 256, 128
KNN_K = 25
KNN_AT = (-74.0, 40.7)
FUSED_THREADS = 8
FUSED_ROUNDS = 4
EARTH_RADIUS_M = 6_371_008.8
#: the write slice: 4M rows per ``ds.write`` (1M rows per shard on four
#: chips — sharded appends split a write across shards in power-of-two
#: slot blocks, so this keeps the shards even)
SLICE_ROWS = 1 << 22
#: rows the Pallas kernels phase runs at (the widths of
#: tests/test_tpu_compile.py)
KERNEL_ROWS = 1 << 22

#: (ecql, box, t_lo_ms, t_hi_ms): DURING bounds are inclusive here, as
#: the store evaluates them
BBOX_QUERIES = [
    ("BBOX(geom,-75,40,-73,42) AND dtg DURING "
     "2018-01-03T00:00:00Z/2018-01-06T00:00:00Z",
     (-75.0, 40.0, -73.0, 42.0), MS0 + 2 * DAY, MS0 + 5 * DAY),
    ("BBOX(geom,0,45,10,55) AND dtg DURING "
     "2018-01-05T12:00:00Z/2018-01-12T12:00:00Z",
     (0.0, 45.0, 10.0, 55.0), MS0 + 4 * DAY + DAY // 2,
     MS0 + 11 * DAY + DAY // 2),
]
ATTR_QUERY = ("name = 'rare'", 4)


class SmokeFailure(RuntimeError):
    """An answer or a health condition the smoke holds the store to."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Phase:
    """Prints one line with the phase's wall time when it completes;
    an exception propagates untouched (no line, no catch)."""

    def __init__(self, name: str):
        self.name = name
        self.notes: list[str] = []

    def note(self, text: str) -> None:
        self.notes.append(text)

    def __enter__(self) -> "Phase":
        self.c0 = compile_seconds()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            say(f"phase {self.name}: {wall:.3f}s (compile "
                f"{compile_seconds() - self.c0:.3f}s) "
                + "; ".join(self.notes))
        return False


def compile_seconds() -> float:
    """Backend compile seconds so far (the recompile listener's
    ``jax.compile.ms`` timer; persistent-cache hits count their load)."""
    from geomesa_tpu.metrics import JAX_COMPILE_MS, registry
    return registry.timer(JAX_COMPILE_MS).total / 1e3


# -- data + oracles ----------------------------------------------------

def make_data(n: int, seed: int) -> dict:
    """World-wide points over two weeks with a skewed name column."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-180.0, 180.0, n)
    y = rng.uniform(-90.0, 90.0, n)
    t = rng.integers(MS0, MS0 + SPAN_DAYS * DAY, n)
    code = rng.choice(len(NAMES), n, p=NAME_P).astype(np.int8)
    return {"x": x, "y": y, "t": t, "code": code}


def bbox_oracle(data: dict, box, lo: int, hi: int) -> np.ndarray:
    x, y, t = data["x"], data["y"], data["t"]
    return np.flatnonzero((x >= box[0]) & (x <= box[2]) & (y >= box[1])
                          & (y <= box[3]) & (t >= lo) & (t <= hi))


def density_oracle(data: dict) -> np.ndarray:
    """Whole-world 256x128 count grid, cells by the store's snapping
    (floor of the offset over the cell size, clipped to the edge)."""
    xmin, ymin, xmax, ymax = WORLD
    dx = (xmax - xmin) / DENSITY_W
    dy = (ymax - ymin) / DENSITY_H
    ix = np.clip(np.floor((data["x"] - xmin) / dx).astype(np.int64),
                 0, DENSITY_W - 1)
    iy = np.clip(np.floor((data["y"] - ymin) / dy).astype(np.int64),
                 0, DENSITY_H - 1)
    return np.bincount(iy * DENSITY_W + ix,
                       minlength=DENSITY_W * DENSITY_H
                       ).reshape(DENSITY_H, DENSITY_W).astype(np.float64)


def knn_oracle(data: dict, k: int) -> tuple[np.ndarray, np.ndarray]:
    lon1, lat1 = np.radians(KNN_AT[0]), np.radians(KNN_AT[1])
    lon2, lat2 = np.radians(data["x"]), np.radians(data["y"])
    a = (np.sin((lat2 - lat1) / 2) ** 2
         + np.cos(lat1) * np.cos(lat2) * np.sin((lon2 - lon1) / 2) ** 2)
    d = 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))
    top = np.argpartition(d, k - 1)[:k]
    return np.sort(top), np.sort(d[top])


# -- phases ------------------------------------------------------------

def open_store(mesh=None):
    from geomesa_tpu.datastore import TpuDataStore
    ds = TpuDataStore(mesh=mesh)
    ds.create_schema(SCHEMA, SPEC)
    check(ds._store(SCHEMA).lean, "schema did not take the lean profile")
    return ds


def ingest(ds, data: dict, slice_rows: int = SLICE_ROWS) -> None:
    n = len(data["x"])
    with Phase("ingest") as ph:
        for lo in range(0, n, slice_rows):
            hi = min(n, lo + slice_rows)
            ds.write(SCHEMA, {"name": NAMES[data["code"][lo:hi]],
                              "dtg": data["t"][lo:hi],
                              "geom": (data["x"][lo:hi],
                                       data["y"][lo:hi])})
        ds._store(SCHEMA)._lean_index().block()
        wall = time.perf_counter() - ph.t0
        ph.note(f"{n} rows in {-(-n // slice_rows)} writes, "
                f"{n / wall:.0f} rows/s")
    check(ds.get_count(SCHEMA) == n, "store row count differs from rows "
                                     "written")


def _positions(ds, ecql: str) -> tuple[np.ndarray, float, float]:
    """(sorted hit positions, cold wall, warm wall) of a solo query."""
    t0 = time.perf_counter()
    ds.query_result(SCHEMA, ecql)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = ds.query_result(SCHEMA, ecql)
    warm = time.perf_counter() - t0
    return np.sort(np.asarray(got.positions)), cold, warm


def bbox_queries(ds, data: dict) -> None:
    for i, (ecql, box, lo, hi) in enumerate(BBOX_QUERIES):
        with Phase(f"bbox_time_{i}") as ph:
            got, cold, warm = _positions(ds, ecql)
            want = bbox_oracle(data, box, lo, hi)
            check(np.array_equal(got, want),
                  f"bbox+time query {i}: {len(got)} hits vs oracle "
                  f"{len(want)}")
            ph.note(f"{len(want)} hits exact, cold {cold:.3f}s, "
                    f"warm {warm:.3f}s")


def attr_query(ds, data: dict) -> None:
    ecql, code = ATTR_QUERY
    with Phase("attr_eq") as ph:
        got, cold, warm = _positions(ds, ecql)
        want = np.flatnonzero(data["code"] == code)
        check(np.array_equal(got, want),
              f"attribute query: {len(got)} hits vs oracle {len(want)}")
        ph.note(f"{len(want)} hits exact, cold {cold:.3f}s, "
                f"warm {warm:.3f}s")


def count_stats(ds, data: dict) -> None:
    from geomesa_tpu.process.stats_process import stats_process
    ecql, box, lo, hi = BBOX_QUERIES[0]
    with Phase("count") as ph:
        total = stats_process(ds, SCHEMA, "INCLUDE", "Count()").count
        check(total == len(data["x"]),
              f"Count(): {total} vs {len(data['x'])} rows")
        window = stats_process(ds, SCHEMA, ecql, "Count()").count
        want = len(bbox_oracle(data, box, lo, hi))
        check(window == want, f"Count() over query 0: {window} vs {want}")
        ph.note(f"Count() {total} total, {window} in window, exact")


def density(ds, data: dict) -> None:
    from geomesa_tpu.process.density import density_process
    with Phase("density") as ph:
        grid = density_process(ds, SCHEMA, "INCLUDE", WORLD,
                               DENSITY_W, DENSITY_H)
        want = density_oracle(data)
        diff = int((np.asarray(grid) != want).sum())
        check(diff == 0, f"density: {diff} of {want.size} cells differ")
        ph.note(f"{DENSITY_W}x{DENSITY_H} grid, {int(want.sum())} "
                "points, per-cell exact")


def knn(ds, data: dict) -> None:
    from geomesa_tpu.process.knn import knn_process
    with Phase("knn") as ph:
        pos, dist = knn_process(ds, SCHEMA, KNN_AT[0], KNN_AT[1], KNN_K)
        want_pos, want_d = knn_oracle(data, KNN_K)
        check(np.array_equal(np.sort(np.asarray(pos)), want_pos),
              "kNN-25: position set differs from the oracle")
        check(np.allclose(np.sort(np.asarray(dist)), want_d, rtol=1e-12,
                          atol=0.0), "kNN-25: distances differ")
        ph.note(f"k={KNN_K} exact set, farthest {want_d[-1]:.1f} m")


def fused(ds, data: dict) -> None:
    from geomesa_tpu import config as gm_config
    from geomesa_tpu.metrics import (SERVING_FUSED_BATCHES,
                                     SERVING_FUSED_REQUESTS, registry)
    ecql = BBOX_QUERIES[0][0]
    with Phase("fused") as ph:
        ref = np.asarray(ds.query_result(SCHEMA, ecql).positions)
        r0 = registry.counter(SERVING_FUSED_REQUESTS).count
        b0 = registry.counter(SERVING_FUSED_BATCHES).count
        results: list = []
        errors: list = []
        barrier = threading.Barrier(FUSED_THREADS)
        # a linger window wide enough that eight threads started on a
        # barrier meet in one batch on a loaded host
        gm_config.set_property("geomesa.serving.fuse.window.ms", 10.0)
        try:
            def client() -> None:
                try:
                    barrier.wait(timeout=60)
                    for _ in range(FUSED_ROUNDS):
                        results.append(np.asarray(
                            ds.query_fused(SCHEMA, ecql).positions))
                except BaseException as e:  # re-raised on the main thread
                    errors.append(e)

            threads = [threading.Thread(target=client)
                       for _ in range(FUSED_THREADS)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=300)
                check(not th.is_alive(), "fused client did not finish")
        finally:
            gm_config.clear_property("geomesa.serving.fuse.window.ms")
        if errors:
            raise errors[0]
        requests = registry.counter(SERVING_FUSED_REQUESTS).count - r0
        batches = registry.counter(SERVING_FUSED_BATCHES).count - b0
        check(len(results) == FUSED_THREADS * FUSED_ROUNDS,
              "fused: missing results")
        check(all(np.array_equal(r, ref) for r in results),
              "fused: positions differ from query_result")
        check(requests > batches,
              f"fused: {requests} requests in {batches} batches (no "
              "fan-in)")
        ph.note(f"{len(results)} requests bit-exact, {requests} fused "
                f"requests in {batches} batches")


def web(ds, data: dict) -> None:
    import pyarrow as pa

    from geomesa_tpu.arrow.schema import FID_FIELD
    from geomesa_tpu.web import WebApp
    from geomesa_tpu.web.wsgi import make_bounded_server
    ecql, box, lo, hi = BBOX_QUERIES[1]
    want = bbox_oracle(data, box, lo, hi)
    server = make_bounded_server("127.0.0.1", 0, WebApp(ds))
    th = threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    try:
        port = server.server_address[1]
        base = (f"http://127.0.0.1:{port}/query?schema={SCHEMA}"
                f"&cql={urllib.parse.quote(ecql)}")
        for label, url in (("web_query", base),
                           ("web_query_arrow", base + "&format=arrow")):
            with Phase(label) as ph:
                with urllib.request.urlopen(url, timeout=300) as resp:
                    check(resp.status == 200, f"{label}: {resp.status}")
                    body = resp.read()
                table = pa.ipc.open_stream(io.BytesIO(body)).read_all()
                ids = np.sort(np.asarray(
                    table.column(FID_FIELD).to_pylist(), dtype=np.int64))
                check(np.array_equal(ids, want),
                      f"{label}: {table.num_rows} rows vs oracle "
                      f"{len(want)}")
                ph.note(f"{table.num_rows} rows exact, {len(body)} "
                        "bytes of Arrow IPC")
    finally:
        server.shutdown()
        server.server_close()
        th.join(timeout=30)


def kernels(data: dict, n: int = KERNEL_ROWS) -> None:
    """The four Pallas kernels on the first ``n`` rows, each against a
    NumPy oracle.  Called directly: the lean store routes none of its
    work through them (only the full-fat and non-lean sharded indexes
    do), and on the chip this is the only run they get."""
    import jax.numpy as jnp

    from geomesa_tpu.curve import TimePeriod, to_binned_time, z2_sfc, z3_sfc
    from geomesa_tpu.curve.zorder import interleave2
    from geomesa_tpu.ops import pallas_kernels as pk
    x, y, t = data["x"][:n], data["y"][:n], data["t"][:n]
    week = t < MS0 + 7 * DAY
    boxes = [q[1] for q in BBOX_QUERIES] + [(-60.0, -30.0, 20.0, 40.0)]

    def in_boxes(ix, iy, ixy):
        hit = np.zeros(len(ix), bool)
        for b in ixy:
            hit |= (ix >= b[0]) & (iy >= b[1]) & (ix <= b[2]) & (iy <= b[3])
        return hit

    with Phase("kernels") as ph:
        grid = np.asarray(pk.density_grid_pallas(
            jnp.asarray(x), jnp.asarray(y), jnp.ones(n, jnp.float32),
            jnp.asarray(week), WORLD, DENSITY_W, DENSITY_H))
        want = density_oracle({"x": x[week], "y": y[week]})
        check(np.array_equal(grid, want), "density_grid_pallas: "
              f"{int((grid != want).sum())} cells differ")

        hour = ((t - MS0) // 3_600_000).astype(np.int32)
        hours = SPAN_DAYS * 24
        mask = data["code"][:n] == 0
        hist = np.asarray(pk.hist1d_pallas(
            jnp.asarray(hour), jnp.ones(n, jnp.float32), jnp.asarray(mask),
            hours))
        check(np.array_equal(hist, np.bincount(hour[mask],
                                               minlength=hours)),
              "hist1d_pallas differs from bincount")

        sfc = z3_sfc(TimePeriod.WEEK)
        _, off = to_binned_time(t, TimePeriod.WEEK)
        off = off.astype(np.float64)
        z = np.asarray(sfc.index(x, y, off, xp=np)).astype(np.int64)
        ix = np.asarray(sfc.lon.normalize(x, xp=np)).astype(np.int64)
        iy = np.asarray(sfc.lat.normalize(y, xp=np)).astype(np.int64)
        it = np.asarray(sfc.time.normalize(off, xp=np)).astype(np.int64)
        ixy = np.array([[sfc.lon.normalize_scalar(b[0]),
                         sfc.lat.normalize_scalar(b[1]),
                         sfc.lon.normalize_scalar(b[2]),
                         sfc.lat.normalize_scalar(b[3])] for b in boxes],
                       np.int32)
        tlo = np.full(n, int(np.quantile(it, 0.25)), np.int32)
        thi = np.full(n, int(np.quantile(it, 0.75)), np.int32)
        got = np.asarray(pk.z3_mask_pallas(jnp.asarray(z), jnp.asarray(ixy),
                                           jnp.asarray(tlo),
                                           jnp.asarray(thi)))
        want = in_boxes(ix, iy, ixy) & (it >= tlo) & (it <= thi)
        check(np.array_equal(got, want), "z3_mask_pallas: "
              f"{int(got.sum())} hits vs oracle {int(want.sum())}")
        z3_hits = int(want.sum())

        sfc2 = z2_sfc()
        ix = np.asarray(sfc2.lon.normalize(x, xp=np)).astype(np.int64)
        iy = np.asarray(sfc2.lat.normalize(y, xp=np)).astype(np.int64)
        z = np.asarray(interleave2(ix, iy, xp=np)).astype(np.int64)
        ixy = np.array([[sfc2.lon.normalize_scalar(b[0]),
                         sfc2.lat.normalize_scalar(b[1]),
                         sfc2.lon.normalize_scalar(b[2]),
                         sfc2.lat.normalize_scalar(b[3])] for b in boxes],
                       np.int32)
        got = np.asarray(pk.z2_mask_pallas(jnp.asarray(z), jnp.asarray(ixy)))
        want = in_boxes(ix, iy, ixy)
        check(np.array_equal(got, want), "z2_mask_pallas: "
              f"{int(got.sum())} hits vs oracle {int(want.sum())}")
        ph.note(f"{n} rows: density {int(grid.sum())} points, hist1d "
                f"{int(hist.sum())}, z3 mask {z3_hits} hits, z2 mask "
                f"{int(want.sum())} hits, all exact (Mosaic: "
                f"{not pk._interpret()})")


# -- health ------------------------------------------------------------

def device_line(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"memory {d}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def health(ds, devices) -> None:
    """Tier residency, HBM budget, Pallas gates and resilience counters;
    raises on the first condition that fails the run."""
    from geomesa_tpu.metrics import (RESILIENCE_BREAKER_OPEN,
                                     RESILIENCE_DEGRADED,
                                     RESILIENCE_RETRIES, registry)
    from geomesa_tpu.ops.pallas_kernels import on_tpu, pallas_health
    st = ds._store(SCHEMA)
    tiers = st._lean_index().tier_counts()
    attr_tiers = st._lean_attr_index("name").tier_counts()
    say(f"tiers z3={tiers} attr:name={attr_tiers}")
    check(not tiers.get("host") and not attr_tiers.get("host"),
          f"generations in the host tier: z3={tiers} name={attr_tiers}")
    budget = st._lean_budget()
    for d in devices:
        limit = (d.memory_stats() or {}).get("bytes_limit")
        check(limit is not None and budget <= limit,
              f"HBM budget {budget} exceeds {d} bytes_limit {limit}")
    say(f"hbm budget {budget} <= bytes_limit on {len(devices)} device(s)")
    ph = pallas_health()
    say(f"pallas {ph}")
    check(on_tpu(), "pallas: on_tpu() is false")
    res = {k: registry.counter(k).count for k in
           (RESILIENCE_DEGRADED, RESILIENCE_RETRIES,
            RESILIENCE_BREAKER_OPEN)}
    say(f"resilience {res}")
    check(not any(res.values()), f"resilience degraded: {res}")


def compile_line(label: str) -> None:
    from geomesa_tpu.metrics import JAX_COMPILE_MS, registry
    say(f"compile {label}: {registry.timer(JAX_COMPILE_MS).count} "
        f"programs, {compile_seconds():.3f}s")


def shard_balance(ds, n: int, n_dev: int) -> None:
    """Rows each device holds, read from the sharded generations'
    ``addressable_shards``: every device must hold about 1/n_dev."""
    idx = ds._store(SCHEMA)._lean_index()
    per: dict = {}
    for gen in idx.generations:
        check(gen.tier != "host", "sharded generation in the host tier")
        for sh in gen.pos.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + int(
                (np.asarray(sh.data) >= 0).sum())
    say("rows per device " + ", ".join(f"{d}={c}" for d, c in
                                       sorted(per.items(),
                                              key=lambda kv: kv[0].id)))
    check(len(per) == n_dev, f"rows on {len(per)} devices, not {n_dev}")
    check(sum(per.values()) == n, f"sharded rows {sum(per.values())} "
                                  f"vs {n}")
    for d, c in per.items():
        check(abs(c - n / n_dev) <= 0.05 * n / n_dev,
              f"{d} holds {c} rows, not about {n / n_dev:.0f}")


# -- entry -------------------------------------------------------------

def run(rows: int, seed: int, chips: int) -> None:
    import jax

    from geomesa_tpu import native
    from geomesa_tpu.ops.pallas_kernels import pallas_health
    devices = jax.devices()[:chips]
    say(f"devices {jax.devices()}")
    say(f"native available={native.available()}")
    say(f"pallas {pallas_health()}")
    with Phase("data") as ph:
        data = make_data(rows, seed)
        ph.note(f"{rows} rows, seed {seed}")
    mesh = None
    if chips > 1:
        from geomesa_tpu.parallel import device_mesh
        mesh = device_mesh(chips)
    ds = open_store(mesh)
    ingest(ds, data)
    device_line(devices)
    health(ds, devices)
    compile_line("after ingest")
    bbox_queries(ds, data)
    attr_query(ds, data)
    count_stats(ds, data)
    density(ds, data)
    if chips > 1:
        shard_balance(ds, rows, chips)
    else:
        knn(ds, data)
        fused(ds, data)
        web(ds, data)
        kernels(data)
    device_line(devices)
    health(ds, devices)
    compile_line("total")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=64_000_000)
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's platform is "
              f"{platform!r}", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2
    from geomesa_tpu.compile_cache import enable_compile_cache
    say(f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    run(args.rows, args.seed, args.chips)
    say(f"total wall {time.perf_counter() - t0:.3f}s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
