"""Workload heat observability (ISSUE 12): per-generation access
temperature with storage-placement join, write-path spans, the
background-job registry, and the web surfaces + param hardening that
ride along.

The heat acceptance shape: a time-partitioned multi-generation lean
store queried repeatedly over a narrow window — the generations that
window draws from must rank hotter than generations every query
merely probes, and every ranked row must join its current device/host
placement from the storage accounting.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.config import clear_property, set_property
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.jobs import CompactionJob, run_compaction, run_ingest
from geomesa_tpu.metrics import registry
from geomesa_tpu.obs import tracer
from geomesa_tpu.obs.heat import (
    HeatTracker, heat_report, heat_tracker, publish_heat_gauges,
)
from geomesa_tpu.obs.jobs import jobs_registry

MS = 1514764800000
DAY = 86_400_000

HOT_Q = ("BBOX(geom,-75,40,-73,42) AND dtg DURING "
         "2018-01-08T00:00:00Z/2018-01-10T00:00:00Z")


def _mk_partitioned_store(name="hevt", slots=4096, budget=None):
    """Lean z3 store with TIME-PARTITIONED generations: slice i holds
    days [3i, 3i+3), one generation per slice — so a narrow time
    window draws from specific generations (the skewed-access shape
    the autopilot needs to see)."""
    rng = np.random.default_rng(11)
    ud = (f"geomesa.index.profile=lean,"
          f"geomesa.lean.generation.slots={slots},"
          f"geomesa.lean.compaction.factor=0")
    if budget:
        ud += f",geomesa.lean.hbm.budget={budget}"
    ds = TpuDataStore(user="heat-test")
    ds.create_schema(name, f"dtg:Date,*geom:Point;{ud}")
    for i in range(4):
        lo = MS + 3 * i * DAY
        ds.write(name, {
            "dtg": rng.integers(lo, lo + 3 * DAY, slots),
            "geom": (rng.uniform(-75, -73, slots),
                     rng.uniform(40, 42, slots))})
    return ds


def _call(app, method, path):
    cap = {}

    def sr(status, headers):
        cap["status"] = int(status.split()[0])
        cap["headers"] = dict(headers)

    qs = ""
    if "?" in path:
        path, qs = path.split("?", 1)
    body = b"".join(app({
        "REQUEST_METHOD": method, "PATH_INFO": path, "QUERY_STRING": qs,
        "CONTENT_LENGTH": "0", "wsgi.input": io.BytesIO(b"")}, sr))
    return cap["status"], cap["headers"], body.decode()


# -- access temperature (tentpole a) ---------------------------------------

def test_heat_ranks_skewed_access_hot_over_cold():
    """ACCEPTANCE: generations a repeated narrow-window query draws
    from rank above generations it only probes, and every ranked row
    joins its current placement from the storage report."""
    ds = _mk_partitioned_store()
    for _ in range(5):
        ds.query("hevt", HOT_Q)
    rep = ds.heat_report()
    rows = [r for r in rep["generations"]
            if (r["schema"], r["index"]) == ("hevt", "z3")]
    assert len(rows) == 4
    hot = [r for r in rows if r["rows_matched"] > 0]
    cold = [r for r in rows if r["rows_matched"] == 0]
    assert hot and cold, "expected a skewed hot/cold split"
    # every hot generation ranks strictly above every cold one
    assert max(r["rank"] for r in hot) < min(r["rank"] for r in cold)
    assert all(r["temperature"] > 0 for r in hot)
    # cold generations were still probed (scans counted, zero weight)
    assert all(r["scans"] >= 5 for r in cold)
    assert all(r["temperature"] == 0.0 for r in cold)
    # placement join: every row carries its CURRENT tier + bytes from
    # the storage accounting, consistent with the storage report
    st = ds._store("hevt")._indexes["z3"].storage_stats()
    by_gen = {g["gen_id"]: g for g in st["generations"]}
    for r in rows:
        p = r["placement"]
        assert p["tier"] == by_gen[r["gen_id"]]["tier"]
        assert p["rows"] == by_gen[r["gen_id"]]["rows"]
        assert p["device_bytes"] == by_gen[r["gen_id"]]["device_bytes"]
    # aggregates cover the index
    agg = rep["indexes"]["hevt.z3"]
    assert agg["generations"] == 4 and agg["scans"] >= 20


def test_untouched_generations_appear_cold():
    """Generations no query ever touched still appear in the report
    (temperature 0) — the autopilot must see the coldest data, not
    just the warmest."""
    ds = _mk_partitioned_store(name="cold1")
    rep = ds.heat_report()     # no queries at all
    rows = [r for r in rep["generations"] if r["schema"] == "cold1"]
    assert len(rows) == 4
    assert all(r["temperature"] == 0.0 and r["scans"] == 0
               for r in rows)
    assert all(r["placement"]["rows"] > 0 for r in rows)


def test_temperature_decays_with_tau():
    """The documented formula: a touch contributes exp(-(now-t)/τ)."""
    tr = HeatTracker(tau_s=10.0)
    tr.record(("s", "z3"), [(1, "keys", 100, 1600, 7)], now=0.0)
    snap = tr.snapshot(now=0.0)
    assert snap[("s", "z3", 1)]["temperature"] == pytest.approx(1.0)
    assert snap[("s", "z3", 1)]["rows_matched"] == 7
    snap = tr.snapshot(now=10.0)
    assert snap[("s", "z3", 1)]["temperature"] == pytest.approx(
        np.exp(-1.0))
    # a second touch stacks on the decayed score
    tr.record(("s", "z3"), [(1, "keys", 100, 1600, 3)], now=10.0)
    snap = tr.snapshot(now=10.0)
    assert snap[("s", "z3", 1)]["temperature"] == pytest.approx(
        1.0 + np.exp(-1.0))
    # zero-match probes count scans but add no heat
    tr.record(("s", "z3"), [(2, "keys", 100, 1600, 0)], now=10.0)
    snap = tr.snapshot(now=10.0)
    assert snap[("s", "z3", 2)]["temperature"] == 0.0
    assert snap[("s", "z3", 2)]["scans"] == 1


def test_compaction_merges_inherit_temperature():
    """LSM maintenance must not reset hot data to cold: the merged
    generation inherits its sources' decayed temperatures."""
    # the 700 kB budget demotes sealed runs to the keys tier, where
    # the size-tiered planner can group them
    ds = _mk_partitioned_store(name="cmp1", budget=700000)
    for _ in range(3):
        ds.query("cmp1", "BBOX(geom,-75,40,-73,42)")   # heat all gens
    idx = ds._store("cmp1")._indexes["z3"]
    before = heat_tracker.snapshot()
    total_before = sum(v["temperature"] for k, v in before.items()
                      if k[0] == "cmp1")
    assert total_before > 0
    stats = idx.compact(factor=2)
    assert stats["merged_groups"] >= 1
    rep = ds.heat_report()
    rows = [r for r in rep["generations"] if r["schema"] == "cmp1"]
    # the merged run carries forward its sources' heat (within decay
    # slack over the test's wall time)
    assert sum(r["temperature"] for r in rows) == pytest.approx(
        total_before, rel=0.05)
    live_ids = {g.gen_id for g in idx.generations}
    assert {r["gen_id"] for r in rows} == live_ids


def test_tracker_bounds_entries():
    tr = HeatTracker(tau_s=10.0, max_entries=20)
    for g in range(100):
        tr.record(("s", "z3"), [(g, "keys", 1, 16, 1)], now=float(g))
    assert len(tr) <= 20
    # the hottest (latest) entries survive the eviction
    assert ("s", "z3", 99) in tr.snapshot(now=100.0)


def test_heat_disabled_records_nothing():
    tr_len = len(heat_tracker)
    set_property("geomesa.obs.heat.enabled", False)
    try:
        ds = _mk_partitioned_store(name="hoff")
        ds.query("hoff", "BBOX(geom,-75,40,-73,42)")
        assert not any(k[0] == "hoff"
                       for k in heat_tracker.snapshot())
        assert len(heat_tracker) <= tr_len + 1
    finally:
        clear_property("geomesa.obs.heat.enabled")


def test_heat_gauges_publish_and_retire():
    ds = _mk_partitioned_store(name="hg1")
    ds.query("hg1", "BBOX(geom,-75,40,-73,42)")
    rep = publish_heat_gauges(ds)
    assert rep["indexes"]
    names = registry.names()
    assert "heat.hg1.z3.temperature" in names
    assert "heat.total.temperature" in names
    # schema removal retires its keys on the next publish
    ds.remove_schema("hg1")
    heat_tracker.drop(("hg1", "z3"),
                      [r["gen_id"] for r in rep["generations"]
                       if r["schema"] == "hg1"])
    publish_heat_gauges(ds)
    assert "heat.hg1.z3.temperature" not in registry.names()


def test_heat_overhead_proxy_on_warm_queries():
    """Heat tracking and tracing, on at their defaults, put no work on
    the device: over the same warm windows they add no dispatch and no
    compile against both off, and the answers match.  Their time cost
    is inside the benchmark cells' end-to-end numbers, where both are
    on by default."""
    from geomesa_tpu.obs import compile_count
    ds = _mk_partitioned_store(name="hperf", slots=16384)
    idx = ds._store("hperf")._indexes["z3"]
    win = [([(-75.0, 40.0, -73.0, 42.0)], MS + 2 * i * DAY,
            MS + (2 * i + 2) * DAY) for i in range(4)]

    def warm_run():
        d0, c0 = idx.dispatch_count, compile_count()
        out = [idx.query_many(win) for _ in range(3)]
        return out, idx.dispatch_count - d0, compile_count() - c0

    idx.query_many(win)                    # warm/compile
    on, on_dispatches, on_compiles = warm_run()
    set_property("geomesa.obs.heat.enabled", False)
    set_property("geomesa.obs.enabled", False)
    try:
        off, off_dispatches, off_compiles = warm_run()
    finally:
        clear_property("geomesa.obs.heat.enabled")
        clear_property("geomesa.obs.enabled")
    assert on_dispatches == off_dispatches > 0
    assert on_compiles == off_compiles == 0
    for a, b in zip(on, off):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# -- write-path spans (tentpole b) -----------------------------------------

def test_write_trace_covers_encode_index_seal_observe_device():
    ds = _mk_partitioned_store(name="wsp1", slots=2048)
    rng = np.random.default_rng(3)
    with tracer.capture() as cap:
        ds.write("wsp1", {
            "dtg": rng.integers(MS, MS + DAY, 5000),
            "geom": (rng.uniform(-75, -73, 5000),
                     rng.uniform(40, 42, 5000))})
    traces = cap.traces()
    assert len(traces) == 1
    t = traces[0]
    assert t.root_span.name == "write"
    assert t.root_span.attributes["schema"] == "wsp1"
    assert t.root_span.attributes["rows"] == 5000
    names = [s.name for s in t.spans]
    for expect in ("write.encode", "write.index", "write.seal",
                   "write.observe", "write.device", "write"):
        assert expect in names, names
    # 5000 rows over 2048 slots seals at least two generations
    assert names.count("write.seal") >= 2
    idx_spans = [s for s in t.spans if s.name == "write.index"]
    assert {s.attributes["index"] for s in idx_spans} == {"z3"}
    # device attribution: the block-until-ready wait rolled up
    dev = [s for s in t.spans if s.name == "write.device"]
    assert dev and "device_ms" in dev[0].attributes
    assert "device_ms" in t.root_span.attributes
    assert registry.counter("write.seals").count >= 2


def test_write_spill_traced_under_budget_pressure():
    """A tight HBM budget forces device→host spills mid-ingest; the
    spill is a device span with honest block-until-ready ms."""
    ds = _mk_partitioned_store(name="wsp2", slots=8192, budget=600000)
    rng = np.random.default_rng(4)
    with tracer.capture() as cap:
        for _ in range(2):
            ds.write("wsp2", {
                "dtg": rng.integers(MS, MS + DAY, 8192),
                "geom": (rng.uniform(-75, -73, 8192),
                         rng.uniform(40, 42, 8192))})
    spills = [s for t in cap.traces() for s in t.spans
              if s.name == "write.spill"]
    assert spills, "expected spills under a 600 kB budget"
    assert all(s.attributes.get("kind") == "device" for s in spills)
    assert all("device_ms" in s.attributes for s in spills)
    assert registry.counter("write.spills").count >= len(spills)


def test_write_block_opt_out_skips_device_span():
    set_property("geomesa.obs.write.block", False)
    try:
        ds = _mk_partitioned_store(name="wsp3", slots=2048)
        rng = np.random.default_rng(5)
        with tracer.capture() as cap:
            ds.write("wsp3", {
                "dtg": rng.integers(MS, MS + DAY, 1000),
                "geom": (rng.uniform(-75, -73, 1000),
                         rng.uniform(40, 42, 1000))})
        names = [s.name for t in cap.traces() for s in t.spans]
        assert "write.device" not in names
        assert "write.index" in names
    finally:
        clear_property("geomesa.obs.write.block")


# -- background-job registry (tentpole c) ----------------------------------

def test_compaction_job_registers_with_phases_and_outcome():
    ds = _mk_partitioned_store(name="job1")
    out = run_compaction(ds, "job1")
    rec = jobs_registry.jobs(kind="compaction", limit=1)[0]
    assert rec.state == "succeeded"
    assert rec.kind == "compaction"
    assert [p["name"] for p in rec.phases] == ["compact"]
    assert rec.phases[0]["ms"] >= 0
    assert rec.progress["merged_groups"] == sum(
        v["merged_groups"] for v in out.values())
    assert rec.duration_ms > 0 and rec.end_ts >= rec.start_ts


def test_failed_job_records_terminal_outcome():
    """ACCEPTANCE: a crashed job is visible with state=failed and the
    error — not vanished."""
    ds = _mk_partitioned_store(name="job2")
    with pytest.raises(KeyError):
        CompactionJob(ds, "no_such_schema").run()
    rec = jobs_registry.jobs(kind="compaction", state="failed",
                             limit=1)[0]
    assert rec.state == "failed"
    assert "no_such_schema" in rec.error
    assert registry.counter("job.compaction.failures").count >= 1


def test_ingest_job_registers_with_progress(tmp_path):
    ds = TpuDataStore(user="heat-test")
    ds.create_schema("ipts", "name:String,v:Int,dtg:Date,*geom:Point")
    files = []
    for i in range(3):
        p = tmp_path / f"in{i}.csv"
        p.write_text("\n".join(
            f"x{j},{j},{MS + j},{i}.25,1.5" for j in range(20)) + "\n")
        files.append(str(p))
    config = {
        "type": "csv",
        "fields": [
            {"name": "name", "transform": "$0"},
            {"name": "v", "transform": "toInt($1)"},
            {"name": "dtg", "transform": "toLong($2)"},
            {"name": "geom", "transform": "point($3,$4)"},
        ],
        "options": {"error-mode": "skip"},
    }
    result = run_ingest(ds, "ipts", config, files, workers=2)
    assert result.ingested == 60
    rec = jobs_registry.jobs(kind="ingest", limit=1)[0]
    assert rec.state == "succeeded"
    assert [p["name"] for p in rec.phases] == ["setup", "ingest"]
    assert rec.progress == {"files": 3, "ingested": 60, "failed": 0}
    assert rec.detail["schema"] == "ipts"


# -- web surfaces + param hardening (satellites) ---------------------------

def test_debug_heat_endpoint_and_paging():
    from geomesa_tpu.web import WebApp
    ds = _mk_partitioned_store(name="web1")
    for _ in range(3):
        ds.query("web1", HOT_Q)
    app = WebApp(ds)
    status, _, body = _call(app, "GET", "/debug/heat")
    assert status == 200
    rep = json.loads(body)
    rows = [r for r in rep["generations"] if r["schema"] == "web1"]
    assert len(rows) == 4
    assert rows == sorted(rows, key=lambda r: r["rank"])
    # heat gauges refreshed by the report land in the prom scrape
    status, _, text = _call(app, "GET", "/metrics.prom")
    assert status == 200
    assert "geomesa_heat_web1_z3_temperature" in text.replace(".", "_")
    # paging truncates the ranked list
    status, _, body = _call(app, "GET", "/debug/heat?limit=2")
    assert status == 200
    assert len(json.loads(body)["generations"]) == 2
    status, _, _ = _call(app, "GET", "/debug/heat?limit=nope")
    assert status == 400
    status, _, _ = _call(app, "GET", "/debug/heat?limit=-1")
    assert status == 400


def test_debug_jobs_endpoint_and_filters():
    from geomesa_tpu.web import WebApp
    ds = _mk_partitioned_store(name="web2")
    run_compaction(ds, "web2")
    app = WebApp(ds)
    status, _, body = _call(app, "GET", "/debug/jobs?kind=compaction")
    assert status == 200
    jobs = json.loads(body)["jobs"]
    assert jobs and jobs[0]["kind"] == "compaction"
    assert jobs[0]["state"] == "succeeded"
    assert jobs[0]["phases"]
    status, _, body = _call(app, "GET", "/debug/jobs?limit=1")
    assert status == 200 and len(json.loads(body)["jobs"]) == 1
    status, _, _ = _call(app, "GET", "/debug/jobs?state=exploded")
    assert status == 400
    status, _, _ = _call(app, "GET", "/debug/jobs?limit=zz")
    assert status == 400


def test_traces_paging_and_param_400s():
    from geomesa_tpu.web import WebApp
    ds = _mk_partitioned_store(name="web3")
    for _ in range(4):
        ds.query("web3", "BBOX(geom,-75,40,-73,42)")
    app = WebApp(ds)
    status, _, body = _call(app, "GET", "/traces")
    assert status == 200
    n_all = len(json.loads(body))
    assert n_all >= 4
    status, _, body = _call(app, "GET", "/traces?limit=2")
    assert status == 200
    page = json.loads(body)
    assert len(page) == 2
    # newest-last contract: the page is the TAIL of the full list
    status, _, body = _call(app, "GET", "/traces")
    assert [t["trace_id"] for t in page] == \
        [t["trace_id"] for t in json.loads(body)[-2:]]
    for bad in ("/traces?limit=abc", "/traces?limit=-5",
                "/traces?slow=maybe"):
        status, _, _ = _call(app, "GET", bad)
        assert status == 400, bad


def test_debug_storage_audit_param():
    from geomesa_tpu.web import WebApp
    ds = _mk_partitioned_store(name="web4")
    app = WebApp(ds)
    status, _, body = _call(app, "GET", "/debug/storage")
    assert status == 200
    assert "reconciliation" in json.loads(body)
    status, _, body = _call(app, "GET", "/debug/storage?audit=0")
    assert status == 200
    assert "reconciliation" not in json.loads(body)
    status, _, _ = _call(app, "GET", "/debug/storage?audit=banana")
    assert status == 400


def test_explain_malformed_cql_is_400():
    from geomesa_tpu.web import WebApp
    ds = _mk_partitioned_store(name="web5")
    app = WebApp(ds)
    status, _, body = _call(
        app, "GET", "/explain?schema=web5&cql=BBOX((")
    assert status == 400, body
    status, _, _ = _call(app, "GET", "/explain")
    assert status == 400


# -- reporter restart + concurrent rotation (satellite) --------------------

def test_periodic_reporter_stop_then_restart(tmp_path):
    """stop() must leave the scheduler restartable: a second start()
    spins a FRESH thread that keeps reporting."""
    from geomesa_tpu.metrics import (
        DelimitedFileReporter, MetricRegistry, PeriodicReporter,
    )
    reg = MetricRegistry()
    reg.counter("obs.test.restarts").inc()
    path = tmp_path / "metrics.csv"
    pr = PeriodicReporter(DelimitedFileReporter(reg, str(path)),
                          interval_s=0.02)
    pr.start()
    t1 = pr._thread
    time.sleep(0.08)
    pr.stop()
    assert pr._thread is None
    n_stopped = path.read_text().count("obs.test.restarts")
    assert n_stopped >= 1
    pr.start()                     # restart after stop
    t2 = pr._thread
    assert t2 is not None and t2 is not t1 and t2.is_alive()
    time.sleep(0.08)
    pr.stop()
    assert path.read_text().count("obs.test.restarts") > n_stopped
    # idempotent stop
    pr.stop()


def test_jsonl_rotation_under_concurrent_writer_and_query_threads(
        tmp_path):
    """The write-path spans make writer-thread + query-thread trace
    emission real: drive both through a size-capped JsonlExporter and
    assert every line stays valid JSON and retention stays bounded
    across rotations (no torn lines, no lost sink)."""
    from geomesa_tpu.obs import JsonlExporter, Tracer

    path = tmp_path / "traces.jsonl"
    cap = 20_000
    tr = Tracer(exporters=[JsonlExporter(str(path), max_bytes=cap)])
    stop = threading.Event()
    errors: list = []

    def emit(kind: str):
        try:
            while not stop.is_set():
                with tr.span(kind, payload="x" * 120):
                    with tr.span(f"{kind}.child"):
                        pass
        except Exception as e:  # noqa: BLE001 — surface in the test
            errors.append(e)

    threads = [threading.Thread(target=emit, args=("write",)),
               threading.Thread(target=emit, args=("query",))]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert not errors
    tr.exporters[0].close()
    assert path.exists()
    # rotation happened (enough concurrent traffic to pass the cap)
    assert (tmp_path / "traces.jsonl.1").exists()
    kinds = set()
    for f in (path, tmp_path / "traces.jsonl.1"):
        assert f.stat().st_size <= cap
        for line in f.read_text().splitlines():
            rec = json.loads(line)       # no torn/interleaved lines
            kinds.add(rec["spans"][-1]["name"])
    assert kinds == {"write", "query"}
