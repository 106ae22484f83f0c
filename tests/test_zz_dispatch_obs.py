"""The lean dispatch traced from the inside: the enqueue/wait split of
``device_span``, the device backlog (``inflight``), the scan work
counters (``lean.scan.*``) against hand counts, the fused batch's
linger and backlog, the estimator's sketch-build span, the planner's
materialize span, and the program's spans on the profiler's timeline."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomesa_tpu import obs
from geomesa_tpu.config import clear_property, set_property
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.index.attr_lean import LeanAttrIndex
from geomesa_tpu.index.z3 import plan_z3_query
from geomesa_tpu.index.z3_lean import (
    _GEN_BUCKET, _MAX_RANGES_PER_WINDOW, LeanZ3Index, _bins_spanned,
)
from geomesa_tpu.metrics import (
    LEAN_DEVICE_DISPATCHES, LEAN_DEVICE_ENQUEUE_MS, LEAN_DEVICE_INFLIGHT_SUM,
    LEAN_DEVICE_WAIT_MS, LEAN_SCAN_BYTES, LEAN_SCAN_CANDIDATES,
    LEAN_SCAN_HITS, LEAN_SCAN_SLOTS, PLAN_SKETCH_BUILD_MS,
    PLAN_SKETCH_BUILDS, registry,
)
from geomesa_tpu.ops.search import gather_capacity, scan_read_bytes

MS = 1514764800000
DAY = 86_400_000
SCAN = (LEAN_SCAN_CANDIDATES, LEAN_SCAN_SLOTS, LEAN_SCAN_HITS,
        LEAN_SCAN_BYTES)


def _counts(*names):
    return [registry.counter(n).count for n in names]


def _delta(before, names):
    return [a - b for a, b in zip(_counts(*names), before)]


def _one_trace(fn):
    with obs.tracer.capture() as cap:
        out = fn()
    (trace,) = cap.traces()
    return trace, out


@jax.jit
def _double(x):
    return x * 2


# -- device_span: the enqueue/wait split and the backlog ---------------

def test_marked_dispatch_splits_device_ms_into_enqueue_and_wait():
    x = jnp.arange(8)
    _double(x).block_until_ready()
    timers = [registry.timer(n) for n in (LEAN_DEVICE_ENQUEUE_MS,
                                          LEAN_DEVICE_WAIT_MS)]
    n0 = [t.count for t in timers]

    def run():
        with obs.span("query"):
            with obs.device_span("query.scan.device", stage="probe") as d:
                res = _double(x)
                d.dispatched()
                return np.asarray(res)

    trace, out = _one_trace(run)
    np.testing.assert_array_equal(out, np.arange(8) * 2)
    (dev,) = [s for s in trace.spans if s.name == "query.scan.device"]
    a = dev.attributes
    assert a["enqueue_ms"] >= 0 and a["wait_ms"] >= 0
    assert a["enqueue_ms"] + a["wait_ms"] == pytest.approx(
        a["device_ms"], abs=2e-3)
    assert [t.count for t in timers] == [c + 1 for c in n0]


def test_unmarked_dispatch_records_device_ms_only():
    timers = [registry.timer(n) for n in (LEAN_DEVICE_ENQUEUE_MS,
                                          LEAN_DEVICE_WAIT_MS)]
    n0 = [t.count for t in timers]

    def run():
        with obs.span("query"):
            with obs.device_span("write.spill", rows=1):
                np.asarray(_double(jnp.arange(4)))

    trace, _ = _one_trace(run)
    (dev,) = [s for s in trace.spans if s.name == "write.spill"]
    assert "device_ms" in dev.attributes
    assert "enqueue_ms" not in dev.attributes
    assert "wait_ms" not in dev.attributes
    assert [t.count for t in timers] == n0


def test_inflight_counts_the_dispatches_already_inside():
    n = 3
    inside = threading.Barrier(n + 1)
    release = threading.Event()

    def blocked():
        with obs.device_span("query.scan.device", stage="probe"):
            inside.wait()
            release.wait(30)

    before = _counts(LEAN_DEVICE_INFLIGHT_SUM, LEAN_DEVICE_DISPATCHES)
    threads = [threading.Thread(target=blocked) for _ in range(n)]
    for th in threads:
        th.start()
    try:
        inside.wait(30)
        assert obs.device_inflight() == n

        def run():
            with obs.span("query"):
                with obs.device_span("query.scan.device", stage="probe"):
                    pass

        trace, _ = _one_trace(run)
    finally:
        release.set()
        for th in threads:
            th.join(30)
    (dev,) = [s for s in trace.spans if s.name == "query.scan.device"]
    assert dev.attributes["inflight"] == n
    # the blocked threads met 0, 1 and 2 in some order, the last one n
    assert _delta(before, (LEAN_DEVICE_INFLIGHT_SUM,
                           LEAN_DEVICE_DISPATCHES)) == [0 + 1 + 2 + n, n + 1]
    assert obs.device_inflight() == 0


# -- scan work: hand counts --------------------------------------------

def _index(slots, sizes, payload_on_device=True, seed=41):
    rng = np.random.default_rng(seed)
    n = sum(sizes)
    x = rng.uniform(-75, -73, n)
    y = rng.uniform(40, 42, n)
    t = rng.integers(MS, MS + 14 * DAY, n)
    idx = LeanZ3Index(period="week", generation_slots=slots,
                      payload_on_device=payload_on_device)
    s = 0
    for m in sizes:
        idx.append(x[s:s + m], y[s:s + m], t[s:s + m])
        s += m
    idx.block()
    return idx, x, y, t


def _hand_candidates(idx, box, lo, hi, max_ranges=2000):
    """Rows inside the covering ranges, per generation, counted on the
    host from the generation's keys and the planner's own ranges."""
    lo, hi = idx._clamp_time(lo, hi)
    budget = min(max_ranges * _bins_spanned(lo, hi, idx.period),
                 _MAX_RANGES_PER_WINDOW)
    plan = plan_z3_query(np.atleast_2d(np.asarray(box, np.float64)), lo,
                         hi, idx.period, budget, sfc=idx.sfc)
    per_gen = []
    for g in idx.generations:
        b = np.asarray(g.bins)[:g.n]
        z = np.asarray(g.z)[:g.n]
        per_gen.append(sum(
            int(((b == rb) & (z >= zl) & (z <= zh)).sum())
            for rb, zl, zh in zip(plan.rbin, plan.rzlo, plan.rzhi)))
    return per_gen, plan.num_ranges


def _brute(x, y, t, box, lo, hi):
    return int(((x >= box[0]) & (x <= box[2]) & (y >= box[1])
                & (y <= box[3]) & (t >= lo) & (t <= hi)).sum())


@pytest.mark.parametrize("payload_on_device", [True, False],
                         ids=["full", "keys"])
def test_scan_work_matches_a_hand_count(payload_on_device):
    """Three generations pad to a bucket of four and share the capacity
    of the largest total; the keys tier counts its hits after the host
    recheck."""
    slots = 8192
    idx, x, y, t = _index(slots, [slots, slots, 3000], payload_on_device)
    assert len(idx.generations) == 3 < _GEN_BUCKET
    box = (-74.6, 40.4, -73.7, 41.3)
    lo, hi = MS + 2 * DAY, MS + 9 * DAY
    idx.query([box], lo, hi)                      # compile
    per_gen, _ = _hand_candidates(idx, box, lo, hi)
    before = _counts(*SCAN)
    got = idx.query([box], lo, hi)
    cand, n_slots, hits, nbytes = _delta(before, SCAN)
    cap = gather_capacity(max(per_gen), minimum=idx.DEFAULT_CAPACITY)
    assert cand == sum(per_gen)
    assert n_slots == _GEN_BUCKET * cap
    assert hits == len(got) == _brute(x, y, t, box, lo, hi)
    assert 0 < hits < cand < n_slots
    assert nbytes >= cand * 4


def test_scan_bytes_follow_the_documented_formula():
    # 10 candidates x 28 B + 2 generations of 1024 slots x 16 ranges x
    # 2 seeks x 10 probes x 12 B
    assert scan_read_bytes(10, 28, 16, [1024, 1024], 12) == (
        10 * 28 + 2 * 16 * 2 * 10 * 12)
    assert scan_read_bytes(0, 28, 8, [1000], 12) == 8 * 2 * 10 * 12


def test_attr_scan_counts_rows_of_the_key():
    idx = LeanAttrIndex("mmsi", "long", generation_slots=4096)
    rng = np.random.default_rng(43)
    vals = rng.integers(0, 50, 9000)
    idx.append(vals, rng.integers(MS, MS + DAY, 9000))
    idx.query_equals(7)                           # compile
    before = _counts(*SCAN)
    got = idx.query_equals(7)
    cand, n_slots, hits, _ = _delta(before, SCAN)
    n_gens = len(idx.generations)
    padded = n_gens + (-n_gens) % _GEN_BUCKET
    assert cand == hits == len(got) == int((vals == 7).sum())
    assert n_slots == padded * gather_capacity(
        cand, minimum=idx.DEFAULT_CAPACITY)


# -- the serving and planning spans ------------------------------------

def _lean_store(name, n=20_000, attrs=""):
    rng = np.random.default_rng(47)
    ds = TpuDataStore(user="dispatch-obs")
    ds.create_schema(
        name, f"{attrs}dtg:Date,*geom:Point;geomesa.index.profile=lean,"
              "geomesa.lean.generation.slots=8192,"
              "geomesa.lean.compaction.factor=0")
    cols = {"dtg": rng.integers(MS, MS + 14 * DAY, n),
            "geom": (rng.uniform(-75, -73, n), rng.uniform(40, 42, n))}
    if attrs:
        cols["vessel"] = rng.integers(0, 40, n)
    ds.write(name, cols)
    return ds


Q = ("BBOX(geom,-74.5,40.5,-73.5,41.5) AND dtg DURING "
     "2018-01-03T00:00:00Z/2018-01-10T00:00:00Z")


def test_fused_batch_records_linger_and_backlog():
    ds = _lean_store("fz")
    ds.query_fused("fz", Q)                       # compile
    trace, res = _one_trace(lambda: ds.query_fused("fz", Q))
    assert len(res.positions) > 0
    (fuse,) = [s for s in trace.spans if s.name == "serving.fuse"]
    assert fuse.attributes["linger_ms"] >= 0
    # nothing else was dispatching when this batch closed
    assert fuse.attributes["inflight"] == 0
    devs = [s for s in trace.spans if s.name == "query.scan.device"]
    assert devs and all("enqueue_ms" in s.attributes for s in devs)


def test_sketch_builds_are_spanned_counted_and_mapped_to_no_stage():
    from geomesa_tpu.obs.attribution import SPAN_STAGE
    ds = _lean_store("sk", attrs="vessel:Long:index=true,")
    n0 = _counts(PLAN_SKETCH_BUILDS)[0]
    t0 = registry.timer(PLAN_SKETCH_BUILD_MS).count
    set_property("geomesa.planning.estimator.min.rows", 0)
    try:
        trace, _ = _one_trace(
            lambda: ds.query_result("sk", f"vessel = 7 AND {Q}"))
        builds = [s for s in trace.spans if s.name == "plan.sketch.build"]
        assert {s.attributes["index"] for s in builds} == {
            "z3", "attr:vessel"}
        assert _counts(PLAN_SKETCH_BUILDS)[0] - n0 == len(builds)
        assert registry.timer(PLAN_SKETCH_BUILD_MS).count - t0 == len(
            builds)
        # a warm repeat reads the cached tables: no build
        trace, _ = _one_trace(
            lambda: ds.query_result("sk", f"vessel = 8 AND {Q}"))
    finally:
        clear_property("geomesa.planning.estimator.min.rows")
    assert not [s for s in trace.spans if s.name == "plan.sketch.build"]
    assert "plan.sketch.build" not in SPAN_STAGE


def test_lean_query_result_materializes_under_its_own_span():
    ds = _lean_store("mz")
    ds.query_result("mz", Q)
    trace, res = _one_trace(lambda: ds.query_result("mz", Q))
    (mat,) = [s for s in trace.spans if s.name == "query.materialize"]
    assert mat.attributes["rows"] == len(res.positions) > 0
    assert obs.attribute(trace)["stages"]["materialize"] > 0


# -- the program's spans on the profiler's timeline --------------------

def _profiled_names(tmp_path, fn):
    from jax.profiler import ProfileData
    fn()                                          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    return {ev.name for p in ProfileData.from_file(str(path)).planes
            if not p.name.startswith("/device:")
            for ln in p.lines for ev in ln.events}


def test_recording_spans_reach_the_profiler(tmp_path):
    ds = _lean_store("pa")
    names = _profiled_names(tmp_path, lambda: ds.query_result("pa", Q))
    assert {"query", "query.plan", "query.scan.device"} <= names


def test_declined_spans_emit_no_annotation(tmp_path):
    ds = _lean_store("pd")
    set_property("geomesa.obs.sampler", "never")
    try:
        names = _profiled_names(tmp_path, lambda: ds.query_result("pd", Q))
    finally:
        clear_property("geomesa.obs.sampler")
    assert not {"query", "query.plan", "query.scan.device"} & names
