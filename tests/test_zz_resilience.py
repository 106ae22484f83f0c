"""Resilience layer (ISSUE 16): deadline/partial semantics, admission
control, degraded execution, the circuit breaker, the deterministic
fault-injection chaos matrix, eager interceptor wiring, and the
bounded web serving path.

Named ``zz`` so the chaos runs land late in the suite ordering, after
the correctness suites have exercised the clean paths.
"""

from __future__ import annotations

import gc
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.resilience import (
    Backpressure, CancelScope, CircuitBreaker, FAULT_POINTS,
    FaultInjected, QueryTimeout, admission_gate, breaker, check_cancel,
    classify_device_failure, deadline_scope, fault_point,
)

MS_2018 = 1_514_764_800_000
DAY = 86_400_000
BBOX = "BBOX(geom,-76,39,-73,42)"


def _clear(*names):
    for n in names:
        config.clear_property(n)


@pytest.fixture(autouse=True)
def _clean_resilience_config():
    """Every test starts and ends with the layer fully disarmed."""
    names = ("geomesa.resilience.fault.points",
             "geomesa.resilience.fault.seed",
             "geomesa.resilience.admission.max.concurrent",
             "geomesa.resilience.admission.queue.ms",
             "geomesa.resilience.hbm.headroom",
             "geomesa.resilience.retry.max",
             "geomesa.resilience.breaker.threshold",
             "geomesa.resilience.breaker.cooldown.s")
    _clear(*names)
    breaker.reset()
    # streams abandoned by OTHER suites release their admission token
    # via ArrowStream.__del__ — collect them, then zero the gate so the
    # inflight assertions here are order-independent
    gc.collect()
    admission_gate.reset()
    yield
    _clear(*names)
    breaker.reset()


def _mk_store(name: str, n: int = 3000, slots: int = 256) -> TpuDataStore:
    ds = TpuDataStore()
    ds.create_schema(
        name,
        "dtg:Date,*geom:Point;geomesa.index.profile=lean,"
        f"geomesa.lean.generation.slots={slots},"
        "geomesa.lean.compaction.factor=0")
    rng = np.random.default_rng(11)
    ds.write(name, {
        "dtg": rng.integers(MS_2018, MS_2018 + 13 * DAY, n),
        "geom": (rng.uniform(-75, -74, n), rng.uniform(40, 41, n))})
    return ds


# -- deadline / cancellation units -----------------------------------------

def test_expired_deadline_raises_query_timeout():
    ds = _mk_store("rz_t1")
    with pytest.raises(QueryTimeout):
        ds.query_result("rz_t1", BBOX, timeout_ms=1e-6)


def test_expired_deadline_partial_returns_flagged_result():
    ds = _mk_store("rz_t2")
    res = ds.query_result("rz_t2", BBOX, timeout_ms=1e-6,
                          partial_results=True)
    assert res.timed_out is True
    # partial means "exact over what WAS scanned" — with an
    # already-expired deadline that is nothing
    assert len(res.positions) == 0


def test_no_timeout_is_unaffected():
    ds = _mk_store("rz_t3", n=500)
    res = ds.query_result("rz_t3", BBOX)
    assert res.timed_out is False
    assert len(res.positions) == 500


def test_generous_timeout_returns_full_result():
    ds = _mk_store("rz_t4", n=500)
    res = ds.query_result("rz_t4", BBOX, timeout_ms=60_000.0)
    assert res.timed_out is False
    assert len(res.positions) == 500


def test_query_windows_timeout():
    ds = _mk_store("rz_t5")
    with pytest.raises(QueryTimeout):
        ds.query_windows(
            "rz_t5",
            [([(-76.0, 39.0, -73.0, 42.0)], MS_2018, MS_2018 + 13 * DAY)],
            timeout_ms=1e-6)
    outs = ds.query_windows(
        "rz_t5",
        [([(-76.0, 39.0, -73.0, 42.0)], MS_2018, MS_2018 + 13 * DAY)],
        timeout_ms=1e-6, partial_results=True)
    assert len(outs) == 1 and len(outs[0]) == 0


def test_cancel_scope_poll_latches_once():
    sc = CancelScope(timeout_ms=1e-6, partial=True)
    assert sc.poll() is True
    assert sc.timed_out is True
    assert sc.poll() is True          # latched, idempotent
    with deadline_scope(scope=sc):
        assert check_cancel("unit") is True   # partial → True, no raise


def test_check_cancel_no_scope_is_free():
    assert check_cancel("unit") is False


def test_expired_arrow_stream_is_wellformed_eos():
    pa = pytest.importorskip("pyarrow")
    ds = _mk_store("rz_t6", n=400)
    stream = ds.query_arrow("rz_t6", BBOX, chunk_rows=64,
                            timeout_ms=1e-6, partial_results=True)
    blob = stream.to_ipc_bytes()
    # a stock reader opens the truncated stream cleanly: schema header
    # + end-of-stream, zero rows delivered
    table = pa.ipc.open_stream(blob).read_all()
    assert table.num_rows == 0
    gc.collect()
    assert admission_gate.inflight == 0


# -- admission control ------------------------------------------------------

def test_backpressure_sheds_when_slots_held():
    ds = _mk_store("rz_a1", n=200)
    config.set_property("geomesa.resilience.admission.max.concurrent", 1)
    config.set_property("geomesa.resilience.admission.queue.ms", 5.0)
    tok = admission_gate.acquire("rz_a1")
    try:
        with pytest.raises(Backpressure) as ei:
            ds.query_result("rz_a1", BBOX)
        assert ei.value.retry_after_s > 0
    finally:
        tok.release()
    # slot free again: the same query admits and runs
    assert len(ds.query_result("rz_a1", BBOX).positions) == 200
    assert admission_gate.inflight == 0


def test_hbm_budget_sheds():
    from geomesa_tpu.metrics import registry as metrics
    ds = _mk_store("rz_a2", n=100)
    g = metrics.gauge("storage.total.device_bytes")
    prior = g.value
    config.set_property("geomesa.resilience.hbm.headroom", 1024)
    config.set_property("geomesa.resilience.admission.queue.ms", 5.0)
    g.set(1 << 30)
    try:
        with pytest.raises(Backpressure):
            ds.query_result("rz_a2", BBOX)
        # back under budget → admitted again (prior may itself exceed
        # the tiny test headroom when earlier suites published real
        # storage bytes, so prove recovery at 0, then restore)
        g.set(0)
        assert len(ds.query_result("rz_a2", BBOX).positions) == 100
    finally:
        g.set(prior)


def test_admission_token_release_is_idempotent():
    tok = admission_gate.acquire("unit")
    assert admission_gate.inflight >= 1
    tok.release()
    tok.release()
    assert admission_gate.inflight == 0


def test_no_leaked_tokens_after_100_cycles():
    ds = _mk_store("rz_a3", n=300)
    config.set_property("geomesa.resilience.admission.max.concurrent", 4)
    for i in range(100):
        if i % 10 == 3:
            # streamed drains release from the generator's finally
            for _ in ds.query_arrow("rz_a3", BBOX, chunk_rows=128):
                pass
        elif i % 10 == 7:
            with pytest.raises(QueryTimeout):
                ds.query_result("rz_a3", BBOX, timeout_ms=1e-6)
        else:
            ds.query_result("rz_a3", BBOX)
    gc.collect()
    assert admission_gate.inflight == 0


# -- degraded execution / breaker -------------------------------------------

def test_classifier():
    assert classify_device_failure(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory while trying "
                     "to allocate")) == "transient"
    assert classify_device_failure(RuntimeError("XLA hlo broke")) == "poison"
    assert classify_device_failure(ValueError("whatever")) == "poison"


def test_circuit_breaker_trip_and_halfopen():
    config.set_property("geomesa.resilience.breaker.threshold", 2)
    config.set_property("geomesa.resilience.breaker.cooldown.s", 0.0)
    cb = CircuitBreaker()
    key = ("unit", 1)
    assert cb.allows(key)
    cb.record_failure(key)
    assert cb.allows(key)
    cb.record_failure(key)
    # cooldown 0 → instantly half-open: one probe dispatch allowed,
    # and a success fully closes the circuit
    assert cb.allows(key)
    cb.record_success(key)
    cb.record_failure(key)
    assert cb.allows(key)


def test_degraded_query_stays_exact():
    """The degraded-mode contract: a transient device failure demotes
    the generation to host and the query still returns exactly the
    un-degraded result."""
    from geomesa_tpu.metrics import RESILIENCE_DEGRADED, registry as metrics
    ds = _mk_store("rz_d1", n=1500, slots=256)
    baseline = sorted(ds.query_result("rz_d1", BBOX).positions.tolist())
    before = metrics.counter(RESILIENCE_DEGRADED).count
    config.set_property("geomesa.resilience.fault.points",
                        "device.dispatch:1=oom")
    degraded = sorted(ds.query_result("rz_d1", BBOX).positions.tolist())
    assert degraded == baseline
    assert metrics.counter(RESILIENCE_DEGRADED).count > before
    # and the store keeps serving exactly after disarming
    config.clear_property("geomesa.resilience.fault.points")
    assert sorted(ds.query_result("rz_d1", BBOX).positions.tolist()) \
        == baseline


def test_poison_dispatch_propagates():
    ds = _mk_store("rz_d2", n=800, slots=256)
    config.set_property("geomesa.resilience.fault.points",
                        "device.dispatch:1=error")
    with pytest.raises(FaultInjected):
        ds.query_result("rz_d2", BBOX)
    config.clear_property("geomesa.resilience.fault.points")
    assert len(ds.query_result("rz_d2", BBOX).positions) == 800


# -- fault-injection harness ------------------------------------------------

def test_unknown_fault_point_spec_rejected():
    config.set_property("geomesa.resilience.fault.points", "no.such.point")
    with pytest.raises(ValueError, match="no.such.point"):
        fault_point("ingest.append")


def test_fault_trigger_fires_on_exact_nth_hit():
    from geomesa_tpu.resilience.faults import FaultRegistry
    config.set_property("geomesa.resilience.fault.points",
                        "arrow.flush:2=error")
    reg = FaultRegistry()
    reg.maybe_fail("arrow.flush")           # hit 1: armed for hit 2
    with pytest.raises(FaultInjected):
        reg.maybe_fail("arrow.flush")       # hit 2 fires
    reg.maybe_fail("arrow.flush")           # hit 3: past the trigger


def test_probabilistic_fault_is_seed_deterministic():
    from geomesa_tpu.resilience.faults import FaultRegistry

    def fire_pattern():
        config.set_property("geomesa.resilience.fault.points",
                            "host.spill:0.5=error")
        config.set_property("geomesa.resilience.fault.seed", 42)
        reg = FaultRegistry()
        out = []
        for _ in range(32):
            try:
                reg.maybe_fail("host.spill")
                out.append(False)
            except FaultInjected:
                out.append(True)
        return out

    a, b = fire_pattern(), fire_pattern()
    assert a == b and any(a) and not all(a)


# -- the chaos matrix: fault point x operation ------------------------------

def test_chaos_ingest_append_loses_only_that_slice():
    ds = _mk_store("rz_c1", n=500)
    config.set_property("geomesa.resilience.fault.points",
                        "ingest.append:1=error")
    with pytest.raises(FaultInjected):
        ds.write("rz_c1", {
            "dtg": np.full(50, MS_2018, dtype=np.int64),
            "geom": (np.full(50, -74.5), np.full(50, 40.5))})
    # atomic slice loss: the failed write added nothing
    assert len(ds.query_result("rz_c1", BBOX).positions) == 500
    config.clear_property("geomesa.resilience.fault.points")
    ds.write("rz_c1", {
        "dtg": np.full(50, MS_2018, dtype=np.int64),
        "geom": (np.full(50, -74.5), np.full(50, 40.5))})
    assert len(ds.query_result("rz_c1", BBOX).positions) == 550


def test_chaos_host_spill_leaves_generation_queryable():
    ds = _mk_store("rz_c2", n=1200, slots=256)
    idx = ds._store("rz_c2")._indexes["z3"]
    baseline = len(ds.query_result("rz_c2", BBOX).positions)
    gen = next(g for g in idx.generations if g.tier == "full")
    config.set_property("geomesa.resilience.fault.points",
                        "host.spill:1=error")
    with pytest.raises(FaultInjected):
        idx._spill(gen)
    # the fault fired BEFORE any transfer: the generation is still
    # device-resident and the store serves the identical result
    assert gen.tier == "full"
    config.clear_property("geomesa.resilience.fault.points")
    assert len(ds.query_result("rz_c2", BBOX).positions) == baseline
    # a clean spill afterwards works and stays exact
    idx._spill(gen)
    assert gen.tier == "host"
    assert len(ds.query_result("rz_c2", BBOX).positions) == baseline


def test_chaos_arrow_flush_releases_admission_slot():
    ds = _mk_store("rz_c3", n=400)
    config.set_property("geomesa.resilience.admission.max.concurrent", 2)
    config.set_property("geomesa.resilience.fault.points",
                        "arrow.flush:1=error")
    from geomesa_tpu.arrow.stream import ipc_chunks
    stream = ds.query_arrow("rz_c3", BBOX, chunk_rows=64)
    with pytest.raises(FaultInjected):
        for _ in ipc_chunks(stream):
            pass
    del stream
    gc.collect()
    assert admission_gate.inflight == 0
    config.clear_property("geomesa.resilience.fault.points")
    assert len(ds.query_result("rz_c3", BBOX).positions) == 400


def test_abandoned_stream_releases_admission_slot():
    # a stream created but NEVER iterated: the drain generator's
    # finally can't run (its body was never entered), so the release
    # must come from ArrowStream.close()/__del__
    ds = _mk_store("rz_c6", n=200)
    stream = ds.query_arrow("rz_c6", BBOX, chunk_rows=64)
    assert admission_gate.inflight == 1
    del stream
    gc.collect()
    assert admission_gate.inflight == 0
    # explicit close works too, and is idempotent
    stream = ds.query_arrow("rz_c6", BBOX, chunk_rows=64)
    stream.close()
    stream.close()
    assert admission_gate.inflight == 0


def test_chaos_killed_web_drain_counts_abort_and_releases_token():
    pytest.importorskip("pyarrow")
    from geomesa_tpu.metrics import registry as metrics
    from geomesa_tpu.web.app import WebApp
    ds = _mk_store("rz_c4", n=400)
    app = WebApp(ds)
    config.set_property("geomesa.resilience.admission.max.concurrent", 2)
    config.set_property("geomesa.resilience.fault.points",
                        "arrow.flush:1=error")
    before = metrics.counter("web.stream_aborted").count
    body = app({"PATH_INFO": "/query", "REQUEST_METHOD": "GET",
                "QUERY_STRING": "schema=rz_c4"}, lambda s, h: None)
    with pytest.raises(FaultInjected):
        for _ in body:
            pass
    del body
    gc.collect()
    assert metrics.counter("web.stream_aborted").count == before + 1
    assert admission_gate.inflight == 0
    config.clear_property("geomesa.resilience.fault.points")
    assert len(ds.query_result("rz_c4", BBOX).positions) == 400


def test_chaos_compaction_interrupt_resumes():
    from geomesa_tpu.index.lsm import compact_incremental
    merged: list = []
    groups = [["a"], ["b"], ["c"]]

    def plan():
        return [g for g in groups if g[0] not in merged]

    def merge_one(group):
        merged.append(group[0])

    config.set_property("geomesa.resilience.fault.points",
                        "compaction.merge_step:1=error")
    with pytest.raises(FaultInjected):
        compact_incremental(plan, merge_one)
    # interrupted BEFORE the first merge: nothing half-applied
    assert merged == []
    # the next compact() replans from the survivors and finishes
    assert compact_incremental(plan, merge_one) == 3
    assert merged == ["a", "b", "c"]


def test_chaos_pyramid_build_interrupt_resumes():
    from geomesa_tpu.index.pyramid import pyramid_spec
    ds = _mk_store("rz_py")
    idx = ds._store("rz_py")._lean_index()
    sealed = [g.gen_id for g in idx.generations[:-1]]
    assert len(sealed) > 1
    want = ds.density_tile("rz_py", 0, 0, 0)   # the unbuilt store's answer
    cache = idx._pyramid_cache.spec_cache(pyramid_spec(
        config.DensityProperties.PYRAMID_BASE.to_int()))
    config.set_property("geomesa.resilience.fault.points",
                        "pyramid.build:1=error")
    with pytest.raises(FaultInjected):
        ds.build_pyramids("rz_py")
    # interrupted BEFORE the first sweep: no pyramid cached
    assert not any(gid in cache for gid in sealed)
    _clear("geomesa.resilience.fault.points")
    # the next pass builds every sealed generation, and tiles stay exact
    assert ds.build_pyramids("rz_py") == len(sealed)
    assert all(gid in cache for gid in sealed)
    np.testing.assert_array_equal(ds.density_tile("rz_py", 0, 0, 0), want)


def test_chaos_grid_covers_every_cataloged_point():
    """Every point in the FAULT_POINTS declaration has a chaos test in
    this module exercising it by name (the matrix stays total as
    points are added)."""
    import pathlib
    src = pathlib.Path(__file__).read_text(encoding="utf-8")
    for point in FAULT_POINTS:
        assert src.count(f'"{point}') >= 1, f"no chaos arm for {point}"


# -- recompile cleanliness --------------------------------------------------

def test_warm_timeout_queries_do_not_recompile():
    from geomesa_tpu.obs import compile_count
    ds = _mk_store("rz_r1", n=600)
    ds.query_result("rz_r1", BBOX)                         # warm
    ds.query_result("rz_r1", BBOX, timeout_ms=60_000.0)    # warm w/ scope
    c0 = compile_count()
    ds.query_result("rz_r1", BBOX)
    ds.query_result("rz_r1", BBOX, timeout_ms=30_000.0)
    ds.query_result("rz_r1", BBOX, timeout_ms=45_000.0,
                    partial_results=True)
    assert compile_count() - c0 == 0


# -- eager interceptor wiring (satellite) -----------------------------------

def _install_test_interceptors():
    mod = types.ModuleType("rz_test_interceptors")

    class RewriteToBBox:
        """Rewrites every query to the test bbox — the 'inject a
        default spatial bound' interceptor shape."""

        def rewrite(self, sft, query):
            from geomesa_tpu.planning.planner import Query
            return Query.of(BBOX, max_features=query.max_features)

    class RejectAll:
        def rewrite(self, sft, query):
            raise ValueError("rejected by policy interceptor")

    mod.RewriteToBBox = RewriteToBBox
    mod.RejectAll = RejectAll
    sys.modules["rz_test_interceptors"] = mod


def test_interceptor_rewrite_wired_at_schema_load():
    _install_test_interceptors()
    ds = TpuDataStore()
    ds.create_schema(
        "rz_i1",
        "dtg:Date,*geom:Point;geomesa.query.interceptors="
        "rz_test_interceptors:RewriteToBBox")
    # resolved EAGERLY: the instance exists before any query runs
    assert type(ds._interceptors["rz_i1"][0]).__name__ == "RewriteToBBox"
    n = 10
    ds.write("rz_i1", {
        "dtg": np.full(n, MS_2018, dtype=np.int64),
        "geom": (np.full(n, -74.5), np.full(n, 40.5))})
    ds.write("rz_i1", {
        "dtg": np.full(n, MS_2018, dtype=np.int64),
        "geom": (np.full(n, 10.0), np.full(n, 10.0))})   # outside bbox
    # INCLUDE is rewritten to the bbox: only the in-bbox rows return
    assert len(ds.query_result("rz_i1", "INCLUDE").positions) == n


def test_interceptor_reject_applies():
    _install_test_interceptors()
    ds = TpuDataStore()
    ds.create_schema(
        "rz_i2",
        "dtg:Date,*geom:Point;geomesa.query.interceptors="
        "rz_test_interceptors:RejectAll")
    with pytest.raises(ValueError, match="rejected by policy"):
        ds.query_result("rz_i2", BBOX)


def test_typoed_interceptor_fails_create_schema_not_first_query():
    ds = TpuDataStore()
    with pytest.raises((ImportError, AttributeError)):
        ds.create_schema(
            "rz_i3",
            "dtg:Date,*geom:Point;geomesa.query.interceptors="
            "no_such_module:Nope")


# -- bounded web serving (satellite) ----------------------------------------

def test_bounded_app_sheds_503_on_saturation():
    import json
    from geomesa_tpu.web.wsgi import BoundedApp

    def app(environ, start_response):
        start_response("200 OK", [("Content-Type", "text/plain")])
        return [b"ok"]

    bounded = BoundedApp(app, max_concurrent=1)
    bounded._sem.acquire()        # simulate one request in flight
    seen = []
    body = bounded({}, lambda s, h: seen.append((s, h)))
    assert seen[0][0].startswith("503")
    assert any(h[0] == "Retry-After" for h in seen[0][1])
    assert json.loads(b"".join(body))["error"]
    bounded._sem.release()
    seen.clear()
    assert b"".join(bounded({}, lambda s, h: seen.append((s, h)))) == b"ok"
    assert seen[0][0].startswith("200")
    # the slot is back after the body drained
    assert bounded._sem.acquire(blocking=False)
    bounded._sem.release()


def test_router_maps_backpressure_and_timeout():
    from geomesa_tpu.web.wsgi import Router

    def shed(method, params, environ):
        raise Backpressure("too busy", retry_after_s=2.0)

    def slow(method, params, environ):
        raise QueryTimeout("deadline", elapsed_ms=10.0)

    router = Router([(r"^/shed$", shed), (r"^/slow$", slow)])
    seen = []
    router.dispatch({"PATH_INFO": "/shed"},
                    lambda s, h: seen.append((s, h)))
    assert seen[0][0].startswith("503")
    assert ("Retry-After", "2") in seen[0][1]
    seen.clear()
    router.dispatch({"PATH_INFO": "/slow"},
                    lambda s, h: seen.append((s, h)))
    assert seen[0][0].startswith("504")


def test_query_stream_accepts_timeout_params():
    pytest.importorskip("pyarrow")
    import pyarrow as pa
    from geomesa_tpu.web.app import WebApp
    ds = _mk_store("rz_w1", n=300)
    app = WebApp(ds)
    seen = []
    body = app({"PATH_INFO": "/query", "REQUEST_METHOD": "GET",
                "QUERY_STRING": "schema=rz_w1&timeout_ms=60000"},
               lambda s, h: seen.append((s, h)))
    blob = b"".join(body)
    assert seen[0][0].startswith("200")
    assert pa.ipc.open_stream(blob).read_all().num_rows == 300
    seen.clear()
    body = app({"PATH_INFO": "/query", "REQUEST_METHOD": "GET",
                "QUERY_STRING": ("schema=rz_w1&timeout_ms=1"
                                 "&partial=1")},
               lambda s, h: seen.append((s, h)))
    blob = b"".join(body)
    assert seen[0][0].startswith("200")
    # expired partial stream: fewer (possibly zero) rows, valid EOS
    assert pa.ipc.open_stream(blob).read_all().num_rows <= 300
    gc.collect()
    assert admission_gate.inflight == 0
