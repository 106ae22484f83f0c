"""Fused serving plane (ISSUE 17): the concurrent-client fusion
correctness matrix (bit-exact vs solo), per-tenant fairness, deadline
composition, recompile-free warm bucketing, admission interplay, and
the AdmissionGate FIFO/metrics satellites.

Named ``zz`` so the concurrency runs land late in the suite ordering,
after the correctness suites have exercised the clean solo paths.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.datastore import TpuDataStore
from geomesa_tpu.metrics import registry
from geomesa_tpu.resilience import (
    Backpressure, QueryTimeout, admission_gate,
)
from geomesa_tpu.serving import FusionScheduler, extract_fused_window
from geomesa_tpu.serving.fusion import _FuseQueue, _Member

MS_2018 = 1_514_764_800_000
DAY = 86_400_000
BBOX = "BBOX(geom,-76,39,-73,42)"

_SERVING_OPTS = ("geomesa.serving.fuse.enabled",
                 "geomesa.serving.fuse.window.ms",
                 "geomesa.serving.fuse.max.batch",
                 "geomesa.serving.tenant.queue.max",
                 "geomesa.serving.tenant.quantum")


@pytest.fixture(autouse=True)
def _clean_serving_config():
    for n in _SERVING_OPTS:
        config.clear_property(n)
    config.clear_property("geomesa.resilience.admission.max.concurrent")
    config.clear_property("geomesa.resilience.admission.queue.ms")
    gc.collect()
    admission_gate.reset()
    yield
    for n in _SERVING_OPTS:
        config.clear_property(n)
    config.clear_property("geomesa.resilience.admission.max.concurrent")
    config.clear_property("geomesa.resilience.admission.queue.ms")
    admission_gate.reset()


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


def _run_threads(fns):
    errs = []

    def wrap(fn):
        try:
            fn()
        except BaseException as e:  # surfaced after join
            errs.append(e)

    ts = [threading.Thread(target=wrap, args=(fn,)) for fn in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]


# -- fused vs solo: the bit-exactness matrix -------------------------------

def test_fused_bit_exact_concurrent_matrix():
    """Mixed bbox / bbox+time / OR-of-bbox clients fused concurrently
    return exactly the positions AND rows solo execution returns."""
    ds = _mk_store("sv_m1")
    queries = [f"BBOX(geom,{-75 + i * 0.03:.2f},40,"
               f"{-74.5 + i * 0.03:.2f},41)" for i in range(6)]
    queries += [
        "BBOX(geom,-75,40,-74.5,41) AND dtg DURING "
        "2018-01-01T00:00:00Z/2018-01-05T00:00:00Z",
        "BBOX(geom,-74.7,40.2,-74.3,40.8) AND dtg DURING "
        "2018-01-03T00:00:00Z/2018-01-09T00:00:00Z",
        "BBOX(geom,-75,40,-74.8,40.5) OR BBOX(geom,-74.2,40.5,-74,41)",
    ]
    solo = [ds.query_result("sv_m1", q) for q in queries]
    fused_before = registry.counter("serving.fused.requests").count
    results: list = [None] * len(queries)

    def run(i):
        def go():
            results[i] = ds.query_fused("sv_m1", queries[i],
                                        tenant=f"t{i % 3}")
        return go

    _run_threads([run(i) for i in range(len(queries))])
    for s, r in zip(solo, results):
        assert r.strategy.index == "fused"
        np.testing.assert_array_equal(s.positions, r.positions)
        for col in s.batch.columns:
            np.testing.assert_array_equal(
                np.asarray(s.batch.columns[col]),
                np.asarray(r.batch.columns[col]))
    assert (registry.counter("serving.fused.requests").count
            - fused_before) == len(queries)


def test_fused_bit_exact_with_tombstones():
    ds = _mk_store("sv_m2")
    # lean implicit ids: row r <=> str(r)
    assert ds.delete("sv_m2", [str(r) for r in range(400)]) == 400
    q = BBOX
    solo = ds.query_result("sv_m2", q)
    results: list = [None] * 4
    _run_threads([
        (lambda i=i: results.__setitem__(
            i, ds.query_fused("sv_m2", q))) for i in range(4)])
    for r in results:
        assert r.strategy.index == "fused"
        np.testing.assert_array_equal(solo.positions, r.positions)


def test_fused_empty_riders():
    """Riders whose window contains nothing demux empty, exactly like
    solo, without perturbing the non-empty members of the batch."""
    ds = _mk_store("sv_m3")
    hit, miss = BBOX, "BBOX(geom,10,10,11,11)"
    solo_hit = ds.query_result("sv_m3", hit).positions
    results: list = [None] * 4
    qs = [hit, miss, hit, miss]
    _run_threads([
        (lambda i=i: results.__setitem__(
            i, ds.query_fused("sv_m3", qs[i]))) for i in range(4)])
    np.testing.assert_array_equal(results[0].positions, solo_hit)
    np.testing.assert_array_equal(results[2].positions, solo_hit)
    assert len(results[1].positions) == 0
    assert len(results[3].positions) == 0


def test_mixed_schema_isolation():
    """Two schemas fusing concurrently never cross-contaminate: each
    schema's compatibility key is its own coalescing queue."""
    ds = _mk_store("sv_a")
    ds.create_schema(
        "sv_b",
        "dtg:Date,*geom:Point;geomesa.index.profile=lean,"
        "geomesa.lean.generation.slots=256,"
        "geomesa.lean.compaction.factor=0")
    rng = np.random.default_rng(7)
    nb = 1000
    ds.write("sv_b", {
        "dtg": rng.integers(MS_2018, MS_2018 + 13 * DAY, nb),
        "geom": (rng.uniform(-75, -74, nb), rng.uniform(40, 41, nb))})
    solo_a = ds.query_result("sv_a", BBOX).positions
    solo_b = ds.query_result("sv_b", BBOX).positions
    out: dict = {}
    _run_threads(
        [(lambda i=i: out.__setitem__(
            ("a", i), ds.query_fused("sv_a", BBOX))) for i in range(3)]
        + [(lambda i=i: out.__setitem__(
            ("b", i), ds.query_fused("sv_b", BBOX))) for i in range(3)])
    for i in range(3):
        np.testing.assert_array_equal(out[("a", i)].positions, solo_a)
        np.testing.assert_array_equal(out[("b", i)].positions, solo_b)


def test_incompatible_queries_bypass():
    """Interceptor-free compatibility gates: projections, sorts,
    limits, id/attribute filters, and non-lean schemas all take the
    solo path untouched."""
    ds = _mk_store("sv_byp")
    before = registry.counter("serving.bypass").count
    r = ds.query_fused("sv_byp", "INCLUDE")      # not a bbox predicate
    assert r.strategy.index != "fused"
    from geomesa_tpu.planning.planner import Query
    r = ds.query_fused("sv_byp", Query.of(BBOX, max_features=5))
    assert r.strategy.index != "fused"
    assert len(r.positions) == 5
    assert registry.counter("serving.bypass").count >= before + 2


def test_fuse_disabled_bypasses():
    ds = _mk_store("sv_off", n=500)
    config.set_property("geomesa.serving.fuse.enabled", False)
    r = ds.query_fused("sv_off", BBOX)
    assert r.strategy.index != "fused"
    assert len(r.positions) == 500


def test_extract_fused_window_shapes():
    ds = _mk_store("sv_ex", n=10)
    sft = ds.get_schema("sv_ex")
    from geomesa_tpu.filters.ast import (
        And, BBox, During, IdFilter, Include, Or,
    )
    b = BBox("geom", -75, 40, -74, 41)
    assert extract_fused_window(sft, b) == (((-75, 40, -74, 41),),
                                            None, None)
    boxes, lo, hi = extract_fused_window(
        sft, And((b, During("dtg", 5, 9))))
    assert boxes == ((-75, 40, -74, 41),) and (lo, hi) == (5, 9)
    assert extract_fused_window(
        sft, Or((b, BBox("geom", 0, 0, 1, 1))))[0] == (
        (-75, 40, -74, 41), (0, 0, 1, 1))
    assert extract_fused_window(sft, Include) is None
    assert extract_fused_window(sft, IdFilter(("x",))) is None
    assert extract_fused_window(
        sft, And((b, During("other", 1, 2)))) is None


# -- deadline composition --------------------------------------------------

def test_expired_rider_drops_without_poisoning_batch():
    """A rider whose deadline is already spent drops out before
    dispatch; live members of the same fused cycle stay bit-exact."""
    ds = _mk_store("sv_d1")
    solo = ds.query_result("sv_d1", BBOX).positions
    results: list = [None] * 3
    failures: list = []

    def live(i):
        results[i] = ds.query_fused("sv_d1", BBOX)

    def dead_raises():
        try:
            ds.query_fused("sv_d1", BBOX, timeout_ms=1e-6)
        except QueryTimeout:
            failures.append("raised")

    def dead_partial():
        r = ds.query_fused("sv_d1", BBOX, timeout_ms=1e-6,
                           partial_results=True)
        assert r.timed_out is True
        failures.append("partial")

    _run_threads([lambda: live(0), lambda: live(1), lambda: live(2),
                  dead_raises, dead_partial])
    assert sorted(failures) == ["partial", "raised"]
    for r in results:
        np.testing.assert_array_equal(solo, r.positions)
    assert admission_gate.inflight == 0


@pytest.mark.parametrize("partial", [True, False])
def test_expired_leader_times_out(partial):
    """A request alone leads its own batch: with its deadline already
    spent it times out (empty partial, or QueryTimeout), never a full
    answer under no deadline."""
    ds = _mk_store("sv_d3", n=500)
    if partial:
        r = ds.query_fused("sv_d3", BBOX, timeout_ms=1e-6,
                           partial_results=True)
        assert r.timed_out is True and len(r.positions) == 0
    else:
        with pytest.raises(QueryTimeout):
            ds.query_fused("sv_d3", BBOX, timeout_ms=1e-6)
    assert admission_gate.inflight == 0


def test_fused_generous_timeout_exact():
    ds = _mk_store("sv_d2", n=500)
    r = ds.query_fused("sv_d2", BBOX, timeout_ms=60_000.0)
    assert r.timed_out is False and len(r.positions) == 500


# -- per-tenant fairness ---------------------------------------------------

def test_drr_assembly_includes_starved_tenant():
    """Deficit-round-robin batch assembly: a tenant flooding the queue
    cannot push another tenant's head-of-line request out of the
    batch, even when the flood arrived first."""
    sched = FusionScheduler()
    q = _FuseQueue()

    def enq(tenant):
        m = _Member(((0.0, 0.0, 1.0, 1.0),), tenant, None, False)
        m.enqueued_at = time.perf_counter()
        dq = q.tenants.get(tenant)
        if dq is None:
            from collections import deque
            dq = q.tenants[tenant] = deque()
            q.rr.append(tenant)
        dq.append(m)
        q.size += 1
        return m

    leader = enq("flood")
    flood = [enq("flood") for _ in range(20)]
    quiet = enq("quiet")
    batch = sched._assemble(q, leader, max_batch=8, quantum=4)
    assert len(batch) == 8
    assert quiet in batch, "flooded tenant starved the quiet one"
    # the flood still gets the lion's share of the batch
    assert sum(1 for m in batch if m.tenant == "flood") == 7
    # FIFO within a tenant: the flood's earliest riders ride first
    assert all(m in batch for m in flood[:6])


def test_tenant_queue_ceiling_sheds():
    """A tenant at its queue.max ceiling sheds Backpressure instead of
    growing the queue; other tenants are unaffected."""
    config.set_property("geomesa.serving.tenant.queue.max", 1)
    config.set_property("geomesa.serving.fuse.window.ms", 1000.0)
    config.set_property("geomesa.serving.fuse.max.batch", 64)
    sched = FusionScheduler()
    n_done = []

    def dispatch(ws):
        return [np.empty(0, dtype=np.int64) for _ in ws]

    def leader():
        sched.submit(("k",), ((0, 0, 1, 1),), dispatch, tenant="hot",
                     schema="s")
        n_done.append("leader")

    t = threading.Thread(target=leader)
    t.start()
    deadline = time.time() + 5.0
    while sched.queued == 0 and time.time() < deadline:
        time.sleep(0.002)
    shed_before = registry.counter("serving.tenant.shed").count
    with pytest.raises(Backpressure):
        sched.submit(("k",), ((0, 0, 1, 1),), dispatch, tenant="hot",
                     schema="s")
    assert registry.counter("serving.tenant.shed").count == \
        shed_before + 1
    assert registry.counter("serving.tenant.shed.hot").count >= 1
    # a different tenant still enters the same batch
    ok = []

    def other():
        sched.submit(("k",), ((0, 0, 1, 1),), dispatch, tenant="cool",
                     schema="s")
        ok.append(True)

    t2 = threading.Thread(target=other)
    t2.start()
    t.join(10)
    t2.join(10)
    assert n_done == ["leader"] and ok == [True]


# -- warm-path recompile & token hygiene -----------------------------------

def test_warm_fused_path_recompile_free():
    """Capacity bucketing: batch sizes pad to powers of two, so once a
    bucket is warm re-dispatching ANY size in it is recompile-free."""
    from geomesa_tpu.obs import compile_count
    ds = _mk_store("sv_w1")
    w = (((-75.0, 40.0, -74.0, 41.0),), MS_2018, MS_2018 + 13 * DAY)
    solo = ds.query_result(
        "sv_w1", "BBOX(geom,-75,40,-74,41) AND dtg DURING "
        "2018-01-01T00:00:00Z/2018-01-14T00:00:00Z").positions
    # warm the 1-, 2- and 4-window buckets
    for n in (1, 2, 3, 4):
        ds._fused_windows_dispatch("sv_w1", [w] * n)
    before = compile_count()
    for n in (1, 2, 3, 4):
        hits = ds._fused_windows_dispatch("sv_w1", [w] * n)
        assert len(hits) == n
        for h in hits:
            np.testing.assert_array_equal(h, solo)
    assert compile_count() == before, "warm fused path recompiled"


def test_no_leaked_admission_tokens_across_fused_cycles():
    """100 fused cycles (mixed solo/concurrent, expired riders, empty
    windows) leave the admission gate at zero in-flight."""
    ds = _mk_store("sv_t1", n=800)
    for i in range(40):
        ds.query_fused("sv_t1", BBOX, tenant=f"t{i % 4}")
    for _ in range(20):
        _run_threads([
            lambda: ds.query_fused("sv_t1", BBOX),
            lambda: ds.query_fused("sv_t1", "BBOX(geom,10,10,11,11)"),
            lambda: ds.query_fused("sv_t1", BBOX, timeout_ms=1e-6,
                                   partial_results=True),
        ])
    assert admission_gate.inflight == 0
    assert ds._fusion.queued == 0


# -- AdmissionGate satellites ----------------------------------------------

def test_admission_fifo_ticket_ordering():
    """Queued acquires admit in ARRIVAL order: a late arrival cannot
    barge past long-queued waiters when a slot frees (satellite pin)."""
    config.set_property("geomesa.resilience.admission.max.concurrent", 1)
    config.set_property("geomesa.resilience.admission.queue.ms", 30_000.0)
    admission_gate.reset()
    first = admission_gate.acquire("fifo")
    order: list = []
    lock = threading.Lock()
    started = threading.Semaphore(0)

    def waiter(i):
        started.release()
        tok = admission_gate.acquire("fifo")
        with lock:
            order.append(i)
        time.sleep(0.002)
        tok.release()

    threads = []
    for i in range(5):
        t = threading.Thread(target=waiter, args=(i,))
        threads.append(t)
        t.start()
        started.acquire()
        # the waiter thread has STARTED; give it time to enqueue its
        # ticket before the next one starts, so arrival order is known
        for _ in range(200):
            if admission_gate._ticket_count() >= i + 1:
                break
            time.sleep(0.001)
    first.release()
    for t in threads:
        t.join(30)
    assert order == [0, 1, 2, 3, 4]
    assert admission_gate.inflight == 0


def test_disabled_gate_records_admission_metrics():
    """Satellite pin: the disabled-gate fast path counts
    resilience.admission.admitted and samples the queue timer, so
    dashboards don't undercount when the gate is off."""
    admission_gate.reset()
    admitted = registry.counter("resilience.admission.admitted").count
    timer_n = registry.timer("resilience.admission.queue_ms").count
    tok = admission_gate.acquire("off")
    try:
        assert registry.counter(
            "resilience.admission.admitted").count == admitted + 1
        assert registry.timer(
            "resilience.admission.queue_ms").count == timer_n + 1
    finally:
        tok.release()
    assert admission_gate.inflight == 0


def test_serving_metrics_visible_in_prom():
    """The serving.* family is scrapeable at /metrics.prom."""
    ds = _mk_store("sv_p1", n=500)
    _run_threads([
        (lambda: ds.query_fused("sv_p1", BBOX)) for _ in range(3)])
    from geomesa_tpu.obs import prometheus_text
    text = prometheus_text(registry.snapshot())
    assert "serving_fused_batches" in text or \
        "serving.fused.batches" in text
    assert "serving_fanin" in text or "serving.fanin" in text
    assert "serving_coalesce_ms" in text or \
        "serving.coalesce_ms" in text
