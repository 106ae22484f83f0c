"""The window probe's trace reductions (``program_ms``,
``idle_gaps_by_span``) on synthetic planes, its window readings from
counter snapshots, and the readers of the program's own counters."""

from collections import namedtuple

import pytest

from benchmark import run
from benchmark import trace_reduce as tr
from benchmark import window_probe as wp

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")

READERS = ("device_backlog", "dispatch_enqueue_ms", "scan_slot_fill",
           "scan_candidates_per_hit", "sketch_build_s")


def _planes(modules, ops, threads):
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev(*e) for e in modules]),
        Line("XLA Ops", [Ev(*e) for e in ops])])
    host = Plane("/host:CPU", [Line(f"t{i}", [Ev(*e) for e in evs])
                               for i, evs in enumerate(threads)])
    return [host, dev]


def test_program_ms_strips_fingerprints_and_weights_by_runs():
    planes = _planes(
        [("jit__lean_scan_exact_coded_10030454298026921029_", 100, 4_000_000),
         ("jit__lean_scan_exact_coded_12527003359610268857_", 5e6, 2_000_000),
         ("jit__lean_count_multi_9721018305249006197_", 8e6, 1_000_000),
         ("jit__lean_count_multi_9721018305249006197_", 50, 1_000_000)],
        [], [[(tr.WINDOW_EVENT, 100, 10_000_000)]])
    got = wp.program_ms(planes)
    # two shapes of one program are one family; a run starting before
    # the window is not counted
    assert got == {"jit__lean_scan_exact_coded": [pytest.approx(3.0), 2],
                   "jit__lean_count_multi": [pytest.approx(1.0), 1]}
    assert wp.family("jit__attr_scan_coded_1325648879109681559_") == \
        "jit__attr_scan_coded"
    assert wp.family("jit__lean_count_multi(9721018305249006197)") == \
        "jit__lean_count_multi"


def test_idle_gaps_go_to_the_innermost_program_span():
    # window [0, 1000); busy [100, 300) and [600, 700): gaps [0, 100),
    # [300, 600), [700, 1000)
    planes = _planes(
        [], [("op", 100, 200), ("op", 600, 100)],
        [[(tr.WINDOW_EVENT, 0, 1000), ("bench.facade.knn", 0, 1000),
          ("query", 250, 400), ("query.plan", 320, 200)],
         [("serving.fuse", 0, 90), ("jit_x", 300, 300)]])
    gaps = dict(wp.idle_gaps_by_span(planes))
    # [300, 600): query and query.plan cover 280 and 200 ns of it,
    # query wins on overlap; [0, 100): serving.fuse; [700, 1000): only
    # the bench annotation
    assert gaps == {"query": pytest.approx(300e-9),
                    "serving.fuse": pytest.approx(100e-9),
                    "bench.facade.knn": pytest.approx(300e-9)}
    # the same gaps as trace_reduce finds them
    assert sum(gaps.values()) == pytest.approx(
        dict(tr.reduce(planes)["idle_gaps"])["bench.facade.knn"])


def test_nested_spans_over_a_whole_gap_label_the_child():
    planes = _planes([], [("op", 0, 100), ("op", 200, 100)],
                     [[(tr.WINDOW_EVENT, 0, 300), ("query", 0, 300),
                       ("query.scan", 50, 200),
                       ("query.scan.device", 90, 150)]])
    assert wp.idle_gaps_by_span(planes) == [
        ["query.scan.device", pytest.approx(100e-9)]]


def test_a_gap_with_no_span_is_no_bench_span():
    planes = _planes([], [("op", 0, 100)], [[(tr.WINDOW_EVENT, 0, 200)]])
    assert wp.idle_gaps_by_span(planes) == [
        [tr.NO_SPAN, pytest.approx(100e-9)]]
    assert wp.idle_gaps_by_span(planes[:1]) == []
    assert wp.program_ms(planes[1:]) == {}


def test_window_readings_from_edge_snapshots():
    before = {"lean.device.dispatches": 10, "lean.device.inflight.sum": 5,
              "lean.scan.candidates": 100, "lean.scan.slots": 1000,
              "lean.scan.hits": 10, "lean.device.enqueue.ms": [10, 1.0],
              "plan.sketch.build.ms": [3, 4500.0]}
    after = {"lean.device.dispatches": 30, "lean.device.inflight.sum": 85,
             "lean.scan.candidates": 500, "lean.scan.slots": 5000,
             "lean.scan.hits": 50, "lean.device.enqueue.ms": [30, 5.0],
             "plan.sketch.build.ms": [3, 4500.0]}
    out = wp.readings(before, after, completed=10, window_s=2.0,
                      programs={"jit__lean_scan_exact_coded": [40.0, 4],
                                "jit__lean_count_multi": [10.0, 4]})
    assert out["device_backlog"] == pytest.approx(4.0)
    assert out["dispatch_enqueue_ms"] == pytest.approx(0.2)
    assert out["dispatches_per_request"] == pytest.approx(2.0)
    assert out["scan_slot_fill"] == pytest.approx(10.0)
    assert out["scan_candidates_per_hit"] == pytest.approx(10.0)
    assert out["sketch_build_s"] == pytest.approx(4.5)
    assert out["scan_program_ms"] == pytest.approx(40.0)
    # 2 dispatches a request x 25 ms a program x 5 requests/s
    assert out["implied_busy"] == pytest.approx(0.25)
    # nothing to read: every ratio is left out
    assert set(wp.readings({}, {}, 0, 2.0, {})) == {"window_delta",
                                                    "completed"}


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_without_the_program_counters(name):
    from geomesa_tpu.metrics import MetricRegistry
    assert run.plugin("metrics", name).read(
        run.Readings(), registry=MetricRegistry()) is None


def test_readers_read_the_program_counters():
    from geomesa_tpu.metrics import MetricRegistry
    reg = MetricRegistry()
    for name, n in (("lean.device.dispatches", 8),
                    ("lean.device.inflight.sum", 12),
                    ("lean.scan.candidates", 300),
                    ("lean.scan.slots", 1200), ("lean.scan.hits", 100)):
        reg.counter(name).inc(n)
    for ms in (0.5, 1.0, 1.0, 900.0):      # one set-up compile
        reg.timer("lean.device.enqueue.ms").update(ms)
    reg.timer("plan.sketch.build.ms").update(2500.0)
    got = {n: run.plugin("metrics", n).read(run.Readings(), registry=reg)
           for n in READERS}
    assert got == {"device_backlog": pytest.approx(1.5),
                   "dispatch_enqueue_ms": pytest.approx(1.0, rel=0.15),
                   "scan_slot_fill": pytest.approx(25.0),
                   "scan_candidates_per_hit": pytest.approx(3.0),
                   "sketch_build_s": pytest.approx(2.5)}
