"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on a synthetic trace and on one recorded here on the CPU."""

from collections import namedtuple

import pytest

from benchmark import trace_reduce as tr

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")


def _planes(device_ops, host_events):
    ops = Line("XLA Ops", [Ev(*e) for e in device_ops])
    dev = Plane("/device:TPU:0", [Line("XLA Modules", []), ops])
    host = Plane("/host:CPU", [Line("python3", [Ev(*e) for e in host_events])])
    return [host, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    # window [100, 1100); ops overlap at [200, 300) and one spills past
    # the window's end
    planes = _planes(
        [("fusion.1", 200, 200), ("sort.2", 250, 100), ("fusion.1", 1000, 500),
         ("copy.3", 0, 50)],
        [(tr.WINDOW_EVENT, 100, 1000)])
    out = tr.reduce(planes)
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [200, 400) + [1000, 1100) = 300 ns
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["idle_share"] == pytest.approx(0.7)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-9)     # 200 + 100 clipped
    assert ops["sort.2"] == pytest.approx(100e-9)
    assert "copy.3" not in ops                           # outside the window


def test_gaps_go_to_the_bench_span_that_overlaps_them_most():
    planes = _planes(
        [("op", 100, 100), ("op", 600, 100)],
        [(tr.WINDOW_EVENT, 100, 900),
         ("bench.http.query", 150, 300),      # covers most of gap [200, 600)
         ("bench.facade.knn", 500, 150),
         ("not_bench", 200, 400)])
    out = tr.reduce(planes)
    gaps = dict(out["idle_gaps"])
    # gap [200, 600): http 250 ns vs knn 100 ns -> http; gap [700, 1000):
    # no bench span
    assert gaps["bench.http.query"] == pytest.approx(400e-9)
    assert gaps[tr.NO_SPAN] == pytest.approx(300e-9)
    assert out["idle_share"] == pytest.approx(700 / 900)


def test_busy_averages_over_devices():
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [Ev("op", 100, 1000)])])
    planes = _planes([("op", 100, 500)], [(tr.WINDOW_EVENT, 100, 1000)])
    out = tr.reduce(planes + [dev1])
    assert out["busy_s"] == pytest.approx(750e-9)


def test_nothing_to_read_gives_none():
    assert tr.reduce(_planes([("op", 0, 10)], [])) is None       # no window
    assert tr.reduce([Plane("/host:CPU", [Line("t", [
        Ev(tr.WINDOW_EVENT, 0, 10)])])]) is None                  # no device


def test_recorded_cpu_trace_has_no_device_plane(tmp_path):
    """A real ``.xplane.pb`` written here: the window annotation is found
    on a host plane, and with no device plane the reduction reads
    nothing (a CPU run never yields a device metric)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_EVENT):
        with jax.profiler.TraceAnnotation("bench.test"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load(str(tmp_path))
    names = [ev.name for p in planes for ln in p.lines for ev in ln.events]
    assert tr.WINDOW_EVENT in names and "bench.test" in names
    assert tr.reduce(planes) is None
