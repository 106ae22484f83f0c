"""The ``probe_gen_share`` reader: the lean z3 totals probe's seeks
over the device generations it held, from the program's counters."""

import pytest

from benchmark import run


def _read(reg):
    return run.plugin("metrics", "probe_gen_share").read(
        run.Readings(), registry=reg)


def test_reads_seeks_over_generations():
    from geomesa_tpu.metrics import MetricRegistry
    reg = MetricRegistry()
    reg.counter("lean.probe.generations").inc(12)
    reg.counter("lean.probe.seeks").inc(5)
    assert _read(reg) == pytest.approx(100.0 * 5 / 12)


def test_returns_none_without_the_program_counters():
    from geomesa_tpu.metrics import MetricRegistry
    assert _read(MetricRegistry()) is None
    reg = MetricRegistry()
    reg.counter("lean.probe.seeks")
    reg.counter("lean.probe.generations")       # registered, no probe
    assert _read(reg) is None
