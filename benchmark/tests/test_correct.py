"""What decides ``correct``: a control run (the reference one precision
lower in the program's place) comes out not correct, a run with the
timed path broken underneath comes out not correct, once for each fault
the cells can have, and a store that left the device ends the run with
no result.  All at the rehearsal's tiny size on the CPU; the control
was also run on the chip at the cells' own size (PERF.md)."""

import json

import numpy as np
import pytest

from benchmark import run as bench_run

SEED = 4_000_000_019


#: the two mixes whose cells are out of ``BENCHMARK.json`` for program
#: faults (PERF.md, Open questions 1 and 2); their harness paths (HTTP
#: load generator, writers) are still driven here
EXTRA_CELLS = [
    {"name": "gdelt.dash_fused", "config": "gdelt_events",
     "traffic": "dash_fused", "chips": 1, "why": "test"},
    {"name": "gdelt.firehose", "config": "gdelt_events",
     "traffic": "firehose", "chips": 1, "why": "test"}]


def _run(monkeypatch, capsys, workload, sample=1000, flags=()):
    """One in-process rehearsal run; every answer of the window is
    checked (``sample`` raised), so a fault cannot hide outside it."""
    load = bench_run.load_json

    def load_json(*parts):
        out = load(*parts)
        if "readers" in out:
            for r in (out["readers"], out["rehearsal"]["readers"]):
                r["sample"] = sample
                r["sample_share"] = 1.0
        if "workloads" in out:
            out["workloads"] = out["workloads"] + EXTRA_CELLS
        return out

    monkeypatch.setattr(bench_run, "load_json", load_json)
    rc = bench_run.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", "2", "--rehearse-cpu", *flags])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CELLS = ("gdelt.dash_facade", "ais.analyst_knn", "gdelt.dash_fused")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(monkeypatch, capsys, cell):
    assert _run(monkeypatch, capsys, cell)["correct"] is True


@pytest.mark.parametrize("cell", CELLS + ("gdelt.firehose",))
def test_control_run_is_not_correct(monkeypatch, capsys, cell):
    """``--control``: float32 reference answers stand in for the sample
    (and the read-back) and go through the same comparison."""
    if cell == "gdelt.firehose":
        _no_readers(monkeypatch)
    res = _run(monkeypatch, capsys, cell, flags=("--control",))
    assert res["correct"] is False
    assert res["control"] is True
    wrong = res["checks"]["requests_wrong"]["value"]
    assert wrong > 0
    if cell == "ais.analyst_knn":
        assert (res["checks"]["knn_gap"]["value"]
                > 100 * bench_run.LIMITS["knn_gap"])


@pytest.mark.parametrize("fault", ["z3_host_tier", "attr_host_tier",
                                   "degraded"])
def test_store_off_the_device_ends_without_result(monkeypatch, capsys,
                                                   fault):
    """A generation in the host tier (its scans run on the CPU) or a
    degraded scan: the run raises and prints no result line."""
    from geomesa_tpu.index import attr_lean, z3_lean
    from geomesa_tpu.metrics import RESILIENCE_DEGRADED, registry
    if fault == "z3_host_tier":
        monkeypatch.setattr(z3_lean.LeanZ3Index, "tier_counts",
                            lambda self: {"full": 3, "keys": 0, "host": 1})
    elif fault == "attr_host_tier":
        monkeypatch.setattr(attr_lean.LeanAttrIndex, "tier_counts",
                            lambda self: {"device": 3, "host": 1})
    else:
        c = registry.counter(RESILIENCE_DEGRADED)
        monkeypatch.setattr(c, "count", c.count + 1)
    with pytest.raises(RuntimeError, match="health after set-up"):
        bench_run.main(["--workload", "ais.analyst_knn", "--seed", "5",
                        "--seconds", "1", "--rehearse-cpu"])
    assert capsys.readouterr().out.strip() == ""


def _bump_x(monkeypatch):
    """Every answer's x one ulp off where it is produced: the host
    payload take (facade) and the device payload gather (Arrow)."""
    from geomesa_tpu.features.lean import LeanBatch
    from geomesa_tpu.index import z3_lean
    gather = z3_lean.LeanZ3Index.gather_payload
    take = LeanBatch.take

    def bad_gather(self, positions):
        x, y, t = gather(self, positions)
        return np.nextafter(x, np.inf), y, t

    def bad_take(self, positions, columns=None):
        fb = take(self, positions, columns)
        if "geom_x" in fb.columns:
            fb.columns["geom_x"] = np.nextafter(fb.columns["geom_x"], np.inf)
        return fb

    monkeypatch.setattr(z3_lean.LeanZ3Index, "gather_payload", bad_gather)
    monkeypatch.setattr(LeanBatch, "take", bad_take)


def _stretch_knn(monkeypatch):
    """The kNN ranking distance off by a part in 10^8."""
    from geomesa_tpu.process import knn
    hav = knn.haversine_m
    monkeypatch.setattr(knn, "haversine_m",
                        lambda *a: hav(*a) * (1.0 + 1e-8))


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(monkeypatch, capsys, cell):
    _bump_x(monkeypatch)
    if cell == "ais.analyst_knn":
        _stretch_knn(monkeypatch)
    res = _run(monkeypatch, capsys, cell)
    assert res["correct"] is False
    if cell == "ais.analyst_knn":
        assert res["checks"]["knn_gap"]["value"] > bench_run.LIMITS["knn_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(monkeypatch, capsys, cell):
    """A fused dispatch answers only the first half of its windows (the
    rest come back empty); kNN's three-ring dispatch likewise."""
    from geomesa_tpu.datastore import TpuDataStore
    fused = TpuDataStore._fused_windows_dispatch
    many = TpuDataStore.query_windows

    def half(hits):
        keep = (len(hits) + 1) // 2
        return list(hits[:keep]) + [np.empty(0, np.int64)] * (len(hits) - keep)

    monkeypatch.setattr(TpuDataStore, "_fused_windows_dispatch",
                        lambda self, name, ws: half(fused(self, name, ws)))
    monkeypatch.setattr(TpuDataStore, "query_windows",
                        lambda self, name, ws, **kw: half(
                            many(self, name, ws, **kw)))
    assert _run(monkeypatch, capsys, cell)["correct"] is False


def test_rehearsal_output_names_cpu(monkeypatch, capsys):
    res = _run(monkeypatch, capsys, "ais.analyst_knn", sample=4)
    assert res["device"]["platform"] == "cpu"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"


def _no_readers(monkeypatch):
    load = bench_run.load_json

    def load_json(*parts):
        out = load(*parts)
        if "writers" in out:
            out["rehearsal"]["readers"]["clients"] = 0
        return out

    monkeypatch.setattr(bench_run, "load_json", load_json)


def test_firehose_writes_alone_read_back(monkeypatch, capsys):
    """The firehose mix's write path with no reader beside it: every
    acknowledged batch is counted and the sampled days read back row for
    row (the witness that the harness's write check is sound)."""
    _no_readers(monkeypatch)
    res = _run(monkeypatch, capsys, "gdelt.firehose")
    assert res["correct"] is True
    assert res["checks"]["writes_lost"]["value"] == 0


def test_write_left_unstored_is_caught(monkeypatch, capsys):
    """A write acknowledged but never stored (the state left unchanged)."""
    from geomesa_tpu.datastore import TpuDataStore
    write = TpuDataStore.write
    calls = []

    def lossy(self, name, data, *a, **kw):
        calls.append(name)
        if len(calls) % 2 == 0:
            return len(next(iter(data.values())))
        return write(self, name, data, *a, **kw)

    _no_readers(monkeypatch)
    monkeypatch.setattr(TpuDataStore, "write", lossy)
    assert _run(monkeypatch, capsys, "gdelt.firehose")["correct"] is False


@pytest.mark.xfail(strict=True, raises=(IndexError, RuntimeError,
                                        AssertionError),
                   reason="program fault (PERF.md, Open questions 1): "
                   "queries beside lean writes lose host column chunks "
                   "and read donated device buffers")
def test_firehose_reads_beside_writes(monkeypatch, capsys):
    assert _run(monkeypatch, capsys, "gdelt.firehose")["correct"] is True
