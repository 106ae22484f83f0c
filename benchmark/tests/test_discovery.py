"""A configuration, a traffic mix and a per-layer metric placed as new
files are found by name: a copy of the benchmark gains a cell by new
files and new entries in ``BENCHMARK.json`` only, and runs it end to end
in the CPU rehearsal."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {p: open(p).read() for p in
              (tmp_path / "benchmark").rglob("*") if p.is_file()}
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "gdelt_events.json"))
    cfg["name"] = "gdelt_small"
    cfg["params"]["centroids"] = 500
    (b / "configs" / "gdelt_small.json").write_text(json.dumps(cfg))
    mix = json.load(open(b / "traffic" / "dash_fused.json"))
    mix["name"] = "few_boxes"
    mix["readers"]["classes"] = mix["readers"]["classes"][:1]
    (b / "traffic" / "few_boxes.json").write_text(json.dumps(mix))
    (b / "metrics" / "query_roots.py").write_text(
        "def read(r):\n    return r.query_roots or None\n")
    bench["configs"].append({"name": "gdelt_small", "source": "test",
                             "file": "benchmark/configs/gdelt_small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gdelt.few_boxes",
                               "config": "gdelt_small",
                               "traffic": "few_boxes", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "query_roots", "unit": "roots",
                               "better": "lower",
                               "source": "program_span", "layer": "test",
                               "moves": "query_qps",
                               "workloads": ["gdelt.few_boxes"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "gdelt.few_boxes", "--seed", "4294967311", "--seconds", "2",
         "--trace", "1", "--rehearse-cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["query_roots"]["value"] > 0
    for p, text in before.items():                 # nothing edited
        assert open(p).read() == text, p


def test_refuses_without_the_rehearsal_flag_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "ais.analyst_knn", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_refuses_without_the_program():
    """A directory holding only BENCHMARK.json and the benchmark's own
    files: no result, non-zero exit."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        shutil.copytree(os.path.join(ROOT, "benchmark"),
                        os.path.join(d, "benchmark"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), d)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("PYTHONPATH", None)
        out = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload",
             "ais.analyst_knn", "--seed", "1", "--seconds", "1",
             "--rehearse-cpu"],
            cwd=d, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
