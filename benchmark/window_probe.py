"""One benchmark run with the lean dispatch's window readings beside it.

    python3 -m benchmark.window_probe --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--probe-out <path>]

Runs ``benchmark.run`` as it is and, around its measured window, reads
what its result line does not carry: the change over the window of the
program's lean dispatch and scan counters (the per-layer readers read
them over the whole run), and, in a traced run, the mean device ms of
one run of each program family (``program_ms``) and the profiled idle
gaps labelled by the program's own spans (``idle_gaps_by_span``).  The
readings go to stderr as one ``probe {...}`` line and to
``--probe-out``; the result line on stdout is the run's own.
"""

from __future__ import annotations

import argparse
import json
import re
import sys

from benchmark import run
from benchmark import trace_reduce as tr

#: the program's span namespaces (docs/observability.md, span taxonomy)
PROGRAM_SPANS = ("query", "serving", "write", "lean", "plan", "tile",
                 "pyramid", "job")
#: a program's compiled-module fingerprint, as the TPU trace writes it
#: (``jit_f(<digits>)``) or as ``jit_f_<digits>_``
_FINGERPRINT = re.compile(r"(\(\d+\)|_\d+_?)$")
#: the lean scan programs (``scan_program_ms``)
SCAN_FAMILIES = ("jit__lean_scan_exact_coded", "jit__lean_scan_exact_keep",
                 "jit__lean_scan_coded", "jit__attr_scan_coded")
COUNTERS = ("lean.device.dispatches", "lean.device.inflight.sum",
            "lean.scan.candidates", "lean.scan.slots", "lean.scan.hits",
            "lean.scan.bytes", "plan.sketch.builds")
TIMERS = ("lean.device.ms", "lean.device.enqueue.ms",
          "lean.device.wait.ms", "plan.sketch.build.ms")


def _window(planes) -> tuple | None:
    for plane in planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == tr.WINDOW_EVENT:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def family(name: str) -> str:
    """A program's name without its compiled-module fingerprint."""
    return _FINGERPRINT.sub("", name)


def program_ms(planes) -> dict:
    """``{family: [mean device ms of one run, runs]}`` over the runs of
    the first device's ``XLA Modules`` line that start inside the
    profiled window."""
    win = _window(planes)
    if win is None:
        return {}
    for plane in planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        runs: dict = {}
        for s, e, n in tr._events(plane, ("XLA Modules",)):
            if win[0] <= s < win[1]:
                c = runs.setdefault(family(n), [0.0, 0])
                c[0] += (e - s) / 1e6
                c[1] += 1
        if runs:
            return {f: [ms / k, k] for f, (ms, k) in runs.items()}
    return {}


def _label(gs, ge, spans) -> str | None:
    """The innermost span over ``[gs, ge)``: most overlap, then the
    shortest (a child covers what its parent does)."""
    best = None
    for s, e, name in spans:
        ov = min(e, ge) - max(s, gs)
        if ov > 0:
            key = (ov, -(e - s))
            if best is None or key > best[0]:
                best = (key, name)
    return None if best is None else best[1]


def idle_gaps_by_span(planes) -> list:
    """The first device's idle gaps in the profiled window (as
    ``trace_reduce.reduce`` finds them), each labelled by the innermost
    program span that overlaps it on any host thread, else by the
    ``bench.*`` annotation, else ``no bench span``; seconds by label,
    largest first."""
    win = _window(planes)
    if win is None:
        return []
    prog: list = []
    bench: list = []
    ops: list = []
    for plane in planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            if not ops:
                ops = tr._events(plane, tr.OP_LINES)
            continue
        for ln in plane.lines:
            for ev in ln.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name == tr.WINDOW_EVENT:
                    continue
                if ev.name.startswith("bench."):
                    bench.append(iv)
                elif ev.name.split(".")[0] in PROGRAM_SPANS:
                    prog.append(iv)
    if not ops:
        return []
    w0, w1 = win
    edge, gaps = w0, []
    for s, e in tr._union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                           if e > w0 and s < w1]):
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if edge < w1:
        gaps.append((edge, w1))
    out: dict = {}
    for gs, ge in gaps:
        label = (_label(gs, ge, prog) or _label(gs, ge, bench)
                 or tr.NO_SPAN)
        out[label] = out.get(label, 0.0) + (ge - gs) / 1e9
    return [[n, s] for n, s in sorted(out.items(), key=lambda kv: -kv[1])]


def snapshot() -> dict:
    from geomesa_tpu.metrics import registry
    names = set(registry.names())
    out = {n: registry.counter(n).count for n in COUNTERS if n in names}
    for n in TIMERS:
        if n in names:
            t = registry.timer(n)
            out[n] = [t.count, t.total]
    return out


def readings(before: dict, after: dict, completed: int, window_s: float,
             programs: dict) -> dict:
    """The window metrics from the edge snapshots; a reading
    whose inputs are missing or zero is left out."""
    def d(name):
        a, b = after.get(name), before.get(name)
        if a is None or b is None:
            return None
        return ([x - y for x, y in zip(a, b)] if isinstance(a, list)
                else a - b)

    out: dict = {}

    def ratio(key, num, den, scale=1.0):
        if num is not None and den:
            out[key] = scale * num / den

    disp = d("lean.device.dispatches")
    enq = d("lean.device.enqueue.ms")
    ratio("device_backlog", d("lean.device.inflight.sum"), disp)
    if enq is not None:
        ratio("dispatch_enqueue_ms", enq[1], enq[0])
    ratio("dispatches_per_request", disp, completed)
    ratio("scan_slot_fill", d("lean.scan.candidates"),
          d("lean.scan.slots"), 100.0)
    ratio("scan_candidates_per_hit", d("lean.scan.candidates"),
          d("lean.scan.hits"))
    if "plan.sketch.build.ms" in before:
        out["sketch_build_s"] = before["plan.sketch.build.ms"][1] / 1e3
    scan = [programs[f] for f in SCAN_FAMILIES if f in programs]
    if scan:
        runs = sum(k for _, k in scan)
        out["scan_program_ms"] = sum(ms * k for ms, k in scan) / runs
    if programs:
        runs = sum(k for _, k in programs.values())
        out["program_ms_mean"] = sum(
            ms * k for ms, k in programs.values()) / runs
        if "dispatches_per_request" in out:
            # device busy share the dispatches imply at the window's rate
            out["implied_busy"] = (out["dispatches_per_request"]
                                   * out["program_ms_mean"]
                                   * completed / window_s / 1e3)
    out["window_delta"] = {n: d(n) for n in after}
    out["completed"] = completed
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--probe-out")
    args, rest = ap.parse_known_args(argv)
    found: dict = {}
    measure, load = run.Run.measure, tr.load

    def measured(self):
        found["before"] = snapshot()
        try:
            measure(self)
        finally:
            found["after"] = snapshot()
            found["completed"] = self.readings.completed
            found["window_s"] = getattr(self, "window_s", 0.0)
            ms = [x["ms"] if "error" not in x else run.FAILED_MS
                  for x in getattr(self, "records", [])]
            if ms and found["window_s"]:
                # a traced run's line carries no end-to-end metric
                found["end_to_end"] = {
                    "query_qps": found["completed"] / found["window_s"],
                    "query_p50_ms": run.percentile(ms, 50),
                    "query_p95_ms": run.percentile(ms, 95)}

    def loaded(trace_dir):
        planes = load(trace_dir)
        found["program_ms"] = program_ms(planes)
        found["idle_gaps_by_span"] = idle_gaps_by_span(planes)
        return planes

    run.Run.measure, tr.load = measured, loaded
    try:
        rc = run.main(rest)
    finally:
        run.Run.measure, tr.load = measure, load
    if "after" in found:
        programs = found.get("program_ms", {})
        out = readings(found["before"], found["after"], found["completed"],
                       found["window_s"], programs)
        out["whole_run"] = found["after"]
        out["end_to_end"] = found.get("end_to_end", {})
        out["program_ms"] = programs
        out["idle_gaps_by_span"] = found.get("idle_gaps_by_span", [])
        line = json.dumps(out)
        print("probe " + line, file=sys.stderr, flush=True)
        if args.probe_out:
            with open(args.probe_out, "a") as f:
                f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
