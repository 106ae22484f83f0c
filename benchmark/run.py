"""One run of one benchmark cell, in a fresh process.

    python3 -m benchmark.run --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is found by name: ``BENCHMARK.json`` names its configuration
(``benchmark/configs/<config>.json``, whose ``generator`` is
``benchmark/datagen/<generator>.py``) and its traffic mix
(``benchmark/traffic/<traffic>.json``); each per-layer metric is read by
``benchmark/metrics/<metric>.py``.  A later cell, mix or metric is new
files and entries, never an edit here.

The run sets up from the seed (data, store, ingest, server, clients),
warms up by replaying the head of each client's own window requests at
the window's concurrency, measures for ``--seconds``, checks a seeded
sample of what the timed path returned against the plain reference, and
prints one JSON result line last.  After set-up and after the window it
checks the store's health (every generation on the device, no degraded
scan, retry or open breaker, the lean budget within the chip's memory)
and fails without a result where it does not hold.  It refuses any
platform but ``tpu``.  ``--rehearse-cpu`` is the CPU rehearsal at the
configuration's tiny size: its output names ``cpu`` and is never a
measurement.  ``--control`` is the control run: the reference one
precision lower stands in for the program's answers in the sample, and
the comparison has to find it not correct (for setting limits; the
benchmark's own runs never pass it).
"""

from __future__ import annotations

import argparse
import copy
import importlib.util
import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse

import numpy as np

from benchmark import mix as mixes
from benchmark import reference
from benchmark.digest import (column_digests, column_names, decimal_codes,
                              layout)
from benchmark.table import DAY_MS, Spec, Table, rng_for

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
_T_IMPORT = time.perf_counter()

#: the numbers compared, and their limits (PERF.md gives the readings
#: each was set from)
LIMITS = {"requests_failed": 0, "requests_wrong": 0, "writes_lost": 0,
          "knn_gap": 1e-9}
FAILED_MS = 120_000.0


def _uptime_at_import() -> float:
    """Seconds from this process's start to ``_T_IMPORT``."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - ticks / os.sysconf("SC_CLK_TCK")
                   - (time.perf_counter() - _T_IMPORT))
    except (OSError, ValueError, IndexError):
        return 0.0


_STARTED = _T_IMPORT - _uptime_at_import()


def log(msg: str) -> None:
    print(f"[bench] {time.perf_counter() - _STARTED:8.3f}s {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merged(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def percentile(ms: list, q: float) -> float:
    return float(np.percentile(np.asarray(ms, np.float64), q))


class Readings:
    """What the per-layer readers read: counts and sums over the window
    of a ``--trace 1`` run."""

    def __init__(self):
        self.completed = 0
        self.query_stage_ms: dict = {}
        self.query_roots = 0
        #: root name -> [count, ms] of the window's other roots
        self.other_roots: dict = {}
        self.write_index_ms = 0.0
        self.write_batches = 0
        self.fused_requests = 0
        self.fused_batches = 0
        self.compiles_in_window = 0
        self.setup_compile_s = 0.0
        self.profile: dict | None = None

    def per_request(self, stages: tuple) -> float | None:
        if not self.completed or not self.query_roots:
            return None
        return sum(self.query_stage_ms.get(s, 0.0)
                   for s in stages) / self.completed


class TraceCollector:
    """Finish hook on the program's tracer: stage sums of the ``query``
    roots (``obs.attribution.attribute``) and the exclusive ms of
    ``write.index`` and ``write.device`` spans of ``write`` roots."""

    def __init__(self, readings: Readings):
        self.r = readings
        self.on = False
        self.lock = threading.Lock()

    def __call__(self, trace, retained) -> None:
        if not self.on or trace.root_span is None:
            return
        from geomesa_tpu.obs import attribute
        root = trace.root_span.name
        if root == "query":
            led = attribute(trace)
            with self.lock:
                self.r.query_roots += 1
                for s, ms in led["stages"].items():
                    self.r.query_stage_ms[s] = (
                        self.r.query_stage_ms.get(s, 0.0) + ms)
        elif root == "write":
            child: dict = {}
            for sp in trace.spans:
                if sp.parent_id is not None:
                    child[sp.parent_id] = (child.get(sp.parent_id, 0.0)
                                           + sp.duration_ms)
            ms = sum(max(0.0, sp.duration_ms - child.get(sp.span_id, 0.0))
                     for sp in trace.spans
                     if sp.name in ("write.index", "write.device"))
            with self.lock:
                self.r.write_index_ms += ms
                self.r.write_batches += 1
        else:
            with self.lock:
                c = self.r.other_roots.setdefault(root, [0, 0.0])
                c[0] += 1
                c[1] += trace.root_span.duration_ms


class FacadeClient:
    """A closed-loop analyst: calls the store facade in this process."""

    def __init__(self, run: "Run", cid: int, warm: list, todo: list,
                 marks: set):
        self.run, self.cid = run, cid
        self.warm, self.todo, self.marks = warm, todo, marks
        self.fused = run.mix["readers"].get("fused", False)
        self.records: list = []
        self.kept: dict = {}
        self.largest = (-1, None)

    def call(self, req: dict):
        run = self.run
        with run.annotate(f"bench.facade.{req['kind']}"):
            if req["kind"] == "knn":
                from geomesa_tpu.process.knn import knn_process
                pos, dist = knn_process(run.ds, run.schema, req["x"],
                                        req["y"], req["k"], req["lo"],
                                        req["hi"])
                return ("knn", np.asarray(pos), np.asarray(dist)), len(pos)
            query = (run.ds.query_fused if self.fused
                     and req["kind"] == "bbox_during" else run.ds.query_result)
            res = query(run.schema, run.ecql(req))
            return ("rows", np.asarray(res.positions), res.batch), len(
                res.positions)

    def warmup(self) -> None:
        for req in self.warm:
            self.call(req)

    def window(self, t0: float, t_end: float) -> None:
        i = 0
        while True:
            sent = time.perf_counter()
            if sent >= t_end:
                return
            req = self.todo[i % len(self.todo)]
            rec = {"client": self.cid, "index": i, "sent_s": sent - t0}
            try:
                out, n = self.call(req)
                rec["ms"] = (time.perf_counter() - sent) * 1e3
                rec["rows"] = n
                if i in self.marks:
                    self.kept[i] = out
                if n > self.largest[0]:
                    self.largest = (n, (i, out))
            except Exception as e:  # noqa: BLE001 — a failed request
                rec["ms"] = (time.perf_counter() - sent) * 1e3
                rec["error"] = repr(e)[:300]
            self.records.append(rec)
            i += 1


class Writer:
    """Open-loop batch writer: batch ``j`` is due ``j * rows / rate``
    seconds into the window; its latency runs from that due time until
    ``write`` has returned and the lean index has blocked."""

    def __init__(self, run: "Run", w: dict, pool: Table, first_day: int):
        self.run, self.w, self.pool = run, w, pool
        self.first_day = first_day
        self.b = w["batch_rows"]
        self.n_pool = w["pool_batches"]
        self.done = 0                       # acknowledged batches
        self.records: list = []

    def table(self, w0: int, w1: int) -> Table:
        """Write-stream rows ``[w0, w1)``: the pool's rows in turn,
        stamped with the days after the preloaded data."""
        w = np.arange(w0, w1, dtype=np.int64)
        r = (w // self.b % self.n_pool) * self.b + w % self.b
        p = self.pool
        return Table(
            x=p.x[r], y=p.y[r],
            t=(self.first_day + w // self.w["rows_per_day"]) * DAY_MS,
            strings={n: (c[r], v) for n, (c, v) in p.strings.items()},
            numbers={n: a[r] for n, a in p.numbers.items()})

    def columns(self, j: int) -> dict:
        tb = self.table(j * self.b, (j + 1) * self.b)
        return tb.write_columns(self.run.spec, 0, self.b)

    def write(self, j: int) -> None:
        run = self.run
        with run.annotate("bench.write"):
            run.ds.write(run.schema, self.columns(j))
            run.ds._store(run.schema)._lean_index().block()
        self.done = j + 1

    def warmup(self) -> None:
        for j in range(self.w["warmup_batches"]):
            self.write(j)

    def window(self, t0: float, t_end: float) -> None:
        j0 = self.done
        period = self.b / self.w["rate_rows_per_s"]
        k = 0
        while True:
            due = t0 + k * period
            if due >= t_end:
                return
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            rec = {"batch": j0 + k, "sent_s": due - t0}
            try:
                self.write(j0 + k)
            except Exception as e:  # noqa: BLE001 — a failed write
                rec["error"] = repr(e)[:300]
            rec["ms"] = (time.perf_counter() - due) * 1e3
            self.records.append(rec)
            k += 1


class Run:
    def __init__(self, args, bench: dict, cell: dict, cfg: dict, mix: dict,
                 devices: list):
        self.args, self.bench, self.cell = args, bench, cell
        self.rehearse = args.rehearse_cpu
        if self.rehearse:
            cfg = merged(cfg, cfg.get("rehearsal", {}))
            mix = merged(mix, mix.get("rehearsal", {}))
        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.seed = args.seed
        self.schema = cfg["schema"]
        self.spec = Spec(cfg["spec"])
        self.readings = Readings()
        self.collector = TraceCollector(self.readings)
        self.http = mix["readers"]["transport"] == "http"
        self.server = self.child = self.writer = None
        self.tmp = tempfile.TemporaryDirectory(prefix="bench-")

    # -- helpers ----------------------------------------------------------
    def annotate(self, label: str):
        if self.args.trace:
            import jax.profiler
            return jax.profiler.TraceAnnotation(label)
        import contextlib
        return contextlib.nullcontext()

    def ecql(self, req: dict) -> str:
        return mixes.ecql(req, self.spec.geom, self.spec.dtg, self.vocabs)

    def url(self, req: dict) -> str:
        return (f"/query?schema={self.schema}&format=arrow&cql="
                + urllib.parse.quote(self.ecql(req)))

    # -- set-up -----------------------------------------------------------
    def setup(self) -> None:
        from geomesa_tpu.datastore import TpuDataStore
        cfg = self.cfg
        gen = plugin("datagen", cfg["generator"])
        self.table = gen.make(cfg, self.seed, cfg["rows"])
        log(f"data: {len(self.table)} rows")
        self.vocabs = {n: v for n, (_, v) in self.table.strings.items()}
        self.layout = layout(self.spec.attrs, self.vocabs)
        spec = cfg["spec"]
        if self.rehearse:
            spec += f",geomesa.lean.generation.slots={cfg['generation_slots']}"
        self.ds = TpuDataStore()
        self.ds.create_schema(self.schema, spec)
        if not self.ds._store(self.schema).lean:
            raise RuntimeError("schema did not take the lean profile")
        n, step = len(self.table), cfg["write_rows"]
        for lo in range(0, n, step):
            self.ds.write(self.schema, self.table.write_columns(
                self.spec, lo, min(n, lo + step)))
        self.ds._store(self.schema)._lean_index().block()
        self.n_preload = n
        log("ingest done")
        readers = self.mix["readers"]
        self.window_reqs = mixes.client_requests(
            readers, self.table, self.seed, 0, readers["per_client"])
        # the warm-up replays the head of each client's own window list:
        # the lean scan's programs are keyed by range, box, capacity and
        # hit-count buckets, and only the window's own requests, fused
        # as the window fuses them, load the programs the window uses
        warm = [reqs[:readers["warmup_per_client"]]
                for reqs in self.window_reqs]
        if "writers" in self.mix:
            w = self.mix["writers"]
            pool = gen.make(cfg, self.seed,
                            w["batch_rows"] * w["pool_batches"], stream=1)
            first_day = int(self.table.t[-1]) // DAY_MS + 1
            self.writer = Writer(self, w, pool, first_day)
        if self.http:
            self.start_http(warm)
        else:
            share = readers["sample_share"]
            self.clients = []
            for c in range(readers["clients"]):
                rng = rng_for(self.seed, 9000 + c)
                marks = set(np.flatnonzero(
                    rng.random(readers["per_client"]) < share).tolist())
                self.clients.append(FacadeClient(
                    self, c, warm[c], self.window_reqs[c], marks))
        if self.writer is not None:
            self.writer.warmup()
        log("clients ready")
        self.warmup()
        log(f"warm-up done, host memory peak {_host_peak_bytes()} B")
        self.health("after set-up")

    def health(self, when: str) -> None:
        """The store as deployed: no z3 or attribute generation spilled
        to the host tier (its scans would run on the CPU), no degraded
        scan, retry or open breaker, and the lean HBM budget within
        each chip's ``bytes_limit``.  Raises, so the run ends with no
        result line (the checks of ``chip_smoke.health``)."""
        from geomesa_tpu.metrics import (RESILIENCE_BREAKER_OPEN,
                                         RESILIENCE_DEGRADED,
                                         RESILIENCE_RETRIES, registry)
        st = self.ds._store(self.schema)
        tiers = {"z3": st._lean_index().tier_counts()}
        for a in st._lean_attr_names():
            tiers["attr:" + a] = st._lean_attr_index(a).tier_counts()
        res = {k: registry.counter(k).count for k in (
            RESILIENCE_DEGRADED, RESILIENCE_RETRIES, RESILIENCE_BREAKER_OPEN)}
        budget = st._lean_budget()
        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in self.devices]
        log(f"health {when}: tiers {tiers} resilience {res} lean budget "
            f"{budget} bytes_limit {limits}")
        bad = [f"{n}: {t['host']} generations in the host tier"
               for n, t in tiers.items() if t.get("host")]
        bad += [f"{k} = {v}" for k, v in res.items() if v]
        if not self.rehearse:
            bad += [f"lean budget {budget} over bytes_limit {lim}"
                    for lim in limits if lim is None or budget > lim]
        if bad:
            raise RuntimeError(f"health {when}: " + "; ".join(bad))

    def start_http(self, warm: list) -> None:
        from geomesa_tpu.web import WebApp
        from geomesa_tpu.web.wsgi import make_bounded_server
        app = WebApp(self.ds)
        if self.args.trace:
            app = _Annotated(app, self)
        self.server = make_bounded_server(
            "127.0.0.1", 0, app, self.mix["server"]["max_concurrent"])
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        port = self.server.server_address[1]
        plan = {"base_url": f"http://127.0.0.1:{port}",
                "warmup": [[self.url(r) for r in c] for c in warm],
                "window": [[self.url(r) for r in c] for c in self.window_reqs],
                "layout": self.layout}
        self.plan_path = os.path.join(self.tmp.name, "plan.json")
        with open(self.plan_path, "w") as f:
            json.dump(plan, f)
        self.child = subprocess.Popen(
            [sys.executable, "-X", "faulthandler", "-m", "benchmark.loadgen",
             self.plan_path],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)
        self.expect("READY")

    def expect(self, word: str) -> None:
        line = self.child.stdout.readline().strip()
        if line != word:
            raise RuntimeError(f"load generator said {line!r}, not {word!r}"
                               f" (exit code {self.child.poll()})")

    def say(self, line: str) -> None:
        self.child.stdin.write(line + "\n")
        self.child.stdin.flush()

    def warmup(self) -> None:
        """The first request of each kind alone, then every client's
        replay at once.  First queries build shared host state that the
        program does not guard: ``LeanBatch.column`` concatenates each
        host column, and the planner's estimator builds its z3 and
        attribute sketches.  Several first queries at once each did the
        whole build: one run died at the machine's 40 GiB, and the
        analysts' warm-up took 200-220 s on the seeds whose first
        requests met that way (PERF.md, Open questions 2)."""
        if self.http:
            self.say("WARMUP")
            self.expect("WARM")
            return
        first: dict = {}
        for c in self.clients:
            for req in c.warm:
                first.setdefault(req["kind"], (c, req))
        for c, req in first.values():
            c.call(req)
        log(f"first request of each kind alone done: {sorted(first)}")
        _threads([c.warmup for c in self.clients])

    # -- the measured window ----------------------------------------------
    def measure(self) -> None:
        from geomesa_tpu.metrics import (JAX_COMPILE_COUNT, JAX_COMPILE_MS,
                                         SERVING_FUSED_BATCHES,
                                         SERVING_FUSED_REQUESTS, registry)
        from geomesa_tpu.obs import tracer
        r = self.readings
        seconds = float(self.args.seconds)
        if self.args.trace:
            from geomesa_tpu import config as gm_config
            gm_config.set_property("geomesa.obs.sampler", "always")
            tracer.add_finish_hook(self.collector)
        c0 = registry.counter(JAX_COMPILE_COUNT).count
        r.setup_compile_s = registry.timer(JAX_COMPILE_MS).total / 1e3
        f0 = (registry.counter(SERVING_FUSED_REQUESTS).count,
              registry.counter(SERVING_FUSED_BATCHES).count)
        self.collector.on = bool(self.args.trace)
        import jax
        compile_log = _CompileLog()
        jax_log = logging.getLogger("jax")
        jax_log.addHandler(compile_log)
        jax.config.update("jax_log_compiles", True)
        jobs = []
        t0 = time.perf_counter()
        self.setup_s = t0 - _STARTED
        t_end = t0 + seconds
        if self.http:
            self.say(f"WINDOW {seconds}")
        else:
            jobs += [lambda c=c: c.window(t0, t_end) for c in self.clients]
        if self.writer is not None:
            jobs.append(lambda: self.writer.window(t0, t_end))
        if self.args.trace:
            jobs.append(lambda: self.profile(t0, seconds))
        _threads(jobs)
        if self.http:
            self.expect("DONE")
            self.child.wait(timeout=60)
            with open(self.plan_path + ".out") as f:
                out = json.load(f)
            self.records = out["records"]
            if out["errors"]:
                raise RuntimeError(f"warm-up failed: {out['errors'][:3]}")
        else:
            self.records = [r_ for c in self.clients for r_ in c.records]
        self.collector.on = False
        jax.config.update("jax_log_compiles", False)
        jax_log.removeHandler(compile_log)
        self.window_s = seconds
        r.compiles_in_window = registry.counter(JAX_COMPILE_COUNT).count - c0
        log(f"window closed: {len(self.records)} requests, "
            f"{r.compiles_in_window} compiles in it, set-up compiled "
            f"{c0} programs in {r.setup_compile_s:.3f} s")
        for name, sec in compile_log.seen:
            log(f"compiled in the window: {name} in {sec} s")
        r.fused_requests = (registry.counter(SERVING_FUSED_REQUESTS).count
                            - f0[0])
        r.fused_batches = (registry.counter(SERVING_FUSED_BATCHES).count
                           - f0[1])
        r.completed = sum(1 for x in self.records if "error" not in x)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.memory_peak = int(max(peaks))
        if self.server is not None:
            self.server.shutdown()
            self.server.server_close()
        self.health("after the window")

    def profile(self, t0: float, seconds: float) -> None:
        """Profile ``PROFILE_S`` seconds in the middle of the window."""
        import jax.profiler

        from benchmark import trace_reduce
        span = min(PROFILE_S, seconds / 2)
        time.sleep(max(0.0, t0 + (seconds - span) / 2 - time.perf_counter()))
        trace_dir = os.path.join(self.tmp.name, "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
                time.sleep(span)
        finally:
            jax.profiler.stop_trace()
        self.trace_dir = trace_dir

    # -- the check --------------------------------------------------------
    def verify(self) -> dict:
        """The numbers compared.  With ``--control`` the reference one
        precision lower stands in for every sampled answer."""
        num = {"requests_failed": sum(1 for x in self.records
                                      if "error" in x),
               "requests_wrong": 0}
        if any(c["kind"] == "knn" for c in self.mix["readers"]["classes"]):
            num["knn_gap"] = 0.0
        for req, got in self.sample():
            self.compare(req, got, num)
        if self.writer is not None:
            self.readback(num)
        return num

    def sample(self):
        """A seeded sample of the window's answers, with the largest."""
        n = self.mix["readers"]["sample"]
        rng = rng_for(self.seed, 7777)
        if self.http:
            ok = [x for x in self.records if "digest" in x]
            pick = ([ok[i] for i in rng.choice(len(ok), min(n, len(ok)),
                                               replace=False)]
                    if ok else [])
            if ok:
                big = max(ok, key=lambda x: x["digest"][0][0])
                if big not in pick:
                    pick.append(big)
            for x in pick:
                req = self.window_reqs[x["client"]][
                    x["index"] % len(self.window_reqs[x["client"]])]
                yield req, ("digest", x["digest"])
            return
        kept = [(c, i, out) for c in self.clients
                for i, out in sorted(c.kept.items())]
        chosen = [kept[i] for i in sorted(rng.permutation(len(kept))[:n])]
        big = max(self.clients, key=lambda c: c.largest[0])
        if big.largest[1] is not None:
            i, out = big.largest[1]
            if not any(c is big and j == i for c, j, _ in chosen):
                chosen.append((big, i, out))
        for c, i, out in chosen:
            yield c.todo[i % len(c.todo)], out

    def compare(self, req: dict, got, num: dict) -> None:
        t = self.table
        control = self.args.control
        if got[0] == "knn":
            want_p, want_d = reference.knn(t, req)
            if control:
                got = ("knn", *reference.knn(t, req, np.float32))
            ok, gap = reference.knn_compare(t, req, got[1], got[2],
                                            want_p, want_d)
            num["requests_wrong"] += 0 if ok else 1
            num["knn_gap"] = max(num["knn_gap"], gap)
            return
        want = reference.answer_digest(t, req, self.layout)
        if control:
            have = reference.answer_digest(t, req, self.layout, np.float32)
        elif got[0] == "digest":
            have = got[1]
        else:
            have = column_digests(got[1], self.batch_columns(got[2]))
        self.tally(num, req, have, want)

    def tally(self, num: dict, req: dict, have: list, want: list) -> None:
        """Count a wrong answer, and say on stderr how it is wrong."""
        if have == want:
            return
        num["requests_wrong"] += 1
        if num["requests_wrong"] <= 5:
            names = column_names(self.layout)
            bad = [n for n, h, w in zip(names, have, want) if h != w]
            log(f"wrong answer: {req} rows {have[0][0]} (reference "
                f"{want[0][0]}), differing: {bad}")

    def batch_columns(self, batch) -> list:
        """A query_result batch in the digest's layout."""
        cols = []
        for name, typ, vocab in self.layout:
            if typ == "Point":
                x, y = batch.geom_xy(name)
                cols += [np.asarray(x, np.float64), np.asarray(y, np.float64)]
            elif typ == "String" and vocab is None:
                cols.append(decimal_codes(batch.column(name)))
            elif typ == "String":
                index = {v: i for i, v in enumerate(vocab)}
                cols.append(np.asarray([index.get(v, -1)
                                        for v in batch.column(name)],
                                       np.int64))
            else:
                cols.append(np.asarray(batch.column(name)))
        return cols

    def readback(self, num: dict) -> None:
        """Every acknowledged write counted, and a seeded sample of the
        written days read back row for row through the facade."""
        wr = self.writer
        acked = wr.done * wr.b
        num["writes_lost"] = max(
            0, self.n_preload + acked - int(self.ds.get_count(self.schema)))
        rpd = wr.w["rows_per_day"]
        last = (acked - 1) // rpd
        rng = rng_for(self.seed, 8888)
        days = sorted({0, last} | set(rng.integers(
            0, last + 1, wr.w["readback_days"]).tolist()))
        for d in days:
            w0, w1 = d * rpd, min(acked, (d + 1) * rpd)
            day = wr.first_day + d
            req = {"kind": "bbox_during", "box": [-180.0, -90.0, 180.0, 90.0],
                   "lo": day * DAY_MS - DAY_MS // 2,
                   "hi": day * DAY_MS + DAY_MS // 2}
            res = self.ds.query_result(self.schema, self.ecql(req))
            pos = self.n_preload + np.arange(w0, w1, dtype=np.int64)
            written = wr.table(w0, w1)
            rows = np.arange(len(written))

            def expect(dtype):
                return column_digests(pos, reference.columns(
                    written, rows, self.layout, dtype))

            have = (expect(np.float32) if self.args.control else
                    column_digests(np.asarray(res.positions),
                                   self.batch_columns(res.batch)))
            self.tally(num, req, have, expect(np.float64))

    # -- the result -------------------------------------------------------
    def metrics(self) -> dict:
        names = _cell_metrics(self.bench, self.cell["name"],
                              "per_layer" if self.args.trace
                              else "end_to_end")
        units = {m["name"]: m["unit"] for m in
                 self.bench["end_to_end"] + self.bench["per_layer"]}
        out = {}
        if self.args.trace:
            from benchmark import trace_reduce
            trace_dir = getattr(self, "trace_dir", None)
            self.readings.profile = (trace_reduce.reduce(
                trace_reduce.load(trace_dir)) if trace_dir else None)
            for name in names:
                v = plugin("metrics", name).read(self.readings)
                if v is not None:
                    out[name] = {"value": float(v), "unit": units[name]}
            return out
        q_ms = [x["ms"] if "error" not in x else FAILED_MS
                for x in self.records]
        w_ms = ([x["ms"] if "error" not in x else FAILED_MS
                 for x in self.writer.records] if self.writer else [])
        values = {"setup_s": self.setup_s,
                  "query_qps": self.readings.completed / self.window_s,
                  "query_p50_ms": percentile(q_ms, 50) if q_ms else None,
                  "query_p95_ms": percentile(q_ms, 95) if q_ms else None,
                  "write_p95_ms": percentile(w_ms, 95) if w_ms else None}
        for name in names:
            if values.get(name) is not None:
                out[name] = {"value": float(values[name]),
                             "unit": units[name]}
        return out

    def close(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
            self.child.wait(timeout=30)
        self.tmp.cleanup()


PROFILE_S = 2.0


class _Annotated:
    """WSGI wrapper for traced runs: one host annotation per request,
    around the app call and the drain of its (lazy) body."""

    def __init__(self, app, run: Run):
        self.app, self.run = app, run

    def __call__(self, environ, start_response):
        body = self.app(environ, start_response)

        def drain():
            with self.run.annotate("bench.http.query"):
                try:
                    yield from body
                finally:
                    close = getattr(body, "close", None)
                    if close is not None:
                        close()
        return drain()


def _host_peak_bytes() -> int:
    """This process's resident high-water mark."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class _CompileLog(logging.Handler):
    """Names and seconds of the programs compiled or loaded from the
    persistent cache while it is on (JAX's ``jax_log_compiles``)."""

    def __init__(self):
        super().__init__()
        self.seen: list = []

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            name, _, rest = msg[len("Finished XLA compilation of "):
                                ].partition(" in ")
            self.seen.append((name, rest.split()[0]))


def _threads(jobs: list) -> None:
    errors: list = []

    def wrap(job):
        try:
            job()
        except BaseException as e:  # re-raised on the main thread
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(j,)) for j in jobs]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]


def _cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` that cell reports: those listing it, and
    those without a list whose moved metric (or which, end to end) the
    cell reports."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if group == "end_to_end":
        return e2e
    return [m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size on the CPU; never a measurement")
    ap.add_argument("--control", action="store_true",
                    help="the control run: the reference one precision "
                    "lower stands in for the sampled answers")
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no cell {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg = load_json(BENCH, "configs", cell["config"] + ".json")
    mix = load_json(BENCH, "traffic", cell["traffic"] + ".json")

    import jax
    devices = jax.devices()
    log(f"devices: {devices}")
    platform = devices[0].platform
    want = "cpu" if args.rehearse_cpu else "tpu"
    if platform != want:
        print(f"benchmark: needs platform {want!r}, JAX has {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    if not args.rehearse_cpu:
        peaks = load_json(BENCH, "peaks.json")["devices"]
        if kind not in peaks:
            print(f"benchmark: no peaks for device kind {kind!r}",
                  file=sys.stderr)
            return 2
    from geomesa_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    run = Run(args, bench, cell, cfg, mix, devices[:cell["chips"]])
    try:
        run.setup()
        run.measure()
        numbers = run.verify()
        log("check done")
        metrics = run.metrics()
    finally:
        run.close()
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    writes = run.writer.records if run.writer else []
    result = {
        "correct": correct,
        "attempted": len(run.records) + len(writes),
        "failed": numbers["requests_failed"]
        + sum(1 for x in writes if "error" in x),
        "metrics": metrics,
        "device": {"platform": platform, "kind": kind,
                   "count": len(run.devices),
                   "memory_peak_bytes": run.memory_peak},
    }
    prof = run.readings.profile
    if args.trace and prof is not None:
        result["device"]["busy_s"] = prof["busy_s"]
        result["device"]["window_s"] = prof["window_s"]
        result["breakdown"] = {"device_ops": prof["device_ops"],
                               "idle_gaps": prof["idle_gaps"]}
    if args.control:
        result["control"] = True
    result["checks"] = checks
    log(f"host memory peak {_host_peak_bytes()} B")
    for k, v in checks.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
