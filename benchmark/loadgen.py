"""HTTP load generator: closed-loop clients in a child process that never
imports JAX.

    python -m benchmark.loadgen <plan.json>

The parent steers it over stdin and stdout, one word a line:

- the child prints ``READY`` once its clients exist;
- ``WARMUP``: every client sends its warm-up requests, then ``WARM``;
- ``WINDOW <seconds>``: every client sends its window requests back to
  back until the window closes, waits for the one in flight, and the
  child writes its records next to the plan and prints ``DONE``.

Each request is timed from the send until the last Arrow batch of the
answer has been decoded.  After that clock stops, the client digests the
answer (row ids and every payload value) for the reference to check.
"""

from __future__ import annotations

import io
import json
import sys
import threading
import time
import urllib.request

from benchmark.digest import arrow_columns, column_digests

TIMEOUT_S = 120.0


def fetch(url: str):
    import pyarrow as pa
    with urllib.request.urlopen(url, timeout=TIMEOUT_S) as resp:
        body = resp.read()
    return pa.ipc.open_stream(io.BytesIO(body)).read_all()


class Client:
    def __init__(self, cid: int, plan: dict):
        self.cid = cid
        self.base = plan["base_url"]
        self.warm = plan["warmup"][cid]
        self.todo = plan["window"][cid]
        self.lay = [tuple(x) for x in plan["layout"]]
        self.records: list = []
        self.errors: list = []

    def warmup(self, start: int = 0, stop: int | None = None) -> None:
        for path in self.warm[start:stop]:
            try:
                fetch(self.base + path)
            except Exception as e:  # noqa: BLE001 — reported to the parent
                self.errors.append(f"warm-up {path}: {e!r}")

    def window(self, t0: float, t_end: float) -> None:
        i = 0
        while True:
            sent = time.perf_counter()
            if sent >= t_end:
                return
            rec = {"client": self.cid, "index": i, "sent_s": sent - t0}
            try:
                table = fetch(self.base + self.todo[i % len(self.todo)])
                rec["ms"] = (time.perf_counter() - sent) * 1e3
                rec["digest"] = column_digests(*arrow_columns(table, self.lay))
            except Exception as e:  # noqa: BLE001 — a failed request
                rec["ms"] = (time.perf_counter() - sent) * 1e3
                rec["error"] = repr(e)[:300]
            self.records.append(rec)
            i += 1


def _run_all(clients, target, *args) -> None:
    threads = [threading.Thread(target=getattr(c, target), args=args)
               for c in clients]
    for th in threads:
        th.start()
    for th in threads:
        th.join()


def main(argv: list[str]) -> int:
    plan_path = argv[0]
    with open(plan_path) as f:
        plan = json.load(f)
    clients = [Client(i, plan) for i in range(len(plan["window"]))]
    print("READY", flush=True)
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "WARMUP":
            # one request alone first: the store's first query builds
            # shared host state that is not thread-safe (run.py, warmup)
            if clients:
                clients[0].warmup(0, 1)
            threads = [threading.Thread(target=c.warmup, args=(int(i == 0),))
                       for i, c in enumerate(clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            print("WARM", flush=True)
        elif cmd[0] == "WINDOW":
            t0 = time.perf_counter()
            _run_all(clients, "window", t0, t0 + float(cmd[1]))
            out = {"records": [r for c in clients for r in c.records],
                   "errors": [e for c in clients for e in c.errors],
                   "wall_s": time.perf_counter() - t0}
            with open(plan_path + ".out", "w") as f:
                json.dump(out, f)
            print("DONE", flush=True)
            return 0
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
