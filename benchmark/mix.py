"""The one general traffic generator.

A traffic mix is a JSON file of parameters (``benchmark/traffic/<mix>.json``);
this module turns it, the run's table and the seed into the requests each
client sends, in order.

Every seed sends the same requests in another order, so a seed does not
change the work of a window.  The mix's own stream (the same for every
seed) draws the list of requests: each one's class, size and place (a
centroid or a port of the configuration's fixed world).  The list is
cut into rounds of one request a client, and client ``c``'s ``j``-th
request comes from round ``j``: the seed deals each round among the
clients and draws each request's time window (and, where a request is
anchored on a data row or names a vessel, that row or vessel).  Closed
loops work through the rounds in step, so the requests a window
completes are about the same set for every seed.

A request is a plain dict, the same for the program and the reference:

- ``bbox_during``: ``box`` ``[x0, y0, x1, y1]``, ``lo``/``hi`` in ms;
- ``attr_during``: ``attr``, ``code`` (into the column's vocabulary),
  ``lo``/``hi``;
- ``knn``: ``x``, ``y``, ``k``, ``lo``/``hi``.

Time bounds never fall on a row's timestamp: day windows run noon to
noon over midnight-stamped rows, hour windows start one second past a
minute over rows stamped on even seconds.
"""

from __future__ import annotations

import datetime

import numpy as np

from benchmark.table import DAY_MS, MINUTE_MS, Table, rng_for

HOUR_MS = 3_600_000


def _pick(rng, weights: np.ndarray) -> int:
    cdf = np.cumsum(weights)
    return int(min(np.searchsorted(cdf, rng.random() * cdf[-1]),
                   len(weights) - 1))


def _window(rng, cls: dict, t0: int, t1: int) -> tuple[int, int]:
    """(lo, hi) inside the data's time span ``[t0, t1]``."""
    if "days" in cls:
        d = int(cls["days"][int(rng.integers(len(cls["days"])))])
        first, last = t0 // DAY_MS, t1 // DAY_MS
        end = int(rng.integers(first + d - 1, last + 1))
        return ((end - d + 1) * DAY_MS - DAY_MS // 2,
                end * DAY_MS + DAY_MS // 2)
    span = int(cls["hours"] * HOUR_MS)
    first, last = t0 // MINUTE_MS, (t1 - span) // MINUTE_MS
    m = int(rng.integers(first, max(first, last) + 1))
    lo = m * MINUTE_MS + 1000
    return lo, lo + span


def place(fixed, cls: dict, table: Table):
    """(anchor name, index) of a request's centre, from the mix's own
    stream: a point of the fixed world, or ``("rows", None)`` for a
    data row that the seed picks."""
    anchors = cls.get("anchor")
    if anchors is None:
        return None
    if isinstance(anchors, dict):
        names = sorted(anchors)
        name = names[_pick(fixed, np.asarray([anchors[n] for n in names]))]
    else:
        name = anchors
    if name == "rows":
        return name, None
    return name, _pick(fixed, table.anchors[name][2])


def _centre(rng, where, table: Table, lo: int, hi: int):
    name, i = where
    if name == "rows":
        a, b = table.time_range(lo, hi)
        r = int(rng.integers(a, max(a + 1, b)))
        return float(table.x[r]), float(table.y[r])
    x, y, _ = table.anchors[name]
    return float(x[i]), float(y[i])


def make_request(rng, cls: dict, table: Table, t0: int, t1: int,
                 where=None) -> dict:
    """One request of class ``cls`` centred on ``where`` (``place``);
    ``rng`` draws its time window and anything the seed decides."""
    lo, hi = _window(rng, cls, t0, t1)
    kind = cls["kind"]
    if kind == "attr_during":
        vocab = table.strings[cls["attr"]][1]
        return {"kind": kind, "attr": cls["attr"],
                "code": int(rng.integers(len(vocab))), "lo": lo, "hi": hi}
    cx, cy = _centre(rng, where, table, lo, hi)
    if kind == "knn":
        return {"kind": kind, "x": cx, "y": cy, "k": int(cls["k"]),
                "lo": lo, "hi": hi}
    w, h = cls["box_deg"]
    box = [max(-180.0, cx - w / 2), max(-90.0, cy - h / 2),
           min(180.0, cx + w / 2), min(90.0, cy + h / 2)]
    return {"kind": kind, "box": box, "lo": lo, "hi": hi}


#: the mix's own stream: the same list of requests for every seed
MIX_SEED = 0


def client_requests(readers: dict, table: Table, seed: int, stream: int,
                    per_client: int) -> list[list[dict]]:
    """``per_client`` requests for each client, dealt round by round
    (module doc)."""
    classes = readers["classes"]
    n = readers["clients"]
    total = n * per_client
    shares = np.asarray([c["weight"] for c in classes], np.float64)
    quota = shares / shares.sum() * total
    counts = np.floor(quota).astype(int)
    # largest remainders first, so short lists still hold every class
    extra = np.argsort(counts - quota, kind="stable")[:total - counts.sum()]
    counts[extra] += 1
    fixed = rng_for(MIX_SEED, stream)
    kinds = fixed.permutation(np.repeat(np.arange(len(classes)), counts))
    where = [place(fixed, classes[k], table) for k in kinds]
    deal = rng_for(seed, 1000 + stream)
    t0, t1 = int(table.t[0]), int(table.t[-1])
    out: list = [[] for _ in range(n)]
    for j in range(per_client):
        for c, slot in zip(deal.permutation(n), range(j * n, (j + 1) * n)):
            out[c].append(make_request(deal, classes[kinds[slot]], table,
                                       t0, t1, where[slot]))
    return out


def iso(ms: int) -> str:
    if ms % 1000:
        raise ValueError(f"{ms} ms is not a whole second")
    return datetime.datetime.fromtimestamp(
        ms // 1000, datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")


def ecql(req: dict, geom: str, dtg: str, vocabs: dict) -> str:
    during = f"{dtg} DURING {iso(req['lo'])}/{iso(req['hi'])}"
    if req["kind"] == "bbox_during":
        x0, y0, x1, y1 = req["box"]
        return f"BBOX({geom},{x0!r},{y0!r},{x1!r},{y1!r}) AND {during}"
    if req["kind"] == "attr_during":
        value = vocabs[req["attr"]][req["code"]]
        return f"{req['attr']} = '{value}' AND {during}"
    raise ValueError(f"no ECQL for {req['kind']!r}")
