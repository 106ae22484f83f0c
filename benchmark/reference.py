"""The plain reference: NumPy brute force over the seeded table.

It imports nothing of the program.  Each answer is computed from the rows
of the request's time range (the table is in time order), in float64 as
the configuration states.  ``dtype=np.float32`` computes the same answers
one precision lower: that is the control, which the comparison has to
refuse.
"""

from __future__ import annotations

import numpy as np

from benchmark.digest import column_digests
from benchmark.table import Table

EARTH_RADIUS_M = 6_371_008.8


def haversine_m(x0: float, y0: float, xs: np.ndarray, ys: np.ndarray,
                dtype=np.float64) -> np.ndarray:
    lon1, lat1 = np.radians(dtype(x0)), np.radians(dtype(y0))
    lon2 = np.radians(xs.astype(dtype))
    lat2 = np.radians(ys.astype(dtype))
    a = (np.sin((lat2 - lat1) / 2) ** 2
         + np.cos(lat1) * np.cos(lat2) * np.sin((lon2 - lon1) / 2) ** 2)
    return 2 * dtype(EARTH_RADIUS_M) * np.arcsin(np.sqrt(np.clip(a, 0, 1)))


def _lower(a: np.ndarray, dtype) -> np.ndarray:
    if dtype is np.float64 or a.dtype != np.float64:
        return a
    return a.astype(dtype).astype(np.float64)


def rows(table: Table, req: dict, dtype=np.float64) -> np.ndarray:
    """Sorted positions answering a ``bbox_during`` or ``attr_during``
    request (bounds inclusive, as the store evaluates them)."""
    a, b = table.time_range(req["lo"], req["hi"])
    if req["kind"] == "attr_during":
        hit = table.strings[req["attr"]][0][a:b] == req["code"]
    else:
        x0, y0, x1, y1 = (dtype(v) for v in req["box"])
        x, y = table.x[a:b].astype(dtype), table.y[a:b].astype(dtype)
        hit = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
    return a + np.flatnonzero(hit)


def knn(table: Table, req: dict, dtype=np.float64):
    """(positions, distances) of the k nearest rows in the window; ties
    go to the lower position."""
    a, b = table.time_range(req["lo"], req["hi"])
    d = haversine_m(req["x"], req["y"], table.x[a:b], table.y[a:b], dtype)
    k = min(req["k"], b - a)
    if not k:
        return np.empty(0, np.int64), np.empty(0)
    cut = d[np.argpartition(d, k - 1)[:k]].max()
    tied = np.flatnonzero(d <= cut)
    sel = tied[np.lexsort((tied, d[tied]))[:k]]
    return a + sel, d[sel].astype(np.float64)


def columns(table: Table, pos: np.ndarray, lay: list,
            dtype=np.float64) -> list:
    """Payload columns of rows ``pos`` in the digest's layout."""
    cols = []
    for name, typ, _ in lay:
        if typ == "Point":
            cols += [_lower(table.x[pos], dtype), _lower(table.y[pos], dtype)]
        elif typ == "Date":
            cols.append(table.t[pos])
        elif typ == "String":
            cols.append(table.strings[name][0][pos])
        else:
            cols.append(_lower(table.numbers[name][pos], dtype))
    return cols


def answer_digest(table: Table, req: dict, lay: list,
                  dtype=np.float64) -> list:
    pos = rows(table, req, dtype)
    return column_digests(pos, columns(table, pos, lay, dtype))


def knn_compare(table: Table, req: dict, pos, dist, want_pos: np.ndarray,
                want_d: np.ndarray) -> tuple[bool, float]:
    """(position set right, widest distance gap as a share of the k-th
    reference distance, floored at 1 m).  A returned row at exactly the
    k-th distance may stand in for another row tied with it."""
    pos = np.asarray(pos, np.int64)
    dist = np.sort(np.asarray(dist, np.float64))
    if len(pos) != len(want_pos):
        return False, 1.0
    if not len(pos):
        return True, 0.0
    kth = float(want_d.max())
    gap = float(np.abs(dist - np.sort(want_d)).max() / max(kth, 1.0))
    got = np.unique(pos)
    inner = want_pos[want_d < kth]
    if len(got) != len(pos) or not np.isin(inner, got).all():
        return False, gap
    outer = np.setdiff1d(got, inner)
    if len(outer):
        if (outer.min() < 0) or (outer.max() >= len(table)):
            return False, gap
        t = table.t[outer]
        d = haversine_m(req["x"], req["y"], table.x[outer], table.y[outer])
        if not (np.all((t >= req["lo"]) & (t <= req["hi"]))
                and np.all(d == kth)):
            return False, gap
    return True, gap
