"""MarineCadastre-shaped AIS broadcasts from the seed: one point per
vessel per minute, with every field of the MarineCadastre.gov record.

Vessels leave ports; a share of them stays moored or anchored (the same
position, SOG 0), the rest move on a persistent heading at a steady
speed, reflected at the region's edges.  Each vessel reports at a fixed
even second of the minute, and rows come out in time order (minute by
minute, vessels ordered by their report second), so window bounds on an
odd second never meet a report.  A vessel's static fields (name, IMO,
call sign, type, dimensions, cargo, transceiver class) are drawn once
and repeat in each of its reports, as in the data.
"""

from __future__ import annotations

import numpy as np

from benchmark.table import MINUTE_MS, Table, categorical, rng_for

M_PER_DEG = 111_195.0
KNOT_M_PER_MIN = 1852.0 / 60.0


def _reflect(u: np.ndarray, lo: float, hi: float) -> np.ndarray:
    span = hi - lo
    w = np.mod(u - lo, 2.0 * span)
    return lo + np.where(w > span, 2.0 * span - w, w)


def _vocab(values: list) -> tuple[np.ndarray, list]:
    index: dict = {}
    idx = np.asarray([index.setdefault(x, len(index)) for x in values],
                     np.int16)
    return idx, list(index)


def _static(p: dict, rng, n_v: int, vtype: np.ndarray) -> dict:
    """Per-vessel static fields: a share of them blank, as broadcast."""
    def blank(share, vals):
        keep = rng.random(n_v) >= share
        return [x if k else "" for x, k in zip(vals, keep)]

    ids = rng.integers(0, 10**7, (3, n_v))
    names = blank(p["name_blank_share"], [f"VESSEL {i:05d}" for i in
                                          range(n_v)])
    imo = blank(p["imo_blank_share"], [f"IMO{x:07d}" for x in ids[0]])
    call = blank(p["callsign_blank_share"], [f"W{x:06d}" for x in ids[1]])
    cls = np.where(rng.random(n_v) < p["class_a_share"], "A", "B")
    big = np.isin(vtype, p["large_types"])
    length = np.round(np.where(big, rng.uniform(80.0, 330.0, n_v),
                               rng.uniform(8.0, 60.0, n_v)), 1)
    width = np.round(length * rng.uniform(0.12, 0.2, n_v), 1)
    draft = np.round(np.where(big, rng.uniform(6.0, 16.0, n_v),
                              rng.uniform(1.0, 5.0, n_v)), 1)
    cargo = np.where(big, vtype, 0).astype(np.int32)
    return {"strings": {"vesselName": _vocab(names), "imo": _vocab(imo),
                        "callSign": _vocab(call),
                        "transceiverClass": _vocab(cls.tolist())},
            "numbers": {"length": length, "width": width, "draft": draft,
                        "cargo": cargo}}


def make(cfg: dict, seed: int, rows: int, stream: int = 0) -> Table:
    p = cfg["params"]
    n_v = p["vessels"]
    minutes = rows // n_v
    if minutes * n_v != rows:
        raise ValueError(f"{rows} rows is not whole minutes of {n_v} "
                         "vessels")
    (x0, x1), (y0, y1) = p["lon"], p["lat"]
    # the ports are the configuration's fixed world; the fleet and its
    # tracks come from the seed
    world = rng_for(p["world_seed"], 0)
    px = world.uniform(x0 + 1.0, x1 - 1.0, p["ports"])
    py = world.uniform(y0 + 1.0, y1 - 1.0, p["ports"])
    rng = rng_for(seed, stream)
    home = rng.integers(0, p["ports"], n_v)
    moored = rng.random(n_v) < p["moored_share"]
    spread = p["moored_spread_deg"]
    vx = px[home] + rng.normal(0.0, spread, n_v)
    vy = py[home] + rng.normal(0.0, spread, n_v)
    head = rng.uniform(0.0, 360.0, n_v)
    speed = np.where(moored, 0.0, rng.uniform(*p["speed_kn"], n_v))
    status = np.where(moored, np.where(rng.random(n_v) < 0.5, 5, 1),
                      0).astype(np.int32)
    vtype = np.asarray(p["vessel_types"], np.int32)[
        categorical(rng, p["vessel_type_weights"], n_v)]
    mmsi = rng.choice(np.arange(338_000_000, 370_000_000), n_v,
                      replace=False)
    static = _static(p, rng, n_v, vtype)
    second = 2 * rng.integers(0, 30, n_v)
    order = np.argsort(second, kind="stable")      # report order in a minute
    # rows: minute-major, vessels in report order
    v = np.tile(order, minutes)
    m = np.repeat(np.arange(minutes, dtype=np.int64), n_v)
    # degrees a minute, per vessel
    rad = np.radians(head)
    step = speed * KNOT_M_PER_MIN / M_PER_DEG
    dlat = step * np.cos(rad)
    dlon = step * np.sin(rad) / np.cos(np.radians(vy))
    lat = _reflect(vy[v] + dlat[v] * m, y0, y1)
    lon = _reflect(vx[v] + dlon[v] * m, x0, x1)
    dec = p["coord_decimals"]
    sog = np.round(speed[v], 1)
    cog = np.round(head[v], 1)
    return Table(
        x=np.round(lon, dec), y=np.round(lat, dec),
        t=p["start_ms"] + m * MINUTE_MS + second[v] * 1000,
        strings={"mmsi": (v.astype(np.int32), [str(s) for s in mmsi]),
                 **{k: (idx[v], vocab)
                    for k, (idx, vocab) in static["strings"].items()}},
        numbers={"sog": sog, "cog": cog, "heading": np.round(cog),
                 "vesselType": vtype[v], "status": status[v],
                 **{k: a[v] for k, a in static["numbers"].items()}},
        anchors={"ports": (px, py, np.ones(p["ports"])),
                 "vessels": (vx, vy, np.ones(n_v))})
