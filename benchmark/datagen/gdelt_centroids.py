"""GDELT-shaped events from the seed: points on geocoding centroids, with
every attribute of GeoMesa's ``gdelt`` feature type.

GDELT geocodes every event to a city, ADM1 or country centroid, so
events sit exactly on a few thousand points whose popularity is very
uneven.  Rows come out in day order, as the daily export files do, and
``dtg`` has day resolution (``SQLDATE``).  Day ``d`` holds rows
``[d * rows // days, (d + 1) * rows // days)``.

Columns that GDELT derives from one coded value are derived here the
same way: the CAMEO event code fixes its base code, root code, quad
class and Goldstein value; an actor fixes its name and six codes.  So a
row costs a handful of random draws and some table lookups.
"""

from __future__ import annotations

import numpy as np

from benchmark.table import DAY_MS, Table, categorical, rng_for, zipf_weights

#: CAMEO roots 01-04 are verbal cooperation (quad class 1), 05-08
#: material cooperation (2), 09-13 verbal conflict (3), 14-20 material
#: conflict (4)
QUAD_OF_ROOT = [1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 3, 3, 4, 4, 4, 4, 4, 4, 4]

ACTOR_FIELDS = ("Name", "Code", "CountryCode", "GroupCode", "EthnicCode",
                "Religion1Code", "Religion2Code")


def _centroids(p: dict, rng) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    n, regions = p["centroids"], p["regions"]
    cx = rng.uniform(-170.0, 170.0, regions)
    cy = rng.uniform(-50.0, 65.0, regions)
    home = rng.integers(0, regions, n)
    x = cx[home] + rng.normal(0.0, p["region_sigma_deg"], n)
    y = cy[home] + rng.normal(0.0, p["region_sigma_deg"], n)
    x = np.round(np.clip(x, -179.9, 179.9), p["coord_decimals"])
    y = np.round(np.clip(y, -84.9, 84.9), p["coord_decimals"])
    w = zipf_weights(n, p["zipf"])[rng.permutation(n)]
    return x, y, w


def _codes(values: list) -> tuple[np.ndarray, list]:
    """(index of each value in the vocabulary, the vocabulary in first
    appearance order)."""
    vocab: dict = {}
    idx = np.asarray([vocab.setdefault(v, len(vocab)) for v in values],
                     np.int16)
    return idx, list(vocab)


def _events(p: dict) -> dict:
    """The CAMEO event code table: per root, ``bases`` three-digit base
    codes, each with ``subs`` four-digit codes under it; Zipf shares
    inside a root, scaled by the root's weight."""
    roots = p["root_codes"]
    b_n, s_n = p["event_bases"], p["event_subs"]
    code, base, root, weight = [], [], [], []
    inner = zipf_weights(b_n * (1 + s_n), 1.0)
    for r, (rc, rw) in enumerate(zip(roots, p["root_weights"])):
        k = 0
        for b in range(b_n):
            bc = f"{rc}{b}"
            for c in [bc] + [f"{bc}{s + 1}" for s in range(s_n)]:
                code.append(c)
                base.append(bc)
                root.append(r)
                weight.append(rw * inner[k] / inner.sum())
                k += 1
    base_idx, base_vocab = _codes(base)
    return {"code": code, "base_idx": base_idx, "base_vocab": base_vocab,
            "root": np.asarray(root, np.int16),
            "weight": np.asarray(weight)}


def _actors(p: dict, rng) -> dict:
    """Actor table: entry 0 is the blank actor; each other actor has a
    name and the six CAMEO actor codes GDELT gives it, most of them
    blank as in the export."""
    n = p["actors"]
    countries = [f"C{i:02d}" for i in range(p["actor_countries"])]
    country = rng.integers(0, len(countries), n)
    roles = p["actor_roles"]
    role = rng.integers(0, len(roles), n)

    def sometimes(share, make):
        hit = rng.random(n) < share
        vals = make(rng.integers(0, 1 << 16, n))
        return [v if h else "" for v, h in zip(vals, hit)]

    cols = {
        "Name": [f"ACTOR{i:04d}" for i in range(n)],
        "Code": [countries[c] + roles[r] for c, r in zip(country, role)],
        "CountryCode": [countries[c] for c in country],
        "GroupCode": sometimes(p["actor_group_share"],
                               lambda v: [f"G{x % 64:02d}" for x in v]),
        "EthnicCode": sometimes(p["actor_ethnic_share"],
                                lambda v: [f"E{x % 96:02d}" for x in v]),
        "Religion1Code": sometimes(p["actor_religion_share"],
                                   lambda v: [f"R{x % 12:02d}" for x in v]),
        "Religion2Code": sometimes(p["actor_religion2_share"],
                                   lambda v: [f"S{x % 24:02d}" for x in v]),
    }
    out = {}
    for f, vals in cols.items():
        idx, vocab = _codes([""] + vals)
        out[f] = (idx, vocab)
    w = zipf_weights(n, 1.0)
    out["weight"] = w
    return out


def _pick_actor(rng, weights: np.ndarray, blank: float, rows: int):
    w = np.concatenate([[weights.sum() * blank / (1.0 - blank)], weights])
    return categorical(rng, w, rows)


def make(cfg: dict, seed: int, rows: int, stream: int = 0) -> Table:
    """``rows`` events over the configuration's days.  The centroid,
    event and actor tables are the configuration's fixed world (its
    ``world_seed``), as GDELT's gazetteer and actor dictionary are; the
    events on them come from the seed."""
    p = cfg["params"]
    days = p["days"]
    table_rng = rng_for(p["world_seed"], 0)
    cx, cy, cw = _centroids(p, table_rng)
    ev = _events(p)
    act = _actors(p, table_rng)
    rng = rng_for(seed, 1 + stream)
    site = categorical(rng, cw, rows)
    day = (np.arange(rows, dtype=np.int64) * days) // rows
    event = categorical(rng, ev["weight"], rows).astype(np.int16)
    root = ev["root"][event]
    strings = {
        "globalEventId": (p["first_event_id"] + stream * (1 << 40)
                          + np.arange(rows, dtype=np.int64), None),
        "eventCode": (event, ev["code"]),
        "eventBaseCode": (ev["base_idx"][event], ev["base_vocab"]),
        "eventRootCode": (root, p["root_codes"]),
    }
    numbers = {"isRootEvent": (rng.random(rows)
                               < p["root_event_share"]).astype(np.int32)}
    for a, blank in (("actor1", p["actor1_blank_share"]),
                     ("actor2", p["actor2_blank_share"])):
        who = _pick_actor(rng, act["weight"], blank, rows)
        for f in ACTOR_FIELDS:
            idx, vocab = act[f]
            strings[a + f] = (idx[who], vocab)
    mentions = rng.geometric(p["mentions_p"], rows).astype(np.int32)
    numbers.update({
        "quadClass": np.asarray(QUAD_OF_ROOT, np.int32)[root],
        "goldsteinScale": np.asarray(p["root_goldstein"], np.float64)[root],
        "numMentions": mentions,
        "numSources": np.minimum(mentions, rng.geometric(
            p["sources_p"], rows).astype(np.int32)),
        "numArticles": mentions,
        "avgTone": np.round(rng.normal(p["tone_mean"], p["tone_sd"], rows),
                            p["tone_decimals"]),
    })
    return Table(x=cx[site], y=cy[site], t=p["start_ms"] + day * DAY_MS,
                 strings=strings, numbers=numbers,
                 anchors={"centroids": (cx, cy, cw)})
