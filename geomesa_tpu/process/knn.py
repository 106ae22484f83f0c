"""k-nearest-neighbors: expanding-window candidate search + exact sort.

The reference's KNNQuery (geomesa-process/.../process/knn/KNNQuery.scala:
34-101) spirals outward over GeoHash cells, querying each cell until k
neighbors are secure.  The TPU-native re-design replaces the cell spiral
with **expanding bbox rounds**: each round issues one indexed window query
(z-range decomposed, vectorized candidate filter) with twice the previous
radius, stopping when k hits are found whose k-th distance is covered by
the window — a handful of large batched scans instead of many tiny ones,
which is the shape device hardware wants.
"""

from __future__ import annotations

import numpy as np

__all__ = ["knn_process", "haversine_m"]

EARTH_RADIUS_M = 6_371_008.8


def haversine_m(lon1, lat1, lon2, lat2):
    """Vectorized great-circle distance in meters."""
    lon1, lat1, lon2, lat2 = (np.radians(np.asarray(v, dtype=np.float64))
                              for v in (lon1, lat1, lon2, lat2))
    dlon = lon2 - lon1
    dlat = lat2 - lat1
    a = np.sin(dlat / 2) ** 2 + np.cos(lat1) * np.cos(lat2) * np.sin(dlon / 2) ** 2
    return 2 * EARTH_RADIUS_M * np.arcsin(np.sqrt(np.clip(a, 0, 1)))


def _deg_window(x: float, y: float, radius_m: float):
    """Bbox covering a radius (meters) around a point, degree-padded."""
    dlat = np.degrees(radius_m / EARTH_RADIUS_M)
    cos = max(0.01, np.cos(np.radians(y)))
    dlon = dlat / cos
    return (max(-180.0, x - dlon), max(-90.0, y - dlat),
            min(180.0, x + dlon), min(90.0, y + dlat))


def knn_process(store, schema: str, x: float, y: float, k: int,
                t_lo_ms: int | None = None, t_hi_ms: int | None = None,
                initial_radius_m: float = 1000.0,
                max_radius_m: float = 2_000_000.0):
    """Return (positions, distances_m) of the k nearest features to (x, y).

    ``store`` is a TpuDataStore; spatial candidates come from the z2/z3
    index via bbox window queries; exact haversine distances rank them.
    """
    sft = store.get_schema(schema)
    geom = sft.geom_field
    radius = float(initial_radius_m)
    st = store._store(schema)
    batch = st.batch
    mh = getattr(st, "multihost", False)
    if (batch is None or len(batch) == 0) and not mh:
        # multihost: a locally-empty process must still enter the
        # collective window scans its peers run
        return np.empty(0, dtype=np.int64), np.empty(0)
    # None bounds mean "no time constraint" — query_windows plans these
    # over the data's extent instead of a sentinel interval
    lo = int(t_lo_ms) if t_lo_ms is not None and sft.dtg_field else None
    hi = int(t_hi_ms) if t_hi_ms is not None and sft.dtg_field else None
    if batch is None:
        from ..features.batch import FeatureBatch
        st.batch = batch = FeatureBatch.empty(sft)
    all_xy = batch.geom_xy(geom)

    def rank(positions):
        """(effective_positions, distances, ascending order) — under
        multihost each process measures ITS rows and the (gid, dist)
        pairs allgather as ONE packed collective, so every process
        ranks the same global list."""
        if mh:
            from ..parallel.multihost import allgather_concat
            from ._multihost import split_local
            rows_l, gids_l, _ = split_local(st, positions)
            d_loc = haversine_m(x, y, all_xy[0][rows_l],
                                all_xy[1][rows_l])
            packed = np.stack([gids_l, d_loc.view(np.int64)], axis=1)
            out = allgather_concat(packed)
            positions = out[:, 0].copy()
            d = out[:, 1].copy().view(np.float64)
        else:
            d = haversine_m(x, y, all_xy[0][positions],
                            all_xy[1][positions])
        order = np.argsort(d, kind="stable")
        return positions, d, order

    # batched expanding rings: each dispatch scans THREE radii at once
    # (r, 2r, 4r) so one dispatch and host sync serve three rounds — the
    # GeoHash-spiral expansion (process/knn/KNNQuery.scala:34-101)
    # re-expressed as indexed window batches
    while True:
        radii = [radius, radius * 2, radius * 4]
        windows = [([_deg_window(x, y, r)], lo, hi) for r in radii]
        ring_hits = store.query_windows(schema, windows)
        for r, positions in zip(radii, ring_hits):
            if not len(positions):
                continue
            pos, d, order = rank(positions)
            # secure condition: the k-th distance fits inside the scanned
            # window (no closer feature can hide outside it)
            if len(order) >= k and d[order[k - 1]] <= r:
                sel = order[:k]
                return pos[sel], d[sel]
        if radii[-1] >= max_radius_m:
            positions = ring_hits[-1]
            if len(positions) == 0:
                return np.empty(0, dtype=np.int64), np.empty(0)
            pos, d, order = rank(positions)
            sel = order[:k]
            return pos[sel], d[sel]
        radius *= 8.0
