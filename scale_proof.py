"""Single-chip 500M-point scale proof (round-3 next #7).

Streams a synthetic GDELT-shaped workload slice-by-slice into a
:class:`geomesa_tpu.index.z3_lean.LeanZ3Index` on the real chip — no
host array ever holds more than one slice of input, the device holds
only the 16 B/point key columns (generational; docs/scale.md budget
asserted at runtime), and the payload lives in host RAM for the exact
re-check.  Ends with oracle-verified queries at full capacity.

Run directly (``python scale_proof.py``) or through ``bench.py`` (the
``scale`` stanza).  ``SCALE_N`` overrides the target row count.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

MS_2021 = 1609459200000  # 2021-01-01
DAY = 86_400_000

#: usable HBM on a v5e chip (15.75 GiB) minus scan/transfer slack
HBM_BUDGET_BYTES = int(13.5 * 2**30)


def _improves(record_path: str, rows: int) -> bool:
    try:
        with open(record_path) as f:
            return rows >= int(json.load(f).get("rows", 0))
    except Exception:
        return True


def _slice_data(i: int, m: int, frac_lo: float = 0.0,
                frac_hi: float = 1.0):
    """Slice ``i`` of the synthetic GDELT-shaped stream: world-spread
    events with population hotspots.  Timestamps draw from the
    ``[frac_lo, frac_hi)`` fraction of the six-month span — the round-5
    1B stream ingests CHRONOLOGICALLY (like the real GDELT feed), so
    generations partition by time and the newest (budget-reserved
    ``full``-tier) generation serves the hot window (round-4 VERDICT
    #5)."""
    rng = np.random.default_rng(9_000 + i)
    hot = rng.integers(0, 4, m)
    cx = np.array([-74.0, 2.3, 116.4, 28.0])[hot]
    cy = np.array([40.7, 48.8, 39.9, -26.2])[hot]
    x = np.clip(cx + rng.normal(0, 20.0, m), -179.9, 179.9)
    y = np.clip(cy + rng.normal(0, 12.0, m), -89.9, 89.9)
    lo = MS_2021 + int(frac_lo * 180 * DAY)
    hi = max(lo + 1, MS_2021 + int(frac_hi * 180 * DAY))
    t = rng.integers(lo, hi, m)
    return x, y, t


def run(n: int = 500_000_000, slice_rows: int = 16_777_216,
        progress=print, record: bool = True) -> dict:
    import jax

    from geomesa_tpu.index.z3_lean import LeanZ3Index

    # round-5: payload ON — the demotion policy RESERVES the live
    # generation's (x, y, t) device payload under the budget (round-4
    # VERDICT #5), so the newest data always serves the fused
    # device-exact path; older payloads drop to keys (16 B/pt) and cold
    # runs spill to host RAM oldest-first (1B: 16 GB of keys > 15.75
    # GiB HBM) where the STACKED numpy bisection answers beside the
    # payload — the tablet server's memory/disk split on one chip.
    idx = LeanZ3Index(period="week", generation_slots=slice_rows,
                      payload_on_device=True,
                      hbm_budget_bytes=HBM_BUDGET_BYTES)
    host_budget = 40 * n  # 16 B/pt spilled keys + 24 B/pt payload
    assert host_budget <= 110 * 2**30, (
        f"host residency {host_budget/2**30:.0f} GiB exceeds this "
        "machine's RAM — shrink SCALE_N")
    windows = [
        ((-75.0, 40.0, -73.0, 42.0),
         MS_2021 + 30 * DAY, MS_2021 + 44 * DAY),   # NYC fortnight
        ((1.0, 47.5, 3.5, 50.0),
         MS_2021 + 90 * DAY, MS_2021 + 97 * DAY),   # Paris week
    ]
    # prewarm the append/count/scan/density programs for EVERY tier on
    # a same-shaped DUMMY generation while the device is empty: with
    # warm jit caches the real queries are pure dispatches
    warm = LeanZ3Index(period="week", generation_slots=slice_rows,
                       payload_on_device=True)
    wx, wy, wt = _slice_data(0, 4096)
    warm.append(wx, wy, wt)
    world = (-180.0, -90.0, 180.0, 90.0)
    for box, lo, hi in windows:
        warm.query([box], lo, hi)         # full-tier scan program
    warm.density([world], None, None, world, 256, 128)
    warm.generations[0].drop_payload()     # keys-tier programs
    warm._sentinels.pop("full", None)
    for box, lo, hi in windows:
        warm.query([box], lo, hi)
    warm.density([world], None, None, world, 256, 128)
    # keys-tier APPEND program too (the live generation appends through
    # it if the budget ever demotes its payload)
    warm.append(wx[:256], wy[:256], wt[:256])
    del warm
    progress("  scale: programs prewarmed (full + keys tiers)")
    def verify(label: str) -> dict:
        """Oracle-verified queries at the CURRENT capacity."""
        xf, yf, tf = idx._payload_flat()
        q_warm, q_hits = [], []
        for bi, (box, lo, hi) in enumerate(windows):
            got = idx.query([box], lo, hi)
            tq = time.perf_counter()
            got = idx.query([box], lo, hi)   # steady-state number
            q_warm.append(time.perf_counter() - tq)
            q_hits.append(len(got))
            want = np.flatnonzero(
                (xf >= box[0]) & (xf <= box[2]) & (yf >= box[1])
                & (yf <= box[3]) & (tf >= lo) & (tf <= hi))
            assert np.array_equal(got, want), (
                f"{label} window {bi}: {len(got)} vs {len(want)}")
        progress(f"  scale: {label} verified — hits {q_hits}, warm "
                 f"{[round(v*1e3) for v in q_warm]}ms (oracle-exact)")
        return {"query_warm_ms": [round(v * 1e3, 1) for v in q_warm],
                "query_hits": q_hits, "oracle_exact": True}

    # the 1B spill regime records separately from the 500M all-resident
    # record (different configurations; both monotonic)
    record_name = ("SCALE_1B_r05.json" if n > 600_000_000
                   else "SCALE_r03.json")
    record_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               record_name)
    t0 = time.perf_counter()
    done = 0
    i = 0
    out: dict = {}
    while done < n:
        m = min(slice_rows, n - done)
        x, y, t = _slice_data(i, m, done / n, (done + m) / n)
        idx.append(x, y, t)
        # block each slice: serialized slices keep the timing honest
        idx.block()
        done += m
        i += 1
        if i % 8 == 0 or done >= n:
            build_s = time.perf_counter() - t0
            resident = idx.device_bytes()
            assert resident <= HBM_BUDGET_BYTES, resident
            stats = jax.local_devices()[0].memory_stats() or {}
            in_use = int(stats.get("bytes_in_use", resident))
            assert in_use <= int(15.75 * 2**30), in_use
            # verify + CHECKPOINT at increasing capacities: a run cut
            # short must not erase the largest oracle-verified
            # capacity already reached
            out = {
                "rows": int(len(idx)),
                "generations": len(idx.generations),
                "tiers": idx.tier_counts(),
                "device_key_bytes": int(resident),
                "host_key_bytes": int(idx.host_key_bytes()),
                "hbm_bytes_in_use": in_use,
                "build_s": round(build_s, 1),
                "ingest_rows_per_sec": int(len(idx) / build_s),
                **verify(f"{done/1e6:.0f}M"),
            }
            if record and _improves(record_path, out["rows"]):
                # monotonic: neither live runs nor a wedged rerun's
                # early checkpoints may replace a larger verified record
                with open(record_path + ".tmp", "w") as f:
                    json.dump(out, f, indent=1)
                os.replace(record_path + ".tmp", record_path)
    # -- round-5 completion extras ------------------------------------
    tiers = idx.tier_counts()
    if n > 600_000_000:
        # the budget-reserved live generation must still be full-tier
        assert tiers["full"] >= 1, tiers
    # hot-window query: the last day of the chronological stream lives
    # in the newest generation(s) — the reserved full tier serves it
    # survivors-only (round-4 VERDICT #5)
    hot = (MS_2021 + 179 * DAY, MS_2021 + 180 * DAY)
    hot_box = (-75.0, 40.0, -73.0, 42.0)
    got = idx.query([hot_box], *hot)
    tq = time.perf_counter()
    got = idx.query([hot_box], *hot)
    hot_warm = time.perf_counter() - tq
    xf, yf, tf = idx._payload_flat()
    want = np.flatnonzero(
        (xf >= hot_box[0]) & (xf <= hot_box[2]) & (yf >= hot_box[1])
        & (yf <= hot_box[3]) & (tf >= hot[0]) & (tf <= hot[1]))
    assert np.array_equal(got, want), (len(got), len(want))
    out["hot_window_warm_ms"] = round(hot_warm * 1e3, 1)
    out["hot_window_hits"] = int(len(want))
    progress(f"  scale: hot-window (last day) warm "
             f"{hot_warm*1e3:.0f}ms, {len(want)} hits, exact "
             f"(tiers {tiers})")
    # whole-extent density push-down: the heatmap accumulates next to
    # the keys per tier and only the grid crosses (round-4 VERDICT #2)
    world = (-180.0, -90.0, 180.0, 90.0)
    grid = idx.density([world], None, None, world, 256, 128)
    tq = time.perf_counter()
    grid = idx.density([world], None, None, world, 256, 128)
    dens_s = time.perf_counter() - tq
    # chunked numpy oracle (bounded host working set)
    want_grid = np.zeros((128, 256))
    step = 1 << 26
    for lo in range(0, len(xf), step):
        gx = np.clip(((xf[lo:lo + step] + 180.0) / 360.0 * 256)
                     .astype(np.int64), 0, 255)
        gy = np.clip(((yf[lo:lo + step] + 90.0) / 180.0 * 128)
                     .astype(np.int64), 0, 127)
        np.add.at(want_grid, (gy, gx), 1.0)
    assert grid.sum() == len(idx), (grid.sum(), len(idx))
    dens_exact = bool(np.array_equal(grid, want_grid))
    out["density_1b_ms"] = round(dens_s * 1e3, 1)
    out["density_oracle_exact"] = dens_exact
    if not dens_exact:
        # cross-platform f64 boundary cells only — record the extent
        diff = np.abs(grid - want_grid)
        out["density_cells_differing"] = int((diff > 0).sum())
        out["density_max_cell_diff"] = float(diff.max())
    progress(f"  scale: whole-extent 256x128 heatmap {dens_s*1e3:.0f}ms"
             f" warm, mass exact, per-cell exact={dens_exact}")
    if record and _improves(record_path, out["rows"]):
        with open(record_path + ".tmp", "w") as f:
            json.dump(out, f, indent=1)
        os.replace(record_path + ".tmp", record_path)
    progress(f"  scale: COMPLETE at {len(idx)/1e6:.0f}M rows, "
             f"{out['hbm_bytes_in_use']/2**30:.2f} GiB HBM")
    return out


if __name__ == "__main__":
    from geomesa_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    n = int(os.environ.get("SCALE_N", 500_000_000))
    out = run(n)
    print(json.dumps({"metric": "scale_proof", **out}))
