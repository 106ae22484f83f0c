"""LeanZ3Index: tiered generational index (the lean profile's scale
path; both benchmark cells run it on the chip, and this file keeps the
logic under the fast CI loop).

Round-4 coverage: sentinel-generation bucket padding does no extra
dispatches (VERDICT #9), the full tier's device-side exact mask equals
the keys tier's host mask and the brute-force oracle (VERDICT #7), and
host-spilled runs answer queries exactly (VERDICT #2 groundwork)."""

import numpy as np
import pytest

from geomesa_tpu.index.z3 import Z3PointIndex
from geomesa_tpu.index.z3_lean import LeanZ3Index

MS = 1514764800000
DAY = 86_400_000


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    n = 60_000
    return (rng.uniform(-75, -73, n), rng.uniform(40, 42, n),
            rng.integers(MS, MS + 14 * DAY, n))


def _brute(x, y, t, boxes, lo, hi):
    m = np.zeros(len(x), dtype=bool)
    for b in np.atleast_2d(np.asarray(boxes)):
        m |= ((x >= b[0]) & (x <= b[2]) & (y >= b[1]) & (y <= b[3]))
    if lo is not None:
        m &= t >= lo
    if hi is not None:
        m &= t <= hi
    return np.flatnonzero(m)


@pytest.mark.parametrize("payload_on_device", [True, False])
def test_generational_build_query_oracle(data, payload_on_device):
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 14,
                      payload_on_device=payload_on_device)
    for s in range(0, len(x), 25_000):  # slices straddle generations
        sl = slice(s, s + 25_000)
        idx.append(x[sl], y[sl], t[sl])
    assert len(idx) == len(x)
    assert len(idx.generations) == -(-len(x) // (1 << 14))
    box = (-74.5, 40.5, -73.5, 41.5)
    lo, hi = MS + 2 * DAY, MS + 9 * DAY
    got = idx.query([box], lo, hi)
    np.testing.assert_array_equal(got, _brute(x, y, t, [box], lo, hi))
    # parity with the full-fat index
    full = Z3PointIndex.build(x, y, t, period="week")
    np.testing.assert_array_equal(got, np.sort(full.query([box], lo, hi)))


def test_open_time_bounds_and_multi_box(data):
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 15)
    idx.append(x, y, t)
    boxes = [(-74.9, 40.1, -74.6, 40.4), (-73.4, 41.6, -73.1, 41.9)]
    got = idx.query(boxes, None, None)
    np.testing.assert_array_equal(got, _brute(x, y, t, boxes, None, None))


def test_query_many_batched_windows(data):
    """Multi-window scans run all windows × all generations in a fixed
    number of dispatches and match per-window brute force + the
    full-fat index's query_many."""
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 14)
    idx.append(x, y, t)
    rng = np.random.default_rng(9)
    windows = []
    for _ in range(7):
        cx = float(rng.uniform(-74.8, -73.2))
        cy = float(rng.uniform(40.2, 41.8))
        lo = MS + int(rng.integers(0, 9)) * DAY
        windows.append(([(cx - .3, cy - .3, cx + .3, cy + .3)],
                        lo, lo + 3 * DAY))
    windows.append(([(-74.5, 40.5, -73.5, 41.5)], None, None))
    before = idx.dispatch_count
    got = idx.query_many(windows)
    # one totals probe + one scan for the single populated tier
    assert idx.dispatch_count - before == 2
    full = Z3PointIndex.build(x, y, t, period="week")
    want = full.query_many(windows)
    for g, w, (bxs, lo, hi) in zip(got, want, windows):
        np.testing.assert_array_equal(g, _brute(x, y, t, bxs, lo, hi))
        np.testing.assert_array_equal(g, np.sort(w))


def test_sentinel_padding_no_extra_dispatches(data):
    """Bucket padding uses the shared EMPTY sentinel generation: 5 real
    generations pad to 8 but the padded slots carry 8-slot all-sentinel
    columns (zero seeks match), and the query still runs in the fixed
    dispatch count (VERDICT r3 weak #5 / next #9)."""
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 14,
                      payload_on_device=False)
    idx.append(x[:30_000], y[:30_000], t[:30_000])
    # 30000 rows / 16384 slots -> 2 generations; add 3 more tiny ones
    for i in range(3):
        idx.generations[-1].n = idx.generations[-1].capacity  # force roll
        s = 30_000 + i * 1000
        idx.append(x[s:s + 1000], y[s:s + 1000], t[s:s + 1000])
    assert len(idx.generations) == 5
    from geomesa_tpu.index.z3_lean import _GEN_BUCKET
    # 5 gens pad to 8 in the totals probe, and in the scan because all
    # 5 are live: fewer than a bucket of live gens would scan unpadded
    assert _GEN_BUCKET == 4
    # the shared per-instance sentinel generation is full-size (uniform
    # program shapes -> one compile per bucket) and matches zero seeks
    sb, sz, sp = idx._sentinel_cols("keys")
    assert sb.shape == (1 << 14,) and int(sp[0]) == -1
    before = idx.dispatch_count
    box = (-74.5, 40.5, -73.5, 41.5)
    got = idx.query([box], MS + 2 * DAY, MS + 9 * DAY)
    assert idx.dispatch_count - before == 2  # probe + one tier scan
    rows = np.concatenate([np.arange(30_000),
                           np.arange(30_000, 33_000)])
    xs, ys, ts = x[rows], y[rows], t[rows]
    np.testing.assert_array_equal(
        got, _brute(xs, ys, ts, [box], MS + 2 * DAY, MS + 9 * DAY))


def test_full_tier_device_exact_mask_matches_host(data):
    """The full tier's fused device mask (VERDICT #7) returns exactly
    the host-masked hit set — verified across the tier boundary by
    querying the same data in both configurations."""
    x, y, t = data
    dev = LeanZ3Index(period="week", generation_slots=1 << 14,
                      payload_on_device=True)
    host = LeanZ3Index(period="week", generation_slots=1 << 14,
                       payload_on_device=False)
    dev.append(x, y, t)
    host.append(x, y, t)
    assert dev.tier_counts()["full"] == len(dev.generations)
    assert host.tier_counts()["keys"] == len(host.generations)
    windows = [([(-74.5, 40.5, -73.5, 41.5)], MS + 2 * DAY, MS + 9 * DAY),
               ([(-74.2, 40.1, -73.1, 41.2)], None, None)]
    for gd, gh, (bxs, lo, hi) in zip(dev.query_many(windows),
                                     host.query_many(windows), windows):
        np.testing.assert_array_equal(gd, gh)
        np.testing.assert_array_equal(gd, _brute(x, y, t, bxs, lo, hi))


def test_budget_demotes_payload_then_spills(data):
    """Under HBM pressure payload drops first (full → keys), then key
    runs spill to host RAM (keys → host), oldest first; queries stay
    oracle-exact across every mix (VERDICT #2 groundwork)."""
    x, y, t = data
    slots = 1 << 14
    # budget fits ~2 keys-tier generations only: 4 generations of data
    # force payload drops AND at least one host spill
    idx = LeanZ3Index(period="week", generation_slots=slots,
                      hbm_budget_bytes=3 * slots * 16,
                      payload_on_device=True)
    idx.append(x, y, t)   # 60k rows -> 4 generations
    tiers = idx.tier_counts()
    assert tiers["host"] >= 1          # spill happened
    assert tiers["full"] == 0          # payloads all dropped
    assert idx.device_bytes() <= 3 * slots * 16
    assert idx.host_key_bytes() > 0
    box = (-74.5, 40.5, -73.5, 41.5)
    lo, hi = MS + 2 * DAY, MS + 9 * DAY
    np.testing.assert_array_equal(idx.query([box], lo, hi),
                                  _brute(x, y, t, [box], lo, hi))
    # appends continue after spills (a fresh device generation opens)
    rng = np.random.default_rng(11)
    nx = rng.uniform(-74.4, -73.6, 500)
    ny = rng.uniform(40.6, 41.4, 500)
    nt = rng.integers(MS, MS + 14 * DAY, 500)
    idx.append(nx, ny, nt)
    ax, ay, at = np.r_[x, nx], np.r_[y, ny], np.r_[t, nt]
    np.testing.assert_array_equal(idx.query([box], lo, hi),
                                  _brute(ax, ay, at, [box], lo, hi))


def test_budget_reserves_live_generation_payload(data):
    """The NEWEST generation keeps its device payload under budget
    pressure (round-4 VERDICT #5): older payloads drop and older key
    runs spill to host to make room, so the hot window is served by the
    fused device-exact path at any store size."""
    x, y, t = data
    slots = 1 << 14
    # budget holds: live full gen (40 B/slot) + both sentinels
    # (16 + 40 B/slot) + ~1 keys-tier gen (16 B/slot); 4 generations of
    # data must therefore end mixed full/keys/host with full >= 1
    budget = slots * (40 + 16 + 40 + 16 + 8)
    idx = LeanZ3Index(period="week", generation_slots=slots,
                      hbm_budget_bytes=budget, payload_on_device=True)
    idx.append(x, y, t)   # 60k rows -> 4 generations
    tiers = idx.tier_counts()
    assert tiers["full"] >= 1
    assert idx.generations[-1].tier == "full"   # the LIVE one
    assert tiers["host"] >= 1                   # others made room
    assert idx.device_bytes() <= budget
    box = (-74.5, 40.5, -73.5, 41.5)
    lo, hi = MS + 2 * DAY, MS + 9 * DAY
    np.testing.assert_array_equal(idx.query([box], lo, hi),
                                  _brute(x, y, t, [box], lo, hi))
    # a hot-window query touching only the live generation's rows is
    # answered exactly too (served from the fused device path)
    np.testing.assert_array_equal(
        idx.query([box], None, None),
        _brute(x, y, t, [box], None, None))


def test_host_stack_flat_seek_many_runs(monkeypatch):
    """50+ host-spilled runs answer a query batch with a BOUNDED number
    of searchsorted/bisection passes — the stacked seek is flat in run
    count (round-4 VERDICT #9), not a Python loop per run per bin."""
    rng = np.random.default_rng(21)
    n = 60_000
    x = rng.uniform(-75, -73, n)
    y = rng.uniform(40, 42, n)
    t = rng.integers(MS, MS + 21 * DAY, n)
    slots = 1 << 10
    idx = LeanZ3Index(period="week", generation_slots=slots,
                      hbm_budget_bytes=2 * slots * (16 + 16 + 40),
                      payload_on_device=False)
    idx.append(x, y, t)
    tiers = idx.tier_counts()
    assert tiers["host"] >= 50
    import geomesa_tpu.index.z3_lean as zl
    calls = {"searchsorted": 0, "bisect": 0}
    real_ss = np.searchsorted
    real_bs = zl._bisect_segments

    def count_ss(*a, **k):
        calls["searchsorted"] += 1
        return real_ss(*a, **k)

    def count_bs(*a, **k):
        calls["bisect"] += 1
        return real_bs(*a, **k)

    monkeypatch.setattr(zl.np, "searchsorted", count_ss)
    monkeypatch.setattr(zl, "_bisect_segments", count_bs)
    box = (-74.5, 40.5, -73.5, 41.5)
    lo, hi = MS + 2 * DAY, MS + 9 * DAY
    got = idx.query([box], lo, hi)
    # flat: exactly 2 bisection passes serve all 50+ runs (the old path
    # did 2 searchsorted calls x runs x distinct bins); the global
    # searchsorted count (numpy is patched module-wide, so planning
    # bookkeeping is included) must stay below one call per run
    assert calls["bisect"] == 2
    assert calls["searchsorted"] < tiers["host"]
    np.testing.assert_array_equal(got, _brute(x, y, t, [box], lo, hi))
    # spill MORE runs: the stack rebuilds and stays exact
    x2 = rng.uniform(-74.4, -73.6, 5_000)
    y2 = rng.uniform(40.6, 41.4, 5_000)
    t2 = rng.integers(MS, MS + 21 * DAY, 5_000)
    idx.append(x2, y2, t2)
    ax, ay, at = np.r_[x, x2], np.r_[y, y2], np.r_[t, t2]
    np.testing.assert_array_equal(idx.query([box], lo, hi),
                                  _brute(ax, ay, at, [box], lo, hi))


def test_empty_and_budget_bookkeeping():
    idx = LeanZ3Index(period="week")
    # open bounds on an empty index must not crash in planning
    assert len(idx.query([(-75, 40, -73, 42)], None, None)) == 0
    assert idx.device_bytes() == 0
    idx2 = LeanZ3Index(period="week", generation_slots=1 << 14,
                       payload_on_device=False)
    rng = np.random.default_rng(4)
    idx2.append(rng.uniform(-75, -73, 100), rng.uniform(40, 42, 100),
                rng.integers(MS, MS + DAY, 100))
    assert idx2.device_bytes() == (1 << 14) * 16
    idx3 = LeanZ3Index(period="week", generation_slots=1 << 14,
                       payload_on_device=True)
    idx3.append(rng.uniform(-75, -73, 100), rng.uniform(40, 42, 100),
                rng.integers(MS, MS + DAY, 100))
    assert idx3.device_bytes() == (1 << 14) * 40
    idx2.block()


def test_big_capacity_falls_back_per_generation(data):
    """Huge candidate sets route through per-generation buffers sized by
    each generation's own total (the batched shared-capacity buffer
    would cost G × max-total slots of HBM): one probe + one dispatch
    per populated generation."""
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 14,
                      payload_on_device=False)
    idx.append(x, y, t)
    idx.BATCH_SCAN_BUDGET = 1 << 14
    before = idx.dispatch_count
    # whole-world query: totals ~= all rows → capacity blows the
    # (shrunken) batched budget → per-generation path
    got = idx.query([(-180, -90, 180, 90)], None, None)
    np.testing.assert_array_equal(got, np.arange(len(x)))
    assert idx.dispatch_count - before == 1 + len(idx.generations)


def test_payload_provider_shares_store_columns(data):
    """With a payload provider the index retains NO payload of its own
    (the store owns the single host copy — VERDICT #1 groundwork)."""
    x, y, t = data
    idx = LeanZ3Index(period="week", generation_slots=1 << 14,
                      payload_on_device=False)
    idx.payload_provider = lambda: (x, y, t)
    idx.append(x, y, t)
    assert idx._payload == [] and idx._flat is None
    box = (-74.5, 40.5, -73.5, 41.5)
    np.testing.assert_array_equal(
        idx.query([box], None, None),
        _brute(x, y, t, [box], None, None))


def _probe_seeks(idx, windows):
    """(answers, generations the totals probe sought) of one batch."""
    from geomesa_tpu.metrics import LEAN_PROBE_SEEKS, registry
    c = registry.counter(LEAN_PROBE_SEEKS)
    before = c.count
    got = idx.query_many(windows)
    return got, c.count - before


def _ordered(data, shuffle=False, **kw):
    """45,000 rows in time order (or shuffled) at 2^14 slots: three
    generations of about 5, 5 and 4 days."""
    x, y, t = data
    n = 45_000
    order = np.argsort(t[:n], kind="stable")
    if shuffle:
        order = np.random.default_rng(5).permutation(order)
    xs, ys, ts = x[:n][order], y[:n][order], t[:n][order]
    idx = LeanZ3Index(period="week", generation_slots=1 << 14, **kw)
    for s in range(0, n, 10_000):
        idx.append(xs[s:s + 10_000], ys[s:s + 10_000], ts[s:s + 10_000])
    assert len(idx.generations) == 3
    return idx, xs, ys, ts


def _cancel_after_first_decompose(monkeypatch, poison: int):
    """Stop the decompose after its first window, and fill what
    ``np.empty`` hands out with ``poison`` (any content is allowed), so
    a probe that read an unplanned window's time would see it."""
    import geomesa_tpu.resilience as res
    planned = []

    def check(point="", scope=None):
        if point != "query.decompose":
            return False
        planned.append(point)
        return len(planned) > 1
    empty = np.empty

    def poisoned(shape, dtype=float, **kw):
        try:
            return np.full(shape, poison, dtype=dtype, **kw)
        except (TypeError, ValueError):
            return empty(shape, dtype=dtype, **kw)
    monkeypatch.setattr(res, "check_cancel", check)
    monkeypatch.setattr(np, "empty", poisoned)


BOX = (-74.5, 40.5, -73.5, 41.5)


@pytest.mark.parametrize("case", ["inside_one", "across_boundary",
                                  "untimed", "shuffled", "merged",
                                  "cancelled_decompose"])
def test_probe_seeks_only_generations_meeting_the_windows(
        data, case, monkeypatch):
    """The totals probe skips every generation whose time span misses
    all windows of the batch, pads nothing below a bucket, and answers
    as the brute force does."""
    idx, xs, ys, ts = _ordered(data, shuffle=case == "shuffled",
                               payload_on_device=case != "merged")
    g0, g1, g2 = idx.generations
    for i, g in enumerate(idx.generations):     # each slice widens
        sl = ts[i << 14:(i + 1) << 14]
        assert (g.t_lo, g.t_hi) == (int(sl.min()), int(sl.max()))
    mid0 = (g0.t_lo + g0.t_hi) // 2
    inside = ([BOX], mid0 - DAY // 2, mid0 + DAY // 2)
    windows, want_seeks = [inside], 1
    if case == "across_boundary":
        windows = [([BOX], g0.t_hi - DAY // 4, g1.t_lo + DAY // 4)]
        want_seeks = 2
    elif case == "untimed":
        windows = [([BOX], None, None)]
        want_seeks = 3
    elif case == "shuffled":
        want_seeks = 3
    elif case == "merged":
        assert idx.compact(factor=2)["merged_groups"] == 1
        merged, live = idx.generations
        assert (merged.tier, merged.t_lo, merged.t_hi) == (
            "keys", g0.t_lo, g1.t_hi)
        assert (live.t_lo, live.t_hi) == (g2.t_lo, g2.t_hi)
        mid1 = (g1.t_lo + g1.t_hi) // 2
        windows = [([BOX], mid1 - DAY // 2, mid1 + DAY // 2)]
    elif case == "cancelled_decompose":
        mid2 = (g2.t_lo + g2.t_hi) // 2
        _cancel_after_first_decompose(monkeypatch, poison=mid2)
        windows = [inside, ([BOX], mid2 - DAY // 2, mid2 + DAY // 2)]
    got, seeks = _probe_seeks(idx, windows)
    assert seeks == want_seeks
    for q, (g, (bxs, lo, hi)) in enumerate(zip(got, windows)):
        want = _brute(xs, ys, ts, bxs, lo, hi)
        if case == "cancelled_decompose" and q > 0:
            want = want[:0]          # never planned: scans nothing
        assert len(want) or case == "cancelled_decompose"
        np.testing.assert_array_equal(g, want)


@pytest.mark.parametrize("n_windows,width", [(1, 8), (3, 8), (8, 8),
                                             (9, 16)])
def test_exact_scan_pads_windows_to_the_bucket(data, monkeypatch,
                                               n_windows, width):
    """The exact scan's per-window arrays (boxes, their qids, the time
    bounds) pad to ``_WINDOW_BUCKET`` or the next power of two past it,
    so batches of one to eight windows share one program; answers stay
    the brute force's."""
    import geomesa_tpu.index.z3_lean as zl
    x, y, t = data
    n = 10_000
    idx = LeanZ3Index(period="week", generation_slots=1 << 14)
    idx.append(x[:n], y[:n], t[:n])
    seen = []
    scan = zl._lean_scan_exact_coded

    def recording(rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi, *cols,
                  **kw):
        seen.append((boxes.shape[0], bqid.shape[0], qtlo.shape[0],
                     qthi.shape[0]))
        return scan(rb, rlo, rhi, rqid, boxes, bqid, qtlo, qthi, *cols,
                    **kw)
    monkeypatch.setattr(zl, "_lean_scan_exact_coded", recording)
    windows = [([(x[i] - .01, y[i] - .01, x[i] + .01, y[i] + .01)],
                int(t[i]) - DAY, int(t[i]) + DAY)
               for i in range(n_windows)]
    got = idx.query_many(windows)
    assert seen and all(s == (width,) * 4 for s in seen)
    for g, (bxs, lo, hi) in zip(got, windows):
        want = _brute(x[:n], y[:n], t[:n], bxs, lo, hi)
        assert len(want)
        np.testing.assert_array_equal(g, want)
