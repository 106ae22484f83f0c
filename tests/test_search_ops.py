"""searchsorted2 / expand_ranges kernels vs numpy equivalents."""

import jax.numpy as jnp
import numpy as np
import pytest

from geomesa_tpu.ops import expand_ranges, searchsorted2


def ref_searchsorted2(hi, lo, qh, ql, side):
    # composite via python tuples
    keys = list(zip(hi.tolist(), lo.tolist()))
    out = []
    import bisect
    for q in zip(qh.tolist(), ql.tolist()):
        fn = bisect.bisect_left if side == "left" else bisect.bisect_right
        out.append(fn(keys, q))
    return np.array(out)


def test_searchsorted2_matches_bisect(rng):
    n = 5000
    hi = np.sort(rng.integers(0, 50, n))
    lo = rng.integers(0, 1 << 40, n)
    # sort lexicographically
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    qh = rng.integers(-1, 52, 200)
    ql = rng.integers(0, 1 << 40, 200)
    for side in ("left", "right"):
        got = np.asarray(searchsorted2(jnp.asarray(hi), jnp.asarray(lo),
                                       jnp.asarray(qh), jnp.asarray(ql), side=side))
        np.testing.assert_array_equal(got, ref_searchsorted2(hi, lo, qh, ql, side))


@pytest.mark.parametrize("k1_dtype", [np.int32, np.int64])
def test_sort_lex2_matches_lexsort(rng, k1_dtype):
    """Two single-key sorts give the (k1, k2) order of a two-key sort,
    negative and sentinel keys included, with payload rows intact."""
    from geomesa_tpu.ops.search import sort_lex2
    n = 4096
    top = np.iinfo(k1_dtype).max
    k1 = rng.integers(-3, 5, n).astype(k1_dtype)
    k2 = rng.integers(-(1 << 40), 1 << 40, n)
    k1[::97], k2[::97] = top, np.iinfo(np.int64).max   # sentinels
    k2[1::5] = k2[0]                                    # ties in k2
    row = np.arange(n, dtype=np.int64)
    a, b, r, f = sort_lex2(jnp.asarray(k1), jnp.asarray(k2),
                           jnp.asarray(row), jnp.asarray(row * 0.5))
    order = np.lexsort((k2, k1))
    np.testing.assert_array_equal(np.asarray(a), k1[order])
    np.testing.assert_array_equal(np.asarray(b), k2[order])
    r = np.asarray(r)
    np.testing.assert_array_equal(k1[r], k1[order])
    np.testing.assert_array_equal(k2[r], k2[order])
    np.testing.assert_array_equal(np.sort(r), row)
    np.testing.assert_array_equal(np.asarray(f), r * 0.5)


def test_searchsorted2_empty_and_single():
    hi = jnp.asarray(np.array([5], dtype=np.int64))
    lo = jnp.asarray(np.array([7], dtype=np.int64))
    q = jnp.asarray(np.array([4, 5, 6], dtype=np.int64))
    ql = jnp.asarray(np.array([9, 7, 0], dtype=np.int64))
    got = np.asarray(searchsorted2(hi, lo, q, ql, side="left"))
    np.testing.assert_array_equal(got, [0, 0, 1])
    got_r = np.asarray(searchsorted2(hi, lo, q, ql, side="right"))
    np.testing.assert_array_equal(got_r, [0, 1, 1])


def test_expand_ranges_basic():
    starts = jnp.asarray(np.array([10, 100, 1000]))
    counts = jnp.asarray(np.array([3, 0, 2]))
    idx, valid, rid = expand_ranges(starts, counts, capacity=8)
    np.testing.assert_array_equal(np.asarray(idx)[np.asarray(valid)],
                                  [10, 11, 12, 1000, 1001])
    np.testing.assert_array_equal(np.asarray(rid)[np.asarray(valid)],
                                  [0, 0, 0, 2, 2])
    assert int(np.asarray(valid).sum()) == 5


def test_expand_ranges_exact_capacity():
    starts = jnp.asarray(np.array([0, 5]))
    counts = jnp.asarray(np.array([2, 2]))
    idx, valid, _ = expand_ranges(starts, counts, capacity=4)
    assert np.asarray(valid).all()
    np.testing.assert_array_equal(np.asarray(idx), [0, 1, 5, 6])


def test_coded_pos_bits_boundaries():
    from geomesa_tpu.ops.search import coded_pos_bits

    # 20 pos bits + 11 qid bits = 31 → int32-eligible layout
    assert coded_pos_bits(1 << 20, 1 << 11) == 20
    # one more pos bit overflows 31 → int64 fallback layout
    assert coded_pos_bits(1 << 21, 1 << 11) == 40
    assert coded_pos_bits(2, 2) == 1
    assert coded_pos_bits((1 << 40), 2) == 40
    # multihost gids span > 2^40 (process << 40 | row): the layout must
    # widen, not truncate process bits into the qid field
    assert coded_pos_bits(1 << 41, 4) == 41
    assert coded_pos_bits(1 << 42, 1 << 21) == 42
    with pytest.raises(ValueError, match="coded layout overflow"):
        coded_pos_bits(1 << 60, 1 << 10)


def test_query_many_int64_wire_path(monkeypatch):
    """Force the 40-bit int64 coding and check exactness (the layout used
    for shards too big for the int32 wire)."""
    import numpy as np

    from geomesa_tpu.index import z3 as z3mod

    monkeypatch.setattr(z3mod, "coded_pos_bits", lambda n, q: 40)
    rng = np.random.default_rng(8)
    n = 20_000
    ms = 1514764800000
    x = rng.uniform(-75, -73, n)
    y = rng.uniform(40, 42, n)
    t = rng.integers(ms, ms + 14 * 86_400_000, n)
    idx = z3mod.Z3PointIndex.build(x, y, t, period="week")
    windows = [
        ([(-74.5, 40.5, -73.5, 41.5)], ms, ms + 7 * 86_400_000),
        ([(-74.2, 40.1, -73.8, 40.9)], ms + 86_400_000, ms + 3 * 86_400_000),
    ]
    out = idx.query_many(windows)
    for (boxes, lo, hi), hits in zip(windows, out):
        b = boxes[0]
        want = np.flatnonzero(
            (x >= b[0]) & (x <= b[2]) & (y >= b[1]) & (y <= b[3])
            & (t >= lo) & (t <= hi))
        np.testing.assert_array_equal(hits, want)


def test_pack_wire_total_survives_int32(monkeypatch):
    """A candidate total ≥ 2^31 must survive the int32 wire (split-word
    header) so capacity overflow is detected, not silently wrapped."""
    import jax.numpy as jnp
    import numpy as np

    from geomesa_tpu.ops.search import _TOTAL_SPLIT, pack_wire

    big = (1 << 31) + 12345
    wire = np.asarray(pack_wire(
        jnp.int64(big), jnp.arange(4, dtype=jnp.int32),
        jnp.ones(4, dtype=bool), jnp.int32))
    decoded = (int(wire[0]) << _TOTAL_SPLIT) | int(wire[1])
    assert decoded == big
