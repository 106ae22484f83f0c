"""Sorted-key search kernels: the TPU replacement for KV-store seeks.

The reference's scan path turns z-ranges into tablet-server seeks over a
distributed sorted map (e.g. AccumuloQueryPlan BatchScanPlan,
geomesa-accumulo/.../data/AccumuloQueryPlan.scala:123-157).  Here the
"table" is a lexicographically sorted pair of device-resident columns
``(hi, lo)`` — for Z3, ``hi`` = time bin and ``lo`` = 63-bit z — and a
seek is a branchless vectorized binary search evaluated for all R query
ranges at once.  Fixed iteration count (log2 n), no data-dependent control
flow: jit/vmap/shard_map friendly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["searchsorted2", "expand_ranges", "gather_capacity",
           "scan_read_bytes",
           "sort_lex2", "coded_pos_bits", "wire_dtype", "pack_wire", "pack_coded",
           "run_packed_query"]

#: bits per word of the split candidate total in the wire header
_TOTAL_SPLIT = 30


def coded_pos_bits(n_rows: int, n_queries: int) -> int:
    """Wire coding for multi-window scans: bits reserved for the position
    field of the ``qid << pos_bits | pos`` code.  Prefers an
    int32-fitting layout (qid_bits + pos_bits <= 31); falls back to a
    40-bit int64 layout for huge shards, widening further for position
    spans beyond 2^40 (multihost gids code ``process << 40 | row``, so
    their span needs ``40 + proc_bits`` position bits — truncating to 40
    would bleed process bits into the qid field).  :func:`wire_dtype`
    maps the result to the wire dtype — keep the two in sync via this
    module."""
    import numpy as np
    pos_bits = max(1, int(np.ceil(np.log2(max(2, n_rows)))))
    qid_bits = max(1, int(np.ceil(np.log2(max(2, n_queries)))))
    if pos_bits + qid_bits <= 31:
        return pos_bits
    pos_bits = max(40, pos_bits)
    if pos_bits + qid_bits > 63:
        raise ValueError(
            f"coded layout overflow: {pos_bits} position bits + "
            f"{qid_bits} query bits exceed int64 — batch fewer windows")
    return pos_bits


def wire_dtype(pos_bits: int):
    """Wire dtype for a coded layout chosen by :func:`coded_pos_bits`."""
    return jnp.int32 if pos_bits < 31 else jnp.int64


def pack_coded(total, qid, pos, mask, pos_bits: int):
    """Encode a multi-window scan result: ``qid << pos_bits | pos`` in
    the dtype :func:`wire_dtype` picks, wrapped by :func:`pack_wire` —
    the single definition of the coded layout shared by every batched
    scan kernel (decode: ``coded >> pos_bits`` / mask)."""
    dt = wire_dtype(pos_bits)
    coded = (qid.astype(dt) << dt(pos_bits)) | pos.astype(dt)
    return pack_wire(total, coded, mask, dt)


def pack_wire(total, values, mask, dt):
    """Encode one scan's result as the packed wire vector
    ``[total_hi, total_lo, v_0|-1, v_1|-1, …]`` in dtype ``dt``.

    The device→host link costs ~125ms/MB, so values travel as int32
    whenever they fit (positions, or qid<<pos_bits|pos codes that fit 31
    bits).  The candidate ``total`` — which can legitimately exceed 2^31
    when overlapping covering ranges double-count a large gather — is
    split into two 30-bit words so the int32 wire can never wrap it into
    a false "fits" signal (overflow detection depends on it).
    """
    head = jnp.stack([(total >> _TOTAL_SPLIT).astype(dt),
                      (total & ((1 << _TOTAL_SPLIT) - 1)).astype(dt)])
    packed = jnp.where(mask, values.astype(dt), dt(-1))
    return jnp.concatenate([head, packed])


def run_packed_query(dispatch, capacity: int):
    """Run a packed one-dispatch scan with adaptive capacity.

    ``dispatch(capacity) -> np.ndarray`` must return a
    :func:`pack_wire` vector (any integer dtype; int32 keeps the
    transfer small).  If ``total`` exceeds the capacity the gather
    truncated — regrow to the next power of two and retry (rare;
    capacity is sticky with the caller).  Returns
    ``(sorted_values int64, capacity)``.
    """
    import numpy as np
    from ..resilience import check_cancel
    while True:
        # deadline yield point shared by every full-fat z2/z3 entry
        # (ISSUE 16): checked before each dispatch, including capacity
        # regrows; partial mode returns what a caller can live with —
        # nothing — rather than a truncated gather
        if check_cancel("query.scan.device"):
            return np.empty(0, dtype=np.int64), capacity
        out = np.asarray(dispatch(capacity))
        total = (int(out[0]) << _TOTAL_SPLIT) | int(out[1])
        if total <= capacity:
            packed = out[2:]
            return np.sort(packed[packed >= 0]).astype(np.int64), capacity
        capacity = gather_capacity(total)


def pad_pow2(n: int, minimum: int = 8) -> int:
    """Next power of two ≥ n — plan arrays pad to bucketed shapes so the
    jitted scan compiles once per bucket, not once per query shape."""
    return gather_capacity(n, minimum)


def pad_ranges(arrays: dict, n_pad: int) -> dict:
    """Pad per-range plan arrays to ``n_pad`` with never-matching ranges
    (zlo > zhi ⇒ searchsorted start == end ⇒ count 0)."""
    import numpy as np
    n = len(next(iter(arrays.values())))
    if n == n_pad:
        return arrays
    fill = {"rbin": -1, "rzlo": 1, "rzhi": 0, "rtlo": 1, "rthi": 0,
            "rqid": 0}
    out = {}
    for k, v in arrays.items():
        pad = np.full(n_pad - n, fill.get(k, 0), dtype=v.dtype)
        out[k] = np.concatenate([v, pad])
    return out


def pad_boxes(ixy, boxes, n_pad: int, bqid=None):
    """Pad box arrays with inverted (never-matching) boxes."""
    import numpy as np
    n = len(ixy)
    if n == n_pad:
        return (ixy, boxes) if bqid is None else (ixy, boxes, bqid)
    ixy_p = np.concatenate(
        [ixy, np.tile(np.array([[1, 1, 0, 0]], ixy.dtype), (n_pad - n, 1))])
    boxes_p = np.concatenate(
        [boxes, np.tile(np.array([[1.0, 1.0, 0.0, 0.0]], boxes.dtype),
                        (n_pad - n, 1))])
    if bqid is None:
        return ixy_p, boxes_p
    bqid_p = np.concatenate([bqid, np.full(n_pad - n, -1, bqid.dtype)])
    return ixy_p, boxes_p, bqid_p


def gather_capacity(total: int, minimum: int = 1024) -> int:
    """Static gather capacity: next power of two ≥ total.  Bounds the number
    of distinct compiled shapes for the candidate-scan kernels to log2(N)."""
    cap = minimum
    while cap < total:
        cap *= 2
    return cap


def scan_read_bytes(candidates: int, gather_bytes: int, n_ranges: int,
                    gen_slots, key_bytes: int) -> int:
    """A lower bound of the HBM bytes one batched range scan reads,
    from shapes: every candidate's gathered columns, plus the seeks —
    per generation of ``gen_slots`` sorted slots, two binary searches
    (the range's lower and upper bound) for each of the ``n_ranges``
    ranges the program carries, each probe reading one key::

        candidates x gather_bytes
          + sum over generations of n_ranges x 2 x ceil(log2 slots) x key_bytes

    Expansion, masks and writes are left out, so the true traffic is
    higher."""
    seeks = sum(max(1, int(s) - 1).bit_length() for s in gen_slots)
    return int(candidates * gather_bytes + n_ranges * 2 * seeks * key_bytes)


def sort_lex2(k1, k2, *cols):
    """Rows ordered by ``(k1, k2)``, every column of ``cols`` carried
    along: the order of ``lax.sort((k1, k2, *cols), num_keys=2)``, built
    from two single-key sorts (``k2`` with a row permutation, then a
    stable sort of ``k1`` through it) and one gather per column.

    The TPU compiler spends minutes on a two-key sort that carries
    payload operands and far less on single-key sorts: the sharded
    4M-slot full-tier append compiled for a described v5e in 721.8 s as
    one six-operand sort and in 81.0 s this way (PERF.md, PR 21).
    Rows equal in both keys keep an arbitrary order, as before."""
    iota = jnp.arange(k2.shape[0], dtype=jnp.int32)
    k2, p = jax.lax.sort((k2, iota), num_keys=1)
    k1, r = jax.lax.sort((k1[p], iota), num_keys=1, is_stable=True)
    perm = p[r]
    return (k1, k2[r], *(c[perm] for c in cols))


def searchsorted2(keys_hi, keys_lo, q_hi, q_lo, side: str = "left"):
    """Vectorized binary search over lexicographically sorted key pairs.

    Equivalent to ``np.searchsorted`` on the composite key ``(hi, lo)``
    (which for Z3 matches the reference's big-endian ``[2B bin][8B z]``
    row-key ordering, index/index/z3/Z3IndexKeySpace.scala:60): returns,
    per query, the first index at which the query could be inserted while
    keeping order ('left'), or the index past any equal run ('right').

    All comparisons are signed int64 — z values occupy ≤63 bits so signed
    order equals unsigned byte order.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    n = keys_hi.shape[0]
    q_hi = jnp.asarray(q_hi)
    q_lo = jnp.asarray(q_lo)
    if n == 0:
        return jnp.zeros(q_hi.shape, jnp.int64)
    # anchor the carry to the keys so that under shard_map the loop carry is
    # shard-varying from iteration 0 (matching the body's output type);
    # scalar (0-d) anchor preserves the queries' shape
    anchor = (keys_hi[0] * 0).astype(jnp.int64)
    lo = jnp.zeros(q_hi.shape, jnp.int64) + anchor
    hi = jnp.full(q_hi.shape, n, jnp.int64) + anchor
    nsteps = max(1, n.bit_length())

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = jnp.minimum((lo + hi) >> 1, n - 1)
        mh = keys_hi[mid]
        ml = keys_lo[mid]
        if side == "left":
            go_right = (mh < q_hi) | ((mh == q_hi) & (ml < q_lo))
        else:
            go_right = (mh < q_hi) | ((mh == q_hi) & (ml <= q_lo))
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, nsteps, body, (lo, hi))
    return lo


def expand_ranges(starts, counts, capacity: int):
    """Flatten R variable-length index ranges into one fixed-size gather.

    Given per-range start offsets and lengths (the result of searchsorted
    over the sorted key columns), produce ``capacity`` gather indices that
    enumerate ``starts[r] + 0..counts[r]-1`` for every range in order, plus
    a validity mask and the owning range id per slot.  ``capacity`` must be
    static (>= total count); surplus slots are masked out.  This is the
    fixed-shape replacement for the KV scan's variable-length result
    iteration — XLA sees one dense gather.
    """
    starts = jnp.asarray(starts, dtype=jnp.int64)
    counts = jnp.asarray(counts, dtype=jnp.int64)
    offsets = jnp.cumsum(counts)
    total = offsets[-1] if counts.shape[0] > 0 else jnp.int64(0)
    j = jnp.arange(capacity, dtype=jnp.int64)
    rid = jnp.searchsorted(offsets, j, side="right")
    rid_c = jnp.minimum(rid, counts.shape[0] - 1)
    prev = jnp.where(rid_c > 0, offsets[rid_c - 1], 0)
    idx = starts[rid_c] + (j - prev)
    valid = j < total
    return jnp.where(valid, idx, 0), valid, rid_c
