"""Order-free digests of a query answer: row ids and every payload value,
bit for bit.  The HTTP clients digest what they decoded, the reference
digests what it computed, and the two must be equal.  Imports no JAX:
the load generators run it.
"""

from __future__ import annotations

import numpy as np

_K1 = np.uint64(0x9E3779B97F4A7C15)
_K2 = np.uint64(0xBF58476D1CE4E5B9)
_K3 = np.uint64(0x94D049BB133111EB)


def _mix(h: np.ndarray) -> np.ndarray:
    h = (h ^ (h >> np.uint64(30))) * _K2
    h = (h ^ (h >> np.uint64(27))) * _K3
    return h ^ (h >> np.uint64(31))


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    if a.dtype == np.float64 or a.dtype == np.int64:
        return a.view(np.uint64)
    return a.astype(np.int64).view(np.uint64)


def digest(ids: np.ndarray, cols: list) -> list:
    """``[rows, sum, xor]`` of per-row hashes over ids and columns."""
    with np.errstate(over="ignore"):
        h = _mix(_bits(np.asarray(ids, np.int64)) + _K1)
        for c in cols:
            h = _mix(h ^ _bits(c))
    if not len(h):
        return [0, 0, 0]
    return [int(len(h)), int(h.sum(dtype=np.uint64)),
            int(np.bitwise_xor.reduce(h))]


def column_digests(ids: np.ndarray, cols: list) -> list:
    """One digest of the ids alone and one per column (each row's value
    bound to its id), so a mismatch names the column."""
    return [digest(ids, [])] + [digest(ids, [c]) for c in cols]


def column_names(lay: list) -> list:
    """Names of ``column_digests``' entries."""
    out = ["ids"]
    for name, typ, _ in lay:
        out += [name + "_x", name + "_y"] if typ == "Point" else [name]
    return out


def layout(spec_attrs: list, vocabs: dict) -> list:
    """``[(name, type, vocabulary or None)]`` in spec order."""
    return [(n, t, vocabs.get(n)) for n, t in spec_attrs]


def decimal_codes(values) -> np.ndarray:
    """The int64 codes of a string id column (``Table``: no vocabulary)."""
    return np.asarray(values).astype(str).astype(np.int64)


def arrow_columns(table, lay: list) -> tuple[np.ndarray, list]:
    """(row ids, payload columns) of a decoded Arrow answer.  Uses no
    pyarrow compute kernel: those crashed when many client threads
    called them at once."""
    def chunks(name):
        return table.column(name).chunks

    def cat(parts, dtype):
        return (np.concatenate(parts) if parts
                else np.empty(0, dtype)).astype(dtype, copy=False)

    ids = cat([c.to_numpy(zero_copy_only=False).astype(np.int64)
               for c in chunks("__fid__")], np.int64)
    cols = []
    for name, typ, vocab in lay:
        if typ == "Point":
            xy = cat([c.values.to_numpy() for c in chunks(name)],
                     np.float64).reshape(-1, 2)
            cols += [xy[:, 0], xy[:, 1]]
        elif typ == "String" and vocab is None:
            cols.append(cat([decimal_codes(c.to_numpy(zero_copy_only=False))
                             for c in chunks(name)], np.int64))
        elif typ == "String":
            index = {v: i for i, v in enumerate(vocab)}
            parts = []
            for c in chunks(name):
                if hasattr(c, "indices"):
                    codes = np.asarray([index.get(v, -1) for v in
                                        c.dictionary.to_pylist()], np.int64)
                    parts.append(codes[c.indices.to_numpy(
                        zero_copy_only=False).astype(np.int64)])
                else:
                    vals = c.to_numpy(zero_copy_only=False)
                    uniq, inv = np.unique(vals.astype(str),
                                          return_inverse=True)
                    codes = np.asarray([index.get(v, -1) for v in uniq],
                                       np.int64)
                    parts.append(codes[inv])
            cols.append(cat(parts, np.int64))
        elif typ == "Date":
            cols.append(cat([c.to_numpy(zero_copy_only=False).view(np.int64)
                             for c in chunks(name)], np.int64))
        else:
            cols.append(cat([c.to_numpy(zero_copy_only=False)
                             for c in chunks(name)], np.float64
                            if typ == "Double" else np.int32))
    return ids, cols
