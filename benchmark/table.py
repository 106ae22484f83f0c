"""The seeded data of one run, as the plain reference sees it.

Rows are in time order (every generator emits them so), and a row's
position in the table is its position in the store: the store numbers
lean rows in append order, and the harness writes the table in order.
String columns are kept as codes into a vocabulary; the harness builds
the object arrays the store is written with slice by slice.  A string
column with no vocabulary (``None``) holds distinct ids: its value is the
decimal form of its int64 code.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

DAY_MS = 86_400_000
MINUTE_MS = 60_000


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of one seed (any integer seed)."""
    return np.random.default_rng([seed & (2**64 - 1), stream])


def zipf_weights(n: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s


def categorical(rng: np.random.Generator, weights, size: int) -> np.ndarray:
    """``size`` draws of indices in proportion to ``weights``, by Walker's
    alias method: two uniform draws a sample, where a search of the
    cumulative weights costs log2(len) steps."""
    w = np.asarray(weights, np.float64)
    n = len(w)
    prob = w * (n / w.sum())
    alias = np.arange(n)
    small = [i for i in range(n) if prob[i] < 1.0]
    large = [i for i in range(n) if prob[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        alias[s] = g
        prob[g] -= 1.0 - prob[s]
        (small if prob[g] < 1.0 else large).append(g)
    for i in small + large:
        prob[i] = 1.0
    i = rng.integers(0, n, size)
    return np.where(rng.random(size) < prob[i], i, alias[i]).astype(np.int32)


@dataclass
class Table:
    x: np.ndarray
    y: np.ndarray
    t: np.ndarray
    #: name -> (integer codes, vocabulary or None for decimal ids)
    strings: dict
    #: name -> numeric column (float64 or int32)
    numbers: dict
    #: name -> (x, y, weight) points that requests are centred on
    anchors: dict = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.t)

    def time_range(self, lo_ms: int, hi_ms: int) -> tuple[int, int]:
        """Rows ``[a, b)`` whose ``t`` lies in ``[lo_ms, hi_ms]``."""
        return (int(np.searchsorted(self.t, lo_ms, "left")),
                int(np.searchsorted(self.t, hi_ms, "right")))

    def write_columns(self, spec: "Spec", lo: int, hi: int) -> dict:
        """Rows ``[lo, hi)`` as ``TpuDataStore.write`` takes them."""
        out = {}
        for name, typ in spec.attrs:
            if typ == "Point":
                out[name] = (self.x[lo:hi], self.y[lo:hi])
            elif typ == "Date":
                out[name] = self.t[lo:hi]
            elif typ == "String":
                codes, vocab = self.strings[name]
                out[name] = (codes[lo:hi].astype(str).astype(object)
                             if vocab is None else
                             np.asarray(vocab, dtype=object)[codes[lo:hi]])
            else:
                out[name] = self.numbers[name][lo:hi]
        return out


class Spec:
    """The attributes of a GeoMesa spec string, in order."""

    def __init__(self, spec: str):
        self.attrs: list[tuple[str, str]] = []
        for part in spec.split(";")[0].split(","):
            name, typ = part.split(":")[:2]
            self.attrs.append((name.lstrip("*"), typ))
        self.geom = next(n for n, t in self.attrs if t == "Point")
        self.dtg = next(n for n, t in self.attrs if t == "Date")
