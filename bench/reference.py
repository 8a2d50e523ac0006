"""The plain reference: exact answers from the cell's raw rows.

Independent of the program under test: no curve, no paging, no engine,
no executor.  Rows are held as one sorted array of packed uint64 keys
(lexicographic order, dimension 0 first), so a window is a slab of
dimension 0 found by binary search and then filtered row by row.

Semantics are those the configuration states: windows are closed
(``lo <= x <= hi`` in every dimension); Range rows come back in
lexicographic order; Point is exact membership; kNN returns the k
nearest rows by exact integer distance (squared Euclidean for ``l2``,
Chebyshev for ``linf``), ties broken by the row's lexicographic order.
"""
from __future__ import annotations

import numpy as np

from datagen import pack_keys, unpack_keys


class Reference:
    """Exact answers over a fixed set of rows."""

    def __init__(self, rows: np.ndarray, K: int):
        rows = np.asarray(rows, dtype=np.uint64)
        self.d = rows.shape[1]
        self.K = K
        self.keys = np.unique(pack_keys(rows, K))
        self.rows = unpack_keys(self.keys, self.d, K)
        self.n = len(self.keys)

    # -- windows -------------------------------------------------------
    def _slab(self, lo, hi):
        """Rows whose dimension 0 lies in [lo[0], hi[0]]."""
        top = np.uint64((1 << (self.K * (self.d - 1))) - 1) \
            if self.d > 1 else np.uint64(0)
        shift = np.uint64(self.K * (self.d - 1))
        a = np.searchsorted(self.keys, np.uint64(lo[0]) << shift, "left")
        b = np.searchsorted(self.keys, (np.uint64(hi[0]) << shift) | top,
                            "right")
        return self.rows[a:b]

    def range(self, lo, hi) -> np.ndarray:
        """Rows inside the closed window, in lexicographic order."""
        lo = np.asarray(lo, dtype=np.uint64)
        hi = np.asarray(hi, dtype=np.uint64)
        sub = self._slab(lo, hi)
        keep = np.all((sub >= lo) & (sub <= hi), axis=1)
        return sub[keep]

    def count(self, lo, hi) -> int:
        return int(len(self.range(lo, hi)))

    # -- points --------------------------------------------------------
    def point(self, xs) -> np.ndarray:
        """(Q,) bool: each row present."""
        k = pack_keys(np.atleast_2d(np.asarray(xs, dtype=np.uint64)), self.K)
        i = np.minimum(np.searchsorted(self.keys, k), self.n - 1)
        return self.keys[i] == k

    # -- nearest neighbours --------------------------------------------
    def _dist(self, rows, c, metric):
        """Exact integer distances as Python ints (no overflow)."""
        diff = np.abs(rows.astype(np.int64) - np.asarray(c).astype(np.int64))
        if metric == "linf":
            return [int(v) for v in diff.max(axis=1)]
        obj = diff.astype(object)
        return [int(v) for v in (obj * obj).sum(axis=1)]

    def _box(self, c, h):
        top = (1 << self.K) - 1
        c = np.asarray(c, dtype=np.int64)
        lo = np.clip(c - h, 0, top).astype(np.uint64)
        hi = np.clip(c + h, 0, top).astype(np.uint64)
        return self.range(lo, hi)

    def knn(self, c, k: int, metric: str = "l2"):
        """(rows (k, d) uint64, dists (k,) float64) of the k nearest rows."""
        c = np.asarray(c, dtype=np.uint64)
        kk = min(int(k), self.n)
        h = 1 << max(0, self.K - 12)
        while True:
            cand = self._box(c, h)
            if len(cand) >= kk or h >= (1 << self.K):
                break
            h *= 4
        # the kk-th distance inside the box bounds the true kk-th: every
        # true neighbour lies within that radius in each dimension
        dist = sorted(self._dist(cand, c, metric))
        bound = dist[kk - 1]
        r = bound if metric == "linf" else _isqrt_ceil(bound)
        cand = self._box(c, r)
        dist = self._dist(cand, c, metric)
        order = sorted(range(len(cand)),
                       key=lambda i: (dist[i], tuple(int(v) for v in cand[i])))
        sel = order[:kk]
        return (cand[sel].reshape(-1, self.d),
                np.array([float(dist[i]) for i in sel], dtype=np.float64))


def _isqrt_ceil(v: int) -> int:
    import math
    r = math.isqrt(v)
    return r if r * r == v else r + 1
