"""The benchmark's own data and training-workload generators.

Copies of the program's surrogates for the paper's datasets (LMSFC,
arXiv 2304.12635, Sec. 7.1), kept here so the yardstick cannot move
with the program.  Two departures from the originals, both for a
steadier benchmark, neither changing the shape of the data:

* `make_osm` draws the cluster layout (centres, weights, spreads) from
  a fixed ``structure_seed`` and only the rows from ``seed``, so every
  seed gives the same map with fresh points, and the work a query
  finds does not swing with the seed.
* Deduplication packs each row into one uint64 key (``d * K <= 64``)
  before `np.unique`, which gives the same rows in the same
  lexicographic order as ``np.unique(axis=0)``, much faster.
"""
from __future__ import annotations

import numpy as np


def default_K(d: int) -> int:
    """Bits per dimension: 64-bit addresses, at most 32 bits a dimension."""
    return min(32, 64 // d)


def pack_keys(rows: np.ndarray, K: int) -> np.ndarray:
    """(n, d) uint64 rows of K-bit coordinates -> (n,) uint64 keys whose
    order is the rows' lexicographic order (dimension 0 first)."""
    rows = np.asarray(rows, dtype=np.uint64)
    d = rows.shape[1]
    if d * K > 64:
        raise ValueError(f"d={d} x K={K} bits do not fit one uint64 key")
    key = np.zeros(len(rows), dtype=np.uint64)
    for i in range(d):
        key = (key << np.uint64(K)) | rows[:, i]
    return key


def unpack_keys(keys: np.ndarray, d: int, K: int) -> np.ndarray:
    """Inverse of `pack_keys`."""
    keys = np.asarray(keys, dtype=np.uint64)
    mask = np.uint64((1 << K) - 1)
    out = np.empty((len(keys), d), dtype=np.uint64)
    for i in range(d):
        out[:, d - 1 - i] = (keys >> np.uint64(i * K)) & mask
    return out


def to_int_grid(x: np.ndarray, K: int) -> np.ndarray:
    """Scale each column to [0, 2^K - 1] integers and drop duplicate
    rows (the paper's preprocessing); rows come out lexicographically
    sorted."""
    lo = x.min(axis=0)
    hi = x.max(axis=0)
    span = np.where(hi > lo, hi - lo, 1.0)
    scaled = (x - lo) / span * (2.0**K - 1.0)
    ints = np.minimum(np.floor(scaled), 2.0**K - 1.0).astype(np.uint64)
    return unpack_keys(np.unique(pack_keys(ints, K)), x.shape[1], K)


def make_osm(n: int, seed: int, structure_seed: int) -> np.ndarray:
    """2-D, heavy spatial clustering: 64 city-like Gaussian clusters with
    Pareto weights over a continent-scale box, 10% uniform rural noise."""
    srng = np.random.default_rng(structure_seed)
    n_clusters = 64
    centers = srng.uniform(0, 1, size=(n_clusters, 2))
    weights = srng.pareto(1.2, n_clusters) + 0.05
    weights /= weights.sum()
    sigmas = srng.uniform(0.002, 0.03, size=n_clusters)
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(int(n * 0.9), weights)
    pts = [centers[c] + rng.normal(0, sigmas[c], size=(s, 2))
           for c, s in enumerate(sizes)]
    pts.append(rng.uniform(0, 1, size=(n - int(sizes.sum()), 2)))
    x = np.clip(np.concatenate(pts), 0, 1)
    return to_int_grid(x, default_K(2))


def make_nyc(n: int, seed: int, structure_seed: int = 0) -> np.ndarray:
    """3-D (pickup location projected to 1-D, trip distance, total
    amount): correlated, heavy-tailed marginals.  The layout is fixed by
    constants, so `structure_seed` is unused."""
    rng = np.random.default_rng(seed)
    loc = np.concatenate([
        rng.normal(0.4, 0.05, size=int(n * 0.6)),
        rng.normal(0.7, 0.08, size=int(n * 0.3)),
        rng.uniform(0, 1, size=n - int(n * 0.6) - int(n * 0.3)),
    ])
    dist = rng.gamma(2.0, 1.5, size=n)
    fare = 2.5 + 2.6 * dist + rng.gamma(2.0, 2.0, size=n)
    x = np.stack([np.clip(loc, 0, 1), dist, fare], axis=1)
    return to_int_grid(x, default_K(3))


GENERATORS = {"osm": make_osm, "nyc": make_nyc}


def make_data(cfg: dict, seed: int) -> np.ndarray:
    """Rows of a configuration (``generator``, ``rows``,
    ``structure_seed``), from `seed`."""
    gen = GENERATORS[cfg["generator"]]
    return gen(int(cfg["rows"]), seed, int(cfg["structure_seed"]))


def make_workload(data: np.ndarray, n_queries: int, seed: int,
                  width_scale: float, skew_frac: float, K: int):
    """Sec. 7.1 window workload: `skew_frac` of the centres are data rows,
    the rest uniform over the domain; widths per dimension uniform in
    (0, width_scale * domain]; windows clipped to the domain.  Returns
    (Ls, Us) uint64 arrays of shape (n_queries, d)."""
    rng = np.random.default_rng(seed)
    d = data.shape[1]
    domain = 2**K - 1
    n_skew = int(round(n_queries * skew_frac))
    centers = np.empty((n_queries, d), dtype=np.float64)
    idx = rng.integers(0, len(data), size=n_skew)
    centers[:n_skew] = data[idx].astype(np.float64)
    centers[n_skew:] = rng.uniform(0, domain, size=(n_queries - n_skew, d))
    widths = rng.uniform(0, width_scale * domain, size=(n_queries, d))
    lo = np.clip(centers - widths / 2, 0, domain)
    hi = np.clip(centers + widths / 2, 0, domain)
    return lo.astype(np.uint64), hi.astype(np.uint64)
