"""The plain reference agrees with the program's CPU engine (the
paper's per-query page walk) on all four kinds."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def setup():
    sys.path.insert(0, os.path.join(tiny.ROOT, "src"))
    from datagen import make_nyc, make_osm
    from reference import Reference
    from repro.api import Database
    out = {}
    for name, rows, K in (("osm", make_osm(6000, 3, 7), 32),
                          ("nyc", make_nyc(6000, 4), 21)):
        out[name] = (Database.fit(rows, learn=False), Reference(rows, K),
                     rows, K)
    return out


def _windows(rows, K, n, width, seed):
    rng = np.random.default_rng(seed)
    c = rows[rng.integers(0, len(rows), n)].astype(np.float64)
    w = rng.uniform(0, width * (2**K - 1), size=c.shape)
    lo = np.clip(c - w / 2, 0, 2**K - 1).astype(np.uint64)
    hi = np.clip(c + w / 2, 0, 2**K - 1).astype(np.uint64)
    return lo, hi


@pytest.mark.parametrize("data", ["osm", "nyc"])
@pytest.mark.parametrize("kind", ["count", "range", "point", "knn"])
def test_reference_matches_cpu_engine(setup, data, kind):
    from repro.api import Count, Knn, Point, Range
    db, ref, rows, K = setup[data]
    lo, hi = _windows(rows, K, 24, 0.05, 5)
    if kind == "count":
        got = db.query(Count(lo, hi), engine="cpu").counts
        assert [int(v) for v in got] == [ref.count(a, b)
                                         for a, b in zip(lo, hi)]
        assert int(np.sum(got)) > 0
    elif kind == "range":
        res = db.query(Range(lo, hi), engine="cpu")
        for i, (a, b) in enumerate(zip(lo, hi)):
            np.testing.assert_array_equal(res.rows_for(i), ref.range(a, b))
    elif kind == "point":
        xs = rows[::250].copy()
        xs[::2, 0] ^= np.uint64(1)
        got = db.query(Point(xs), engine="cpu").found
        np.testing.assert_array_equal(got, ref.point(xs))
        assert got.any() and not got.all()
    else:
        centers = np.concatenate([rows[::700], lo[:4]])
        res = db.query(Knn(centers, k=7, metric="l2"), engine="cpu")
        for i, c in enumerate(centers):
            want, dists = ref.knn(c, 7, "l2")
            np.testing.assert_array_equal(res.neighbors_for(i), want)
            np.testing.assert_array_equal(res.dists_for(i), dists)
