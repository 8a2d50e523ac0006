"""Quickstart for the `repro.api.Database` facade: learn a monotonic SFC
with SMBO, build the LMSFC index, run the typed query algebra (COUNT,
RANGE retrieval, POINT lookup, exact kNN), apply LMSFCb delta updates,
and compare against the fixed-z-order ZM-index.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.api import Count, Database, Knn, Point, Range
from repro.compile_cache import use_compile_cache
from repro.baselines.zm import build_zm_index
from repro.core.query import (brute_force_count, brute_force_knn,
                              brute_force_range, run_workload)
from repro.core.theta import default_K
from repro.data.synth import make_dataset
from repro.data.workload import make_workload


def main():
    use_compile_cache()
    print("== LMSFC quickstart ==")
    data = make_dataset("osm", 30_000, seed=0)
    K = default_K(2)
    Ls_tr, Us_tr = make_workload(data, 100, seed=1, K=K)
    Ls_te, Us_te = make_workload(data, 200, seed=2, K=K)

    print("Database.fit: SMBO θ-learning (random-forest surrogate) + "
          "cost-based paging + per-page sort dims + PGM forward index...")
    t0 = time.time()
    db = Database.fit(data, (Ls_tr, Us_tr), K=K,
                      smbo=dict(max_iters=4, n_init=6, evals_per_iter=3,
                                verbose=True))
    print(f"fitted in {time.time()-t0:.1f}s; SMBO cost history: "
          f"{[round(y, 2) for _, y in db.fit_result.history]}")
    print(db)

    res = db.query((Ls_te, Us_te))          # CPU engine attaches by default
    oracle = np.asarray([brute_force_count(data, l, u)
                         for l, u in zip(Ls_te, Us_te)])
    assert np.array_equal(res.counts, oracle), "exactness violated!"
    assert res.exact
    print(f"exact on {len(res)} queries ✓ (engine={res.engine})")

    zm = build_zm_index(data, K=K)
    _, zstats = run_workload(zm, Ls_te, Us_te)
    stats = res.stats
    print(f"LMSFC:    pages/query={stats.pages_accessed/200:.1f}  "
          f"false-positive points/query={stats.false_positives/200:.1f}")
    print(f"ZM-index: pages/query={zstats.pages_accessed/200:.1f}  "
          f"false-positive points/query={zstats.false_positives/200:.1f}")
    print(f"page-access reduction: "
          f"{zstats.pages_accessed/max(1, stats.pages_accessed):.2f}x")

    print("typed query algebra: RANGE retrieval + POINT + exact kNN...")
    rr = db.query(Range(Ls_te[:20], Us_te[:20]))
    np.testing.assert_array_equal(
        rr.rows_for(0), brute_force_range(data, Ls_te[0], Us_te[0]))
    print(f"Range: {int(rr.counts.sum())} rows over 20 windows, "
          f"per-query offsets, lexicographic order ✓")
    pt = db.query(Point(data[:5]))
    assert pt.found.all()
    centers = data[:4]
    nn = db.query(Knn(centers, k=5, metric="l2"))
    for i, c in enumerate(centers):
        oracle, _ = brute_force_knn(data, c, 5, "l2")
        np.testing.assert_array_equal(nn.neighbors_for(i), oracle)
    print(f"Point: 5/5 found ✓   Knn: k=5 matches the brute-force oracle "
          f"on {len(centers)} centers ✓")

    print("execution layer: explain() + Session micro-batching...")
    print(db.explain(Count(Ls_te[:8], Us_te[:8])))
    with db.session() as s:                      # 3 clients, one tick
        t1 = s.submit(Count(Ls_te[:8], Us_te[:8]), client="alice")
        t2 = s.submit(Knn(centers, k=5), client="bob")
        t3 = s.submit(Count(Ls_te[8:16], Us_te[8:16]), client="carol")
    serial = db.query(Count(Ls_te[:16], Us_te[:16]))
    np.testing.assert_array_equal(
        np.concatenate([t1.result().counts, t3.result().counts]),
        serial.counts)
    np.testing.assert_array_equal(t2.result().neighbors, nn.neighbors)
    print(f"session: 3 clients coalesced into {s.batches_run} batches, "
          f"results == serial ✓")

    print("LMSFCb updates: insert 100 rows, tombstone one...")
    rng = np.random.default_rng(7)
    new = np.unique(rng.integers(0, 2**K, size=(100, 2), dtype=np.uint64),
                    axis=0)
    db.insert(new)
    db.delete(data[0])
    res2 = db.query((Ls_te, Us_te))
    assert res2.exact
    print(f"post-update queries still exact ✓ (epoch={res2.epoch}, "
          f"live rows={db.n})")
    assert not db.query(Point(data[0])).found[0]   # tombstoned ⇒ gone
    print("tombstoned row is point-lookup invisible ✓")


if __name__ == "__main__":
    main()
