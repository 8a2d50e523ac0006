"""Multi-shard serving driver: one logical dataset behind a `Router`.

Partitions the rows across N shard Databases (the `repro.dist` sharding
rules decide the split), attaches a device engine on every shard, and
scatters a mixed workload (Count / Range / Point / Knn) through the
Router — then checks every merged answer against one unsharded oracle
Database, bit for bit (Count sums, Range lex-stitches, Knn re-ranks on
exact integer distances).

    PYTHONPATH=src python examples/serve_router.py [--shards 4]
"""
import argparse
import time

import numpy as np

from repro.api import (Count, Database, EngineConfig, Knn, Point, Range,
                       Router)
from repro.compile_cache import use_compile_cache
from repro.core.index import IndexConfig
from repro.core.theta import default_K
from repro.data.synth import make_dataset
from repro.data.workload import make_workload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--n-q", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    data = make_dataset("osm", args.n, seed=0)
    K = default_K(2)
    Ls, Us = make_workload(data, args.n_q, seed=1, K=K)
    cfg = IndexConfig(paging="heuristic", page_bytes=2048)

    t0 = time.time()
    router = Router.build(data, args.shards, K=K, learn=False, cfg=cfg)
    router.engine("xla", EngineConfig(q_chunk=8, max_cand=64, max_hits=512))
    print(f"built {router} in {time.time()-t0:.1f}s "
          f"(~{router.n // args.shards} rows/shard)")
    print(router.explain(Count(Ls[:4], Us[:4])))

    oracle = Database.fit(data, K=K, learn=False, cfg=cfg)

    centers = data[::max(1, len(data) // 8)][:8]
    workload = [Count(Ls, Us), Range(Ls[:8], Us[:8]),
                Point(data[::max(1, len(data) // 16)]),
                Knn(centers, k=5)]
    for q in workload:
        t0 = time.perf_counter()
        res = router.query(q)
        dt = time.perf_counter() - t0
        want = oracle.query(q)
        for f in ("counts", "rows", "offsets", "found", "neighbors",
                  "dists"):
            if hasattr(want, f):
                np.testing.assert_array_equal(getattr(res, f),
                                              getattr(want, f))
        print(f"{q.kind:5s}: merged from {args.shards} shards in "
              f"{dt*1e3:7.1f} ms == unsharded oracle ✓ ({res.engine})")

    # updates route through the router too: scatter inserts, broadcast
    # tombstones; queries stay exact across the shard set
    new = np.unique(np.random.default_rng(7).integers(
        0, 2**K, size=(64, 2), dtype=np.uint64), axis=0)
    router.insert(new)
    oracle.insert(new)
    router.delete(new[0])
    oracle.delete(new[0])
    np.testing.assert_array_equal(router.query(Count(Ls, Us)).counts,
                                  oracle.query(Count(Ls, Us)).counts)
    print(f"post-update parity after {len(new)} scattered inserts + 1 "
          f"broadcast delete ✓ (n={router.n})")


if __name__ == "__main__":
    main()
