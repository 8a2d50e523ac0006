"""Out-of-core scale benchmark + exactness gate for `repro.store`
(CI ``scale-smoke``).

Three measurements, written to BENCH_scale.json:

  1. **Build** — a 10M+-row external-sort segment build streamed from
     `iter_chunks` in a *child subprocess*, with peak RSS measured as the
     ``ru_maxrss`` delta over the child's post-import baseline.
     Hard-asserted: the delta stays under a bound derived from the chunk
     size + merge window + allocator slack — far below the dataset size,
     which is the whole point of the external sort.
  2. **Serve** — the segment reopened (`Database.from_segment`) and the
     `store` engine driven through Count / Range / Point / Knn batches;
     sustained q/s per kind plus the page-group cache's hit/miss/
     eviction/bypass accounting (hard-asserted: hits + misses == lookups
     and resident bytes never exceed the budget).
  3. **Exactness** — a subsampled segment served by the store engine is
     bit-compared against an in-memory `Database.fit` oracle with
     *different* page boundaries, on every query kind.  Hard-asserted
     before anything is reported.

The report carries the common benchmark envelope from the start.

    PYTHONPATH=src python benchmarks/bench_scale.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

SLACK_MB = 96          # allocator / interpreter growth allowance
Q_PER_KIND = 128       # timed batch size per query kind
KNN_CENTERS = 16
KNN_K = 8


def rss_kb() -> int:
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)


# ---------------------------------------------------------------------------
# child: build the segment, report the RSS envelope on stdout
# ---------------------------------------------------------------------------


def child_build(n: int, chunk: int, d: int, path: str, page_rows: int) -> None:
    """Runs in a fresh interpreter so ru_maxrss isolates the build."""
    from repro.core.curve import default_curve
    from repro.core.theta import default_K
    from repro.data.synth import iter_chunks
    from repro.store import build_segment

    default_curve(d, default_K(d))     # settle import-time allocations
    baseline_kb = rss_kb()
    t0 = time.time()
    build_segment(iter_chunks(n, chunk, seed=0, d=d), path,
                  page_rows=page_rows,
                  build_info={"source": "iter_chunks", "n": n,
                              "chunk": chunk, "seed": 0})
    build_s = time.time() - t0
    print(json.dumps({"baseline_kb": baseline_kb, "peak_kb": rss_kb(),
                      "build_s": build_s}))


def run_build(n: int, chunk: int, d: int, path: str, page_rows: int) -> dict:
    # the build is host-only: the child must not claim an accelerator
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        p for p in (src, os.environ.get("PYTHONPATH")) if p))
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--n", str(n), "--chunk", str(chunk), "--d", str(d),
         "--path", path, "--page-rows", str(page_rows)],
        capture_output=True, text=True, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"build child failed:\n{out.stderr[-4000:]}")
    rep = json.loads(out.stdout.strip().splitlines()[-1])

    chunk_mb = chunk * d * 8 / 1e6
    bound_mb = 4 * chunk_mb + SLACK_MB          # ~2 resident chunk copies +
    delta_mb = (rep["peak_kb"] - rep["baseline_kb"]) / 1e3  # sort scratch
    dataset_mb = n * d * 8 / 1e6
    assert delta_mb <= bound_mb, (
        f"build peak RSS delta {delta_mb:.0f} MB exceeds the "
        f"{bound_mb:.0f} MB out-of-core bound (chunk={chunk_mb:.0f} MB)")
    return {
        "seconds": round(rep["build_s"], 2),
        "rows_per_s": round(n / rep["build_s"]),
        "rss_baseline_mb": round(rep["baseline_kb"] / 1e3, 1),
        "rss_peak_mb": round(rep["peak_kb"] / 1e3, 1),
        "rss_delta_mb": round(delta_mb, 1),
        "rss_bound_mb": round(bound_mb, 1),
        "rss_bounded": True,
        "dataset_mb": round(dataset_mb, 1),
    }


# ---------------------------------------------------------------------------
# serve: q/s per kind + cache accounting on the full segment
# ---------------------------------------------------------------------------


def _time_qps(fn, n_queries: int, reps: int = 3) -> float:
    fn()                                        # warm (trace + cache fill)
    t0 = time.time()
    for _ in range(reps):
        fn()
    return round(n_queries * reps / (time.time() - t0), 1)


def run_serve(path: str, d: int, group_pages: int, cache_bytes: int) -> dict:
    from repro.api import Count, Database, EngineConfig, Knn, Point, Range
    from repro.core.theta import default_K
    from repro.data.workload import make_workload

    db = Database.from_segment(path, verify="meta")
    db.engine("store", EngineConfig(group_pages=group_pages,
                                    cache_bytes=cache_bytes))
    seg = db.segment
    sample = np.asarray(seg.xs[:: max(1, seg.n // 4096)], dtype=np.uint64)
    Ls, Us = make_workload(sample, Q_PER_KIND, seed=1, K=default_K(d))
    pts = sample[:Q_PER_KIND]
    centers = sample[1::257][:KNN_CENTERS]

    qps = {
        "count_qps": _time_qps(lambda: db.query(Count(Ls, Us)), Q_PER_KIND),
        "range_qps": _time_qps(lambda: db.query(Range(Ls, Us)), Q_PER_KIND),
        "point_qps": _time_qps(lambda: db.query(Point(pts)), Q_PER_KIND),
        "knn_qps": _time_qps(
            lambda: db.query(Knn(centers, k=KNN_K, metric="l2")),
            KNN_CENTERS),
    }
    eng = db.engines["store"]
    st = eng.cache.stats
    cache = {
        "group_pages": group_pages,
        "budget_bytes": cache_bytes,
        "block_bytes": seg.group_nbytes(group_pages),
        "hits": st.hits, "misses": st.misses, "evictions": st.evictions,
        "bypass": st.bypass, "lookups": st.lookups,
        "resident_bytes": eng.cache.resident_bytes,
        "resident_groups": eng.cache.resident_groups,
    }
    assert st.hits + st.misses == st.lookups, "cache accounting leak"
    assert eng.cache.resident_bytes <= cache_bytes, "cache over budget"
    cache["accounting_ok"] = True
    return {**qps, "queries_per_kind": Q_PER_KIND,
            "segment_rows": seg.n, "segment_pages": seg.num_pages,
            "segment_bytes": seg.data_bytes(), "cache": cache}


# ---------------------------------------------------------------------------
# exactness: store engine vs in-memory oracle on a subsampled segment
# ---------------------------------------------------------------------------


def run_exactness(path: str, d: int, stride: int, tmp: str) -> dict:
    from repro.api import Count, Database, EngineConfig, Knn, Point, Range
    from repro.core.index import IndexConfig
    from repro.core.theta import default_K
    from repro.data.workload import make_workload
    from repro.store import build_segment, open_segment

    big = open_segment(path, verify="none")
    sub = np.asarray(big.xs[::stride], dtype=np.uint64)
    sub_path = os.path.join(tmp, "sub_seg")
    build_segment(iter([sub]), sub_path, page_rows=128)
    sdb = Database.from_segment(sub_path, verify="full")
    sdb.engine("store", EngineConfig(q_chunk=8, group_pages=16,
                                     cache_bytes=1 << 22))
    # the oracle pages differently on purpose: parity despite disagreeing
    # page boundaries is what proves exactness-by-construction
    odb = Database.fit(sub, K=default_K(d), learn=False,
                       cfg=IndexConfig(paging="heuristic", page_bytes=4096))

    Ls, Us = make_workload(sub, 32, seed=2, K=default_K(d))
    pts = np.concatenate([sub[::701], (sub[:8] | np.uint64(1))
                          + np.uint64(2)])
    centers = sub[5::997][:8]
    checked = 0
    for q in (Count(Ls, Us), Range(Ls, Us), Point(pts),
              Knn(centers, k=5, metric="l2"),
              Knn(centers, k=5, metric="linf")):
        want = odb.query(q, engine="cpu")
        got = sdb.query(q, engine="store")
        for attr in ("counts", "rows", "offsets", "found", "neighbors",
                     "dists"):
            a, b = getattr(want, attr, None), getattr(got, attr, None)
            if a is not None:
                np.testing.assert_array_equal(
                    np.asarray(b), np.asarray(a),
                    err_msg=f"{type(q).__name__}.{attr}")
                checked += 1
    return {"bit_identical": True, "rows": int(len(sub)),
            "kinds_checked": ["count", "range", "point", "knn_l2",
                              "knn_linf"],
            "arrays_checked": checked}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run(smoke: bool = None, out: str = "BENCH_scale.json") -> dict:
    if smoke is None:
        smoke = os.environ.get("REPRO_BENCH_SMOKE", "") == "1"
    d = 3
    if smoke:
        n, chunk, page_rows, stride = 200_000, 50_000, 128, 10
        group_pages, cache_bytes = 32, 16 << 20
    else:
        n, chunk, page_rows, stride = 10_000_000, 500_000, 256, 50
        group_pages, cache_bytes = 64, 64 << 20

    from repro.obs import bench_envelope
    tmp = tempfile.mkdtemp(prefix="bench_scale_")
    try:
        seg_path = os.path.join(tmp, "seg")
        print(f"### building {n:,} rows (chunk={chunk:,}) out of core ...")
        build = run_build(n, chunk, d, seg_path, page_rows)
        print(f"### build {build['seconds']}s, peak RSS delta "
              f"{build['rss_delta_mb']} MB (bound {build['rss_bound_mb']} "
              f"MB, dataset {build['dataset_mb']} MB)")
        serve = run_serve(seg_path, d, group_pages, cache_bytes)
        print(f"### serve: count {serve['count_qps']} q/s, range "
              f"{serve['range_qps']} q/s, point {serve['point_qps']} q/s, "
              f"knn {serve['knn_qps']} q/s")
        exact = run_exactness(seg_path, d, stride, tmp)
        print(f"### exactness: {exact['arrays_checked']} result arrays "
              f"bit-identical over {exact['rows']:,} subsampled rows")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    doc = {**bench_envelope(),
           "config": {"n": n, "d": d, "chunk": chunk, "page_rows": page_rows,
                      "smoke": bool(smoke)},
           "build": build, "serve": serve, "exactness": exact}
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"### wrote {out}")
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_scale.json")
    # child-process build protocol (internal)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--chunk", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--d", type=int, help=argparse.SUPPRESS)
    ap.add_argument("--path", help=argparse.SUPPRESS)
    ap.add_argument("--page-rows", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        child_build(args.n, args.chunk, args.d, args.path, args.page_rows)
        return
    run(smoke=args.smoke, out=args.out)


if __name__ == "__main__":
    main()
