"""End-to-end serving driver (the paper's kind of system), on the
`repro.api.Database` facade: fit an LMSFC index (SMBO θ + build), attach
the "distributed" engine (pages range-sharded over a device mesh,
psum-reduced counts), and serve batched window-query requests — exact by
construction, overflow-escalated automatically.

    PYTHONPATH=src python examples/serve_distributed.py [--devices 8]
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--n", type=int, default=40_000)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--qbatch", type=int, default=64)
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices} "
        + os.environ.get("XLA_FLAGS", ""))
    import time

    import numpy as np

    from repro.api import Database, EngineConfig
    from repro.compile_cache import use_compile_cache
    from repro.core.index import IndexConfig
    from repro.core.query import brute_force_count
    from repro.core.theta import default_K
    from repro.data.synth import make_dataset
    from repro.data.workload import make_workload
    from repro.dist.compat import make_mesh

    use_compile_cache()
    data = make_dataset("osm", args.n, seed=0)
    K = default_K(2)
    Ls_tr, Us_tr = make_workload(data, 80, seed=1, K=K)
    db = Database.fit(data, (Ls_tr, Us_tr), K=K,
                      cfg=IndexConfig(paging="heuristic"),
                      smbo=dict(max_iters=3, n_init=5, evals_per_iter=2))

    d, m = (args.devices // 2, 2) if args.devices > 1 else (1, 1)
    mesh = make_mesh((d, m), ("data", "model"))
    db.engine("distributed", EngineConfig(mesh=mesh, max_cand=256,
                                          q_chunk=16))
    print(f"serving on {args.devices} devices, {db.num_pages} pages "
          f"(~{db.num_pages // args.devices}/device)")

    total_q = 0
    total_t = 0.0
    for b in range(args.batches):
        Ls, Us = make_workload(data, args.qbatch, seed=100 + b, K=K)
        t0 = time.perf_counter()
        res = db.query((Ls, Us))
        dt = time.perf_counter() - t0
        if b == 0:  # verify exactness on the first batch (compile excluded)
            want = np.asarray([brute_force_count(data, l, u)
                               for l, u in zip(Ls, Us)])
            assert np.array_equal(res.counts, want) and res.exact
            print("exactness check on first batch ✓")
            continue
        total_q += args.qbatch
        total_t += dt
        print(f"batch {b}: {args.qbatch} queries in {dt*1e3:.1f} ms "
              f"({args.qbatch/dt:.0f} q/s, escalations={res.escalations})")
    print(f"steady-state throughput: {total_q/total_t:.0f} queries/s")


if __name__ == "__main__":
    main()
