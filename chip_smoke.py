"""Drive the index's main path once on one TPU chip, through the entry
points a user calls, and check every answer against the CPU engine.

    python3 chip_smoke.py [--rows N] [--seed S] [--out DIR]
    python3 chip_smoke.py --chips 4      # the distributed engine only

Phases, one result line each (``phase <name> {...}``):

  fit     ``Database.fit`` on ``make_osm(N)`` (d=2) with a 200-query
          training workload; SMBO's pooled JAX evaluator runs on the chip.
  memory  the ``pallas`` engine serves Count, Range, Point and Knn (and
          the ``xla`` engine Count), each compared bit for bit with the
          ``cpu`` engine.
  serve   ``Database.serve``: client threads submit mixed queries; every
          ticket resolves and matches a serial replay.
  store   an out-of-core ``build_segment`` of N rows (d=3), served by the
          ``store`` engine through a page-group cache smaller than the
          segment, compared with the ``cpu`` engine on all four kinds.

With ``--chips 4`` only the ``distributed`` engine runs (pages sharded
over a 4-device mesh), on Count and Point against the ``cpu`` engine.

Every device result must come from the device engine asked for: not
routed to the CPU, no CPU exactness net, no serving retry.  The times
printed are smoke timings (``first_s`` includes compilation, ``warm_s``
repeats the same call); they are not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_ROWS = 10_000_000      # the paper's OSM scale (in-memory and store)
N_TRAIN = 200            # SMBO training queries
N_Q = 64                 # queries per kind and batch
N_SERVE = 256            # served submissions per burst
N_CLIENTS = 4
KNN_K = 10
STORE_CHUNK = 500_000    # rows per build_segment spill run
STORE_GROUP_PAGES = 64


# ---------------------------------------------------------------------------
# checks shared by every phase
# ---------------------------------------------------------------------------
def _device_platforms(arr) -> set:
    return {d.platform for d in arr.devices()}


def check_device_result(res, engine: str) -> None:
    """`res` was served by device engine `engine` itself: no routing to
    the CPU engine, at least one device call, no CPU exactness net."""
    acct = res.plan.accounting
    if res.engine != engine or res.plan.routed:
        raise AssertionError(f"{res.plan.kind} served by {res.engine!r} "
                             f"(routed={res.plan.routed}), not {engine!r}")
    if acct.device_calls <= 0:
        raise AssertionError(f"{res.plan.kind} on {engine}: no device call")
    if acct.cpu_fallbacks or res.cpu_fallbacks:
        raise AssertionError(f"{res.plan.kind} on {engine}: "
                             f"{acct.cpu_fallbacks} CPU fallbacks")


def check_same(got, want, what: str) -> None:
    """Bit-for-bit equality of every payload field of two results."""
    from repro.serving import assert_bit_identical
    assert_bit_identical(got, want, context=what)


def _first_warm(fn):
    """(warm result, first-call seconds, warm-call seconds) of `fn()`;
    the first result must equal the warm one."""
    t0 = time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    warm = fn()
    t2 = time.perf_counter()
    check_same(warm, first, "warm vs first call")
    return warm, t1 - t0, t2 - t1


def _query_sets(data, n_q: int, seed: int, K: int) -> dict:
    """One batch per kind, sized so a device pass needs few escalations:
    Count windows span 1% of the domain per dimension, Range windows 0.1%
    (their rows come back to the host), Point probes are half present
    rows and half perturbed ones, kNN centers are rows of the data."""
    import numpy as np
    from repro.api import Count, Knn, Point, Range
    from repro.data.workload import make_workload
    rng = np.random.default_rng(seed)
    cL, cU = make_workload(data, n_q, seed=seed, width_scale=0.01, K=K)
    rL, rU = make_workload(data, n_q, seed=seed + 1, width_scale=0.001, K=K)
    rows = data[rng.choice(len(data), n_q, replace=False)]
    probe = rows.copy()
    probe[n_q // 2:, 0] ^= np.uint64(1)
    centers = data[rng.choice(len(data), n_q, replace=False)]
    return {"count": Count(cL, cU), "range": Range(rL, rU),
            "point": Point(probe), "knn": Knn(centers, k=KNN_K, metric="l2")}


def _run_kinds(db, engine: str, queries: dict) -> dict:
    """Each query on `engine` (first + warm call) against the cpu engine;
    returns per-kind smoke timings."""
    out = {}
    for kind, q in queries.items():
        res, first, warm = _first_warm(lambda: db.query(q, engine=engine))
        check_device_result(res, engine)
        check_same(res, db.query(q, engine="cpu"), f"{engine}.{kind}")
        out[kind] = {"first_s": first, "warm_s": warm,
                     "escalations": res.plan.accounting.escalations}
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_fit(rows: int, seed: int):
    """SMBO fit + in-memory build; returns (db, data, report)."""
    from repro import obs
    from repro.api import Database
    from repro.data.synth import make_osm
    from repro.data.workload import make_workload
    import numpy as np
    data = make_osm(rows, seed=seed)
    train = make_workload(data, N_TRAIN, seed=seed)
    pooled = obs.counter("smbo.pool_eval.dispatches")
    before, was_on = pooled.value, obs.enabled()
    obs.enable()
    try:
        t0 = time.perf_counter()
        db = Database.fit(data, workload=train, seed=seed,
                          smbo={"evals_per_iter": 4})
        fit_s = time.perf_counter() - t0
    finally:
        if not was_on:
            obs.disable()
    dispatches = pooled.value - before
    if dispatches <= 0:
        raise AssertionError("SMBO never ran the pooled JAX evaluator")
    return db, data, {
        "rows": int(db.n), "d": db.d, "pages": db.num_pages,
        "cap": int(np.diff(db.index.starts).max()),
        "train_queries": N_TRAIN, "pooled_jax_dispatches": dispatches,
        "fit_s": fit_s, "match": True}


def phase_memory(db, data, cfg, platform: str, seed: int,
                 n_q: int = N_Q) -> dict:
    """pallas engine on all four kinds, xla engine on Count."""
    import jax
    from repro.core.theta import default_K
    queries = _query_sets(data, n_q, seed + 10, default_K(db.d))
    db.engine("pallas", cfg)
    pallas = _run_kinds(db, "pallas", queries)
    pts = db.engines["pallas"]._arrays.points
    if _device_platforms(pts) != {platform}:
        raise AssertionError(f"pallas arrays on {_device_platforms(pts)}")
    db.engine("xla", cfg)
    xla = _run_kinds(db, "xla", {"count": queries["count"]})
    stats = jax.devices()[0].memory_stats() or {}
    return {"rows": int(db.n), "pages": db.num_pages, "q_per_kind": n_q,
            "pallas": pallas, "xla": xla,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "match": True}


def phase_serve(db, data, seed: int, n_serve: int = N_SERVE,
                engine: str = "pallas") -> dict:
    """Two bursts of mixed single-query submissions from client threads
    (the first compiles the coalesced shape buckets); every ticket must
    resolve on `engine` and equal a serial replay of the query log."""
    import numpy as np
    from repro.api import Knn, Point
    from repro.core.theta import default_K
    from repro.serving import replay_serial
    qs = _query_sets(data, n_serve, seed + 20, default_K(db.d))
    bounds = {k: qs[k].normalized(d=db.d) for k in qs}

    def make(kind, i):
        """Query i of `kind`'s batch, as a one-row query of that kind."""
        if kind in ("count", "range"):
            L, U = bounds[kind]
            return type(qs[kind])(L[i:i + 1], U[i:i + 1])
        xs = bounds[kind][i:i + 1]
        return Point(xs) if kind == "point" else Knn(xs, k=KNN_K)

    kinds = list(qs)
    rng = np.random.default_rng(seed + 21)
    plan = [(kinds[int(rng.integers(len(kinds)))], i) for i in range(n_serve)]
    walls, tickets = [], []
    with db.serve(engine=engine) as srv:
        for _ in range(2):
            burst = []
            lock = threading.Lock()

            def client(c):
                mine = [srv.submit(make(k, i), client=f"c{c}")
                        for k, i in plan[c::N_CLIENTS]]
                with lock:
                    burst.extend(mine)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(N_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            if any(t.is_alive() for t in threads):
                raise AssertionError("a serving client never finished")
            for t in burst:
                t.result(timeout=600)
            walls.append(time.perf_counter() - t0)
            tickets.extend(burst)
        st = srv.stats()
        log = srv.query_log()
    if st["failed"] or st["shed"] or st["retries"]:
        raise AssertionError(f"serving counters: {st}")
    if st["served"] != len(tickets):
        raise AssertionError(f"{st['served']} served of {len(tickets)}")
    t0 = time.perf_counter()
    replay = replay_serial(db, log, engine=engine)
    replay_s = time.perf_counter() - t0
    for t in tickets:
        res = t.result()
        check_device_result(res, engine)
        check_same(res, replay[t.seq], f"serve.seq{t.seq}")
    return {"engine": engine, "submissions": len(tickets),
            "clients": N_CLIENTS, "batches": st["batches"],
            "first_s": walls[0], "warm_s": walls[1], "replay_s": replay_s,
            "failed": st["failed"], "shed": st["shed"],
            "retries": st["retries"], "match": True}


def phase_store(rows: int, seed: int, cfg, platform: str, out_dir: str,
                n_q: int = N_Q, chunk: int = STORE_CHUNK,
                group_pages: int = STORE_GROUP_PAGES) -> dict:
    """Out-of-core build, then the store engine with a cache budget of a
    quarter of the segment's device footprint (so eviction runs)."""
    import dataclasses
    import numpy as np
    from repro.api import Database
    from repro.core.theta import default_K
    from repro.data.synth import iter_chunks
    from repro.store import build_segment
    d = 3
    path = os.path.join(out_dir, "segment")
    shutil.rmtree(path, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        build_segment(iter_chunks(rows, chunk, seed, d=d), path)
        build_s = time.perf_counter() - t0
        db = Database.from_segment(path)
        seg = db.segment
        footprint = seg.num_groups(group_pages) * seg.group_nbytes(
            group_pages)
        budget = footprint // 4
        db.engine("store", dataclasses.replace(
            cfg, group_pages=group_pages, cache_bytes=budget))
        sample = np.asarray(seg.xs[::max(1, seg.n // 65536)],
                            dtype=np.uint64)
        queries = _query_sets(sample, n_q, seed + 30, default_K(d))
        kinds = _run_kinds(db, "store", queries)
        cache = db.engines["store"].cache
        st = cache.stats.snapshot()
        if st.hits + st.misses != st.lookups:
            raise AssertionError(f"cache accounting: {st}")
        if cache.resident_bytes > budget:
            raise AssertionError(f"cache over budget: {cache.resident_bytes}")
        if st.evictions <= 0:
            raise AssertionError(f"no eviction under the budget: {st}")
        blocks = cache.get(np.array([0]))
        if _device_platforms(blocks[0].points) != {platform}:
            raise AssertionError("store blocks are not on the device")
        return {"rows": seg.n, "d": d, "pages": seg.num_pages,
                "cap": seg.cap, "build_s": build_s, "q_per_kind": n_q,
                "footprint_bytes": footprint, "cache_bytes": budget,
                "hits": st.hits, "misses": st.misses,
                "evictions": st.evictions, "bypass": st.bypass,
                "store": kinds, "match": True}
    finally:
        shutil.rmtree(path, ignore_errors=True)


def phase_distributed(rows: int, seed: int, cfg, n_devices: int,
                      n_q: int = N_Q) -> dict:
    """The distributed engine over all devices: Count and Point against
    the cpu engine, page arrays spread over `n_devices` devices."""
    from repro.api import Database
    from repro.core.theta import default_K
    from repro.data.synth import make_osm
    t0 = time.perf_counter()
    data = make_osm(rows, seed=seed)
    db = Database.fit(data, learn=False)
    build_s = time.perf_counter() - t0
    queries = _query_sets(data, n_q, seed + 40, default_K(db.d))
    db.engine("distributed", cfg)
    kinds = _run_kinds(db, "distributed",
                       {k: queries[k] for k in ("count", "point")})
    pts = db.engines["distributed"]._arrays.points
    devices = {s.device for s in pts.addressable_shards}
    if len(devices) != n_devices:
        raise AssertionError(f"page arrays on {len(devices)} devices, "
                             f"expected {n_devices}")
    return {"rows": int(db.n), "pages": db.num_pages,
            "build_s": build_s, "shard_devices": len(devices),
            "distributed": kinds, "match": True}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def _line(name: str, report: dict) -> None:
    print(f"phase {name} {json.dumps(report)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".smoke_out"))
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing ran", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: compile cache {cache_dir}; device {device}; "
          f"rows {args.rows}; smoke timings, not benchmark numbers",
          flush=True)

    from repro.api import EngineConfig
    cfg = EngineConfig()                # interpret=False: real kernels
    if args.chips == 4:
        _line("distributed", phase_distributed(args.rows, args.seed, cfg,
                                               n_devices=4))
    else:
        db, data, rep = phase_fit(args.rows, args.seed)
        _line("fit", rep)
        _line("memory", phase_memory(db, data, cfg, "tpu", args.seed))
        _line("serve", phase_serve(db, data, args.seed))
        del db, data
        os.makedirs(args.out, exist_ok=True)
        _line("store", phase_store(args.rows, args.seed, cfg, "tpu",
                                   args.out))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
