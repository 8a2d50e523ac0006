"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the root ``BENCHMARK.json``; it
names a configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  Each metric of the cell is read by its
own reader, ``bench/metrics/<metric>.py``.  So a new cell, configuration,
mix or metric is a new file and a new entry, never an edit here.

One process holds the chip.  Set-up (rows from the seed, curve fit,
index or segment build, upload, warm-up of the cell's own shapes) runs
first and is timed as ``setup_s``; then the window serves the mix
through ``Database.serve`` for ``--seconds``; then every sampled answer
is compared with the plain reference (``bench/reference.py``).  With
``--trace 1`` the window runs under the profiler with the program's
spans and counters on, and the line carries the per-layer metrics
instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  ``--control`` runs the control instead of the
program as configured: the engine without its escalation ladder and CPU
exactness net, which must come out not correct (``bench/tests``).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

#: Engine settings of the control: the ladder and the CPU net switched off,
#: so overflowed Count and Range windows come back short.
CONTROL = {"escalate": False, "cpu_fallback": False}

#: Answers compared per kind, drawn from the seed among those due in the
#: window (Point answers are all compared: the check is one search).
SAMPLE = {"count": 300, "range": 150, "point": None, "knn": 60}

WAIT_S = 60.0          # how long past the window answers are waited for


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: str, name: str) -> tuple:
    """(benchmark, workload entry, config, mix) for cell `name`."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    here = os.path.join(root, "bench")
    cfg = load_json(os.path.join(here, "configs", cell["config"] + ".json"))
    mix = load_json(os.path.join(here, "traffic", cell["traffic"] + ".json"))
    return bench, cell, cfg, mix


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in mine
                             else [])]


def load_reader(name: str, root: str = ROOT):
    """``bench/metrics/<name>.py``'s ``read(ctx) -> number | None``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# warm-up: every shape the window can use, before the clock starts
# ---------------------------------------------------------------------------
def warm_up(system, gen, slo, requests, replay, log=None) -> dict:
    """Every request of `requests`, in batches of one kind as large as the
    server forms (``slo.batch_max`` submissions), straight through
    ``Database.query``: each batch climbs the escalation ladder as far as
    its heaviest window, so every program those requests can need is
    loaded or compiled here.  Then `replay` through a server, to warm the
    serving front.  Each step's seconds and program builds go to `log`."""
    from repro.api import Count, Knn, Point, Range
    import traffic as tr
    db = system.db
    cache = db.executor.cache
    steps = []
    for kind in gen.kinds:
        mine = [r for r in requests if r.kind == kind]
        t, c, esc = time.perf_counter(), cache.compiles, 0
        for i in range(0, len(mine), slo.batch_max):
            reqs = mine[i:i + slo.batch_max]
            if kind in ("count", "range"):
                q = (Count if kind == "count" else Range)(
                    np.concatenate([r.lo for r in reqs]),
                    np.concatenate([r.hi for r in reqs]))
            else:
                xs = np.concatenate([r.xs for r in reqs])
                q = Point(xs) if kind == "point" else Knn(
                    xs, k=reqs[0].k, metric=reqs[0].metric)
            esc += db.query(q, engine=system.engine).escalations
        steps.append(f"{kind} x{len(mine)} {time.perf_counter() - t:.2f}s "
                     f"{cache.compiles - c}c {esc}e")
    t, c = time.perf_counter(), cache.compiles
    with db.serve(slo=slo, engine=system.engine) as srv:
        sent, _ = tr.run_open_loop(srv, replay)
        tr.collect(sent, WAIT_S)
    steps.append(f"replay x{len(sent)} {time.perf_counter() - t:.2f}s "
                 f"{cache.compiles - c}c")
    if log is not None:
        print("bench: warm-up " + "; ".join(steps), file=log, flush=True)
    return {"requests": len(requests) + len(sent)}


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------
def compare(sent: list, ref, seed_seq) -> dict:
    """Mismatched and missing answers among a seeded sample of the
    requests due in the window."""
    rng = np.random.default_rng(seed_seq)
    by_kind = {}
    for s in sent:
        if s.ticket is not None:
            by_kind.setdefault(s.req.kind, []).append(s)
    checked = mismatched = missing = 0
    first_bad = None
    for kind, items in sorted(by_kind.items()):
        n = SAMPLE[kind]
        if n is not None and len(items) > n:
            pick = rng.choice(len(items), n, replace=False)
            items = [items[i] for i in sorted(pick)]
        for s in items:
            checked += 1
            if s.error is not None or not s.ticket.done():
                missing += 1
                continue
            bad = answer_differs(s.req, s.ticket.result(), ref)
            if bad:
                mismatched += 1
                first_bad = first_bad or f"{kind}: {bad}"
    return {"checked": checked, "mismatched": mismatched,
            "missing": missing, "first_bad": first_bad}


def answer_differs(req, res, ref):
    """None when the program's answer equals the reference's, else a
    short description of the first difference."""
    kind = req.kind
    if kind == "count":
        want = [ref.count(a, b) for a, b in zip(req.lo, req.hi)]
        got = [int(v) for v in np.asarray(res.counts)]
        return None if got == want else f"counts {got[:4]} != {want[:4]}"
    if kind == "range":
        for i, (a, b) in enumerate(zip(req.lo, req.hi)):
            want = ref.range(a, b)
            got = np.asarray(res.rows_for(i), dtype=np.uint64)
            if got.shape != want.shape or not np.array_equal(got, want):
                return f"window {i}: {len(got)} rows != {len(want)}"
        return None
    if kind == "point":
        want = ref.point(req.xs)
        got = np.asarray(res.found, dtype=bool)
        return None if np.array_equal(got, want) else "found flags differ"
    for i, c in enumerate(req.xs):
        rows, dists = ref.knn(c, req.k, req.metric)
        got = np.asarray(res.neighbors_for(i), dtype=np.uint64)
        gd = np.asarray(res.dists_for(i), dtype=np.float64)
        if not (np.array_equal(got, rows) and np.array_equal(gd, dists)):
            return f"knn centre {i}: neighbours differ"
    return None


# ---------------------------------------------------------------------------
# roofline work: bytes of every row on the pages a window's MBR meets
# ---------------------------------------------------------------------------
def window_work_bytes(sent: list, system, block: int = 256) -> float:
    """Sum over the Count, Range and Point windows sent of the rows on
    pages whose MBR intersects the window, times d x 4 bytes."""
    lo_m = system.page_mbrs[:, :, 0].astype(np.uint64)
    hi_m = system.page_mbrs[:, :, 1].astype(np.uint64)
    rows = system.page_rows.astype(np.float64)
    los, his = [], []
    for s in sent:
        r = s.req
        if r.kind in ("count", "range"):
            los.append(r.lo)
            his.append(r.hi)
        elif r.kind == "point":
            los.append(r.xs)
            his.append(r.xs)
    if not los:
        return 0.0
    L = np.concatenate(los)
    U = np.concatenate(his)
    d = L.shape[1]
    total = 0.0
    for i in range(0, len(L), block):
        a, b = L[i:i + block, None, :], U[i:i + block, None, :]
        hit = np.all((lo_m[None] <= b) & (a <= hi_m[None]), axis=2)
        total += float((hit.astype(np.float64) @ rows).sum())
    return total * d * 4


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def _counter_sums() -> dict:
    from repro import obs
    out = {}
    for m in obs.registry.metrics():
        if m.kind == "counter":
            out[m.name] = out.get(m.name, 0) + m.value
    return out


def start(root: str, name: str, require_chip: bool, config_patch: dict,
          mix_patch: dict) -> tuple:
    """The cell's entries and files (patched), the program on the path,
    JAX's compile cache placed, and the device checked; returns
    (benchmark, cell, config, mix, device, cache directory)."""
    bench, cell, cfg, mix = cell_spec(root, name)
    cfg = _patched(cfg, config_patch)
    mix = _patched(mix, mix_patch)
    sys.path.insert(0, os.path.join(root, "src"))
    # the cache lives in the checkout, at a fixed path (the path is part of
    # the key); the program's own placement takes the directory given here
    cache_dir = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program, however fast it compiled: a later run of the
    # cell loads it instead of compiling inside its set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size cap, so no eviction: an environment that caps the cache
    # would make every run evict and compile again, and eviction fails on
    # an entry copied in without its access-time file
    jax.config.update("jax_compilation_cache_max_size", -1)
    device = device_info(int(cell["chips"]), require_chip)
    return bench, cell, cfg, mix, device, cache_dir


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_chip: bool = True,
             config_patch: dict = None, mix_patch: dict = None,
             engine_overrides: dict = None, after_build=None,
             workdir: str = None, log=sys.stderr) -> dict:
    """One run of cell `name`; returns the result-line dict.
    `config_patch` / `mix_patch` update the configuration and mix (the
    tests shrink a cell with them); `after_build(system)` may break the
    system under test (the tests' planted faults); `workdir` holds the
    run's working files (default ``bench/.work/<cell>``)."""
    bench, cell, cfg, mix, device, cache_dir = start(
        root, name, require_chip, config_patch, mix_patch)
    metrics = cell_metrics(bench, cell, trace)
    import jax
    peaks_all = load_json(os.path.join(HERE, "peaks.json"))
    peaks = peaks_all.get(device["kind"]) if require_chip else None
    if require_chip and peaks is None:
        raise KeyError(f"device kind {device['kind']!r} is not in "
                       f"bench/peaks.json")
    from repro import obs
    from repro.serving import SLOConfig
    import system as sysmod
    import traffic as tr
    from reference import Reference
    from trace_reduce import WINDOW, find_xplane, reduce_trace

    seeds = np.random.SeedSequence(int(seed)).spawn(4)
    workdir = workdir or os.path.join(HERE, ".work", name)
    system = sysmod.build(cfg, int(seed), workdir,
                          engine_overrides=engine_overrides)
    try:
        if after_build is not None:
            after_build(system)
        gen = tr.Generator(mix, system.rows, system.K,
                           int(cfg["structure_seed"]))
        slo = SLOConfig(**mix["slo"])
        print(f"bench: built in {time.perf_counter() - T_START:.2f} s "
              f"{system.timings}; {len(system.rows)} rows, "
              f"{len(system.page_rows)} pages, padded to a multiple of "
              f"{system.db.engines[system.engine].pad_pages_to}",
              file=log, flush=True)
        rate = float(mix["rate_qps"])
        schedule = gen.schedule(seeds[1], seconds, rate)
        # the window's own requests where no state carries from one query
        # to the next; else another draw (a page cache warmed by the
        # window's own requests would make a cold cell hot)
        own = schedule if system.stateless else gen.schedule(
            seeds[1], seconds, rate, stream=1)
        replay = gen.schedule(seeds[0], float(mix["warmup"]["seconds"]),
                              rate, stream=2)
        warm = warm_up(system, gen, slo, own, replay, log)
        compiles0 = system.db.executor.cache.compiles
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            obs.enable()
            obs.reset()
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - T_START
        srv = system.db.serve(slo=slo, engine=system.engine)
        try:
            span_t0 = obs.clock_ns()
            with jax.profiler.TraceAnnotation(WINDOW) \
                    if trace else contextlib.nullcontext():
                sent, t0 = tr.run_open_loop(srv, schedule)
                tr.collect(sent, WAIT_S)
            stats = srv.stats()
        finally:
            srv.close()
        if trace:
            jax.profiler.stop_trace()
            spans = obs.tracer.snapshot()
            counters = _counter_sums()
            obs.disable()
        compiles = system.db.executor.cache.compiles - compiles0
        mem_peak = peak_bytes()
        trace_red = None
        work = None
        if trace:
            trace_red = reduce_trace(find_xplane(trace_dir), spans, span_t0,
                                     host_as_device=not require_chip)
            shutil.rmtree(trace_dir, ignore_errors=True)
            work = window_work_bytes(sent, system)
        rows, K = system.rows, system.K
        timings = dict(system.timings)
    finally:
        system.close()
    del system
    gc.collect()
    t_ref = time.perf_counter()
    ref = Reference(rows, K)
    cmp = compare(sent, ref, seeds[2])
    ref_s = time.perf_counter() - t_ref

    lat_ms, failed = latencies_ms(sent)
    # what the metric readers (bench/metrics/*.py) read
    ctx = types.SimpleNamespace(
        sent=sent, t0=t0, seconds=seconds, setup_s=setup_s,
        latencies_ms=lat_ms, timings=timings, stats=stats,
        counters=counters if trace else {}, spans=spans if trace else [],
        trace=trace_red, work_bytes=work, peaks=peaks, compiles=compiles,
        mix=mix, nearest_rank=nearest_rank)
    values = {}
    for m in metrics:
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device["memory_peak_bytes"] = mem_peak
    if trace:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
    correct = cmp["mismatched"] == 0 and cmp["missing"] == 0
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": failed, "metrics": values, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["compared"] = {
        "mismatched": {"value": cmp["mismatched"], "limit": 0},
        "missing": {"value": cmp["missing"], "limit": 0}}
    print(f"bench: cell {name} seed {seed} compile cache {cache_dir}; "
          f"warm-up {warm['requests']} requests; setup {setup_s:.2f} s "
          f"({timings}); {len(sent)} sent, {stats['batches']} batches, "
          f"{compiles} compiles in window; reference {ref_s:.2f} s over "
          f"{cmp['checked']} answers; first difference: {cmp['first_bad']}",
          file=log)
    print(f"compared mismatched {cmp['mismatched']} limit 0", file=log)
    print(f"compared missing {cmp['missing']} limit 0", file=log,
          flush=True)
    return out


def nearest_rank(sorted_values, p: float):
    """The p-th percentile of a sorted list by nearest rank."""
    if not sorted_values:
        return None
    return sorted_values[max(0, math.ceil(p / 100.0 * len(sorted_values))
                             - 1)]


def latencies_ms(sent: list) -> tuple:
    """Latency of every request (ms), shed and failed ones placed above
    every completed one; and how many were shed or failed."""
    done, failed = [], 0
    for s in sent:
        if s.ticket is None or s.error is not None or s.ticket.t_done is None:
            failed += 1
        else:
            done.append((s.ticket.t_done - s.t_due) * 1e3)
    top = (max(done) if done else 0.0) + WAIT_S * 1e3
    return sorted(done) + [top] * failed, failed


def _patched(d: dict, patch: dict) -> dict:
    out = json.loads(json.dumps(d))
    for k, v in (patch or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _patched(out[k], v)
        else:
            out[k] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve with the control's engine settings")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace),
                       engine_overrides=CONTROL if args.control else None)
    except NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
