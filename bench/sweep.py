"""Find an open-loop cell's knee once, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 100,200,400 [--mix-patch '{"slo": {"adaptive": false}}']

A rate may name the seed that orders its window, as ``60@5``: the same
rate twice with one seed repeats a window, with two seeds reorders it.
``--slo-patches '[{"adaptive": false, "window_init_ms": 2}, ...]'``
offers every rate once under each serving setting, on one set-up.

One process sets the cell up once (as ``run.py`` does), then offers each
rate in turn to a fresh server for ``--seconds`` and prints one line per
rate: p50, p90, p99 (nearest rank, shed and failed requests above every
completed one), shed, failed, batches and compiles in the window.  The
knee is the highest rate below every rate whose latency at the mix's
``knee.percentile`` passes ``knee.limit_ms`` or that shed or failed a
request (a failing rate bounds the knee even where a higher one, drawn
luckier, passes); the cell's
mix file then gets ``rate_qps`` = 0.8 x the knee, written by hand, as a
number.
Each rate's window is drawn as ``run.py`` draws the cell's, and the
warm-up runs them all first, so no rate compiles inside its window.
Answers are not compared here: ``run.py`` does that at the cell's rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import run  # noqa: E402


def sweep(name: str, seed: int, seconds: float, rates: list, *,
          root: str = run.ROOT, require_chip: bool = True,
          config_patch: dict = None, mix_patch: dict = None,
          orders: list = None, slo_patches: list = None) -> list:
    """One line per entry of `rates`; `orders[i]`, where given and not
    None, is the seed that orders window i (else one spawned from
    `seed`).  Each of `slo_patches` (default: the mix's SLO as it is)
    updates the mix's ``slo`` for one pass over every rate."""
    _, cell, cfg, mix, _, _ = run.start(root, name, require_chip,
                                        config_patch, mix_patch)
    from repro.serving import SLOConfig
    import system as sysmod
    import traffic as tr
    seeds = np.random.SeedSequence(int(seed)).spawn(len(rates) + 1)
    system = sysmod.build(cfg, int(seed), os.path.join(HERE, ".work", name))
    points = []
    pct = float(mix["knee"]["percentile"])
    key = f"p{pct:g}_ms"
    try:
        gen = tr.Generator(mix, system.rows, system.K,
                           int(cfg["structure_seed"]))
        slo = SLOConfig(**mix["slo"])
        slo_patches = slo_patches or [{}]
        orders = orders or [None] * len(rates)
        scheds = [gen.schedule(s if o is None else
                               np.random.SeedSequence(int(o)),
                               seconds, float(r))
                  for r, s, o in zip(rates, seeds[1:], orders)]
        # windows of one request count hold the same requests: warm each
        # set once
        sets = {len(sc): sc for sc in scheds}
        own = [q for sc in sets.values() for q in sc] if system.stateless \
            else gen.schedule(seeds[0], seconds, float(max(rates)), stream=1)
        replay = gen.schedule(seeds[0], float(mix["warmup"]["seconds"]),
                              float(rates[0]), stream=2)
        print(f"sweep: built {system.timings}; {len(system.rows)} rows, "
              f"{len(system.page_rows)} pages", file=sys.stderr, flush=True)
        run.warm_up(system, gen, slo, own, replay, sys.stderr)
        print(f"sweep: setup {time.perf_counter() - run.T_START:.1f} s",
              file=sys.stderr, flush=True)
        for patch, rate, sched, order in [
                (sp, *w) for sp in slo_patches
                for w in zip(rates, scheds, orders)]:
            slo = SLOConfig(**{**mix["slo"], **patch})
            c0 = system.db.executor.cache.compiles
            with system.db.serve(slo=slo, engine=system.engine) as srv:
                sent, _ = tr.run_open_loop(srv, sched)
                tr.collect(sent, run.WAIT_S)
                st = srv.stats()
            lat, failed = run.latencies_ms(sent)
            late = sorted(x.late_s * 1e3 for x in sent)
            p = {"rate_qps": rate, "order": order, "slo": patch,
                 "sent": len(sent),
                 "p50_ms": run.nearest_rank(lat, 50),
                 "p90_ms": run.nearest_rank(lat, 90),
                 "p99_ms": run.nearest_rank(lat, 99),
                 "mean_ms": float(np.mean(lat)) if lat else None,
                 "max_ms": lat[-1] if lat else None,
                 "by_kind": by_kind(sent),
                 "shed": st["shed"], "failed": failed,
                 "batches": st["batches"],
                 "batch_fill": st["served"] / max(1, st["batches"]),
                 "gen_late_p99_ms": run.nearest_rank(late, 99),
                 "compiles": system.db.executor.cache.compiles - c0,
                 "controller": st["controller"]}
            p.setdefault(key, run.nearest_rank(lat, pct))
            points.append(p)
            print(json.dumps(p), flush=True)
    finally:
        system.close()
    for sp in slo_patches:
        mine = [p for p in points if p["slo"] == sp]
        bad = [p["rate_qps"] for p in mine
               if p["failed"] or p["shed"] or p[key] is None
               or p[key] > float(mix["knee"]["limit_ms"])]
        ok = [p["rate_qps"] for p in mine
              if p["rate_qps"] < min(bad, default=float("inf"))]
        knee = {"knee_qps": max(ok) if ok else None,
                "rate_qps_at_0.8": 0.8 * max(ok) if ok else None}
        if len(slo_patches) > 1:
            knee["slo"] = sp
        print(json.dumps(knee), flush=True)
    return points


def by_kind(sent: list) -> dict:
    """Each kind's completed requests: count, p50 and p99 latency (ms)."""
    out = {}
    for s in sent:
        if s.ticket is not None and s.ticket.t_done is not None:
            out.setdefault(s.req.kind, []).append(
                (s.ticket.t_done - s.t_due) * 1e3)
    return {k: [len(v), run.nearest_rank(sorted(v), 50),
                run.nearest_rank(sorted(v), 99)]
            for k, v in sorted(out.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, ascending; "
                    "rate@seed names the seed that orders that window")
    ap.add_argument("--mix-patch", default="{}",
                    help="JSON object merged into the mix")
    ap.add_argument("--slo-patches", default="[{}]",
                    help="JSON list of objects, each merged into the mix's "
                    "slo for one pass over every rate")
    ap.add_argument("--config-patch", default="{}",
                    help="JSON object merged into the configuration, "
                    "e.g. '{\"rows\": 50000000}' to probe another size")
    args = ap.parse_args(argv)
    try:
        items = [r.split("@") for r in args.rates.split(",")]
        sweep(args.workload, args.seed, args.seconds,
              [float(i[0]) for i in items],
              config_patch=json.loads(args.config_patch),
              mix_patch=json.loads(args.mix_patch),
              orders=[int(i[1]) if len(i) > 1 else None for i in items],
              slo_patches=json.loads(args.slo_patches))
    except run.NoChip as e:
        print(f"sweep: {e}; nothing measured", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
