"""Run every paper-table benchmark.  Prints ``name,us_per_call,derived`` CSV.

Sizing via env: REPRO_BENCH_N (points, default 2000000), REPRO_BENCH_Q
(queries, default 200), REPRO_SMBO_ITERS (default 4).

Every BENCH_*.json the suites leave behind is stamped with the common
envelope (``{"schema": 1, "host": ..., "jax_version": ...}`` — see
`repro.obs.bench_envelope`) so the perf trajectory across PRs stays
machine-comparable; reports that already carry a ``schema`` key are left
untouched.
"""
from __future__ import annotations

import glob
import json
import time
import traceback


def stamp_envelopes(pattern: str = "BENCH_*.json") -> list:
    """Add the common envelope to every matching report that lacks one;
    returns the stamped paths."""
    from repro.obs import bench_envelope
    env = bench_envelope()
    stamped = []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict) or "schema" in doc:
            continue
        doc = {**env, **doc}       # envelope keys first, report keys win
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
        stamped.append(path)
    return stamped


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from . import (bench_ablation, bench_learning_size, bench_query_perf,
                   bench_scale, bench_selectivity_scale_aspect,
                   bench_serve_engine, bench_serving, bench_smbo,
                   bench_split_paging)
    suites = [
        ("fig6_query_perf", bench_query_perf.run),
        ("fig7_8_9_sel_scale_aspect", bench_selectivity_scale_aspect.run),
        ("fig10_ablation", bench_ablation.run),
        ("tab3_4_5_split_paging", bench_split_paging.run),
        ("fig11_12_tab6_7_learning_size", bench_learning_size.run),
        ("serve_engine", bench_serve_engine.run),
        # these three write their own envelopes — stamp_envelopes() skips them
        ("serving", bench_serving.run),
        ("smbo", bench_smbo.run),
        ("scale", bench_scale.run),
    ]
    t_all = time.time()
    failures = []
    for name, fn in suites:
        t0 = time.time()
        print(f"### suite {name}")
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"### suite {name} done in {time.time()-t0:.1f}s")
    stamped = stamp_envelopes()
    if stamped:
        print(f"### stamped envelope onto {len(stamped)} report(s): "
              f"{', '.join(stamped)}")
    print(f"### all suites done in {time.time()-t_all:.1f}s")
    if failures:
        raise SystemExit(f"failed suites: {failures}")


if __name__ == "__main__":
    main()
