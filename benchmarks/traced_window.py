"""Run one benchmark cell's traced window and take its drain thread apart.

    python3 benchmarks/traced_window.py --workload <cell> --seed <n> \
        --seconds <s> [--python-tracer-level 0|1] [--span-cost] \
        [--out <path.json>]

The window is ``bench/run.py``'s traced run (same set-up, traffic,
readers and reference); this script keeps the program spans the trace
reduction reads, and prints one JSON object:

* ``result``: the run's result line;
* ``batches``: the five longest ``serving.batch`` spans, each with the
  kinds it served, its ladder rungs (``executor.device_call`` labels) and
  the stage under it with the most self time;
* ``gaps``: the longest device-idle gaps, each with its label, the
  share of it the drain thread's own spans cover (recorded request
  intervals left out) and the milliseconds of it the garbage collector
  ran (``gc_ms``);
* ``gc``: the collector's pauses in the window, by generation;
* ``host_plane``: how many profiler events on the host plane carry an
  obs span's name;
* with ``--span-cost``, host ns per ``obs.span``: disabled and enabled,
  without a profiler and under one with its Python call tracer on and off.

``--python-tracer-level`` sets the profiler's Python call tracer for the
window (jax records every Python call by default, level 1).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))

import run  # noqa: E402  (bench/run.py)
import trace_reduce  # noqa: E402

SPAN_PREFIXES = ("serving.", "session.", "executor.", "planner.")


def _profiler_options(level: int):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = level
    return opts


def _self_ns(spans) -> dict:
    """sid -> the span's duration less its children's."""
    out = {s.sid: s.dur_ns for s in spans if not s.recorded}
    for s in spans:
        if not s.recorded and s.parent in out:
            out[s.parent] -= s.dur_ns
    return out


def batch_breakdown(spans, top: int = 5) -> list:
    """The `top` longest serving.batch spans, each taken apart."""
    kids = defaultdict(list)
    for s in spans:
        if not s.recorded and s.parent is not None:
            kids[s.parent].append(s)
    self_ns = _self_ns(spans)
    rows = []
    batches = sorted((s for s in spans if s.name == "serving.batch"),
                     key=lambda s: -s.dur_ns)[:top]
    for b in batches:
        desc, todo = [], [b]
        while todo:
            s = todo.pop()
            desc.append(s)
            todo.extend(kids[s.sid])
        stage = defaultdict(int)
        for s in desc:
            stage[s.name] += self_ns[s.sid]
        rungs = sorted({(s.labels.get("kind"), s.labels.get("stage"),
                         s.labels.get("max_cand"), s.labels.get("max_hits"))
                        for s in desc if s.name == "executor.device_call"},
                       key=str)
        execs = defaultdict(int)
        for s in desc:
            if s.name == "executor.execute":
                execs[s.labels.get("kind")] += s.dur_ns
        held = sorted(stage.items(), key=lambda kv: -kv[1])[:4]
        rows.append({
            "ms": b.dur_ns / 1e6, "batch": b.labels.get("batch"),
            "kinds": b.labels.get("kinds"), "size": b.labels.get("size"),
            "execute_ms_by_kind": {k: v / 1e6 for k, v in execs.items()},
            "rungs": [list(r) for r in rungs],
            "self_ms_by_stage": [[n, v / 1e6] for n, v in held]})
    return rows


def gc_pauses() -> list:
    """Start recording the garbage collector's pauses, as
    (t0_ns, t1_ns, generation) on the obs clock, into the list returned."""
    pauses, start = [], []

    def note(phase, info):
        now = time.perf_counter_ns()
        if phase == "start":
            start[:] = [now]
        elif start:
            pauses.append((start.pop(), now, info["generation"]))
    gc.callbacks.append(note)
    return pauses


def gc_summary(pauses, spans) -> dict:
    """Count, total and longest ms of the pauses within the spans' span
    of time, by generation."""
    if not spans:
        return {}
    lo = min(s.t0_ns for s in spans)
    hi = max(s.t1_ns for s in spans)
    out = {}
    for a, b, gen in pauses:
        if lo <= a and b <= hi:
            g = out.setdefault(f"gen{gen}", {"n": 0, "ms": 0.0,
                                             "max_ms": 0.0})
            g["n"] += 1
            g["ms"] += (b - a) / 1e6
            g["max_ms"] = max(g["max_ms"], (b - a) / 1e6)
    return out


def gap_coverage(gap, spans, span_t0, trace_t0, pauses=()) -> dict:
    """How much of one idle gap the drain thread's spans cover, the
    deepest of them covering at least half of it, and the collector's
    share of it."""
    a = gap[0] - trace_t0 + span_t0
    b = gap[1] - trace_t0 + span_t0
    gc_ns = sum(max(0, min(b, t1) - max(a, t0)) for t0, t1, _ in pauses)
    ivs, deep = [], None
    for s in spans:
        if s.recorded:
            continue
        lo, hi = max(a, s.t0_ns), min(b, s.t1_ns)
        if hi > lo:
            ivs.append((lo, hi))
            if 2 * (hi - lo) >= b - a and (deep is None
                                           or s.depth > deep.depth):
                deep = s
    covered = sum(hi - lo for lo, hi in trace_reduce._union(ivs))
    return {"ms": (b - a) / 1e6, "drain_covered": covered / (b - a),
            "deepest_over_half": deep.name if deep else None,
            "gc_ms": gc_ns / 1e6}


def host_plane_names(path: str) -> dict:
    from jax.profiler import ProfileData
    counts = defaultdict(int)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    counts[e.name] += 1
    return dict(sorted(counts.items()))


def span_cost(workdir: str, n: int = 20000) -> dict:
    """Host ns per obs span (two labels), in each posture."""
    import jax
    from repro import obs

    def loop():
        t = time.perf_counter_ns()
        for i in range(n):
            with obs.span("cost.probe", kind="count", seq=i):
                pass
        return (time.perf_counter_ns() - t) / n

    out = {}
    for level in (None, 1, 0):
        where = "no_profiler" if level is None else f"python_tracer_{level}"
        if level is not None:
            shutil.rmtree(workdir, ignore_errors=True)
            jax.profiler.start_trace(
                workdir, profiler_options=_profiler_options(level))
        obs.disable()
        out[f"disabled_{where}"] = loop()
        obs.enable()
        obs.reset()
        out[f"enabled_{where}"] = loop()
        obs.disable()
        obs.reset()
        if level is not None:
            jax.profiler.stop_trace()
    shutil.rmtree(workdir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer-level", type=int, choices=(0, 1))
    ap.add_argument("--span-cost", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax

    kept = {"gaps": []}
    reduce_trace, label = trace_reduce.reduce_trace, trace_reduce._label

    def keep_reduce(path, spans=None, span_clock_at_window=None, **kw):
        kept["spans"] = spans
        kept["host_plane"] = host_plane_names(path)
        return reduce_trace(path, spans, span_clock_at_window, **kw)

    def keep_label(gap, spans, span_t0, trace_t0):
        name = label(gap, spans, span_t0, trace_t0)
        kept["gaps"].append(dict(
            gap_coverage(gap, spans, span_t0, trace_t0, pauses), label=name))
        return name

    trace_reduce.reduce_trace = keep_reduce
    trace_reduce._label = keep_label
    pauses = gc_pauses()
    start_trace = jax.profiler.start_trace
    if args.python_tracer_level is not None:
        opts = _profiler_options(args.python_tracer_level)
        jax.profiler.start_trace = \
            lambda log_dir, **kw: start_trace(log_dir, profiler_options=opts)
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True)
    except run.NoChip as e:
        print(f"traced_window: {e}; nothing measured", file=sys.stderr)
        return 3
    finally:
        jax.profiler.start_trace = start_trace
    spans = kept.get("spans") or []
    out = {"workload": args.workload, "seed": args.seed,
           "python_tracer_level": args.python_tracer_level,
           "result": result, "spans": len(spans),
           "batches": batch_breakdown(spans), "gaps": kept["gaps"],
           "gc": gc_summary(pauses, spans),
           "host_plane": kept.get("host_plane")}
    if args.span_cost:
        out["span_cost_ns"] = span_cost(
            os.path.join(ROOT, "results", "span_cost"))
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
