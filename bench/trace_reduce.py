"""Reduce a profiler trace (``.xplane.pb``) of one traced window to the
numbers the per-layer metrics read.

* The window is the host annotation `WINDOW` (a `TraceAnnotation` the
  benchmark opens when the measured window starts and closes when its
  last answer is in); device events are clipped to it.
* Device busy time is the union of the intervals of the device's
  ``XLA Ops`` events (all its lines where that line is absent), averaged
  over the device planes (``/device:TPU:<n>``).
* Query-program time is the summed duration of ``XLA Modules`` events
  whose name starts with one of `QUERY_PROGRAMS`: the jitted serving
  programs ``make_query_fn`` / ``make_range_fn`` return, which jit names
  ``jit_query_batch``.  Count, Point (a degenerate window), Range and
  kNN's box retrieval all run inside them.
* Idle gaps are the spaces between busy intervals, each labelled with
  the program span (``repro.obs``) that covers most of it; spans are on
  the host's ``perf_counter_ns`` clock and are moved onto the trace's
  clock by the window annotation's start.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
QUERY_PROGRAMS = ("jit_query_batch",)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """A device op's name as the trace gives it (its HLO text), cut to
    the name, result type and opcode: ``%fusion.7 = s32[16384] fusion``."""
    text = re.sub(r"\{[^{}]*\}", "", hlo)
    head, _, _ = text.partition("(")
    return head.strip()[:120]


def _union(intervals):
    """Sorted, merged [(a, b)] of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _events(line, lo, hi):
    for e in line.events:
        a = e.start_ns
        b = a + e.duration_ns
        if b > lo and a < hi:
            yield e.name, max(a, lo), min(b, hi)


def reduce_trace(path: str, spans=None, span_clock_at_window=None,
                 top: int = 10, host_as_device: bool = False) -> dict:
    """Numbers of one traced window.  `spans` (optional) are obs `Span`s
    and `span_clock_at_window` the obs clock reading taken when the
    window annotation opened; together they label idle gaps.
    `host_as_device` reads a CPU-only trace's XLA executor threads as the
    device (rehearsals on the CPU; never a device number)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    win = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                or plane.name.startswith("/device:GPU"):
            devices.append(plane)
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
    if win is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    if not devices and host_as_device:
        devices = [_HostAsDevice(p) for p in pd.planes
                   if p.name == "/host:CPU"]
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo, hi = win
    busy_total = 0.0
    program_ns = 0.0
    ops = defaultdict(float)
    gaps_all = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = [lines["XLA Ops"]] if "XLA Ops" in lines else list(
            plane.lines)
        ivs = []
        for ln in op_lines:
            for name, a, b in _events(ln, lo, hi):
                ivs.append((a, b))
                ops[op_name(name)] += (b - a) / len(devices)
        merged = _union(ivs)
        busy_total += sum(b - a for a, b in merged)
        prev = lo
        for a, b in merged:
            if a > prev:
                gaps_all.append((prev, a))
            prev = b
        if hi > prev:
            gaps_all.append((prev, hi))
        if "XLA Modules" in lines:
            for name, a, b in _events(lines["XLA Modules"], lo, hi):
                if name.startswith(QUERY_PROGRAMS):
                    program_ns += b - a
    n_dev = len(devices)
    window_s = (hi - lo) / 1e9
    out = {
        "window_s": window_s,
        "busy_s": busy_total / n_dev / 1e9,
        "query_program_s": program_ns / n_dev / 1e9,
        "device_ops": [[n, v / 1e9] for n, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
    }
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    out["idle_gaps"] = [[_label(g, spans, span_clock_at_window, lo),
                         (g[1] - g[0]) / 1e9] for g in gaps]
    return out


class _HostAsDevice:
    """A CPU trace's XLA executor threads, read as one device's ops."""

    def __init__(self, plane):
        self.name = plane.name
        self.lines = [ln for ln in plane.lines
                      if ln.name.startswith("tf_XLA")]


def _label(gap, spans, span_t0, trace_t0) -> str:
    """The deepest obs span covering at least half of `gap`; else the
    one covering most of it."""
    if not spans or span_t0 is None:
        return "unlabelled"
    a = gap[0] - trace_t0 + span_t0
    b = gap[1] - trace_t0 + span_t0
    half = (b - a) / 2
    deep, most, cover = None, None, 0
    for s in spans:
        c = min(b, s.t1_ns) - max(a, s.t0_ns)
        if c >= half and c > 0 and (deep is None or s.depth > deep.depth):
            deep = s
        if c > cover:
            most, cover = s, c
    best = deep or most
    if best is None:
        return "no program span (host between requests)"
    return best.name
