"""The observability layer (`repro.obs`): metrics, spans, exporters —
and the contract that instrumentation NEVER changes results.
"""
from __future__ import annotations

import io
import json
import logging
import threading

import numpy as np
import pytest

from repro import obs
from repro.api import Count, Database, EngineConfig, Knn, Point, Range
from repro.api.exec.router import Router
from repro.core.index import IndexConfig
from repro.core.theta import default_K
from repro.data.synth import make_dataset
from repro.data.workload import make_workload
from repro.obs.metrics import Histogram, Registry
from repro.obs.trace import NULL_SPAN, Tracer


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with the global obs layer off + empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def fake_clock(step=1000):
    t = [0]

    def clk():
        t[0] += step
        return t[0]
    return clk


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    r = Registry()
    c = r.counter("q", kind="count")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    with pytest.raises(ValueError, match="monotonic"):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(3.5)
    g.add(-1.0)
    assert g.snapshot() == 2.5
    # same name, different labels = different series
    assert r.counter("q", kind="range") is not c
    assert r.counter("q", kind="count") is c
    with pytest.raises(TypeError, match="already registered"):
        r.gauge("q", kind="count")


def test_histogram_quantiles_exact_nearest_rank():
    h = Histogram("lat")
    for v in range(1, 101):          # 1..100
        h.observe(v)
    assert h.exact
    assert h.percentile(50) == 50
    assert h.percentile(95) == 95
    assert h.percentile(99) == 99
    assert h.percentile(100) == 100
    q = h.quantiles()
    assert q["p50"] <= q["p95"] <= q["p99"]
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["sum"] == 5050 and snap["exact"]
    with pytest.raises(ValueError):
        h.percentile(0)


def test_histogram_reservoir_overflow_falls_back_to_buckets():
    h = Histogram("lat", max_samples=10)
    for v in [2000] * 15:            # > cap: 5 dropped from the reservoir
        h.observe(v)
    assert not h.exact
    assert h.samples_dropped == 5
    # bucket fallback: upper bound of the bucket holding the rank (2048)
    assert h.percentile(50) == 2048
    assert h.snapshot()["samples_dropped"] == 5
    # monotone even on the bucket path
    q = h.quantiles()
    assert q["p50"] <= q["p95"] <= q["p99"]


def test_empty_histogram_has_no_quantiles():
    h = Histogram("lat")
    assert h.percentile(50) is None
    assert h.snapshot()["count"] == 0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_spans_nest_with_deterministic_clock():
    tr = Tracer(clock=fake_clock())
    with tr.span("outer", kind="a"):
        with tr.span("inner"):
            pass
    spans = tr.snapshot()
    assert [s.name for s in spans] == ["inner", "outer"]  # finish order
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert outer.t0_ns < inner.t0_ns
    assert inner.t1_ns <= outer.t1_ns
    assert outer.labels == {"kind": "a"}


def test_span_label_after_open_and_histogram_feed():
    reg = Registry()
    tr = Tracer(clock=fake_clock(), registry=reg)
    with tr.span("planner.plan", kind="count") as sp:
        sp.label(engine="xla")
    s, = tr.snapshot()
    assert s.labels == {"kind": "count", "engine": "xla"}
    h = reg.histogram("planner.plan_ns", kind="count", engine="xla")
    assert h.count == 1 and h.sum == 1000


def test_sid_and_parent_follow_nesting_per_thread():
    tr = Tracer(clock=fake_clock())
    out = {}
    both_open = threading.Barrier(2)

    def work(tag):
        with tr.span("outer", kind=tag) as a:
            both_open.wait()               # the two threads interleave
            with tr.span("mid") as b:
                with tr.span("leaf") as c:
                    pass
            with tr.span("sibling") as e:
                pass
        with tr.span("explicit", parent=a.sid) as f:
            pass
        out[tag] = (a.sid, b.sid, c.sid, e.sid, f.sid)
    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = {s.sid: s for s in tr.snapshot()}
    assert len(spans) == 10                     # sids are process-unique
    for a, b, c, e, f in out.values():
        assert spans[a].parent is None and spans[a].depth == 0
        assert spans[b].parent == a and spans[c].parent == b
        assert spans[e].parent == a
        assert spans[f].parent == a and spans[f].depth == 0
        assert len({spans[i].tid for i in (a, b, c, e, f)}) == 1
    x, y = out.values()
    assert spans[x[0]].tid != spans[y[0]].tid


def test_record_spans_threads_at_depth_zero():
    obs.enable(clock=fake_clock())
    t_admit = obs.clock_ns()                    # stamped on this thread
    sids = []

    def drain():
        with obs.span("serving.batch"):
            with obs.span("executor.execute", kind="count"):
                req = obs.record("serving.request", t_admit, obs.clock_ns(),
                                 kind="count", seq=7)
                sids.append(req)
                sids.append(obs.record("serving.wait", t_admit,
                                       obs.clock_ns(), parent=req,
                                       kind="count", seq=7))
    t = threading.Thread(target=drain)
    t.start()
    t.join()
    spans = {s.name: s for s in obs.tracer.snapshot()}
    req, wait = spans["serving.request"], spans["serving.wait"]
    assert [req.sid, wait.sid] == sids
    assert req.depth == wait.depth == 0 and req.recorded and wait.recorded
    assert req.parent is None and wait.parent == req.sid
    assert req.t0_ns == wait.t0_ns == t_admit
    assert req.tid == wait.tid == spans["serving.batch"].tid
    assert spans["executor.execute"].depth == 1
    assert not spans["serving.batch"].recorded
    assert obs.registry.histogram("serving.wait_ns", kind="count").count == 1
    # the same bounded buffer and drop accounting as spans
    obs.tracer.max_spans = len(obs.tracer)
    assert obs.record("serving.wait", 0, 1) is not None
    assert obs.tracer.spans_dropped == 1
    obs.tracer.max_spans = 200_000


def test_histogram_series_stay_bounded():
    obs.enable(clock=fake_clock())
    for i in range(1000):
        with obs.span("serving.batch", size=i, seq=i, batch=i,
                      kind="count" if i % 2 else "range"):
            pass
        obs.record("serving.wait", 0, i + 1, kind="point", seq=i, batch=i)
    series = [m for m in obs.registry.metrics() if m.kind == "histogram"]
    keys = sorted((m.name, m.labels) for m in series)
    assert keys == [("serving.batch_ns", (("kind", "count"),)),
                    ("serving.batch_ns", (("kind", "range"),)),
                    ("serving.wait_ns", (("kind", "point"),))]
    assert sum(m.count for m in series) == 2000
    # every label stays on the span records
    last = obs.tracer.snapshot()[-2]
    assert last.labels == {"size": 999, "seq": 999, "batch": 999,
                           "kind": "count"}


def test_disabled_leaves_registry_buffer_and_profiler_empty(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path), profiler_options=_no_pytracer())
    with obs.span("obs.never", kind="count"):
        with obs.span("obs.never.inner"):
            pass
    assert obs.record("obs.never.record", 0, 1) is None
    obs.inc("obs.never.c")
    jax.profiler.stop_trace()
    assert obs.registry.snapshot() == {} and len(obs.tracer) == 0
    names = {e.name for plane in ProfileData.from_file(_xplane(tmp_path))
             .planes for line in plane.lines for e in line.events}
    assert not any(n.startswith("obs.never") for n in names)


def _no_pytracer():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _xplane(path):
    import glob
    found = glob.glob(str(path / "plugins/profile/*/*.xplane.pb"))
    assert len(found) == 1, found
    return found[0]


def test_span_buffer_bounded_with_drop_accounting():
    tr = Tracer(clock=fake_clock(), max_spans=3)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert len(tr) == 3
    assert tr.spans_dropped == 2


def test_null_span_is_inert_and_shared():
    assert obs.span("anything", x=1) is NULL_SPAN
    with obs.span("nope") as sp:
        assert sp is NULL_SPAN
        assert sp.label(a=1) is NULL_SPAN
    assert len(obs.tracer) == 0


def test_disabled_hooks_record_nothing():
    obs.inc("c", 5)
    obs.observe("h", 1.0)
    obs.set_gauge("g", 2.0)
    assert obs.registry.snapshot() == {}
    obs.enable(clock=fake_clock())
    obs.inc("c", 5)
    obs.observe("h", 1.0)
    obs.set_gauge("g", 2.0)
    snap = obs.registry.snapshot()
    assert snap["c"] == 5 and snap["g"] == 2.0 and snap["h"]["count"] == 1


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_trace_export_balanced_and_nested(tmp_path):
    obs.enable(clock=fake_clock())
    with obs.span("outer", kind="count"):
        with obs.span("inner"):
            pass
    with obs.span("solo"):
        pass
    path = tmp_path / "trace.json"
    n = obs.export_trace(str(path))
    assert n == 3
    doc = json.loads(path.read_text())
    ev = doc["traceEvents"]
    assert sum(1 for e in ev if e["ph"] == "B") == 3
    assert sum(1 for e in ev if e["ph"] == "E") == 3
    # nesting: outer opens before inner; inner closes before outer
    names = [(e["name"], e["ph"]) for e in ev]
    assert names.index(("outer", "B")) < names.index(("inner", "B"))
    assert names.index(("inner", "E")) < names.index(("outer", "E"))
    sids = {e["name"]: e["args"]["sid"] for e in ev if e["ph"] == "B"}
    assert ev[0]["args"] == {"kind": "count", "sid": sids["outer"],
                             "parent": None}
    inner = next(e for e in ev if e["name"] == "inner" and e["ph"] == "B")
    assert inner["args"]["parent"] == sids["outer"]
    assert doc["otherData"]["spans_dropped"] == 0
    # timestamps are microseconds
    assert ev[0]["ts"] == pytest.approx(ev[0]["ts"], abs=1e-9)
    tss = [e["ts"] for e in ev]
    assert tss == sorted(tss)


def test_trace_export_writes_records_as_async_pairs():
    obs.enable(clock=fake_clock())
    with obs.span("serving.batch", batch=0) as b:
        pass
    sid = obs.record("serving.request", 500, 4500, parent=b.sid, seq=3)
    ev = obs.trace_events()
    assert [e["ph"] for e in ev] == ["b", "B", "E", "e"]
    begin = ev[0]
    assert begin["id"] == ev[-1]["id"] == sid
    assert begin["args"] == {"seq": "3", "sid": sid, "parent": b.sid}


def test_prometheus_text_format():
    obs.enable(clock=fake_clock())
    obs.inc("executor.queries", 7, kind="count")
    obs.observe("lat", 2000)
    text = obs.prometheus_text()
    assert '# TYPE repro_executor_queries counter' in text
    assert 'repro_executor_queries{kind="count"} 7' in text
    assert '# TYPE repro_lat histogram' in text
    assert 'repro_lat_bucket{le="2048"} 1' in text
    assert 'repro_lat_bucket{le="+Inf"} 1' in text
    assert 'repro_lat_sum 2000.0' in text and 'repro_lat_count 1' in text


def test_validate_quantiles_rejects_bad_histograms():
    obs.validate_quantiles({"p50": 1, "p95": 2, "p99": 3})
    with pytest.raises(AssertionError, match="non-monotone"):
        obs.validate_quantiles({"p50": 3, "p95": 2, "p99": 1})
    with pytest.raises(AssertionError, match="missing"):
        obs.validate_quantiles({"p50": 1, "p95": None, "p99": 2})


def test_bench_envelope_shape():
    env = obs.bench_envelope()
    assert env["schema"] == 1
    assert isinstance(env["host"], str)
    assert env["jax_version"]          # jax is baked into this container


def test_thread_safety_of_registry_and_tracer():
    obs.enable()                        # real clock: concurrent increments
    errs = []

    def work():
        try:
            for _ in range(300):
                obs.inc("t.c")
                obs.observe("t.h", 5)
                with obs.span("t.s"):
                    pass
        except Exception as e:          # pragma: no cover
            errs.append(e)
    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    snap = obs.registry.snapshot()
    assert snap["t.c"] == 1200
    assert snap["t.h"]["count"] == 1200
    assert len(obs.tracer) + obs.tracer.spans_dropped == 1200


# ---------------------------------------------------------------------------
# instrumentation is inert: results bit-identical with obs on
# ---------------------------------------------------------------------------


def _small_db(n=1200, seed=0):
    data = make_dataset("osm", n, seed=seed)
    K = default_K(2)
    Ls, Us = make_workload(data, 8, seed=seed + 1, K=K)
    db = Database.fit(data, (Ls, Us), K=K, learn=False,
                      cfg=IndexConfig(paging="heuristic", page_bytes=1024))
    db.engine("xla", EngineConfig(q_chunk=8, max_cand=16, max_hits=128))
    return db, data, (Ls, Us)


def test_instrumented_queries_bit_identical_and_metrics_flow():
    db, data, (Ls, Us) = _small_db()
    queries = [Count(Ls, Us), Range(Ls, Us), Point(data[:5]),
               Knn(data[:3], k=3)]
    want = [db.query(q) for q in queries]          # obs off
    obs.enable()
    got = [db.query(q) for q in queries]           # obs on
    with db.session(engine="xla", tick=3) as s:    # coalesced, obs on
        tickets = [s.submit(q) for q in queries for _ in range(2)]
    obs.disable()
    for w, g in zip(want, got):
        for f in ("counts", "rows", "offsets", "found", "neighbors",
                  "dists"):
            if hasattr(w, f):
                np.testing.assert_array_equal(getattr(w, f), getattr(g, f))
    for i, t in enumerate(tickets):
        w = want[i // 2]
        for f in ("counts", "rows", "offsets", "found", "neighbors",
                  "dists"):
            if hasattr(w, f):
                np.testing.assert_array_equal(getattr(w, f),
                                              getattr(t.result(), f))
    snap = db.stats()
    names = {k.split("{")[0] for k in snap["metrics"]}
    for expected in ("planner.plan_ns", "executor.device_call_ns",
                     "executor.execute_ns", "executor.queries",
                     "session.service_ns", "session.queue_wait_ns",
                     "session.coalesce_size", "session.tick_fill"):
        assert expected in names, expected
    assert snap["executor_cache"]["calls"] > 0
    # per-ticket service latency: one sample per coalesced submission
    svc = [v for k, v in snap["metrics"].items()
           if k.startswith("session.service_ns")]
    assert sum(h["count"] for h in svc) == len(tickets)
    for h in svc:
        assert h["p50"] <= h["p95"] <= h["p99"]
    assert db.stats(format="prometheus").startswith("# TYPE")
    with pytest.raises(ValueError, match="format"):
        db.stats(format="xml")


def test_served_spans_land_in_the_profiler_trace(tmp_path):
    """A served workload under ``jax.profiler`` with obs on: the spans
    sit on the host plane, nested as obs recorded them, one clock offset
    apart; every ticket books one wait and one request span."""
    import jax
    from jax.profiler import ProfileData
    from repro.serving import SLOConfig
    db, data, (Ls, Us) = _small_db()
    queries = [Count(Ls[:2], Us[:2]), Range(Ls[2:4], Us[2:4]),
               Point(data[:3]), Knn(data[3:5], k=3)] * 3
    obs.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_no_pytracer())
    with db.serve(slo=SLOConfig(window_init_ms=1.0, adaptive=False),
                  engine="xla") as srv:
        tickets = [srv.submit(q) for q in queries]
        for t in tickets:
            t.result(timeout=120)
    jax.profiler.stop_trace()
    spans = obs.tracer.snapshot()
    # more requests make no new registry series once every stage ran
    series = []
    for _ in range(2):
        with db.serve(slo=SLOConfig(window_init_ms=1.0, adaptive=False),
                      engine="xla") as srv:
            for t in [srv.submit(q) for q in queries]:
                t.result(timeout=120)
        series.append({(m.name, m.labels) for m in obs.registry.metrics()})
    assert series[0] == series[1]
    per_request = {"size", "fill", "group", "bytes", "run", "rows",
                   "iteration", "seq", "batch"}
    assert not {k for _, labels in series[1] for k, _ in labels} & per_request
    obs.disable()
    by_sid = {s.sid: s for s in spans}

    def ancestors(s):
        while s.parent is not None:
            s = by_sid[s.parent]
            yield s

    events = {}
    for plane in ProfileData.from_file(_xplane(tmp_path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serving.", "executor.")):
                    events.setdefault(e.name, []).append(
                        (plane.name, line.name, e.start_ns,
                         e.start_ns + e.duration_ns, e))
    matched, offsets = {}, []
    for name in ("serving.batch", "executor.fetch", "executor.dispatch"):
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.t0_ns)
        theirs = sorted(events.get(name, []), key=lambda e: e[2])
        assert mine and len(mine) == len(theirs), name
        assert {e[0] for e in theirs} == {"/host:CPU"}
        for s, e in zip(mine, theirs):
            matched[s.sid] = e
            offsets.append(s.t0_ns - e[2])
    offset = sorted(offsets)[len(offsets) // 2]      # one offset, +-1 ms
    assert all(abs(o - offset) <= 1e6 for o in offsets)
    batches = {}
    for s in spans:
        if s.name == "serving.batch":
            ev = matched[s.sid]
            assert dict(ev[4].stats)["batch"] == s.labels["batch"]
            batches[s.labels["batch"]] = ev
    for s in spans:
        if s.name not in ("executor.fetch", "executor.dispatch"):
            continue
        up = list(ancestors(s))
        names = [u.name for u in up]
        assert "executor.execute" in names, names
        if s.name == "executor.dispatch":
            assert names[0] == "executor.device_call"
        # inside its batch's profiler event, on the same thread's line
        batch = next(u for u in up if u.name == "serving.batch")
        outer, inner = batches[batch.labels["batch"]], matched[s.sid]
        assert outer[1] == inner[1]
        assert outer[2] <= inner[2] and inner[3] <= outer[3]
    # every ticket: one wait and one request, wait <= request
    for t in tickets:
        mine = [s for s in spans if s.labels.get("seq") == t.seq
                and s.name in ("serving.wait", "serving.request")]
        assert sorted(s.name for s in mine) == ["serving.request",
                                                "serving.wait"]
        wait, req = sorted(mine, key=lambda s: s.name != "serving.wait")
        assert wait.t0_ns == req.t0_ns and wait.dur_ns <= req.dur_ns
        assert wait.parent == req.sid and wait.labels["kind"] == t.kind
        assert req.labels["batch"] in batches
    for q, t in zip(queries, tickets):
        want = db.query(q, engine="xla")
        for f in ("counts", "rows", "found", "neighbors"):
            if hasattr(want, f):
                np.testing.assert_array_equal(getattr(t.result(), f),
                                              getattr(want, f))


def test_instrumented_router_exact_with_per_shard_accounting():
    data = make_dataset("osm", 1200, seed=7)
    K = default_K(2)
    Ls, Us = make_workload(data, 6, seed=8, K=K)
    oracle = Database.fit(data, (Ls, Us), K=K, learn=False,
                          cfg=IndexConfig(paging="heuristic",
                                          page_bytes=1024))
    want = oracle.query(Count(Ls, Us)).counts
    router = Router.build(data, 2, learn=False,
                          cfg=IndexConfig(paging="heuristic",
                                          page_bytes=1024))
    obs.enable()
    res = router.query(Count(Ls, Us))
    obs.disable()
    np.testing.assert_array_equal(res.counts, want)
    assert len(res.plan.accounting.per_shard) == 2
    names = {k.split("{")[0] for k in router.stats()["metrics"]}
    assert {"router.query_ns", "router.shard_ns",
            "router.merge_ns"} <= names


def test_device_call_stages_are_disjoint_and_labeled():
    db, data, (Ls, Us) = _small_db(n=2500)
    db.engine("xla", EngineConfig(q_chunk=8, max_cand=1))  # force the ladder
    obs.enable()
    res = db.query(Count(Ls, Us))        # cold: every rung traces anew
    res2 = db.query(Count(Ls, Us))       # warm: rungs book as escalate
    obs.disable()
    assert res.exact and res.escalations > 0
    stages = {}
    for m in obs.registry.metrics():
        if m.name == "executor.device_call_ns":
            stages[dict(m.labels)["stage"]] = m.count
    # first launch of each traced (fn, shape) books as compile — even a
    # ladder rung; only warm rungs book as escalate (disjoint stages)
    assert stages.get("compile", 0) >= 1 + res.escalations
    assert stages.get("escalate", 0) == res2.escalations
    assert stages.get("first", 0) >= 1   # the warm first pass
    total = sum(stages.values())
    assert total == (res.plan.accounting.device_calls
                     + res2.plan.accounting.device_calls)


def test_fit_and_smbo_spans_recorded():
    data = make_dataset("osm", 400, seed=2)
    K = default_K(2)
    Ls, Us = make_workload(data, 4, seed=3, K=K)
    obs.enable()
    Database.fit(data, (Ls, Us), K=K, learn=True,
                 smbo={"max_iters": 1, "n_init": 2, "evals_per_iter": 1},
                 sample=200)
    obs.disable()
    names = {k.split("{")[0] for k in obs.registry.snapshot()}
    assert {"database.fit_ns", "database.fit.learn_ns",
            "database.fit.build_ns", "smbo.iteration_ns",
            "smbo.evaluations"} <= names


# ---------------------------------------------------------------------------
# structured logging (repro.obs.log)
# ---------------------------------------------------------------------------


def test_logging_silent_by_default_and_byte_compatible_when_configured():
    from repro.obs import log as obs_log

    logger = obs_log.get_logger("launch.train")
    assert logger.name == "repro.launch.train"
    # silent by default: the repro root carries a NullHandler only
    root = logging.getLogger("repro")
    assert any(isinstance(h, logging.NullHandler) for h in root.handlers)
    # configured: "%(message)s" output is byte-identical to the print()
    # calls it replaced
    buf = io.StringIO()
    obs_log.configure(stream=buf)
    step, loss, gnorm, dt = 3, 0.1234, 1.5, 0.0421
    logger.info("step %d: loss=%.4f gnorm=%.3f %.0fms",
                step, loss, gnorm, dt * 1e3)
    printed = f"step {step}: loss={loss:.4f} gnorm={gnorm:.3f} {dt*1e3:.0f}ms"
    assert buf.getvalue() == printed + "\n"
    # idempotent: re-configure replaces, never stacks handlers
    n = len(logging.getLogger("repro").handlers)
    obs_log.configure(stream=buf)
    assert len(logging.getLogger("repro").handlers) == n
    logging.getLogger("repro").handlers[:] = [logging.NullHandler()]


def test_enable_disable_reset_roundtrip():
    assert not obs.enabled()
    obs.enable(clock=fake_clock())
    assert obs.enabled()
    assert obs.clock_ns() == 1000
    with obs.span("s"):
        pass
    assert len(obs.tracer) == 1
    obs.reset()
    assert len(obs.tracer) == 0 and obs.registry.snapshot() == {}
    assert obs.enabled()                # reset clears data, not the switch
    obs.disable()
    assert not obs.enabled()
    import time
    assert abs(obs.clock_ns() - time.perf_counter_ns()) < 10 ** 9
