"""The readers of the serving front's and the executor's spans and
counters (queue_wait_p99_ms, drain_busy_share, exec_host_ms_per_kq,
fetch_mb_per_kq): on hand-made spans, on a program that records none of
them, and in a traced rehearsal of the open-loop cell."""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

NAMES = ("queue_wait_p99_ms", "drain_busy_share", "exec_host_ms_per_kq",
         "fetch_mb_per_kq")


def _span(name, t0_ms, t1_ms):
    return types.SimpleNamespace(name=name, t0_ns=int(t0_ms * 1e6),
                                 t1_ns=int(t1_ms * 1e6),
                                 dur_ns=int((t1_ms - t0_ms) * 1e6))


def _ctx(spans=(), counters=None, window_s=None):
    import run
    return types.SimpleNamespace(
        spans=list(spans), counters=counters or {}, seconds=1.0,
        trace={"window_s": window_s} if window_s else None,
        nearest_rank=run.nearest_rank)


@pytest.mark.parametrize("name", NAMES)
def test_reader_leaves_metric_out_without_spans(name):
    """A program that records none of these spans or counters (as before
    they existed) gives no value, and the reader does not raise."""
    import run
    assert run.load_reader(name, tiny.ROOT)(_ctx()) is None


def test_span_readers_on_hand_made_spans():
    import run
    spans = ([_span("serving.wait", 0, w) for w in range(1, 101)]
             + [_span("serving.batch", 0, 100), _span("serving.batch", 50, 150),
                _span("serving.batch", 400, 500)]
             + [_span("executor.fetch", 0, 2), _span("executor.resolve", 2, 5),
                _span("executor.device_call", 5, 50)])
    counters = {"executor.queries": 500, "executor.fetch_bytes": 4_000_000}
    ctx = _ctx(spans, counters, window_s=0.5)
    read = {n: run.load_reader(n, tiny.ROOT)(ctx) for n in NAMES}
    assert read["queue_wait_p99_ms"] == pytest.approx(99.0)
    # union of [0, 150] and [400, 500] over a 500 ms window
    assert read["drain_busy_share"] == pytest.approx(50.0)
    # 5 ms of host stages (the device call is not one) per 0.5 kq
    assert read["exec_host_ms_per_kq"] == pytest.approx(10.0)
    assert read["fetch_mb_per_kq"] == pytest.approx(8.0)


def test_traced_rehearsal_reports_span_metrics(tmp_path):
    cell = next(c for c in tiny.cells() if c["traffic"].endswith("open")
                and "store" not in c["config"])
    out = tiny.run_tiny(cell, tmp_path, trace=True)
    assert out["correct"] is True, out["_log"]
    m = out["metrics"]
    for name in NAMES:
        assert name in m, sorted(m)
    assert 0 < m["drain_busy_share"]["value"] <= 100
    assert m["fetch_mb_per_kq"]["value"] > 0
    assert m["queue_wait_p99_ms"]["value"] >= 0
    assert m["exec_host_ms_per_kq"]["value"] > 0
