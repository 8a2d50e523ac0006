"""The trace reduction on a small trace recorded on one TPU v5e: three
serving programs (Count, Range, Point over a 50,000-row pallas index)
inside the benchmark's window annotation."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

TRACE = os.path.join(tiny.BENCH, "testdata", "serving_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    from trace_reduce import reduce_trace
    return reduce_trace(TRACE)


def test_window_and_busy_time(reduced):
    assert 0.04 < reduced["window_s"] < 0.06
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_query_programs_hold_the_busy_time(reduced):
    # every op in the window belongs to one of the three query programs
    assert reduced["query_program_s"] == pytest.approx(reduced["busy_s"],
                                                       rel=0.01)


def test_breakdown(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert all(t > 0 for _, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert any("window_filter_pallas" in n for n, _ in ops)
    assert all("{" not in n for n, _ in ops)
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert sum(g for _, g in gaps) <= reduced["window_s"] - reduced["busy_s"] \
        + 1e-9


def test_gap_labels_come_from_spans():
    from trace_reduce import _label
    sys.path.insert(0, os.path.join(tiny.ROOT, "src"))
    from repro.obs.trace import Span
    spans = [Span("serving.batch", 100, 1000, 0, 1, {}),
             Span("store.assemble", 200, 500, 1, 1, {})]
    # the gap [250, 650) in trace time, window opened at trace 0 / span 0
    assert _label((250, 650), spans, 0, 0) == "store.assemble"
    assert _label((5000, 6000), spans, 0, 0).startswith("no program span")


def test_missing_window_is_an_error(tmp_path):
    from trace_reduce import reduce_trace
    with pytest.raises(ValueError):
        # a trace with no window annotation: the CPU-only test trace
        reduce_trace(_cpu_trace(tmp_path))


def _cpu_trace(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
