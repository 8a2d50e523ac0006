"""Every cell of BENCHMARK.json, rehearsed on the CPU at a tiny size
through run.py's own path, and run.py's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402


@pytest.mark.parametrize("cell", tiny.cells(), ids=lambda c: c["name"])
def test_cell_rehearsal(cell, tmp_path):
    import run
    out = tiny.run_tiny(cell, tmp_path)
    assert out["correct"] is True, out["_log"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2] == "compared"           # the compared key is last
    bench = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    want = {m["name"] for m in run.cell_metrics(bench, cell, False)}
    assert set(out["metrics"]) == want
    assert out["metrics"]["p50_ms"]["value"] > 0
    assert "compared mismatched 0 limit 0" in out["_log"]


def test_traced_rehearsal_reports_per_layer_metrics(tmp_path):
    cell = next(c for c in tiny.cells() if c["traffic"].endswith("open")
                and "store" not in c["config"])
    out = tiny.run_tiny(cell, tmp_path, trace=True)
    assert out["correct"] is True, out["_log"]
    m = out["metrics"]
    for name in ("batch_fill", "escalations_per_kq", "compiles_in_window",
                 "gen_late_p99_ms", "fit_s", "idle_share"):
        assert name in m, sorted(m)
    assert "p50_ms" not in m and "served_p99_ms" in m
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(out["breakdown"]["device_ops"]) <= 10


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_refuses_without_a_tpu():
    cell = tiny.cells()[0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tiny.ROOT, env=_child_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    cell = tiny.cells()[0]["name"]
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_child_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


STORE_METRICS = [
    {"name": "cache_hit_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "store cache", "moves": "p50_ms"},
    {"name": "assemble_ms_per_batch", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "store select and assemble",
     "moves": "p50_ms"},
    {"name": "build_s", "unit": "s", "better": "lower",
     "source": "host_clock", "layer": "segment build", "moves": "setup_s"}]


@pytest.mark.parametrize("config,traffic,trace,e2e,layer", [
    ("store10m_d3", "store_cold_open", True, [], STORE_METRICS),
], ids=["store-cold-open"])
def test_kept_cell_rehearsal(config, traffic, trace, e2e, layer, tmp_path):
    """The cell PERF.md keeps under Open questions (the out-of-core store
    cell) runs through the same path, with its own metrics, once a
    BENCHMARK.json names it."""
    import run
    bench = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
    cell = {"name": "kept-cell", "config": config, "traffic": traffic,
            "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["end_to_end"] += [dict(m, workloads=["kept-cell"]) for m in e2e]
    bench["per_layer"] += [dict(m, workloads=["kept-cell"]) for m in layer]
    root = tmp_path / "root"
    root.mkdir()
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    os.symlink(tiny.BENCH, root / "bench")
    os.symlink(os.path.join(tiny.ROOT, "src"), root / "src")
    out = tiny.run_tiny(cell, tmp_path / "work", trace=trace,
                        root=str(root))
    assert out["correct"] is True, out["_log"]
    want = {m["name"] for m in run.cell_metrics(bench, cell, trace)}
    assert set(out["metrics"]) <= want
    for m in e2e + layer:
        assert m["name"] in out["metrics"], sorted(out["metrics"])
        assert out["metrics"][m["name"]]["value"] >= 0


def test_sweep_rehearsal(capsys):
    """``sweep.py`` offers each rate to a fresh server over one set-up and
    prints one line per rate, then the knee."""
    import sweep
    cell = next(c for c in tiny.cells() if c["traffic"].endswith("open")
                and "store" not in c["config"])
    with tiny.jax_cache_settings():
        points = sweep.sweep(cell["name"], tiny.SEED, 1.0, [10.0, 20.0],
                             require_chip=False,
                             config_patch=tiny.config_patch(cell["config"]),
                             mix_patch=tiny.MIX_PATCH)
    assert [p["rate_qps"] for p in points] == [10.0, 20.0]
    assert all(p["sent"] > 0 and p["p50_ms"] <= p["p99_ms"] for p in points)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == {"knee_qps", "rate_qps_at_0.8"}
