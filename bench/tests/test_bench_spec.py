"""BENCHMARK.json keeps to the benchmark's naming rules, and a new
configuration, traffic mix and metric are found by name alone."""
import json
import os
import re
import shutil
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))


def _names():
    out = []
    for c in BENCH["configs"]:
        out.append(("config", c["name"]))
        out += [("reduced", k) for k in c["reduced"]]
    for w in BENCH["workloads"]:
        out += [("workload", w["name"]), ("config", w["config"]),
                ("traffic", w["traffic"])]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        out.append(("metric", m["name"]))
    return out


@pytest.mark.parametrize("what,name", _names(),
                         ids=lambda v: v if isinstance(v, str) else None)
def test_name_characters(what, name):
    assert NAME.match(name), (what, name)


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        moves = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # every cell that reports this metric reports what it moves
        assert set(metric["workloads"]) <= set(moves.get("workloads",
                                                         cells))
        assert os.path.exists(os.path.join(tiny.BENCH, "metrics",
                                           metric["name"] + ".py"))
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_every_cell_names_existing_files():
    for w in BENCH["workloads"]:
        for sub, key in (("configs", "config"), ("traffic", "traffic")):
            assert os.path.exists(os.path.join(tiny.BENCH, sub,
                                               w[key] + ".json")), w
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert BENCH["end_to_end"][0]["name"] == "setup_s"


def test_new_files_are_found_by_name(tmp_path):
    """A new cell needs a config file, a mix file, a metric reader and
    entries in BENCHMARK.json: nothing in the harness changes."""
    import run
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(tmp_path / "bench/configs/osm10m_mem.json"))
    cfg.update(name="osm_small_new", rows=1000)
    json.dump(cfg, open(tmp_path / "bench/configs/osm_small_new.json", "w"))
    mix = json.load(open(tmp_path / "bench/traffic/osm_mixed_open.json"))
    mix["rate_qps"] = 7
    json.dump(mix, open(tmp_path / "bench/traffic/new_mix.json", "w"))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return ctx.answer\n")
    bench["workloads"].append({"name": "new-cell", "config": "osm_small_new",
                               "traffic": "new_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "executor", "moves": "p50_ms",
                               "workloads": ["new-cell"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    _, cell, cfg2, mix2 = run.cell_spec(str(tmp_path), "new-cell")
    assert cfg2["rows"] == 1000 and mix2["rate_qps"] == 7
    names = [m["name"] for m in run.cell_metrics(bench, cell, True)]
    assert names == ["new_metric"]
    assert run.load_reader("new_metric", str(tmp_path))(
        types.SimpleNamespace(answer=42)) == 42
    e2e = [m["name"] for m in run.cell_metrics(bench, cell, False)]
    assert e2e == ["setup_s", "p50_ms"]


def test_every_seed_offers_the_same_work():
    """A window's requests and gaps come from the structure seed; the run's
    seed only reorders them, so seeds differ in order, not in load."""
    import numpy as np
    import traffic as tr
    mix = json.load(open(os.path.join(tiny.BENCH, "traffic",
                                      "osm_mixed_open.json")))
    data = np.arange(4000, dtype=np.uint64).reshape(2000, 2)
    gen = tr.Generator(mix, data, 32, structure_seed=7)

    def payload(r):
        arrs = (r.lo, r.hi) if r.lo is not None else (r.xs,)
        return (r.kind, r.client) + tuple(a.tobytes() for a in arrs)

    a = gen.schedule(np.random.SeedSequence(1), 10.0, 60.0)
    b = gen.schedule(np.random.SeedSequence(tiny.SEED), 10.0, 60.0)
    assert len(a) == len(b) == 600
    assert sorted(map(payload, a)) == sorted(map(payload, b))
    assert [payload(r) for r in a] != [payload(r) for r in b]
    gaps = [np.diff([0.0] + [r.t for r in s]) for s in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]), rtol=0, atol=0.3)
    assert all(0 <= r.t < 10.0 for r in a + b)
    again = gen.schedule(np.random.SeedSequence(1), 10.0, 60.0)
    assert [payload(r) for r in again] == [payload(r) for r in a]
    other = gen.schedule(np.random.SeedSequence(1), 10.0, 60.0, stream=1)
    assert sorted(map(payload, other)) != sorted(map(payload, a))
