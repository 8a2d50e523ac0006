"""CPU rehearsal of ``chip_smoke.py``: every phase at ~20,000 rows with the
Pallas kernels in interpret mode, under the same assertions the chip run
makes (device engine served every query, no CPU routing or net, answers
bit-identical to the CPU engine); plus the script's refusal to run
without a TPU, and where the compile cache goes."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.api import EngineConfig

REPO_ROOT = Path(__file__).resolve().parents[1]

ROWS = 20_000
CFG = EngineConfig(interpret=True)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fitted(smoke):
    """(db, data) after the fit phase, with the pallas engine attached."""
    db, data, rep = smoke.phase_fit(ROWS, seed=0)
    assert rep["pooled_jax_dispatches"] > 0 and rep["rows"] == db.n
    assert rep["pages"] == db.num_pages and rep["cap"] > 0
    db.engine("pallas", CFG)
    return db, data


def test_memory_phase(smoke, fitted):
    db, data = fitted
    rep = smoke.phase_memory(db, data, CFG, "cpu", seed=0)
    assert rep["match"] and set(rep["pallas"]) == {"count", "range",
                                                   "point", "knn"}
    assert set(rep["xla"]) == {"count"}


def test_serve_phase(smoke, fitted):
    db, data = fitted
    rep = smoke.phase_serve(db, data, seed=0, n_serve=64)
    assert rep["submissions"] == 128 and rep["match"]
    assert rep["failed"] == rep["shed"] == rep["retries"] == 0


def test_store_phase(smoke, tmp_path):
    rep = smoke.phase_store(ROWS, 0, CFG, "cpu", str(tmp_path), chunk=5000,
                            group_pages=2)
    assert rep["match"] and rep["evictions"] > 0
    assert rep["cache_bytes"] < rep["footprint_bytes"]
    assert not (tmp_path / "segment").exists()       # cleaned up


def test_distributed_phase(smoke):
    rep = smoke.phase_distributed(ROWS, 0, CFG, jax.device_count())
    assert rep["match"] and rep["shard_devices"] == jax.device_count()
    assert set(rep["distributed"]) == {"count", "point"}


def test_device_check_rejects_cpu_routing(smoke, fitted):
    """A result the planner routed to the CPU engine fails the check."""
    from repro.api import Count
    db, data = fitted
    res = db.query(Count(data[:4], data[:4]), engine="cpu")
    with pytest.raises(AssertionError, match="served by 'cpu'"):
        smoke.check_device_result(res, "pallas")


def test_script_refuses_without_tpu(cpu_child):
    r = cpu_child("import runpy, sys; sys.argv = ['chip_smoke.py', "
                  "'--rows', '1000']; runpy.run_path('chip_smoke.py', "
                  "run_name='__main__')", devices=1)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout and "no TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    """Copied without the rest of the repo, the script cannot run."""
    shutil.copy(REPO_ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rows", "1000"],
                       cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"ok": true' not in r.stdout


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_directory(cpu_child, tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands
    and compiles land there; without it, the fixed in-checkout directory."""
    unset = "" if placed else "os.environ.pop('JAX_COMPILATION_CACHE_DIR')"
    code = textwrap.dedent(f"""
        import os
        {unset}
        import jax, jax.numpy as jnp
        from repro.compile_cache import CACHE_DIR, use_compile_cache
        got = use_compile_cache()
        want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
        assert got == want == jax.config.jax_compilation_cache_dir, got
        if {placed}:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
            assert os.listdir(want), "no cache entry written"
        print("OK-CACHE")
    """)
    r = cpu_child(code, devices=1,
                  env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert "OK-CACHE" in r.stdout, r.stderr[-2000:]
