"""Shared by the benchmark's tests: the cells of ``BENCHMARK.json`` shrunk
to a size a CPU test run holds (Pallas in interpret mode), and a guard
that puts JAX's cache settings back after a run changed them."""
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

SEED = 2**31 + 977          # larger than 32 signed bits hold


def config_patch(cfg_name: str, **engine) -> dict:
    patch = {"rows": 20000,
             "engine_config": {"interpret": True, "pad_pages_to": 8,
                               "cap": None, **engine},
             "fit": {"rows": 5000,
                     "smbo": {"evals_per_iter": 2, "max_iters": 1,
                              "n_init": 3}}}
    cfg = json.load(open(os.path.join(BENCH, "configs", cfg_name + ".json")))
    if cfg["system"] == "store":
        patch["store"] = {"chunk_rows": 5000, "group_pages": 4}
    return patch


MIX_PATCH = {"warmup": {"seconds": 0.5}, "rate_qps": 25}


def cells() -> list:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return bench["workloads"]


@contextlib.contextmanager
def jax_cache_settings():
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env


def run_tiny(cell: dict, tmp_path, trace=False, seconds=2.0, engine=None,
             mix=None, **kw) -> dict:
    import io
    import run
    log = io.StringIO()
    os.makedirs(tmp_path, exist_ok=True)
    with jax_cache_settings():
        out = run.run_cell(cell["name"], SEED, seconds, trace,
                           require_chip=False,
                           config_patch=config_patch(cell["config"],
                                                     **(engine or {})),
                           mix_patch={**MIX_PATCH, **(mix or {})},
                           workdir=str(tmp_path),
                           log=log, **kw)
    out["_log"] = log.getvalue()
    return out
