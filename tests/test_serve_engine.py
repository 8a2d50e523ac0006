"""TPU-vectorized serving engine vs brute force (+ distributed shard_map)."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Database, EngineConfig
from repro.core.index import IndexConfig, LMSFCIndex
from repro.core.query import brute_force_count
from repro.core.serve import (build_serving_arrays, make_distributed_query_fn,
                              make_query_fn, shard_serving_arrays)
from repro.core.theta import default_K, random_theta
from repro.data.synth import make_dataset
from repro.dist.compat import make_mesh
from repro.data.workload import make_workload


def _setup(name="osm", n=3000, n_q=32, seed=0, paging="heuristic"):
    data = make_dataset(name, n, seed=seed)
    d = data.shape[1]
    K = default_K(d)
    rng = np.random.default_rng(seed)
    theta = random_theta(rng, d, K)
    Ls, Us = make_workload(data, n_q, seed=seed, K=K)
    cfg = IndexConfig(paging=paging, page_bytes=2048)
    idx = LMSFCIndex.build(data, theta=theta, cfg=cfg, workload=(Ls, Us), K=K)
    queries = np.stack([Ls, Us], axis=-1).astype(np.uint64)
    q_i32 = jnp.asarray(queries.astype(np.uint32).view(np.int32))
    want = np.asarray([brute_force_count(data, l, u) for l, u in zip(Ls, Us)])
    return data, idx, theta, q_i32, want, (Ls, Us)


@pytest.mark.parametrize("name", ["osm", "nyc", "stock"])
def test_vectorized_engine_exact(name):
    data, idx, theta, q, want, wl = _setup(name)
    arrays = build_serving_arrays(idx)
    qfn = make_query_fn(theta, k_maxsplit=4, max_cand=max(64, idx.num_pages),
                        q_chunk=8)
    counts, overflow = jax.jit(qfn)(arrays, q)
    assert np.asarray(overflow).dtype == np.int32  # counts, not bools
    assert not np.any(np.asarray(overflow))
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_overflow_flag_when_cand_bound_too_small():
    data, idx, theta, q, want, wl = _setup("osm", n=5000, n_q=16)
    arrays = build_serving_arrays(idx)
    qfn = make_query_fn(theta, max_cand=1, q_chunk=8)
    counts, overflow = jax.jit(qfn)(arrays, q)
    got = np.asarray(counts)
    assert np.asarray(overflow).dtype == np.int32
    over = np.asarray(overflow) > 0
    # exact wherever not overflowed; flagged wherever undercounted
    assert np.all(got[~over] == want[~over])
    assert np.all(got[over] <= want[over])


def test_distributed_engine_single_device_mesh():
    data, idx, theta, q, want, wl = _setup("nyc")
    mesh = make_mesh((1, 1), ("data", "model"))
    arrays = build_serving_arrays(idx, pad_pages_to=1)
    arrays = shard_serving_arrays(arrays, mesh)
    fn, _ = make_distributed_query_fn(theta, mesh,
                                      max_cand=max(64, idx.num_pages), q_chunk=8)
    counts, over = fn(arrays, q)
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_facade_routes_same_engine_exactly():
    """The repro.api facade over the same index matches the hand-wired
    core engines (xla and distributed), unified under QueryResult."""
    data, idx, theta, q, want, (Ls, Us) = _setup("osm")
    db = Database(idx)
    db.engine("xla", EngineConfig(max_cand=max(64, idx.num_pages), q_chunk=8))
    res = db.query((Ls, Us))
    assert res.exact and not res.overflowed.any()
    np.testing.assert_array_equal(res.counts, want)
    db.engine("distributed",
              EngineConfig(max_cand=max(64, idx.num_pages), q_chunk=8))
    res = db.query((Ls, Us))
    assert res.exact
    np.testing.assert_array_equal(res.counts, want)


def test_distributed_engine_8_devices(cpu_child):
    """Page-sharded serving on a 4x2 fake-device mesh: exact counts + psum."""
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core.index import IndexConfig, LMSFCIndex
        from repro.core.query import brute_force_count
        from repro.core.serve import (build_serving_arrays,
                                      make_distributed_query_fn,
                                      shard_serving_arrays)
        from repro.core.theta import default_K, random_theta
        from repro.data.synth import make_dataset
        from repro.data.workload import make_workload
        from repro.dist.compat import make_mesh

        assert jax.device_count() == 8
        data = make_dataset("osm", 4000, seed=1)
        K = default_K(2)
        theta = random_theta(np.random.default_rng(1), 2, K)
        Ls, Us = make_workload(data, 24, seed=1, K=K)
        idx = LMSFCIndex.build(data, theta=theta,
                               cfg=IndexConfig(paging="heuristic",
                                               page_bytes=2048),
                               workload=(Ls, Us), K=K)
        mesh = make_mesh((4, 2), ("data", "model"))
        arrays = shard_serving_arrays(
            build_serving_arrays(idx, pad_pages_to=8), mesh)
        fn, _ = make_distributed_query_fn(theta, mesh,
                                          max_cand=idx.num_pages, q_chunk=8)
        q = jnp.asarray(np.stack([Ls, Us], -1).astype(np.uint32).view(np.int32))
        counts, over = fn(arrays, q)
        want = np.asarray([brute_force_count(data, l, u)
                           for l, u in zip(Ls, Us)])
        np.testing.assert_array_equal(np.asarray(counts), want)
        print("OK-8DEV")
    """)
    r = cpu_child(code)
    assert "OK-8DEV" in r.stdout, r.stderr[-3000:]


def test_moe_shardmap_matches_global_dispatch(cpu_child):
    """Fully-manual shard_map MoE == global-dispatch MoE (8 fake devices).
    Capacity semantics differ (per-shard), so use capacity ample enough
    that nothing is dropped in either variant."""
    code = textwrap.dedent("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_arch, reduced_config
        from repro.dist.compat import make_mesh
        from repro.dist.sharding import ShardingRules
        from repro.models.moe import init_moe, moe_ffn, moe_ffn_shardmap

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = ShardingRules(model_size=2, data_size=4, fsdp=True)
        cfg = dataclasses.replace(
            reduced_config(get_arch("granite-moe-3b-a800m")),
            moe_d_ff=128, moe_token_shards=4)
        p, spec = init_moe(jax.random.PRNGKey(0), cfg, rules)
        p = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), p, spec)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model),
                              jnp.bfloat16) * 0.3
        x = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))

        y0, d0 = jax.jit(lambda p, x: moe_ffn(p, cfg, x, capacity_factor=8.0))(p, x)
        y1, d1 = jax.jit(lambda p, x: moe_ffn_shardmap(
            p, cfg, x, mesh, rules, capacity_factor=8.0))(p, x)
        assert float(d0) == 0.0 and float(d1) == 0.0
        np.testing.assert_allclose(np.asarray(y0, np.float32),
                                   np.asarray(y1, np.float32),
                                   atol=3e-2, rtol=3e-2)
        print("OK-MOE-SHARDMAP")
    """)
    r = cpu_child(code)
    assert "OK-MOE-SHARDMAP" in r.stdout, r.stderr[-3000:]


@pytest.mark.parametrize("kind", ["count", "range"])
def test_query_programs_keep_the_module_prefix(kind):
    """Each kind and ladder rung compiles to its own module name, under
    the prefix the benchmark's trace reduction sums as query-program
    time (``bench/trace_reduce.QUERY_PROGRAMS``)."""
    import os
    import sys
    from repro.core.serve import pack_query_rects
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from trace_reduce import QUERY_PROGRAMS
    data = make_dataset("osm", 1000, seed=0)
    db = Database.fit(data, K=16, learn=False,
                      cfg=IndexConfig(paging="heuristic", page_bytes=1024))
    _, eng = db._get_engine("xla")
    eng.sync()
    q = jnp.asarray(pack_query_rects(data[:16], data[:16]))
    fn = eng._build_qfn(32) if kind == "count" else eng._build_rfn(32, 64)
    text = fn.lower(eng._arrays, q).as_text()
    module = text.split("module @", 1)[1].split(" ", 1)[0]
    want = ("query_batch_count_c32" if kind == "count"
            else "query_batch_range_c32_h64")
    assert module == "jit_" + want
    assert module.startswith(QUERY_PROGRAMS[0])
