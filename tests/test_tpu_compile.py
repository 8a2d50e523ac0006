"""The main path's kernels and jitted programs compile for a TPU v5e.

Nothing runs: each test lowers a program for one chip of a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what interpret mode accepts — block shapes off
the (8, 128) tiling, scalar reads from vector memory, programs too big
for the chip.  Widths are those ``chip_smoke.py`` runs at 10M rows: the
in-memory OSM index (d=2, cap 1024) and the out-of-core segment (d=3,
cap 256, 64-page groups).

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.api.result import EngineConfig
from repro.core.batcheval import _pack_index_pool, _pool_program
from repro.core.curve import default_curve, pack_curve_pool, random_curve
from repro.core.index import IndexConfig, LMSFCIndex
from repro.core.serve import (ServingArrays, make_distributed_query_fn,
                              make_query_fn, make_range_fn)
from repro.core.theta import default_K
from repro.data.synth import make_osm
from repro.data.workload import make_workload
from repro.dist.compat import make_mesh
from repro.kernels.sfc_encode.kernel import sfc_encode_dn, sfc_encode_pool_dn
from repro.kernels.window_filter.kernel import (window_filter_pallas,
                                                window_match_pallas)

CFG = EngineConfig()
G = CFG.q_chunk * CFG.max_cand        # (query, candidate page) pairs/chunk
# (d, cap, pages the query fns see): the in-memory index at 10M OSM rows
# (11,897 pages under z-order on the chip), and the store engine
# assembling every group of a 10M-row segment (611 groups -> the
# 1024-block bucket of 64 pages)
WIDTHS = {"memory": (2, 1024, 11_897), "store": (3, 256, 1024 * 64)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache entirely
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the program"


@pytest.mark.parametrize("where", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", [window_filter_pallas,
                                    window_match_pallas],
                         ids=["window_filter", "window_match"])
def test_window_kernels_compile(one_chip, kernel, where):
    d, cap, _ = WIDTHS[where]
    compiled = kernel.lower(_spec(one_chip, (G, d, cap)),
                            _spec(one_chip, (G, d, 2)),
                            _spec(one_chip, (G,))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("family", ["global", "piecewise"])
def test_sfc_encode_compiles(one_chip, family):
    d = 3
    curve = default_curve(d, default_K(d), family=family)
    compiled = sfc_encode_dn.lower(_spec(one_chip, (d, 8 * 2048)),
                                   curve=curve).compile()
    _assert_kernel(compiled)


def test_sfc_encode_pool_compiles(one_chip):
    """The candidate-batched encode over a mixed global/piecewise pool
    (the region table rides in SMEM, read by program id)."""
    d, K = 2, default_K(2)
    curves = [random_curve(np.random.default_rng(i), d, K) for i in range(4)]
    curves += [random_curve(np.random.default_rng(9 + i), d, K,
                            family="piecewise", depth=2) for i in range(4)]
    pool = pack_curve_pool(curves)
    compiled = sfc_encode_pool_dn.lower(
        _spec(one_chip, (d, 8 * 2048)), _spec(one_chip, pool.pos.shape),
        _spec(one_chip, pool.reg.shape)).compile()
    _assert_kernel(compiled)


def _serving_specs(one_chip, d, cap, pages):
    return ServingArrays(points=_spec(one_chip, (pages, d, cap)),
                         page_zmin=_spec(one_chip, (pages, 2)),
                         page_zmax=_spec(one_chip, (pages, 2)),
                         page_mbr=_spec(one_chip, (pages, d, 2)),
                         page_size=_spec(one_chip, (pages,)))


@pytest.mark.parametrize("where", sorted(WIDTHS))
@pytest.mark.parametrize("make", [make_query_fn, make_range_fn],
                         ids=["query_fn", "range_fn"])
def test_serving_programs_compile(one_chip, make, where):
    """The engines' jitted count / range programs with the Pallas window
    kernels, at the smoke's page counts and a 64-query bucket."""
    d, cap, pages = WIDTHS[where]
    fn = make(default_curve(d, default_K(d)), k_maxsplit=CFG.k_maxsplit,
              max_cand=CFG.max_cand, q_chunk=CFG.q_chunk, backend="pallas")
    compiled = jax.jit(fn).lower(_serving_specs(one_chip, d, cap, pages),
                                 _spec(one_chip, (64, d, 2))).compile()
    _assert_kernel(compiled)


def test_distributed_program_compiles(topo):
    """The distributed engine's count program: pages sharded over the four
    chips of the host, queries replicated, counts psum-reduced."""
    d, cap, pages = WIDTHS["memory"]
    mesh = make_mesh((len(topo.devices),), ("pages",), devices=topo.devices)
    fn, specs = make_distributed_query_fn(
        default_curve(d, default_K(d)), mesh, k_maxsplit=CFG.k_maxsplit,
        max_cand=CFG.max_cand, q_chunk=CFG.q_chunk)
    pages = -(-pages // 4) * 4
    shapes = _serving_specs(None, d, cap, pages)
    arrays = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, specs)
    q = jax.ShapeDtypeStruct((64, d, 2), jnp.int32,
                             sharding=NamedSharding(mesh, PartitionSpec()))
    text = jax.jit(fn).lower(arrays, q).compile().as_text()
    assert "all-reduce" in text


def test_smbo_pool_program_compiles(one_chip):
    """SMBO's pooled evaluator (one jitted program per candidate round) at
    `Database.fit`'s sizes: a 3000-row sample, 200 training queries and a
    4-candidate round padded to the pool bucket."""
    d, K = 2, default_K(2)
    data = make_osm(3000, seed=0)
    Ls, Us = make_workload(data, 200, seed=0, K=K)
    cfg = IndexConfig(paging="heuristic")
    idxs = [LMSFCIndex.build(data, curve=random_curve(
        np.random.default_rng(i), d, K), cfg=cfg, workload=(Ls, Us))
        for i in range(4)]
    stacked = tuple(_spec(one_chip, a.shape, a.dtype)
                    for a in _pack_index_pool(idxs))
    q = _spec(one_chip, Ls.shape)
    compiled = _pool_program.lower(d, cfg.k_maxsplit, q, q, stacked).compile()
    assert compiled.as_text()
