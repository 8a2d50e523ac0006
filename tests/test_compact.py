"""The scatter-free compaction of the count and range programs.

`_compact` against `np.nonzero`, and the count / range programs built on
it against a copy of the scatter formulation they replaced, rung by rung
up the escalation ladder: counts, ids, hit counts and both overflow flags
stay bit-identical.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.curve import as_curve
from repro.core.index import IndexConfig, LMSFCIndex
from repro.core.serve import (_compact, _u32_le, build_serving_arrays,
                              make_query_fn, make_range_fn)
from repro.core.split import recursive_split_jax, zranges_jax
from repro.core.theta import default_K, random_theta
from repro.core.zorder64 import z64_le
from repro.data.synth import make_dataset
from repro.data.workload import make_workload
from repro.kernels.window_filter.ops import window_filter, window_match


def _rows(N, width, fill):
    rng = np.random.default_rng(N * 131 + width)
    return fill(rng, N, width)


# (N, width, mask rows) — each row its own edge
CASES = {
    "empty": (300, 16, lambda r, N, w: np.zeros((3, N), bool)),
    "all_true": (300, 16, lambda r, N, w: np.ones((3, N), bool)),
    "exactly_width": (1000, 64, lambda r, N, w: np.stack(
        [np.isin(np.arange(N), r.choice(N, w, replace=False))
         for _ in range(3)])),
    "width_plus_one": (1000, 64, lambda r, N, w: np.stack(
        [np.isin(np.arange(N), r.choice(N, w + 1, replace=False))
         for _ in range(3)])),
    "n_below_128": (37, 8, lambda r, N, w: r.random((4, N)) < 0.4),
    "n_not_lane_multiple": (10_752 - 5, 64, lambda r, N, w:
                            r.random((4, N)) < 0.01),
    "width_above_n": (100, 300, lambda r, N, w: r.random((4, N)) < 0.6),
    "three_levels": (20_000, 512, lambda r, N, w: r.random((2, N)) < 0.02),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compact_matches_nonzero(case):
    N, width, fill = CASES[case]
    mask = _rows(N, width, fill)
    pos, n = jax.jit(_compact, static_argnums=1)(jnp.asarray(mask), width)
    want = np.full((mask.shape[0], width), -1, np.int32)
    for r, row in enumerate(mask):
        nz = np.nonzero(row)[0][:width]
        want[r, :len(nz)] = nz
    assert pos.dtype == jnp.int32 and n.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(pos), want)
    np.testing.assert_array_equal(np.asarray(n), mask.sum(axis=1))


# ---------------------------------------------------------------------------
# the programs as they were, with their scatter compactions (reference)
# ---------------------------------------------------------------------------


def _prune(arrays, queries, curve, k_maxsplit):
    rects, valid = recursive_split_jax(queries.astype(jnp.uint32), curve,
                                       k_maxsplit)
    zlo, zhi = zranges_jax(rects, curve)
    ov = (z64_le(zlo[:, :, None, :], arrays.page_zmax[None, None]) &
          z64_le(arrays.page_zmin[None, None], zhi[:, :, None, :]))
    ov = jnp.any(ov & valid[:, :, None], axis=1)
    qlo = queries[:, None, :, 0]
    qhi = queries[:, None, :, 1]
    mlo = arrays.page_mbr[None, :, :, 0]
    mhi = arrays.page_mbr[None, :, :, 1]
    intersect = jnp.all(_u32_le(mlo, qhi) & _u32_le(qlo, mhi), -1)
    contained = jnp.all(_u32_le(qlo, mlo) & _u32_le(mhi, qhi), -1)
    return ov & intersect, contained


def _scatter_cand(sel, max_cand):
    Qc, Pn = sel.shape
    pos = jnp.cumsum(sel, axis=1) - 1
    n_cand = pos[:, -1] + 1
    cand = jnp.zeros((Qc, max_cand), jnp.int32)
    qidx = jnp.broadcast_to(jnp.arange(Qc)[:, None], sel.shape)
    pidx = jnp.broadcast_to(jnp.arange(Pn)[None, :], sel.shape)
    okpos = sel & (pos < max_cand)
    cand = cand.at[jnp.where(okpos, qidx, Qc), jnp.where(okpos, pos, 0)
                   ].set(pidx, mode="drop")
    cand_valid = (jnp.arange(max_cand)[None, :]
                  < jnp.minimum(n_cand, max_cand)[:, None])
    return cand, cand_valid, n_cand


def _scatter_query_fn(curve, *, k_maxsplit, max_cand, q_chunk, backend,
                      interpret):
    curve = as_curve(curve)

    def _chunk(arrays, queries):
        Qc = queries.shape[0]
        live, contained = _prune(arrays, queries, curve, k_maxsplit)
        full = live & contained
        partial = live & ~contained
        base = jnp.sum(jnp.where(full, arrays.page_size[None, :], 0), axis=1)
        cand, cand_valid, n_cand = _scatter_cand(partial, max_cand)
        pts = arrays.points[cand]
        size = jnp.where(cand_valid, arrays.page_size[cand], 0)
        d, cap = pts.shape[2], pts.shape[3]
        rect = jnp.broadcast_to(queries[:, None], (Qc, max_cand, d, 2))
        cnt = window_filter(pts.reshape(-1, d, cap), rect.reshape(-1, d, 2),
                            size.reshape(-1), backend=backend,
                            interpret=interpret)
        return (base + jnp.sum(cnt.reshape(Qc, max_cand), axis=1),
                n_cand > max_cand)

    def query_batch(arrays, queries):
        Q = queries.shape[0]
        qs = queries.reshape(Q // q_chunk, q_chunk, *queries.shape[1:])
        counts, over = jax.lax.map(functools.partial(_chunk, arrays), qs)
        return counts.reshape(Q), over.reshape(Q).astype(jnp.int32)
    return query_batch


def _scatter_range_fn(curve, *, k_maxsplit, max_cand, max_hits, q_chunk,
                      backend, interpret):
    curve = as_curve(curve)

    def _chunk(arrays, queries):
        Qc = queries.shape[0]
        live, _ = _prune(arrays, queries, curve, k_maxsplit)
        cand, cand_valid, n_cand = _scatter_cand(live, max_cand)
        pts = arrays.points[cand]
        size = jnp.where(cand_valid, arrays.page_size[cand], 0)
        d, cap = pts.shape[2], pts.shape[3]
        rect = jnp.broadcast_to(queries[:, None], (Qc, max_cand, d, 2))
        mask = window_match(pts.reshape(-1, d, cap), rect.reshape(-1, d, 2),
                            size.reshape(-1), backend=backend,
                            interpret=interpret)
        mask = mask.reshape(Qc, max_cand * cap)
        gid = (cand[:, :, None] * cap
               + jnp.arange(cap, dtype=jnp.int32)[None, None, :])
        gid = gid.reshape(Qc, max_cand * cap)
        hpos = jnp.cumsum(mask, axis=1) - 1
        n_hits = (hpos[:, -1] + 1).astype(jnp.int32)
        out = jnp.full((Qc, max_hits), -1, jnp.int32)
        hq = jnp.broadcast_to(jnp.arange(Qc)[:, None], mask.shape)
        okh = mask & (hpos < max_hits)
        out = out.at[jnp.where(okh, hq, Qc), jnp.where(okh, hpos, 0)
                     ].set(gid, mode="drop")
        return (out, n_hits, (n_cand > max_cand).astype(jnp.int32),
                (n_hits > max_hits).astype(jnp.int32))

    def query_batch(arrays, queries):
        Q = queries.shape[0]
        qs = queries.reshape(Q // q_chunk, q_chunk, *queries.shape[1:])
        ids, n_hits, co, ho = jax.lax.map(
            functools.partial(_chunk, arrays), qs)
        return (ids.reshape(Q, -1), n_hits.reshape(Q), co.reshape(Q),
                ho.reshape(Q))
    return query_batch


# ---------------------------------------------------------------------------
# program parity, rung by rung
# ---------------------------------------------------------------------------

# rung i: max_cand 2**i, max_hits 4**i; the last is overflow-free for the
# index below (asserted)
RUNGS = range(6)


@pytest.fixture(scope="module")
def small_index():
    data = make_dataset("osm", 1500, seed=3)
    K = default_K(2)
    theta = random_theta(np.random.default_rng(3), 2, K)
    Ls, Us = make_workload(data, 16, seed=3, K=K)
    idx = LMSFCIndex.build(data, theta=theta,
                           cfg=IndexConfig(paging="heuristic",
                                           page_bytes=800),
                           workload=(Ls, Us), K=K)
    q = jnp.asarray(np.stack([Ls, Us], -1).astype(np.uint32).view(np.int32))
    return theta, build_serving_arrays(idx), q


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_programs_match_scatter_formulation(small_index, backend, rung):
    theta, arrays, q = small_index
    kw = dict(k_maxsplit=2, max_cand=2 ** rung, q_chunk=8, backend=backend,
              interpret=backend == "pallas")
    got = jax.jit(make_query_fn(theta, **kw))(arrays, q)
    want = jax.jit(_scatter_query_fn(theta, **kw))(arrays, q)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    got = jax.jit(make_range_fn(theta, max_hits=4 ** rung, **kw))(arrays, q)
    want = jax.jit(_scatter_range_fn(theta, max_hits=4 ** rung, **kw))(
        arrays, q)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    ids, n_hits, cand_over, hit_over = map(np.asarray, got)
    if rung == 0:
        assert cand_over.any() and hit_over.any()
    if rung == RUNGS[-1]:
        assert not cand_over.any() and not hit_over.any()
        assert (ids >= 0).sum() == n_hits.sum() > 0
