"""TPU-vectorized distributed window-query serving (DESIGN.md §2).

Prefer the `repro.api.Database` facade over calling this module directly:
it owns the engine lifecycle (serving-array packing + delta refresh),
threads `k_maxsplit`/`max_cand`/`q_chunk`/`backend` through one
`EngineConfig`, and escalates overflowed queries so counts are exact by
construction.  This module remains the execution layer underneath the
"xla", "pallas", and "distributed" engines.

The paper's per-query page walk is re-expressed as a static-shape pipeline:

  split      — recursive query splitting (§6.1), vectorized over (Q, 2^k)
  prune      — page-level candidate mask: z-range overlap with any sub-query
               AND MBR intersection (metadata-only compares; this is where
               RQS' skipping pays off, mirroring the CPU engine)
  contain    — pages whose MBR ⊆ query contribute size() with *no* gather
               (the paper's containment shortcut)
  compact    — top-C candidate page ids per query (static bound), by a
               scatter-free rank-select over 128-lane prefix counts
  gather     — only candidate pages' points (the expensive HBM term)
  filter     — points-in-rectangle count (Pallas window_filter on TPU)

Pages are range-sharded over the flattened device mesh; queries are
replicated; per-device partial counts are psum-reduced.  Exactness: the
sub-rectangles partition the query, so filtering with the *full* query
rectangle counts every point exactly once, and cross-device page shards are
disjoint.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.window_filter.ops import window_filter, window_match
from .curve import as_curve
from .index import LMSFCIndex
from .split import recursive_split_jax, zranges_jax
from .zorder64 import u64_to_z64, z64_le, z64_to_u64

# ---------------------------------------------------------------------------
# serving arrays
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServingArrays:
    """Page-major device arrays.  All leaves shard on axis 0 (pages)."""
    points: Any      # (P, d, cap) int32 — transposed for the filter kernel
    page_zmin: Any   # (P, 2) int32 Z64
    page_zmax: Any   # (P, 2) int32
    page_mbr: Any    # (P, d, 2) int32
    page_size: Any   # (P,) int32


jax.tree_util.register_dataclass(
    ServingArrays,
    data_fields=["points", "page_zmin", "page_zmax", "page_mbr", "page_size"],
    meta_fields=[])


def pack_serving_arrays(index: LMSFCIndex, pad_pages_to: int = 1,
                        cap: int | None = None) -> ServingArrays:
    """Materialize padded page-major **host** (numpy) arrays from a built
    index.  Small-page regimes (large page counts) pack via one bulk flat
    scatter per dimension instead of a Python loop over pages — the loop
    used to dominate engine startup there; with few large pages the
    per-page block copy is pure memcpy and stays the faster path."""
    if pad_pages_to is None or pad_pages_to < 1:
        raise ValueError(f"pad_pages_to must be >= 1 (the page count is "
                         f"rounded up to a multiple of it); got "
                         f"{pad_pages_to!r}")
    Pn = index.num_pages
    d = index.d
    sizes = np.diff(index.starts).astype(np.int64)
    max_size = int(sizes.max())
    cap = cap or max_size
    if cap < max_size:
        raise ValueError(f"cap={cap} < largest page ({max_size} rows); "
                         f"points would be dropped")
    P_pad = -(-Pn // pad_pages_to) * pad_pages_to
    pts = np.zeros((P_pad, d, cap), dtype=np.uint32)
    size = np.zeros(P_pad, dtype=np.int32)
    size[:Pn] = sizes
    if index.n < 128 * Pn:          # measured crossover: ~100 rows/page
        # bulk scatter: row r of page p, dim i lands at
        # pts[p, i, slot] == flat[p*d*cap + i*cap + slot]; destinations
        # are piecewise contiguous, so each per-dim scatter streams
        page_of_row = np.repeat(np.arange(Pn, dtype=np.int64), sizes)
        slot_of_row = (np.arange(index.n, dtype=np.int64)
                       - np.repeat(index.starts[:-1].astype(np.int64), sizes))
        flat = pts.reshape(-1)
        base = page_of_row * (d * cap) + slot_of_row
        xs32 = index.xs.astype(np.uint32)
        for i in range(d):
            flat[base + i * cap] = xs32[:, i]
    else:
        for p in range(Pn):
            s, e = index.starts[p], index.starts[p + 1]
            pts[p, :, :e - s] = index.xs[s:e].astype(np.uint32).T
    mbr = np.zeros((P_pad, d, 2), dtype=np.uint32)
    mbr[:Pn] = index.mbrs.astype(np.uint32)
    # padded pages: impossible MBR (lo > hi) so they never match
    mbr[Pn:, :, 0] = np.uint32(0xFFFFFFFF)
    zmin = np.full((P_pad, 2), np.int32(-1))   # 0xFFFF.. = +inf unsigned
    zmax = np.zeros((P_pad, 2), dtype=np.int32)
    zmin[:Pn] = u64_to_z64(index.page_zmin)
    zmax[:Pn] = u64_to_z64(index.page_zmax)
    return ServingArrays(
        points=pts.view(np.int32),
        page_zmin=zmin,
        page_zmax=zmax,
        page_mbr=mbr.view(np.int32),
        page_size=size,
    )


def build_serving_arrays(index: LMSFCIndex, pad_pages_to: int = 1,
                         cap: int | None = None) -> ServingArrays:
    """Padded page-major device arrays from a built index."""
    host = pack_serving_arrays(index, pad_pages_to=pad_pages_to, cap=cap)
    return jax.tree.map(jnp.asarray, host)


# ---------------------------------------------------------------------------
# shape buckets: the compiled-kernel surface the executor caches against
# ---------------------------------------------------------------------------


def bucket_pow2(n: int, multiple: int = 1) -> int:
    """Smallest ``multiple * 2**j >= max(n, 1)`` — the shape-bucket boundary
    used by the exec layer so varying batch sizes / candidate budgets hit a
    bounded set of compiled kernels instead of recompiling per shape."""
    if multiple < 1:
        raise ValueError(f"multiple must be >= 1; got {multiple}")
    chunks = -(-max(int(n), 1) // multiple)
    return multiple * (1 << (chunks - 1).bit_length())


def pack_query_rects(Ls, Us, Q_pad: int = None) -> np.ndarray:
    """Pack uint64 rect bounds as the (Q_pad, d, 2) int32 host array the
    query fns consume, padded up to `Q_pad` by repeating the last rect (a
    repeated query is exact and cheap; results beyond Q are sliced off).
    This is the bucket-aware twin of the inline padding `make_query_fn`
    callers used to hand-roll; `Q_pad` must be a q_chunk multiple."""
    rect = np.stack([np.asarray(Ls), np.asarray(Us)],
                    axis=-1).astype(np.uint32)            # (Q, d, 2)
    Q = rect.shape[0]
    if Q_pad is not None and Q_pad != Q:
        if Q_pad < Q:
            raise ValueError(f"Q_pad={Q_pad} < batch size {Q}")
        if Q == 0:
            # no rect to repeat; np.repeat would silently return an
            # unpadded (0, d, 2) array, breaking the padding contract —
            # callers must short-circuit empty batches instead
            raise ValueError("cannot pad an empty query batch")
        rect = np.concatenate([rect, np.repeat(rect[-1:], Q_pad - Q, axis=0)])
    return rect.view(np.int32)


# ---------------------------------------------------------------------------
# single-shard batched query engine
# ---------------------------------------------------------------------------

_SIGN = np.int32(-(2**31))


def _named(fn, name: str):
    """`fn` renamed, so ``jax.jit`` names its module ``jit_<name>``: each
    kind and ladder rung of the query programs reads apart in a profile."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _u32_le(a, b):
    return (a ^ _SIGN) <= (b ^ _SIGN)


_LANES = 128
_DENSE = 1 << 28    # most (slot, block) pairs compared at the top level


def _compact(mask, width: int, tags=None):
    """Positions of the first `width` set entries of each row of `mask`
    (R, N) bool, ascending: ``(pos (R, width) int32, -1 past the row's
    count; n (R,) int32 set entries)``.  Given `tags` (R, ceil(N/128))
    int32 in [0, 2**23), one per 128-lane block of a row, also returns
    each slot's block tag (R, width).

    A rank-select over a radix-128 tree of prefix counts, with gathers
    where a scatter would go (the TPU runs a scatter about one update at
    a time, masked updates included).  Each level cuts its rows into
    128-lane blocks and keeps their inclusive cumsums, up to the first
    level with few enough blocks (<= 128, or R * width * blocks <=
    2**28) to compare every slot with all of them.  Slot j then walks
    down: at each level it gathers one 128-lane row and counts the
    entries <= its remaining rank.  Nothing of size (R, width, N) is
    built.  A tag rides in bits 8.. of its level-0 row (a count there is
    at most 128), so it costs no gather of its own."""
    R = mask.shape[0]
    c = mask.astype(jnp.int32)
    levels = []
    while True:
        c = jnp.pad(c, ((0, 0), (0, -c.shape[1] % _LANES)))
        cs = jnp.cumsum(c.reshape(R, -1, _LANES), axis=2)   # (R, B, 128)
        c = cs[:, :, -1]                                    # block counts
        levels.append(cs)
        if c.shape[1] <= _LANES or R * width * c.shape[1] <= _DENSE:
            break
    if tags is not None:
        levels[0] = levels[0] | (tags[:, :, None] << 8)
    top = jnp.cumsum(c, axis=1)
    n = top[:, -1]
    k = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32), (R, width))
    le = top[:, None, :] <= k[:, :, None]
    b = jnp.sum(le, axis=2, dtype=jnp.int32)
    k = k - jnp.max(jnp.where(le, top[:, None, :], 0), axis=2)
    for lvl in reversed(range(len(levels))):
        b = jnp.minimum(b, levels[lvl].shape[1] - 1)  # slots past n: any
        row = jnp.take_along_axis(levels[lvl], b[:, :, None], axis=1)
        if lvl == 0 and tags is not None:
            tag, row = row[:, :, 0] >> 8, row & 0xFF
        le = row <= k[:, :, None]                       # (R, W, 128)
        k = k - jnp.max(jnp.where(le, row, 0), axis=2)
        b = b * _LANES + jnp.sum(le, axis=2, dtype=jnp.int32)
    pos = jnp.where(jnp.arange(width)[None, :] < n[:, None], b, -1)
    return (pos, n) if tags is None else (pos, n, tag)


def make_query_fn(curve, *, k_maxsplit: int = 4, max_cand: int = 64,
                  q_chunk: int = 16, backend: str = "xla",
                  interpret: bool = False):
    """Returns query_batch_count_c<max_cand>(arrays, queries (Q, d, 2)
    int32) -> (counts (Q,), overflowed (Q,) int32 overflow counts — 0/1
    on a single shard, psum-additive across shards in the distributed
    engine).  Static shapes throughout; Q % q_chunk == 0.  `curve` is any
    `MonotonicCurve` (legacy `Theta` values are coerced)."""
    curve = as_curve(curve)

    def _chunk(arrays: ServingArrays, queries):
        Qc = queries.shape[0]
        rects, valid = recursive_split_jax(
            queries.astype(jnp.uint32), curve, k_maxsplit)
        zlo, zhi = zranges_jax(rects, curve)          # (Qc, S, 2)
        # ---- prune: page z-range overlaps any live sub-query ------------
        pz_min = arrays.page_zmin                     # (P, 2)
        pz_max = arrays.page_zmax
        ov = (z64_le(zlo[:, :, None, :], pz_max[None, None]) &
              z64_le(pz_min[None, None], zhi[:, :, None, :]))  # (Qc, S, P)
        ov = jnp.any(ov & valid[:, :, None], axis=1)  # (Qc, P)
        qlo = queries[:, None, :, 0]                  # (Qc, 1, d)
        qhi = queries[:, None, :, 1]
        mlo = arrays.page_mbr[None, :, :, 0]          # (1, P, d)
        mhi = arrays.page_mbr[None, :, :, 1]
        intersect = jnp.all(_u32_le(mlo, qhi) & _u32_le(qlo, mhi), -1)
        contained = jnp.all(_u32_le(qlo, mlo) & _u32_le(mhi, qhi), -1)
        live = ov & intersect                         # (Qc, P)
        full = live & contained
        partial = live & ~contained
        # ---- containment shortcut ---------------------------------------
        base = jnp.sum(jnp.where(full, arrays.page_size[None, :], 0), axis=1)
        # ---- compact: first C partial candidates (rank-select) -----------
        cand, n_cand = _compact(partial, max_cand)    # (Qc, C), -1 past n
        overflow = n_cand > max_cand
        cand_valid = cand >= 0
        cand = jnp.maximum(cand, 0)                   # invalid: page 0
        # ---- gather + filter ---------------------------------------------
        pts = arrays.points[cand]                     # (Qc, C, d, cap)
        size = jnp.where(cand_valid, arrays.page_size[cand], 0)
        d = pts.shape[2]
        cap = pts.shape[3]
        rect = jnp.broadcast_to(queries[:, None], (Qc, max_cand, d, 2))
        cnt = window_filter(pts.reshape(-1, d, cap), rect.reshape(-1, d, 2),
                            size.reshape(-1), backend=backend,
                            interpret=interpret)
        return base + jnp.sum(cnt.reshape(Qc, max_cand), axis=1), overflow

    def query_batch(arrays: ServingArrays, queries):
        Q = queries.shape[0]
        assert Q % q_chunk == 0
        qs = queries.reshape(Q // q_chunk, q_chunk, *queries.shape[1:])
        counts, over = jax.lax.map(functools.partial(_chunk, arrays), qs)
        return counts.reshape(Q), over.reshape(Q).astype(jnp.int32)

    return _named(query_batch, f"query_batch_count_c{max_cand}")


# ---------------------------------------------------------------------------
# range retrieval: gather matching row ids into a static output buffer
# ---------------------------------------------------------------------------


def make_range_fn(curve, *, k_maxsplit: int = 4, max_cand: int = 64,
                  max_hits: int = 1024, q_chunk: int = 16,
                  backend: str = "xla", interpret: bool = False):
    """The retrieval twin of `make_query_fn`: instead of reducing to a
    count, matching rows are compacted device-side into a static per-query
    id buffer (global row id = page * cap + slot, so the host resolves rows
    from its packed copy with one gather).

    Returns query_batch_range_c<max_cand>_h<max_hits>(arrays, queries
    (Q, d, 2) int32) ->
      ids      (Q, max_hits) int32 — matching global row ids, -1 padded
      n_hits   (Q,) int32 — total matches within the candidate-page set
      cand_over (Q,) int32 — candidate pages overflowed max_cand
      hit_over  (Q,) int32 — matches overflowed max_hits (ids truncated)

    Unlike the count path there is no containment shortcut: contained
    pages' rows must be emitted too, so every live page is a candidate.
    Exact iff both overflow flags are 0 (the Database planner escalates
    the rest).  Assumes pages*cap < 2^31 (ids are int32); raises past
    2^23 pages (the compaction tags hits with 23-bit page ids).
    """
    curve = as_curve(curve)

    def _chunk(arrays: ServingArrays, queries):
        Qc = queries.shape[0]
        if arrays.page_size.shape[0] > 1 << 23:
            raise ValueError(f"{arrays.page_size.shape[0]} pages; range "
                             f"retrieval takes at most 2**23")
        rects, valid = recursive_split_jax(
            queries.astype(jnp.uint32), curve, k_maxsplit)
        zlo, zhi = zranges_jax(rects, curve)          # (Qc, S, 2)
        pz_min = arrays.page_zmin                     # (P, 2)
        pz_max = arrays.page_zmax
        ov = (z64_le(zlo[:, :, None, :], pz_max[None, None]) &
              z64_le(pz_min[None, None], zhi[:, :, None, :]))  # (Qc, S, P)
        ov = jnp.any(ov & valid[:, :, None], axis=1)  # (Qc, P)
        qlo = queries[:, None, :, 0]                  # (Qc, 1, d)
        qhi = queries[:, None, :, 1]
        mlo = arrays.page_mbr[None, :, :, 0]          # (1, P, d)
        mhi = arrays.page_mbr[None, :, :, 1]
        intersect = jnp.all(_u32_le(mlo, qhi) & _u32_le(qlo, mhi), -1)
        live = ov & intersect                         # (Qc, P)
        # ---- compact: first C candidate pages (rank-select) --------------
        cand, n_cand = _compact(live, max_cand)       # (Qc, C), -1 past n
        cand_over = n_cand > max_cand
        cand_valid = cand >= 0
        cand = jnp.maximum(cand, 0)                   # invalid: page 0
        # ---- gather + match (index-emitting window filter) ---------------
        pts = arrays.points[cand]                     # (Qc, C, d, cap)
        size = jnp.where(cand_valid, arrays.page_size[cand], 0)
        d = pts.shape[2]
        cap = pts.shape[3]
        rect = jnp.broadcast_to(queries[:, None], (Qc, max_cand, d, 2))
        mask = window_match(pts.reshape(-1, d, cap), rect.reshape(-1, d, 2),
                            size.reshape(-1), backend=backend,
                            interpret=interpret)      # (Qc*C, cap) bool
        # ---- compact matches into the static id buffer (rank-select) -----
        # pages padded to whole 128-lane blocks, each tagged with its page
        capp = -(-cap // _LANES) * _LANES
        mask = jnp.pad(mask.reshape(Qc, max_cand, cap),
                       ((0, 0), (0, 0), (0, capp - cap)))
        hpos, n_hits, page = _compact(
            mask.reshape(Qc, max_cand * capp), max_hits,
            tags=jnp.repeat(cand, capp // _LANES, axis=1))
        hit_over = n_hits > max_hits
        out = jnp.where(hpos >= 0, page * cap + hpos % capp, -1)
        return (out, n_hits, cand_over.astype(jnp.int32),
                hit_over.astype(jnp.int32))

    def query_batch(arrays: ServingArrays, queries):
        Q = queries.shape[0]
        assert Q % q_chunk == 0
        qs = queries.reshape(Q // q_chunk, q_chunk, *queries.shape[1:])
        ids, n_hits, co, ho = jax.lax.map(
            functools.partial(_chunk, arrays), qs)
        return (ids.reshape(Q, -1), n_hits.reshape(Q),
                co.reshape(Q), ho.reshape(Q))

    return _named(query_batch,
                  f"query_batch_range_c{max_cand}_h{max_hits}")


# ---------------------------------------------------------------------------
# kNN seeding: page-ring expansion around each center's curve address,
# vectorized over centers (host-side, over the packed serving arrays)
# ---------------------------------------------------------------------------


def knn_seed_radius(host: ServingArrays, curve, centers: np.ndarray,
                    k: int, metric: str = "l2") -> list:
    """Upper-bound each center's k-th-NN distance by expanding page rings
    around its curve address over the *packed* (host numpy) serving arrays
    — the same live row set the device filters, so the bound holds after
    delta refreshes.

    Ring r covers pages [p0 - r, p0 + r]; r doubles until a ring holds at
    least min(k, total_live) live rows (or the whole index).  The exact
    k-th candidate distance then bounds the true k-th-NN distance, and the
    returned per-center box half-width is inflated past any float64
    rounding, so the box [c - r, c + r] provably contains the k nearest.
    Vectorized over all still-active centers per ring round.
    """
    centers = np.atleast_2d(np.asarray(centers, dtype=np.uint64))
    pts_u32 = np.ascontiguousarray(host.points).view(np.uint32)  # (P, d, cap)
    Pn, d, cap = pts_u32.shape
    sizes = np.asarray(host.page_size, dtype=np.int64)
    csum = np.concatenate([[0], np.cumsum(sizes)])
    kk = min(int(k), int(csum[-1]))
    Q = len(centers)
    if kk <= 0:
        return [0] * Q
    zmin_u64 = z64_to_u64(np.asarray(host.page_zmin))  # padded pages: +inf
    zc = curve.encode_np(centers)
    p0 = np.clip(np.searchsorted(zmin_u64, zc, side="right") - 1, 0, Pn - 1)
    radius = [0] * Q
    active = np.ones(Q, dtype=bool)
    w = 1
    slot = np.arange(cap)
    while active.any():
        idxs = np.nonzero(active)[0]
        lo = np.maximum(p0[idxs] - w, 0)
        hi = np.minimum(p0[idxs] + w, Pn - 1)
        ready = ((csum[hi + 1] - csum[lo] >= kk)
                 | ((lo == 0) & (hi == Pn - 1)))
        ridx = idxs[ready]
        if len(ridx):
            offs = np.arange(-w, w + 1)
            pg = p0[ridx, None] + offs[None, :]       # (R, W)
            okp = (pg >= 0) & (pg < Pn)
            pgc = np.clip(pg, 0, Pn - 1)
            blk = pts_u32[pgc]                        # (R, W, d, cap)
            bsz = np.where(okp, sizes[pgc], 0)
            valid = slot[None, None, :] < bsz[:, :, None]   # (R, W, cap)
            R = len(ridx)
            if metric == "linf":
                diff = np.abs(blk.astype(np.int64)
                              - centers[ridx].astype(np.int64)[:, None, :, None])
                dist = np.where(valid, diff.max(axis=2),
                                np.iinfo(np.int64).max)
                kth = np.partition(dist.reshape(R, -1), kk - 1)[:, kk - 1]
                for i, v in zip(ridx, kth):           # L∞: exact, no slop
                    radius[i] = int(v)
            else:
                c = centers[ridx].astype(np.float64)[:, None, :, None]
                diff = blk.astype(np.float64) - c
                d2 = np.where(valid, np.sum(diff * diff, axis=2), np.inf)
                kth = np.partition(d2.reshape(R, -1), kk - 1)[:, kk - 1]
                for i, v in zip(ridx, kth):
                    # float64 may round the exact integer d2 either way;
                    # inflate so the half-width stays an upper bound
                    safe = float(v) * (1 + 1e-9) + 1.0
                    radius[i] = int(math.ceil(math.sqrt(safe))) + 1
            active[ridx] = False
        w *= 2
    return radius


# ---------------------------------------------------------------------------
# distributed engine (pages sharded over the whole mesh)
# ---------------------------------------------------------------------------


def make_distributed_query_fn(curve, mesh, *, k_maxsplit: int = 4,
                              max_cand: int = 64, q_chunk: int = 16,
                              backend: str = "xla", interpret: bool = False):
    """shard_map over all mesh axes: every device prunes/scans its own page
    shard for the full (replicated) query batch; counts are psum-reduced."""
    axes = tuple(mesh.axis_names)
    local = make_query_fn(curve, k_maxsplit=k_maxsplit, max_cand=max_cand,
                          q_chunk=q_chunk, backend=backend,
                          interpret=interpret)

    def _local(arrays, queries):
        counts, over = local(arrays, queries)
        counts = jax.lax.psum(counts, axes)
        over = jax.lax.psum(over, axes)  # int32: # of overflowed shards
        return counts, over

    shard_specs = ServingArrays(
        points=P(axes), page_zmin=P(axes), page_zmax=P(axes),
        page_mbr=P(axes), page_size=P(axes))
    f = jax.shard_map(_local, mesh=mesh,
                      in_specs=(shard_specs, P()),
                      out_specs=(P(), P()))
    return f, shard_specs


def shard_serving_arrays(arrays: ServingArrays, mesh) -> ServingArrays:
    axes = tuple(mesh.axis_names)
    put = lambda x: jax.device_put(x, NamedSharding(mesh, P(axes)))
    return jax.tree.map(put, arrays)
