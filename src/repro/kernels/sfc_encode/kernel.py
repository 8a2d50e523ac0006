"""Pallas TPU kernels: monotonic-SFC bit scramble (z-address encode).

Layout is transposed to (d, n) so the point axis rides the 128-wide VPU
lanes (d is tiny: 2–4).  The curve is static — the shift/and/or chains are
fully unrolled and constant-folded.  Output is Z64: (2, n) int32 (hi, lo).

Two kernel bodies, dispatched on the curve kind:

  global     — one ≤64-step chain (the paper's single θ)
  piecewise  — region code from the top `depth` bits of every dimension,
               the shared monotone prefix emitted once into the top output
               positions, then one low-bit chain per region merged with a
               region-mask select (regions are static, so XLA folds the
               per-leaf constants; R·d·(K-depth) + d·depth total bit ops)

VMEM budget per program: d·block_n·4 B in + 2·block_n·4 B out; with
block_n = 2048 and d = 4 that is 48 KiB — far under the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.curve import GlobalTheta, PiecewiseCurve, as_curve


def _encode_kernel(x_ref, out_ref, *, dim, bit):
    """x_ref: (d, block_n) int32; out_ref: (2, block_n) int32."""
    lo = jnp.zeros_like(x_ref[0, :])
    hi = jnp.zeros_like(lo)
    for l in range(len(dim)):
        b = (x_ref[dim[l], :] >> np.int32(bit[l])) & 1
        if l < 32:
            lo = lo | (b << np.int32(l))
        else:
            hi = hi | (b << np.int32(l - 32))
    out_ref[0, :] = hi
    out_ref[1, :] = lo


def _place(hi, lo, b, pos):
    """OR bit-vector b into output position pos of the (hi, lo) pair."""
    if pos < 32:
        return hi, lo | (b << np.int32(pos))
    return hi | (b << np.int32(pos - 32)), lo


def _encode_piecewise_kernel(x_ref, out_ref, *, d, depth, low, prefix_dims,
                             leaf_dims, leaf_bits):
    """x_ref: (d, block_n) int32; out_ref: (2, block_n) int32.

    prefix_dims: tuple of d*depth dims (region bit m reads dim
    prefix_dims[m], source bit low + m//d); leaf_dims/leaf_bits: per-region
    tuples of the d*low low-position assignments."""
    n_low = d * low
    zeros = jnp.zeros_like(x_ref[0, :])
    # region code + shared monotone prefix (top t·d output bits)
    r = zeros
    hi, lo = zeros, zeros
    for m in range(d * depth):
        b = (x_ref[prefix_dims[m], :] >> np.int32(low + m // d)) & 1
        r = r | (b << np.int32(m))
        hi, lo = _place(hi, lo, b, n_low + m)
    # per-region low-bit chains, merged by region mask
    for leaf in range(len(leaf_dims)):
        lhi, llo = zeros, zeros
        for l in range(n_low):
            b = (x_ref[leaf_dims[leaf][l], :] >> np.int32(leaf_bits[leaf][l])) & 1
            lhi, llo = _place(lhi, llo, b, l)
        sel = r == leaf
        hi = hi | jnp.where(sel, lhi, 0)
        lo = lo | jnp.where(sel, llo, 0)
    out_ref[0, :] = hi
    out_ref[1, :] = lo


def _kernel_body(curve):
    """Static kernel body for a curve (dispatch point for new curve kinds)."""
    if isinstance(curve, GlobalTheta):
        theta = curve.theta
        return functools.partial(
            _encode_kernel,
            dim=tuple(int(v) for v in theta.dim_of_pos),
            bit=tuple(int(v) for v in theta.bit_of_pos))
    if isinstance(curve, PiecewiseCurve):
        low = curve.K - curve.depth
        leaf_dims, leaf_bits = [], []
        for rcode in range(curve.num_regions):
            ft = curve.full_theta(rcode)
            leaf_dims.append(tuple(int(v) for v in ft.dim_of_pos[:curve.d * low]))
            leaf_bits.append(tuple(int(v) for v in ft.bit_of_pos[:curve.d * low]))
        return functools.partial(
            _encode_piecewise_kernel,
            d=curve.d, depth=curve.depth, low=low,
            prefix_dims=tuple(curve.prefix_order[m % curve.d]
                              for m in range(curve.d * curve.depth)),
            leaf_dims=tuple(leaf_dims), leaf_bits=tuple(leaf_bits))
    raise TypeError(f"no sfc_encode kernel for curve kind "
                    f"{type(curve).__name__!r}")


@functools.partial(jax.jit, static_argnames=("curve", "block_n", "interpret"))
def sfc_encode_dn(x_dn, curve, block_n: int = 2048,
                  interpret: bool = False):
    """x_dn: (d, n) int32, n % block_n == 0 -> (2, n) int32 Z64.
    `curve` is any `MonotonicCurve` (or a legacy `Theta`)."""
    curve = as_curve(curve)
    d, n = x_dn.shape
    assert n % block_n == 0, "caller pads n to a block multiple"
    return pl.pallas_call(
        _kernel_body(curve),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((d, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((2, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((2, n), jnp.int32),
        interpret=interpret,
    )(x_dn)


# ---------------------------------------------------------------------------
# candidate-batched variant: the curve pool rides a leading grid axis
# ---------------------------------------------------------------------------


def _encode_pool_kernel(x_ref, pos_ref, reg_ref, out_ref):
    """x_ref: (d, block_n) int32 — shared point block;
    pos_ref: (1, R, T) int32 — this candidate's output-position table
    (region r, flat input bit t = dim*K + bit), rows past the real region
    count repeat row 0; reg_ref: (P, M) int32 in SMEM — the whole pool's
    flat region-bit indexes (sentinel T = always-zero), read as scalars at
    row `program_id(0)`; out_ref: (1, 2, block_n) int32 Z64.

    Unlike the static bodies above, the curve arrives as *data*, so the
    shift amounts are traced values: bit planes are built once (static
    per-dim chains), the region code via masked sums over the plane axis,
    and each region's placement as clamped variable shifts gated by
    `pos < 32` / `pos >= 32` — output positions within a region are
    distinct, so the sums reproduce the static kernels' OR chains."""
    d, N = x_ref.shape
    R, T = pos_ref.shape[1], pos_ref.shape[2]
    M = reg_ref.shape[1]
    p = pl.program_id(0)
    K = T // d
    # bit planes, (T, N): plane t = i*K + j holds bit j of dimension i
    planes = [((x_ref[i, :][None, :] >>
                jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)) & 1)
              for i in range(d)]
    bits = jnp.concatenate(planes, axis=0)
    tidx = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    # region code from the M (possibly sentinel) region-bit indexes
    r = jnp.zeros((N,), jnp.int32)
    for m in range(M):
        bm = jnp.where(tidx == reg_ref[p, m], bits, 0).sum(axis=0)
        r = r | (bm << np.int32(m))
    # per-region variable-shift placement, merged by region mask
    hi = jnp.zeros((N,), jnp.int32)
    lo = jnp.zeros_like(hi)
    for rr in range(R):
        prr = pos_ref[0, rr, :][:, None]              # (T, 1) traced
        lo_r = jnp.where(prr < 32,
                         bits << jnp.minimum(prr, 31), 0).sum(axis=0)
        hi_r = jnp.where(prr >= 32,
                         bits << jnp.clip(prr - 32, 0, 31), 0).sum(axis=0)
        sel = r == rr
        lo = lo | jnp.where(sel, lo_r, 0)
        hi = hi | jnp.where(sel, hi_r, 0)
    out_ref[0, 0, :] = hi
    out_ref[0, 1, :] = lo


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sfc_encode_pool_dn(x_dn, pos, reg, block_n: int = 2048,
                       interpret: bool = False):
    """x_dn: (d, n) int32 with n % block_n == 0; pos: (P, R, T) int32 and
    reg: (P, M) int32 from `core.curve.pack_curve_pool` -> (P, 2, n) int32
    Z64 — every candidate curve's encode of the same points, one launch."""
    d, n = x_dn.shape
    P, R, T = pos.shape
    assert n % block_n == 0, "caller pads n to a block multiple"
    return pl.pallas_call(
        _encode_pool_kernel,
        grid=(P, n // block_n),
        in_specs=[pl.BlockSpec((d, block_n), lambda p, i: (0, i)),
                  pl.BlockSpec((1, R, T), lambda p, i: (p, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, 2, block_n), lambda p, i: (p, 0, i)),
        out_shape=jax.ShapeDtypeStruct((P, 2, n), jnp.int32),
        interpret=interpret,
    )(x_dn, pos, reg)
