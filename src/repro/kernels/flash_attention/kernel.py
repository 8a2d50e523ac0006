"""Pallas TPU flash attention (forward) with GQA, causal and sliding-window.

Grid (BH, n_q_blocks, n_kv_blocks) with the kv axis innermost ("arbitrary"
semantics); online-softmax state lives in VMEM scratch and the output block
is finalized on the last kv step.  Fully-masked (q, kv) blocks are skipped
with @pl.when, so causal costs ~half of full and sliding-window touches only
ceil(window/bk)+1 kv blocks per q block — the same skipping structure the
XLA fallback (models/attention.py) uses, so roofline accounting matches.

VMEM per program (bq = bk = 512, dh = 128, fp32 scratch):
q/k/v tiles 3·512·128·4 B = 768 KiB, acc 256 KiB, m/l 4 KiB — ~1 MiB.
MXU work per step: two 512×128×512 matmuls (dims 128-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq, bk, causal, window, scale, n_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = qi * bq
    k_start = ki * bk

    # static-shape mask decisions happen per block at trace time via pl.when
    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # is this kv block reachable from this q block?
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window > 0:
        live &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                # (bk, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols >= rows - window + 1
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """q: (BH, S, dh); k/v: (BKH, S, dh) where BH = B*H, BKH = B*KH (the
    ops wrapper flattens and maps GQA groups via the kv index_map)."""
    BH, S, dh = q.shape
    BKH = k.shape[0]
    group = BH // BKH
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    n_q, n_kv = S // bq, S // bk
    scale = dh ** -0.5

    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                             window=window, scale=scale, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
