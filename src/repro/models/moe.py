"""Mixture-of-experts FFN with capacity-based sort/scatter dispatch
(MaxText-style dense layout — no (T, E·C) one-hot blow-up).

Dispatch: flatten tokens -> top-k experts -> rank within expert via a sorted
cumulative count -> scatter into an (E, C, D) buffer (drop past capacity) ->
per-expert batched matmuls -> gather back, combine with gate weights.
All shapes static; the dropped-token fraction is an auxiliary output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import dense


def init_moe(key, cfg, rules):
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    kg, ke = jax.random.split(key)
    p, s = {}, {}
    p["w_gate"], _ = dense(kg, D, E, None)
    s["w_gate"] = P(rules.fsdp_ax, None)  # tiny router: no tensor parallel

    def expert_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (dense(k1, D, F, None)[0], dense(k2, D, F, None)[0],
                dense(k3, F, D, None)[0])

    gates, ups, downs = jax.vmap(expert_init)(jax.random.split(ke, E))
    p["we_gate"], s["we_gate"] = gates, rules.expert_in(E, D, F)
    p["we_up"], s["we_up"] = ups, rules.expert_in(E, D, F)
    p["we_down"], s["we_down"] = downs, rules.expert_out(E, F, D)
    return p, s


def moe_ffn(p, cfg, x, *, capacity_factor: float = 1.25):
    """x: (B, S, D) -> (y (B, S, D), drop_frac scalar)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    T = B * S
    C = max(1, int(T * k / E * capacity_factor))
    xf = x.reshape(T, D)
    logits = (xf @ p["w_gate"]).astype(jnp.float32)          # (T, E)
    gate, eidx = jax.lax.top_k(logits, k)                    # (T, k)
    gate = jax.nn.softmax(gate, axis=-1).astype(x.dtype)

    # ---- rank of each (token, choice) within its expert -----------------
    e_flat = eidx.reshape(-1)                                # (T*k,)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E))       # (E,)
    rank_sorted = jnp.arange(T * k) - starts[e_sorted]
    rank = jnp.zeros(T * k, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < C
    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))

    # ---- scatter to (E*C, D) ---------------------------------------------
    tok_of_pair = jnp.repeat(jnp.arange(T), k)
    dest = jnp.where(keep, e_flat * C + rank, E * C)         # OOB -> dropped
    buf = jnp.zeros((E * C, D), x.dtype).at[dest].set(
        xf[tok_of_pair], mode="drop")
    xe = buf.reshape(E, C, D)

    # ---- expert compute ---------------------------------------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"]))
    u = jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    ye = jnp.einsum("ecf,efd->ecd", g * u, p["we_down"])     # (E, C, D)

    # ---- combine -----------------------------------------------------------
    pair_out = ye.reshape(E * C, D)[jnp.minimum(dest, E * C - 1)]
    pair_out = jnp.where(keep[:, None], pair_out, 0)
    w = gate.reshape(-1)[:, None].astype(pair_out.dtype)
    y = jnp.zeros((T, D), x.dtype).at[tok_of_pair].add(pair_out * w)
    return y.reshape(B, S, D), drop_frac


def moe_ffn_local(p, cfg, x, *, capacity_factor: float = 1.25):
    """Data-local (shard-major) dispatch: tokens never cross their data
    shard.  The pair arrays are reshaped to (shards, T_local·k) so ranking,
    scatter, expert matmuls, gather and combine are all per-shard-local
    (GSPMD keeps a sharded leading dim local); per-shard capacity
    C_local = C/shards.  Cross-shard traffic reduces to the FSDP weight
    all-gather + the TP psum of the down-projection — the TB-scale
    dispatch all-reduce of the global variant disappears.  Capacity
    semantics: per-shard instead of global (same capacity_factor)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    shards = max(1, cfg.moe_token_shards)
    if B % shards:
        shards = 1
    T = B * S
    Tl = T // shards
    Cl = max(1, int(Tl * k / E * capacity_factor))
    xf = x.reshape(shards, Tl, D)
    logits = (xf @ p["w_gate"]).astype(jnp.float32)          # (sh, Tl, E)
    gate, eidx = jax.lax.top_k(logits, k)
    gate = jax.nn.softmax(gate, axis=-1).astype(x.dtype)

    e_flat = eidx.reshape(shards, Tl * k)                    # (sh, P)
    order = jnp.argsort(e_flat, axis=1, stable=True)
    e_sorted = jnp.take_along_axis(e_flat, order, axis=1)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E)))(e_sorted)
    rank_sorted = (jnp.arange(Tl * k)[None, :]
                   - jnp.take_along_axis(starts, e_sorted, axis=1))
    rank = jnp.zeros((shards, Tl * k), jnp.int32).at[
        jnp.arange(shards)[:, None], order].set(rank_sorted.astype(jnp.int32))
    keep = rank < Cl
    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))

    tok_of_pair = jnp.repeat(jnp.arange(Tl), k)[None, :]     # (1, P)
    dest = jnp.where(keep, e_flat * Cl + rank, E * Cl)       # (sh, P), OOB->drop
    src = jnp.broadcast_to(tok_of_pair, dest.shape)
    rows = jnp.broadcast_to(jnp.arange(shards)[:, None], dest.shape)
    # structured 2-D scatter: the shard axis is an explicit batch dim, so
    # GSPMD partitions the scatter along the sharded dim instead of
    # replicating (a flat 1-D scatter forces an all-reduce of the buffer)
    updates = jnp.take_along_axis(xf, src[..., None], axis=1)  # (sh, P, D)
    buf = jnp.zeros((shards, E * Cl, D), x.dtype).at[
        rows, dest].set(updates, mode="drop")
    xe = buf.reshape(shards, E, Cl, D)

    g = jax.nn.silu(jnp.einsum("secd,edf->secf", xe, p["we_gate"]))
    u = jnp.einsum("secd,edf->secf", xe, p["we_up"])
    ye = jnp.einsum("secf,efd->secd", g * u, p["we_down"])   # (sh, E, Cl, D)

    pair_out = jnp.take_along_axis(
        ye.reshape(shards, E * Cl, D),
        jnp.minimum(dest, E * Cl - 1)[..., None], axis=1)    # (sh, P, D)
    pair_out = jnp.where(keep[..., None], pair_out, 0)
    w = gate.reshape(shards, Tl * k, 1).astype(pair_out.dtype)
    y = jnp.zeros((shards, Tl, D), x.dtype).at[
        jnp.arange(shards)[:, None], src].add(pair_out * w)
    return y.reshape(B, S, D), drop_frac


def moe_apply(p, cfg, x, mesh=None, rules=None, **kw):
    dispatch = getattr(cfg, "moe_dispatch", "global")
    if dispatch == "shardmap" and mesh is not None and rules is not None:
        return moe_ffn_shardmap(p, cfg, x, mesh, rules, **kw)
    if dispatch == "local" and cfg.moe_token_shards > 1:
        return moe_ffn_local(p, cfg, x, **kw)
    return moe_ffn(p, cfg, x, **kw)


def moe_ffn_shardmap(p, cfg, x, mesh, rules, *, capacity_factor: float = 1.25):
    """Decisive data-local dispatch: FULLY-MANUAL shard_map over the whole
    mesh.  Dispatch/combine ops are literally shard-local; the FSDP weight
    all-gather (over 'data') and the tensor-parallel down-projection psum
    (over 'model') are explicit — no GSPMD guessing, no resharding.
    (The partial-auto variant tickles an XLA-CPU AllReducePromotion crash,
    so we spell everything out.)"""
    import dataclasses as _dc

    B = x.shape[0]
    axes = rules.batch_ax(B)
    if not axes:
        return moe_ffn(p, cfg, x, capacity_factor=capacity_factor)
    axes = axes if isinstance(axes, tuple) else (axes,)
    cfg_local = _dc.replace(cfg, moe_token_shards=1)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    tp_ax = "model" if F % rules.model_size == 0 and rules.model_size > 1 \
        else None
    fsdp_ax = rules.fsdp_ax

    wspec = {"w_gate": P(fsdp_ax, None),
             "we_gate": rules.expert_in(E, D, F),
             "we_up": rules.expert_in(E, D, F),
             "we_down": rules.expert_out(E, F, D)}

    def local_fn(pl, xl):
        wg, wu, wd, wr = pl["we_gate"], pl["we_up"], pl["we_down"], pl["w_gate"]
        if fsdp_ax:  # explicit FSDP gather of the reduction dims
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=2, tiled=True)
            wr = jax.lax.all_gather(wr, fsdp_ax, axis=0, tiled=True)

        Bl, S, _ = xl.shape
        T = Bl * S
        k = cfg.moe_top_k
        C = max(1, int(T * k / E * capacity_factor))
        xf = xl.reshape(T, D)
        logits = (xf @ wr).astype(jnp.float32)
        gate, eidx = jax.lax.top_k(logits, k)
        gate = jax.nn.softmax(gate, axis=-1).astype(xl.dtype)

        e_flat = eidx.reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = e_flat[order]
        starts = jnp.searchsorted(e_sorted, jnp.arange(E))
        rank_sorted = jnp.arange(T * k) - starts[e_sorted]
        rank = jnp.zeros(T * k, jnp.int32).at[order].set(
            rank_sorted.astype(jnp.int32))
        keep = rank < C
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))

        tok_of_pair = jnp.repeat(jnp.arange(T), k)
        dest = jnp.where(keep, e_flat * C + rank, E * C)
        buf = jnp.zeros((E * C, D), xl.dtype).at[dest].set(
            xf[tok_of_pair], mode="drop")
        xe = buf.reshape(E, C, D)

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg))
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", g * u, wd)
        # The combine (gather + weighted segment-add) is LINEAR in ye, so
        # the tensor-parallel reduction over the F-sharded contraction is
        # deferred past it: psum of (T, D) tokens instead of the (E, C, D)
        # capacity buffer — ~E·C/T = k·capacity_factor× less wire, and it
        # rides the same deferred position in the VJP.
        pair_out = ye.reshape(E * C, D)[jnp.minimum(dest, E * C - 1)]
        pair_out = jnp.where(keep[:, None], pair_out, 0)
        w = gate.reshape(-1)[:, None].astype(pair_out.dtype)
        y = jnp.zeros((T, D), jnp.float32).at[tok_of_pair].add(
            (pair_out * w).astype(jnp.float32))
        if tp_ax:
            y = jax.lax.psum(y, tp_ax)
        return y.astype(xl.dtype).reshape(Bl, S, D), drop[None]

    f = jax.shard_map(local_fn, mesh=mesh,
                      in_specs=(wspec, P(axes, None, None)),
                      out_specs=(P(axes, None, None), P(axes)),
                      check_vma=False)
    y, drop = f(p, x)
    return y, jnp.mean(drop)
