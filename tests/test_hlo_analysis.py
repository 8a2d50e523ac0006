"""Loop-aware HLO analyzer: exact flops on a known scan+grad program, and
regression guards on the parser primitives."""
import textwrap

from repro.dist.hlo_analysis import (HloAnalyzer, _shape_bytes,
                                     analyze_hlo_text, parse_computations)
from repro.dist.roofline import model_flops
from repro.configs.base import SHAPES
from repro.configs.registry import get_arch


def test_shape_bytes():
    assert _shape_bytes("f32[4,128]{1,0}") == 4 * 128 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(s32[], f32[6,4,32])") == 4 + 6 * 4 * 32 * 4
    assert _shape_bytes("pred[]") == 1
    # sharding annotations must not match as shapes
    assert _shape_bytes("replica_groups=[2,4]<=[8]") == 0


def test_analyzer_counts_scan_trip_counts(cpu_child):
    """6-layer scan + grad: exactly 3 dots of 2*4*128*32 flops per layer."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist.compat import make_mesh
        from repro.dist.hlo_analysis import analyze_hlo_text

        def step(params, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, params)
            return h.sum()

        mesh = make_mesh((2, 4), ("data", "model"))
        ps = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32,
                                  sharding=NamedSharding(mesh, P(None, None, "model")))
        xs = jax.ShapeDtypeStruct((8, 128), jnp.float32,
                                  sharding=NamedSharding(mesh, P("data", None)))
        comp = jax.jit(jax.grad(step)).lower(ps, xs).compile()
        res = analyze_hlo_text(comp.as_text())
        assert res["flops"] == 6 * 3 * (2 * 4 * 128 * 32), res["flops"]
        assert res["bytes"] > 0 and res["bytes_unfused"] >= res["bytes"]
        assert res["collectives"]["all-gather"]["count"] == 12
        xla = comp.cost_analysis()["flops"]
        assert res["flops"] > 3 * xla  # XLA undercounts loop bodies
        print("OK-ANALYZER")
    """)
    r = cpu_child(code)
    assert "OK-ANALYZER" in r.stdout, r.stderr[-2000:]


def test_analyzer_loop_accounting_on_canned_hlo():
    """Millisecond-fast guard on trip-count weighting, dot flops, and
    async-start payload accounting (the subprocess exactness test above is
    deselected in CI for time; this keeps the invariant covered there)."""
    text = textwrap.dedent("""\
        HloModule canned, num_partitions=8

        %body.1 (p.2: (s32[], f32[4,128])) -> (s32[], f32[4,128]) {
          %p.2 = (s32[], f32[4,128]) parameter(0)
          %iv.3 = s32[] get-tuple-element(%p.2), index=0
          %h.4 = f32[4,128]{1,0} get-tuple-element(%p.2), index=1
          %w.5 = f32[128,32]{1,0} constant({...})
          %dot.6 = f32[4,32]{1,0} dot(%h.4, %w.5), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %ag.7 = (f32[4,32]{1,0}, f32[4,128]{1,0}) all-gather-start(%dot.6), replica_groups=[2,4]<=[8], dimensions={1}
          %agd.8 = f32[4,128]{1,0} all-gather-done(%ag.7)
          %one.9 = s32[] constant(1)
          %next.10 = s32[] add(%iv.3, %one.9)
          ROOT %tup.11 = (s32[], f32[4,128]) tuple(%next.10, %agd.8)
        }

        %cond.12 (p.13: (s32[], f32[4,128])) -> pred[] {
          %p.13 = (s32[], f32[4,128]) parameter(0)
          %iv.14 = s32[] get-tuple-element(%p.13), index=0
          %trip.15 = s32[] constant(6)
          ROOT %lt.16 = pred[] compare(%iv.14, %trip.15), direction=LT
        }

        ENTRY %main.17 (x.18: f32[4,128]) -> f32[4,128] {
          %x.18 = f32[4,128]{1,0} parameter(0)
          %zero.19 = s32[] constant(0)
          %init.20 = (s32[], f32[4,128]) tuple(%zero.19, %x.18)
          %loop.21 = (s32[], f32[4,128]) while(%init.20), condition=%cond.12, body=%body.1
          ROOT %out.22 = f32[4,128]{1,0} get-tuple-element(%loop.21), index=1
        }
    """)
    res = analyze_hlo_text(text)
    assert res["flops"] == 6 * (2 * 4 * 32 * 128)        # 1 dot x trip 6
    ag = res["collectives"]["all-gather"]
    assert ag["count"] == 6
    # async-start payload = largest tuple component (f32[4,128] = 2048 B),
    # not the tuple sum; ring all-gather moves n*(g-1)/g per device
    assert ag["bytes"] == 6 * 2048 * 3 / 4
    assert res["bytes_unfused"] >= res["bytes"] > 0


def test_model_flops_sane():
    cfg = get_arch("yi-6b")
    N = cfg.param_count()
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    dc = model_flops(cfg, SHAPES["decode_32k"])
    # train: 6·N·D ≈ 6 · 6.06e9 · 1.05e6 tokens ≈ 3.8e16 (+ attention)
    assert 6 * N * 256 * 4096 <= tr < 1.3 * 6 * N * 256 * 4096
    assert 2 * N * 32 * 32768 <= pf < 2.0 * 2 * N * 32 * 32768
    assert 2 * N * 128 <= dc < 3.0 * 2 * N * 128
    enc = get_arch("seamless-m4t-medium")
    # decode flops count only the decoder stack (not the encoder), plus
    # self+cross attention over the 32k cache (which dominates for a 0.35B
    # backbone): strictly less than full-param 2·N·B + the attention term
    full = 2 * enc.param_count() * 128
    attn = 2 * 2 * (2 * enc.n_layers) * enc.n_heads * enc.head_dim * 32768 * 128
    got = model_flops(enc, SHAPES["decode_32k"])
    assert got < full + attn
    assert got > attn / 2  # attention term present
