"""Optimizer, checkpoint (incl. elastic restore onto a different mesh),
gradient compression, FT supervisor, data pipeline."""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import (latest_step, restore_checkpoint,
                                   save_checkpoint)
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch, reduced_config
from repro.data.pipeline import (CurriculumPhase, IndexedDataset,
                                 TokenBatcher, synth_corpus)
from repro.launch.ft import FTConfig, Supervisor
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.optim.compress import dequantize_int8, quantize_int8


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.asarray([2.0, -3.0, 1.0], jnp.bfloat16)}
    opt = init_opt_state(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)

    def loss(p):
        return jnp.sum(jnp.square(p["w"].astype(jnp.float32)))

    l0 = float(loss(params))
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, opt, stats = adamw_update(cfg, g, opt, params)
    assert float(loss(params)) < 0.05 * l0
    assert int(opt["step"]) == 60
    assert float(stats["grad_norm"]) >= 0


def test_adamw_grad_clip():
    params = {"w": jnp.ones(4, jnp.float32)}
    opt = init_opt_state(params)
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0)
    g = {"w": jnp.full(4, 100.0)}
    p2, opt, stats = adamw_update(cfg, g, opt, params)
    assert float(stats["grad_norm"]) == pytest.approx(200.0)
    # post-clip effective |update| bounded by lr * O(1)
    assert float(jnp.max(jnp.abs(p2["w"] - params["w"]))) < 5e-3


# ---------------------------------------------------------------------------
# int8 compression
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    q, scale = quantize_int8(g)
    back = dequantize_int8(q, scale)
    assert q.dtype == jnp.int8
    err = float(jnp.max(jnp.abs(back - g)))
    assert err <= float(scale) / 2 + 1e-6  # half-ulp rounding bound


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_keep_k(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones(5, jnp.bfloat16),
                       "step": jnp.int32(7)}}
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    assert latest_step(str(tmp_path)) == 4
    assert len([d for d in os.listdir(tmp_path) if d.startswith("step_")]) == 2
    restored, manifest = restore_checkpoint(str(tmp_path), 4, tree)
    assert manifest["step"] == 4
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), tree, restored)


def test_checkpoint_elastic_restore_different_mesh(cpu_child):
    """Save on a (4,2) mesh, restore onto (2,2) — subprocess w/ 8 devices."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.checkpoint import save_checkpoint, restore_checkpoint
        from repro.dist.compat import make_mesh

        mesh_a = make_mesh((4, 2), ("data", "model"))
        w = jnp.arange(64.0 * 32).reshape(64, 32)
        wa = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 10, {"w": wa})

        mesh_b = make_mesh((2, 2), ("data", "model"))
        shard_b = {"w": NamedSharding(mesh_b, P("model", "data"))}
        restored, _ = restore_checkpoint(d, 10, {"w": wa}, shard_b)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("OK-ELASTIC")
    """)
    r = cpu_child(code)
    assert "OK-ELASTIC" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# FT supervisor
# ---------------------------------------------------------------------------


def test_supervisor_detects_straggler_and_deadline():
    sup = Supervisor(4, FTConfig(straggler_factor=2.0, patience=2,
                                 deadline_s=10.0))
    t = 1000.0
    for step in range(5):
        t += 1
        for w in range(3):
            sup.heartbeat(w, 1.0, now=t)
        sup.heartbeat(3, 5.0, now=t)  # persistent straggler
        bad = sup.check(now=t)
    assert (3, "straggler") in sup.events
    assert sup.healthy_count() == 3
    # deadline: worker 2 stops beating
    for step in range(3):
        t += 20
        for w in (0, 1):
            sup.heartbeat(w, 1.0, now=t)
        sup.check(now=t)
    assert any(w == 2 and r == "deadline" for w, r in sup.events)
    # elastic downsizing proposes a power-of-two data axis
    assert sup.elastic_data_axis(model_size=4, chips_per_host=4) in (1, 2)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_indexed_pipeline_selection_and_resume():
    docs, meta = synth_corpus(n_docs=400, vocab=128, max_len=64, seed=0)
    ds = IndexedDataset(docs, meta, seed=0)
    ids = ds.select((0.0, 0.0, 0.7, 0.0), (1.0, 1.0, 1.0, 1.0))
    assert len(ids) > 0
    assert np.all(meta[ids, 2] >= 0.7 - 1e-3)

    phases = [CurriculumPhase("easy", (0.0, 0.0, 0.5, 0.0),
                              (0.6, 1.0, 1.0, 1.0), steps=3),
              CurriculumPhase("hard", (0.0, 0.0, 0.0, 0.0),
                              (1.0, 1.0, 1.0, 1.0), steps=2)]
    tb = TokenBatcher(ds, phases, batch=4, seq_len=32, seed=1)
    batches = list(tb)
    assert len(batches) == 5
    assert batches[0][0]["tokens"].shape == (4, 32)

    # resume from the recorded state mid-stream
    tb2 = TokenBatcher(ds, phases, batch=4, seq_len=32, seed=1)
    tb2.set_state(batches[2][1])
    rest = list(tb2)
    assert len(rest) == 2
