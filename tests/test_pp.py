"""Pipeline-parallel step tests: schedule-invariance (the pipeline is only a
schedule — the math must equal the single-device forward), learning under
compression, and config validation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.models import transformer as tf
from tpu_compressed_dp.parallel.dp import CompressionConfig
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.pp_step import (
    init_pp_ef_state,
    make_pp_mesh,
    make_pp_train_step,
    stack_layer_params,
)
from tpu_compressed_dp.train.state import TrainState


def _cfg(**kw):
    base = dict(vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
                ffn_hidden=64, dtype=jnp.float32)
    base.update(kw)
    return tf.LlamaConfig(**base)


def _setup(cfg, mesh, comp, lr=0.0, microbatches=2):
    params = tf.init_llama(cfg, jax.random.key(0))
    sp = stack_layer_params(params)
    opt = SGD(lr=lr, momentum=0.9 if lr else 0.0)
    state = TrainState.create(
        sp, {}, opt.init(sp), init_pp_ef_state(cfg, sp, comp, mesh),
        jax.random.key(3),
    )
    step = make_pp_train_step(cfg, opt, comp, mesh, microbatches=microbatches,
                              donate=False)
    return params, state, step


@pytest.mark.parametrize("dp,pp,mb", [
    pytest.param(1, 2, 2, marks=pytest.mark.slow),
    (2, 2, 2),   # the general dp>1 row stays tier-1
    pytest.param(1, 4, 3, marks=pytest.mark.slow),
    (2, 4, 1),
])
def test_pipeline_loss_matches_single_device(dp, pp, mb):
    cfg = _cfg()
    x = jax.random.randint(jax.random.key(1), (4 * dp * mb, 16), 0, 64)
    y = jax.random.randint(jax.random.key(2), (4 * dp * mb, 16), 0, 64)
    ref = float(tf.vocab_parallel_xent(tf.apply_llama(cfg, params := tf.init_llama(
        cfg, jax.random.key(0)), x), y))
    mesh = make_pp_mesh(dp, pp)
    _, state, step = _setup(cfg, mesh, CompressionConfig(method=None),
                            microbatches=mb)
    _, m = step(state, {"input": x, "target": y})
    assert float(m["loss"]) == pytest.approx(ref, rel=1e-5)


def test_pipeline_learns_with_compression():
    cfg = _cfg()
    mesh = make_pp_mesh(2, 2)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.05, error_feedback=True)
    _, state, step = _setup(cfg, mesh, comp, lr=0.2)
    batch = {
        "input": jax.random.randint(jax.random.key(1), (8, 16), 0, 64),
        "target": jax.random.randint(jax.random.key(2), (8, 16), 0, 64),
    }
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert float(m["comm/sent_elems"]) / float(m["comm/dense_elems"]) == \
        pytest.approx(0.05, rel=0.05)
    ef_norm = sum(float(jnp.sum(jnp.abs(e))) for e in jax.tree.leaves(state.ef))
    assert ef_norm > 0


def test_pipeline_clip_stabilisers():
    """clip_norm + clip_sent_norm through the pipelined step: pipe-sharded
    layer norms psum over the pipe axis; training stays finite and moves."""
    cfg = _cfg(n_layers=2)
    mesh = make_pp_mesh(2, 2)
    comp = CompressionConfig(method="randomk", granularity="entiremodel",
                             ratio=0.05, error_feedback=True, mode="wire")
    params = tf.init_llama(cfg, jax.random.key(0))
    sp = stack_layer_params(params)
    opt = SGD(lr=0.2, momentum=0.9)
    state = TrainState.create(
        sp, {}, opt.init(sp), init_pp_ef_state(cfg, sp, comp, mesh),
        jax.random.key(3),
    )
    step = make_pp_train_step(cfg, opt, comp, mesh, microbatches=2,
                              clip_norm=1.0, clip_sent_norm=1.0, donate=False)
    batch = {
        "input": jax.random.randint(jax.random.key(1), (8, 16), 0, 64),
        "target": jax.random.randint(jax.random.key(2), (8, 16), 0, 64),
    }
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_pipeline_moe_layers():
    cfg = _cfg(n_experts=2, moe_every=1, capacity_factor=4.0)
    mesh = make_pp_mesh(1, 2)
    x = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    y = jax.random.randint(jax.random.key(2), (4, 16), 0, 64)
    ref = float(tf.vocab_parallel_xent(
        tf.apply_llama(cfg, tf.init_llama(cfg, jax.random.key(0)), x), y))
    _, state, step = _setup(cfg, mesh, CompressionConfig(method=None))
    _, m = step(state, {"input": x, "target": y})
    assert float(m["loss"]) == pytest.approx(ref, rel=1e-5)


def test_validation_errors():
    cfg = _cfg(n_layers=3)
    with pytest.raises(ValueError, match="divide"):
        make_pp_train_step(cfg, SGD(lr=0.1), CompressionConfig(),
                           make_pp_mesh(1, 2), microbatches=2)
    cfg = _cfg(n_experts=2, moe_every=2)
    with pytest.raises(ValueError, match="homogeneous"):
        make_pp_train_step(cfg, SGD(lr=0.1), CompressionConfig(),
                           make_pp_mesh(1, 2), microbatches=2)
    with pytest.raises(ValueError, match="homogeneous"):
        stack_layer_params(tf.init_llama(cfg, jax.random.key(0)))


def test_pp_checkpoint_resume(tmp_path):
    """PP-step checkpoint/resume (`train_imagenet_nv.py:193-198` analog):
    save mid-run, restore into a fresh state, re-place on the (data, pipe)
    mesh, and continue stepping with identical results to the uninterrupted
    run."""
    from tpu_compressed_dp.train.pp_step import place_pp_state
    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    cfg = _cfg(n_layers=2)
    mesh = make_pp_mesh(2, 2)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.25, error_feedback=True)
    _, state, step = _setup(cfg, mesh, comp, lr=1e-2)
    batch = {
        "input": jax.random.randint(jax.random.key(5), (8, 16), 0, 64),
        "target": jax.random.randint(jax.random.key(6), (8, 16), 0, 64),
    }
    state, _ = step(state, batch)
    state, _ = step(state, batch)

    ckpt = Checkpointer(str(tmp_path / "pp"))
    ckpt.save(state, {"step": int(state.step)})
    ckpt.close()

    # uninterrupted continuation (reference trajectory)
    cont, m_ref = step(state, batch)

    # restore into a freshly-initialised state, re-place, continue
    _, fresh, step2 = _setup(cfg, mesh, comp, lr=1e-2)
    restore = Checkpointer(str(tmp_path / "pp"))
    restored, meta = restore.restore(fresh)
    restore.close()
    assert meta["step"] == 2
    restored = place_pp_state(restored, cfg, comp, mesh)
    assert int(restored.step) == 2
    resumed, m_new = step2(restored, batch)
    assert int(resumed.step) == 3
    assert float(m_new["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-6)
    # EF residual survived the round-trip (it is part of the checkpoint)
    for a, b in zip(jax.tree.leaves(cont.ef), jax.tree.leaves(resumed.ef)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("dp,pp,tp,mb",
                         [(1, 2, 2, 2), (2, 2, 2, 4), (1, 2, 4, 2),
                          # mb % pp != 0: the deferred-head uneven fallback
                          # (every stage heads the full drained batch, scale
                          # 1/stages) must still match (ADVICE r3)
                          (1, 2, 2, 3)])
def test_pipeline_tensor_composition_matches_single_device(dp, pp, tp, mb):
    """pipe x tensor (VERDICT r2 #9): megatron sharding inside each stage
    must leave the loss equal to the unsharded single-device forward."""
    cfg = _cfg(n_kv_heads=4) if tp == 4 else _cfg()
    x = jax.random.randint(jax.random.key(1), (4 * dp * mb, 16), 0, 64)
    y = jax.random.randint(jax.random.key(2), (4 * dp * mb, 16), 0, 64)
    ref = float(tf.vocab_parallel_xent(tf.apply_llama(cfg, tf.init_llama(
        cfg, jax.random.key(0)), x), y))
    mesh = make_pp_mesh(dp, pp, tp)
    _, state, step = _setup(cfg, mesh, CompressionConfig(method=None),
                            microbatches=mb)
    _, m = step(state, {"input": x, "target": y})
    assert float(m["loss"]) == pytest.approx(ref, rel=1e-5)


def test_pipeline_tensor_learns_with_compression():
    cfg = _cfg()
    mesh = make_pp_mesh(2, 2, 2)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.1, error_feedback=True)
    _, state, step = _setup(cfg, mesh, comp, lr=0.3, microbatches=2)
    x = jax.random.randint(jax.random.key(4), (8, 16), 0, 64)
    y = jnp.roll(x, -1, axis=1)
    first = last = None
    for i in range(30):
        state, m = step(state, {"input": x, "target": y})
        if i == 0:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.7
    assert float(m["comm/sent_elems"]) < float(m["comm/dense_elems"]) * 0.2


@pytest.mark.parametrize("dp,sp,pp,tp,mb",
                         [(1, 2, 2, 2, 2), (2, 2, 2, 1, 2),
                          (1, 2, 2, 2, 3)])  # uneven mb % pp fallback
def test_pipeline_full_composition_matches_single_device(dp, sp, pp, tp, mb):
    """data x seq x pipe x tensor in ONE step (round 3): ring attention over
    `seq` inside each pipeline stage, megatron sharding inside each stage,
    vocab-parallel deferred head — loss must equal the unsharded
    single-device forward."""
    cfg = _cfg()
    x = jax.random.randint(jax.random.key(1), (4 * dp * mb, 16), 0, 64)
    y = jax.random.randint(jax.random.key(2), (4 * dp * mb, 16), 0, 64)
    ref = float(tf.vocab_parallel_xent(tf.apply_llama(cfg, tf.init_llama(
        cfg, jax.random.key(0)), x), y))
    mesh = make_pp_mesh(dp, pp, tp, sp)
    _, state, step = _setup(cfg, mesh, CompressionConfig(method=None),
                            microbatches=mb)
    _, m = step(state, {"input": x, "target": y})
    assert float(m["loss"]) == pytest.approx(ref, rel=1e-5)


@pytest.mark.slow  # ~8 s; the tensor-composition parity row and
# test_pipeline_tensor_learns keep dp+pp+tp quick coverage
def test_pipeline_full_composition_learns_with_compression():
    cfg = _cfg()
    mesh = make_pp_mesh(1, 2, 2, 2)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.1, error_feedback=True)
    _, state, step = _setup(cfg, mesh, comp, lr=0.3, microbatches=2)
    x = jax.random.randint(jax.random.key(4), (4, 16), 0, 64)
    y = jnp.roll(x, -1, axis=1)
    first = last = None
    for i in range(30):
        state, m = step(state, {"input": x, "target": y})
        if i == 0:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.7
    assert float(m["comm/sent_elems"]) < float(m["comm/dense_elems"]) * 0.2
