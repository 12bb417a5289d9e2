"""MoE / expert-parallel tests.

Key properties: a single-expert MoE with ample capacity IS the dense SwiGLU
(routing multiplies by softmax prob == 1); expert-parallel sharding over the
tensor axis computes the same function as the unsharded layer; over-capacity
tokens fall through to the residual; the full MoE LM step trains under
gradient compression.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from tpu_compressed_dp.models import transformer as tf


def _cfg(**kw):
    base = dict(vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                ffn_hidden=64, dtype=jnp.float32, n_experts=4, moe_every=1,
                capacity_factor=2.0)
    base.update(kw)
    return tf.LlamaConfig(**base)


class TestMoEFFN:
    def test_single_expert_equals_dense_swiglu(self):
        cfg = _cfg(n_experts=1, capacity_factor=2.0)
        lp = {
            "router": jnp.zeros((32, 1)),
            "w_gate": jax.random.normal(jax.random.key(0), (1, 32, 64)) * 0.1,
            "w_up": jax.random.normal(jax.random.key(1), (1, 32, 64)) * 0.1,
            "w_down": jax.random.normal(jax.random.key(2), (1, 64, 32)) * 0.1,
        }
        x = jax.random.normal(jax.random.key(3), (2, 8, 32))
        out, aux = tf._moe_ffn(cfg, lp, x, None)
        gate = jax.nn.silu(x @ lp["w_gate"][0])
        dense = (gate * (x @ lp["w_up"][0])) @ lp["w_down"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)
        assert float(aux) == pytest.approx(1.0)  # perfectly balanced: E*1*1/E

    def test_capacity_drops_tokens(self):
        # capacity ~0 -> every token dropped -> output is exactly zero
        cfg = _cfg(n_experts=4, capacity_factor=1e-9)
        lp = {
            "router": jax.random.normal(jax.random.key(0), (32, 4)),
            "w_gate": jnp.ones((4, 32, 64)), "w_up": jnp.ones((4, 32, 64)),
            "w_down": jnp.ones((4, 64, 32)),
        }
        x = jax.random.normal(jax.random.key(1), (2, 8, 32))
        out, _ = tf._moe_ffn(cfg, lp, x, None)
        # capacity clamps to 1 slot per expert: at most 4 tokens survive
        nonzero_tokens = int(jnp.sum(jnp.any(out.reshape(-1, 32) != 0, axis=-1)))
        assert nonzero_tokens <= 4

    @pytest.mark.slow  # ~18 s; MoE keeps quick rows (step+compression, lm flag)
    def test_sharded_matches_unsharded(self):
        # capacity queues are per (data, seq) shard — parity with the
        # unsharded run holds exactly only in the drop-free regime, so use a
        # capacity factor >= n_experts (cap >= tokens => nothing ever drops)
        cfg = _cfg(n_experts=4, capacity_factor=8.0)
        params = tf.init_llama(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
        ref = tf.apply_llama(cfg, params, tokens)
        from tpu_compressed_dp.train.lm_step import make_lm_mesh

        mesh = make_lm_mesh(2, 2, 2)
        got = shard_map(
            lambda p, t: tf.apply_llama(cfg, p, t, tensor_axis="tensor",
                                        seq_axis="seq"),
            mesh=mesh,
            in_specs=(tf.param_specs(cfg), P("data", "seq")),
            out_specs=P("data", "seq", "tensor"),
        )(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_aux_loss_favors_balance(self):
        cfg = _cfg(n_experts=4)
        x = jax.random.normal(jax.random.key(2), (2, 32, 32))
        # collapsed router (all tokens -> expert 0) must score worse than a
        # spread router
        collapsed = {
            "router": jnp.zeros((32, 4)).at[:, 0].set(5.0),
            "w_gate": jnp.zeros((4, 32, 64)), "w_up": jnp.zeros((4, 32, 64)),
            "w_down": jnp.zeros((4, 64, 32)),
        }
        spread = dict(collapsed, router=jnp.zeros((32, 4)))
        _, aux_c = tf._moe_ffn(cfg, collapsed, x, None)
        _, aux_s = tf._moe_ffn(cfg, spread, x, None)
        assert float(aux_c) > float(aux_s) >= 0.99

    def test_expert_divisibility_validated(self):
        with pytest.raises(ValueError, match="n_experts"):
            _cfg(n_experts=3).validate_mesh(2)


class TestMoELMStep:
    def test_moe_step_with_compression(self):
        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.train.lm_step import (
            init_lm_ef_state, make_lm_mesh, make_lm_train_step,
        )
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState

        cfg = _cfg(n_experts=4, moe_every=2)  # layer 1 MoE, layer 0 dense
        mesh = make_lm_mesh(2, 2, 2)
        params = tf.init_llama(cfg, jax.random.key(0))
        assert "router" in params["layers"][1] and "router" not in params["layers"][0]
        opt = SGD(lr=0.1, momentum=0.9)
        comp = CompressionConfig(method="topk", granularity="entiremodel",
                                 ratio=0.05, error_feedback=True)
        state = TrainState.create(
            params, {}, opt.init(params),
            init_lm_ef_state(cfg, params, comp, mesh), jax.random.key(1),
        )
        step = make_lm_train_step(cfg, opt, comp, mesh)
        batch = {
            "input": jax.random.randint(jax.random.key(2), (4, 16), 0, 64),
            "target": jax.random.randint(jax.random.key(3), (4, 16), 0, 64),
        }
        losses = []
        for _ in range(6):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
        assert float(m["comm/sent_elems"]) / float(m["comm/dense_elems"]) == \
            pytest.approx(0.05, rel=0.05)

    def test_lm_harness_moe_flag(self):
        from tpu_compressed_dp.harness import lm

        s = lm.main(["--preset", "tiny", "--dp", "2", "--sp", "2", "--tp", "2",
                     "--experts", "4", "--moe_every", "1",
                     "--steps", "10", "--seq_len", "32", "--global_batch", "8",
                     "--fp32", "--log_every", "5"])
        assert np.isfinite(s["loss"])
