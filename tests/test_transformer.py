"""Transformer / ring-attention / LM-step tests on the virtual 8-CPU mesh.

The load-bearing property: the sharded model (tensor-parallel layers, ring
attention over the sequence axis, vocab-parallel loss) computes the SAME
function as the plain single-device forward — parallelism must be a layout
choice, not a semantics change.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from tpu_compressed_dp.models import transformer as tf
from tpu_compressed_dp.ops.ring_attention import dense_causal_attention, ring_attention


def _mesh(d, s, t):
    from tpu_compressed_dp.train.lm_step import make_lm_mesh

    return make_lm_mesh(d, s, t)


class TestRingAttention:
    def test_single_block_matches_naive(self):
        k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(k1, (2, 4, 16, 8))
        k = jax.random.normal(k2, (2, 4, 16, 8))
        v = jax.random.normal(k3, (2, 4, 16, 8))
        out = dense_causal_attention(q, k, v)
        # naive reference
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8)
        mask = jnp.tril(jnp.ones((16, 16), bool))
        s = jnp.where(mask, s, -jnp.inf)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gqa_head_repeat(self):
        k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(k1, (1, 4, 8, 8))
        k = jax.random.normal(k2, (1, 2, 8, 8))
        v = jax.random.normal(k3, (1, 2, 8, 8))
        out = dense_causal_attention(q, k, v)
        ref = dense_causal_attention(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_fused_gate_is_shape_and_backend_aware(self):
        """The fused flash path engages only on TPU at lane-multiple seq and
        MXU-friendly head_dim; on the CPU test backend it must stay off so
        dense_causal_attention remains the independent reference."""
        from tpu_compressed_dp.ops.ring_attention import use_fused_attention

        on_tpu = jax.default_backend() == "tpu"
        assert use_fused_attention((8, 12, 1024, 64), (8, 12, 1024, 64)) == on_tpu
        # never at these shapes, regardless of backend:
        assert not use_fused_attention((8, 12, 1000, 64), (8, 12, 1000, 64))
        # in-repo kernel tiles any 128-multiple seq (768 -> block 128)
        assert use_fused_attention((8, 12, 768, 64), (8, 12, 768, 64)) == on_tpu
        # VMEM gate: the dkv backward holds full Q + packed cotangent
        assert not use_fused_attention((1, 1, 1 << 15, 128),
                                       (1, 1, 1 << 15, 128))
        assert not use_fused_attention((8, 12, 64, 64), (8, 12, 64, 64))
        assert not use_fused_attention((8, 12, 1024, 80), (8, 12, 1024, 80))
        assert not use_fused_attention((8, 12, 1024, 64), (8, 12, 512, 64))

    @pytest.mark.parametrize("ring", [2, 4])
    def test_ring_matches_dense(self, ring):
        mesh = jax.make_mesh((ring,), ("seq",))
        keys = jax.random.split(jax.random.key(2), 3)
        T = 32
        q = jax.random.normal(keys[0], (2, 4, T, 8))
        k = jax.random.normal(keys[1], (2, 4, T, 8))
        v = jax.random.normal(keys[2], (2, 4, T, 8))
        ref = dense_causal_attention(q, k, v)
        ringed = shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
            mesh=mesh,
            in_specs=(P(None, None, "seq"), P(None, None, "seq"), P(None, None, "seq")),
            out_specs=P(None, None, "seq"),
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(ringed), np.asarray(ref), atol=1e-5)


class TestVocabParallelXent:
    def test_matches_dense(self):
        mesh = jax.make_mesh((4,), ("tensor",))
        logits = jax.random.normal(jax.random.key(3), (2, 8, 64))
        targets = jax.random.randint(jax.random.key(4), (2, 8), 0, 64)
        ref = float(tf.vocab_parallel_xent(logits, targets))
        # dense softmax cross-check
        logz = jax.nn.log_softmax(logits)
        want = float(-jnp.mean(jnp.take_along_axis(logz, targets[..., None], -1)))
        assert ref == pytest.approx(want, rel=1e-5)
        sharded = shard_map(
            lambda z, t: tf.vocab_parallel_xent(z, t, tensor_axis="tensor"),
            mesh=mesh,
            in_specs=(P(None, None, "tensor"), P()),
            out_specs=P(),
        )(logits, targets)
        assert float(sharded) == pytest.approx(want, rel=1e-5)


class TestLlamaParity:
    def setup_method(self):
        # fp32 everywhere so the sharded/unsharded comparison is tight
        self.cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                                  n_kv_heads=2, ffn_hidden=64, dtype=jnp.float32)
        self.params = tf.init_llama(self.cfg, jax.random.key(0))
        self.tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)

    def test_sharded_forward_matches_single_device(self):
        ref = tf.apply_llama(self.cfg, self.params, self.tokens)
        mesh = _mesh(2, 2, 2)
        sharded = shard_map(
            lambda p, t: tf.apply_llama(self.cfg, p, t, tensor_axis="tensor",
                                        seq_axis="seq"),
            mesh=mesh,
            in_specs=(tf.param_specs(self.cfg), P("data", "seq")),
            out_specs=P("data", "seq", "tensor"),
        )(self.params, self.tokens)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_sharded_loss_matches_single_device(self):
        # 17 tokens -> (x, y) shifted pairs of length 16 (divisible by seq=2)
        toks = jax.random.randint(jax.random.key(5), (4, 17), 0, 64)
        x, y = toks[:, :-1], toks[:, 1:]
        ref = float(tf.vocab_parallel_xent(
            tf.apply_llama(self.cfg, self.params, x), y))
        mesh = _mesh(2, 2, 2)

        def f(p, x, y):
            z = tf.apply_llama(self.cfg, p, x, tensor_axis="tensor", seq_axis="seq")
            loss = tf.vocab_parallel_xent(z, y, tensor_axis="tensor")
            # equal per-worker token counts -> pmean of local means == global mean
            return jax.lax.pmean(loss, ("data", "seq"))

        got = float(shard_map(
            f, mesh=mesh,
            in_specs=(tf.param_specs(self.cfg), P("data", "seq"), P("data", "seq")),
            out_specs=P(),
        )(self.params, x, y))
        assert got == pytest.approx(ref, rel=1e-4)


class TestLMTrainStep:
    def _setup(self, comp_kwargs, d=2, s=2, t=2):
        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.train.lm_step import (
            init_lm_ef_state, make_lm_train_step,
        )
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState

        cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, ffn_hidden=64, dtype=jnp.float32)
        mesh = _mesh(d, s, t)
        params = tf.init_llama(cfg, jax.random.key(0))
        opt = SGD(lr=0.1, momentum=0.9)
        comp = CompressionConfig(**comp_kwargs)
        state = TrainState.create(
            params, {}, opt.init(params),
            init_lm_ef_state(cfg, params, comp, mesh), jax.random.key(1),
        )
        step = make_lm_train_step(cfg, opt, comp, mesh)
        batch = {
            "input": jax.random.randint(jax.random.key(2), (4, 16), 0, 64),
            "target": jax.random.randint(jax.random.key(3), (4, 16), 0, 64),
        }
        return cfg, state, step, batch

    def test_dense_step_learns(self):
        cfg, state, step, batch = self._setup({"method": None})
        losses = []
        for _ in range(8):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert int(state.step) == 8
        assert losses[-1] < losses[0]  # memorises the fixed batch
        assert float(m["tokens"]) == 4 * 16

    def test_entiremodel_topk_ef_step(self):
        cfg, state, step, batch = self._setup({
            "method": "topk", "granularity": "entiremodel", "ratio": 0.01,
            "error_feedback": True,
        })
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        assert float(m["comm/sent_elems"]) / float(m["comm/dense_elems"]) == \
            pytest.approx(0.01, rel=0.05)
        # EF residual became nonzero (dropped coordinates stored)
        ef_norm = sum(float(jnp.sum(jnp.abs(e))) for e in jax.tree.leaves(state.ef))
        assert ef_norm > 0

    def test_wire_randomk_step(self):
        cfg, state, step, batch = self._setup({
            "method": "randomk", "granularity": "entiremodel", "ratio": 0.05,
            "mode": "wire", "error_feedback": True,
        })
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        assert float(m["comm/sent_elems"]) / float(m["comm/dense_elems"]) == \
            pytest.approx(0.05, rel=0.05)

    def test_tensor_axis_divisibility_validated(self):
        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.train.lm_step import make_lm_train_step
        from tpu_compressed_dp.train.optim import SGD

        cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=3,
                             n_kv_heads=3, ffn_hidden=64, dtype=jnp.float32)
        with pytest.raises(ValueError, match="divide"):
            make_lm_train_step(cfg, SGD(lr=0.1), CompressionConfig(), _mesh(2, 2, 2))


class TestRemat:
    def test_remat_identical_forward_and_grads(self):
        import dataclasses

        cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, ffn_hidden=64, dtype=jnp.float32)
        cfg_r = dataclasses.replace(cfg, remat=True)
        params = tf.init_llama(cfg, jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
        tgts = jax.random.randint(jax.random.key(2), (2, 16), 0, 64)

        def loss(c):
            return lambda p: tf.vocab_parallel_xent(tf.apply_llama(c, p, toks), tgts)

        l0, g0 = jax.value_and_grad(loss(cfg))(params)
        l1, g1 = jax.value_and_grad(loss(cfg_r))(params)
        assert float(l0) == pytest.approx(float(l1), rel=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                    atol=1e-5, rtol=1e-5),
            g0, g1)

    def test_remat_in_sharded_step(self):
        import dataclasses
        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.train.lm_step import (
            init_lm_ef_state, make_lm_mesh, make_lm_train_step,
        )
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState

        cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                             n_kv_heads=2, ffn_hidden=64, dtype=jnp.float32,
                             remat=True)
        mesh = make_lm_mesh(2, 2, 2)
        params = tf.init_llama(cfg, jax.random.key(0))
        opt = SGD(lr=0.1, momentum=0.9)
        comp = CompressionConfig(method="topk", granularity="entiremodel",
                                 ratio=0.05, error_feedback=True)
        state = TrainState.create(params, {}, opt.init(params),
                                  init_lm_ef_state(cfg, params, comp, mesh),
                                  jax.random.key(1))
        step = make_lm_train_step(cfg, opt, comp, mesh)
        batch = {"input": jax.random.randint(jax.random.key(2), (4, 16), 0, 64),
                 "target": jax.random.randint(jax.random.key(3), (4, 16), 0, 64)}
        losses = []
        for _ in range(5):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


@pytest.mark.quick
class TestFusedHeadXent:
    """fused_head_xent == vocab_parallel_xent(h @ w) — value AND grads —
    including the vocab-sharded (tensor-parallel) form and non-dividing
    chunk sizes (vocab padding)."""

    def _mk(self, n=12, d=16, v=50, seed=0):
        ks = jax.random.split(jax.random.key(seed), 3)
        h = jax.random.normal(ks[0], (3, n // 3, d), jnp.float32) * 0.5
        w = jax.random.normal(ks[1], (d, v), jnp.float32) * 0.2
        y = jax.random.randint(ks[2], (3, n // 3), 0, v)
        return h, w, y

    @pytest.mark.parametrize("chunk", [8, 16, 64])
    def test_matches_unfused_value_and_grads(self, chunk):
        from tpu_compressed_dp.models.transformer import (fused_head_xent,
                                                          vocab_parallel_xent)

        h, w, y = self._mk()
        ref_fn = lambda h, w: vocab_parallel_xent(h @ w, y)
        fused_fn = lambda h, w: fused_head_xent(h, w, y, None, chunk)
        ref, (dh_r, dw_r) = jax.value_and_grad(ref_fn, (0, 1))(h, w)
        got, (dh_f, dw_f) = jax.value_and_grad(fused_fn, (0, 1))(h, w)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(dh_f), np.asarray(dh_r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                                   atol=1e-6)

    def test_vocab_parallel_matches(self):
        # v=50 over 2 shards: v_local=25 does NOT divide chunk=8 -> each
        # shard has a 7-column pad window that aliases the NEXT shard's
        # first target ids (the inf-loss bug class: a target in a foreign
        # pad window must not gather the -inf masked logit)
        from tpu_compressed_dp.models.transformer import (fused_head_xent,
                                                          vocab_parallel_xent)

        h, w, y = self._mk(v=50)
        y = y.at[0, 0].set(25)  # shard 1's first id == shard 0's pad alias
        y = y.at[0, 1].set(3)   # in-shard-0 control
        from tpu_compressed_dp.parallel.mesh import make_mesh as _mm
        mesh = _mm((2,), ("tensor",))
        ref = float(vocab_parallel_xent(h @ w, y))

        def local(h, w, y):
            return fused_head_xent(h, w, y, "tensor", 8)

        got = shard_map(local, mesh=mesh,
                        in_specs=(P(), P(None, "tensor"), P()),
                        out_specs=P())(h, w, y)
        np.testing.assert_allclose(float(got), ref, rtol=1e-6)

        # grads through the sharded form: dw shards concatenate to the
        # unfused dw; dh (cotangent of the REPLICATED h) must come back
        # psum'd across shards — the custom VJP owns that psum
        dw_r = jax.grad(lambda w: vocab_parallel_xent(h @ w, y))(w)
        dh_r = jax.grad(lambda h: vocab_parallel_xent(h @ w, y))(h)
        dh_f, dw_f = shard_map(
            lambda h, w, y: jax.grad(
                lambda hw: fused_head_xent(hw[0], hw[1], y, "tensor", 8)
            )((h, w)),
            mesh=mesh, in_specs=(P(), P(None, "tensor"), P()),
            out_specs=(P(), P(None, "tensor")))(h, w, y)
        np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(dh_f), np.asarray(dh_r),
                                   atol=1e-6)


def test_fused_xent_auto_uses_logits_itemsize(monkeypatch):
    """ADVICE r5: the auto heuristic must size the logits buffer at the
    CONFIG's dtype width, not hardcoded bf16 — an fp32 config crosses the
    1 GiB auto-on threshold at half the token*vocab product."""
    from tpu_compressed_dp.models import transformer as tf_mod

    monkeypatch.setattr(tf_mod, "_FUSED_XENT", "")
    elems = (1 << 28) + 1  # > 1 GiB at fp32, exactly half that at bf16
    assert tf_mod.use_fused_head_xent(elems, 1, itemsize=4)
    assert not tf_mod.use_fused_head_xent(elems, 1, itemsize=2)
    # the default preserves the r5 bf16 behaviour
    assert not tf_mod.use_fused_head_xent(elems, 1)
    assert tf_mod.use_fused_head_xent((1 << 29) + 1, 1)
