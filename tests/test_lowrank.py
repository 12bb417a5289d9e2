"""PowerSGD low-rank compression: math, stateful sync engine, checkpointing.

The properties pinned here are the ones the subsystem's design rests on
(ops/lowrank.py):

  * psum-linearity — every nonlinear step happens AFTER a psum, so the
    2-worker sync equals the same compression applied to the worker-mean
    gradient;
  * transport — the P/Q factors ride the psum ring and nothing else
    (``sent_bits_psum > 0``, ``sent_bits_allgather == 0``), at fewer bits
    than dense;
  * state — the warm-start Q threads through the sync and survives an Orbax
    checkpoint round-trip bitwise, and warm-starting actually helps (the
    reconstruction error of a repeated gradient decreases across steps).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from tpu_compressed_dp.ops import compressors, lowrank
from tpu_compressed_dp.parallel.dp import (
    CompressionConfig,
    init_comp_state,
    init_comp_state_grouped,
    init_ef_state,
    make_grad_sync,
    make_grouped_grad_sync,
)


@pytest.fixture(scope="module")
def mesh2():
    from tpu_compressed_dp.parallel.mesh import make_data_mesh

    return make_data_mesh(2)


def run_sync(mesh, cfg, grads_per_dev, comp, ef=None, seed=0):
    """grads_per_dev leaves have leading dim == mesh size; returns
    (synced, new_ef, new_comp, stats) with comp threaded through."""
    sync = make_grad_sync(cfg, "data")
    if ef is None:
        ef = init_ef_state(jax.tree.map(lambda g: g[0], grads_per_dev), cfg)

    def f(g, e, c):
        return sync(jax.tree.map(lambda x: x[0], g), e, c, jax.random.key(seed))

    shard_spec = jax.tree.map(lambda _: P("data"), grads_per_dev)
    fn = shard_map(
        f, mesh=mesh,
        in_specs=(shard_spec, P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return fn(grads_per_dev, ef, comp)


@pytest.mark.quick
class TestDims:
    def test_near_square_and_rank_clamp(self):
        m, n2, r = lowrank.powersgd_dims(10000, 4)
        assert m * n2 >= 10000 and abs(m - n2) <= 1
        assert r == 4
        # rank ~ m means the factors cost ~2n — always the dense fallback
        # (the clamp to min(m, n2) can never beat it at near-square shapes)
        assert lowrank.powersgd_dims(10000, 1000) is None
        assert lowrank.powersgd_dims(256, 64) is None

    def test_dense_fallback_for_tiny_groups(self):
        # factors r*(m+n2) >= n: biases / norm scales send dense
        assert lowrank.powersgd_dims(32, 4) is None
        assert lowrank.powersgd_dims(1, 1) is None
        assert lowrank.powersgd_group_bits(32, 4) == 32.0 * 32

    def test_payload_bits_per_elem(self):
        n = 1 << 20
        m, n2, r = lowrank.powersgd_dims(n, 2)
        got = compressors.payload_bits_per_elem("powersgd", rank=2, n=n)
        assert got == pytest.approx(32.0 * r * (m + n2) / n)
        assert got < 1.0  # ~0.25% of dense at 1M elements, r=2
        with pytest.raises(ValueError, match="shape-dependent"):
            compressors.payload_bits_per_elem("powersgd", rank=2)

    def test_registry(self):
        assert "powersgd" in compressors.REGISTRY
        assert compressors.canonical_name("power_sgd") == "powersgd"
        bound = compressors.get_compressor("powersgd", rank=2)
        assert bound.is_stateful and bound.needs_rng
        g = jax.random.normal(jax.random.key(0), (4096,))
        out = bound.fn(g, jax.random.key(1))
        assert out.shape == g.shape
        # a single-shot rank-2 approximation is not the identity but keeps
        # a nontrivial fraction of the energy
        err = float(jnp.linalg.norm(out - g) / jnp.linalg.norm(g))
        assert 0.0 < err < 1.0


@pytest.mark.quick
class TestGramSchmidt:
    def test_orthonormal_columns(self):
        p = jax.random.normal(jax.random.key(3), (50, 4))
        q = lowrank.gram_schmidt(p)
        np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(4), atol=1e-5)

    def test_batched(self):
        p = jax.random.normal(jax.random.key(4), (3, 50, 2))
        q = lowrank.gram_schmidt(p)
        for b in range(3):
            np.testing.assert_allclose(
                np.asarray(q[b].T @ q[b]), np.eye(2), atol=1e-5)

    def test_zero_and_deficient_columns_stay_finite(self):
        q = lowrank.gram_schmidt(jnp.zeros((10, 3)))
        assert np.all(np.isfinite(np.asarray(q)))
        # duplicated column: the second projects to ~0, must not NaN
        col = jax.random.normal(jax.random.key(5), (10, 1))
        q = lowrank.gram_schmidt(jnp.concatenate([col, col], axis=1))
        assert np.all(np.isfinite(np.asarray(q)))


def _local_reference(mean_flat, q0, rank):
    """The engine's math on a single (already-averaged) gradient."""
    n = mean_flat.shape[0]
    m, n2, r = lowrank.powersgd_dims(n, rank)
    mat = lowrank._as_matrix(mean_flat, m, n2)
    p_hat = lowrank.gram_schmidt(lowrank._dot(mat, q0))
    q1 = lowrank._dot(mat.T, p_hat)
    recon = lowrank._dot(p_hat, q1.T).reshape(-1)[:n]
    return recon, q1


class TestTwoWorkerSync:
    """The acceptance-criteria tests: psum-linearity and transport split."""

    def make(self, n=4096, rank=2):
        cfg = CompressionConfig(method="powersgd", rank=rank,
                                granularity="entiremodel")
        grads = {"w": jax.random.normal(jax.random.key(11), (2, n))}
        comp = init_comp_state({"w": grads["w"][0]}, cfg)
        return cfg, grads, comp

    def test_psum_linearity(self, mesh2):
        """2-worker PowerSGD sync == the same compression applied to the
        mean of the per-worker gradients (every nonlinear step runs after
        a psum, so the collective IS a mean over low-rank factor payloads)."""
        cfg, grads, comp = self.make()
        out, _, new_comp, _ = run_sync(mesh2, cfg, grads, comp)
        mean = jnp.mean(grads["w"], axis=0)
        exp, q1 = _local_reference(mean, comp["q0"], cfg.rank)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(exp),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(new_comp["q0"]), np.asarray(q1),
                                   rtol=1e-5, atol=1e-6)

    def test_factors_ride_psum_only(self, mesh2):
        cfg, grads, comp = self.make()
        _, _, _, stats = run_sync(mesh2, cfg, grads, comp)
        assert float(stats["sent_bits_psum"]) > 0
        assert float(stats["sent_bits_allgather"]) == 0.0
        assert float(stats["sent_bits"]) == float(stats["sent_bits_psum"])
        # compressed: far below the 32 bits/elem dense wire
        assert float(stats["sent_bits"]) < 32.0 * float(stats["dense_elems"])
        m, n2, r = lowrank.powersgd_dims(4096, cfg.rank)
        assert float(stats["sent_elems"]) == r * (m + n2)
        assert float(stats["num_collectives"]) == 2.0  # P-psum + Q-psum

    def test_ef_residual_identity(self, mesh2):
        """Per worker: transmitted reconstruction + residual == gradient
        (Stich-style memory, here against the worker-mean reconstruction)."""
        cfg = CompressionConfig(method="powersgd", rank=2,
                                granularity="entiremodel", error_feedback=True)
        grads = {"w": jax.random.normal(jax.random.key(12), (2, 2048))}
        comp = init_comp_state({"w": grads["w"][0]}, cfg)
        out, new_ef, _, _ = run_sync(mesh2, cfg, grads, comp)
        # run_sync returns device-0's residual slice (out_specs P())
        np.testing.assert_allclose(
            np.asarray(new_ef["w"]),
            np.asarray(grads["w"][0] - out["w"]), rtol=1e-5, atol=1e-6)

    def test_layerwise_mixes_compressed_and_dense_groups(self, mesh2):
        cfg = CompressionConfig(method="powersgd", rank=4,
                                granularity="layerwise")
        grads = {
            "w": jax.random.normal(jax.random.key(13), (2, 4096)),
            "b": jax.random.normal(jax.random.key(14), (2, 8)),
        }
        comp = init_comp_state(
            jax.tree.map(lambda g: g[0], grads), cfg)
        # leaves sort by key: 'b' is group 0 (dense fallback, no state),
        # 'w' is group 1 (compressed)
        assert set(comp) == {"q1"}
        out, _, new_comp, stats = run_sync(mesh2, cfg, grads, comp)
        # dense-fallback group is exactly the mean
        np.testing.assert_allclose(np.asarray(out["b"]),
                                   np.asarray(grads["b"].mean(0)), rtol=1e-6)
        assert set(new_comp) == {"q1"}
        # dense group bills 32 bits/elem, still on the psum ring
        assert float(stats["sent_bits_allgather"]) == 0.0

    def test_missing_state_raises(self, mesh2):
        cfg, grads, _ = self.make()
        with pytest.raises(ValueError, match="init_comp_state"):
            run_sync(mesh2, cfg, grads, ())

    def test_check_sync_reports_warm_start_agreement(self, mesh2):
        """check_sync (the check_reduction analog): agreeing warm starts
        report sync_agree == 1.0 — the factor psums are only meaningful in a
        shared basis, so divergence here is the powersgd equivalent of
        misaligned Random-K indices."""
        cfg = CompressionConfig(method="powersgd", rank=2,
                                granularity="entiremodel", check_sync=True)
        grads = {"w": jax.random.normal(jax.random.key(11), (2, 4096))}
        comp = init_comp_state({"w": grads["w"][0]}, cfg)
        _, _, _, stats = run_sync(mesh2, cfg, grads, comp)
        assert float(stats["sync_agree"]) == 1.0

    def test_warm_start_converges_on_repeated_gradient(self, mesh2):
        """Power iteration with a persistent Q: reconstruction error of a
        FIXED gradient strictly improves over fresh-random single shots
        within a few steps (the whole point of warm-starting)."""
        cfg, grads, comp = self.make(n=2048, rank=2)
        mean = np.asarray(jnp.mean(grads["w"], axis=0))
        errs = []
        for _ in range(6):
            out, _, comp, _ = run_sync(mesh2, cfg, grads, comp)
            errs.append(float(np.linalg.norm(np.asarray(out["w"]) - mean)))
        assert errs[-1] <= errs[0] * (1 + 1e-6)
        assert errs[-1] == min(errs)


class TestGroupedSync:
    def test_comp_threads_through_signature_groups(self, mesh2):
        cfg = CompressionConfig(method="powersgd", rank=2,
                                granularity="layerwise")
        grads = {"a": jax.random.normal(jax.random.key(21), (2, 1024)),
                 "b": jax.random.normal(jax.random.key(22), (2, 900))}
        local = jax.tree.map(lambda g: g[0], grads)
        is_sharded = [False, False]
        comp = init_comp_state_grouped(local, cfg, is_sharded, "data")
        assert set(comp) == {"sig0"} and set(comp["sig0"]) == {"q0", "q1"}
        sync = make_grouped_grad_sync(cfg, "data", is_sharded, "data")

        def f(g, c):
            return sync(jax.tree.map(lambda x: x[0], g), (), c,
                        jax.random.key(0))

        out, _, new_comp, stats = shard_map(
            f, mesh=mesh2,
            in_specs=(jax.tree.map(lambda _: P("data"), grads), P()),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )(grads, comp)
        assert set(new_comp) == {"sig0"}
        for k in ("q0", "q1"):
            assert new_comp["sig0"][k].shape == comp["sig0"][k].shape
        assert float(stats["sent_bits_allgather"]) == 0.0


class TestCheckpointRoundTrip:
    def test_warm_start_q_survives_orbax_bitwise(self, tmp_path):
        """Acceptance criterion: TrainState.comp round-trips through Orbax
        exactly — a resumed run continues the power iteration from the
        converged subspace, not from random."""
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.utils.checkpoint import (
            restore_checkpoint, save_checkpoint)

        cfg = CompressionConfig(method="powersgd", rank=4,
                                granularity="layerwise", error_feedback=True)
        params = {"w": jnp.zeros((4096,)), "b": jnp.zeros((8,))}
        comp = init_comp_state(params, cfg, num_devices=2)
        ef = init_ef_state(params, cfg, num_devices=2)
        # make the state visibly non-fresh so the round-trip is meaningful
        comp = jax.tree.map(lambda q: q + 0.123, comp)
        state = TrainState.create(params, {}, {"momentum": params}, ef,
                                  jax.random.key(7), comp=comp)
        save_checkpoint(str(tmp_path / "ckpt"), state)

        target = TrainState.create(
            params, {}, {"momentum": params},
            jax.tree.map(jnp.zeros_like, ef), jax.random.key(0),
            comp=jax.tree.map(jnp.zeros_like, comp))
        restored, _ = restore_checkpoint(str(tmp_path / "ckpt"), target)
        assert set(restored.comp) == set(comp)
        for k in comp:
            assert np.array_equal(np.asarray(restored.comp[k]),
                                  np.asarray(comp[k]))  # bitwise
            assert restored.comp[k].dtype == comp[k].dtype

    def test_stateless_comp_roundtrips_as_empty(self, tmp_path):
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.utils.checkpoint import (
            restore_checkpoint, save_checkpoint)

        params = {"w": jnp.ones((16,))}
        state = TrainState.create(params, {}, {"momentum": params}, (),
                                  jax.random.key(1))
        save_checkpoint(str(tmp_path / "ckpt"), state)
        restored, _ = restore_checkpoint(str(tmp_path / "ckpt"), state)
        assert restored.comp == ()

    def test_pre_comp_checkpoint_still_restores(self, tmp_path, monkeypatch):
        """Back-compat: checkpoints written before TrainState grew `comp`
        have no such key on disk; restore must fall back instead of failing
        Orbax's structure check, keeping the caller's comp — () normally, a
        freshly-built warm start when resuming an old run with powersgd
        newly enabled."""
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.utils import checkpoint as ck

        params = {"w": jnp.arange(4096, dtype=jnp.float32)}
        state = TrainState.create(params, {}, {"momentum": params}, (),
                                  jax.random.key(1))
        orig = ck._to_saveable

        def legacy_saveable(s):
            d = orig(s)
            d.pop("comp")  # what an old writer produced
            return d

        monkeypatch.setattr(ck, "_to_saveable", legacy_saveable)
        ck.save_checkpoint(str(tmp_path / "ckpt"), state)
        monkeypatch.setattr(ck, "_to_saveable", orig)
        restored, _ = ck.restore_checkpoint(str(tmp_path / "ckpt"), state)
        assert restored.comp == ()
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(params["w"]))
        # resuming that same old checkpoint with powersgd newly ON: the
        # freshly-built warm start must survive the fallback restore
        cfg = CompressionConfig(method="powersgd", rank=2)
        comp = init_comp_state(params, cfg)
        target = TrainState.create(params, {}, {"momentum": params}, (),
                                   jax.random.key(0), comp=comp)
        restored2, _ = ck.restore_checkpoint(str(tmp_path / "ckpt"), target)
        assert set(restored2.comp) == set(comp)
        for k in comp:
            np.testing.assert_array_equal(np.asarray(restored2.comp[k]),
                                          np.asarray(comp[k]))

    def test_powersgd_rejected_with_pipeline_parallelism(self):
        from tpu_compressed_dp.models.transformer import LlamaConfig
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.pp_step import make_pp_train_step

        cfg = LlamaConfig(dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
                          vocab_size=64)
        with pytest.raises(NotImplementedError, match="pipeline"):
            make_pp_train_step(
                cfg, SGD(lr=0.1),
                CompressionConfig(method="powersgd", rank=2),
                mesh=None, microbatches=2)
