"""Tests for the compressed-DP gradient sync engine on an 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from tpu_compressed_dp.parallel.dp import CompressionConfig, init_ef_state, make_grad_sync


def run_sync(mesh, cfg, grads_per_dev, ef=None, seed=0, comp=None):
    """grads_per_dev: pytree whose leaves have leading dim 8 (one slice per device)."""
    from tpu_compressed_dp.parallel.dp import init_comp_state

    sync = make_grad_sync(cfg, "data")
    if ef is None:
        ef = init_ef_state(jax.tree.map(lambda g: g[0], grads_per_dev), cfg)
    if comp is None:
        comp = init_comp_state(jax.tree.map(lambda g: g[0], grads_per_dev), cfg)

    def f(g, e, c):
        out, new_ef, new_comp, stats = sync(g, e, c, jax.random.key(seed))
        return out, new_ef, new_comp, stats

    shard_spec = jax.tree.map(lambda _: P("data"), grads_per_dev)
    # one slice per device in, replicated grads out
    fn = shard_map(
        lambda g, e, c: f(jax.tree.map(lambda x: x[0], g), e, c),
        mesh=mesh,
        in_specs=(shard_spec, P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    out, new_ef, new_comp, stats = fn(grads_per_dev, ef, comp)
    return out, new_ef, stats


def make_grads(shape_leading=8, n=64, seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(k, (shape_leading, n), jnp.float32),
        "b": jax.random.normal(jax.random.fold_in(k, 1), (shape_leading, 8), jnp.float32),
    }


class TestDense:
    def test_dense_sync_is_mean(self, mesh8):
        cfg = CompressionConfig(method=None)
        grads = make_grads()
        out, _, stats = run_sync(mesh8, cfg, grads)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(grads["w"]).mean(0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out["b"]), np.asarray(grads["b"]).mean(0), rtol=1e-5)
        assert float(stats["sent_elems"]) >= 0

    def test_entiremodel_dense_matches_layerwise(self, mesh8):
        grads = make_grads()
        out_l, _, _ = run_sync(mesh8, CompressionConfig(method=None, granularity="layerwise"), grads)
        out_e, _, _ = run_sync(mesh8, CompressionConfig(method=None, granularity="entiremodel"), grads)
        for k in out_l:
            np.testing.assert_allclose(np.asarray(out_l[k]), np.asarray(out_e[k]), rtol=1e-5)


class TestCompressed:
    @pytest.mark.parametrize("gran", ["layerwise", "entiremodel"])
    def test_topk_sync(self, mesh8, gran):
        cfg = CompressionConfig(method="topk", ratio=0.25, granularity=gran)
        grads = make_grads()
        out, _, stats = run_sync(mesh8, cfg, grads)
        # Every device compresses its own slice then the results are averaged:
        # reconstruct expected value with the numpy reference.
        from tpu_compressed_dp.ops import compressors as C

        if gran == "layerwise":
            exp_w = np.mean(
                [np.asarray(C.top_k(grads["w"][d], ratio=0.25)) for d in range(8)], axis=0
            )
            np.testing.assert_allclose(np.asarray(out["w"]), exp_w, rtol=1e-5)
        assert float(stats["sent_elems"]) < float(stats["dense_elems"])

    def test_randomk_per_worker_masks_differ_in_simulate(self, mesh8):
        # simulate mode folds the worker index into the key (unseeded CIFAR
        # harness analog): per-device masks differ, so the averaged result has
        # more nonzeros than one mask's worth.
        cfg = CompressionConfig(method="randomk", ratio=0.25, granularity="layerwise")
        grads = {"w": jnp.ones((8, 256), jnp.float32)}
        out, _, _ = run_sync(mesh8, cfg, grads)
        nnz = int(jnp.count_nonzero(out["w"]))
        assert nnz > 64  # > one mask's keep count => masks differed across devices

    def test_randomk_shared_mask(self, mesh8):
        cfg = CompressionConfig(method="randomk", ratio=0.25, shared_mask=True)
        grads = {"w": jnp.ones((8, 256), jnp.float32)}
        out, _, _ = run_sync(mesh8, cfg, grads)
        nnz = int(jnp.count_nonzero(out["w"]))
        assert nnz == 64  # identical masks across devices

    def test_num_collectives(self, mesh8):
        grads = make_grads()
        _, _, s_l = run_sync(mesh8, CompressionConfig(method="topk", ratio=0.5), grads)
        _, _, s_e = run_sync(
            mesh8, CompressionConfig(method="topk", ratio=0.5, granularity="entiremodel"), grads
        )
        assert float(s_l["num_collectives"]) == 2.0  # one per parameter tensor
        assert float(s_e["num_collectives"]) == 1.0  # one for the whole model


class TestErrorFeedback:
    def test_residual_property(self, mesh8):
        # compressed + residual == accumulated gradient, per leaf per device.
        cfg = CompressionConfig(method="topk", ratio=0.25, error_feedback=True, shared_mask=True)
        grads = make_grads()
        out, new_ef, _ = run_sync(mesh8, cfg, grads)
        assert set(new_ef.keys()) == {"w", "b"}
        # After one step from zero EF: residual = g_local - compress(g_local).
        # Top-K is deterministic, so recompute device 0's compression directly.
        from tpu_compressed_dp.ops import compressors as C

        for leaf in ("w", "b"):
            g0 = np.asarray(grads[leaf])[0]
            res0 = np.asarray(new_ef[leaf])
            comp0 = np.asarray(C.top_k(jnp.asarray(g0), ratio=0.25))
            np.testing.assert_allclose(res0, g0 - comp0, rtol=1e-6)

    def test_ef_accumulates_small_grads(self, mesh8):
        # A coordinate never selected by Top-K accumulates in the residual so
        # it is eventually sent (the EF convergence mechanism).
        cfg = CompressionConfig(method="topk", ratio=0.05, error_feedback=True)
        g = jnp.concatenate([jnp.full((5,), 10.0), jnp.linspace(0.01, 0.1, 95)])
        grads = {"w": jnp.tile(g[None, :], (8, 1))}
        ef = {"w": jnp.zeros((100,), jnp.float32)}
        out, ef1, _ = run_sync(mesh8, cfg, grads, ef=ef)
        # small coords went to residual
        assert float(jnp.sum(jnp.abs(ef1["w"]))) > 0
        out2, ef2, _ = run_sync(mesh8, cfg, grads, ef=ef1, seed=1)
        # residual keeps growing for untransmitted coords
        assert float(jnp.max(ef2["w"])) >= float(jnp.max(ef1["w"]))


class TestBucketedGranularity:
    """granularity='bucketed': the reference DDP's static 25MB bucketing
    (`ddp.py:188,238-241`) — contiguous leaves concatenated into capped
    groups, one operator + one collective per bucket."""

    def test_make_leaf_groups(self):
        from tpu_compressed_dp.parallel.dp import make_leaf_groups

        # byte sizes (size * itemsize), ADVICE r1: bf16 leaves pack at their
        # real density, not a hardcoded 4 bytes/elem
        sizes = [400, 400, 1200, 200, 2400, 40]
        groups = make_leaf_groups(sizes, "bucketed", 800.0)
        assert groups == [[0, 1], [2], [3], [4], [5]]
        assert make_leaf_groups(sizes, "layerwise", 800.0) == [[i] for i in range(6)]
        assert make_leaf_groups(sizes, "entiremodel", 800.0) == [list(range(6))]
        assert make_leaf_groups([], "entiremodel", 800.0) == []
        # oversized single leaf still gets its own bucket
        assert make_leaf_groups([10**9], "bucketed", 800.0) == [[0]]
        # half-width leaves fill a bucket at twice the element count
        assert make_leaf_groups([400, 400, 400, 400], "bucketed", 800.0) == [
            [0, 1], [2, 3]]

    def test_mixed_dtype_group_keeps_leaf_dtypes_and_fp32_ef(self, mesh8):
        # ADVICE r1: concatenating bf16+fp32 leaves promotes; the synced
        # grads must come back at each leaf's dtype while the EF residual
        # stays fp32 (sub-bf16-epsilon dropped mass must accumulate).
        k = jax.random.key(3)
        grads = {
            "a": jax.random.normal(k, (8, 48), jnp.float32).astype(jnp.bfloat16),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (8, 32), jnp.float32),
        }
        cfg = CompressionConfig(method="topk", ratio=0.25, granularity="bucketed",
                                bucket_mb=1e-3, error_feedback=True)
        out, new_ef, _ = run_sync(mesh8, cfg, grads)
        assert out["a"].dtype == jnp.bfloat16 and out["b"].dtype == jnp.float32
        assert new_ef["a"].dtype == jnp.float32 and new_ef["b"].dtype == jnp.float32

    def test_dense_bucketed_equals_layerwise(self, mesh8):
        grads = make_grads()
        cfg_b = CompressionConfig(method=None, granularity="bucketed", bucket_mb=1e-4)
        cfg_l = CompressionConfig(method=None, granularity="layerwise")
        out_b, _, stats_b = run_sync(mesh8, cfg_b, grads)
        out_l, _, _ = run_sync(mesh8, cfg_l, grads)
        for leaf in out_b:
            np.testing.assert_allclose(
                np.asarray(out_b[leaf]), np.asarray(out_l[leaf]), rtol=1e-6)

    def test_bucket_count_and_collectives(self, mesh8):
        # leaves: w 64 elems (256B), b 8 elems (32B); capacity 256B -> 2 buckets
        grads = make_grads()
        cfg = CompressionConfig(method="topk", ratio=0.25, granularity="bucketed",
                                bucket_mb=256 / 1e6, shared_mask=True)
        _, _, stats = run_sync(mesh8, cfg, grads)
        assert float(stats["num_collectives"]) == 2.0
        # huge capacity -> one bucket, entiremodel-equivalent selection
        cfg1 = CompressionConfig(method="topk", ratio=0.25, granularity="bucketed",
                                 bucket_mb=25.0, shared_mask=True)
        out1, _, stats1 = run_sync(mesh8, cfg1, grads)
        cfg_e = CompressionConfig(method="topk", ratio=0.25, granularity="entiremodel",
                                  shared_mask=True)
        out_e, _, _ = run_sync(mesh8, cfg_e, grads)
        assert float(stats1["num_collectives"]) == 1.0
        for leaf in out1:
            np.testing.assert_allclose(
                np.asarray(out1[leaf]), np.asarray(out_e[leaf]), rtol=1e-6)

    def test_ef_residual_identity_bucketed(self, mesh8):
        # residual + transmitted == accumulated gradient, per worker
        grads = make_grads()
        cfg = CompressionConfig(method="topk", ratio=0.25, granularity="bucketed",
                                bucket_mb=256 / 1e6, error_feedback=True)
        out, ef1, _ = run_sync(mesh8, cfg, grads)
        from tpu_compressed_dp.ops.compressors import topk_keep_count

        g0 = np.asarray(grads["w"])[0]
        k = topk_keep_count(64, 0.25)
        idx = np.argsort(-np.abs(g0))[:k]
        exp_res = g0.copy()
        exp_res[idx] = 0.0
        np.testing.assert_allclose(np.asarray(ef1["w"]), exp_res, rtol=1e-5)

    def test_rejects_bad_bucket_mb(self):
        with pytest.raises(ValueError, match="bucket_mb"):
            CompressionConfig(method="topk", granularity="bucketed", bucket_mb=0.0)


class TestFusedSimulateEpilogue:
    def test_fused_topk_path_matches_unfused(self, mesh8, monkeypatch):
        """The TPU-only fused sparsify epilogue must produce identical synced
        grads, EF residuals, and comm stats to the unfused chain (forced on
        via interpret-mode here; CPU CI never dispatches it otherwise)."""
        import functools
        from tpu_compressed_dp.ops import kernels

        grads = make_grads(n=700)
        cfg = CompressionConfig(method="topk", ratio=0.1,
                                granularity="entiremodel", error_feedback=True)
        out_ref, ef_ref, stats_ref = run_sync(mesh8, cfg, grads)

        monkeypatch.setattr(kernels, "use_fused_sparsify", lambda n: True)
        monkeypatch.setattr(kernels, "fused_sparsify",
                            functools.partial(kernels.fused_sparsify,
                                              interpret=True))
        out_f, ef_f, stats_f = run_sync(mesh8, cfg, grads)
        for k in out_ref:
            np.testing.assert_allclose(np.asarray(out_ref[k]),
                                       np.asarray(out_f[k]), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(ef_ref[k]),
                                       np.asarray(ef_f[k]), rtol=1e-6)
        assert float(stats_f["sent_elems"]) == float(stats_ref["sent_elems"])
        assert float(stats_f["sent_bits"]) == float(stats_ref["sent_bits"])


@pytest.mark.quick
class TestTerngradChunkResolution:
    """terngrad_chunk=-1 (auto, ADVICE r3): layerwise keeps the reference's
    exact per-tensor global-max semantics on every leaf size; chunked scales
    apply only where the reference has no working behavior to match."""

    def test_auto_layerwise_is_per_tensor_max(self):
        assert CompressionConfig(method="terngrad",
                                 granularity="layerwise").resolved_terngrad_chunk == 0

    def test_auto_entiremodel_and_bucketed_chunk(self):
        for gran in ("entiremodel", "bucketed"):
            assert CompressionConfig(
                method="terngrad",
                granularity=gran).resolved_terngrad_chunk == 1 << 21

    def test_explicit_value_wins(self):
        for gran in ("layerwise", "entiremodel"):
            cfg = CompressionConfig(method="terngrad", granularity=gran,
                                    terngrad_chunk=4096)
            assert cfg.resolved_terngrad_chunk == 4096
        assert CompressionConfig(
            method="terngrad", granularity="entiremodel",
            terngrad_chunk=0).resolved_terngrad_chunk == 0


def test_merged_stats_are_traced_in_one_order():
    """The adds merge_stat_dicts traces end up in the compiled step; a
    hash-ordered walk over the keys would change the program from process
    to process (PYTHONHASHSEED) and the persistent compile cache would
    never hit (found on the chip: the LM step missed a warm cache)."""
    from tpu_compressed_dp.parallel.dp import merge_stat_dicts

    keys = [f"stat_{i}" for i in range(40)]
    a = {k: 1.0 for k in keys[:30]}
    b = {k: 2.0 for k in keys[10:]}
    merged = merge_stat_dicts(a, b)
    assert list(merged) == sorted(keys)
    assert merged["stat_5"] == 1.0 and merged["stat_20"] == 3.0
