"""Wire-sparse gradient sync (mode='wire') on the 8-device CPU mesh.

The key guarantees: (1) shared-mask Random-K wire is bit-identical to its
simulate-mode counterpart (same mask derivation, k-element psum vs dense
psum); (2) error-feedback residual + transmitted == accumulated gradient;
(3) the analytic payload accounting reflects a genuinely smaller payload.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from tpu_compressed_dp.ops import wire
from tpu_compressed_dp.parallel.dp import CompressionConfig, init_ef_state, make_grad_sync

# a case that takes 10 s or more on the 8-virtual-device CPU mesh carries
# `slow`; what is left runs in tier-1 on one worker in about two minutes
slow = pytest.mark.slow


def run_sync(mesh, cfg, grads_per_dev, ef=None, seed=0):
    sync = make_grad_sync(cfg, "data")
    if ef is None:
        ef = init_ef_state(jax.tree.map(lambda g: g[0], grads_per_dev), cfg)

    def f(g, e):
        out, new_ef, _, stats = sync(
            jax.tree.map(lambda x: x[0], g), e, (), jax.random.key(seed))
        return out, new_ef, stats

    shard_spec = jax.tree.map(lambda _: P("data"), grads_per_dev)
    fn = shard_map(
        f,
        mesh=mesh,
        in_specs=(shard_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return fn(grads_per_dev, ef)


def make_grads(n=64, seed=0):
    k = jax.random.key(seed)
    return {
        "w": jax.random.normal(k, (8, n), jnp.float32),
        "b": jax.random.normal(jax.random.fold_in(k, 1), (8, 8), jnp.float32),
    }


class TestRandomKWire:
    @slow
    @pytest.mark.parametrize("gran", ["layerwise", "entiremodel"])
    def test_matches_simulate_exactly(self, mesh8, gran):
        grads = make_grads()
        sim = CompressionConfig(
            method="randomk", ratio=0.25, granularity=gran, mode="simulate", shared_mask=True
        )
        wire = CompressionConfig(method="randomk", ratio=0.25, granularity=gran, mode="wire")
        out_s, _, _ = run_sync(mesh8, sim, grads)
        out_w, _, stats = run_sync(mesh8, wire, grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]), rtol=1e-6
            )
        # the wire payload is k elements, not n
        assert float(stats["sent_elems"]) < float(stats["dense_elems"])

    def test_payload_is_exactly_k(self, mesh8):
        grads = {"w": jnp.ones((8, 256), jnp.float32)}
        cfg = CompressionConfig(method="randomk", ratio=0.25, mode="wire")
        _, _, stats = run_sync(mesh8, cfg, grads)
        assert float(stats["sent_elems"]) == 64.0
        assert float(stats["sent_bits"]) == 64.0 * 32  # indices implied by shared key

    def test_rejects_per_worker_masks(self, mesh8):
        cfg = CompressionConfig(method="randomk", ratio=0.25, mode="wire", shared_mask=False)
        with pytest.raises(ValueError, match="shared_mask"):
            run_sync(mesh8, cfg, make_grads())


@slow
class TestTopKWire:
    def test_union_scatter_add(self, mesh8):
        # With distinct per-device top-k index sets, the result is the
        # world-average of per-device k-sparse vectors: verify against a
        # numpy model of exactly-k (no-ties) top-k.
        rng = np.random.default_rng(0)
        g = rng.normal(size=(8, 64)).astype(np.float32)
        cfg = CompressionConfig(method="topk", ratio=0.25, mode="wire")
        out, _, stats = run_sync(mesh8, cfg, {"w": jnp.asarray(g)})

        from tpu_compressed_dp.ops.compressors import topk_keep_count

        k = topk_keep_count(64, 0.25)
        exp = np.zeros(64, np.float32)
        for d in range(8):
            idx = np.argsort(-np.abs(g[d]))[:k]
            dense = np.zeros(64, np.float32)
            dense[idx] = g[d][idx]
            exp += dense
        exp /= 8
        np.testing.assert_allclose(np.asarray(out["w"]), exp, rtol=1e-5)
        assert float(stats["sent_elems"]) == float(k)
        assert float(stats["sent_bits"]) == k * 64.0  # values + explicit indices

    def test_error_feedback_residual(self, mesh8):
        grads = make_grads()
        cfg = CompressionConfig(method="topk", ratio=0.25, mode="wire", error_feedback=True)
        out, ef1, _ = run_sync(mesh8, cfg, grads)
        # device-0 residual: acc minus its own k-sparse transmission
        from tpu_compressed_dp.ops.compressors import topk_keep_count

        g0 = np.asarray(grads["w"])[0]
        k = topk_keep_count(64, 0.25)
        idx = np.argsort(-np.abs(g0))[:k]
        exp_res = g0.copy()
        exp_res[idx] = 0.0
        np.testing.assert_allclose(np.asarray(ef1["w"]), exp_res, rtol=1e-5)


class TestQuantizerWire:
    @slow
    @pytest.mark.parametrize("method", ["terngrad", "qsgd"])
    def test_matches_simulate_with_per_worker_rng(self, mesh8, method):
        # Quantizer wire packs per-worker levels+scale; combined result equals
        # the simulate-mode psum of per-worker dequantised tensors when RNG
        # keys line up.  simulate uses per-worker keys by default; wire
        # derives the same leaf key without a worker fold, so compare with
        # shared_mask=True simulate (identical keys everywhere).
        grads = make_grads()
        sim = CompressionConfig(method=method, mode="simulate", shared_mask=True)
        wire = CompressionConfig(method=method, mode="wire", shared_mask=True)
        out_s, _, _ = run_sync(mesh8, sim, grads)
        out_w, _, stats = run_sync(mesh8, wire, grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]), rtol=1e-5, atol=1e-6
            )
        # quantizers send every element but at reduced width
        assert float(stats["sent_elems"]) == float(stats["dense_elems"])
        assert float(stats["sent_bits"]) < 32.0 * float(stats["dense_elems"])

    def test_ef_rejected_for_quantizers(self, mesh8):
        cfg = CompressionConfig(method="qsgd", mode="wire", error_feedback=True)
        with pytest.raises(ValueError, match="unbiased"):
            run_sync(mesh8, cfg, make_grads())


@pytest.mark.quick
class TestWirePacking:
    """Bit-packing primitives for the quantizer wire payloads (round 4)."""

    @pytest.mark.parametrize("n", [1, 3, 4, 7, 8, 1000])
    def test_ternary_roundtrip(self, n):
        rng = np.random.default_rng(n)
        levels = rng.integers(-1, 2, size=n).astype(np.int8)
        packed = wire.pack_ternary(jnp.asarray(levels))
        assert packed.dtype == jnp.uint8 and packed.shape == ((n + 3) // 4,)
        np.testing.assert_array_equal(
            np.asarray(wire.unpack_ternary(packed, n)), levels)

    @pytest.mark.parametrize("n", [1, 5, 8, 9, 1000])
    def test_bits_roundtrip(self, n):
        rng = np.random.default_rng(n)
        bits = rng.integers(0, 2, size=n).astype(bool)
        packed = wire.pack_bits(jnp.asarray(bits))
        assert packed.dtype == jnp.uint8 and packed.shape == ((n + 7) // 8,)
        np.testing.assert_array_equal(np.asarray(wire.unpack_bits(packed, n)), bits)

    def test_unpack_with_gather_axis(self):
        rng = np.random.default_rng(0)
        levels = rng.integers(-1, 2, size=(3, 10)).astype(np.int8)
        packed = jnp.stack([wire.pack_ternary(jnp.asarray(r)) for r in levels])
        np.testing.assert_array_equal(
            np.asarray(wire.unpack_ternary(packed, 10)), levels)

    @pytest.mark.parametrize("qstates", [15, 127, 200, 255, 1000])
    def test_qsgd_roundtrip(self, qstates):
        rng = np.random.default_rng(qstates)
        levels = rng.integers(-qstates, qstates + 1, size=333).astype(np.int16)
        payload = wire.qsgd_wire_pack(jnp.asarray(levels), qstates)
        widths = {p.dtype.itemsize for p in payload}
        if qstates <= 127:
            assert [p.dtype for p in payload] == [jnp.int8]
        elif qstates <= 255:
            assert [p.dtype for p in payload] == [jnp.uint8, jnp.uint8]
            assert payload[1].size == (333 + 7) // 8  # packed sign bitmap
        else:
            assert widths == {2}
        out = wire.qsgd_wire_unpack(payload, 333, qstates)
        np.testing.assert_array_equal(np.asarray(out), levels.astype(np.float32))


class TestMeasuredTransport:
    """`sent_bits` must equal 8 x the actual bytes handed to the collective
    for EVERY wire method — payload dtypes inspected at trace time, never
    assumed (VERDICT r3 #1; the TPU-static analog of the reference's NIC
    meter, `IMAGENET/training/meter.py:24-47`)."""

    CONFIGS = [
        dict(method="randomk", ratio=0.25),
        dict(method="topk", ratio=0.25),
        dict(method="blocktopk", ratio=0.25, block_size=16),
        dict(method="terngrad"),
        dict(method="terngrad", terngrad_chunk=16),   # chunked [nc] scales
        dict(method="qsgd", qstates=255),             # uint8 mags + sign bitmap
        dict(method="qsgd", qstates=127),             # int8 sign (x) level
        dict(method="qsgd", qstates=300),             # int16 fallback
        dict(method="thresholdv", threshold=0.5, wire_cap_ratio=0.25),
        dict(method="adaptive_threshold", wire_cap_ratio=0.25),
    ]

    @pytest.mark.parametrize("gran", [pytest.param("layerwise", marks=slow),
                                      "entiremodel"])
    @pytest.mark.parametrize("kw", [
        pytest.param(c, id=f"{c['method']}-{i}",
                     marks=[slow] if c["method"] in ("topk", "blocktopk") else [])
        for i, c in enumerate(CONFIGS)])
    def test_sent_bits_is_measured_payload_bytes(self, mesh8, monkeypatch, gran, kw):
        recorded = []

        real_gather = wire._all_gather
        real_psum = jax.lax.psum

        def spy_gather(x, axis_name, **kwargs):
            recorded.append(x.size * x.dtype.itemsize)
            return real_gather(x, axis_name, **kwargs)

        def spy_psum(x, axis_name, **kwargs):
            # payload psums only; the scalar world count is not a payload
            if hasattr(x, "ndim") and x.ndim >= 1:
                recorded.append(x.size * x.dtype.itemsize)
            return real_psum(x, axis_name, **kwargs)

        monkeypatch.setattr(wire, "_all_gather", spy_gather)
        monkeypatch.setattr(jax.lax, "psum", spy_psum)

        cfg = CompressionConfig(mode="wire", granularity=gran, **kw)
        _, _, stats = run_sync(mesh8, cfg, make_grads())
        assert recorded, "no collective payloads observed"
        assert float(stats["sent_bits"]) == 8.0 * sum(recorded)

    def test_terngrad_chunked_wire_matches_simulate(self, mesh8):
        # chunked scales (the entire-model NaN fix) through the WIRE path:
        # per-chunk fp32 scales travel with the int8 levels and the combined
        # result equals simulate mode with the same chunking
        grads = make_grads()
        sim = CompressionConfig(method="terngrad", mode="simulate",
                                granularity="entiremodel", shared_mask=True,
                                terngrad_chunk=16)
        wire = CompressionConfig(method="terngrad", mode="wire",
                                 granularity="entiremodel", shared_mask=True,
                                 terngrad_chunk=16)
        out_s, _, _ = run_sync(mesh8, sim, grads)
        out_w, _, stats = run_sync(mesh8, wire, grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]),
                rtol=1e-5, atol=1e-6)
        assert float(stats["sent_bits_allgather"]) > 0.0


class TestThresholdWire:
    """Fixed-capacity wire Threshold-V / Adaptive-Threshold (6/6 wire
    matrix): survivors pack into a cap-sized buffer; overflow stays in EF."""

    @slow
    @pytest.mark.parametrize("method", ["thresholdv", "adaptive_threshold"])
    def test_matches_simulate_when_capacity_suffices(self, mesh8, method):
        grads = make_grads()
        kw = {"threshold": 0.8} if method == "thresholdv" else {}
        sim = CompressionConfig(method=method, granularity="layerwise", **kw)
        wire = CompressionConfig(method=method, granularity="layerwise",
                                 mode="wire", wire_cap_ratio=1.0, **kw)
        out_s, _, stats_s = run_sync(mesh8, sim, grads)
        out_w, _, stats_w = run_sync(mesh8, wire, grads)
        for k in out_s:
            np.testing.assert_allclose(np.asarray(out_s[k]), np.asarray(out_w[k]),
                                       rtol=1e-5, atol=1e-6)
        assert float(stats_w["threshold_overflow"]) == 0.0
        # both modes count the coordinates that actually survived
        assert float(stats_w["sent_elems"]) == pytest.approx(
            float(stats_s["sent_elems"]))

    def test_overflow_goes_to_ef(self, mesh8):
        # capacity 25% but ~50% of coordinates survive V: the clipped
        # survivors must land in the residual, and sent + residual must
        # reassemble the accumulated gradient exactly
        grads = make_grads(n=256)
        cfg = CompressionConfig(method="thresholdv", threshold=0.5,
                                granularity="entiremodel", mode="wire",
                                wire_cap_ratio=0.25, error_feedback=True)
        out, new_ef, stats = run_sync(mesh8, cfg, grads)
        assert float(stats["threshold_overflow"]) > 0.0
        # device-0 decomposition: gradient == sent + residual, exactly
        sent = {k: np.asarray(grads[k])[0] - np.asarray(new_ef[k])
                for k in grads}
        sent_flat = np.concatenate([sent[k].ravel() for k in sorted(sent)])
        nz = sent_flat[sent_flat != 0.0]
        # every coordinate that travelled exceeded V
        assert np.all(np.abs(nz) >= 0.5)
        # the cap-sized buffer filled completely (more survivors than cap)
        n_total = sum(np.asarray(v)[0].size for v in grads.values())
        cap = round(0.25 * n_total)
        assert len(nz) == cap

    def test_cap_billing_is_static(self, mesh8):
        # transport bills the full cap buffer even when half-empty
        grads = make_grads(n=256)
        cfg = CompressionConfig(method="thresholdv", threshold=100.0,
                                granularity="entiremodel", mode="wire",
                                wire_cap_ratio=0.25)
        _, _, stats = run_sync(mesh8, cfg, grads)
        n_total = 256 + 8
        cap = round(0.25 * n_total)
        assert float(stats["sent_bits"]) == cap * 64.0
        assert float(stats["sent_elems"]) == 0.0  # nothing survived V=100


class TestWireRejections:

    def test_dense_over_wire_falls_back_to_dense_allreduce(self, mesh8):
        # method=None has no sparse form; its wire format IS the dense psum.
        grads = make_grads()
        out, _, stats = run_sync(mesh8, CompressionConfig(method=None, mode="wire"), grads)
        np.testing.assert_allclose(
            np.asarray(out["w"]), np.asarray(grads["w"]).mean(0), rtol=1e-5
        )
        assert float(stats["sent_elems"]) == float(stats["dense_elems"])


@slow
class TestWirePerWorkerDither:
    @pytest.mark.parametrize("method", ["terngrad", "qsgd"])
    def test_per_worker_rng_matches_simulate(self, mesh8, method):
        # shared_mask=False must decorrelate quantisation noise across workers
        # in wire mode exactly as it does in simulate mode (same leaf_key
        # derivation with the worker fold).
        grads = make_grads()
        sim = CompressionConfig(method=method, mode="simulate", shared_mask=False)
        wire = CompressionConfig(method=method, mode="wire", shared_mask=False)
        out_s, _, _ = run_sync(mesh8, sim, grads)
        out_w, _, _ = run_sync(mesh8, wire, grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]), rtol=1e-5, atol=1e-6
            )

    def test_per_worker_differs_from_shared(self, mesh8):
        grads = make_grads()
        out_shared, _, _ = run_sync(
            mesh8, CompressionConfig(method="qsgd", mode="wire", shared_mask=True), grads
        )
        out_pw, _, _ = run_sync(
            mesh8, CompressionConfig(method="qsgd", mode="wire", shared_mask=False), grads
        )
        assert not np.allclose(np.asarray(out_shared["w"]), np.asarray(out_pw["w"]))


@slow
class TestWireTrainStep:
    def test_full_step_with_wire_randomk(self, mesh8):
        """The whole train step compiles and runs with a wire-sparse sync."""
        from tpu_compressed_dp.harness.dawn import MODELS
        from tpu_compressed_dp.models.common import init_model, make_apply_fn
        from tpu_compressed_dp.train.optim import SGD
        from tpu_compressed_dp.train.state import TrainState
        from tpu_compressed_dp.train.step import make_train_step

        module = MODELS["resnet9"](0.125)
        params, stats = init_model(
            module, jax.random.key(0), jnp.zeros((1, 32, 32, 3), jnp.float32)
        )
        opt = SGD(lr=0.01, momentum=0.9)
        cfg = CompressionConfig(
            method="randomk", ratio=0.1, mode="wire", error_feedback=True
        )
        state = TrainState.create(
            params, stats, opt.init(params), init_ef_state(params, cfg, 8), jax.random.key(1)
        )
        step = make_train_step(make_apply_fn(module), opt, cfg, mesh8)
        batch = {
            "input": jnp.zeros((16, 32, 32, 3), jnp.float32),
            "target": jnp.zeros((16,), jnp.int32),
        }
        state, metrics = step(state, batch)
        assert int(state.step) == 1
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["comm/sent_elems"]) < float(metrics["comm/dense_elems"])


class TestCheckSync:
    """The ``check_reduction`` analog: wire Random-K verifies cross-worker
    index agreement before the packed psum."""

    def _sync_with_keys(self, mesh8, key_fn):
        cfg = CompressionConfig(method="randomk", ratio=0.1, mode="wire",
                                check_sync=True)
        sync = make_grad_sync(cfg, "data")

        def f(g):
            return sync({"w": g[0]}, (), (), key_fn())[3]

        return shard_map(
            f, mesh=mesh8, in_specs=P("data"), out_specs=P(),
        )(jnp.ones((8, 4096)))

    def test_shared_key_agrees(self, mesh8):
        stats = self._sync_with_keys(mesh8, lambda: jax.random.key(0))
        assert float(stats["sync_agree"]) == 1.0

    def test_diverged_keys_detected(self, mesh8):
        def per_worker_key():
            return jax.random.fold_in(jax.random.key(0),
                                      jax.lax.axis_index("data"))

        # out_specs P() would reject the device-varying stats of diverged
        # masks at the type level; run with varying out to read the flag
        cfg = CompressionConfig(method="randomk", ratio=0.1, mode="wire",
                                check_sync=True)
        sync = make_grad_sync(cfg, "data")

        def f(g):
            stats = sync({"w": g[0]}, (), (), per_worker_key())[3]
            return stats["sync_agree"].reshape(1)

        agree = shard_map(f, mesh=mesh8, in_specs=P("data"),
                          out_specs=P("data"))(jnp.ones((8, 4096)))
        assert float(jnp.min(agree)) == 0.0


def test_packed_indices_underfull_mask_degrades_benignly():
    """Ranks beyond the mask's true count fill with index 0, matching
    jnp.nonzero(size=, fill_value=0) (the documented precondition guard)."""
    from tpu_compressed_dp.ops.wire import packed_indices_from_mask

    mask = jnp.zeros((1000,), bool).at[jnp.array([3, 500, 999])].set(True)
    idx = packed_indices_from_mask(mask, 8)
    np.testing.assert_array_equal(
        np.asarray(idx), np.asarray(jnp.nonzero(mask, size=8, fill_value=0)[0]))


@pytest.mark.quick
@slow
def test_packed_indices_exact_oracle_across_shapes():
    """Pack v2 (r5: fused row-starts gather + bf16 tri-matmul) must stay
    bit-identical to ``np.flatnonzero(mask)[:keep]`` padded with 0 — the
    oracle the round-5 rewrite was verified against — across row-boundary
    shapes, densities, and keep <, ==, > count."""
    from tpu_compressed_dp.ops.wire import packed_indices_from_mask

    rng = np.random.default_rng(7)
    for n in (5, 127, 128, 129, 1000, 4096):
        for frac in (0.02, 0.3, 0.9):
            mask = rng.random(n) < frac
            count = int(mask.sum())
            for keep in {1, max(1, count // 2), max(count, 1),
                         min(count + 3, n)}:
                got = np.asarray(
                    packed_indices_from_mask(jnp.asarray(mask), int(keep)))
                want = np.flatnonzero(mask)[:keep]
                want = np.pad(want, (0, keep - len(want)))
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"n={n} frac={frac} "
                                                      f"keep={keep}")


class TestBlockTopKWire:
    """Net-new blocktopk: whole contiguous blocks travel as lane-aligned rows."""

    @slow
    @pytest.mark.parametrize("gran", ["layerwise", "entiremodel"])
    def test_matches_simulate_exactly(self, mesh8, gran):
        grads = make_grads()
        sim = CompressionConfig(method="blocktopk", ratio=0.25, granularity=gran,
                                mode="simulate", block_size=16)
        wire = CompressionConfig(method="blocktopk", ratio=0.25, granularity=gran,
                                 mode="wire", block_size=16)
        out_s, _, _ = run_sync(mesh8, sim, grads)
        out_w, _, stats = run_sync(mesh8, wire, grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]), rtol=1e-6
            )
        assert float(stats["sent_elems"]) < float(stats["dense_elems"])

    @slow
    def test_union_scatter_add(self, mesh8):
        # distinct per-device block sets -> world-average of block-sparse
        # vectors; verify against a numpy model
        rng = np.random.default_rng(1)
        g = rng.normal(size=(8, 64)).astype(np.float32)
        bs, ratio = 8, 0.25
        cfg = CompressionConfig(method="blocktopk", ratio=ratio, mode="wire", block_size=bs)
        out, _, stats = run_sync(mesh8, cfg, {"w": jnp.asarray(g)})

        from tpu_compressed_dp.ops.compressors import blocktopk_keep_blocks

        kb = blocktopk_keep_blocks(64, ratio, bs)
        exp = np.zeros(64, np.float32)
        for d in range(8):
            scores = (g[d].reshape(-1, bs) ** 2).sum(axis=1)
            sel = np.argsort(-scores)[:kb]
            dense = np.zeros(64, np.float32)
            for b in sel:
                dense[b * bs:(b + 1) * bs] = g[d][b * bs:(b + 1) * bs]
            exp += dense
        exp /= 8
        np.testing.assert_allclose(np.asarray(out["w"]), exp, rtol=1e-5)
        assert float(stats["sent_elems"]) == float(kb * bs)
        # 32-bit values + one 32-bit index per block
        assert float(stats["sent_bits"]) == kb * bs * (32.0 + 32.0 / bs)

    @slow
    def test_error_feedback_residual(self, mesh8):
        grads = make_grads()
        bs = 16
        cfg = CompressionConfig(method="blocktopk", ratio=0.25, mode="wire",
                                block_size=bs, error_feedback=True)
        out, ef1, _ = run_sync(mesh8, cfg, grads)
        from tpu_compressed_dp.ops.compressors import blocktopk_keep_blocks

        g0 = np.asarray(grads["w"])[0]
        kb = blocktopk_keep_blocks(64, 0.25, bs)
        scores = (g0.reshape(-1, bs) ** 2).sum(axis=1)
        sel = np.argsort(-scores)[:kb]
        exp_res = g0.copy()
        for b in sel:
            exp_res[b * bs:(b + 1) * bs] = 0.0
        np.testing.assert_allclose(np.asarray(ef1["w"]), exp_res, rtol=1e-5)

    def test_small_leaf_dense_fallback(self, mesh8):
        # leaves <= block_size keep their only (padded) block; the wire path
        # must psum them dense rather than inflate to a padded block row
        grads = {"small": jnp.broadcast_to(jnp.arange(8, 10, 0.2, dtype=jnp.float32), (8, 10))}
        cfg = CompressionConfig(method="blocktopk", ratio=0.25, mode="wire",
                                block_size=256, error_feedback=True)
        out, ef1, stats = run_sync(mesh8, cfg, grads)
        assert float(stats["sent_elems"]) == 10.0  # n, not block_size
        np.testing.assert_allclose(np.asarray(out["small"]),
                                   np.asarray(grads["small"])[0], rtol=1e-6)
        np.testing.assert_allclose(np.asarray(ef1["small"]), np.zeros(10))



    @slow
    def test_small_bs_ef_immune_to_inf_in_sent_block(self, mesh8):
        """Covering-row EF (r5): a sent block containing inf must leave the
        residual finite and zeroed there — a scatter-multiply formulation
        would produce inf*0 = NaN and poison error feedback permanently
        (caught in r5 review; the mask-accumulate + where form is immune)."""
        from tpu_compressed_dp.ops import wire as wire_mod

        def f(flat):
            world = jax.lax.psum(1, "data")
            dense, ef, bits = wire_mod._leaf_sync_blocktopk(
                flat[0], 2, 8, "data", world, True)
            return dense, ef[None]

        g = np.random.default_rng(0).standard_normal(256).astype(np.float32)
        g[5] = np.inf
        gb = jnp.broadcast_to(jnp.asarray(g), (8, 256))
        dense, ef = shard_map(f, mesh=mesh8, in_specs=P("data"),
                              out_specs=(P(), P("data")))(gb)
        ef0 = np.asarray(ef)[0]
        assert np.isfinite(ef0).all()
        assert (ef0[0:8] == 0).all()

    def test_topk_poisoned_tail_keeps_payload_monotone(self, mesh8):
        """Poisoned-tail regression (histogram-edge clamp): a NaN in the
        gradient must not collapse the top-k histogram edges — pre-clamp a
        non-finite ``max(mag)`` made every edge NaN, the survivor count
        dropped below ``keep``, and the underfull pack padded duplicate
        index 0, voiding the sorted/unique scatter hints downstream.  The
        select must stay a veto (NaN never travels) with a full, strictly
        monotone payload."""
        from tpu_compressed_dp.ops import wire as wire_mod

        n, keep = 70000, 700
        g = np.random.default_rng(1).standard_normal(n).astype(np.float32)
        g[123] = np.nan
        flat = jnp.asarray(g)
        from tpu_compressed_dp.ops import kernels
        t = kernels.topk_threshold(jnp.abs(flat).astype(jnp.float32), keep)
        _, idx, count = wire_mod._select_pack(
            flat, jnp.abs(flat).astype(jnp.float32), t, keep)
        assert int(count) >= keep            # no underfull pack
        assert bool(wire_mod.packed_indices_monotone(idx))
        assert 123 not in np.asarray(idx)    # the NaN coordinate is vetoed

@slow
class TestBucketedWire:
    def test_bucketed_wire_matches_simulate(self, mesh8):
        # multi-leaf buckets through the wire path: same grouping and keys as
        # simulate mode, so shared-mask randomk agrees exactly
        grads = make_grads()
        kw = dict(method="randomk", ratio=0.25, granularity="bucketed",
                  bucket_mb=256 / 1e6, shared_mask=True)
        out_s, _, _ = run_sync(mesh8, CompressionConfig(mode="simulate", **kw), grads)
        out_w, _, stats = run_sync(mesh8, CompressionConfig(mode="wire", **kw), grads)
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(out_s[leaf]), np.asarray(out_w[leaf]), rtol=1e-6)
        assert float(stats["num_collectives"]) == 2.0
        assert float(stats["sent_elems"]) < float(stats["dense_elems"])

    def test_bucketed_wire_ef_topk(self, mesh8):
        grads = make_grads()
        cfg = CompressionConfig(method="topk", ratio=0.25, granularity="bucketed",
                                bucket_mb=256 / 1e6, mode="wire", error_feedback=True)
        out, ef1, _ = run_sync(mesh8, cfg, grads)
        from tpu_compressed_dp.ops.compressors import topk_keep_count

        g0 = np.asarray(grads["w"])[0]
        k = topk_keep_count(64, 0.25)
        idx = np.argsort(-np.abs(g0))[:k]
        exp_res = g0.copy()
        exp_res[idx] = 0.0
        np.testing.assert_allclose(np.asarray(ef1["w"]), exp_res, rtol=1e-5)
