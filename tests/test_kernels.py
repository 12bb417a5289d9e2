"""Pallas kernel tests (interpret mode on the CPU mesh backend).

The kernels must agree with the pure-JAX reference operators in
:mod:`tpu_compressed_dp.ops.compressors`:
  * Top-K histogram threshold selects exactly the same coordinate set as the
    exact ``lax.top_k`` threshold for tie-free data;
  * the fused quantizers produce levels with the right range, sign, and
    (for QSGD) unbiasedness, from their own hardware-PRNG stream.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.ops import compressors, kernels


@pytest.fixture(autouse=True)
def _pallas_off_dispatch():
    # unit-test the kernels directly (interpret mode); keep auto-dispatch from
    # engaging inside compressor calls on the CPU backend
    kernels.set_pallas_mode("off")
    yield
    kernels.set_pallas_mode("auto")


class TestTopkThreshold:
    def _exact(self, mag, keep):
        return jax.lax.top_k(mag, keep)[0][-1]

    @pytest.mark.parametrize("n,keep", [(5000, 500), (8192, 1), (300, 299), (70000, 7000)])
    def test_matches_exact_selection(self, n, keep):
        mag = jnp.abs(jax.random.normal(jax.random.key(n + keep), (n,)))
        t = kernels._topk_threshold_pallas(mag, keep, interpret=True)
        exact = self._exact(mag, keep)
        # identical coordinate sets (data is tie-free at kernel resolution)
        np.testing.assert_array_equal(np.asarray(mag >= t), np.asarray(mag >= exact))
        assert int(jnp.sum(mag >= t)) == keep

    @pytest.mark.parametrize("keep_frac", [0.01, 0.1])
    def test_sampled_init_large_n(self, keep_frac):
        # large n + moderate keep engages the sampled-init fast path (slab
        # subsample -> quantile-edge round -> 3 narrow rounds; the gate
        # requires the sample to be <= n/16, true here); the count >= keep
        # guarantee and tie-level surplus must hold there too
        n = 1 << 22
        keep = max(1, int(n * keep_frac))
        mag = jnp.abs(jax.random.normal(jax.random.key(7), (n,)))
        t = kernels._topk_threshold_pallas(mag, keep, interpret=True)
        cnt = int(jnp.sum(mag >= t))
        assert cnt >= keep
        assert cnt <= keep + 256  # surplus at final-bin resolution only

    def test_small_or_dense_keep_uses_exact_full_path(self):
        # mid-size tensors (sample can't be << data) must take the exact
        # full-range histogram: tie-exact count
        n = 1 << 18
        keep = 262
        mag = jnp.abs(jax.random.normal(jax.random.key(9), (n,)))
        t = kernels._topk_threshold_pallas(mag, keep, interpret=True)
        assert int(jnp.sum(mag >= t)) == keep

    def test_sampled_init_adversarial_layout_keeps_guarantee(self):
        # the slab sample reads the first 128 lanes of each C-block (C=4096
        # for this n/keep); hide MORE than `keep` spikes in the unsampled
        # lanes so every sample quantile is noise-level and the k-th
        # magnitude lands in the huge top bin.  The structural guarantee
        # (count >= keep; refine rounds shrink the surplus) must survive
        # this worst case — there is deliberately no data-dependent branch
        # (a cond would run both sides under shard_map).
        n = 1 << 22
        keep = int(n * 0.1)
        base = jnp.abs(jax.random.normal(jax.random.key(8), (n,))) * 1e-3
        lanes = jnp.arange(n) % 4096
        spike = lanes >= 128  # every lane the slab sample never reads
        vals = 100.0 + (jnp.arange(n) % 977).astype(jnp.float32) / 977.0
        mag = jnp.where(spike, vals, base)
        t = kernels._topk_threshold_pallas(mag, keep, interpret=True)
        cnt = int(jnp.sum(mag >= t))
        assert cnt >= keep
        # degraded-case surplus is bounded by the selected bin's population
        # after 16^3 refinement (~4% here); EF reabsorbs the boundary
        # elements the fixed-size pack then drops
        assert cnt <= int(keep * 1.05)
        assert float(t) > 1.0  # found the spikes, not the base noise

    def test_ties_all_kept(self):
        mag = jnp.ones((4096,))
        t = kernels._topk_threshold_pallas(mag, 100, interpret=True)
        assert int(jnp.sum(mag >= t)) == 4096  # reference keeps ties (core.py:183)

    def test_all_zero(self):
        mag = jnp.zeros((2048,))
        t = kernels._topk_threshold_pallas(mag, 10, interpret=True)
        assert int(jnp.sum(mag >= t)) == 2048

    def test_keep_all_shortcut(self):
        mag = jnp.abs(jax.random.normal(jax.random.key(0), (128,)))
        assert float(kernels.topk_threshold(mag, 128)) == 0.0

    def test_dispatch_cpu_is_exact(self):
        g = jax.random.normal(jax.random.key(1), (1 << 17,))
        out = compressors.top_k(g, ratio=0.01)
        keep = compressors.topk_keep_count(g.shape[0], 0.01)
        assert int(jnp.count_nonzero(out)) == keep


class TestQuantKernels:
    """Interpret-mode PRNG is a zero stub on CPU (dither u == 0), so these
    cover everything EXCEPT the dither draw: with u=0 QSGD degenerates to
    deterministic truncation — range, sign, dtype, and scale stay testable.
    The dither itself (unbiasedness, per-key determinism) is validated on
    real hardware by ``chip_smoke.py``'s kernel phase."""

    def test_qsgd_levels_range_sign(self):
        g = jax.random.normal(jax.random.key(2), (20000,))
        levels, scale = kernels.qsgd_quantize(g, jax.random.key(3), qstates=255,
                                              interpret=True)
        assert levels.dtype == jnp.int16
        lv = np.asarray(levels)
        assert np.all(np.abs(lv) <= 255)
        nz = lv != 0
        assert np.all(np.sign(lv[nz]) == np.sign(np.asarray(g)[nz]))
        # u=0 -> levels == floor(|g|/norm * s) exactly
        ref = np.floor(np.abs(np.asarray(g)) / np.linalg.norm(np.asarray(g)) * 255)
        np.testing.assert_array_equal(np.abs(lv), ref)
        assert float(scale) == pytest.approx(
            float(jnp.linalg.norm(g)) / 255, rel=1e-6)

    def test_terngrad_levels(self):
        g = jax.random.normal(jax.random.key(7), (12000,))
        levels, scale = kernels.terngrad_quantize(g, jax.random.key(8), interpret=True)
        assert levels.dtype == jnp.int8
        lv = np.asarray(levels)
        assert set(np.unique(lv)) <= {-1, 0, 1}
        nz = lv != 0
        assert np.all(np.sign(lv[nz]) == np.sign(np.asarray(g)[nz]))
        assert float(scale) == pytest.approx(float(jnp.max(jnp.abs(g))))

    def test_zero_grad_maps_to_zero(self):
        g = jnp.zeros((8192,))
        lq, sq = kernels.qsgd_quantize(g, jax.random.key(9), interpret=True)
        lt, st = kernels.terngrad_quantize(g, jax.random.key(9), interpret=True)
        assert not np.asarray(lq).any() and not np.asarray(lt).any()
        assert float(sq) == 0.0 and float(st) == 0.0


class TestFusedSparsify:
    """The simulate-mode fused epilogue must match the unfused
    where/subtract/count chain exactly."""

    @pytest.mark.parametrize("want_ef", [True, False])
    def test_matches_unfused(self, want_ef):
        n = 5000
        acc = jax.random.normal(jax.random.key(1), (n,))
        t = kernels.topk_threshold(jnp.abs(acc), 500)
        comp, new_ef, cnt = kernels.fused_sparsify(acc, t, want_ef=want_ef,
                                                   interpret=True)
        exp_comp = jnp.where(jnp.abs(acc) >= t, acc, 0.0)
        np.testing.assert_allclose(np.asarray(comp), np.asarray(exp_comp),
                                   rtol=1e-6)
        if want_ef:
            np.testing.assert_allclose(np.asarray(new_ef),
                                       np.asarray(acc - exp_comp), rtol=1e-6)
        else:
            assert new_ef is None
        assert int(cnt) == int(jnp.count_nonzero(exp_comp))

    def test_zero_threshold_counts_nonzeros_only(self):
        # t == 0 keeps every real coordinate; the pad tail AND exact zeros
        # must not count as sent (count_nonzero parity with the unfused path)
        n = 200  # far from a chunk multiple
        acc = jnp.ones((n,)).at[7].set(0.0).at[100].set(0.0)
        comp, new_ef, cnt = kernels.fused_sparsify(
            acc, jnp.float32(0.0), interpret=True)
        assert int(cnt) == n - 2
        np.testing.assert_allclose(np.asarray(comp), np.asarray(acc))
        np.testing.assert_allclose(np.asarray(new_ef), np.zeros(n))


def test_topk_threshold_jnp_fallback_guarantee():
    """The pure-jnp histogram (the >int32 fallback) keeps the structural
    count(mag >= t) >= keep guarantee with tie-resolution surplus only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_compressed_dp.ops.kernels import _topk_threshold_jnp

    for seed, n, keep in [(0, 4096, 41), (1, 1000, 1), (2, 8192, 8000)]:
        mag = jnp.abs(jax.random.normal(jax.random.key(seed), (n,)))
        t = _topk_threshold_jnp(mag, keep)
        cnt = int(jnp.sum(mag >= t))
        assert cnt >= keep
        exact = float(jax.lax.top_k(mag, keep)[0][-1])
        # threshold within the refinement resolution of the exact k-th value
        assert float(t) <= exact
        assert cnt <= keep + max(8, int(0.01 * n))


class TestFusedSelectPack:
    """One-pass select+pack vs the XLA mask -> packed_indices_from_mask ->
    sorted-gather chain: the payloads must be BITWISE identical (values,
    indices, and survivor count) whenever the mask fills the buffer —
    exactly the regime the top-k histogram threshold guarantees."""

    def _xla(self, flat, mag, t, keep):
        from tpu_compressed_dp.ops import wire

        mask = mag >= t
        idx = wire.packed_indices_from_mask(mask, keep)
        return (wire._sorted_gather(flat, idx), idx,
                jnp.sum(mask, dtype=jnp.int32))

    # the multi-chunk ragged case in both dtypes, the keep=1 and keep=n
    # extremes and an odd size, each in both dtypes (a row pays ~2 s of
    # interpreter compile)
    @pytest.mark.parametrize("n,keep,dtype", [
        (70000, 700, jnp.float32),
        (70000, 700, jnp.bfloat16),
        (65536, 1, jnp.float32),
        (4096, 4096, jnp.float32),
        (65536, 1, jnp.bfloat16),
        (4096, 4096, jnp.bfloat16),
        (12345, 300, jnp.float32),
        (12345, 300, jnp.bfloat16),
    ])
    def test_bitwise_parity_topk(self, n, keep, dtype):
        flat = jax.random.normal(jax.random.key(n + keep), (n,), dtype)
        mag = jnp.abs(flat).astype(jnp.float32)
        t = kernels.topk_threshold(mag, keep)
        fv, fi, fc = kernels.fused_select_pack(flat, t, keep, interpret=True)
        xv, xi, xc = self._xla(flat, mag, t, keep)
        assert np.array_equal(np.asarray(fi), np.asarray(xi))
        assert np.array_equal(np.asarray(fv), np.asarray(xv))
        assert int(fc) == int(xc)
        assert fv.dtype == flat.dtype

    T = 2.0     # the threshold the placed cases are built around

    def _placed(self, case, dtype):
        """A vector whose survivors of ``|x| >= T`` sit where ``case`` puts
        them, over noise under ``T / 2``; every value exact in bfloat16."""
        seg, lanes = kernels._SEG, kernels._LANES
        block = kernels._SEG_PER_BLOCK * seg
        rng = np.random.default_rng(len(case))
        n = {"empty_segments": 12 * seg,
             "ragged_block": block + seg + 777}.get(case, 2 * seg + 777)
        x = rng.integers(-63, 64, n) / 64.0
        if case == "empty_segments":
            # a few, many, one and every element of a segment, empty
            # segments between them and at both ends
            per_seg = {1: 3, 4: 200, 5: 1, 9: seg, 10: 50}
            where = np.concatenate([s * seg + rng.choice(seg, c, replace=False)
                                    for s, c in per_seg.items()])
        elif case == "all_survive":
            # nothing in the segment moves (every d == 0), its neighbours do
            where = np.concatenate([rng.choice(seg, 40, replace=False),
                                    seg + np.arange(seg),
                                    2 * seg + rng.choice(777, 9, replace=False)])
        elif case == "lone_last_slot":
            # d == _SEG - 1: the one survivor moves in every round
            where = np.array([seg - 1, 2 * seg - 1])
        elif case == "row_boundary":
            # runs across the end of a 128-lane row, which travel by shifts
            # with a lane part, behind enough survivors that the compacted
            # run crosses the end of the segment's first row too
            across = np.arange(-3, 3)
            where = seg + np.concatenate([rng.choice(3 * lanes, lanes - 3, replace=False),
                                          5 * lanes + across, 20 * lanes + across,
                                          [seg - lanes - 1, seg - lanes, seg - 1]])
        elif case == "ragged_block":
            # the second block ends inside its second segment: survivors at
            # its first slot, across its one whole segment's end, at n - 1
            where = np.concatenate([rng.choice(block, 30, replace=False),
                                    block + np.array([0, seg - 1, seg, seg + 776]),
                                    block + rng.choice(np.arange(1, seg - 1), 20, replace=False)])
        else:
            count = {"ragged_tail": 64, "ties": 300, "underfull": 40}[case]
            where = rng.choice(n, count, replace=False)
            # the first and the last element of the tail beyond the segments
            where[:2] = [2 * seg, n - 1]
        keep = {"ties": 100, "underfull": 64}.get(case, len(where))
        mags = self.T if case == "ties" else self.T + rng.integers(0, 64, len(where)) / 32.0
        x[where] = mags * rng.choice([-1.0, 1.0], len(where))
        return jnp.asarray(x, dtype), keep, len(set(where.tolist()))

    # survivors placed by hand around a fixed threshold: the layouts a
    # random draw with its own top-k threshold never produces
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", ["empty_segments", "ragged_tail", "ties",
                                      "underfull", "all_survive",
                                      "lone_last_slot", "row_boundary",
                                      "ragged_block"])
    def test_bitwise_parity_placed_survivors(self, case, dtype):
        flat, keep, count = self._placed(case, dtype)
        mag = jnp.abs(flat).astype(jnp.float32)
        t = jnp.float32(self.T)
        fv, fi, fc = kernels.fused_select_pack(flat, t, keep, interpret=True)
        xv, xi, xc = self._xla(flat, mag, t, keep)
        assert int(fc) == int(xc) == count
        assert (count > keep) == (case == "ties")
        live = min(count, keep)
        assert (live < keep) == (case == "underfull")
        assert np.array_equal(np.asarray(fi), np.asarray(xi))
        assert np.array_equal(np.asarray(fv)[:live], np.asarray(xv)[:live])
        # the first `keep` survivors by ascending index, nothing else
        want = np.flatnonzero(np.asarray(mag) >= self.T)[:keep]
        assert np.array_equal(np.asarray(fi)[:live], want)
        assert not np.any(np.asarray(fv, np.float32)[live:])
        assert fv.dtype == flat.dtype

    @pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32],
                             ids=["i32", "f32"])
    @pytest.mark.parametrize("shift", [1 << b for b in range(kernels._SEG_BITS)])
    def test_roll_flat_is_a_flattened_left_roll(self, shift, dtype):
        # the network's every shift on the kernel's block, wrap inside it
        from jax.experimental import pallas as pl

        shape = (kernels._SEG_PER_BLOCK * kernels._SEG_ROWS, kernels._LANES)
        a = jax.random.randint(jax.random.key(shift), shape, -1 << 20,
                               1 << 20).astype(dtype)

        def body(a_ref, out_ref):
            out_ref[:] = kernels._roll_flat(a_ref[:], shift)

        got = pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(shape, dtype),
                             interpret=True)(a)
        want = jnp.roll(a.reshape(-1), -shift).reshape(shape)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_blocktopk_scores_parity(self):
        # block scores are non-negative and serve as their own magnitudes
        flat = jax.random.normal(jax.random.key(4), (40960,))
        scores = compressors.blocktopk_scores(flat, 256)
        kb = 16
        t = kernels.topk_threshold(scores, kb)
        fv, fi, fc = kernels.fused_select_pack(scores, t, kb, interpret=True)
        _, xi, xc = self._xla(scores, scores, t, kb)
        assert np.array_equal(np.asarray(fi), np.asarray(xi))
        assert int(fc) == int(xc)

    def test_monotone_invariant_on_fused_output(self):
        # full buffer -> strictly ascending unique indices: the downstream
        # sorted/unique scatter hints depend on this
        from tpu_compressed_dp.ops import wire

        flat = jax.random.normal(jax.random.key(5), (30000,))
        t = kernels.topk_threshold(jnp.abs(flat), 300)
        _, fi, _ = kernels.fused_select_pack(flat, t, 300, interpret=True)
        assert bool(wire.packed_indices_monotone(fi))

    def test_underfull_pads_zero_value_zero_index(self):
        # an underfull mask (threshold above every |x|) pads (0.0, 0) —
        # scatter-add identities, unlike the XLA chain's flat[0] replication
        flat = jnp.arange(1.0, 5001.0)
        fv, fi, fc = kernels.fused_select_pack(
            flat, jnp.float32(4998.5), 10, interpret=True)
        assert int(fc) == 2
        np.testing.assert_array_equal(
            np.asarray(fv), [4999.0, 5000.0] + [0.0] * 8)
        np.testing.assert_array_equal(np.asarray(fi), [4998, 4999] + [0] * 8)

    def test_dispatch_gate(self):
        assert not kernels.use_select_pack(1 << 10, 8)   # below size floor
        assert not kernels.use_select_pack(1 << 20, 0)   # degenerate keep
        assert not kernels.use_select_pack((1 << 31) + 2, 100)  # int32 wrap


class TestQuantPackKernels:
    """Matmul bit-packing vs the XLA shift/sum packers: wire BYTES must be
    bitwise identical (the receiver's unpack is shared)."""

    @pytest.mark.parametrize("n", [70000, 12345, 65533, 7])
    def test_pack_ternary_parity(self, n):
        from tpu_compressed_dp.ops import wire

        rng = np.random.default_rng(n)
        levels = jnp.asarray(rng.integers(-1, 2, n), jnp.int8)
        got = kernels.pack_ternary_pallas(levels, interpret=True)
        want = wire.pack_ternary(levels)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("n", [70000, 12347])
    def test_qsgd_pack_levels_parity(self, n):
        from tpu_compressed_dp.ops import wire

        rng = np.random.default_rng(n)
        levels = jnp.asarray(rng.integers(-255, 256, n), jnp.int16)
        gm, gs = kernels.qsgd_pack_pallas(levels, interpret=True)
        wm, ws = wire.qsgd_wire_pack(levels, 255)
        assert np.array_equal(np.asarray(gm), np.asarray(wm))
        assert np.array_equal(np.asarray(gs), np.asarray(ws))

    def test_terngrad_pack_bytes(self):
        from tpu_compressed_dp.ops import wire

        g = jax.random.normal(jax.random.key(2), (20000,))
        packed, scale = kernels.terngrad_pack(g, jax.random.key(3),
                                              interpret=True)
        assert packed.dtype == jnp.uint8 and packed.shape == (-(-20000 // 4),)
        lv = wire.unpack_ternary(packed[None], 20000)[0]
        assert set(np.unique(np.asarray(lv))) <= {-1, 0, 1}
        assert float(scale) == pytest.approx(float(jnp.max(jnp.abs(g))))

    def test_qsgd_pack_bytes(self):
        g = jax.random.normal(jax.random.key(6), (20000,))
        mags, signs, scale = kernels.qsgd_pack(g, jax.random.key(7),
                                               interpret=True)
        assert mags.dtype == jnp.uint8 and signs.dtype == jnp.uint8
        assert mags.shape == (20000,) and signs.shape == (-(-20000 // 8),)
        # u=0 stub -> levels == floor(|g|/norm * s) exactly
        ref = np.floor(np.abs(np.asarray(g))
                       / np.linalg.norm(np.asarray(g)) * 255)
        np.testing.assert_array_equal(np.asarray(mags), ref)


class TestFusedBucketRoute:
    """Fused per-destination bucket build vs the XLA slot scatter in
    wire_sharded: buckets must be bitwise identical, monotone rows kept."""

    def _xla(self, vals, idx, valid, W, cap, shard_n):
        dest = jnp.minimum(idx // shard_n, W - 1).astype(jnp.int32)
        if valid is not None:
            dest = jnp.where(valid, dest, W)
        counts = jnp.zeros((W + 1,), jnp.int32).at[dest].add(
            1, indices_are_sorted=True, mode="promise_in_bounds")
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(idx.shape[0], dtype=jnp.int32) - starts[dest]
        accepted = rank < cap
        if valid is not None:
            accepted = accepted & valid
        slot = jnp.where(accepted, dest * cap + rank, W * cap)
        local = (idx - dest * shard_n).astype(jnp.int32)
        bvals = jnp.zeros((W * cap + 1,), vals.dtype).at[slot].add(vals)[:-1]
        bidx = jnp.full((W * cap + 1,), shard_n, jnp.int32
                        ).at[slot].set(local)[:-1]
        return bvals.reshape(W, cap), bidx.reshape(W, cap), dest

    @pytest.mark.parametrize("seed,n,keep,W", [(0, 70000, 700, 8),
                                               (1, 30000, 333, 4)])
    def test_bitwise_parity(self, seed, n, keep, W):
        rng = np.random.default_rng(seed)
        pick = np.sort(rng.choice(n, keep, replace=False))
        idx = jnp.asarray(pick, jnp.int32)
        vals = jnp.asarray(rng.standard_normal(keep), jnp.float32)
        shard_n = -(-n // W)
        cap = max(1, int(1.25 * keep / W))
        xv, xi, dest = self._xla(vals, idx, None, W, cap, shard_n)
        fv, fi = kernels.fused_bucket_route(vals, idx, dest, W, cap,
                                            shard_n, interpret=True)
        assert np.array_equal(np.asarray(fv), np.asarray(xv))
        assert np.array_equal(np.asarray(fi), np.asarray(xi))

    def test_valid_prefix_routes_to_dump(self, ):
        # threshold-style zero-padded tails (valid prefix) must not consume
        # any bucket capacity
        rng = np.random.default_rng(2)
        n, keep, nvalid, W = 40000, 77, 60, 8
        pick = np.sort(rng.choice(n, nvalid, replace=False))
        idx = jnp.asarray(np.concatenate([pick, np.zeros(keep - nvalid)]),
                          jnp.int32)
        vals = jnp.asarray(
            np.concatenate([rng.standard_normal(nvalid),
                            np.zeros(keep - nvalid)]), jnp.float32)
        valid = jnp.arange(keep) < nvalid
        shard_n = -(-n // W)
        cap = 13
        xv, xi, dest = self._xla(vals, idx, valid, W, cap, shard_n)
        fv, fi = kernels.fused_bucket_route(vals, idx, dest, W, cap,
                                            shard_n, interpret=True)
        assert np.array_equal(np.asarray(fv), np.asarray(xv))
        assert np.array_equal(np.asarray(fi), np.asarray(xi))
        # monotone rows: filled ascending prefix then constant shard_n tail
        for w in range(W):
            row = np.asarray(fi[w])
            filled = row[row < shard_n]
            assert np.all(np.diff(filled) > 0)

    def test_dispatch_gate(self):
        assert not kernels.use_bucket_route(1 << 10, 8, 64)   # size floor
        assert not kernels.use_bucket_route(1 << 20, 1, 64)   # no routing
        assert not kernels.use_bucket_route(1 << 20, 8, 1 << 20)  # cap blowup


class TestPoisonedTailHistogram:
    """A NaN/Inf guard-vetoed gradient must not collapse the histogram bin
    edges: a non-finite ``max(mag)`` used to propagate into every edge
    (``x >= NaN`` is false everywhere), driving the survivor count to zero,
    underfilling the pack, and voiding the sorted/unique scatter hints.
    The FP32_MAX clamp keeps the structural ``count >= keep`` guarantee —
    degraded resolution (t -> 0, EF reabsorbs the surplus), never a
    duplicate-index payload.  The -1.0 padding fill stays strictly below
    every edge, so padding lanes never leak into the counts either."""

    @pytest.mark.parametrize("poison", ["nan", "inf", "both"])
    def test_pallas_histogram_guarantee_survives(self, poison):
        mag = jnp.abs(jax.random.normal(jax.random.key(11), (10000,)))
        if poison in ("nan", "both"):
            mag = mag.at[17].set(jnp.nan)
        if poison in ("inf", "both"):
            mag = mag.at[4242].set(jnp.inf)
        t = kernels._topk_threshold_pallas(mag, 100, interpret=True)
        assert bool(jnp.isfinite(t))
        assert int(jnp.sum(mag >= t)) >= 100  # NaN compares false: vetoed

    @pytest.mark.parametrize("poison", ["nan", "inf"])
    def test_jnp_fallback_guarantee_survives(self, poison):
        mag = jnp.abs(jax.random.normal(jax.random.key(12), (4096,)))
        mag = mag.at[7].set(jnp.nan if poison == "nan" else jnp.inf)
        t = kernels._topk_threshold_jnp(mag, 41)
        assert bool(jnp.isfinite(t))
        assert int(jnp.sum(mag >= t)) >= 41

    def test_exact_path_nan_demoted_below_topk(self):
        # the exact lax.top_k dispatch path: NaN sorts as LARGEST and would
        # steal a slot, landing the threshold one rank too high (underfull
        # pack).  The demotion keeps count(mag >= t) >= keep with NaN vetoed.
        mag = jnp.abs(jax.random.normal(jax.random.key(14), (70000,)))
        mag = mag.at[123].set(jnp.nan)
        t = kernels.topk_threshold(mag, 700)
        assert int(jnp.sum(mag >= t)) >= 700
        assert not bool(jnp.isnan(mag[123]) & (mag[123] >= t))

    def test_finite_inputs_unchanged(self):
        # the clamp must be invisible for ordinary finite gradients
        mag = jnp.abs(jax.random.normal(jax.random.key(13), (8192,)))
        t = kernels._topk_threshold_pallas(mag, 80, interpret=True)
        exact = jax.lax.top_k(mag, 80)[0][-1]
        np.testing.assert_array_equal(np.asarray(mag >= t),
                                      np.asarray(mag >= exact))
