"""In-repo flash attention kernel (ops/flash_attention.py): interpret-mode
parity of forward AND backward against the exact online-softmax reference —
the kernel is the dispatched single-block attention path of the LM step, so
a sign/transpose slip in the hand-written VJP would corrupt training
gradients silently."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

import tpu_compressed_dp.ops.flash_attention as fa
import tpu_compressed_dp.ops.ring_attention as ra_mod
from tpu_compressed_dp.ops.flash_attention import flash_causal_attention


def exact(q, k, v):
    old = ra_mod._FUSED_ATTN
    ra_mod._FUSED_ATTN = False
    try:
        return ra_mod.ring_attention(q, k, v)
    finally:
        ra_mod._FUSED_ATTN = old


@pytest.mark.parametrize(
    "shape",
    [
        (1, 2, 128, 64),    # padded head_dim (lse rides the pad lanes)
        (2, 1, 256, 128),   # unpadded head_dim (lse gets its own tile)
        (1, 1, 384, 64),    # seq needs the reduced 128 block
        (1, 2, 512, 128),   # Ouro's head size at one whole 512 block, two heads
        (1, 1, 1024, 128),  # ... and over two blocks: the causal block skip
        (1, 2, 1536, 64),   # three 512-blocks: dq block 2 sums three kv steps
        (1, 1, 2048, 128),  # four, Ouro's head size: the skip is 0, 1, 2, 3 blocks
    ],
)
def test_forward_and_grads_match_exact(shape):
    """Against the masked softmax over the whole [T, T] of every head."""
    B, H, T, D = shape
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.5
               for kk in ks[:3])
    o_f = flash_causal_attention(q, k, v, None, True)
    o_e = exact(q, k, v)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_e), atol=1e-5)

    tgt = jax.random.normal(ks[3], shape)
    lf = lambda q, k, v: jnp.mean(
        (flash_causal_attention(q, k, v, None, True) - tgt) ** 2)
    le = lambda q, k, v: jnp.mean((exact(q, k, v) - tgt) ** 2)
    gf = jax.grad(lf, (0, 1, 2))(q, k, v)
    ge = jax.grad(le, (0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, ge, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   err_msg=f"d{nm}")


@pytest.mark.parametrize(
    "shape", [(1, 2, 256, 64), (1, 1, 256, 128), (1, 1, 1536, 64)])
def test_streamed_bwd_matches_resident(monkeypatch, shape):
    """The backward kernel's DMA/double-buffered staging of Q and the packed
    cotangent against its VMEM-resident staging, both under interpret: the
    streamed staging is the only one real TPU runs take, but interpret mode
    (the only CI-runnable path) defaults to the resident one — so the
    explicit-DMA machinery had zero off-chip coverage (ADVICE r5).
    `TPU_CDP_FORCE_STREAMED_DKV=1` runs it under the Pallas interpreter;
    the two must agree to fp32 roundoff (identical math via
    `_bwd_block_math`, different operand staging).  At a head size of 128
    the packed cotangent it streams is two lane tiles wide; at three blocks
    the first q block of a kv step lands in either buffer slot and the dq
    accumulator carries over the kv axis."""
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.5
               for kk in ks[:3])
    tgt = jax.random.normal(ks[3], shape)

    def loss(q, k, v):
        return jnp.mean((flash_causal_attention(q, k, v, None, True) - tgt) ** 2)

    monkeypatch.delenv("TPU_CDP_FORCE_STREAMED_DKV", raising=False)
    g_resident = jax.grad(loss, (0, 1, 2))(q, k, v)
    monkeypatch.setenv("TPU_CDP_FORCE_STREAMED_DKV", "1")
    g_streamed = jax.grad(loss, (0, 1, 2))(q, k, v)
    for a, b, nm in zip(g_streamed, g_resident, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=f"d{nm} streamed vs resident")


def _frozen_fwd_kernel(scale, blk_q, blk_k, n_k, d,
                       q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, window=None):
    """The forward kernel as it stood before its statistics were widened
    (PR 40's parent), frozen here as the plain reference: the running maximum
    and sum are `[blk_q, 1]` columns, lane 0 of today's scratch, broadcast
    over the lanes wherever they meet a block."""
    m_ref, l_ref = m_ref.at[:, :1], l_ref.at[:, :1]
    qi = pl.program_id(1)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, fa._NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    q = q_ref[0]

    def body(kj, _):
        k = k_ref[0, pl.ds(kj * blk_k, blk_k)]
        v = v_ref[0, pl.ds(kj * blk_k, blk_k)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(fa._causal_pos(qi, kj, blk_q, blk_k), s, fa._NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        return 0

    n_live = jnp.minimum(((qi + 1) * blk_q + blk_k - 1) // blk_k, n_k)
    jax.lax.fori_loop(0, n_live, body, 0)
    l = l_ref[:]
    o = acc_ref[:] / l
    lse = m_ref[:] + jnp.log(l)
    d_store = o_ref.shape[-1]
    out = jnp.concatenate(
        [o[:, :d], lse] + ([jnp.zeros((blk_q, d_store - d - 1), jnp.float32)]
                           if d_store - d - 1 else []), axis=1)
    o_ref[0] = out.astype(o_ref.dtype)


def _o_lse_grads(q, k, v, do, window=None):
    """(o, lse, dq, dk, dv) through the wrappers the custom VJP runs."""
    o, res = fa._fa_fwd(q, k, v, None, True, window)
    return (o, res[4]) + tuple(fa._fa_bwd(None, True, res, do, window))


@pytest.mark.parametrize(
    "shape, dtype, blk",
    [
        ((1, 2, 128, 64), jnp.float32, None),     # t < 512: one pair a head
        ((2, 1, 256, 128), jnp.float32, None),    # a head of 128: o's second lane tile
        ((1, 1, 384, 64), jnp.bfloat16, None),    # one 384-block: three lane tiles of s
        ((1, 2, 512, 128), jnp.bfloat16, None),
        ((1, 1, 1024, 128), jnp.float32, None),   # two blocks: corr rescales the accumulator
        ((1, 2, 1536, 64), jnp.bfloat16, None),
        ((1, 1, 2048, 128), jnp.float32, None),   # four: the statistics carried over four pairs
        ((1, 1, 512, 256), jnp.bfloat16, None),   # the widest head: corr under two lane tiles
        # blocks handed to `_fwd` / `_bwd` that differ, both ways about
        ((1, 2, 512, 64), jnp.float32, (256, 128)),
        ((1, 2, 512, 64), jnp.bfloat16, (128, 256)),
    ],
)
def test_bitwise_the_one_lane_statistics(monkeypatch, shape, dtype, blk):
    """`o`, `lse` and, through them, dq, dk and dv of the kernel that keeps
    its running maximum and sum in 128 equal lanes, bit for bit those of the
    frozen kernel that keeps them in one: the same maxima, differences,
    `exp`s and sums on the same operands in the same order."""
    ks = jax.random.split(jax.random.key(7), 4)
    q, k, v, do = ((jax.random.normal(kk, shape, jnp.float32) * 0.5)
                   .astype(dtype) for kk in ks)
    if blk is not None:
        monkeypatch.setattr(fa, "_pick_blocks", lambda t: blk)
    got = _o_lse_grads(q, k, v, do)
    monkeypatch.setattr(fa, "_fwd_kernel", _frozen_fwd_kernel)
    want = _o_lse_grads(q, k, v, do)
    for a, b, nm in zip(got, want, ("o", "lse", "dq", "dk", "dv")):
        assert a.dtype == b.dtype and a.shape == b.shape, nm
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)),
            err_msg=nm)


def masked_softmax_attention(q, k, v, window):
    """The plain form: the whole [T, T] of every head, the band as a mask."""
    t, d = q.shape[-2:]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (j <= i) & (i - j < window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize(
    "shape, window, blk, streamed",
    [
        ((1, 2, 1024, 64), 512, None, False),    # a block multiple: pairs (1, 0) and the diagonal's
        ((1, 1, 1024, 128), 200, None, False),   # inside one block: the edge cuts pair (1, 0)
        ((1, 1, 1536, 64), 700, None, True),     # wider than a block: three pairs a q block
        ((2, 1, 1024, 64), 130, (128, 128), True),   # 8 blocks: whole pairs behind the band skipped
        ((1, 2, 1024, 64), 256, (128, 256), False),  # unequal blocks, both loops' ends
        ((1, 1, 1024, 64), 384, (256, 128), True),
        ((1, 1, 512, 64), 1, (128, 128), False),     # the token itself alone
        ((1, 1, 512, 64), 4096, (128, 128), True),   # a band wider than the sequence: causal
        # the shipped blocks of 512 over four q blocks (what T = 8,192 runs
        # sixteen of): the window a whole block, two pairs a q block ...
        ((1, 1, 2048, 64), 512, None, False),
        ((1, 1, 2048, 64), 512, None, True),
        # ... and half of one: the band's far edge cuts the pair behind the diagonal's
        ((1, 1, 2048, 64), 256, None, False),
        ((1, 1, 2048, 64), 256, None, True),
    ],
)
def test_banded_kernels_match_the_masked_softmax(monkeypatch, shape, window,
                                                 blk, streamed):
    """o, dq, dk and dv of the kernels with a window against the masked
    softmax over the whole [T, T], resident and (as the chip runs it) with q
    and the cotangent streamed by DMA: the pair loops' lower and upper ends,
    the second masked edge, and a prefetch that must stop where the loop does."""
    if blk is not None:
        monkeypatch.setattr(fa, "_pick_blocks", lambda t: blk)
    if streamed:
        monkeypatch.setenv("TPU_CDP_FORCE_STREAMED_DKV", "1")
    ks = jax.random.split(jax.random.key(11), 4)
    q, k, v, tgt = (jax.random.normal(kk, shape, jnp.float32) * 0.5 for kk in ks)
    lf = lambda q, k, v: jnp.mean(
        (flash_causal_attention(q, k, v, None, True, window) - tgt) ** 2)
    le = lambda q, k, v: jnp.mean(
        (masked_softmax_attention(q, k, v, window) - tgt) ** 2)
    np.testing.assert_allclose(
        np.asarray(flash_causal_attention(q, k, v, None, True, window)),
        np.asarray(masked_softmax_attention(q, k, v, window)), atol=1e-5)
    for a, b, nm in zip(jax.grad(lf, (0, 1, 2))(q, k, v),
                        jax.grad(le, (0, 1, 2))(q, k, v), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   err_msg=f"d{nm}")


@pytest.mark.parametrize("shape, dtype", [((1, 2, 1024, 64), jnp.float32),
                                          ((1, 1, 1536, 128), jnp.bfloat16)])
def test_bitwise_no_window_is_a_band_over_everything(shape, dtype):
    """A call without a window visits every pair under the diagonal and masks
    by the diagonal alone (the frozen kernel above is held to it bit for bit);
    a band that reaches past the first key visits and keeps the same: o, lse,
    dq, dk, dv bit for bit."""
    ks = jax.random.split(jax.random.key(13), 4)
    q, k, v, do = ((jax.random.normal(kk, shape, jnp.float32) * 0.5)
                   .astype(dtype) for kk in ks)
    for a, b, nm in zip(_o_lse_grads(q, k, v, do),
                        _o_lse_grads(q, k, v, do, window=shape[2]),
                        ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)), err_msg=nm)


def test_the_xla_chain_takes_the_same_band():
    """Off the TPU, and for shapes the kernel refuses, `ring_attention` masks
    the band in its XLA chain; the ring path refuses a window."""
    shape = (1, 4, 96, 16)
    ks = jax.random.split(jax.random.key(17), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
    np.testing.assert_allclose(
        np.asarray(ra_mod.ring_attention(q, k[:, :2], v[:, :2], window=24)),
        np.asarray(masked_softmax_attention(
            q, jnp.repeat(k[:, :2], 2, 1), jnp.repeat(v[:, :2], 2, 1), 24)),
        atol=1e-5)


def _grad_of_sum(shape, dtype, window=None, **aval):
    """`jax.grad` of a sum through the kernel as dispatched (not interpreted),
    and its abstract operand: for tests that trace or compile and never run."""
    assert ra_mod.fused_attention_fits(shape, shape, jnp.dtype(dtype).itemsize)
    loss = lambda q, k, v: jnp.sum(
        flash_causal_attention(q, k, v, None, False, window).astype(jnp.float32))
    return jax.grad(loss, (0, 1, 2)), jax.ShapeDtypeStruct(shape, dtype, **aval)


def _pallas_calls(jaxpr):
    """(name, grid) of every `pallas_call` under a jaxpr, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], tuple(eqn.params["grid_mapping"].grid)
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


@pytest.mark.parametrize(
    "shape, dtype",
    [
        ((1, 2, 256, 64), jnp.float32),
        ((1, 16, 4096, 128), jnp.bfloat16),   # the LM cell's attention call
        ((1, 16, 8192, 128), jnp.bfloat16),   # the longest admitted sequence, in blocks of 512
    ],
)
def test_grad_is_one_forward_and_one_backward_kernel(shape, dtype):
    """The mechanism, not the numbers: differentiating through the kernel
    launches the forward and ONE backward `pallas_call`, for every shape the
    dispatcher admits — no second backward kernel, no chooser between forms.
    Traced only (shapes in, jaxpr out): nothing compiles or runs."""
    grad, x = _grad_of_sum(shape, dtype)
    jaxpr = jax.make_jaxpr(grad)(x, x, x)
    assert [name for name, _ in _pallas_calls(jaxpr.jaxpr)] == [
        "flash_attn_fwd", "flash_attn_bwd"]


@pytest.mark.parametrize("window", [None, 512])
def test_the_longest_sequence_runs_in_blocks_of_512(window):
    """One block rule for every length and for the band: at 8,192 tokens the
    forward's q axis and the backward's kv axis are 16 grid steps a head, not
    the 32 of blocks of 256 (a pair costs ~0.34 us forward and ~0.67 us
    backward whatever its size, and blocks of 256 pay it four times as
    often).  Traced only: it fails if the rule grows a branch on T again, and
    says nothing of VMEM (the compile below does)."""
    grad, x = _grad_of_sum((1, 2, 8192, 128), jnp.bfloat16, window)
    jaxpr = jax.make_jaxpr(grad)(x, x, x)
    assert list(_pallas_calls(jaxpr.jaxpr)) == [
        ("flash_attn_fwd", (2, 16)), ("flash_attn_bwd", (2, 16))]


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler refuses here
    what it would refuse on the chip.  Described inside the fixture, never at
    import: only the worker that runs this file loads libtpu."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "shape, dtype, window",
    [
        ((1, 16, 4096, 128), jnp.bfloat16, None),   # the LM cell's attention call
        ((1, 2, 8192, 128), jnp.bfloat16, None),    # longest T, blocks of 512: dq accumulator 4 MB
        ((1, 2, 4096, 256), jnp.bfloat16, None),    # widest head: 4 MB at 512-blocks
        ((1, 2, 4096, 128), jnp.float32, None),     # fp32 operands, 256-lane cotangent
        ((1, 2, 8192, 128), jnp.bfloat16, 512),     # the Laguna cell's banded call, blocks of 512
        ((1, 2, 4096, 128), jnp.bfloat16, 200),     # a band inside one 512-block
    ],
)
def test_backward_compiles_for_v5e_at_admitted_extremes(one_chip, shape, dtype,
                                                        window):
    """Forward and the one backward kernel pass Mosaic for a v5e at the
    extremes `fused_attention_fits` admits: the [T, d_pad] float32 dq
    accumulator, the streamed blocks and the [blk, blk] temporaries fit the
    scoped-VMEM ceiling, so no shape needs a second form of the backward.
    A compile, not a run: nothing here is a time."""
    grad, x = _grad_of_sum(shape, dtype, window, sharding=one_chip)
    compiled = jax.jit(grad).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


_spec = importlib.util.spec_from_file_location(
    "flash_attn_bench", os.path.join(os.path.dirname(__file__), "..", "tools",
                                     "flash_attn_bench.py"))
flash_attn_bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(flash_attn_bench)


@pytest.mark.parametrize("window", [None, 100])
def test_the_bench_exact_answer_is_the_masked_softmax(window):
    """`tools/flash_attn_bench.py` measures every side's distance from
    `exact`: it has to be this file's plain form, the outputs and, for one
    cotangent, the three gradients."""
    shape = (2, 3, 256, 64)
    ks = jax.random.split(jax.random.key(19), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32) * 0.5 for kk in ks)
    plain = lambda q, k, v: masked_softmax_attention(q, k, v, window or shape[2])
    o, vjp = jax.vjp(plain, q, k, v)
    got = flash_attn_bench.exact(q, k, v, do, window)
    for a, b, nm in zip([got[0]] + got[2:], (o,) + vjp(do), ("o", "dq", "dk", "dv")):
        np.testing.assert_allclose(a, np.asarray(b), atol=2e-6, err_msg=nm)
    # over the heads the second argument holds: largest, root-mean-square
    for n, gap in flash_attn_bench.gaps(got, [x[:, :2] + 0.5 for x in got]).items():
        np.testing.assert_allclose(gap, [0.5, 0.5], rtol=1e-6, err_msg=n)


def test_the_bench_tells_another_order_of_sums_from_another_answer(tmp_path):
    """Two copies of the module whose blocks differ are not bit for bit, and
    the rehearsal's rows say how far apart they are (`gap_to_first`) and that
    each stands as far from the exact answer as the other (`gap_to_exact`):
    what a hand-in of a block-shape change has to show."""
    other = tmp_path / "flash_attention_256.py"
    src = open(fa.__file__).read()
    assert "bq = min(512, t)" in src
    other.write_text(src.replace("bq = min(512, t)", "bq = min(256, t)"))
    out = tmp_path / "rows.json"
    rc = flash_attn_bench.main(["--rehearse", "--out", str(out),
                                f"tree={fa.__file__}", f"b256={other}"])
    rows = json.load(open(out))["rows"]
    assert rc == 1 and len(rows) == 2 * len(flash_attn_bench.REHEARSAL_SHAPES)
    for first, second in zip(rows[0::2], rows[1::2]):
        assert (first["side"], second["side"]) == ("tree", "b256")
        assert all(first["bitwise"].values()) and not all(second["bitwise"].values())
        for n in ("o", "dq", "dk", "dv"):
            (_, a), (_, b) = first["gap_to_exact"][n], second["gap_to_exact"][n]
            assert 0 < second["gap_to_first"][n][1] < 2 * a and 0.5 * a < b < 2 * a, (n, a, b)


@pytest.mark.parametrize(
    "shape, window, fused, streamed",
    [
        ((1, 2, 512, 64), None, True, False),    # one block, two maps
        ((1, 1, 1024, 64), 200, True, False),    # the band's edge inside a pair
        ((1, 1, 1024, 64), 512, True, True),     # as the chip stages it
        ((2, 2, 256, 64), None, False, False),   # the XLA chain, whole ...
        ((1, 2, 256, 64), 100, False, False),    # ... and in a band
        ((1, 1, 256, 32), None, False, False),   # keys of 32 under values of 64
    ],
)
def test_values_twice_as_wide_as_the_keys(monkeypatch, shape, window, fused,
                                          streamed):
    """q and k of a head's width on v of twice that (a differential-attention
    pair's values), through ``ring_attention`` whichever way it routes the
    call: o, dq, dk and dv against the masked softmax at the head's own scale.
    The kernels see q and k zero-padded to v's width; the gradients come back
    in the operands' own widths."""
    if fused:
        monkeypatch.setattr(ra_mod, "use_fused_attention", lambda *a: True)
        monkeypatch.setattr(
            ra_mod, "_fused_causal",
            lambda q, k, v, scale, window=None: flash_causal_attention(
                q, k, v, scale, True, window))
    if streamed:
        monkeypatch.setenv("TPU_CDP_FORCE_STREAMED_DKV", "1")
    b, h, t, d = shape
    ks = jax.random.split(jax.random.key(23), 4)
    q, k = (jax.random.normal(kk, shape, jnp.float32) * 0.5 for kk in ks[:2])
    v, tgt = (jax.random.normal(kk, (b, h, t, 2 * d), jnp.float32) * 0.5
              for kk in ks[2:])
    lf = lambda q, k, v: jnp.mean(
        (ra_mod.ring_attention(q, k, v, window=window) - tgt) ** 2)
    le = lambda q, k, v: jnp.mean(
        (masked_softmax_attention(q, k, v, window or t) - tgt) ** 2)
    got = ra_mod.ring_attention(q, k, v, window=window)
    assert got.shape == v.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        masked_softmax_attention(q, k, v, window or t)), atol=1e-5)
    for a, e, nm in zip(jax.grad(lf, (0, 1, 2))(q, k, v),
                        jax.grad(le, (0, 1, 2))(q, k, v), "qkv"):
        assert a.shape == e.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), atol=1e-5,
                                   err_msg=f"d{nm}")


def test_values_narrower_than_the_keys_are_refused():
    q = jnp.zeros((1, 1, 128, 64))
    with pytest.raises(ValueError, match="wider than the keys"):
        ra_mod.ring_attention(q, q, q[..., :32])
