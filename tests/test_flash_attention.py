"""In-repo flash attention kernel (ops/flash_attention.py): interpret-mode
parity of forward AND backward against the exact online-softmax reference —
the kernel is the dispatched single-block attention path of the LM step, so
a sign/transpose slip in the hand-written VJP would corrupt training
gradients silently."""

import functools
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


def _o_lse_grads(q, k, v, do, window=None, module=fa, interpret=True):
    """(o, lse, dq, dk, dv) through the wrappers the custom VJP runs."""
    o, res = module._fa_fwd(q, k, v, None, interpret, window)
    return (o, res[4]) + tuple(module._fa_bwd(None, interpret, res, do, window))


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
    "shape, window, blk",
    [
        # blocks of 512 walked in halves of 256 (what T = 8,192 runs sixteen
        # of): a window of a whole block, six quarters of eight a q block ...
        ((1, 2, 1024, 64), 512, None),
        ((1, 1, 2048, 64), 512, None),
        ((1, 1, 2048, 128), 512, None),      # ... at a head of two lane tiles in the packing
        # ... of half a block: a half's rows meet two halves of keys
        ((1, 1, 2048, 64), 256, None),
        ((1, 1, 1024, 128), 200, None),      # inside one half: both edges cut the pair behind
        ((1, 1, 1536, 64), 700, None),       # wider than a block: two blocks behind the diagonal's
        ((1, 1, 1024, 64), 1023, None),      # window = t - 1, the band kernels at their widest
        ((1, 1, 512, 64), 300, None),        # one block a head: nothing behind it to fetch
        # blocks of 128 and 64 are walked whole
        ((2, 1, 1024, 64), 130, (128, 128)),     # 8 blocks, two behind
        ((1, 2, 1024, 64), 256, (256, 256)),     # halves of 128
        ((1, 1, 1024, 64), 384, (128, 128)),     # a block multiple: no band edge inside the middle pairs
        ((1, 1, 1024, 64), 600, (128, 128)),     # the first five q blocks fetch block 0 in place of what is not there
        ((1, 1, 512, 64), 1, (128, 128)),        # the token itself alone
        ((1, 1, 512, 64), 4096, (128, 128)),     # a band wider than the sequence: the whole-sequence kernels
        ((1, 1, 64, 64), 24, None),              # not a multiple of the block or of its half: one whole pair
        ((1, 1, 256, 64), 160, (64, 64)),        # three blocks behind, the dq carry three deep
        ((1, 1, 256, 64), 100, (64, 64)),        # two, the oldest cut by the band's edge
    ],
)
def test_banded_kernels_match_the_masked_softmax(monkeypatch, shape, window, blk):
    """o, dq, dk and dv of the band kernels against the masked softmax over
    the whole [T, T]: the static walk over the key blocks a q block reaches
    (and back, the q blocks that reach a key block), the halves' own key
    ranges, each mask's one or two edges, the blocks clamped at either end
    of the sequence, and the dq carry from step to step.  The cotangent is
    of the outputs' own size, so a gradient's error is not hidden under a
    mean's 1 / n."""
    if blk is not None:
        monkeypatch.setattr(fa, "_pick_blocks", lambda t: blk)
    ks = jax.random.split(jax.random.key(11), 4)
    q, k, v, do = (jax.random.normal(kk, shape, jnp.float32) * 0.5 for kk in ks)
    o, vjp = jax.vjp(lambda q, k, v: masked_softmax_attention(q, k, v, window),
                     q, k, v)
    got, got_vjp = jax.vjp(
        lambda q, k, v: flash_causal_attention(q, k, v, None, True, window),
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(o), atol=1e-5)
    for a, b, nm in zip(got_vjp(do), vjp(do), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   err_msg=f"d{nm}")


@pytest.mark.parametrize(
    "t, window, blk, geometry, pairs",
    [
        (8192, 512, 512, (256, 1, 2), 16 * 6),   # both cells: six quarters of eight a grid step
        (8192, 256, 512, (256, 1, 1), 16 * 4),
        (8192, 1024, 512, (256, 2, 4), 16 * 10),
        (8192, 200, 512, (256, 1, 1), 16 * 4),
        (384, 130, 128, (128, 2, 2), 3 * 3),     # a half under a lane tile: whole blocks
        (512, 300, 512, (256, 0, 2), 3),         # one block: its second half meets both
    ],
)
def test_the_band_walk_is_static(monkeypatch, t, window, blk, geometry, pairs):
    """What a band kernel's grid step computes is fixed by (block, window, T)
    alone: the rows of a sub-block, the blocks fetched behind the diagonal's,
    the sub-blocks a sub-block's band reaches; and the bench counts those
    sub-block pairs (``live_pairs``), the same at every grid step."""
    assert fa._band_geometry(blk, window, t // blk) == geometry
    monkeypatch.setattr(fa, "_pick_blocks", lambda t: (blk, blk))
    assert flash_attn_bench.live_pairs(fa, t, window) == (pairs, geometry[0])


@pytest.mark.parametrize("shape, dtype", [((1, 2, 1024, 64), jnp.float32),
                                          ((1, 1, 1536, 128), jnp.bfloat16)])
def test_bitwise_no_window_is_a_band_over_everything(shape, dtype):
    """A band that reaches the first key from the last query IS the call
    without a window: handed on as None to the whole-sequence kernels (no
    band kernel is traced), so o, lse, dq, dk, dv are bit for bit."""
    ks = jax.random.split(jax.random.key(13), 4)
    q, k, v, do = ((jax.random.normal(kk, shape, jnp.float32) * 0.5)
                   .astype(dtype) for kk in ks)
    for a, b, nm in zip(_o_lse_grads(q, k, v, do),
                        _o_lse_grads(q, k, v, do, window=shape[2]),
                        ("o", "lse", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)), err_msg=nm)
    jaxpr = jax.make_jaxpr(lambda *x: _o_lse_grads(*x, window=shape[2]))(q, k, v, do)
    assert [name for name, _ in _pallas_calls(jaxpr.jaxpr)] == [
        "flash_attn_fwd", "flash_attn_bwd"]


@pytest.mark.parametrize("shape, dtype", [((1, 2, 256, 64), jnp.float32),
                                          ((1, 2, 1024, 64), jnp.bfloat16),
                                          ((1, 1, 1536, 128), jnp.float32),
                                          ((2, 1, 1024, 128), jnp.bfloat16)])
def test_bitwise_no_window_is_the_parents(shape, dtype):
    """Taking the window out of the whole-sequence kernels moved nothing: a
    call without one traces to the jaxpr that the parent's module does
    (`fixtures/flash_attention_pr45.py`: PR 45's file, frozen, whose
    whole-sequence kernels still carry the window's bounds and second mask
    edge), letter for letter, interpreted and as the chip stages it, and o,
    lse, dq, dk, dv are bit for bit the parent's."""
    parent = flash_attn_bench.load("pr45", os.path.join(
        os.path.dirname(__file__), "fixtures", "flash_attention_pr45.py"))
    ks = jax.random.split(jax.random.key(29), 4)
    q, k, v, do = ((jax.random.normal(kk, shape, jnp.float32) * 0.5)
                   .astype(dtype) for kk in ks)
    for interpret in (True, False):
        trace = lambda module: str(jax.make_jaxpr(functools.partial(
            _o_lse_grads, module=module, interpret=interpret))(q, k, v, do))
        assert trace(fa) == trace(parent)
    for a, b, nm in zip(_o_lse_grads(q, k, v, do),
                        _o_lse_grads(q, k, v, do, module=parent),
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


@pytest.mark.parametrize("window, fits", [(None, True), (512, True), (2048, True),
                                          (3073, True), (3074, False),
                                          (8191, False), (8192, True)])
def test_the_gate_holds_a_band_to_what_its_kernels_hold(window, fits):
    """The band kernels keep a q block's band in VMEM, not the sequence: the
    gate admits a window while the backward's blocks stay under 12 MB (six
    blocks of 512 behind the diagonal's at the longest admitted sequence) and
    hands a wider one to the XLA chain; a window that reaches the whole
    sequence is the call without one."""
    shape = (1, 2, 8192, 128)
    assert ra_mod.fused_attention_fits(shape, shape, 2, window) is fits
    if window in (512, 3073):
        blocks_behind = -(-(window - 1) // 512)
        assert fa.band_vmem_bytes(8192, 128, 2, window) == int(
            (2.5 + 1.5 * blocks_behind) * 2 ** 20)


def _grad_of_sum(shape, dtype, window=None, **aval):
    """`jax.grad` of a sum through the kernel as dispatched (not interpreted),
    and its abstract operand: for tests that trace or compile and never run."""
    assert ra_mod.fused_attention_fits(shape, shape, jnp.dtype(dtype).itemsize,
                                       window)
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
    "shape, dtype, window",
    [
        ((1, 2, 256, 64), jnp.float32, None),
        ((1, 16, 4096, 128), jnp.bfloat16, None),   # the LM cell's attention call
        ((1, 16, 8192, 128), jnp.bfloat16, None),   # the longest admitted sequence, in blocks of 512
        ((1, 64, 8192, 128), jnp.bfloat16, 512),    # the Laguna cell's banded call
        ((1, 40, 8192, 128), jnp.bfloat16, 512),    # the Phi cell's: 40 maps, keys padded to the values' 128
        ((1, 2, 256, 64), jnp.float32, 1),          # any window under T, down to the token alone
    ],
)
def test_grad_is_one_forward_and_one_backward_kernel(shape, dtype, window):
    """The mechanism, not the numbers: differentiating through the kernel
    launches the forward and ONE backward `pallas_call`, for every shape the
    dispatcher admits — the whole-sequence pair without a window, the band
    pair with one; no second backward kernel, no chooser between forms.
    Traced only (shapes in, jaxpr out): nothing compiles or runs."""
    grad, x = _grad_of_sum(shape, dtype, window)
    jaxpr = jax.make_jaxpr(grad)(x, x, x)
    band = "" if window is None else "band_"
    assert [name for name, _ in _pallas_calls(jaxpr.jaxpr)] == [
        f"flash_attn_{band}fwd", f"flash_attn_{band}bwd"]


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
    band = "" if window is None else "band_"
    assert list(_pallas_calls(jaxpr.jaxpr)) == [
        (f"flash_attn_{band}fwd", (2, 16)), (f"flash_attn_{band}bwd", (2, 16))]


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
        ((1, 2, 8192, 128), jnp.bfloat16, 512),     # the Laguna and Phi cells' banded call, halves of 256
        ((1, 2, 4096, 128), jnp.bfloat16, 200),     # a band inside one half
        ((1, 2, 8192, 128), jnp.bfloat16, 2048),    # four blocks behind the diagonal's: the dq carry 1 MB
        ((1, 2, 4096, 64), jnp.float32, 1000),      # fp32 operands, no edge on a block's boundary
        ((1, 2, 384, 64), jnp.bfloat16, 128),       # blocks of 128, walked whole
    ],
)
def test_backward_compiles_for_v5e_at_admitted_extremes(one_chip, shape, dtype,
                                                        window):
    """Forward and the one backward kernel pass Mosaic for a v5e at the
    extremes `fused_attention_fits` admits: the [T, d_pad] float32 dq
    accumulator, the streamed blocks and the [blk, blk] temporaries fit the
    scoped-VMEM ceiling, so no shape needs a second form of the backward;
    and the band kernels' static slices, clamped index maps and dq carry
    pass it at both cells' shape and at windows of several blocks.
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
        ((1, 1, 1024, 64), None, True, True),    # as the chip stages it
        ((1, 2, 1024, 64), 512, True, False),    # the Phi cell's banded layer: halves of 256
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
