"""In-repo flash attention kernel (ops/flash_attention.py): interpret-mode
parity of forward AND backward against the exact online-softmax reference —
the kernel is the dispatched single-block attention path of the LM step, so
a sign/transpose slip in the hand-written VJP would corrupt training
gradients silently."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

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


@pytest.mark.parametrize("shape", [(1, 2, 256, 64), (1, 1, 256, 128)])
def test_streamed_dkv_matches_resident(monkeypatch, shape):
    """The DMA/double-buffered dkv kernel (`_dkv_kernel_streamed`) against
    the VMEM-resident form, both under interpret: the streamed path is the
    only one real TPU runs take for the backward, but interpret mode (the
    only CI-runnable path) defaulted to the resident kernel — so the
    explicit-DMA machinery had zero off-chip coverage (ADVICE r5).
    `TPU_CDP_FORCE_STREAMED_DKV=1` runs it under the Pallas interpreter;
    the two must agree to fp32 roundoff (identical math via
    `_dkv_block_math`, different operand staging).  At a head size of 128
    the packed cotangent it streams is two lane tiles wide."""
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
