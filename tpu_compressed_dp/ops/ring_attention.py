"""Ring attention: causal attention over a sequence-sharded axis.

Net-new capability relative to the reference (SURVEY.md §5: long-context /
sequence parallelism is **absent** there — its workloads are CNNs), required
for the Llama pretrain stretch config (BASELINE.json) and demanded by the
framework goal: long sequences scale by sharding the *sequence* dimension
over a mesh axis, with K/V blocks rotating around the ring via
``jax.lax.ppermute`` while each device accumulates its queries' attention
online (flash-attention style running softmax).  Compute overlaps the
neighbor exchange because XLA schedules the ppermute alongside the block
matmuls — the same latency-hiding the reference hand-built with NCCL side
streams (`ddp.py:429-456`), applied to sequence parallelism.

Semantics: exact causal attention — bitwise-equivalent (up to fp reassociation)
to dense softmax attention over the full sequence, verified in
tests/test_transformer.py.  Rotation count is the ring size (static), so the
whole loop unrolls into XLA with static shapes.

Layout: ``(batch, heads, seq_block, head_dim)`` per device; the global
sequence position of a block is recovered from the device's ring index, so
causal masking is correct without materialising a [T, T] mask.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

__all__ = ["ring_attention", "dense_causal_attention", "use_fused_attention"]

_NEG_INF = -1e30

# Fused flash-attention for the single-block (ring size 1) case: the tiled
# Pallas kernel never materialises the [T, T] probability matrix in HBM —
# at T=1024 the unfused chain round-trips ~400 MB of fp32 scores per layer
# pass, the dominant non-matmul HBM traffic of the LM step (VERDICT r3 weak
# #5).  The multi-block ring path keeps the exact online-softmax: its
# per-step K/V blocks already bound the score working set to [T_loc, T_loc],
# and block outputs merge through the (o, m, l) carry that a fused kernel
# would have to export anyway.
#
# Operand-precision note (ADVICE r4): for bf16 models the kernel feeds bf16
# q/k straight to the MXU (fp32 accumulation), while the unfused path
# upcast q/k to fp32 before the score matmul — so enabling the default-on
# kernel shifts bf16 loss curves at the last-ulp level.  This matches
# standard XLA attention practice; set TPU_CDP_FUSED_ATTN=0 to recover the
# old operand precision when diffing curves against pre-round-4 runs.
_FUSED_ATTN = os.environ.get("TPU_CDP_FUSED_ATTN", "1") != "0"


def use_fused_attention(q_shape, k_shape, itemsize: int = 2,
                        window: Optional[int] = None) -> bool:
    """True when the single-block causal path should hit the fused kernels
    (:mod:`tpu_compressed_dp.ops.flash_attention`): TPU backend and shapes
    the kernels take (:func:`fused_attention_fits`)."""
    if not _FUSED_ATTN or jax.default_backend() != "tpu":
        return False
    return fused_attention_fits(q_shape, k_shape, itemsize, window)


def fused_attention_fits(q_shape, k_shape, itemsize: int = 2,
                         window: Optional[int] = None) -> bool:
    """Shapes the fused kernels take: seq a lane multiple, head_dim
    MXU-friendly, K/V small enough to stream through VMEM whole.  A call with
    a window passes the same gate, though its kernels keep nothing of length
    T: what they hold grows with the blocks a band reaches, and a band too
    wide for that takes the XLA chain."""
    b, h, t, d = q_shape
    d_pad = d + (-d) % 128
    # What is resident for a whole head, and so grows with T.  Forward:
    # Mosaic-managed full-T K + V blocks, held at input dtype (bf16 in
    # practice) and double-buffered.  Backward (one kernel): the float32 dq
    # accumulator, T * d_pad * 4 bytes — never more than the K + V set below
    # (equal at bf16, half at fp32) and single-buffered — beside per-block
    # K/V and the streamed q/do blocks; its full-T operands stay in HBM.
    # Both must fit the TPU's ~16 MB scoped-vmem ceiling.  Cap the
    # single-buffered K+V set at 4 MB (= 8 MB doubled + block buffers):
    # admits the chip-verified T=8192 at d=128 exactly, where the kernels run
    # in blocks of 512 like every shorter call (136 pairs a head) and the
    # compile for a v5e reports 10.00 MB forward and 7.00 MB backward
    # (flash_attention._pick_blocks has the sums); T=16384 (8 MB single, ~18+
    # doubled) would hit the scoped-vmem wall — long-context's designed path
    # is the seq-axis ring sharding T_local below this gate.
    resident = t * 2 * d_pad * itemsize   # K + V at input dtype
    fits = (t == k_shape[2] and t >= 128 and t % 128 == 0 and d % 64 == 0
            and resident <= 4 * 1024 * 1024)
    if fits and window is not None and window < t:
        # The band kernels (a q block against the key blocks its band
        # reaches) are far under that at a window of a block or two (2.25 MB
        # forward, 4.00 backward at T=8192, d=128, window 512) and grow by
        # 1.5 MB a block of 512 behind the diagonal's: held to 12 MB, a
        # window of 3,073 there (Mosaic passes 4,096 and refuses 8,191).
        # The T cap above is not lifted for them: no cell runs a banded
        # layer past it.
        from tpu_compressed_dp.ops.flash_attention import band_vmem_bytes

        fits = band_vmem_bytes(t, d, itemsize, window) <= 12 * 1024 * 1024
    return fits


def _fused_causal(q: Array, k: Array, v: Array, scale: float,
                  window: Optional[int] = None) -> Array:
    from tpu_compressed_dp.ops.flash_attention import flash_causal_attention

    return flash_causal_attention(q, k, v, scale, False, window)


def _block_attend(q, k, v, q_pos, k_pos, scale, o, m, l, window=None):
    """One online-softmax accumulation step against a K/V block.

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; *_pos: [Tq]/[Tk] global positions.
    o/m/l: running output [B,H,Tq,D], row max [B,H,Tq], row sum [B,H,Tq].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    causal = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
    if window is not None:
        causal &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(causal[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # fully-masked rows keep m == -inf sentinel; exp(-inf - -inf) guarded to 0
    corr = jnp.where(m > _NEG_INF / 2, jnp.exp(m - m_new), 0.0)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(causal[None, None], p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    o_new = o * corr[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    axis_name: Optional[str] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> Array:
    """Causal attention; ``q/k/v``: [B, H, T_local, D] (local sequence block).

    With ``axis_name`` set (inside shard_map over a sequence mesh axis), the
    full sequence is ``ring_size * T_local`` long and device ``i`` holds
    positions ``[i*T_local, (i+1)*T_local)``.  Without it, plain single-block
    causal attention (the ring degenerates to one step).

    GQA: pass K/V with fewer heads than Q as long as ``H_q % H_kv == 0``
    (heads are repeated locally — no extra wire traffic).

    Head widths: ``q`` and ``k`` share one, and ``scale`` defaults to its
    inverse root; ``v`` may be WIDER (a differential-attention pair's values
    are two heads side by side: q, k of 64 on v of 128).  ``q`` and ``k`` are
    then zero-padded to ``v``'s width (zero columns are inert in ``q k^T``)
    and the call is one of that width: the flash kernels pad every head to
    128 lanes anyway, so at 64 on 128 nothing is added to what they move,
    and the score product does 128 columns where 64 are data.

    ``window``: a query sees itself and the ``window - 1`` keys before it
    (sliding-window attention).  Single block only: the fused band kernels
    meet each q block with the key blocks its band reaches, the XLA chain
    masks the rest; the ring path, whose blocks behind the band would still
    travel, refuses one.
    """
    if q.shape[1] != k.shape[1]:
        if q.shape[1] % k.shape[1]:
            raise ValueError(f"H_q={q.shape[1]} not a multiple of H_kv={k.shape[1]}")
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    t_local = q.shape[2]
    d = q.shape[3]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if v.shape[3] != d:
        if v.shape[3] < d:
            raise ValueError(f"values of {v.shape[3]} under keys of {d}: the "
                             "values may be wider than the keys, not narrower")
        widen = ((0, 0),) * 3 + ((0, v.shape[3] - d),)
        q, k, d = jnp.pad(q, widen), jnp.pad(k, widen), v.shape[3]

    if axis_name is None:
        ring, my = 1, 0
    else:
        # the axis size is static at trace time — a size-1 seq axis (the LM
        # harness always names the axis, sp=1 or not) degenerates to the
        # single-block case and must hit the same fused path
        ring = jax.lax.psum(1, axis_name)
        my = jax.lax.axis_index(axis_name)
    if window is not None and ring != 1:
        raise NotImplementedError("a window on the ring path is not written: "
                                  "every block would still go round")
    if ring == 1 and use_fused_attention(q.shape, k.shape, q.dtype.itemsize,
                                         window):
        return _fused_causal(q, k, v, scale, window)

    q_pos = my * t_local + jnp.arange(t_local)
    qf = q.astype(jnp.float32)
    o = jnp.zeros(q.shape[:3] + (d,), jnp.float32)
    m = jnp.full(q.shape[:3], _NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3], jnp.float32)

    perm = None
    if ring > 1:
        # block i travels i -> i+1 each step, so after s steps device `my`
        # holds block (my - s) mod ring
        perm = [(i, (i + 1) % ring) for i in range(ring)]

    def step(s, carry):
        o, m, l, kb, vb = carry
        src = (my - s) % ring if axis_name is not None else 0
        k_pos = src * t_local + jnp.arange(t_local)
        o, m, l = _block_attend(qf, kb.astype(jnp.float32), vb, q_pos, k_pos,
                                scale, o, m, l, window)
        if perm is not None:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
        return o, m, l, kb, vb

    carry = (o, m, l, k, v)
    # static ring size -> unrolled python loop (each iteration's ppermute can
    # overlap the next block's compute in XLA's schedule)
    for s in range(ring):
        carry = step(s, carry)
    o, m, l = carry[:3]

    # every causal query row attends to itself, so l > 0
    return (o / l[..., None]).astype(q.dtype)


def dense_causal_attention(q: Array, k: Array, v: Array,
                           scale: Optional[float] = None) -> Array:
    """Reference implementation (full [T, T] scores) for tests."""
    return ring_attention(q, k, v, axis_name=None, scale=scale)
