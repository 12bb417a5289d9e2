"""Tiled causal flash attention (forward + backward) in Pallas, over the
whole sequence or, with a ``window``, over the band a sliding-window layer
sees.

The single-block attention path of :mod:`tpu_compressed_dp.ops.ring_attention`
— the unfused XLA chain materialises the [T, T] probability matrix in HBM
(~400 MB fp32 per layer pass at T=1024, 16x that at 4096), the dominant
non-matmul HBM traffic of the LM step (VERDICT r3 weak #5).  This kernel
streams K/V blocks through VMEM with the standard online-softmax recurrence,
so only O(T·D) leaves the chip per pass.

Built in-repo rather than taken from jax.experimental's ops because the sync
engines run inside ``shard_map`` with replication checking on: every
``pallas_call`` out_shape must carry the varying-mesh-axes of its inputs
(``_vma`` plumbing, like ops/kernels.py), which stock kernels do not thread.

Backward follows the flash-attention recipe: save (o, lse) from forward,
precompute ``delta = rowsum(do * o)``, then ONE kernel walks the live
(q block, kv block) pairs of a head, recomputes each pair's score block in
VMEM instead of reading a saved [T, T], and feeds s, p, dP and ds — computed
once a pair — to all three gradients: dk/dv of the grid step's kv block
accumulate over its q blocks, dq of the whole head accumulates over the kv
axis in a float32 [T, d_pad] VMEM scratch (5 products a pair; a kernel for
dq and one for dk/dv would each recompute s and dP: 7, and the chain twice).

Which pairs are masked: a (q block, kv block) pair wholly above the diagonal
is never visited; every visited pair builds ``_causal_pos`` and selects
through it, though only the pairs that straddle the diagonal have a masked
element (8 of 36 a head at T=4096, 16 of 136 at T=8192).  Bodies without the
mask for the pairs under the diagonal were built and timed (PERF.md, PR 40):
the 651 mask operations a 512 x 512 pair sit in VALU slots that are empty
anyway, and two loops a kernel read 0.7 % slower to 0.7 % faster than one, so
there is one loop.

The band (``window``, a static argument: a query sees itself and the
``window - 1`` keys before it, ``i - window < j <= i``).  A pair wholly behind
the band is never visited either: the forward's pair loop starts at the kv
block of the q block's first row's oldest key, the backward's ends at the q
block of the last row that still sees the kv block's last key (its DMA
prefetch stops there too).  At T=8192 in blocks of 512 a window of 512 visits
2 pairs a q block, 31 a head, where causal attention visits 136; every
visited pair selects through the one mask, which then has both edges
(``_causal_pos``).  With no window every bound and the mask are what they
were, and so is the trace.  What stays resident for a whole head does not shrink with
the band: K and V in the forward, the float32 dq accumulator in the backward
still span T (a band needs only ``window + block`` of either; not written).

The forward pair's pace is the cross-lane unit, which is
why its running maximum and sum are kept lane-replicated in ``[blk_q, 128]``
scratch: a ``[blk_q, 1]`` statistic has to be broadcast over the lanes again,
an XLU round trip a row group, wherever it meets a block.

Mosaic-shaped storage: per-row scalars (lse, delta) cannot leave a kernel as
``[1, block_q]`` blocks (block last-two-dims must be 8/128-divisible), so
they ride the LANE dimension of the tensors that already flow: the forward
packs ``lse`` into lane ``d`` of the (lane-padded) output block, and the
backward wrapper packs ``delta``/``lse`` into lanes ``d``/``d+1`` of the
incoming cotangent.  At a head_dim of 64 the pad lanes exist anyway and the
stats travel free.  At 128 (Ouro-2.6B) the data fills its tile, so the stats
take a second 128-lane tile: the forward's packed output and the backward's
packed cotangent are 256 lanes wide, float32, and the kernels that write and
read them move twice the bytes of ``o`` and ``do`` for two lanes of stats.

Layout: [B, H, T, D]; causal, whole or banded (the framework's LM decoders:
no bidirectional or document-boundary mask); D padded to
the 128-lane tile in the wrapper (zero columns are inert through qk/pv and
sliced off).  Matmuls run on the MXU with fp32 accumulation
(``preferred_element_type``); bf16 inputs keep bf16 operands — the same
accumulation discipline as XLA's own attention lowering.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

__all__ = ["flash_causal_attention"]

_NEG_INF = -1e30


def _vma(x: Array):
    return jax.typeof(x).vma


def _causal_pos(qi, kj, blk_q, blk_k, window=None):
    """Which elements of the (q block, kv block) pair a query sees: the keys
    at or before it and, with a ``window``, no further back than the
    ``window - 1`` before it (``i - window < j <= i``)."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic under ``n`` lanes, a
    multiple of 128 as every block and padded head is: the same vregs again,
    no operation."""
    return jnp.concatenate([x] * (n // 128), axis=1)


def _fwd_kernel(scale: float, blk_q: int, blk_k: int, n_k: int, d: int,
                q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, window=None):
    qi = pl.program_id(1)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                     # [blk_q, d_pad]

    def body(kj, _):
        k = k_ref[0, pl.ds(kj * blk_k, blk_k)]       # [blk_k, d_pad]
        v = v_ref[0, pl.ds(kj * blk_k, blk_k)]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
        s = jnp.where(_causal_pos(qi, kj, blk_q, blk_k, window), s, _NEG_INF)
        # m and l are [blk_q, 128] with every lane of a row equal: a row
        # reduce's result leaves the cross-lane unit in every lane, so
        # widening it is no operation, and neither m under s nor corr over
        # the accumulator needs the lane broadcast (an XLU round trip a row
        # group, the pair's pace: PERF.md, PR 40) that a [blk_q, 1] column does
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(
            jnp.max(s, axis=1, keepdims=True), m_prev.shape))
        p = jnp.exp(s - _lanes(m_new, blk_k))        # masked lanes -> 0
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), corr.shape)
        acc_ref[:] = (acc_ref[:] * _lanes(corr, acc_ref.shape[1])
                      + jax.lax.dot_general(
                          p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
        m_ref[:] = m_new
        return 0

    # causal: q block qi attends kv blocks 0..ceil((qi+1)*blk_q / blk_k)-1;
    # trailing blocks are fully masked — skipped entirely.  With a window the
    # blocks wholly behind the band are skipped too: the block's first row
    # sees back to key qi*blk_q - window + 1.  A row whose band starts after
    # the first visited block takes that block's _NEG_INF as its maximum and
    # counts its lanes as exp(0); the first real maximum (the diagonal pair
    # is always visited, and last) multiplies all of that by exp(-1e30 - m),
    # which is 0
    n_live = jnp.minimum(((qi + 1) * blk_q + blk_k - 1) // blk_k, n_k)
    first = 0 if window is None else (
        jnp.maximum(qi * blk_q - (window - 1), 0) // blk_k)
    jax.lax.fori_loop(first, n_live, body, 0)
    l = l_ref[:]
    o = acc_ref[:] / _lanes(l, acc_ref.shape[1])     # [blk_q, d_pad]
    lse = (m_ref[:] + jnp.log(l))[:, :1]             # [blk_q, 1]
    d_store = o_ref.shape[-1]
    out = jnp.concatenate(
        [o[:, :d], lse] + ([jnp.zeros((blk_q, d_store - d - 1), jnp.float32)]
                           if d_store - d - 1 else []), axis=1)
    o_ref[0] = out.astype(o_ref.dtype)


def _bwd_block_math(scale, blk_q, blk_k, d, kj, qi, q, dop, k, v,
                    dq_acc, dk_acc, dv_acc, window=None):
    """One (q block) x (kv block) pair of the backward: s, p, dP and ds are
    computed once and feed all three accumulators — shared by the
    VMEM-resident and the HBM-streamed stagings of the kernel."""
    d_pad = k.shape[-1]
    # re-pad do to d_pad lanes so contractions align with the padded k/v
    # (zero lanes are inert through every product)
    do = jnp.concatenate(
        [dop[:, :d], jnp.zeros((blk_q, d_pad - d), dop.dtype)],
        axis=1).astype(jnp.float32) if d_pad > d else dop[:, :d].astype(jnp.float32)
    delta = dop[:, d:d + 1].astype(jnp.float32)
    lse = dop[:, d + 1:d + 2].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    p = jnp.where(_causal_pos(qi, kj, blk_q, blk_k, window),
                  jnp.exp(s - lse), 0.0)
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq_acc[pl.ds(qi * blk_q, blk_q)] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_kernel(scale: float, blk_q: int, blk_k: int, n_q: int, d: int,
                q_ref, k_ref, v_ref, dop_ref, dq_ref, dk_ref, dv_ref,
                dq_acc, dk_acc, dv_acc, *stream, window=None):
    """The whole backward of one head, one kv block a grid step.  dk/dv of
    the step's kv block accumulate over the q blocks at or below the
    diagonal; dq of the whole head accumulates in ``dq_acc`` [T, d_pad],
    which stays in VMEM across the (sequential) kv axis: q block ``qi``
    receives its kv blocks in ascending ``kj``, and q rows inside kv block
    ``kj`` attend nothing past it, so their dq is final when step ``kj``
    ends and leaves as that step's output block.

    Two stagings of the full-T operands (Q and the packed cotangent).  With
    ``stream`` empty they are whole VMEM blocks (interpret mode's default).
    Otherwise they stay in HBM and ``stream`` = (q_buf, dop_buf, q_sem,
    dop_sem) double-buffers them per q block via explicit DMA: at
    T=8192/d=128 the resident q (bf16, 2 MB) + packed f32 cotangent (8 MB),
    Mosaic-double-buffered, blow the 16 MB scoped-vmem ceiling (measured
    17.5 MB, r5); streamed, residency is 2 q-blocks + 2 dop-blocks (~1 MB)
    plus the dq accumulator (T * d_pad * 4 bytes, 4 MB at most under
    ``ring_attention.fused_attention_fits``)."""
    bh = pl.program_id(0)
    kj = pl.program_id(1)
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kj == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    k = k_ref[0]                                     # [blk_k, d_pad]
    v = v_ref[0]
    rows = lambda qi: pl.ds(qi * blk_q, blk_q)
    # q blocks qi >= kj*blk_k // blk_q can contain positions >= this kv block;
    # with a window the last row that sees the block's last key is
    # (kj+1)*blk_k - 1 + window - 1, and the q blocks past it are skipped
    first = kj * blk_k // blk_q
    last = n_q if window is None else jnp.minimum(
        ((kj + 1) * blk_k + window - 2) // blk_q + 1, n_q)

    if not stream:
        fetch = lambda qi: (q_ref[0, rows(qi)], dop_ref[0, rows(qi)])
    else:
        q_buf, dop_buf, q_sem, dop_sem = stream

        def dmas(qi):
            slot = jax.lax.rem(qi, 2)
            return (
                pltpu.make_async_copy(q_ref.at[bh, rows(qi)], q_buf.at[slot],
                                      q_sem.at[slot]),
                pltpu.make_async_copy(dop_ref.at[bh, rows(qi)],
                                      dop_buf.at[slot], dop_sem.at[slot]))

        for dma in dmas(first):
            dma.start()

        def fetch(qi):
            @pl.when(qi + 1 < last)
            def _():
                for dma in dmas(qi + 1):
                    dma.start()

            for dma in dmas(qi):
                dma.wait()
            slot = jax.lax.rem(qi, 2)
            return q_buf[slot], dop_buf[slot]

    def body(qi, _):
        q, dop = fetch(qi)
        _bwd_block_math(scale, blk_q, blk_k, d, kj, qi, q, dop, k, v,
                        dq_acc, dk_acc, dv_acc, window)
        return 0

    jax.lax.fori_loop(first, last, body, 0)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
    dq_ref[0] = dq_acc[pl.ds(kj * blk_k, blk_k)].astype(dq_ref.dtype)


def _pick_blocks(t: int) -> tuple:
    # One rule for every length, banded or not: 512 x 512, halved until it
    # divides T.  A pair costs ~0.34 us forward and ~0.67 us backward whatever
    # its size (PERF.md, PR 40), so nothing smaller is taken while 512 fits:
    # at T=8192 a head is 136 pairs, 31 in a band of 512 (2 a q block), where
    # blocks of 256 visit 528 and 94.  What the compile for a v5e reports at
    # the admitted extreme, (b, h, 8192, 128) in bf16, of the 16 MB scoped-VMEM
    # ceiling: forward 10.00 MB (K and V whole and double-buffered 8, the
    # packed output's two blocks 1, accumulator and statistics 0.75, q 0.25),
    # backward 7.00 MB (the dq accumulator 4, the streamed cotangent's two
    # blocks 1 and q's 0.25, dk/dv accumulators 0.5, k/v in and dq/dk/dv out
    # 1.25).
    bq = min(512, t)
    while t % bq:
        bq //= 2
    return bq, bq


def _d_store(d: int) -> int:
    d_pad = d + (-d) % 128
    # lse/delta ride lanes d, d+1 — need two spare lanes past the data.  A
    # d that fills its tile (128) pays a whole further tile for them: the
    # packed o / do are then [T, 256] float32, 2 KB a row where 1 KB is data
    return d_pad if d_pad - d >= 2 else d_pad + 128


def _pad_lanes(x: Array, to: int) -> Array:
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, to - x.shape[-1]),))


def _fwd(q, k, v, scale, blk, interpret, d, window=None):
    """q/k/v pre-padded to d_pad lanes; returns packed o (lse at lane d)."""
    b, h, t, d_pad = q.shape
    bq, bk = blk
    vma = _vma(q)
    qs, ks, vs = (x.reshape(b * h, t, d_pad) for x in (q, k, v))
    ds = _d_store(d)
    kv_spec = pl.BlockSpec((1, t, d_pad), lambda bh, qi: (bh, 0, 0),
                           memory_space=pltpu.VMEM)
    o_packed = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, bq, bk, t // bk, d, window=window),
        grid=(b * h, t // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d_pad), lambda bh, qi: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            kv_spec, kv_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, ds), lambda bh, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, t, ds), jnp.float32, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((bq, d_pad), jnp.float32),
            # m and l, lane-replicated: the 64 vregs a block that a [bq, 1]
            # scratch is tiled to anyway, every lane of them in use
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn_fwd",
    )(qs, ks, vs)
    return o_packed.reshape(b, h, t, ds)


def _bwd(q, k, v, dop, scale, blk, interpret, out_dtype, d, window=None):
    b, h, t, d_pad = q.shape
    bq, bk = blk
    vma = _vma(q)
    ds = dop.shape[-1]
    qs, ks, vs = (x.reshape(b * h, t, d_pad) for x in (q, k, v))
    dops = dop.reshape(b * h, t, ds)
    kv_block = pl.BlockSpec((1, bk, d_pad), lambda bh, kj: (bh, kj, 0),
                            memory_space=pltpu.VMEM)
    # Streamed off-interpret: Q and the packed cotangent stay in HBM, the
    # kernel DMAs per-q-block slices itself (see _bwd_kernel).  Interpret
    # mode (CPU tests) keeps them as whole VMEM blocks — identical math via
    # _bwd_block_math — unless TPU_CDP_FORCE_STREAMED_DKV=1, which runs the
    # DMA/double-buffer machinery under the Pallas interpreter so the
    # streamed staging has off-chip parity coverage (ADVICE r5;
    # tests/test_flash_attention.py::test_streamed_bwd_matches_resident).
    if interpret and os.environ.get("TPU_CDP_FORCE_STREAMED_DKV") != "1":
        full = lambda w: pl.BlockSpec((1, t, w), lambda bh, kj: (bh, 0, 0),
                                      memory_space=pltpu.VMEM)
        q_spec, dop_spec = full(d_pad), full(ds)
        stream_scratch = []
    else:
        q_spec = dop_spec = pl.BlockSpec(memory_space=pl.ANY)
        stream_scratch = [
            pltpu.VMEM((2, bq, d_pad), qs.dtype),
            pltpu.VMEM((2, bq, ds), dops.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    # the kv axis carries dq_acc from step to step: it must stay sequential
    # (Mosaic's default for an axis nobody declares parallel)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale, bq, bk, t // bq, d, window=window),
        grid=(b * h, t // bk),
        in_specs=[q_spec, kv_block, kv_block, dop_spec],
        out_specs=[kv_block] * 3,
        out_shape=[jax.ShapeDtypeStruct((b * h, t, d_pad), out_dtype,
                                        vma=vma)] * 3,
        scratch_shapes=[
            pltpu.VMEM((t, d_pad), jnp.float32),
            pltpu.VMEM((bk, d_pad), jnp.float32),
            pltpu.VMEM((bk, d_pad), jnp.float32),
        ] + stream_scratch,
        interpret=interpret,
        name="flash_attn_bwd",
    )(qs, ks, vs, dops)
    rs = lambda x: x.reshape(b, h, t, d_pad)
    return rs(dq), rs(dk), rs(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_causal_attention(q: Array, k: Array, v: Array,
                           scale: Optional[float] = None,
                           interpret: bool = False,
                           window: Optional[int] = None) -> Array:
    """Exact causal attention, flash-tiled; [B, H, T, D] (equal q/kv heads —
    GQA repeat happens in the caller, ring_attention).  With ``window`` a
    query sees itself and the ``window - 1`` keys before it."""
    o, _ = _fa_fwd(q, k, v, scale, interpret, window)
    return o


def _fa_fwd(q, k, v, scale, interpret, window=None):
    b, h, t, d = q.shape
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    d_pad = d + (-d) % 128
    qp, kp, vp = (_pad_lanes(x, d_pad) for x in (q, k, v))
    o_packed = _fwd(qp, kp, vp, s, _pick_blocks(t), interpret, d, window)
    o = o_packed[..., :d].astype(q.dtype)
    lse = o_packed[..., d]
    return o, (q, k, v, o, lse)


def _fa_bwd(scale, interpret, res, do, window=None):
    q, k, v, o, lse = res
    b, h, t, d = q.shape
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    d_pad = d + (-d) % 128
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    ds = _d_store(d)
    dop = _pad_lanes(
        jnp.concatenate([do.astype(jnp.float32), delta[..., None],
                         lse[..., None]], axis=-1), ds)
    qp, kp, vp = (_pad_lanes(x, d_pad) for x in (q, k, v))
    dq, dk, dv = _bwd(qp, kp, vp, dop, s, _pick_blocks(t), interpret,
                      q.dtype, d, window)
    return dq[..., :d], dk[..., :d], dv[..., :d]


flash_causal_attention.defvjp(
    _fa_fwd, lambda scale, interpret, window, res, do: _fa_bwd(
        scale, interpret, res, do, window))
