"""Tiled causal flash attention (forward + backward) in Pallas: one pair of
kernels over the whole sequence and, for a call with a ``window``, a pair of
their own over the band a sliding-window layer sees.

The single-block attention path of :mod:`tpu_compressed_dp.ops.ring_attention`
— the unfused XLA chain materialises the [T, T] probability matrix in HBM
(~400 MB fp32 per layer pass at T=1024, 16x that at 4096), the dominant
non-matmul HBM traffic of the LM step (VERDICT r3 weak #5).  These kernels
stream K/V blocks through VMEM, so only O(T·D) leaves the chip per pass.

Built in-repo rather than taken from jax.experimental's ops because the sync
engines run inside ``shard_map`` with replication checking on: every
``pallas_call`` out_shape must carry the varying-mesh-axes of its inputs
(``_vma`` plumbing, like ops/kernels.py), which stock kernels do not thread.

One entry, ``flash_causal_attention``, and a static argument chooses:
``window is None`` (or a window that reaches the whole sequence, which is the
same call) takes ``flash_attn_fwd`` / ``flash_attn_bwd``, anything else
``flash_attn_band_fwd`` / ``flash_attn_band_bwd``.  The two pairs share the
block rule (``_pick_blocks``), the lane packing of ``lse`` and ``delta``
(below) and the wrappers' pads; they share no loop.

**The whole-sequence kernels.**  The forward keeps K and V of a head resident
and walks the kv blocks under a q block's diagonal with the standard
online-softmax recurrence.  Backward follows the flash-attention recipe: save
(o, lse) from forward, precompute ``delta = rowsum(do * o)``, then ONE kernel
walks the live (q block, kv block) pairs of a head, recomputes each pair's
score block in VMEM instead of reading a saved [T, T], and feeds s, p, dP and
ds — computed once a pair — to all three gradients: dk/dv of the grid step's
kv block accumulate over its q blocks, dq of the whole head accumulates over
the kv axis in a float32 [T, d_pad] VMEM scratch (5 products a pair; a kernel
for dq and one for dk/dv would each recompute s and dP: 7, and the chain
twice).

Which pairs are masked: a (q block, kv block) pair wholly above the diagonal
is never visited; every visited pair builds ``_causal_pos`` and selects
through it, though only the pairs that straddle the diagonal have a masked
element (8 of 36 a head at T=4096, 16 of 136 at T=8192).  Bodies without the
mask for the pairs under the diagonal were built and timed (PERF.md, PR 40):
the 651 mask operations a 512 x 512 pair sit in VALU slots that are empty
anyway, and two loops a kernel read 0.7 % slower to 0.7 % faster than one, so
there is one loop.

**The band kernels** (``window``, a static argument: a query sees itself and
the ``window - 1`` keys before it, ``i - window < j <= i``).  A q block meets
its own key block and the ``n_back = ceil((window - 1) / block)`` before it,
a static number (1 in blocks of 512 under a window of 512), so a grid step is
straight-line code and nothing of length T is resident.  Forward, grid
``(b*h, T // block)``: K and V arrive as ``n_back + 1`` ordinary BlockSpecs
each on the same array (index ``max(qi - r, 0)``; a block whose index was
clamped is masked whole, by where it would stand); every score of a row is in
hand before its maximum is taken, so there is one softmax a row, no running
statistic, no correction and no scratch.  Backward, the same grid over key
blocks: the q blocks that reach key block ``kj`` are ``kj .. kj + n_back``, q
and the packed cotangent arrive by BlockSpec (index ``min(kj + r, last)``:
the pipeline's own double buffer), dk/dv of the step's block are whole when
it ends, and dq of a q block is summed over ``n_back + 1`` steps in
``n_back`` blocks of float32 scratch and leaves at the step of its own key
block.  A key-major step reads every q and cotangent block ``n_back + 1``
times: ~3.9 GB a call of (128, 8192, 128) where a q-major one (dk/dv
carried) reads ~3.2; the MXU's schedule binds it, not the bytes, and the
q-major form read 6 % slower on the chip (PERF.md, PR 46).

Where a half block is a lane multiple (blocks of 256 and 512) the band is
walked in halves: a half's rows see nothing of the half block past their own
diagonal, nor of the half behind their band's far end, which a whole block's
rows have to compute and mask.  Under a window of 512 in blocks of 512 each
half meets 768 of the 1,024 fetched keys: six quarters of eight, and of the
six only the oldest (the band's edge) and the newest (the diagonal) build a
mask; an edge is built only where it cuts a pair (``_band_seen``).  A half's
keys (backward: a key half's q rows) are multiplied a fetched block at a
time, not a quarter at a time: two products of 256 x 512 and 256 x 256 in
place of three of 256 x 256 (the MXU's slots 77 % full against 64 % in the
backward's static schedule, PERF.md, PR 46).

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

__all__ = ["flash_causal_attention", "band_vmem_bytes"]

_NEG_INF = -1e30


def _vma(x: Array):
    return jax.typeof(x).vma


def _causal_pos(qi, kj, blk_q, blk_k):
    """Which elements of the (q block, kv block) pair a query sees: the keys
    at or before it."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    return q_pos >= k_pos


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic under ``n`` lanes, a
    multiple of 128 as every block and padded head is: the same vregs again,
    no operation.  (Blocks under 128, which only the interpreter is handed,
    keep their statistic as wide as they are.)"""
    return jnp.concatenate([x] * (n // x.shape[1]), axis=1)


def _pack_o_lse(o, lse, d: int, d_store: int):
    """``lse`` [rows, 1] into lane ``d`` of the output's rows, zeros behind."""
    rows = o.shape[0]
    return jnp.concatenate(
        [o[:, :d], lse] + ([jnp.zeros((rows, d_store - d - 1), jnp.float32)]
                           if d_store - d - 1 else []), axis=1)


def _unpack_cotangent(dop, d: int, d_pad: int):
    """(do re-padded to ``d_pad`` lanes so contractions align with the padded
    k/v — zero lanes are inert through every product —, delta, lse) of the
    packed cotangent's rows, all float32."""
    rows = dop.shape[0]
    do = jnp.concatenate(
        [dop[:, :d], jnp.zeros((rows, d_pad - d), dop.dtype)],
        axis=1).astype(jnp.float32) if d_pad > d else dop[:, :d].astype(jnp.float32)
    delta = dop[:, d:d + 1].astype(jnp.float32)
    lse = dop[:, d + 1:d + 2].astype(jnp.float32)
    return do, delta, lse


def _fwd_kernel(scale: float, blk_q: int, blk_k: int, n_k: int, d: int,
                q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
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
        s = jnp.where(_causal_pos(qi, kj, blk_q, blk_k), s, _NEG_INF)
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
    # trailing blocks are fully masked — skipped entirely
    n_live = jnp.minimum(((qi + 1) * blk_q + blk_k - 1) // blk_k, n_k)
    jax.lax.fori_loop(0, n_live, body, 0)
    l = l_ref[:]
    o = acc_ref[:] / _lanes(l, acc_ref.shape[1])     # [blk_q, d_pad]
    lse = (m_ref[:] + jnp.log(l))[:, :1]             # [blk_q, 1]
    o_ref[0] = _pack_o_lse(o, lse, d, o_ref.shape[-1]).astype(o_ref.dtype)


def _bwd_block_math(scale, blk_q, blk_k, d, kj, qi, q, dop, k, v,
                    dq_acc, dk_acc, dv_acc):
    """One (q block) x (kv block) pair of the backward: s, p, dP and ds are
    computed once and feed all three accumulators — shared by the
    VMEM-resident and the HBM-streamed stagings of the kernel."""
    do, delta, lse = _unpack_cotangent(dop, d, k.shape[-1])
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    p = jnp.where(_causal_pos(qi, kj, blk_q, blk_k),
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
                dq_acc, dk_acc, dv_acc, *stream):
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
    # q blocks qi >= kj*blk_k // blk_q can contain positions >= this kv block
    first = kj * blk_k // blk_q

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
            @pl.when(qi + 1 < n_q)
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
                        dq_acc, dk_acc, dv_acc)
        return 0

    jax.lax.fori_loop(first, n_q, body, 0)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
    dq_ref[0] = dq_acc[pl.ds(kj * blk_k, blk_k)].astype(dq_ref.dtype)


def _band_seen(rows: int, cols: int, back: int, window: int, live=None):
    """Which elements of a ``rows`` x ``cols`` pair a query sees when the
    pair's first key stands ``back`` positions before its first query (row
    ``i``, column ``j``: ``0 <= back + i - j < window``), or None where that
    is all of them.  Both edges are static and each is built only where it
    cuts the pair.  ``live`` is a traced scalar, false where the pair's block
    does not exist (its index was clamped at an end of the sequence): it
    rides the band edge's bound, so nothing of such a pair is seen."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
             - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    seen = ahead >= -back if back < cols - 1 else None
    if back + rows - 1 >= window or live is not None:
        edge = min(window - back, rows)          # ahead < rows always holds
        if live is not None:
            edge = jnp.where(live, edge, -cols)  # ahead < -cols never does
        seen = ahead < edge if seen is None else seen & (ahead < edge)
    return seen


def _band_geometry(blk: int, window: int, n_blocks: int):
    """(rows of a sub-block, blocks behind the diagonal's that the kernels
    fetch, sub-blocks behind its own that a sub-block's band reaches) for a
    sequence of ``n_blocks`` blocks.  The band is walked in halves of a block
    where a half is a lane multiple, in whole blocks otherwise: a half's rows
    see nothing of the half block past their diagonal nor of the one behind
    their band's far end, which a whole block's rows have to compute and
    mask."""
    sub = blk // 2 if blk % 256 == 0 else blk
    n_back = min(-(-(window - 1) // blk), n_blocks - 1)
    return sub, n_back, -(-(window - 1) // sub)


def _spans(lo: int, hi: int, blk: int, n_back: int):
    """The positions ``lo .. hi`` (counted from the first of ``n_back + 1``
    consecutive blocks) as (block, slice within it, first position)."""
    for r in range(n_back + 1):
        start, stop = max(lo, r * blk), min(hi, (r + 1) * blk)
        if start < stop:
            yield r, slice(start - r * blk, stop - r * blk), start


def _band_fwd_kernel(scale: float, blk: int, sub: int, reach: int,
                     window: int, d: int, q_ref, *refs):
    """One q block against the key blocks its band reaches, ``refs`` = K's
    blocks from the diagonal's back to the ``n_back``-th behind it, V's
    likewise, the packed output: straight-line code, one softmax a row (every
    score of a row is in hand before its maximum is taken, so there is no
    running statistic and no correction).  A sub-block's keys are multiplied
    a fetched block at a time."""
    n_back = (len(refs) - 1) // 2 - 1
    k_refs, v_refs, o_ref = refs[:n_back + 1], refs[n_back + 1:-1], refs[-1]
    qi = pl.program_id(1)
    n_sub, lanes = blk // sub, min(sub, 128)
    for a in range(n_sub):
        rows = slice(a * sub, (a + 1) * sub)
        q = q_ref[0, rows]                            # [sub, d_pad]
        scores, values = [], []
        # positions counted from the oldest fetched block's first key
        q0 = n_back * blk + a * sub
        lo = max(q0 - reach * sub, 0)
        for b, cols, k0 in _spans(lo, q0 + sub, blk, n_back):
            r = n_back - b
            s = jax.lax.dot_general(
                q, k_refs[r][0, cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # the first n_back q blocks of a head fetch block 0 in place of
            # the blocks before it: masked whole, by where they would stand
            seen = _band_seen(sub, cols.stop - cols.start, q0 - k0, window,
                              qi >= r if r else None)
            scores.append(s if seen is None else jnp.where(seen, s, _NEG_INF))
            values.append(v_refs[r][0, cols])
        # the diagonal's keys are always among them, so m is a real score and
        # a masked lane's exp(-1e30 - m) is 0; m and l lane-replicated as in
        # the whole-sequence kernel, for the same reason
        m = jnp.broadcast_to(functools.reduce(jnp.maximum, [
            jnp.max(s, axis=1, keepdims=True) for s in scores]), (sub, lanes))
        ps = [jnp.exp(s - _lanes(m, s.shape[1])) for s in scores]
        l = jnp.broadcast_to(sum(
            jnp.sum(p, axis=1, keepdims=True) for p in ps), (sub, lanes))
        acc = sum(jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for p, v in zip(ps, values))
        o = acc / _lanes(l, acc.shape[1])             # [sub, d_pad]
        lse = (m + jnp.log(l))[:, :1]
        o_ref[0, rows] = _pack_o_lse(o, lse, d, o_ref.shape[-1]).astype(
            o_ref.dtype)


def _band_bwd_kernel(scale: float, blk: int, sub: int, reach: int,
                     window: int, d: int, n_q: int, k_ref, v_ref, *refs):
    """The backward of one key block against the q blocks whose band reaches
    it, ``refs`` = q's blocks from the diagonal's on to the ``n_back``-th past
    it, the packed cotangent's likewise, dq, dk, dv and (where ``n_back``)
    the dq carry: straight-line code, s, p, dP and ds computed once a pair as
    in ``_bwd_block_math``.  dk/dv of the step's block are whole when it
    ends.  dq of q block ``kj + r`` is not: it leaves at step ``kj + r``, and
    until then its sum rides ``dq_carry[r - 1]``, float32 (``n_back`` blocks,
    where the whole-sequence kernel carries T rows)."""
    n_back = (len(refs) - 3) // 2 - 1
    q_refs, dop_refs = refs[:n_back + 1], refs[n_back + 1:2 * n_back + 2]
    dq_ref, dk_ref, dv_ref = refs[2 * n_back + 2:2 * n_back + 5]
    dq_carry = refs[-1] if n_back else None
    kj = pl.program_id(1)
    n_sub = blk // sub
    d_pad = k_ref.shape[-1]

    if n_back:
        @pl.when(kj == 0)
        def _():
            dq_carry[:] = jnp.zeros_like(dq_carry)

    dq = {}     # (q block past the diagonal's, sub-block) -> this step's sum
    for c in range(n_sub):
        cols = slice(c * sub, (c + 1) * sub)
        k, v = k_ref[0, cols], v_ref[0, cols]         # [sub, d_pad]
        dk = dv = 0.0
        hi = min((c + reach + 1) * sub, (n_back + 1) * blk)
        for r, rows, q0 in _spans(c * sub, hi, blk, n_back):
            n_rows = rows.stop - rows.start
            q = q_refs[r][0, rows]
            do, delta, lse = _unpack_cotangent(dop_refs[r][0, rows], d, d_pad)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - lse)
            # the last n_back key blocks of a head fetch the last q block in
            # place of the blocks past it: masked whole
            seen = _band_seen(n_rows, sub, q0 - c * sub, window,
                              kj + r < n_q if r else None)
            if seen is not None:
                p = jnp.where(seen, p, 0.0)
            dv += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dk += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            part = jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for i in range(n_rows // sub):
                a = rows.start // sub + i
                dq[r, a] = dq.get((r, a), 0.0) + part[i * sub:(i + 1) * sub]
        dk_ref[0, cols] = dk.astype(dk_ref.dtype)
        dv_ref[0, cols] = dv.astype(dv_ref.dtype)
    # in ascending r: carry[r - 1] is read (as block r - 1's) before it is
    # written (with block r's), so the carry moves up a block as it is summed
    for r in range(n_back + 1):
        for a in range(n_sub):
            rows = slice(a * sub, (a + 1) * sub)
            total = dq.get((r, a), jnp.zeros((sub, d_pad), jnp.float32))
            if r < n_back:
                total += dq_carry[r, rows]
            if r:
                dq_carry[r - 1, rows] = total
            else:
                dq_ref[0, rows] = total.astype(dq_ref.dtype)


def _pick_blocks(t: int) -> tuple:
    # One rule for every length and for both pairs of kernels: 512 x 512,
    # halved until it divides T.  A pair costs ~0.34 us forward and ~0.67 us
    # backward whatever its size (PERF.md, PR 40), so nothing smaller is taken
    # while 512 fits: at T=8192 a head is 136 pairs where blocks of 256 visit
    # 528, and a band of 512 is 16 grid steps of six 256 x 256 quarters.
    # What the compile for a v5e reports at the admitted extreme,
    # (b, h, 8192, 128) in bf16, of the 16 MB scoped-VMEM ceiling.  The
    # whole-sequence kernels: forward 10.00 MB (K and V whole and
    # double-buffered 8, the packed output's two blocks 1, accumulator and
    # statistics 0.75, q 0.25), backward 7.00 MB (the dq accumulator 4, the
    # streamed cotangent's two blocks 1 and q's 0.25, dk/dv accumulators 0.5,
    # k/v in and dq/dk/dv out 1.25).  The band kernels under a window of 512
    # (one block behind the diagonal's), every operand the pipeline's two
    # blocks: forward 2.25 MB (q 0.25, K and V 0.5 each, the packed output 1),
    # backward 4.00 MB (the packed cotangent's two blocks 2, q's 0.5, k and v
    # 0.5, dq/dk/dv out 0.75, the dq carry 0.25); a further block behind adds
    # 0.5 MB forward and 1.5 MB backward (``band_vmem_bytes``).
    bq = min(512, t)
    while t % bq:
        bq //= 2
    return bq, bq


def band_vmem_bytes(t: int, d: int, itemsize: int, window: int) -> int:
    """What the band backward, the larger of the two band kernels, holds in
    VMEM for (T, head width, bytes an element, window): two buffers a block
    of k, v, the three outputs, and of q and the packed cotangent for each q
    block a key block's band reaches, and the float32 dq carry.  The gate
    (``ring_attention.fused_attention_fits``) holds it to 12 MB: Mosaic
    passes 14.5 (a window of 4,096 at the extreme above) and refuses 16."""
    blk, _ = _pick_blocks(t)
    d_pad = d + (-d) % 128
    _, n_back, _ = _band_geometry(blk, window, t // blk)
    block = 2 * blk * d_pad * itemsize
    return (5 * block + (n_back + 1) * (block + 2 * blk * _d_store(d) * 4)
            + n_back * blk * d_pad * 4)


def _d_store(d: int) -> int:
    d_pad = d + (-d) % 128
    # lse/delta ride lanes d, d+1 — need two spare lanes past the data.  A
    # d that fills its tile (128) pays a whole further tile for them: the
    # packed o / do are then [T, 256] float32, 2 KB a row where 1 KB is data
    return d_pad if d_pad - d >= 2 else d_pad + 128


def _pad_lanes(x: Array, to: int) -> Array:
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, to - x.shape[-1]),))


def _fwd(q, k, v, scale, blk, interpret, d):
    """q/k/v pre-padded to d_pad lanes; returns packed o (lse at lane d)."""
    b, h, t, d_pad = q.shape
    bq, bk = blk
    vma = _vma(q)
    qs, ks, vs = (x.reshape(b * h, t, d_pad) for x in (q, k, v))
    ds = _d_store(d)
    kv_spec = pl.BlockSpec((1, t, d_pad), lambda bh, qi: (bh, 0, 0),
                           memory_space=pltpu.VMEM)
    o_packed = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, bq, bk, t // bk, d),
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


def _bwd(q, k, v, dop, scale, blk, interpret, out_dtype, d):
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
        functools.partial(_bwd_kernel, scale, bq, bk, t // bq, d),
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


def _band_fwd(q, k, v, scale, blk, interpret, d, window):
    """``_fwd`` for a call with a window: K and V reach the kernel a block at
    a time, ``n_back + 1`` BlockSpecs on each, so nothing of length T is
    resident."""
    b, h, t, d_pad = q.shape
    vma = _vma(q)
    qs, ks, vs = (x.reshape(b * h, t, d_pad) for x in (q, k, v))
    ds = _d_store(d)
    sub, n_back, reach = _band_geometry(blk, window, t // blk)
    behind = [pl.BlockSpec(
        (1, blk, d_pad), lambda bh, qi, r=r: (bh, jnp.maximum(qi - r, 0), 0),
        memory_space=pltpu.VMEM) for r in range(n_back + 1)]
    o_packed = pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale, blk, sub, reach, window, d),
        grid=(b * h, t // blk),
        in_specs=behind[:1] + behind * 2,       # q's is the diagonal's
        out_specs=pl.BlockSpec((1, blk, ds), lambda bh, qi: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, t, ds), jnp.float32, vma=vma),
        interpret=interpret,
        name="flash_attn_band_fwd",
    )(qs, *[ks] * (n_back + 1), *[vs] * (n_back + 1))
    return o_packed.reshape(b, h, t, ds)


def _band_bwd(q, k, v, dop, scale, blk, interpret, out_dtype, d, window):
    """``_bwd`` for a call with a window: q and the packed cotangent reach the
    kernel a block at a time, ``n_back + 1`` BlockSpecs on each (the
    pipeline's own double buffer), and dq is carried in ``n_back`` blocks."""
    b, h, t, d_pad = q.shape
    vma = _vma(q)
    ds = dop.shape[-1]
    qs, ks, vs = (x.reshape(b * h, t, d_pad) for x in (q, k, v))
    dops = dop.reshape(b * h, t, ds)
    n_q = t // blk
    sub, n_back, reach = _band_geometry(blk, window, n_q)
    block = lambda w, r=0: pl.BlockSpec(
        (1, blk, w), lambda bh, kj: (bh, jnp.minimum(kj + r, n_q - 1), 0),
        memory_space=pltpu.VMEM)
    # the kv axis carries dq_carry from step to step: it must stay sequential
    # (Mosaic's default for an axis nobody declares parallel)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_band_bwd_kernel, scale, blk, sub, reach, window, d,
                          n_q),
        grid=(b * h, n_q),
        in_specs=([block(d_pad)] * 2
                  + [block(d_pad, r) for r in range(n_back + 1)]
                  + [block(ds, r) for r in range(n_back + 1)]),
        out_specs=[block(d_pad)] * 3,
        out_shape=[jax.ShapeDtypeStruct((b * h, t, d_pad), out_dtype,
                                        vma=vma)] * 3,
        scratch_shapes=[pltpu.VMEM((n_back, blk, d_pad), jnp.float32)
                        ] if n_back else [],
        interpret=interpret,
        name="flash_attn_band_bwd",
    )(ks, vs, *[qs] * (n_back + 1), *[dops] * (n_back + 1))
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


def _band_of(window, t: int):
    """A window that reaches the whole sequence is the call without one."""
    return None if window is None or window >= t else window


def _fa_fwd(q, k, v, scale, interpret, window=None):
    b, h, t, d = q.shape
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    d_pad = d + (-d) % 128
    qp, kp, vp = (_pad_lanes(x, d_pad) for x in (q, k, v))
    blk, window = _pick_blocks(t), _band_of(window, t)
    if window is None:
        o_packed = _fwd(qp, kp, vp, s, blk, interpret, d)
    else:
        o_packed = _band_fwd(qp, kp, vp, s, blk[0], interpret, d, window)
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
    blk, window = _pick_blocks(t), _band_of(window, t)
    if window is None:
        dq, dk, dv = _bwd(qp, kp, vp, dop, s, blk, interpret, q.dtype, d)
    else:
        dq, dk, dv = _band_bwd(qp, kp, vp, dop, s, blk[0], interpret,
                               q.dtype, d, window)
    return dq[..., :d], dk[..., :d], dv[..., :d]


flash_causal_attention.defvjp(
    _fa_fwd, lambda scale, interpret, window, res, do: _fa_bwd(
        scale, interpret, res, do, window))
