"""Pallas TPU kernels for the hot compression ops.

The pure-JAX operators in :mod:`tpu_compressed_dp.ops.compressors` are the
reference semantics; these kernels are drop-in accelerations for the pieces
that map badly onto stock XLA at gradient scale (SURVEY.md §7 "hard parts"):

  * **Top-K threshold select** — the reference thresholds at
    ``kthvalue(|g|)`` (`CIFAR10/core.py:181-183`).  ``jax.lax.top_k`` at
    ResNet-50 scale (25M elements) pays for a full sort; the kernel instead
    finds the threshold by *iterative histogram refinement*: each round makes
    one streaming pass over ``|g|``, counting elements at or above 16
    equispaced bin edges (per-edge compare + sum, pure VPU work), then
    narrows the candidate range to the bin containing the k-th magnitude.
    Seven rounds resolve the threshold to ~``max|g| / 16^7`` = ``max|g| /
    2^28`` — below fp32 tie resolution for real gradients — in O(rounds·n)
    streamed bytes and O(1) memory, with tie semantics identical to the
    reference (everything ``>= threshold`` is kept).  (16 bins x 7 rounds
    replaced 128 x 4: same resolution, ~4x less compare work on the
    compute-bound counting pass.)
  * **Fused stochastic quantisation** (QSGD / TernGrad,
    `core.py:200-213`) — one pass that draws hardware PRNG bits
    (``pltpu.prng_random_bits``), dithers, and emits packed integer levels
    (int16 / int8), instead of XLA materialising a full fp32 uniform tensor.
    The integer levels are exactly what the wire path transmits.
  * **Fused select+pack** (``fused_select_pack``) — one pass from the
    histogram threshold to the compacted ascending ``(value, index)`` wire
    payload (per-segment shift-network compaction + an nseg-sized rank
    bucketing epilogue), replacing the wire path's dense mask ->
    `packed_indices_from_mask` -> `_sorted_gather` chain.  Bitwise-parity
    with the XLA chain, gated in tier-1 under the interpreter.
  * **Fused quantize+pack** (``terngrad_pack`` / ``qsgd_pack``) — dither
    AND bit-pack in the same pass: 2-bit TernGrad codes or QSGD uint8
    magnitudes + sign bitmap come out as wire bytes directly (matmul-based
    lane packing; the byte layout is bitwise `wire.pack_ternary` /
    `wire.pack_bits`).
  * **Fused bucket route** (``fused_bucket_route``) — the sharded
    transport's per-destination fixed-capacity bucket build as W windowed
    DMA copies instead of a [W*cap+1] scatter pair, preserving the
    monotone-row invariant the owner-side sorted-scatter hints rely on.

Dispatch: ``auto`` (default) uses the kernels on TPU backends for tensors
of at least ``MIN_PALLAS_ELEMS`` elements and the pure-JAX operators
elsewhere; ``off`` / ``force`` override.  Off-TPU, ``force`` runs the
kernels under the Pallas interpreter — slow, but it executes the fused
dispatch call sites end to end in CPU CI (the PRNG kernels under the
TPU-semantics interpreter, ``pltpu.InterpretParams``).  The
quantizer kernels draw from the TPU hardware PRNG, a *different stream* than
``jax.random`` — same distribution, so estimators stay unbiased, but
bitwise results differ from the pure path (the dispatch seed is derived from
the caller's key, so runs remain reproducible for a fixed config).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

__all__ = [
    "set_pallas_mode",
    "pallas_mode",
    "topk_threshold",
    "topk_thresholds",
    "fused_sparsify",
    "use_fused_sparsify",
    "fused_select_pack",
    "use_select_pack",
    "pack_ternary_pallas",
    "qsgd_pack_pallas",
    "terngrad_pack",
    "terngrad_pack_prescaled",
    "qsgd_pack",
    "use_quant_pack",
    "fused_bucket_route",
    "use_bucket_route",
    "qsgd_quantize",
    "terngrad_quantize",
    "terngrad_quantize_prescaled",
    "MIN_PALLAS_ELEMS",
]

_MODE = "auto"  # auto | off | force
MIN_PALLAS_ELEMS = 1 << 16
_LANES = 128
_ROWS = 64  # rows per grid step -> 8192-element chunks, int8-tile aligned


def set_pallas_mode(mode: str) -> None:
    global _MODE
    if mode not in ("auto", "off", "force"):
        raise ValueError(f"pallas mode must be auto|off|force, got {mode!r}")
    _MODE = mode


def pallas_mode() -> str:
    return _MODE


def _dispatch_to_pallas(n: int) -> bool:
    if _MODE == "off":
        return False
    if _MODE == "force":
        return True
    return jax.default_backend() == "tpu" and n >= MIN_PALLAS_ELEMS


def _auto_interpret() -> bool:
    """``force`` off-TPU runs the kernels under the Pallas interpreter, so
    the fused dispatch *paths* (wire/sharded call sites included) execute end
    to end in CPU CI instead of dying in Mosaic lowering.  PRNG kernels run
    under the TPU-semantics interpreter (``pltpu.InterpretParams``) — the
    stock HLO interpreter's PRNG is a zero stub."""
    return _MODE == "force" and jax.default_backend() != "tpu"


def _pad_chunks(flat: Array, fill: float, rows: int = _ROWS) -> Tuple[Array, int]:
    """Pad a flat vector to whole (rows, 128) chunks, reshaped 2D.

    Fill discipline (audited): padding lanes must be invisible to every
    consumer even when the DATA is poisoned (NaN/Inf guard-vetoed steps).
    The histogram kernels use ``fill=-1.0`` — strictly below every bin edge
    (edges are ``>= lo >= 0`` and stay finite via the non-finite ``hi``
    clamp in the threshold paths) — while the pack/quantize/select kernels
    use ``fill=0`` and mask by global position (``pos < n``) instead, which
    holds for any fill.  New kernels must pick one of those two disciplines;
    a fill that merely compares below *typical* data is not enough.
    """
    n = flat.shape[0]
    chunk = rows * _LANES
    padded_n = -(-n // chunk) * chunk
    if padded_n != n:
        flat = jnp.concatenate(
            [flat, jnp.full((padded_n - n,), fill, flat.dtype)]
        )
    return flat.reshape(padded_n // _LANES, _LANES), padded_n // chunk


# ---------------------------------------------------------------------------
# Top-K threshold select
# ---------------------------------------------------------------------------


# big blocks for the streaming histogram: the per-bin compare loop keeps the
# block in vector registers (no 128-wide broadcast materialised), so the
# limits are grid-step overhead and VPU compare throughput
_HIST_ROWS = 1024

# 16 bins x 7 rounds resolves the threshold to max|g| / 16^7 — identical to
# the original 128 bins x 4 rounds (16^7 == 128^4 == 2^28, below fp32 tie
# resolution for real gradients) — but costs 7*16 = 112 compare-ops per
# element instead of 4*128 = 512 on the compute-bound counting pass (~4x
# less VPU work for ~1.75x more streamed bytes, a net ~3x at 170M elements).
_HIST_BINS = 16


def _count_ge_kernel(lo_ref, hi_ref, x_ref, counts_ref):
    """counts[b] += #{x : edge_b <= x < hi} for _HIST_BINS equispaced edges
    in [lo, hi).  Grid walks chunks of the flattened magnitudes; TPU grid
    steps run sequentially, so accumulating into the single output block is
    safe.

    The per-bin unrolled loop compares the block against each scalar edge —
    faster than a broadcast compare (which round-trips bins-times the data
    through VMEM), and the ``lo + width*b`` edge values are bit-identical to
    the thresholds the refine loop narrows to, keeping count/threshold
    consistency exact.  The output block stays one 128-lane row; lanes
    beyond _HIST_BINS are unused.
    """

    @pl.when(pl.program_id(0) == 0)
    def _():
        counts_ref[:] = jnp.zeros_like(counts_ref)

    lo = lo_ref[0, 0]
    hi = hi_ref[0, 0]
    width = (hi - lo) / _HIST_BINS
    x = x_ref[:]
    valid = x < hi
    counts = []
    for b in range(_HIST_BINS):
        edge = lo + width * b
        counts.append(
            jnp.sum(jnp.logical_and(x >= edge, valid).astype(jnp.float32)))
    # full 128-lane row write (lane-partial stores lower poorly on TPU)
    counts += [jnp.float32(0.0)] * (_LANES - _HIST_BINS)
    counts_ref[0, :] += jnp.stack(counts)


def _count_edges_kernel(edges_ref, x_ref, counts_ref):
    """CUMULATIVE counts at arbitrary ascending edges: counts[b] +=
    #{x : edges[b] <= x < edges[_HIST_BINS]} — i.e. count(>= edges[b]) since
    the top edge exceeds max(x).  The data-adapted first round of the
    sampled threshold (equispaced bins can't exploit the sample without a
    branch; quantile edges can).  17 SMEM edges = 16 bins; the selection
    compares these cumulative counts directly against keep."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        counts_ref[:] = jnp.zeros_like(counts_ref)

    x = x_ref[:]
    hi = edges_ref[0, _HIST_BINS]
    valid = x < hi
    counts = []
    for b in range(_HIST_BINS):
        counts.append(jnp.sum(
            jnp.logical_and(x >= edges_ref[0, b], valid).astype(jnp.float32)))
    counts += [jnp.float32(0.0)] * (_LANES - _HIST_BINS)
    counts_ref[0, :] += jnp.stack(counts)


def _vma(x: Array):
    """Varying-mesh-axes of ``x`` — must be propagated onto pallas_call
    out_shapes when the kernel runs on device-varying data inside shard_map."""
    return jax.typeof(x).vma


def _topk_threshold_pallas(mag: Array, keep: int, **kw) -> Array:
    return _topk_thresholds_pallas([mag], keep, **kw)[0]


def _topk_thresholds_pallas(
    mags, keep: int, *, rounds: int = 7, interpret: bool = False,
    sample_init: bool = True,
) -> Array:
    """Thresholds ``[m]`` of ``m`` magnitude vectors of one size, their
    refinement rounds in lock-step: ONE loop carries the brackets of all
    members as vectors and narrows them in one vectorised pass, and a round
    launches the count kernel once per member on the member's own buffer
    (no stacked copy).  Per member the arithmetic is the single-vector
    refinement's, scalar for scalar."""
    members = range(len(mags))
    n = mags[0].shape[0]
    vma = tuple(sorted(_vma(mags[0])))
    # clamp BEFORE the sampled-init rank arithmetic: keep > n would give
    # lo_rank > hi_rank and an IndexError at trace time in sv[rk] (the exact
    # path already clamps via keep_f; mirror it here)
    keep = min(keep, n)
    x2ds = [_pad_chunks(mag.astype(jnp.float32), fill=-1.0, rows=_HIST_ROWS)[0]
            for mag in mags]
    num_chunks = x2ds[0].shape[0] // _HIST_ROWS

    def counts_call(kernel, bracket_specs):
        return pl.pallas_call(
            kernel,
            grid=(num_chunks,),
            in_specs=bracket_specs + [
                pl.BlockSpec((_HIST_ROWS, _LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, _LANES), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((1, _LANES), jnp.float32,
                                           vma=_vma(mags[0])),
            interpret=interpret,
        )

    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    count_ge = counts_call(_count_ge_kernel, [scalar, scalar])

    keep_f = jnp.float32(min(keep, n))

    def narrow(lo, hi, above, counts):
        total_ge = above + counts  # monotone nonincreasing over bins
        b = jnp.sum((total_ge >= keep_f).astype(jnp.int32)) - 1
        b = jnp.clip(b, 0, _HIST_BINS - 1)
        width = (hi - lo) / _HIST_BINS
        new_lo = lo + width * b.astype(jnp.float32)
        new_hi = jnp.where(b == _HIST_BINS - 1, hi, lo + width * (b + 1).astype(jnp.float32))
        counts_next = jnp.concatenate([counts, jnp.zeros((1,), jnp.float32)])
        new_above = above + jnp.where(
            b == _HIST_BINS - 1, 0.0, counts_next[jnp.clip(b + 1, 0, _HIST_BINS)]
        )
        return new_lo, new_hi, new_above

    def round_body(_, carry):
        lo, hi, above = carry
        lo2, hi2 = lo.reshape(-1, 1, 1), hi.reshape(-1, 1, 1)
        counts = jnp.stack([count_ge(lo2[j], hi2[j], x2ds[j])[0][:_HIST_BINS]
                            for j in members])
        return jax.vmap(narrow)(lo, hi, above, counts)

    def varying(v):
        # carries become device-varying after a count round (counts derive
        # from the varying magnitudes) — pcast replicated values so loop /
        # cond branch types match
        return jax.lax.pcast(v, vma, to="varying") if vma and not _vma(v) else v

    # max|g| strictly below hi so the top element always lands in a bin.
    # A non-finite max (guard-vetoed NaN/Inf gradient, or fp32 overflow of
    # the eps bump) would poison every bin edge — counts degenerate and the
    # refinement collapses to t = 0, selecting *everything*.  Clamping hi to
    # FP32_MAX keeps the histogram ranking the finite magnitudes: padding
    # lanes (fill -1.0, strictly below every edge >= lo >= 0) still never
    # count, NaNs compare-false out of every bin, and +-Inf sits above every
    # edge exactly like the true max used to.
    hi_raw = jnp.stack([jnp.max(mag).astype(jnp.float32) for mag in mags]
                       ) * 1.0000002 + 1e-30
    zeros = varying(jnp.zeros((len(mags),), jnp.float32))
    hi0 = varying(jnp.where(jnp.isfinite(hi_raw), hi_raw,
                            jnp.float32(3.4028235e38)))     # max*(1+eps)
    full_init = (zeros, hi0, zeros)

    if not sample_init or keep < 1 or n < (1 << 18):
        return jax.lax.fori_loop(0, rounds, round_body, full_init)[0]

    # Sampled init, BRANCHLESS (a lax.cond fallback would run BOTH branches
    # under shard_map — the predicate is device-varying — costing more than
    # the full histogram).  Round 1 counts at data-adapted edges: quantiles
    # of a subsample around the expected k-th rank, bracketed by 0 below and
    # (global max)*(1+eps) above, so the k-th magnitude ALWAYS falls in some
    # bin — no validity branch, and when the sample is representative
    # (always, in practice) the selected bin is already ~delta ranks wide.
    # Four equispaced rounds then refine the selected bin by 16^4.
    #   * sample size targets ~1024 expected survivors so the top_k on the
    #     sample stays cheap at every keep;
    #   * the sample is the first 128 lanes of every C-element block — 512 B
    #     contiguous reads spread across the whole tensor (a fine-strided
    #     slice costs ~a full pass in gathers; slab reads are ~free);
    #   * worst case (adversarial layout hiding all mass from the sample)
    #     degrades RESOLUTION only — the count(mag >= t) >= keep guarantee
    #     is structural (narrow() keeps the k-th inside [lo, hi)), with
    #     surplus up to the selected bin's population instead of tie-level.
    m_target = int(min(max(1024 * n / keep, 1 << 16), 1 << 21))
    C = 128
    while C < (1 << 17) and n * 128 // (C * 2) >= m_target and C * 2 <= n:
        C *= 2
    nb = n // C
    m = nb * 128
    if m > n // 16:
        # mid-size tensors where the sample can't be much smaller than the
        # data: the sample top_k would rival the full histogram — use the
        # exact full-range rounds instead
        return jax.lax.fori_loop(0, rounds, round_body, full_init)[0]
    r = keep * m / n
    delta = 4.0 * float(r) ** 0.5 + 8.0
    hi_rank = int(min(m - 1, r + delta))
    lo_rank = int(max(0, r - delta))
    # 15 interior quantile edges spanning [rank r+delta, rank r-delta],
    # ascending in value (17 edges = 16 bins with the 0 and max*(1+eps)
    # brackets); duplicate edges (sample ties) just yield empty bins.
    # A NaN slab sample (guard-vetoed gradient) poisons its top_k quantiles
    # — a NaN edge survives jnp.minimum, zeroes that bin's count, and the
    # bin selection then violates the count >= keep guarantee (underfull
    # pack -> duplicate-index payload).  Clamp non-finite edges to the hi
    # bracket: an empty top bin, exactly like a duplicate edge.
    qranks = [int(round(lo_rank + (hi_rank - lo_rank) * i / 14.0))
              for i in range(15)]

    def member_edges(mag, hi0):
        sample = jax.lax.slice(
            mag[: nb * C].reshape(nb, C).astype(jnp.float32), (0, 0), (nb, 128)
        ).reshape(-1)
        sv = jax.lax.top_k(sample, hi_rank + 1)[0]
        interior = [sv[rk] for rk in reversed(qranks)]       # ascending
        return jnp.stack(
            [varying(jnp.float32(0.0))]
            + [jnp.where(jnp.isfinite(e), jnp.minimum(e, hi0), hi0)
               for e in interior] + [hi0])

    edges = jnp.stack([member_edges(mags[j], hi0[j]) for j in members])
    count_edges = counts_call(_count_edges_kernel, [pl.BlockSpec(
        (1, _HIST_BINS + 1), lambda i: (0, 0), memory_space=pltpu.SMEM)])
    counts = jnp.stack([count_edges(edges[j:j + 1], x2ds[j])[0][:_HIST_BINS]
                        for j in members])

    def first_bin(edges, counts):
        # bin selection against the edge ARRAY (narrow()'s arithmetic edges
        # don't apply to the quantile round); counts[b] already counts
        # >= edges[b] (above == 0)
        b = jnp.clip(jnp.sum((counts >= keep_f).astype(jnp.int32)) - 1,
                     0, _HIST_BINS - 1)
        counts_ext = jnp.concatenate([counts, jnp.zeros((1,), jnp.float32)])
        return edges[b], edges[b + 1], counts_ext[jnp.clip(b + 1, 0, _HIST_BINS)]

    # 4 equispaced rounds refine the selected bin by 16^4: tie-level surplus
    # for representative samples, and a few percent even when the whole
    # top-k mass hides from the sample (the degraded worst case — see
    # tests/test_kernels.py adversarial-layout case)
    return jax.lax.fori_loop(0, 4, round_body,
                             jax.vmap(first_bin)(edges, counts))[0]


_INT32_MAX = (1 << 31) - 1


def _topk_threshold_jnp(mag: Array, keep: int, rounds: int = 7) -> Array:
    """Pure-jnp histogram refinement — the Pallas kernel's algorithm without
    the kernel: 16 bins per round via one bucketize + scatter-add pass (not
    16 per-edge compare passes), 7 rounds -> threshold resolved to
    ``max|g| / 2^28``.  The fallback for sizes where ``lax.top_k`` would
    overflow its int32 indices (> 2^31 elements: the 8B entire-model
    groups), and for abstract evaluation of those configs off-TPU.

    Counts accumulate in float32, whose ulp at 2^32 is 512 — the bin
    selection therefore targets ``keep + margin`` with ``margin`` a few
    float32 ulps of n, so cumulative-count rounding can only ADD surplus
    (threshold a hair low), never break ``count(mag >= t) >= keep``.
    """
    n = mag.shape[0]
    mag = mag.astype(jnp.float32)
    # conservative target: fp32 summation error is bounded by a few ulps of
    # the running total; 8 ulps of n keeps the guarantee one-sided
    margin = 8.0 * n / float(1 << 23) if n > (1 << 23) else 0.0
    keep_f = jnp.float32(min(keep + margin, n))
    lo = jnp.float32(0.0)
    # same non-finite clamp as the kernel path: a NaN/Inf max must not
    # poison the bin edges (see _topk_threshold_pallas)
    hi_raw = (jnp.max(mag) * 1.0000002 + 1e-30).astype(jnp.float32)
    hi = jnp.where(jnp.isfinite(hi_raw), hi_raw, jnp.float32(3.4028235e38))
    above = jnp.float32(0.0)
    for _ in range(rounds):
        width = (hi - lo) / _HIST_BINS
        idx = jnp.clip(((mag - lo) / width).astype(jnp.int32),
                       0, _HIST_BINS - 1)
        valid = (mag >= lo) & (mag < hi)
        hist = jnp.zeros((_HIST_BINS,), jnp.float32).at[
            jnp.where(valid, idx, 0)].add(valid.astype(jnp.float32))
        # counts[b] = #{x : x >= edge_b, x < hi} = suffix sum of the hist
        counts = jnp.cumsum(hist[::-1])[::-1]
        total_ge = above + counts
        b = jnp.clip(jnp.sum((total_ge >= keep_f).astype(jnp.int32)) - 1,
                     0, _HIST_BINS - 1)
        new_lo = lo + width * b.astype(jnp.float32)
        new_hi = jnp.where(b == _HIST_BINS - 1, hi,
                           lo + width * (b + 1).astype(jnp.float32))
        counts_next = jnp.concatenate([counts, jnp.zeros((1,), jnp.float32)])
        above = above + jnp.where(
            b == _HIST_BINS - 1, 0.0,
            counts_next[jnp.clip(b + 1, 0, _HIST_BINS)])
        lo, hi = new_lo, new_hi
    return lo


def topk_thresholds(mags, keep: int):
    """:func:`topk_threshold` of several vectors of one size: where the
    histogram kernel serves them, their refinement rounds share one loop."""
    n = mags[0].shape[0]
    if keep < n and _dispatch_to_pallas(n):
        ts = _topk_thresholds_pallas(mags, keep, interpret=_auto_interpret())
        return [ts[j] for j in range(len(mags))]
    return [topk_threshold(mag, keep) for mag in mags]


def topk_threshold(mag: Array, keep: int) -> Array:
    """Magnitude threshold keeping ``>= keep`` elements (ties included).

    Exact (``lax.top_k``) below the dispatch cutoff or off-TPU; histogram
    kernel above it; pure-jnp histogram beyond int32 sizes.  Either way
    ``count(mag >= t) >= keep`` with surplus only from ties at the returned
    threshold's resolution.
    """
    n = mag.shape[0]
    if keep >= n:
        return jnp.zeros((), jnp.float32)
    if _dispatch_to_pallas(n):
        # fp32 always: downcasting the bin edge to a lower-precision input
        # dtype could round UP past the true k-th magnitude and break the
        # count(mag >= t) >= keep guarantee
        return _topk_threshold_pallas(mag, keep, interpret=_auto_interpret())
    if n > _INT32_MAX:
        return _topk_threshold_jnp(mag, keep)
    # NaN sorts as LARGEST under lax.top_k: each guard-vetoed NaN would
    # steal a top-k slot, land the threshold one rank too high, and
    # underfill the pack (duplicate-index payload, voided scatter hints —
    # the poisoned-tail leak).  Demote NaN below every magnitude so the
    # threshold ranks the finite values; NaN still never travels (it
    # compares false against any threshold).
    m32 = mag.astype(jnp.float32)
    m32 = jnp.where(jnp.isnan(m32), -1.0, m32)
    return jax.lax.top_k(m32, keep)[0][-1]


# ---------------------------------------------------------------------------
# Fused sparsify (simulate-mode Top-K / threshold epilogue)
# ---------------------------------------------------------------------------


def _fused_sparsify_kernel(want_ef: bool, n: int, t_ref, x_ref, *refs):
    """One streaming pass over the accumulated gradient: apply the magnitude
    threshold and emit the compressed tensor, (optionally) the new EF
    residual, and the nonzero-survivor count — replacing the where/subtract/
    count_nonzero pass chain XLA would otherwise run as separate kernels
    around the pallas threshold call (pallas_call boundaries block fusion).
    Padding beyond ``n`` is excluded from the count via a global-position
    mask, and exact zeros never count as sent (matching ``count_nonzero`` on
    the unfused path even at threshold 0)."""
    if want_ef:
        comp_ref, ef_ref, count_ref = refs
    else:
        comp_ref, count_ref = refs
        ef_ref = None

    @pl.when(pl.program_id(0) == 0)
    def _():
        count_ref[:] = jnp.zeros_like(count_ref)

    rows, lanes = comp_ref.shape
    acc = x_ref[:]
    base = pl.program_id(0) * rows * lanes
    pos = (base
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    keep = jnp.logical_and(jnp.abs(acc) >= t_ref[0, 0], pos < n)
    comp = jnp.where(keep, acc, 0.0)
    comp_ref[:] = comp
    if ef_ref is not None:
        ef_ref[:] = acc - comp
    sent = jnp.logical_and(keep, acc != 0.0)
    # int32 accumulation: fp32 partial sums round past 2^24 sent elements,
    # drifting from the unfused path's integer-exact count_nonzero
    row = [jnp.sum(sent.astype(jnp.int32))]
    row += [jnp.int32(0)] * (_LANES - 1)
    count_ref[0, :] += jnp.stack(row)


# fat blocks: <=3 streams x 512 rows x 128 lanes x 4 B = <=0.8 MB live VMEM
# per grid step; fewer grid steps matter — 64-row blocks measured ~8 ms
# SLOWER at a 100M-element leaf from per-step overhead alone
_SPARSIFY_ROWS = 512


def fused_sparsify(acc: Array, t: Array, *, want_ef: bool = True,
                   interpret: bool | None = None):
    """``(comp, new_ef | None, count)`` keeping coordinates ``|acc| >= t`` —
    the simulate-mode epilogue fused into one pass over the (already
    EF-accumulated) gradient.  fp32 in/out: the caller gates dispatch on
    fp32 inputs so the psum payload dtype matches the unfused path."""
    if interpret is None:
        interpret = _auto_interpret()
    n = acc.shape[0]
    rows = _SPARSIFY_ROWS
    x2d, num_chunks = _pad_chunks(acc.astype(jnp.float32), fill=0.0, rows=rows)
    vma = _vma(acc)
    big = pl.BlockSpec((rows, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_specs = [big] + ([big] if want_ef else []) + [
        pl.BlockSpec((1, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct(x2d.shape, jnp.float32, vma=vma)]
    if want_ef:
        out_shape.append(jax.ShapeDtypeStruct(x2d.shape, jnp.float32, vma=vma))
    out_shape.append(jax.ShapeDtypeStruct((1, _LANES), jnp.int32, vma=vma))
    outs = pl.pallas_call(
        functools.partial(_fused_sparsify_kernel, want_ef, n),
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            big,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(t.reshape(1, 1).astype(jnp.float32), x2d)
    comp = outs[0].reshape(-1)[:n]
    new_ef = outs[1].reshape(-1)[:n] if want_ef else None
    return comp, new_ef, outs[-1][0, 0].astype(jnp.float32)


def use_fused_sparsify(n: int) -> bool:
    """Whether the fused simulate-mode epilogue should serve this tensor.

    Above int32 sizes the kernel's global-position iota would wrap — the
    unfused path (threshold + where) handles those (XLA indexes with s64
    where needed)."""
    return _dispatch_to_pallas(n) and n <= _INT32_MAX


# ---------------------------------------------------------------------------
# Fused select+pack (wire-mode Top-K: one-pass threshold select -> payload)
# ---------------------------------------------------------------------------
#
# Segment = _SEG_ROWS x 128 elements compacted independently; _SEG_PER_BLOCK
# segments per grid step amortise grid overhead.  Per segment the kernel
# computes in-segment survivor ranks (one tri-matmul in-row prefix + a
# Hillis-Steele row scan), then routes each survivor LEFT by its compaction
# distance d = pos - (rank-1) in log2(SEG) rounds of STATIC flattened rolls
# (round b moves every element whose remaining distance has bit b set by
# 2^b).  Distances are monotone non-decreasing in position, which makes the
# LSB->MSB schedule collision-free: an arrival can only land on a dead slot
# or a slot simultaneously vacated (fuzz-verified; tests).  No per-element
# dynamic stores, no one-hot materialisation — the two walls a one-hot
# placement kernel measured (benchmarks/pack_kernel_r3.txt).
#
# Vector work bounds the kernel, not bytes, and the cross-lane moves lead it,
# so the network routes as little as it can, on the native rotates
# (`pltpu.roll`; `jnp.roll` is a slice pair and a concatenate in Mosaic): TWO
# 32-bit words a round, the value and `w = (spos << 12) | d`, the element's
# in-segment source position above its remaining distance (both under
# _SEG = 2^12).  The global index is put back after the last round from the
# slot's own segment base; 16-bit values ride as fp32 (exact both ways).
#
# Each segment is FULLY left-compacted (capacity = segment size, so no
# survivor is ever clipped: a 128-slot cap per segment dropped sent mass on
# concentrated LM gradients, benchmarks/pack_kernel_r4.txt), staging
# compacted (value, global-index) pairs plus a per-segment survivor count in
# ONE pass over the gradient.  A small XLA epilogue (cumsum over nseg counts
# + one rank-bucketed gather of exactly `keep` slots) then assembles the wire
# payload — the `packed_indices_from_mask` trick at segment granularity, ~32x
# fewer buckets than the per-128-lane-row XLA chain, and without the chain's
# full-width mask materialisation, row-count pass, and element gather over n.
# Within-segment compaction preserves ascending order and segments are
# ascending, so the payload is bitwise identical to the unfused
# mask -> packed_indices_from_mask -> _sorted_gather pipeline (parity-gated
# in tier-1 under the interpreter).
_SEG_ROWS = 32                    # 4096 elements per segment
_SEG = _SEG_ROWS * _LANES
_SEG_BITS = _SEG.bit_length() - 1
_SEG_PER_BLOCK = 16               # 512 rows / grid step


def _roll_flat(a: Array, s: int):
    """Flattened-order left roll by static ``s`` on a [R, 128] block of
    32-bit words, with the wrap INSIDE the block (what crosses a segment's
    end is the caller's to rule out):
    ``out[r, l] = a.reshape(-1)[(r * 128 + l + s) % a.size]``.

    One lane rotate, then the two sublane rotates of THAT result a slot can
    read from (the row ``s // 128`` down, and the one after it for the lanes
    that ran off the row's end); `pltpu.roll` rotates towards higher indices,
    so a left roll by k of an axis of size m is a roll by ``m - k``.  A roll
    by 0 is no operation at all."""
    rows = a.shape[0]
    row_part, lane_part = divmod(s, _LANES)

    def up(x, r):
        return x if r == 0 else pltpu.roll(x, rows - r, 0)

    if lane_part == 0:
        return up(a, row_part)
    here = pltpu.roll(a, _LANES - lane_part, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.where(lane < _LANES - lane_part,
                     up(here, row_part), up(here, row_part + 1))


def _select_pack_kernel(n: int, t_ref, x_ref, vals_ref, idx_ref, cnt_ref):
    rows = x_ref.shape[0]                        # _SEG_PER_BLOCK * _SEG_ROWS
    # the rotates are 32-bit: bf16 rides as fp32, exact there and back, and
    # the fp32 magnitude compare below is the wire path's
    # `jnp.abs(flat).astype(f32) >= t` bit for bit (abs is exact)
    x = x_ref[:].astype(jnp.float32)
    base = pl.program_id(0) * rows * _LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    gpos = base + row * _LANES + lane
    seg_row = row % _SEG_ROWS
    spos = seg_row * _LANES + lane
    m = jnp.logical_and(jnp.abs(x) >= t_ref[0, 0], gpos < n)

    # in-segment 1-based survivor rank: in-row inclusive prefix (tri matmul,
    # rows are segment-local by construction) + exclusive row prefix within
    # the segment (Hillis-Steele over sublanes, masked at segment boundaries)
    mf = m.astype(jnp.float32)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
           ).astype(jnp.float32)
    inrow = jax.lax.dot_general(mf, tri, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    rowcnt = jnp.broadcast_to(inrow[:, _LANES - 1:], (rows, _LANES))
    rowpfx = rowcnt
    s = 1
    while s < _SEG_ROWS:
        shifted = pltpu.roll(rowpfx, s, 0)
        rowpfx = jnp.where(seg_row >= s, rowpfx + shifted, rowpfx)
        s *= 2
    rank = (rowpfx - rowcnt + inrow).astype(jnp.int32)   # 1-based, survivors

    # route EVERY survivor left by d = spos - (rank-1); d == 0 is dead.  No
    # eligibility cap, so distances stay monotone non-decreasing in position
    # and the LSB->MSB schedule stays collision-free for the full log2(_SEG)
    # rounds.  A slot nothing arrives at keeps its own spos above a zero d.
    w = (spos << _SEG_BITS) | jnp.where(m, spos - (rank - 1), 0)
    vals = x
    for b in range(_SEG_BITS):
        sft = 1 << b
        rw = _roll_flat(w, sft)
        rv = _roll_flat(vals, sft)
        # arrivals: the element sft slots to the right, if it moves now.  It
        # is of this segment, with no mask for the rolls' wrap: an element's
        # remaining distance never exceeds its in-segment position (it ends
        # at rank-1 >= 0), so one within sft of its segment's start, which is
        # all a wrap can bring, has no bit b left to move by
        move_in = (rw & sft) != 0
        my_move = (w & sft) != 0
        vals = jnp.where(move_in, rv, vals)
        # bit b of an arriving d is set: taking 2^b off borrows nothing from
        # the spos above it
        w = jnp.where(move_in, rw - sft, jnp.where(my_move, w & ~(_SEG - 1), w))

    vals_ref[:] = vals.astype(vals_ref.dtype)
    idx_ref[:] = gpos - spos + (w >> _SEG_BITS)
    # per-segment survivor totals: rowpfx at each segment's last row is the
    # inclusive count (identical across lanes) — full 128-lane row writes,
    # the reader takes lane 0
    r3 = rowpfx.reshape(rows // _SEG_ROWS, _SEG_ROWS, _LANES)
    cnt_ref[:] = r3[:, _SEG_ROWS - 1, :].astype(jnp.int32)


def run_starts(bucket_of: Array) -> Array:
    """``starts[r]``: the position at which the run of equal values holding
    position ``r`` of the never-decreasing ``bucket_of`` began.  With
    ``bucket_of`` the bucket (segment, mask row) of each payload rank, a
    rank's bucket changes exactly at the first rank of every bucket that
    holds survivors, so this is the bucket's exclusive start
    ``(ends - counts)[bucket_of]`` — empty buckets, an empty first bucket
    and the clamped ranks beyond the survivor count included — from one
    elementwise pass and one scan over the ranks: no ``keep``-sized gather,
    and no scatter of bucket starts, whose indices repeat wherever a bucket
    is empty."""
    pos = jnp.arange(bucket_of.shape[0], dtype=jnp.int32)
    # position 0 may read as opening a run: its value is 0 either way
    opens = bucket_of != jnp.pad(bucket_of[:-1], (1, 0))
    return jax.lax.cummax(jnp.where(opens, pos, 0))


def _select_pack_payload(vals_st: Array, idx_st: Array, counts: Array,
                         keep: int):
    """Rank-bucket the per-segment compacted prefixes into the exact
    ``keep``-slot payload (ascending global index).  Segment-granular
    `packed_indices_from_mask`: find each payload rank's segment via a
    histogram over segment end-counts and the segment's first rank via
    `run_starts`, then one sorted gather from each staging buffer.
    Underfull masks (count < keep — only reachable on poisoned gradients;
    `topk_threshold` guarantees count >= keep otherwise) pad with value 0 /
    index 0, scatter-add identities."""
    nseg = counts.shape[0]
    v = vals_st.reshape(nseg, _SEG)
    ix = idx_st.reshape(nseg, _SEG)
    ends = jnp.cumsum(counts)                              # inclusive
    total = ends[nseg - 1]
    ranks = jnp.arange(1, keep + 1, dtype=jnp.int32)
    hist = jnp.zeros((keep + 1,), jnp.int32).at[
        jnp.minimum(ends, keep)].add(1, indices_are_sorted=True)
    seg_of = jnp.cumsum(hist)[:keep]
    valid = seg_of < nseg
    # clamp to the last segment (not 0) so flat_pos stays monotone and the
    # gather can keep its sorted hint
    seg_of = jnp.where(valid, seg_of, nseg - 1)
    within = jnp.clip(ranks - run_starts(seg_of) - 1, 0, _SEG - 1)
    flat_pos = seg_of * _SEG + within
    gv = v.reshape(-1).at[flat_pos].get(indices_are_sorted=True,
                                        mode="promise_in_bounds")
    gi = ix.reshape(-1).at[flat_pos].get(indices_are_sorted=True,
                                         mode="promise_in_bounds")
    pvals = jnp.where(valid, gv, jnp.zeros((), vals_st.dtype))
    pidx = jnp.where(valid, gi, 0)
    return pvals, pidx, total


def fused_select_pack(flat: Array, t: Array, keep: int, *,
                      interpret: bool | None = None):
    """``(vals [keep], idx [keep], count)``: the wire-mode Top-K payload —
    coordinates with ``|flat| >= t`` by ascending index, their values in
    ``flat.dtype`` — in one Pallas pass plus an nseg-sized epilogue.

    Bitwise-identical to ``mask -> packed_indices_from_mask -> _sorted_gather``
    whenever the `topk_threshold` contract ``count(|flat| >= t) >= keep``
    holds (the one difference is deliberate: an underfull mask pads value 0 /
    index 0 instead of replicating ``flat[0]``).  ``count`` is the total
    survivor count (int32) for surplus accounting.
    """
    n = flat.shape[0]
    if n > _INT32_MAX:
        raise ValueError(f"fused_select_pack indexes int32; got n={n}")
    if interpret is None:
        interpret = _auto_interpret()
    rows_blk = _SEG_PER_BLOCK * _SEG_ROWS
    x2d, num_blocks = _pad_chunks(flat, fill=0.0, rows=rows_blk)
    nseg = x2d.shape[0] // _SEG_ROWS
    vma = _vma(flat)
    blk = pl.BlockSpec((rows_blk, _LANES), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    seg_out = pl.BlockSpec((_SEG_PER_BLOCK, _LANES), lambda i: (i, 0),
                           memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        functools.partial(_select_pack_kernel, n),
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            blk,
        ],
        out_specs=[blk, blk, seg_out],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, flat.dtype, vma=vma),
            jax.ShapeDtypeStruct(x2d.shape, jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((nseg, _LANES), jnp.int32, vma=vma),
        ],
        interpret=interpret,
    )(jnp.asarray(t).reshape(1, 1).astype(jnp.float32), x2d)
    return _select_pack_payload(outs[0], outs[1], outs[2][:, 0], int(keep))


def use_select_pack(n: int, keep: int) -> bool:
    """Whether the wire Top-K select+pack should take the fused kernel.
    Full per-segment compaction has no overflow pathology, so it dispatches
    on the standard gates; the epilogue gather is O(keep)."""
    return _dispatch_to_pallas(n) and n <= _INT32_MAX and keep >= 1


# ---------------------------------------------------------------------------
# Fused stochastic quantisation
# ---------------------------------------------------------------------------


def _uniform_from_bits(shape) -> Array:
    # random bits come back as signed i32 on TPU — bitcast before shifting so
    # the shift is logical, then use the top 24 bits -> exact fp32 in [0, 1)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    # (Mosaic has no u32->f32 cast; the 24-bit value is sign-safe as i32.)
    top24 = pltpu.bitcast(bits >> 8, jnp.int32)
    return top24.astype(jnp.float32) * (1.0 / (1 << 24))


def _sign(x: Array) -> Array:
    # jnp.sign's Mosaic lowering emits an unsupported `pvary` when traced
    # under shard_map's varying-axes tracking; select-based sign lowers clean
    return jnp.where(x > 0, 1.0, 0.0) - jnp.where(x < 0, 1.0, 0.0)


def _qsgd_kernel(qstates: int, seed_ref, inv_norm_ref, x_ref, out_ref):
    pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
    x = x_ref[:]
    u = _uniform_from_bits(x.shape)
    levels = jnp.floor(jnp.abs(x) * inv_norm_ref[0, 0] * qstates + u)
    out_ref[:] = (_sign(x) * levels).astype(jnp.int16)


def _terngrad_kernel(seed_ref, inv_max_ref, x_ref, out_ref):
    pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
    x = x_ref[:]
    u = _uniform_from_bits(x.shape)
    keep = (u < jnp.abs(x) * inv_max_ref[0, 0]).astype(jnp.float32)
    out_ref[:] = (_sign(x) * keep).astype(jnp.int8)


def _run_quant(kernel, out_dtype, flat: Array, inv_scale: Array, seed: Array,
               interpret: bool) -> Array:
    n = flat.shape[0]
    x2d, num_chunks = _pad_chunks(flat.astype(jnp.float32), fill=0.0)
    out = pl.pallas_call(
        kernel,
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, out_dtype, vma=_vma(flat)),
        # TPU-semantics interpreter: the stock HLO interpreter has no
        # prng_seed/prng_random_bits (NB: its PRNG is a zero stub — dither
        # u == 0 under interpretation; see tests/test_kernels.py)
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        seed.reshape(1, 1).astype(jnp.int32),
        inv_scale.reshape(1, 1).astype(jnp.float32),
        x2d,
    )
    return out.reshape(-1)[:n]


def _seed_from_key(key: Array) -> Array:
    return jax.random.bits(key, dtype=jnp.uint32).astype(jnp.int32)


def qsgd_quantize(flat: Array, key: Array, *, qstates: int = 255,
                  interpret: bool | None = None) -> Tuple[Array, Array]:
    """Fused QSGD levels: ``(int16 levels in [-s, s], fp32 scale)``.

    Same estimator as :func:`compressors.qsgd_levels` (`core.py:207-213`),
    dither drawn from the TPU hardware PRNG seeded off ``key``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    norm = jnp.linalg.norm(flat.astype(jnp.float32))
    inv = jnp.where(norm > 0, 1.0 / jnp.where(norm > 0, norm, 1.0), 0.0)
    levels = _run_quant(
        functools.partial(_qsgd_kernel, qstates), jnp.int16,
        flat, inv, _seed_from_key(key), interpret,
    )
    scale = jnp.where(norm > 0, norm, 0.0) / qstates
    return levels, scale


def terngrad_quantize(flat: Array, key: Array, *,
                      interpret: bool | None = None) -> Tuple[Array, Array]:
    """Fused TernGrad levels: ``(int8 levels in {-1,0,1}, fp32 scale)``
    (`core.py:200-206`), dither from the TPU hardware PRNG."""
    if interpret is None:
        interpret = _auto_interpret()
    gmax = jnp.max(jnp.abs(flat.astype(jnp.float32)))
    inv = jnp.where(gmax > 0, 1.0 / jnp.where(gmax > 0, gmax, 1.0), 0.0)
    levels = _run_quant(
        _terngrad_kernel, jnp.int8, flat, inv, _seed_from_key(key), interpret,
    )
    return levels, gmax


def terngrad_quantize_prescaled(scaled: Array, key: Array, *,
                                interpret: bool | None = None) -> Array:
    """TernGrad levels for an already chunk-normalised input (``|x| <= 1``,
    unit scale) — the chunked-scale path's quantisation pass."""
    if interpret is None:
        interpret = _auto_interpret()
    return _run_quant(
        _terngrad_kernel, jnp.int8, scaled,
        jnp.asarray(1.0, jnp.float32), _seed_from_key(key), interpret,
    )


def use_quant_kernels(n: int) -> bool:
    """Whether the fused quantizer kernels should serve this tensor."""
    return _dispatch_to_pallas(n)


# ---------------------------------------------------------------------------
# Fused quantize+pack (TernGrad 2-bit / QSGD mag + sign-bitmap wire bytes)
# ---------------------------------------------------------------------------
#
# The quantizer kernels above emit integer LEVELS; XLA then runs
# `wire.pack_ternary` / `wire.pack_bits` as separate shift/sum passes over
# the levels before anything hits the wire.  These kernels emit the wire
# BYTES directly.  Bit-packing on the VPU has no sub-word shuffles, so
# packing is two matmuls (`_bytepack`): the first weighs each lane by its
# bit position and sums every ``g`` consecutive lanes into one byte, the
# second stacks ``g`` consecutive rows' bytes side by side into one
# 128-lane byte row.  Operands are small exact integers (codes <= 2,
# weights <= 128, bytes <= 255 < 2^8), so even the MXU's bf16 default
# precision is exact, like the 0/1 count matmuls in the pack kernels.  Byte
# order matches the XLA packers bitwise: byte j of the flat output packs
# elements g*j .. g*j+g-1 little-endian.

# 256-row element blocks: ternary bytes come out [64, 128] and sign-bitmap
# bytes [32, 128] — both at or above the uint8 (32, 128) min tile
_QPACK_ROWS = 256


def _bytepack(v: Array, g: int) -> Array:
    """[R, 128] f32 small-int codes -> [R // g, 128] f32 bytes packing ``g``
    consecutive lanes per byte, little-endian; flat order == wire order.

    Row ``r`` yields ``128 / g`` bytes, and ``g`` consecutive rows fill one
    output row.  Every array keeps the full 128-lane minor dimension
    (Mosaic has no ``[R, 128/g] -> [R/g, 128]`` shape cast): the packing
    matrix writes the row's bytes into all ``g`` lane blocks at once, a
    mask keeps lane block ``r % g``, and a 0/1 grouping matmul sums each
    ``g`` rows, whose kept blocks are disjoint."""
    rows = v.shape[0]
    cols = _LANES // g                  # bytes per element row
    lg, lc = g.bit_length() - 1, cols.bit_length() - 1    # both powers of two
    src = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    weight = jnp.left_shift(1, (src & (g - 1)) * (8 // g))
    pm = jnp.where((src >> lg) == (dst & (cols - 1)), weight, 0
                   ).astype(jnp.float32)
    y = jax.lax.dot(v, pm, preferred_element_type=jnp.float32)     # [R, 128]
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    z = jnp.where((lane >> lc) == (r & (g - 1)), y, 0.0)
    out_r = jax.lax.broadcasted_iota(jnp.int32, (rows // g, rows), 0)
    in_r = jax.lax.broadcasted_iota(jnp.int32, (rows // g, rows), 1)
    group = ((in_r >> lg) == out_r).astype(jnp.float32)
    return jax.lax.dot(group, z, preferred_element_type=jnp.float32)


def _pack2b_kernel(levels_ref, out_ref):
    c = levels_ref[:].astype(jnp.float32) + 1.0            # {0,1,2}
    out_ref[:] = _bytepack(c, 4).astype(jnp.int32).astype(jnp.uint8)


def _qsgd_pack_levels_kernel(levels_ref, mag_ref, sign_ref):
    lv = levels_ref[:].astype(jnp.int32)
    mag_ref[:] = jnp.abs(lv).astype(jnp.uint8)
    neg = (lv < 0).astype(jnp.float32)
    sign_ref[:] = _bytepack(neg, 8).astype(jnp.int32).astype(jnp.uint8)


def _pack_bytes_call(kernel, levels: Array, out_divs, out_dtypes,
                     interpret: bool):
    """Shared pallas_call plumbing for the byte packers: grid over
    _QPACK_ROWS-row level chunks, one output per (rows-divisor, dtype)."""
    n = levels.shape[0]
    x2d, num_chunks = _pad_chunks(levels, fill=0, rows=_QPACK_ROWS)
    vma = _vma(levels)
    outs = pl.pallas_call(
        kernel,
        grid=(num_chunks,),
        in_specs=[pl.BlockSpec((_QPACK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((_QPACK_ROWS // d, _LANES), lambda i, d=d: (i, 0),
                                memory_space=pltpu.VMEM) for d in out_divs],
        out_shape=[jax.ShapeDtypeStruct((x2d.shape[0] // d, _LANES), dt,
                                             vma=vma)
                   for d, dt in zip(out_divs, out_dtypes)],
        interpret=interpret,
    )(x2d)
    return outs


def pack_ternary_pallas(levels: Array, *, interpret: bool | None = None) -> Array:
    """``uint8[ceil(n/4)]`` — bitwise-identical to :func:`wire.pack_ternary`
    (the XLA packer zero-pads levels to a multiple of 4; chunk padding here
    is level 0 -> code 1, the same byte content)."""
    n = levels.shape[0]
    if interpret is None:
        interpret = _auto_interpret()
    (out,) = _pack_bytes_call(_pack2b_kernel, levels.astype(jnp.int8),
                              (4,), (jnp.uint8,), interpret)
    return out.reshape(-1)[: -(-n // 4)]


def qsgd_pack_pallas(levels: Array, *, interpret: bool | None = None):
    """``(uint8 mags [n], uint8 signs [ceil(n/8)])`` — bitwise-identical to
    :func:`wire.qsgd_wire_pack` for ``qstates <= 255`` given the same int16
    levels."""
    n = levels.shape[0]
    if interpret is None:
        interpret = _auto_interpret()
    mags, signs = _pack_bytes_call(
        _qsgd_pack_levels_kernel, levels.astype(jnp.int16),
        (1, 8), (jnp.uint8, jnp.uint8), interpret)
    return mags.reshape(-1)[:n], signs.reshape(-1)[: -(-n // 8)]


def _terngrad_pack_kernel(seed_ref, inv_max_ref, x_ref, out_ref):
    pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
    x = x_ref[:]
    u = _uniform_from_bits(x.shape)
    keep = (u < jnp.abs(x) * inv_max_ref[0, 0]).astype(jnp.float32)
    codes = _sign(x) * keep + 1.0                          # {0,1,2}
    out_ref[:] = _bytepack(codes, 4).astype(jnp.int32).astype(jnp.uint8)


def _qsgd_pack_kernel(qstates: int, seed_ref, inv_norm_ref, x_ref,
                      mag_ref, sign_ref):
    pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
    x = x_ref[:]
    u = _uniform_from_bits(x.shape)
    lv = jnp.floor(jnp.abs(x) * inv_norm_ref[0, 0] * qstates + u)
    mag_ref[:] = lv.astype(jnp.int32).astype(jnp.uint8)
    # sign bit set iff the signed level is negative: x < 0 AND lv > 0
    # (jnp.sign(x) * 0 == +-0, never < 0 — matches qsgd_wire_pack)
    neg = jnp.logical_and(x < 0, lv > 0).astype(jnp.float32)
    sign_ref[:] = _bytepack(neg, 8).astype(jnp.int32).astype(jnp.uint8)


def _run_quant_pack(kernel, flat: Array, inv_scale: Array, seed: Array,
                    out_divs, interpret: bool):
    n = flat.shape[0]
    x2d, num_chunks = _pad_chunks(flat.astype(jnp.float32), fill=0.0,
                                  rows=_QPACK_ROWS)
    vma = _vma(flat)
    outs = pl.pallas_call(
        kernel,
        grid=(num_chunks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((_QPACK_ROWS, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[pl.BlockSpec((_QPACK_ROWS // d, _LANES), lambda i, d=d: (i, 0),
                                memory_space=pltpu.VMEM) for d in out_divs],
        out_shape=[jax.ShapeDtypeStruct((x2d.shape[0] // d, _LANES),
                                             jnp.uint8, vma=vma)
                   for d in out_divs],
        # hardware PRNG — TPU-semantics interpreter required off-TPU
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        seed.reshape(1, 1).astype(jnp.int32),
        inv_scale.reshape(1, 1).astype(jnp.float32),
        x2d,
    )
    return outs


def terngrad_pack(flat: Array, key: Array, *,
                  interpret: bool | None = None) -> Tuple[Array, Array]:
    """Fused TernGrad quantize+pack: ``(uint8 wire bytes [ceil(n/4)], fp32
    scale)`` — draw, dither, and 2-bit-pack in ONE pass instead of the
    levels pass + XLA `pack_ternary` pass.  Same hardware-PRNG stream caveat
    as :func:`terngrad_quantize` (unbiased, not bitwise with `jax.random`);
    chunk padding packs as code 1 exactly like the XLA packer's zero-pad."""
    if interpret is None:
        interpret = _auto_interpret()
    gmax = jnp.max(jnp.abs(flat.astype(jnp.float32)))
    inv = jnp.where(gmax > 0, 1.0 / jnp.where(gmax > 0, gmax, 1.0), 0.0)
    (packed,) = _run_quant_pack(
        _terngrad_pack_kernel, flat, inv, _seed_from_key(key), (4,), interpret)
    n = flat.shape[0]
    return packed.reshape(-1)[: -(-n // 4)], gmax


def terngrad_pack_prescaled(scaled: Array, key: Array, *,
                            interpret: bool | None = None) -> Array:
    """Quantize+pack for an already chunk-normalised input (``|x| <= 1``) —
    the chunked-scale TernGrad path's fused second pass."""
    if interpret is None:
        interpret = _auto_interpret()
    (packed,) = _run_quant_pack(
        _terngrad_pack_kernel, scaled, jnp.asarray(1.0, jnp.float32),
        _seed_from_key(key), (4,), interpret)
    n = scaled.shape[0]
    return packed.reshape(-1)[: -(-n // 4)]


def qsgd_pack(flat: Array, key: Array, *, qstates: int = 255,
              interpret: bool | None = None):
    """Fused QSGD quantize+pack for the uint8 wire layout (``qstates <=
    255``): ``(uint8 mags [n], uint8 sign bitmap [ceil(n/8)], fp32 scale)``
    in one pass — replacing levels + `qsgd_wire_pack`'s abs/compare/pack_bits
    chain.  Hardware-PRNG stream caveat as :func:`qsgd_quantize`."""
    if not 0 < qstates <= 255:
        raise ValueError(f"qsgd_pack packs uint8 magnitudes; qstates={qstates}")
    if interpret is None:
        interpret = _auto_interpret()
    norm = jnp.linalg.norm(flat.astype(jnp.float32))
    inv = jnp.where(norm > 0, 1.0 / jnp.where(norm > 0, norm, 1.0), 0.0)
    mags, signs = _run_quant_pack(
        functools.partial(_qsgd_pack_kernel, qstates), flat, inv,
        _seed_from_key(key), (1, 8), interpret)
    n = flat.shape[0]
    scale = jnp.where(norm > 0, norm, 0.0) / qstates
    return mags.reshape(-1)[:n], signs.reshape(-1)[: -(-n // 8)], scale


def use_quant_pack(n: int) -> bool:
    """Whether the fused quantize+pack kernels should serve this tensor."""
    return _dispatch_to_pallas(n)


# ---------------------------------------------------------------------------
# Hardware-PRNG uniforms
# ---------------------------------------------------------------------------


def _uniform_kernel(seed_ref, out_ref):
    pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
    out_ref[:] = _uniform_from_bits(out_ref.shape)


# PRNG seeding has per-grid-step cost — use fat blocks (512 KB) so the fill
# is bandwidth-bound, not step-bound
_UNIFORM_ROWS = 1024


def _uniform_pallas(seed: Array, n: int, interpret: bool = False) -> Array:
    chunk = _UNIFORM_ROWS * _LANES
    padded_n = -(-n // chunk) * chunk
    out = pl.pallas_call(
        _uniform_kernel,
        grid=(padded_n // chunk,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((_UNIFORM_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((padded_n // _LANES, _LANES), jnp.float32,
                                       vma=_vma(seed)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed.reshape(1, 1).astype(jnp.int32))
    return out.reshape(-1)[:n]


def uniform(key: Array, n: int) -> Array:
    """Uniform [0, 1) draws; hardware PRNG on TPU at scale (threefry is
    ~10x slower there for multi-million element draws), ``jax.random``
    elsewhere.  Deterministic in ``key`` on both paths — a replicated key
    yields identical draws on every worker (the shared-seed contract
    Random-K masks rely on) — but the two paths draw different streams."""
    if _dispatch_to_pallas(n):
        return _uniform_pallas(_seed_from_key(key), n)
    return jax.random.uniform(key, (n,))


# ---------------------------------------------------------------------------
# Fused bucket route (sharded transport per-destination bucket build)
# ---------------------------------------------------------------------------
#
# The sharded transport's route phase turns the ascending (value, index)
# payload into per-destination fixed-capacity buckets.  The XLA build is a
# pair of [W*cap+1]-slot scatters (value add + index set with a dump slot).
# Because the indices are ascending, each destination's accepted elements
# are a CONTIGUOUS window [starts[w], starts[w] + min(count, cap)) of the
# payload — so the scatter is really W windowed copies.  The kernel grids
# over destinations, DMAs each window from HBM, masks the tail, and writes
# full bucket rows: zero value / `shard_n` guard index on empty slots,
# identical bit-for-bit to the scatter build, and rows stay monotone (window
# order = payload order), preserving the owner-side sorted-scatter hints.
#
# Mosaic addresses HBM at row granularity, and a window starts at any
# element: the payload is viewed as [rows, 128], the DMA fetches the rows
# that cover the window, and the window's offset inside its first row is
# taken out in VMEM by one dynamic lane rotation plus a one-row shift.

# per-destination window bound: value + index windows of cap_p elements
# must sit in VMEM alongside the output block
_ROUTE_MAX_CAPP = 1 << 15
# window rows are a whole number of (16, 128) tiles, so the output block
# satisfies the bf16 tiling as well as the 32-bit one
_ROUTE_ROW_ALIGN = 16


def _route_rows(cap: int) -> int:
    return -(-cap // (_ROUTE_ROW_ALIGN * _LANES)) * _ROUTE_ROW_ALIGN


def _bucket_route_kernel(cap: int, r2: int, shard_n: int,
                         starts_ref, counts_ref, vals_ref, idx_ref,
                         bv_ref, bi_ref, win_v, win_i, sem_v, sem_i):
    w = pl.program_id(0)
    start = starts_ref[w]
    cnt = jnp.minimum(counts_ref[w], cap)
    first_row = start >> 7                         # start // 128, start >= 0
    off = start & (_LANES - 1)
    rows = win_v.shape[0]                          # r2 + 8: one spill row, tile-aligned
    # the payload is padded so the last destination's rows stay in bounds
    cv = pltpu.make_async_copy(vals_ref.at[pl.ds(first_row, rows), :], win_v,
                               sem_v)
    ci = pltpu.make_async_copy(idx_ref.at[pl.ds(first_row, rows), :], win_i,
                               sem_i)
    cv.start()
    ci.start()
    cv.wait()
    ci.wait()
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    shift = (_LANES - off) & (_LANES - 1)

    def window(a):
        # out[r, l] = payload[start + r * 128 + l]: lanes below 128 - off
        # come from row r, the rest from row r + 1
        here = pltpu.roll(a, shift, 1)
        below = pltpu.roll(here, rows - 1, 0)
        return jnp.where(lane < _LANES - off, here, below)[:r2]

    v = window(win_v[:])
    ix = window(win_i[:])
    pos = (jax.lax.broadcasted_iota(jnp.int32, (r2, _LANES), 0) * _LANES
           + jax.lax.broadcasted_iota(jnp.int32, (r2, _LANES), 1))
    take = pos < cnt
    bv_ref[:] = jnp.where(take, v, 0.0).astype(bv_ref.dtype)
    # bucket-local index; empty slots carry the shard_n guard row the owner
    # reduce scatters into
    bi_ref[:] = jnp.where(take, ix - w * shard_n, shard_n)


def fused_bucket_route(vals: Array, idx: Array, dest: Array, world: int,
                       cap: int, shard_n: int, *,
                       interpret: bool | None = None):
    """``(bvals [W, cap], bidx [W, cap])`` — the sharded transport's
    per-destination buckets, built as W windowed copies instead of a
    [W*cap+1] scatter pair.  ``dest`` is the per-element destination (dump
    value ``world`` for invalid tail slots), ascending by the payload's
    monotone-index contract."""
    k = vals.shape[0]
    if interpret is None:
        interpret = _auto_interpret()
    r2 = _route_rows(cap)
    cap_p = r2 * _LANES
    win_rows = r2 + 8
    # per-destination totals and exclusive starts (tiny: W+1 buckets); the
    # dump bucket keeps invalid tail slots out of every window
    counts_all = jnp.zeros((world + 1,), jnp.int32).at[dest].add(
        1, indices_are_sorted=True, mode="promise_in_bounds")
    starts = (jnp.cumsum(counts_all) - counts_all)[:world].astype(jnp.int32)
    counts = counts_all[:world]
    # 32-bit windows (sub-32-bit rows pack in pairs and cannot start at an
    # odd row); bf16 -> f32 -> bf16 is exact
    pay_rows = k // _LANES + win_rows
    pad = pay_rows * _LANES - k
    vpad = jnp.concatenate([vals.astype(jnp.float32),
                            jnp.zeros((pad,), jnp.float32)]
                           ).reshape(pay_rows, _LANES)
    ipad = jnp.concatenate([idx, jnp.zeros((pad,), jnp.int32)]
                           ).reshape(pay_rows, _LANES)
    vma = _vma(vals)
    outs = pl.pallas_call(
        functools.partial(_bucket_route_kernel, int(cap), r2, int(shard_n)),
        grid=(world,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((r2, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r2, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((world * r2, _LANES), vals.dtype, vma=vma),
            jax.ShapeDtypeStruct((world * r2, _LANES), jnp.int32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((win_rows, _LANES), jnp.float32),
            pltpu.VMEM((win_rows, _LANES), jnp.int32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(starts, counts, vpad, ipad)
    bvals = outs[0].reshape(world, cap_p)[:, :cap]
    bidx = outs[1].reshape(world, cap_p)[:, :cap]
    return bvals, bidx


def use_bucket_route(k: int, world: int, cap: int) -> bool:
    """Whether the sharded route phase should take the fused window kernel.
    Element-granular payloads only (the blocky Block-Top-K row layout keeps
    the XLA scatter); the window bound keeps both scratch copies in VMEM."""
    return (_dispatch_to_pallas(k) and k <= _INT32_MAX and world >= 2
            and _route_rows(cap) * _LANES <= _ROUTE_MAX_CAPP)
