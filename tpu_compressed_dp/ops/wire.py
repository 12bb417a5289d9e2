"""Wire-sparse gradient sync: genuinely bandwidth-reducing payloads.

The reference's simulated compression allreduces a full-size zero-filled dense
tensor (`CIFAR10/core.py:218,278`) — only `RandomKSparsifiedDDP` actually
shrinks the payload, by `masked_select`-ing k elements per parameter into the
reduction bucket (`IMAGENET/training/sparsified_ddp.py:412,460-462`) and
relying on a shared RNG seed so every rank picks the same indices
(`sparsified_ddp.py:164`).  This module is the TPU-native generalisation of
that path (``mode='wire'`` of :class:`~tpu_compressed_dp.parallel.dp.CompressionConfig`),
covering all six reference operators plus the net-new Block-Top-K:

  * **Random-K** (the `RandomKSparsifiedDDP` equivalent): a PRNG key shared by
    all workers selects identical coordinates; only the k surviving *values*
    travel, packed into a ``[k]`` buffer that is ``lax.psum``-reduced.  Indices
    never travel — they are implied by the common key.  Unlike the reference
    (which returns the **sum**, `sparsified_ddp.py:481-483` + §3.3 note), the
    reduced values are divided by world size, consistent with every other path
    here.
  * **Top-K**: worker-local index sets differ, so values *and* indices travel:
    fixed-size ``([k] values, [k] int32 indices)`` pairs are ``all_gather``-ed
    and scatter-added into a dense vector.  Exactly ``k = topk_keep_count(n)``
    elements are kept per worker (fixed-size for XLA); the simulate path's
    keep-all-ties semantics (`core.py:181-183`) can keep a few more — the two
    modes agree whenever ``|g|`` has no ties at the threshold.
  * **Block-Top-K** (net-new, no reference equivalent): element Top-K's wire
    form needs per-element stream compaction of the full gradient; selecting
    whole contiguous blocks by L2 norm instead moves the compaction onto the
    ~n/block_size block *scores*, and the payload — ``[kb, block_size]``
    value rows + ``[kb]`` block indices — gathers/scatters as contiguous
    lane-aligned rows.  The TPU-native fast path among the sparsifiers.
  * **TernGrad**: per-worker ternary levels bit-packed four-per-byte
    (codes ``level+1 ∈ {0,1,2}`` → 2 bits each) plus the fp32 scale(s),
    combined via ``all_gather`` — the collective moves the 2 bits/elem the
    analytic accounting bills (round 4; previously int8 shipped while 2 bits
    were billed, a 4× understatement).
  * **QSGD / random dithering**: narrowest layout that fits ``qstates``:
    ``sign ⊗ level`` int8 for ``qstates ≤ 127`` (8 bits/elem), uint8
    magnitudes + a bit-packed sign bitmap for ``qstates ≤ 255`` (9 bits/elem),
    int16 beyond; plus one fp32 norm, combined via ``all_gather``.
  * **Threshold-V / Adaptive-Threshold** (`core.py:189-199`): survivor
    counts are data-dependent — hostile to XLA's static shapes — so the wire
    form is a **fixed-capacity buffer**: each worker packs its first
    ``cap = wire_cap_ratio * n`` surviving coordinates (ascending index)
    into ``([cap] values, [cap] int32 indices)``, zero-padding unused slots
    (padded slots carry idx 0 / value 0 — additive identities under the
    scatter-add combine).  Survivors beyond ``cap`` stay in the error
    feedback residual when EF is on, and are *dropped* (exactly as if below
    threshold) when it is off; ``comm/threshold_overflow`` reports the
    clipped count so capacity can be sized.  Transport is the full
    cap-sized buffer, and the analytic accounting bills it as such
    (``sent_bits = cap * 64`` even when half-empty — fixed-size transport
    is the honest wire cost).

The index-carrying sparsifiers (Top-K, Block-Top-K, Threshold-V/Adaptive)
support two combines, selected by ``CompressionConfig.transport``: the flat
``all_gather`` described above (per-chip volume and decode ``O(W*k)``), or
the owner-sharded sparse reduce (``transport='sharded'``,
:mod:`tpu_compressed_dp.ops.wire_sharded`): pairs route to contiguous shard
owners over one ``lax.all_to_all``, owners scatter-add their dense ``n/W``
shard, and the reduced shards return via one ``all_gather`` — per-chip
``O(k + n/W)``, the scalable regime at large worker counts (OKTopk,
PAPERS.md).  ``transport='hierarchical'`` adds a two-level reduce over a
``dp_pods x dp_chips`` virtual mesh: dense psum along the fast intra-pod
ICI axis, re-compress the pod union, and exchange only (value, index)
pairs across the slow DCN axis via the sharded bucket-route machinery —
per-chip DCN volume ``O(k + n/W_pods)``, billed per fabric.
``parallel.dp.wire_transport`` is the classifier (psum / allgather /
sharded / hierarchical) behind the ``sent_bits_psum`` /
``sent_bits_allgather`` / ``sent_bits_alltoall`` — and, hierarchical,
``sent_bits_ici`` / ``sent_bits_dcn`` — accounting split.

All wire methods bill **measured transport**: ``sent_bits`` is computed from
the actual byte sizes of the arrays handed to the collective (including
scales/norms), the TPU-static analog of the reference's NIC byte meter
(`IMAGENET/training/meter.py:24-47,66-86`).

One chain per size, not per leaf: the reduction groups that share a flat
size, a dtype and a transport (:func:`chain_parts`) sync in ONE traced chain.
Members under ``kernels.MIN_PALLAS_ELEMS`` sync as a stack (``jax.vmap`` of
the per-group chain over ``[m, n]``); longer ones stay on their own buffers
and share the Top-K threshold search, one loop a part
(``kernels.topk_thresholds``) — see :func:`_stacks` for why.  ResNet-50's 161
layer-wise leaves make 22 chains, each member's result bitwise what a chain of
its own gives.  ``comm/sync_chains`` reports the count beside
``comm/num_collectives`` (the reduction groups).

Error feedback composes with the sparsifiers exactly as in
`sparsified_ddp.py:408-413`: the residual (dropped coordinates) is returned
for the caller to re-add next step.  Quantizers are unbiased estimators and
get a zero residual.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
# The gathered payload is identical on every worker; the *_invariant
# variant carries that fact in the type so shard_map's replication
# checker accepts replicated out_specs downstream (plain all_gather
# keeps the device-varying tag).
from jax._src.lax.parallel import all_gather_invariant as _all_gather
from jax._src.lax.parallel import psum_p as _psum_p

from tpu_compressed_dp.ops import compressors

Array = jax.Array

__all__ = ["make_wire_grad_sync", "WIRE_METHODS", "pack_ternary",
           "unpack_ternary", "pack_bits", "unpack_bits", "qsgd_wire_pack",
           "qsgd_wire_unpack", "packed_indices_monotone", "select_pack_topk"]

WIRE_METHODS = ("randomk", "topk", "blocktopk", "terngrad", "qsgd",
                "thresholdv", "adaptive_threshold")


def pack_ternary(levels: Array) -> Array:
    """Bit-pack ternary levels (int8 in {-1,0,1}) four-per-byte.

    Codes ``level+1 ∈ {0,1,2}`` occupy 2 bits; byte layout is little-endian
    within the byte (element i sits at bits ``2*(i%4)``).  Output is
    ``uint8[ceil(n/4)]`` — the actual wire form TernGrad's all_gather moves.
    Arithmetic runs in int32 (TPU-native lane width); only the final cast is
    uint8, so no sub-word shift ops are required of Mosaic/XLA.
    """
    n = levels.shape[0]
    pad = (-n) % 4
    c = jnp.pad(levels, (0, pad)).astype(jnp.int32) + 1  # {0,1,2}
    c = c.reshape(-1, 4)
    packed = c[:, 0] + (c[:, 1] << 2) + (c[:, 2] << 4) + (c[:, 3] << 6)
    return packed.astype(jnp.uint8)


def unpack_ternary(packed: Array, n: int) -> Array:
    """Inverse of :func:`pack_ternary`: ``uint8[ceil(n/4)] -> int8[n]``."""
    p = packed.astype(jnp.int32)
    codes = jnp.stack(
        [p & 3, (p >> 2) & 3, (p >> 4) & 3, (p >> 6) & 3], axis=-1)
    return (codes.reshape(*packed.shape[:-1], -1)[..., :n] - 1).astype(jnp.int8)


def pack_bits(bits: Array) -> Array:
    """Pack a boolean vector eight-per-byte (little-endian within the byte)."""
    n = bits.shape[0]
    pad = (-n) % 8
    b = jnp.pad(bits, (0, pad)).astype(jnp.int32).reshape(-1, 8)
    w = (1 << jnp.arange(8, dtype=jnp.int32))
    return jnp.sum(b * w, axis=1).astype(jnp.uint8)


def unpack_bits(packed: Array, n: int) -> Array:
    """Inverse of :func:`pack_bits`: ``uint8[ceil(n/8)] -> bool[n]``."""
    p = packed.astype(jnp.int32)
    bits = jnp.stack([(p >> i) & 1 for i in range(8)], axis=-1)
    return bits.reshape(*packed.shape[:-1], -1)[..., :n].astype(bool)


def qsgd_wire_pack(levels: Array, qstates: int) -> tuple[Array, ...]:
    """Narrowest wire layout for QSGD ``sign ⊗ level`` int16 levels.

    * ``qstates <= 127``: one int8 array (sign and magnitude share the byte);
    * ``qstates <= 255``: uint8 magnitudes + a bit-packed sign bitmap
      (9 bits/elem — the fixed-width layout `payload_bits_per_elem` bills);
    * beyond: the int16 levels unchanged (16 bits/elem).
    """
    if qstates <= 127:
        return (levels.astype(jnp.int8),)
    if qstates <= 255:
        mags = jnp.abs(levels.astype(jnp.int32)).astype(jnp.uint8)
        signs = pack_bits(levels < 0)
        return (mags, signs)
    return (levels,)


def qsgd_wire_unpack(payload: tuple[Array, ...], n: int, qstates: int,
                     dtype=jnp.float32) -> Array:
    """Inverse of :func:`qsgd_wire_pack`, returning ``sign ⊗ level`` in
    ``dtype`` (ready to scale); accepts a leading gather axis."""
    if qstates <= 127 or qstates > 255:
        return payload[0].astype(dtype)
    mags, signs = payload
    neg = unpack_bits(signs, n)
    return jnp.where(neg, -mags.astype(dtype), mags.astype(dtype))


def _sorted_gather(a: Array, idx: Array) -> Array:
    """``a[idx]`` where ``idx`` is known ascending (not necessarily unique)
    and in bounds.  The hints matter at wire scale: XLA's general gather
    assumes arbitrary indices; sorted+in-bounds lowers to a cheaper sequence
    on TPU for the k~1M element-granular loads this path lives on."""
    return a.at[idx].get(indices_are_sorted=True, mode="promise_in_bounds")


def _select_pack(flat: Array, mag: Array, t, keep: int):
    """``(payload [keep], idx [keep], survivor count)``: the coordinates
    with ``mag >= t`` by ascending index — the wire select+pack step.

    One fused Pallas pass (`kernels.fused_select_pack`) when dispatched;
    otherwise the XLA mask -> `packed_indices_from_mask` -> `_sorted_gather`
    chain.  Payloads are bitwise identical across the two paths whenever
    ``count >= keep`` (`topk_threshold`'s guarantee; parity-gated in
    tests/test_kernels.py) — underfull masks differ only in the padding
    slots, which every caller re-masks or treats as scatter identities."""
    from tpu_compressed_dp.ops import kernels

    if kernels.use_select_pack(flat.shape[0], keep):
        return kernels.fused_select_pack(flat, t, keep)
    mask = mag >= t
    idx = packed_indices_from_mask(mask, keep)
    return _sorted_gather(flat, idx), idx, jnp.sum(mask, dtype=jnp.int32)


def select_pack_topk(flat: Array, keep: int):
    """Top-``keep``-by-magnitude select+pack of a flat vector: the wire
    compress step (threshold + select + pack, Pallas-fused when
    dispatched) exposed for non-gradient payloads — the delta stream in
    :mod:`tpu_compressed_dp.stream` runs it on parameter drift.

    Returns ``(payload [keep], idx [keep] ascending, survivor count)``;
    when ``count < keep`` (underfull mask — e.g. non-finite inputs)
    trailing ranks pad with index 0 and callers must trim to
    ``min(count, keep)``.  Magnitudes are computed internally (``|flat|``
    in fp32) because the fused kernel recomputes them from ``flat``."""
    from tpu_compressed_dp.ops import kernels

    mag = jnp.abs(flat).astype(jnp.float32)
    t = kernels.topk_threshold(mag, keep)
    return _select_pack(flat, mag, t, keep)


def _scatter_combine(shape, dtype, g_idx: Array, g_vals: Array, world,
                     block_size: int = 0) -> Array:
    """Gathered ``[W, k]`` (indices, values) payload -> dense sum / world.

    Each worker's index row is ascending and unique by construction
    (`packed_indices_from_mask`), but a flattened ``[W*k]`` scatter-add
    forfeits that: XLA must assume arbitrary duplicate order.  Per-row
    scatters keep the ``indices_are_sorted`` / ``unique_indices`` hints
    alive; ``W`` is a static mesh size so the loop unrolls at trace time.
    Beyond 16 rows fall back to the single fused scatter (compile-size
    guard — the hint's win is per-element dispatch, already amortised at
    large ``W``).  ``block_size > 0`` scatters contiguous value rows
    (Block-Top-K payloads, ``g_vals: [W, kb, bs]``).
    """
    W = g_idx.shape[0]
    dense = jnp.zeros(shape, dtype)
    if W <= 16:
        for w in range(W):
            dense = dense.at[g_idx[w]].add(
                g_vals[w], indices_are_sorted=True, unique_indices=True,
                mode="promise_in_bounds")
    else:
        vals = (g_vals.reshape(-1, block_size) if block_size
                else g_vals.reshape(-1))
        dense = dense.at[g_idx.reshape(-1)].add(vals)
    return dense / world


def packed_indices_from_mask(mask: Array, keep: int) -> Array:
    """Ascending indices of the first ``keep`` True positions of ``mask``.

    Precondition: the mask should have at least ``keep`` set bits; ranks
    beyond the actual count degrade benignly to index 0 (the same fill
    ``jnp.nonzero(size=keep, fill_value=0)`` used).

    ``jnp.nonzero(size=)`` and a flat 1-D cumsum both lower poorly on TPU at
    gradient scale (~400ms / ~190ms at 42M elements).  Hierarchical stream
    compaction instead: per-128-lane-row counts (one linear reduce), a small
    cumsum over row totals, then a rank->row map via bucketing each row's
    inclusive end and prefix-summing — ``row_of[r-1] = #{i : row_ends[i] < r}``
    (== searchsorted(row_ends, r, left)) — which replaced a binary search's
    serialized gather chain (258ms -> ~25ms at 170M, round 2).

    The per-rank stage is ONE gather per rank, of the mask rows (round 5
    had a second one, of precomputed row starts; before that three + an
    fp32 tri-matmul): per-rank costs are billed per random ACCESS, and the
    round-5 bisect of the pack's sub-stages (ROUND5_NOTES.md) measured ~7 ms
    per [keep]-sized gather at keep=1.25M.  A rank's row start is where its run
    of equal ``row_of`` began (`kernels.run_starts`): a scan over the
    ranks, no access.  The in-row prefix matmul runs in bf16 (row
    prefix counts are <= 128, exactly representable), halving the gathered
    rows' materialisation traffic vs fp32.  Two rejected redesigns, both
    measured slower: bit-packing rows into uint32 words for a single
    32-byte-row gather (the uint32 pack pass itself costs ~30 ms — integer
    multiply-reduce over the full tensor does not vectorise well on the
    VPU), and a full-tensor scatter formulation emitting (idx, val) pairs
    elementwise (XLA does not stream sorted 125M-update scatters: 2.2 s).
    """
    from tpu_compressed_dp.ops import kernels

    lanes = 128
    n = mask.shape[0]
    pad = (-n) % lanes
    m2 = jnp.pad(mask, (0, pad)).reshape(-1, lanes)
    nrows = m2.shape[0]
    row_counts = jnp.sum(m2, axis=1, dtype=jnp.int32)
    # NB: plain 1-D cumsum here — at the ~n/128 and ~keep sizes these run at,
    # XLA's native scan beats a hand-rolled two-level decomposition (measured
    # +18ms/step at LM scale from a hier_cumsum variant, round 2)
    row_ends = jnp.cumsum(row_counts)                      # inclusive offsets
    ranks = jnp.arange(1, keep + 1, dtype=jnp.int32)
    # row_ends is a cumsum — monotone — so the histogram scatter and the
    # gathers below ride the sorted-indices fast path
    ends_hist = jnp.zeros((keep + 1,), jnp.int32).at[
        jnp.minimum(row_ends, keep)].add(
            1, indices_are_sorted=True, mode="promise_in_bounds")
    row_of = jnp.cumsum(ends_hist)[:keep]
    valid = row_of < nrows                                 # rank <= total count
    # pad invalid ranks with the LAST row (not row 0): keeps row_of monotone
    # so the sorted-gather hints stay truthful; the final jnp.where still
    # returns index 0 for invalid ranks
    row_of = jnp.where(valid, row_of, nrows - 1)
    # rank within the row: global rank minus everything before the row,
    # which is where the rank's run of equal `row_of` began — a scan over
    # the ranks, not a third gather of (row_ends - row_counts)
    within = ranks - kernels.run_starts(row_of)           # 1-based in-row rank
    rows = _sorted_gather(m2, row_of).astype(jnp.bfloat16)  # [keep, 128]
    tri = jnp.tril(jnp.ones((lanes, lanes), jnp.bfloat16))
    # inclusive in-row prefix on the MXU; counts <= 128 are bf16-exact
    prefix = jax.lax.dot(rows, tri.T,
                         preferred_element_type=jnp.float32)
    hit = (prefix >= within[:, None].astype(jnp.float32)) & (rows > 0)
    col = jnp.argmax(hit, axis=1).astype(jnp.int32)
    return jnp.where(valid, row_of * lanes + col, 0)


def packed_indices_monotone(idx: Array) -> Array:
    """Debug predicate for the ``indices_are_sorted``/``unique_indices``
    scatter hints downstream of :func:`packed_indices_from_mask`: True iff
    ``idx`` is strictly ascending (ascending AND unique), which holds
    exactly when the source mask had at least ``keep`` set bits.

    The known violation is a non-finite gradient: NaNs compare false
    against the Top-K threshold, the mask underfills, and the pack pads
    trailing ranks with duplicate index 0 — at which point the hinted
    scatters in `_scatter_combine` and the sharded transport's EF zeroing
    are undefined rather than benignly degraded (the allgather Top-K
    residual scatters nothing and `comm/topk_underfull` counts such groups).
    Run with this check (outside the hot path —
    it is a debug aid, not a runtime guard) when chasing corruption under
    suspected overflow/NaN gradients; tests/test_wire_sharded.py pins both
    directions of the predicate.
    """
    return jnp.all(idx[1:] > idx[:-1]) if idx.shape[0] > 1 else jnp.asarray(True)


def _randomk_indices(key: Array, n: int, keep: int) -> Array:
    """The coordinates Random-K keeps, bit-identical to the simulate mask
    (same ``randomk_mask`` call, so wire and simulate modes always agree)."""
    mask = compressors.randomk_mask(key, n, keep)
    return packed_indices_from_mask(mask, keep)


def _leaf_sync_randomk(flat: Array, key: Array, keep: int, axis_name: str, world,
                       check: bool = False):
    idx = _randomk_indices(key, flat.shape[0], keep)
    payload = _sorted_gather(flat, idx)                   # [k] — all that travels
    bits = _payload_bits(payload)
    reduced = jax.lax.psum(payload, axis_name) / world
    # NB: fresh zeros, not zeros_like(flat) — the latter would inherit the
    # device-varying manifest-axes tag of the local gradient and defeat
    # shard_map's replication inference for the psum-reduced result.
    dense = jnp.zeros(flat.shape, flat.dtype).at[idx].set(
        reduced, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds")
    agree = None
    if check:
        # `check_reduction` analog: all workers must have selected the SAME
        # indices or the packed psum silently mixes coordinates
        h = jnp.sum(idx.astype(jnp.float64 if jax.config.jax_enable_x64
                               else jnp.float32) * (1.0 + jnp.arange(keep) % 7))
        agree = (jax.lax.pmax(h, axis_name) == jax.lax.pmin(h, axis_name)
                 ).astype(jnp.float32)
    return dense, idx, agree, bits


def _leaf_sync_topk(flat: Array, keep: int, axis_name: str, world,
                    want_ef: bool, t=None):
    """Element Top-K on the allgather transport: ``(dense, new_ef, bits,
    count)``, ``count`` the survivors of the threshold (``keep`` of them
    travel)."""
    # threshold-select + hierarchical pack instead of lax.top_k's full sort;
    # near-threshold membership can differ from exact top-k by a few elements
    # at the histogram's final-bin resolution (error feedback reabsorbs the
    # difference).  fp32 magnitudes keep the count >= keep guarantee that
    # packed_indices_from_mask requires.
    from tpu_compressed_dp.ops import kernels

    n = flat.shape[0]
    mag = jnp.abs(flat).astype(jnp.float32)
    if t is None:
        t = kernels.topk_threshold(mag, keep)
    payload, idx, count = _select_pack(flat, mag, t, keep)
    bits = _payload_bits(payload, idx)
    g_vals = _all_gather(payload, axis_name)       # [W, k]
    g_idx = _all_gather(idx, axis_name)            # [W, k]
    dense = _scatter_combine(flat.shape, flat.dtype, g_idx, g_vals, world)
    new_ef = None
    if want_ef:
        # EF residual = the coordinates that did NOT travel.  Survivors
        # travel by ascending index and are cut at `keep`, so the sent set
        # is every survivor up to the last packed index (every survivor of
        # an underfull mask: a NaN gradient, `comm/topk_underfull`), and
        # the residual is one streamed pass with the compare that chose the
        # payload — no `keep`-sized scatter into a copy of `flat`, and no
        # index row promised unique to XLA that a NaN could break.
        # Survivors beyond `keep` (ties, the final bin's surplus) lie after
        # the last packed index and stay in the residual.
        upto_last = jnp.arange(n, dtype=jnp.int32) <= idx[keep - 1]
        sent = (mag >= t) & (upto_last | (count < keep))
        new_ef = jnp.where(sent, 0, flat)
    return dense, new_ef, bits, count


def _leaf_sync_blocktopk(flat: Array, keep_blocks: int, block_size: int,
                         axis_name: str, world, want_ef: bool):
    """Block-granular Top-K: whole contiguous blocks travel.

    The TPU-native fast path — selected blocks gather/scatter as contiguous
    lane-aligned rows, so there is no per-element stream compaction at all:
    the pack runs on the ~n/block_size block scores instead of n elements.
    Payload per worker: ``[keep_blocks, block_size]`` values +
    ``[keep_blocks]`` int32 block indices, all_gather-combined (worker-local
    block sets differ, as with element Top-K).
    """
    from tpu_compressed_dp.ops import kernels

    n = flat.shape[0]
    scores = compressors.blocktopk_scores(flat, block_size)
    t = kernels.topk_threshold(scores, keep_blocks)
    # scores are non-negative, so they serve as their own magnitudes for the
    # fused select+pack dispatch; only the index stream is consumed here
    bidx = _select_pack(scores, scores, t, keep_blocks)[1]
    if block_size < 128 and 128 % block_size == 0:
        return _blocktopk_small_bs(flat, bidx, block_size, axis_name, world,
                                   want_ef)
    g2 = compressors.blocktopk_blocks(flat, block_size)
    payload = _sorted_gather(g2, bidx)         # [kb, bs] contiguous rows
    bits = _payload_bits(payload, bidx)
    g_vals = _all_gather(payload, axis_name)   # [W, kb, bs]
    g_idx = _all_gather(bidx, axis_name)       # [W, kb]
    dense2 = _scatter_combine(g2.shape, flat.dtype, g_idx, g_vals, world,
                              block_size=block_size)
    dense = dense2.reshape(-1)[:n]
    new_ef = (g2.at[bidx].set(0.0, indices_are_sorted=True,
                              unique_indices=True, mode="promise_in_bounds")
              .reshape(-1)[:n] if want_ef else None)
    return dense, new_ef, bits


def _blocktopk_small_bs(flat: Array, bidx: Array, block_size: int,
                        axis_name: str, world, want_ef: bool):
    """Block-Top-K wire sync for sub-128-lane blocks via COVERING rows.

    A ``[nb, block_size]`` view pads every row to the 128-lane register
    width, so gathering/scattering ``block_size``-wide rows at bs=8 wastes
    16x the memory machinery (measured 36 ms of "rest" at the 125M/1%
    config, round 5).  Instead keep the natural
    ``[m, 128]`` layout and touch only full cache-line rows:

      * payload gather: fetch each selected block's COVERING 128-lane row
        (one full-line access), then select its ``128/bs`` sub-block in
        registers (jnp.where + sum over the sub-block axis — `where`, not
        multiply-by-mask, so inf/nan gradients in unselected blocks cannot
        poison the selection);
      * scatter-add reconstruction: expand each worker's ``[kb, bs]``
        payload into zeros-padded covering rows and scatter-add full rows
        (duplicate row ids — two selected blocks sharing a row — are
        legal for add);
      * EF: scatter-MULTIPLY the covering rows by a keep-mask (commutative,
        so duplicate rows compose correctly).

    The wire format and billing are unchanged: ``[kb, bs]`` values +
    ``[kb]`` indices travel, exactly like the wide-block path.
    """
    n = flat.shape[0]
    per = 128 // block_size
    pad = (-n) % 128
    g128 = jnp.pad(flat, (0, pad)).reshape(-1, 128)       # [m, 128]
    kb = bidx.shape[0]
    rowid = bidx // per                                   # sorted, not unique
    sub = bidx % per
    rows = _sorted_gather(g128, rowid)                    # [kb, 128] full lines
    sel = (jnp.arange(per, dtype=jnp.int32)[None, :] == sub[:, None])
    payload = jnp.sum(
        jnp.where(sel[:, :, None], rows.reshape(kb, per, block_size), 0.0),
        axis=1)                                           # [kb, bs]
    bits = _payload_bits(payload, bidx)
    g_vals = _all_gather(payload, axis_name)              # [W, kb, bs]
    g_idx = _all_gather(bidx, axis_name)                  # [W, kb]
    W = g_idx.shape[0]

    def expand(idx_row, vals_row):
        s = (jnp.arange(per, dtype=jnp.int32)[None, :]
             == (idx_row % per)[:, None])
        return jnp.where(s[:, :, None], vals_row[:, None, :],
                         0.0).reshape(-1, 128)

    dense128 = jnp.zeros(g128.shape, flat.dtype)
    if W <= 16:
        for w in range(W):
            dense128 = dense128.at[g_idx[w] // per].add(
                expand(g_idx[w], g_vals[w]), indices_are_sorted=True,
                mode="promise_in_bounds")
    else:
        # compile-size guard (same rationale as _scatter_combine): one fused
        # unhinted scatter over all workers' expanded rows
        dense128 = dense128.at[(g_idx // per).reshape(-1)].add(
            expand(g_idx.reshape(-1), g_vals.reshape(-1, block_size)))
    dense = (dense128 / world).reshape(-1)[:n]
    new_ef = None
    if want_ef:
        # EF = zero exactly the sent sub-blocks.  A direct scatter-multiply
        # of g128 by a 0/1 mask would turn a sent inf into inf*0 = NaN and
        # poison the residual (the wide path's set(0.0) is immune) — so
        # accumulate the mask separately (finite 0/1 values compose under
        # duplicate covering rows) and apply it with where.
        keep_mask = jnp.broadcast_to(
            ~sel[:, :, None], (kb, per, block_size)).astype(
                jnp.uint8).reshape(kb, 128)
        maskarr = jnp.ones(g128.shape, jnp.uint8).at[rowid].multiply(
            keep_mask, indices_are_sorted=True, mode="promise_in_bounds")
        new_ef = jnp.where(maskarr.astype(bool), g128,
                           0.0).reshape(-1)[:n]
    return dense, new_ef, bits


def _leaf_sync_threshold(flat: Array, v, cap: int, axis_name: str, world,
                         want_ef: bool):
    """Fixed-capacity wire form of the data-dependent-count threshold
    operators (`core.py:189-199`): pack the first ``cap`` survivors by
    ascending index, zero-pad the rest, all_gather, scatter-add.

    Returns ``(dense, new_ef, sent_count, overflow)`` where ``sent_count``
    is the (dynamic) number of coordinates that actually travelled and
    ``overflow`` how many survivors were clipped by the capacity.
    """
    mag = jnp.abs(flat)
    vals, idx, count = _select_pack(flat, mag, v, cap)
    sent_count = jnp.minimum(count, cap)
    rank = jnp.arange(1, cap + 1, dtype=jnp.int32)
    valid = rank <= sent_count
    vals = jnp.where(valid, vals, 0.0)
    idx = jnp.where(valid, idx, 0)
    bits = _payload_bits(vals, idx)                  # the full cap-sized buffer
    g_vals = _all_gather(vals, axis_name)            # [W, cap]
    g_idx = _all_gather(idx, axis_name)              # [W, cap]
    dense = (
        jnp.zeros(flat.shape, flat.dtype)
        .at[g_idx.reshape(-1)]
        .add(g_vals.reshape(-1))
        / world
    )
    new_ef = None
    if want_ef:
        # zero exactly the sent coordinates; padded slots multiply coord 0
        # by 1 (scatter-mul identity)
        new_ef = flat.at[idx].mul(jnp.where(valid, 0.0, 1.0))
    overflow = jnp.maximum(count - cap, 0)
    return dense, new_ef, sent_count, overflow, bits


def _payload_bits(*arrays: Array) -> float:
    """Measured transport: total bits of the arrays handed to the collective
    (one worker's payload — the per-chip quantity the traffic model scales)."""
    return float(sum(a.size * a.dtype.itemsize * 8 for a in arrays))


def _shard_plan(cfg, n_units: int, keep: int, world: int, unit_size: int):
    from tpu_compressed_dp.ops import wire_sharded

    return wire_sharded.make_shard_plan(
        n_units, keep, world, unit_size,
        cfg.shard_route_factor, cfg.shard_return_factor)


def _group_psum(x: Array, axis_name, groups, *, same_everywhere: bool
                ) -> Array:
    """Sum ``x`` inside each group of ``axis_index_groups``.

    ``jax.lax.psum`` types its result as invariant over the axis and so
    refuses groups under shard_map's replication check (a group sum differs
    from group to group).  The all-reduce primitive itself lowers groups to
    replica groups; bind it directly and state the result's type here:
    varying, unless the caller knows every group arrives at the same sum.
    """
    axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) else (
        axis_name,)
    varies = jax.typeof(x).vma
    out = _psum_p.bind(x, axes=axes,
                       axis_index_groups=tuple(tuple(g) for g in groups))
    # the primitive's type rule forgets every axis; put back the ones the
    # sum still varies over (model axes always, the summed axes unless all
    # groups agree)
    if same_everywhere:
        varies = varies - set(axes)
    return jax.lax.pcast(out, tuple(sorted(varies)), to="varying") if varies else out


def _hier_combine(contrib: Array, keep: int, axis_name: str, world, cfg):
    """Two-level (ICI x DCN) exchange of one group's compressed-dense
    contribution (``transport='hierarchical'``).

    ``contrib`` is this worker's selection scattered dense (``[n]``, zeros
    at unselected coordinates) — the SAME selection the flat transports
    ship, so hierarchical stays coordinate-equivalent to them.  The flat dp
    axis is viewed as ``dp_pods x chips`` (:func:`~tpu_compressed_dp.ops.
    wire_sharded.hier_axis_groups`):

      1. **ici-reduce** — one dense psum of ``contrib`` inside the pod:
         cheap fabric, and cross-worker duplicates collapse here so only
         the pod UNION crosses the DCN.
      2. **recompress** — pack the pod sum's nonzero union (ascending, the
         Threshold-V prefix-validity discipline) into a ``cap_union``
         buffer sized by ``hier_route_factor_ici x keep``, then slice it
         into per-chip slabs: chip ``c`` of every pod carries slab ``c``,
         so each DCN column moves ``1/chips`` of the pod payload.
      3. **dcn route/reduce/return** — the slabs ride the ordinary
         owner-sharded exchange (:func:`~tpu_compressed_dp.ops.
         wire_sharded.sharded_combine`) restricted to the chip-rank column
         across pods (``axis_index_groups``, ``pods`` senders).
      4. **ici-reduce (back)** — a second dense pod psum sums the chips'
         disjoint-slab partials into the full inter-pod total.

    Returns ``(total, ef_extra, bits_ici, bits_dcn_route, bits_dcn_ret,
    overflow)``: ``total`` is the sum over ALL workers of their transmitted
    contributions (caller divides by world); ``ef_extra`` is this worker's
    exact refund of everything clipped after its pod reduce — recompress
    clips refund ``pod_sum / chips`` on every pod chip (the clip is
    pod-replicated), DCN route/return clips refund the full pod value on
    the one chip whose slab carried them — so summed across workers,
    ``transmitted + refunds == sum of contributions`` (the
    ``comm/shard_overflow`` EF invariant).  ``overflow`` counts recompress
    clips (chip-rank 0 only, so the psum'd figure counts each pod once)
    plus the DCN exchange's route/return clips.
    """
    from tpu_compressed_dp.obs import trace as obs_trace
    from tpu_compressed_dp.ops import wire_sharded

    n = contrib.shape[0]
    plan = wire_sharded.make_hier_plan(
        n, keep, world, cfg.dp_pods, cfg.hier_route_factor_ici,
        cfg.hier_route_factor_dcn)
    P, C = plan.pods, plan.chips
    ici_groups, dcn_groups = wire_sharded.hier_axis_groups(world, P)
    zero_ovf = jnp.zeros((), jnp.int32)

    with obs_trace.phase("ici_reduce"):
        if C > 1:
            pod_sum = _group_psum(contrib, axis_name, ici_groups,
                                  same_everywhere=False)
            bits_ici = _payload_bits(contrib)
        else:
            pod_sum = contrib
            bits_ici = 0.0
    if P == 1:
        # one pod: the ICI psum above already reduced the whole world and
        # nothing crosses a DCN — transmitted == sum of contributions
        return pod_sum, jnp.zeros_like(contrib), bits_ici, 0.0, 0.0, zero_ovf

    with obs_trace.phase("recompress"):
        cap = plan.cap_union
        mask = pod_sum != 0
        nnz = jnp.sum(mask, dtype=jnp.int32)
        uidx = packed_indices_from_mask(mask, cap)
        uvalid = (jnp.arange(1, cap + 1, dtype=jnp.int32)
                  <= jnp.minimum(nnz, cap))
        uvals = jnp.where(
            uvalid, pod_sum.at[uidx].get(mode="promise_in_bounds"), 0.0)
        uidx = jnp.where(uvalid, uidx, 0)
        # union coordinates clipped by cap_union: the clip is identical on
        # every pod chip (pod_sum is), so each chip refunds 1/C of the pod
        # value and the pod as a whole refunds it exactly once
        taken = jnp.zeros((n,), jnp.uint8).at[uidx].max(
            uvalid.astype(jnp.uint8))
        union_clip = jnp.where(mask & (taken == 0), pod_sum, 0.0) / C
        c_rank = jax.lax.axis_index(axis_name) % C
        slab = plan.slab
        s_vals = jax.lax.dynamic_slice_in_dim(uvals, c_rank * slab, slab)
        s_idx = jax.lax.dynamic_slice_in_dim(uidx, c_rank * slab, slab)
        s_valid = jax.lax.dynamic_slice_in_dim(uvalid, c_rank * slab, slab)

    dense_u, sent, route_bits, ret_bits, dcn_overflow = (
        wire_sharded.sharded_combine(
            s_vals, s_idx, plan.dcn, axis_name, valid=s_valid,
            # one chip a pod: the column is the whole axis, and the ungrouped
            # gather types its result as the same on every worker
            axis_index_groups=dcn_groups if C > 1 else None))
    partial = dense_u[:n]
    with obs_trace.phase("ici_reduce"):
        if C > 1:
            # chip c of every pod holds the same inter-pod sum of slab c,
            # so every pod adds up the same slabs
            total = _group_psum(partial, axis_name, ici_groups,
                                same_everywhere=True)
            bits_ici += _payload_bits(partial)
        else:
            total = partial
    # DCN clips: only this chip's slab carried these units for its pod, so
    # the full pod value is refunded here and nowhere else in the pod
    slice_refund = jnp.zeros((n,), contrib.dtype).at[s_idx].add(
        jnp.where(s_valid & ~sent, s_vals, 0.0))
    ef_extra = union_clip + slice_refund
    union_clipped = jnp.where(c_rank == 0, jnp.maximum(nnz - cap, 0), 0)
    return (total, ef_extra, bits_ici, route_bits, ret_bits,
            dcn_overflow + union_clipped)


def _leaf_sync_topk_sharded(flat: Array, keep: int, axis_name: str, world,
                            cfg, want_ef: bool, t=None):
    """Element Top-K over the owner-sharded transport
    (:mod:`~tpu_compressed_dp.ops.wire_sharded`): same selection as
    `_leaf_sync_topk`, but the (value, index) pairs route to shard owners
    instead of visiting every chip.  Coordinates clipped by the route or
    return capacities stay in the EF residual (EF on) or are dropped and
    counted (EF off) — ``comm/shard_overflow`` sizes the caps either way.
    """
    from tpu_compressed_dp.ops import kernels, wire_sharded

    mag = jnp.abs(flat).astype(jnp.float32)
    if t is None:
        t = kernels.topk_threshold(mag, keep)
    vals, idx, count = _select_pack(flat, mag, t, keep)
    plan = _shard_plan(cfg, flat.shape[0], keep, world, 1)
    dense_u, sent, route_bits, ret_bits, overflow = (
        wire_sharded.sharded_combine(vals, idx, plan, axis_name))
    dense = (dense_u[:flat.shape[0]] / world).astype(flat.dtype)
    new_ef = None
    if want_ef:
        # zero exactly the coordinates the synced gradient contains; routed-
        # but-return-clipped survivors keep their value (set, not mul: a
        # sent inf must not become inf*0 = NaN in the residual)
        new_ef = flat.at[idx].set(
            jnp.where(sent, 0.0, vals), indices_are_sorted=True,
            unique_indices=True, mode="promise_in_bounds")
    # the allgather path's EF-off surplus accounting (ADVICE r2): above-
    # threshold survivors beyond `keep` are a selection-stage drop, reported
    # under its own key — folding it into shard_overflow would pollute the
    # capacity-sizing signal (the factors cannot drive a tie surplus to 0)
    surplus = None if want_ef else jnp.maximum(count - keep, 0)
    # sent_elems = coordinates the synced gradient actually contains
    # (route-accepted AND returned) — same semantics as threshold-sharded,
    # dynamic when the capacity factors clip
    sent_count = jnp.sum(sent, dtype=jnp.int32)
    return (dense, new_ef, sent_count, route_bits + ret_bits, route_bits,
            overflow, surplus)


def _leaf_sync_blocktopk_sharded(flat: Array, keep_blocks: int,
                                 block_size: int, axis_name: str, world,
                                 cfg, want_ef: bool):
    """Block-Top-K over the owner-sharded transport: whole ``[block_size]``
    value rows route to the owners of their block-index shard.  The
    sub-128-lane covering-row trick stays an allgather-path optimisation —
    this path moves ``[kb, bs]`` rows directly at any block size."""
    from tpu_compressed_dp.ops import kernels, wire_sharded

    n = flat.shape[0]
    scores = compressors.blocktopk_scores(flat, block_size)
    t = kernels.topk_threshold(scores, keep_blocks)
    # scores are non-negative, so they serve as their own magnitudes
    bidx = _select_pack(scores, scores, t, keep_blocks)[1]
    g2 = compressors.blocktopk_blocks(flat, block_size)     # [nb, bs]
    payload = _sorted_gather(g2, bidx)                      # [kb, bs]
    plan = _shard_plan(cfg, g2.shape[0], keep_blocks, world, block_size)
    dense_u, sent, route_bits, ret_bits, overflow = (
        wire_sharded.sharded_combine(payload, bidx, plan, axis_name))
    dense = (dense_u / world).astype(flat.dtype).reshape(-1)[:n]
    new_ef = None
    if want_ef:
        new_ef = (g2.at[bidx].set(
            jnp.where(sent[:, None], 0.0, payload), indices_are_sorted=True,
            unique_indices=True, mode="promise_in_bounds")
            .reshape(-1)[:n])
    # sent blocks that actually reached the synced gradient, in ELEMENTS
    # (whole zero-padded block rows travel — same convention as the
    # allgather path's keep accounting)
    sent_count = jnp.sum(sent, dtype=jnp.int32) * block_size
    return dense, new_ef, sent_count, route_bits + ret_bits, route_bits, overflow


def _leaf_sync_threshold_sharded(flat: Array, v, cap: int, axis_name: str,
                                 world, cfg, want_ef: bool):
    """Threshold-V fixed-capacity buffer over the owner-sharded transport:
    the zero-padded tail slots route to the dump destination (they must not
    consume shard-0 bucket capacity).  Returns the threshold cap overflow
    and the transport overflow separately — they size different knobs
    (``wire_cap_ratio`` vs ``shard_route_factor``/``shard_return_factor``).
    """
    from tpu_compressed_dp.ops import wire_sharded

    mag = jnp.abs(flat)
    vals, idx, count = _select_pack(flat, mag, v, cap)
    sent_count = jnp.minimum(count, cap)
    rank = jnp.arange(1, cap + 1, dtype=jnp.int32)
    valid = rank <= sent_count
    vals = jnp.where(valid, vals, 0.0)
    plan = _shard_plan(cfg, flat.shape[0], cap, world, 1)
    dense_u, sent, route_bits, ret_bits, overflow = (
        wire_sharded.sharded_combine(vals, idx, plan, axis_name, valid=valid))
    dense = (dense_u[:flat.shape[0]] / world).astype(flat.dtype)
    new_ef = None
    if want_ef:
        # mul keeps the padded tail slots (idx 0, factor 1) identities,
        # exactly like the allgather path's EF
        new_ef = flat.at[idx].mul(jnp.where(sent, 0.0, 1.0))
    cap_overflow = jnp.maximum(count - cap, 0)
    sent_transported = jnp.sum(sent, dtype=jnp.int32)
    return (dense, new_ef, sent_transported, route_bits + ret_bits,
            route_bits, cap_overflow, overflow)


def _leaf_sync_topk_hier(flat: Array, keep: int, axis_name: str, world,
                         cfg, want_ef: bool, t=None):
    """Element Top-K over the hierarchical transport: the flat transports'
    exact selection, scattered dense and handed to :func:`_hier_combine`.
    EF is the base residual (everything unselected) plus the combine's
    exact clip refunds."""
    from tpu_compressed_dp.ops import kernels

    mag = jnp.abs(flat).astype(jnp.float32)
    if t is None:
        t = kernels.topk_threshold(mag, keep)
    vals, idx, count = _select_pack(flat, mag, t, keep)
    contrib = jnp.zeros(flat.shape, flat.dtype).at[idx].set(
        vals, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds")
    total, ef_extra, b_ici, b_rt, b_ret, overflow = _hier_combine(
        contrib, keep, axis_name, world, cfg)
    dense = (total / world).astype(flat.dtype)
    new_ef = (flat - contrib + ef_extra) if want_ef else None
    surplus = None if want_ef else jnp.maximum(count - keep, 0)
    return dense, new_ef, (b_ici, b_rt, b_ret), overflow, surplus


def _leaf_sync_blocktopk_hier(flat: Array, keep_blocks: int, block_size: int,
                              axis_name: str, world, cfg, want_ef: bool):
    """Block-Top-K over the hierarchical transport: selected blocks scatter
    dense, and the pod-reduced gradient recompresses element-granular (the
    inter-pod exchange is the pod UNION's nonzeros, not block rows)."""
    from tpu_compressed_dp.ops import kernels

    n = flat.shape[0]
    scores = compressors.blocktopk_scores(flat, block_size)
    t = kernels.topk_threshold(scores, keep_blocks)
    # scores are non-negative, so they serve as their own magnitudes
    bidx = _select_pack(scores, scores, t, keep_blocks)[1]
    g2 = compressors.blocktopk_blocks(flat, block_size)     # [nb, bs]
    payload = _sorted_gather(g2, bidx)                      # [kb, bs]
    contrib = jnp.zeros(g2.shape, flat.dtype).at[bidx].set(
        payload, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds").reshape(-1)[:n]
    total, ef_extra, b_ici, b_rt, b_ret, overflow = _hier_combine(
        contrib, min(keep_blocks * block_size, n), axis_name, world, cfg)
    dense = (total / world).astype(flat.dtype)
    new_ef = (flat - contrib + ef_extra) if want_ef else None
    return dense, new_ef, (b_ici, b_rt, b_ret), overflow


def _leaf_sync_threshold_hier(flat: Array, v, cap: int, axis_name: str,
                              world, cfg, want_ef: bool):
    """Threshold-V fixed-capacity buffer over the hierarchical transport.
    The cap clip (survivors beyond ``wire_cap_ratio``) stays a selection
    matter — it never enters ``contrib`` so it lands in the base residual;
    transport clips refund through :func:`_hier_combine`."""
    mag = jnp.abs(flat)
    vals, idx, count = _select_pack(flat, mag, v, cap)
    sent_count = jnp.minimum(count, cap)
    rank = jnp.arange(1, cap + 1, dtype=jnp.int32)
    valid = rank <= sent_count
    vals = jnp.where(valid, vals, 0.0)
    idx = jnp.where(valid, idx, 0)
    # add, not set: the zero-padded tail slots all alias coordinate 0 and
    # must not clobber a genuinely selected value there
    contrib = jnp.zeros(flat.shape, flat.dtype).at[idx].add(vals)
    total, ef_extra, b_ici, b_rt, b_ret, overflow = _hier_combine(
        contrib, cap, axis_name, world, cfg)
    dense = (total / world).astype(flat.dtype)
    new_ef = (flat - contrib + ef_extra) if want_ef else None
    cap_overflow = jnp.maximum(count - cap, 0)
    return (dense, new_ef, sent_count, (b_ici, b_rt, b_ret), cap_overflow,
            overflow)


def _leaf_sync_terngrad(flat: Array, key: Array, chunk: int, axis_name: str,
                        world):
    from tpu_compressed_dp.ops import kernels

    n = flat.shape[0]
    if kernels.use_quant_pack(n):
        # fused quantize+pack: dither and 2-bit wire bytes in one kernel
        # pass, no materialised int8 level vector (bitwise-identical bytes)
        if compressors.terngrad_num_chunks(n, chunk) == 1:
            packed, scale = kernels.terngrad_pack(flat, key)
        else:
            scaled, scale = compressors.terngrad_prescale(flat, chunk)
            packed = kernels.terngrad_pack_prescaled(scaled, key)
    else:
        levels, scale = compressors.terngrad_levels(flat, key, chunk=chunk)
        packed = pack_ternary(levels)                     # uint8[ceil(n/4)]
    bits = _payload_bits(packed, scale)
    g_packed = _all_gather(packed, axis_name)             # [W, ceil(n/4)]
    g_scale = _all_gather(scale, axis_name)               # [W] or [W, nc]
    g_levels = unpack_ternary(g_packed, n)                # [W, n] int8
    if scale.ndim == 0:
        dense = jnp.sum(
            g_scale[:, None] * g_levels.astype(flat.dtype), axis=0) / world
        return dense, bits
    # chunked scales: broadcast each worker's [nc] scales over its chunks
    nc = scale.shape[0]
    pad = nc * chunk - n
    lv = jnp.pad(g_levels, ((0, 0), (0, pad))).reshape(-1, nc, chunk)
    dense = jnp.sum(
        g_scale[:, :, None] * lv.astype(flat.dtype), axis=0
    ).reshape(-1)[:n] / world
    return dense, bits


def _leaf_sync_qsgd(flat: Array, key: Array, qstates: int, axis_name: str, world):
    from tpu_compressed_dp.ops import kernels

    n = flat.shape[0]
    if 127 < qstates <= 255 and kernels.use_quant_pack(n):
        # fused quantize+pack emits the byte-magnitude + packed-sign wire
        # format directly (the qstates <= 255 branch of qsgd_wire_pack)
        mags, signs, scale = kernels.qsgd_pack(flat, key, qstates=qstates)
        payload = (mags, signs)
    else:
        levels, scale = compressors.qsgd_levels(flat, key, qstates=qstates)
        payload = qsgd_wire_pack(levels, qstates)
    bits = _payload_bits(*payload, scale)
    g_payload = tuple(_all_gather(p, axis_name) for p in payload)
    g_scale = _all_gather(scale, axis_name)               # [W]
    g_levels = qsgd_wire_unpack(g_payload, n, qstates, dtype=flat.dtype)
    dense = jnp.sum(g_scale[:, None] * g_levels, axis=0) / world
    return dense, bits


def chain_parts(leaves, groups, method: str, cfg) -> Dict[tuple, list]:
    """Partition reduction ``groups`` (lists of leaf positions) into the
    parts the wire sync traces one chain each for: ``{(flat size, dtype,
    transport): [group index, ...]}`` in order of first appearance.  Groups
    that share a flat size, a dtype and a transport sync together, so a
    model's many equal-sized leaves cost one chain's scalar and small-array
    device operations, not one chain's each.  ``leaves`` need only ``size``
    and ``dtype`` (abstract trees do)."""
    from tpu_compressed_dp.parallel.dp import wire_transport

    parts: Dict[tuple, list] = {}
    for gi, idxs in enumerate(groups):
        n = sum(leaves[i].size for i in idxs)
        dtype = jnp.result_type(*[leaves[i].dtype for i in idxs])
        parts.setdefault((n, dtype, wire_transport(method, n, cfg)),
                         []).append(gi)
    return parts


def _stacks(n: int, transport: str) -> bool:
    """Whether a part's members sync as ONE stack (``jax.vmap`` of the
    per-group chain over ``[m, n]``), or each on its own buffer with only the
    threshold search shared.  Short members stack: the copy is a few KB and
    every operation of the chain launches once for all of them.  Long ones
    do not: on the TPU a ``[m, n]`` stack of few long rows shares each
    (8, 128) tile among the members, so building it, slicing it and sorting
    along it move strided tiles (my chip run, PR 26: the stacked form of
    ResNet-50's 42 long leaves cost 8 ms a step in copies and 2.8 ms in
    sorts, against 3 ms saved).  The owner-sharded and hierarchical
    exchanges never stack: ``all_to_all(axis_index_groups=...)`` has no
    batching rule, and `fused_bucket_route` has no member axis."""
    from tpu_compressed_dp.ops import kernels

    return (n < kernels.MIN_PALLAS_ELEMS
            and transport not in ("sharded", "hierarchical"))


def make_wire_grad_sync(cfg, axis_name: str = "data", *,
                        group_offset: int = 0):
    """Build ``sync(grads, ef, key) -> (synced, new_ef, comm_stats)``.

    Same contract as the simulate-mode sync in
    :func:`tpu_compressed_dp.parallel.dp.make_grad_sync` (which dispatches
    here for ``mode='wire'`` and adapts this 3-tuple to its stateful
    4-tuple — every wire method is stateless, so the compressor state
    passes through untouched); must run inside ``shard_map`` over
    ``axis_name``.

    ``group_offset`` shifts the per-group RNG derivation to the chunk's
    global group indices when the overlap driver
    (:mod:`tpu_compressed_dp.parallel.overlap`) syncs a slice of the tree,
    so chunked and whole-tree syncs draw identical randomness per group.
    """
    from tpu_compressed_dp.parallel.dp import wire_transport

    comp = compressors.get_compressor(
        cfg.method, ratio=cfg.ratio, threshold=cfg.threshold,
        qstates=cfg.qstates, block_size=cfg.block_size,
        terngrad_chunk=cfg.resolved_terngrad_chunk,
    )
    if comp.name not in WIRE_METHODS:
        raise NotImplementedError(
            f"mode='wire' supports {WIRE_METHODS}, got {comp.name!r}"
        )
    if comp.name == "randomk" and not cfg.resolved_shared_mask:
        raise ValueError(
            "wire randomk needs shared_mask=True so worker index sets line up "
            "(the shared-seed trick, sparsified_ddp.py:164)"
        )
    if cfg.error_feedback and comp.name in ("terngrad", "qsgd"):
        raise ValueError(
            "error feedback composes with sparsifiers (topk/randomk); "
            "terngrad/qsgd are unbiased quantizers with no dropped coordinates"
        )

    # Quantizer dither may (and, for variance reduction, should) differ across
    # workers: honour shared_mask=False the same way simulate mode does.
    # Random-K requires a shared key (checked above); Top-K uses no RNG.
    per_worker_rng = (not cfg.resolved_shared_mask) and comp.needs_rng

    def leaf_keep(n: int) -> int:
        if comp.name == "topk":
            return compressors.topk_keep_count(n, cfg.ratio)
        if comp.name == "randomk":
            return compressors.randomk_keep_count(n, cfg.ratio)
        if comp.name in ("thresholdv", "adaptive_threshold"):
            # fixed transport capacity for the data-dependent survivor count
            return max(1, int(round(cfg.wire_cap_ratio * n)))
        if comp.name == "blocktopk":
            # whole blocks travel, pad zeros included — honest wire size;
            # capped at n: when every block is kept (small leaves round up
            # to >= 1 block) the leaf psums dense instead, with no payload
            # inflation from block padding
            kb = compressors.blocktopk_keep_blocks(n, cfg.ratio, cfg.block_size)
            return min(kb * cfg.block_size, n)
        return n  # quantizers transmit every coordinate (at reduced width)

    check = getattr(cfg, "check_sync", False)

    def sync_flat(acc: Array, want_ef: bool, key: Array, world, t=None):
        """One group's accumulated gradient (the residual already added
        when ``want_ef``) -> its synced mean and its new residual; ``t`` is
        its Top-K threshold where the caller already has it.  Returns
        ``(dense, new_ef, sent, bits, bits_route, agree, overflows,
        fabric)``; ``sent`` may be dynamic (threshold methods),
        the rest of the accounting is static.  ``bits`` is MEASURED from
        the payload arrays each leaf sync actually hands its collective —
        never an analytic per-element model; ``bits_route`` is the
        all_to_all share of ``bits`` (sharded transport only, else 0).
        ``overflows`` maps comm-stat keys to clip counts.  ``fabric`` is
        None except for hierarchical groups, where it is the per-fabric
        split ``(ici_bits, dcn_route_bits, dcn_return_bits)`` summing to
        ``bits`` (the flat collective-kind buckets stay whole-world-only —
        hierarchical bits bill per fabric instead)."""
        n = acc.shape[0]
        if n > (1 << 31) - 1 and comp.name not in ("terngrad", "qsgd"):
            # the packed index pipeline is int32 throughout (32-bit indices
            # ARE the wire format); groups beyond int32 must be cut smaller
            raise ValueError(
                f"wire-mode {comp.name} group of {n} elements exceeds int32 "
                "index range; use granularity='bucketed' (25 MB buckets) or "
                "'layerwise' for models this large")
        keep = leaf_keep(n)
        agree = None
        idx = None
        # W=1 has no cross-worker duplicates to owner-reduce (and the route
        # collective would be a copy): the allgather combine is the same
        # arithmetic with less machinery, so sharded AND hierarchical
        # degrade to it.
        transport = wire_transport(comp.name, n, cfg)
        sharded = transport == "sharded" and world > 1
        hier = transport == "hierarchical" and world > 1
        if comp.name in ("thresholdv", "adaptive_threshold"):
            v = (cfg.threshold if comp.name == "thresholdv"
                 else jnp.max(jnp.abs(acc)) * 0.5)
            if hier:
                (dense, new_ef, sent_count, fabric, cap_overflow,
                 shard_overflow) = _leaf_sync_threshold_hier(
                    acc, v, keep, axis_name, world, cfg, want_ef)
                return (dense, new_ef, sent_count.astype(jnp.float32),
                        sum(fabric), 0.0, agree,
                        {"threshold_overflow": cap_overflow,
                         "shard_overflow": shard_overflow}, fabric)
            if sharded:
                (dense, new_ef, sent_count, bits, bits_route, cap_overflow,
                 shard_overflow) = _leaf_sync_threshold_sharded(
                    acc, v, keep, axis_name, world, cfg, want_ef)
                return (dense, new_ef, sent_count.astype(jnp.float32), bits,
                        bits_route, agree,
                        {"threshold_overflow": cap_overflow,
                         "shard_overflow": shard_overflow}, None)
            dense, new_ef, sent_count, overflow, bits = _leaf_sync_threshold(
                acc, v, keep, axis_name, world, want_ef)
            # transport is the full cap-sized buffer even when half-empty
            return (dense, new_ef, sent_count.astype(jnp.float32),
                    bits, 0.0, agree, {"threshold_overflow": overflow}, None)
        if comp.name == "randomk":
            dense, idx, agree, bits = _leaf_sync_randomk(
                acc, key, keep, axis_name, world, check)
        elif comp.name == "topk":
            if hier:
                dense, new_ef, fabric, overflow, surplus = (
                    _leaf_sync_topk_hier(acc, keep, axis_name, world, cfg,
                                         want_ef, t))
                ovf = {"shard_overflow": overflow}
                if surplus is not None:
                    ovf["topk_surplus_dropped"] = surplus
                return (dense, new_ef, float(keep), sum(fabric), 0.0, agree,
                        ovf, fabric)
            if sharded:
                (dense, new_ef, sent_count, bits, bits_route, overflow,
                 surplus) = _leaf_sync_topk_sharded(
                    acc, keep, axis_name, world, cfg, want_ef, t)
                ovf = {"shard_overflow": overflow}
                if surplus is not None:
                    ovf["topk_surplus_dropped"] = surplus
                return (dense, new_ef, sent_count.astype(jnp.float32), bits,
                        bits_route, agree, ovf, None)
            dense, new_ef, bits, count = _leaf_sync_topk(
                acc, keep, axis_name, world, want_ef, t)
            ovf = {"topk_underfull": count < keep}
            if not want_ef:
                # with EF on the surplus is reabsorbed by the residual; with
                # EF off it is a real (silent) drop — count and report it
                # (ADVICE r2)
                ovf["topk_surplus_dropped"] = jnp.maximum(count - keep, 0)
            return dense, new_ef, float(keep), bits, 0.0, agree, ovf, None
        elif comp.name == "blocktopk":
            if keep >= n:
                # every block selected (leaves <= block_size always are, and
                # ratio~1 configs): identical to simulate mode's keep-all
                # result, and a dense psum is strictly cheaper than padded
                # block rows — matches the reference protocol of never
                # sending more than the dense tensor
                dense = jax.lax.psum(acc, axis_name) / world
                bits = _payload_bits(acc)
                new_ef = jnp.zeros_like(acc) if want_ef else None
            elif hier:
                dense, new_ef, fabric, overflow = _leaf_sync_blocktopk_hier(
                    acc, keep // cfg.block_size, cfg.block_size, axis_name,
                    world, cfg, want_ef)
                return (dense, new_ef, float(keep), sum(fabric), 0.0, agree,
                        {"shard_overflow": overflow}, fabric)
            elif sharded:
                dense, new_ef, sent_count, bits, bits_route, overflow = (
                    _leaf_sync_blocktopk_sharded(
                        acc, keep // cfg.block_size, cfg.block_size,
                        axis_name, world, cfg, want_ef))
                return (dense, new_ef, sent_count.astype(jnp.float32), bits,
                        bits_route, agree, {"shard_overflow": overflow}, None)
            else:
                dense, new_ef, bits = _leaf_sync_blocktopk(
                    acc, keep // cfg.block_size, cfg.block_size, axis_name,
                    world, want_ef)
            return dense, new_ef, float(keep), bits, 0.0, agree, {}, None
        elif comp.name == "terngrad":
            dense, bits = _leaf_sync_terngrad(
                acc, key, cfg.resolved_terngrad_chunk, axis_name, world)
        else:  # qsgd
            dense, bits = _leaf_sync_qsgd(acc, key, cfg.qstates, axis_name, world)
        # What falls through: Random-K and the quantizers.  EF with
        # quantizers is rejected at build time, so want_ef implies Random-K,
        # whose indices follow no threshold: its residual zeroes the sent
        # coordinates by a scatter (in place of building a dense local
        # reconstruction: a full scatter + elementwise pass at model scale).
        # Its idx is ascending-unique whatever the gradient holds (the mask
        # comes from the key with exactly `keep` set bits, `randomk_mask`),
        # so the hints are truthful.
        new_ef = (acc.at[idx].set(0, indices_are_sorted=True,
                                  unique_indices=True,
                                  mode="promise_in_bounds")
                  if want_ef else None)
        return dense, new_ef, float(keep), bits, 0.0, agree, {}, None

    def sync(grads: Any, ef: Any, key: Array) -> Tuple[Any, Any, Dict[str, Array]]:
        from tpu_compressed_dp.obs import trace as obs_trace
        from tpu_compressed_dp.ops import kernels
        from tpu_compressed_dp.parallel.dp import (
            BUCKET_MB, group_concat, group_split, make_leaf_groups,
        )

        world = jax.lax.psum(1, axis_name)
        use_ef = cfg.error_feedback
        leaves, treedef = jax.tree.flatten(grads)
        acc_leaves = leaves
        if use_ef:
            # the residual joins each gradient in the leaf's own shape,
            # before any flattening or stacking: a flattened or stacked copy
            # of the residual alone depends on no gradient, and the
            # scheduler would hold it from the step's start through the
            # backward pass
            with obs_trace.phase("ef"):
                acc_leaves = [g + e for g, e in zip(leaves, jax.tree.leaves(ef))]

        # One packed payload per reduction group (layerwise / entiremodel /
        # 25MB-bucketed — the same static grouping as simulate mode,
        # parallel/dp.py:make_leaf_groups) ...
        groups = make_leaf_groups(
            [g.size * g.dtype.itemsize for g in leaves],
            cfg.granularity, cfg.bucket_mb * BUCKET_MB)
        # ... and one traced chain per PART of groups that can share one
        parts = chain_parts(acc_leaves, groups, comp.name, cfg)
        out_leaves = [None] * len(leaves)
        new_ef_leaves = [None] * len(leaves)
        agrees = []
        # per-kind clip counters: threshold_overflow (capacity vs survivor
        # count), topk_surplus_dropped (EF-off tie surplus), topk_underfull
        # (allgather Top-K groups with fewer survivors than keep),
        # shard_overflow (sharded-transport route/return clips) — a leaf may
        # report several
        overflows: Dict[str, list] = {}
        sent = 0.0
        bits = 0.0
        bits_psum = 0.0
        bits_ag = 0.0
        bits_a2a = 0.0
        bits_ici = 0.0
        bits_dcn = 0.0
        bits_dcn_route = 0.0
        dense_total = 0.0

        for (n, _, transport), members in parts.items():
            m = len(members)
            accs = [group_concat(acc_leaves, groups[gi]) for gi in members]
            keys = [compressors.leaf_key(key, gi + group_offset,
                                         per_worker_rng, axis_name)
                    for gi in members]
            # what `sync_flat` knows at trace time (payload bits, a fixed
            # keep) is the same for every member: kept apart from the
            # results that are arrays
            static = {}

            def chain(acc, ki, t=None):
                (dense, new_ef_flat, sent_leaf, static["bits"],
                 static["bits_route"], agree, leaf_overflows,
                 static["fabric"]) = sync_flat(acc, use_ef, ki, world, t)
                static["sent"] = sent_leaf
                if isinstance(sent_leaf, float):
                    sent_leaf = None
                return dense, new_ef_flat, (sent_leaf, agree, leaf_overflows)

            # one scope over the whole wire sync of the part (select + pack
            # + combine): the sharded transport's route/reduce/return scopes
            # nest inside (xprof shows tcdp.compress/tcdp.route etc.), and
            # the allgather combine's collectives split out by op name
            with obs_trace.phase("compress"):
                # Top-K thresholds come from each member's own buffer: the
                # searches of long members advance in one loop, the exact
                # sorts of short ones stay one a member (the TPU sorts a
                # stack of few rows several times slower than the rows)
                ts = None
                if comp.name == "topk":
                    ts = kernels.topk_thresholds(
                        [jnp.abs(a).astype(jnp.float32) for a in accs],
                        leaf_keep(n))
                if _stacks(n, transport):
                    dense, new_ef_flat, counts = jax.vmap(chain)(
                        jnp.stack(accs), jnp.stack(keys),
                        None if ts is None else jnp.stack(ts))
                else:
                    dense, new_ef_flat, counts = zip(*map(
                        chain, accs, keys, ts or [None] * m))
                    counts = jax.tree.map(lambda *xs: jnp.stack(xs), *counts)
            sent_dyn, agree, leaf_overflows = counts
            bits_leaf = m * static["bits"]
            bits_route = m * static["bits_route"]
            # which collective(s) this part's payloads actually rode
            # (VERDICT r2 #2) — shared classifier with the simulate engine.
            # A sharded group splits: route bits ride the all_to_all, the
            # shard return rides an all_gather.  A hierarchical group bills
            # per FABRIC instead — the flat collective-kind buckets stay
            # whole-world-only so their traffic arithmetic needs no
            # topology caveats.
            if static["fabric"] is not None:
                f_ici, f_rt, f_ret = static["fabric"]
                bits_ici += m * f_ici
                bits_dcn += m * (f_rt + f_ret)
                bits_dcn_route += m * f_rt
            elif transport == "psum":
                bits_psum += bits_leaf
            elif transport == "sharded" and world > 1:
                bits_a2a += bits_route
                bits_ag += bits_leaf - bits_route
            else:
                bits_ag += bits_leaf
            with obs_trace.phase("return"):
                for j, gi in enumerate(members):
                    group_split(dense[j], leaves, groups[gi], out_leaves)
                    if use_ef:
                        # EF residual is fp32 by design (see group_split
                        # docstring)
                        group_split(new_ef_flat[j], leaves, groups[gi],
                                    new_ef_leaves, dtype=jnp.float32)
            if agree is not None:
                agrees.append(agree)
            for k, v in leaf_overflows.items():
                overflows.setdefault(k, []).append(v)
            # dynamic for threshold methods
            sent = sent + (m * static["sent"] if sent_dyn is None
                           else jnp.sum(sent_dyn))
            bits += bits_leaf
            dense_total += float(m * n)

        stats = {
            "sent_elems": jnp.asarray(sent, jnp.float32),
            "sent_bits": jnp.asarray(bits, jnp.float32),
            "sent_bits_psum": jnp.asarray(bits_psum, jnp.float32),
            "sent_bits_allgather": jnp.asarray(bits_ag, jnp.float32),
            "sent_bits_alltoall": jnp.asarray(bits_a2a, jnp.float32),
            "sent_bits_ici": jnp.asarray(bits_ici, jnp.float32),
            "sent_bits_dcn": jnp.asarray(bits_dcn, jnp.float32),
            "sent_bits_dcn_route": jnp.asarray(bits_dcn_route, jnp.float32),
            "dense_elems": jnp.asarray(dense_total, jnp.float32),
            "num_collectives": jnp.asarray(float(len(groups)), jnp.float32),
            "sync_chains": jnp.asarray(float(len(parts)), jnp.float32),
        }
        if agrees:
            stats["sync_agree"] = jnp.min(jnp.concatenate(agrees))
        for k, vs in overflows.items():
            # threshold_overflow: survivors clipped by the fixed capacity
            # (0 = cap was enough).  topk_surplus_dropped: above-threshold
            # survivors beyond keep, truncated by ascending index (ADVICE
            # r2).  topk_underfull: groups whose threshold left fewer
            # survivors than keep (0 on finite gradients; else the residual
            # zeroed every survivor and the payload is padded).
            # shard_overflow: coordinates clipped by the sharded
            # transport's route/return capacities (EF reabsorbs them when
            # on; this worker's route clips + this owner's return clips).
            stats[k] = jnp.sum(jnp.concatenate(vs)).astype(jnp.float32)
        out = jax.tree.unflatten(treedef, out_leaves)
        new_ef = jax.tree.unflatten(treedef, new_ef_leaves) if use_ef else ()
        return out, new_ef, stats

    return sync
