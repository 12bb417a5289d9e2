"""Compressed data-parallel gradient synchronisation.

TPU-native re-design of the reference's three DP flavours (SURVEY.md §2.2):

  * dense per-layer allreduce loop            -> `method=None`
  * ``layerwise_compressed_comm``             -> ``granularity='layerwise'``
    (`CIFAR10/core.py:175-225`)
  * ``entiremodel_compressed_comm``           -> ``granularity='entiremodel'``
    (`CIFAR10/core.py:227-301`; the reference copy crashes if called —
    SURVEY.md §2.3 — ours works)
  * ``RandomKSparsifiedDDP`` error feedback   -> ``error_feedback=True``
    (`IMAGENET/training/sparsified_ddp.py:222,408-413`)

Instead of per-parameter autograd hooks driving NCCL buckets from C++
(`ddp.py:394-409`), the whole pipeline — compress, reduce, average — is traced
into the jitted train step under ``shard_map``; XLA's latency-hiding scheduler
overlaps the psums with remaining backward compute, which is what the
reference's reverse-order bucketing bought it by hand.

Two payload modes (SURVEY.md §2.3 item 6):

  * ``mode='simulate'`` — the paper's protocol: the compressed gradient is kept
    dense (zeros at dropped coordinates) and allreduced full-size.  Studies
    convergence, not bandwidth; bytes-on-wire are *accounted analytically*.
  * ``mode='wire'`` — genuinely sparse payloads (packed k values; see
    :mod:`tpu_compressed_dp.ops.wire`), the `RandomKSparsifiedDDP` equivalent.

Stateful compressors: every sync is ``sync(grads, ef, comp, key[, ok]) ->
(synced, new_ef, new_comp, stats)`` — ``comp`` is a persistent compressor
state pytree threaded through the jitted step alongside the EF residual
(``()`` for the stateless element-wise methods).

Step guard (``ok``): the optional keyword is the globally-voted finiteness
verdict from :mod:`tpu_compressed_dp.train.guard`.  When given, BOTH engines
(element-wise/wire and PowerSGD) gate themselves: local gradients are zeroed
on a bad step (every downstream collective stays finite — the wire scatter
paths have a documented finite-input precondition) and, critically, the
persistent EF residual and compressor state are held bitwise at their
pre-step values — a single poisoned gradient must not enter state that
replays across every future step.  The stats gain ``guard/nonfinite``
(1.0 = this step was vetoed).  ``ok=None`` (the default) is the exact
pre-guard behaviour.  The first occupant is
PowerSGD (``method='powersgd'``, :mod:`tpu_compressed_dp.ops.lowrank`),
whose warm-start ``Q`` factors live in ``TrainState.comp``, are sharded
like ``ef``, and round-trip through Orbax checkpoints; its payloads are
linear in the gradient, so it is the one compressor family whose wire form
always rides the psum ring rather than an all_gather.  Build the state
with :func:`init_comp_state`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_compressed_dp.obs import registry as obs_registry
from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.ops import compressors, kernels

__all__ = ["CompressionConfig", "make_grad_sync", "make_grouped_grad_sync",
           "make_leaf_groups", "group_concat", "group_split", "init_ef_state",
           "init_comp_state", "init_comp_state_partitioned",
           "init_comp_state_grouped", "make_sharded_clip", "merge_stat_dicts",
           "wire_rides_psum", "wire_transport"]


def wire_transport(name: str, n: int, cfg: "CompressionConfig") -> str:
    """Which collective the method's WIRE form rides for an ``n``-element
    group (VERDICT r2 #2): ``'psum'`` | ``'allgather'`` | ``'sharded'`` |
    ``'hierarchical'`` — the single source of truth for the
    ``sent_bits_psum`` / ``sent_bits_allgather`` / ``sent_bits_alltoall``
    (and, hierarchical, the per-fabric ``sent_bits_ici`` /
    ``sent_bits_dcn``) split in BOTH sync engines.

    Dense and SHARED-seed Random-K psum-reduce a (packed) buffer — per-chip
    ring traffic ``2(W-1)/W x payload``; PowerSGD's P/Q factors are linear
    in the gradient and always psum; Block-Top-K keep-all groups fall back
    to a dense psum.  Every other method's payloads are worker-distinct
    (indices or quantizer scales differ): by default they ride an
    all_gather — per-chip traffic ``~(W-1) x payload``, i.e. ``O(W*k)``.
    ``cfg.transport='sharded'`` moves the index-carrying sparsifiers
    (:data:`~tpu_compressed_dp.ops.wire_sharded.SHARDED_METHODS`) onto the
    owner-sharded reduce instead: all_to_all route (``(W-1)/W x``) plus a
    shard-return all_gather — ``O(k + n/W)`` per chip.  Quantizers carry no
    indices to route and keep the all_gather regardless.  Per-rank-mask
    Random-K (simulate default, the unseeded CIFAR harness) ships
    worker-distinct indices too — all_gather, matching its own 64-bit
    accounting.  ``cfg.transport='hierarchical'`` applies to the same
    index-carrying sparsifiers: dense psum inside each ``dp_chips``-wide
    pod (ICI), re-compress the pod union, (value, index) exchange across
    the ``dp_pods`` axis (DCN) — per-chip DCN volume ``O(k + n/W_pods)``.
    """
    if name == "none" or (name == "randomk" and cfg.resolved_shared_mask):
        return "psum"
    if name == "powersgd":
        return "psum"
    if name == "blocktopk":
        kb = compressors.blocktopk_keep_blocks(n, cfg.ratio, cfg.block_size)
        if kb * cfg.block_size >= n:
            return "psum"
    if cfg.transport in ("sharded", "hierarchical"):
        from tpu_compressed_dp.ops.wire_sharded import SHARDED_METHODS

        if name in SHARDED_METHODS:
            return cfg.transport
    return "allgather"


def wire_rides_psum(name: str, n: int, cfg: "CompressionConfig") -> bool:
    """Back-compat predicate over :func:`wire_transport`."""
    return wire_transport(name, n, cfg) == "psum"


def _sharded_group_bits(name: str, n: int, world: int,
                        cfg: "CompressionConfig"):
    """Analytic ``(route_bits, return_bits)`` of the sharded wire form for
    an ``n``-element group — the per-method unit geometry feeding
    :func:`~tpu_compressed_dp.ops.wire_sharded.sharded_payload_bits` (whose
    result equals the wire engine's measured fp32 buffer bits, so simulate
    and wire accounting agree for the sharded transport too)."""
    from tpu_compressed_dp.ops import wire_sharded

    if name == "blocktopk":
        kb = compressors.blocktopk_keep_blocks(n, cfg.ratio, cfg.block_size)
        nb = -(-n // cfg.block_size)
        return wire_sharded.sharded_payload_bits(
            nb, kb, world, cfg.block_size,
            cfg.shard_route_factor, cfg.shard_return_factor)
    if name in ("thresholdv", "adaptive_threshold"):
        keep = max(1, int(round(cfg.wire_cap_ratio * n)))
    else:
        keep = compressors.topk_keep_count(n, cfg.ratio)
    return wire_sharded.sharded_payload_bits(
        n, keep, world, 1, cfg.shard_route_factor, cfg.shard_return_factor)


def _hier_group_bits(name: str, n: int, world: int,
                     cfg: "CompressionConfig"):
    """Analytic ``(ici_bits, dcn_route_bits, dcn_return_bits)`` of the
    hierarchical wire form for an ``n``-element group — feeds
    :func:`~tpu_compressed_dp.ops.wire_sharded.hier_payload_bits` (which
    equals the wire engine's measured fp32 buffer bits, keeping simulate
    and wire per-fabric accounting identical).  ``keep`` is element-granular
    here even for blocktopk: the pod union is packed per element, not per
    block."""
    from tpu_compressed_dp.ops import wire_sharded

    if name == "blocktopk":
        kb = compressors.blocktopk_keep_blocks(n, cfg.ratio, cfg.block_size)
        keep = min(kb * cfg.block_size, n)
    elif name in ("thresholdv", "adaptive_threshold"):
        keep = max(1, int(round(cfg.wire_cap_ratio * n)))
    else:
        keep = compressors.topk_keep_count(n, cfg.ratio)
    return wire_sharded.hier_payload_bits(
        n, keep, world, cfg.dp_pods,
        cfg.hier_route_factor_ici, cfg.hier_route_factor_dcn)


def make_partitioned_clip(leaf_axes):
    """Build ``clip_tree(tree, limit)`` clipping by the FULL-model L2 norm
    for gradient trees whose leaves are sharded over per-leaf model-axis
    subsets (``leaf_axes`` aligned with ``jax.tree.leaves`` order; ``()`` =
    replicated, already psum'd by shard_map AD, counts once).  Squared
    norms accumulate per signature and psum once per signature."""
    leaf_axes = [tuple(a) for a in leaf_axes]
    sigs = sorted(set(leaf_axes))

    def global_norm(tree):
        leaves = jax.tree.leaves(tree)
        total = jnp.zeros((), jnp.float32)
        for sig in sigs:
            sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                     for g, a in zip(leaves, leaf_axes) if a == sig)
            if sig:
                sq = jax.lax.psum(sq, sig)
            total = total + sq
        return jnp.sqrt(total)

    def clip_tree(tree, limit):
        factor = jnp.minimum(1.0, limit / jnp.maximum(global_norm(tree), 1e-20))
        return jax.tree.map(lambda g: g * factor, tree)

    return clip_tree


def make_sharded_clip(is_sharded, shard_axis):
    """Binary convenience wrapper over :func:`make_partitioned_clip`."""
    axes = (shard_axis,) if isinstance(shard_axis, str) else tuple(shard_axis)
    return make_partitioned_clip([axes if s else () for s in is_sharded])


# Stats that are 0/1 diagnostics, identical across ranks (or min/max
# verdicts), NOT additive volumes: the partitioned sync must not psum them
# over model axes or sum them across signature groups.  Maps key -> the
# (cross-rank collective, cross-group combiner) pair.  Derived from the
# metric registry's declared reductions (obs/registry.py) so the engine's
# diagnostic table can never silently disagree with the declarations the
# conformance test enforces.
_DIAG_COLLECTIVES = {
    "min": (jax.lax.pmin, jnp.minimum),
    "max": (jax.lax.pmax, jnp.maximum),
}
_DIAG_STATS = {
    key: _DIAG_COLLECTIVES[red]
    for key, red in obs_registry.engine_diag_reductions().items()
}


def merge_stat_dicts(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Combine two engine stat dicts from disjoint slices of one sync
    (signature groups in the partitioned wrapper, chunks in the overlap
    driver): additive volumes sum; min/max diagnostics (``sync_agree``,
    ``guard/nonfinite``) combine with their registry-declared reduction,
    and survive when EITHER side reports them — a slice of diagnostic-free
    groups must not silence the other slice's divergence signal."""
    # sorted: a set of strings iterates in hash order, which changes from
    # process to process, and the order these adds are traced in is part of
    # the program the persistent compile cache keys on
    merged = {
        k: a.get(k, 0.0) + b.get(k, 0.0)
        for k in sorted((set(a) | set(b)) - set(_DIAG_STATS))
    }
    for k, (_, combine) in _DIAG_STATS.items():
        vals = [c[k] for c in (a, b) if k in c]
        if vals:
            merged[k] = vals[0] if len(vals) == 1 else combine(*vals)
    return merged


def _with_guard(inner_sync):
    """Give a ``sync(grads, ef, comp, key)`` engine the optional step-guard
    gate (``ok`` = the globally-voted finiteness verdict,
    :func:`tpu_compressed_dp.train.guard.finite_vote`).

    On a vetoed step the engine's job is damage containment: the local
    gradients are replaced with zeros (so every collective — psum,
    all_gather, the sharded transport's scatter/all_to_all, whose index
    arithmetic has a documented finite-input precondition — computes on
    finite data), and the persistent EF residual and compressor state come
    back bitwise equal to their inputs instead of absorbing either the
    poison or the zeroed-gradient artifact (with EF on, a zero gradient
    would still rotate ``compress(ef)`` out of the residual).  The synced
    output is then compression noise the caller discards along with the
    whole update.
    """
    # lazy: a module-level `from tpu_compressed_dp.train.guard import ...`
    # would cycle (train/__init__ -> step -> this module); by factory time
    # everything is loaded
    from tpu_compressed_dp.train.guard import select_tree

    def sync(grads: Any, ef: Any, comp: Any, key: jax.Array,
             ok: Optional[jax.Array] = None):
        if ok is None:
            return inner_sync(grads, ef, comp, key)
        safe = jax.tree.map(lambda g: jnp.where(ok, g, jnp.zeros_like(g)),
                            grads)
        out, new_ef, new_comp, stats = inner_sync(safe, ef, comp, key)
        stats = dict(stats)
        stats["guard/nonfinite"] = (~ok).astype(jnp.float32)
        return out, select_tree(ok, new_ef, ef), \
            select_tree(ok, new_comp, comp), stats

    return sync


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Mirrors the reference CLI surface (`dawn.py:15-19`, `train_imagenet_nv.py`).

    method:        none | topk | blocktopk | randomk | thresholdv |
                   adaptive_threshold | terngrad | qsgd | powersgd
                   (reference spellings accepted; blocktopk is net-new —
                   contiguous-block Top-K by block L2 norm, the TPU-native
                   fast wire path, see :mod:`tpu_compressed_dp.ops.wire`;
                   powersgd is net-new too — warm-started rank-``rank``
                   low-rank factorisation whose P/Q payloads ride the psum
                   ring, see :mod:`tpu_compressed_dp.ops.lowrank`.  PowerSGD
                   is stateful: build ``TrainState.comp`` with
                   :func:`init_comp_state`)
    rank:          r for powersgd (default 4); per-group payload is
                   ``r·(m + n/m)`` fp32 words for an ``n``-element group
                   reshaped to ``(m, n/m)``, ``m ~ sqrt(n)``
    granularity:   'layerwise' (one op + one reduce per parameter tensor),
                   'entiremodel' (flatten the whole gradient, one op + reduce),
                   or 'bucketed' (contiguous parameter tensors concatenated
                   into <= bucket_mb groups, one op + reduce per bucket — the
                   reference DDP's 25 MB bucketing, `ddp.py:188,238-241`,
                   computed statically at trace time).  Recommendation for
                   layer-wise semantics at scale: 'bucketed' — single-chip
                   step time matches 'layerwise' (VGG-16: 42.3 vs 42.7 ms,
                   benchmarks/vgg16_bucketed_r2.tsv) while cutting the
                   collective count ~5x (32 -> 7 on VGG-16, 161 -> 5 on
                   ResNet-50), which is what matters once psums ride real
                   interconnect; 'entiremodel' pays extra whole-model
                   concat/split copies and is the slowest single-chip.
    bucket_mb:     bucket capacity for granularity='bucketed' (default 25,
                   matching the reference)
    mode:          'simulate' (dense payload, paper protocol) or 'wire'
                   (packed sparse payload)
    transport:     'allgather' (flat combine: every worker's (value, index)
                   pairs visit every chip, O(W*k) per chip) or 'sharded'
                   (owner-sharded sparse reduce, ops/wire_sharded.py:
                   all_to_all route to contiguous shard owners, owner
                   scatter-add, shard-return all_gather — O(k + n/W) per
                   chip).  Applies to the index-carrying sparsifiers
                   (topk/blocktopk/thresholdv/adaptive_threshold); psum
                   riders and the index-free quantizers are unaffected.
                   Capacity knobs: shard_route_factor/shard_return_factor
                   (x k/W slots); clips fold into EF / comm/shard_overflow.
    ratio:         K for topk/randomk (`--ratio`, default 0.5)
    threshold:     V for thresholdv (`--threshold`, default 1e-3)
    qstates:       quantisation states for qsgd (`--qstates`, default 255)
    error_feedback: keep the dropped residual and re-add next step
                   (`sparsified_ddp.py:408-413`); the reference only has this
                   in RandomKSparsifiedDDP — here it composes with any method.
                   NB: EF defers ~1/k steps of gradient mass per coordinate;
                   under momentum that delay diverges at high peak lr — for
                   the reference's own update rule too (torch repro in
                   tools/ef_bisect.py; results in
                   benchmarks/ef_momentum_bisect_r2.txt).  Stabilise with the
                   train step's ``clip_norm`` (DGC-style local-gradient
                   clipping) or momentum=0.
    shared_mask:   random masks identical across workers (shared-seed trick,
                   `sparsified_ddp.py:164`).  Defaults: False for 'simulate'
                   (the unseeded CIFAR harness draws per-rank masks), True is
                   required for 'wire' randomk so indices line up.
    check_sync:    debug guard (the ``check_reduction`` analog,
                   `ddp.py:312-327`): wire-mode Random-K verifies every
                   worker selected identical indices before the packed psum
                   (misalignment would silently corrupt gradients) and
                   reports ``comm/sync_agree`` (1.0 = agreement).
    """

    method: Optional[str] = None
    granularity: str = "layerwise"
    mode: str = "simulate"
    # sync_overlap: decompose the gradient sync into up to this many
    # independent chunk syncs issued in reverse-topological order so XLA's
    # latency-hiding scheduler can interleave each chunk's collective with
    # the remaining backward (and, in train/step.py, the other chunks'
    # optimizer-update slices).  1 = the single-dispatch behaviour; K > 1
    # routes through parallel/overlap.py.  Chunk boundaries always align
    # with the granularity's reduction-group boundaries, so per-group
    # compression, RNG and transport are BITWISE unchanged — only the
    # dependency/schedule structure differs (tests/test_overlap.py pins
    # this).  Evidence: tools/overlap_evidence.py / benchmarks/.
    sync_overlap: int = 1
    # transport: which collective carries index-carrying wire payloads.
    # 'allgather' — every worker's (value, index) pairs visit every chip:
    # per-chip volume/decode O(W*k), fine at small W.  'sharded' — the
    # owner-sharded sparse reduce (ops/wire_sharded.py): pairs route to
    # contiguous shard owners via all_to_all, owners reduce, shards return
    # via one all_gather — O(k + n/W) per chip, the scalable regime
    # (OKTopk, PAPERS.md).  'hierarchical' — two-level reduce over the
    # dp_pods x dp_chips virtual mesh (below): dense psum along the fast
    # intra-pod ICI axis, sparse (value, index) exchange across the slow
    # DCN axis only — per-chip DCN volume O(k + n/W_pods), billed per
    # fabric (sent_bits_ici / sent_bits_dcn).  Both apply to topk/
    # blocktopk/thresholdv/adaptive_threshold; psum-riding methods and the
    # index-free quantizers are unaffected (see wire_transport).
    transport: str = "allgather"
    ratio: float = 0.5
    threshold: float = 1e-3
    qstates: int = 255
    # powersgd: rank of the low-rank approximation (r in Vogels et al.);
    # wire cost per group is r*(m + n/m) fp32 words, always on the psum ring
    rank: int = 4
    error_feedback: bool = False
    shared_mask: Optional[bool] = None
    check_sync: bool = False
    block_size: int = 256  # blocktopk: elements per contiguous block
    bucket_mb: float = 25.0  # bucketed: capacity per bucket (ddp.py:188)
    # wire thresholdv/adaptive_threshold: transport capacity as a fraction of
    # elements (survivor counts are data-dependent; the wire buffer is not).
    # Overflowing survivors stay in the EF residual (or are dropped, EF off);
    # comm/threshold_overflow reports the clip count.
    wire_cap_ratio: float = 0.05
    # sharded transport capacity factors, in units of the per-shard fair
    # share k/W.  Route: per-destination bucket = route_factor * k/W slots
    # (uniform-spread assumption; skew clips into EF / shard_overflow).
    # Return: sparse-union buffer = return_factor * k/W units (worker
    # selections overlap — the premise compression rests on; the buffer is
    # clamped to its lossless bound W*cap_dest and to the shard size, and
    # the dense shard returns instead whenever that bills no bigger).
    shard_route_factor: float = 1.25
    shard_return_factor: float = 1.25
    # hierarchical transport: the W data-parallel workers form a virtual
    # dp_pods x dp_chips mesh (rank g -> pod g // dp_chips, chip g %
    # dp_chips; world must divide evenly, checked at trace time).  The
    # intra-pod ICI axis carries a dense psum of each worker's
    # compressed-dense contribution; the pod-reduced gradient is then
    # re-compressed (packed nonzero union, capacity hier_route_factor_ici
    # x keep, sliced one slab per chip) and only (value, index) pairs
    # cross the DCN axis via the sharded bucket-route machinery with
    # capacity factor hier_route_factor_dcn.  Clips on either hop refund
    # exactly into EF (comm/shard_overflow invariant).  dp_pods=1 keeps
    # the classifier/billing surface but degenerates to one dense ICI
    # psum (no DCN traffic at all).
    dp_pods: int = 1
    hier_route_factor_ici: float = 1.25
    hier_route_factor_dcn: float = 1.25
    # terngrad: elements per scale chunk (0 = single global max; -1 = auto).
    # A single max over an entire-model gradient drives keep-probabilities
    # toward zero and the estimator variance unbounded (the r2 NaN row); one
    # max per ~2M elements keeps entire-model granularity at layer-wise-like
    # statistics.  Auto resolves to 0 for layerwise (exact reference
    # per-tensor max semantics on EVERY leaf, LM embedding included — a fixed
    # 2M default silently diverged on >2M-element leaves, ADVICE r3) and to
    # 2M for entiremodel/bucketed, where the reference has no working
    # behavior to match (its path crashed, SURVEY.md §2.3.2).
    terngrad_chunk: int = -1

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")
        if self.sync_overlap < 1:
            raise ValueError(
                f"sync_overlap must be >= 1, got {self.sync_overlap} "
                "(1 = single-dispatch sync; K > 1 = chunk-pipelined)")
        if self.granularity not in ("layerwise", "entiremodel", "bucketed"):
            raise ValueError(
                f"granularity must be layerwise|entiremodel|bucketed, got {self.granularity!r}")
        if self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be positive, got {self.bucket_mb}")
        if self.mode not in ("simulate", "wire"):
            raise ValueError(f"mode must be simulate|wire, got {self.mode!r}")
        if self.transport not in ("allgather", "sharded", "hierarchical"):
            raise ValueError(
                "transport must be allgather|sharded|hierarchical, "
                f"got {self.transport!r}")
        if self.dp_pods < 1:
            raise ValueError(
                f"dp_pods must be >= 1, got {self.dp_pods} (the DCN axis of "
                "the virtual dp_pods x dp_chips mesh; world must divide "
                "evenly, checked when the mesh size is known)")
        if self.hier_route_factor_ici <= 0 or self.hier_route_factor_dcn <= 0:
            raise ValueError(
                "hier_route_factor_ici/hier_route_factor_dcn must be "
                f"positive, got {self.hier_route_factor_ici}/"
                f"{self.hier_route_factor_dcn} (they size the pod-union "
                "recompression and inter-pod route buffers)")
        if self.shard_route_factor <= 0 or self.shard_return_factor <= 0:
            raise ValueError(
                "shard_route_factor/shard_return_factor must be positive, "
                f"got {self.shard_route_factor}/{self.shard_return_factor} "
                "(they scale the fixed per-destination and return-union "
                "buffer capacities; 0 would allocate no transport at all)")
        if not (0.0 < self.wire_cap_ratio <= 1.0):
            raise ValueError(
                f"wire_cap_ratio must be in (0, 1], got {self.wire_cap_ratio} "
                "(0/negative would degrade to a 1-element transport buffer; "
                ">1 allocates a buffer larger than the tensor)")

    @property
    def resolved_shared_mask(self) -> bool:
        if self.shared_mask is not None:
            return self.shared_mask
        return self.mode == "wire"

    @property
    def resolved_terngrad_chunk(self) -> int:
        if self.terngrad_chunk >= 0:
            return self.terngrad_chunk
        return 0 if self.granularity == "layerwise" else 1 << 21


def init_ef_state(grads_like: Any, cfg: CompressionConfig, num_devices: Optional[int] = None) -> Any:
    """Zero error-feedback residual pytree (empty tuple when EF is off).

    The residual is per-worker state (the reference keeps one ``epsilon`` per
    rank, `sparsified_ddp.py:222-223`): pass ``num_devices`` to get leaves with
    a leading device axis, to be sharded over the data mesh axis.  Unlike the
    reference, this residual is part of the train state and hence checkpointed
    (SURVEY.md §5 checkpoint gap).
    """
    if not cfg.error_feedback:
        return ()
    if num_devices is None:
        # fp32 regardless of gradient dtype: sub-epsilon dropped mass must
        # accumulate across steps, not round away (see group_split)
        return jax.tree.map(
            lambda g: jnp.zeros(g.shape, dtype=jnp.float32), grads_like)
    return jax.tree.map(
        lambda g: jnp.zeros((num_devices,) + g.shape, dtype=jnp.float32), grads_like
    )


def init_comp_state(grads_like: Any, cfg: CompressionConfig,
                    num_devices: Optional[int] = None, *, seed: int = 0) -> Any:
    """Persistent compressor-state pytree (``()`` for stateless methods).

    PowerSGD: one fp32 warm-start ``Q`` of shape ``[n2, r]`` per compressed
    leaf group (the same static grouping the sync uses), keyed ``'q<gi>'``.
    Drawn from a fixed PRNG so every worker holds the IDENTICAL warm start —
    the P/Q psums average factors, which is only meaningful when all workers
    iterate in the same basis.  Dense-fallback groups (factors would cost >=
    the dense vector: biases, norm scales) carry no state.

    Like :func:`init_ef_state`, pass ``num_devices`` to get leaves with a
    leading device axis, sharded over the data mesh axis and checkpointed as
    ``TrainState.comp``.
    """
    if compressors.canonical_name(cfg.method) != "powersgd":
        return ()
    from tpu_compressed_dp.ops import lowrank

    leaves = jax.tree.leaves(grads_like)
    groups = make_leaf_groups(
        [g.size * g.dtype.itemsize for g in leaves],
        cfg.granularity, cfg.bucket_mb * BUCKET_MB)
    key = jax.random.key(seed)
    state = {}
    for gi, idxs in enumerate(groups):
        n = sum(leaves[i].size for i in idxs)
        q = lowrank.init_group_state(n, cfg.rank, jax.random.fold_in(key, gi))
        if q is None:
            continue
        if num_devices is not None:
            q = jnp.tile(q[None], (num_devices, 1, 1))
        state[f"q{gi}"] = q
    return state if state else ()


def init_comp_state_partitioned(grads_like: Any, cfg: CompressionConfig,
                                leaf_axes, num_devices: Optional[int] = None,
                                *, seed: int = 0) -> Any:
    """Compressor state for :func:`make_partitioned_grad_sync`: one
    :func:`init_comp_state` sub-pytree per replication signature, keyed
    ``'sig<i>'`` in the same sorted-signature order the partitioned sync
    iterates (``()`` when every signature is stateless)."""
    if compressors.canonical_name(cfg.method) != "powersgd":
        return ()
    leaf_axes = [tuple(a) for a in leaf_axes]
    sigs = sorted(set(leaf_axes))
    leaves = jax.tree.leaves(grads_like)
    state = {}
    for gi, sig in enumerate(sigs):
        sub = init_comp_state(
            [l for l, a in zip(leaves, leaf_axes) if a == sig], cfg,
            num_devices, seed=seed + gi)
        if sub != ():
            state[f"sig{gi}"] = sub
    return state if state else ()


def init_comp_state_grouped(grads_like: Any, cfg: CompressionConfig,
                            is_sharded, shard_axis,
                            num_devices: Optional[int] = None, *,
                            seed: int = 0) -> Any:
    """Binary convenience wrapper over :func:`init_comp_state_partitioned`
    (mirrors :func:`make_grouped_grad_sync`)."""
    axes = (shard_axis,) if isinstance(shard_axis, str) else tuple(shard_axis)
    return init_comp_state_partitioned(
        grads_like, cfg, [axes if s else () for s in is_sharded],
        num_devices, seed=seed)


# The reference's bucket unit is MiB: ``bucket_bytes_cap = bucket_cap_mb *
# 1024 * 1024`` (`ddp.py:182,188`).
BUCKET_MB = 1024.0 * 1024.0


def make_leaf_groups(byte_sizes, granularity: str, bucket_bytes: float):
    """Partition leaf indices into reduction groups, statically at trace time.

    'layerwise' -> one leaf per group (one collective per parameter,
    `core.py:176`); 'entiremodel' -> every leaf in one group (`core.py:229`);
    'bucketed' -> contiguous leaves greedily packed into <= ``bucket_bytes``
    groups by actual byte size (``size * dtype.itemsize``, like the reference
    DDP's ``_dist_bucket_tensors(..., 25MB)`` C++ bucketing,
    `ddp.py:188,238`); an oversized single leaf gets its own bucket.
    """
    n = len(byte_sizes)
    if granularity == "layerwise":
        return [[i] for i in range(n)]
    if granularity == "entiremodel":
        return [list(range(n))] if n else []
    groups, cur, cur_bytes = [], [], 0.0
    for i, b in enumerate(byte_sizes):
        if cur and cur_bytes + b > bucket_bytes:
            groups.append(cur)
            cur, cur_bytes = [], 0.0
        cur.append(i)
        cur_bytes += b
    if cur:
        groups.append(cur)
    return groups


def group_concat(leaves, idxs):
    """Flatten-and-concatenate a reduction group's leaves (single-leaf groups
    skip the concat)."""
    flats = [leaves[i].reshape(-1) for i in idxs]
    return flats[0] if len(flats) == 1 else jnp.concatenate(flats)


def group_split(flat, leaves, idxs, out, dtype=None):
    """Slice a group's flat result back into per-leaf shapes, writing into
    ``out`` at the original leaf positions.

    ``group_concat`` of a mixed-dtype group (bf16 + fp32 leaves) promotes to
    a common dtype; each output leaf is cast back to the corresponding input
    leaf's dtype — or to ``dtype`` when given (the EF residual is fp32 by
    design regardless of gradient precision: sub-epsilon dropped mass must
    accumulate, not round away)."""
    off = 0
    for i in idxs:
        n = leaves[i].size
        out[i] = (flat[off:off + n].reshape(leaves[i].shape)
                  .astype(dtype or leaves[i].dtype))
        off += n


def make_grad_sync(cfg: CompressionConfig, axis_name: str = "data", *,
                   group_offset: int = 0, chunking: bool = True):
    """Build ``sync(grads, ef, comp, key[, ok]) -> (synced, new_ef, new_comp,
    stats)`` (``ok`` is the step guard's finiteness verdict — see
    :func:`_with_guard`; omit it for ungated behaviour).

    Must be called *inside* ``shard_map`` (uses ``lax.psum`` / ``axis_index``
    over ``axis_name``).  ``grads`` are the local worker's gradients at the
    same scale the reference compresses (see train/step.py); the return value
    is the world-averaged gradient, matching `core.py:217-222`.

    ``cfg.sync_overlap > 1`` dispatches to the chunk-pipelined driver
    (:func:`tpu_compressed_dp.parallel.overlap.make_chunked_grad_sync`),
    which calls back here once per chunk with ``chunking=False`` and the
    chunk's global ``group_offset``.  The offset shifts the per-group RNG
    derivation (:func:`~tpu_compressed_dp.ops.compressors.leaf_key`) and the
    PowerSGD warm-start keys (``q<gi>``) so a chunk's groups compute
    bitwise-identically to the same groups in a single whole-tree sync.

    ``comp`` is the persistent compressor-state pytree
    (:func:`init_comp_state`): the PowerSGD warm-start factors, threaded in
    and out of the jitted step like the EF residual.  Stateless methods take
    and return ``()`` unchanged.

    ``comm_stats`` reports per-step communication analytically (SURVEY.md §5:
    the reference measured NIC bytes via /proc/net/dev; on TPU the payload is
    known at trace time for fixed-k methods and counted at run time for
    threshold methods): ``sent_elems`` is the element count the wire
    representation would carry, ``sent_bits`` its analytic bit volume
    (quantizers send every element at 2-9 bits), ``dense_elems`` the
    uncompressed size.
    """
    if chunking and cfg.sync_overlap > 1:
        if group_offset:
            raise ValueError("group_offset is only meaningful for the "
                             "per-chunk engines (chunking=False)")
        from tpu_compressed_dp.parallel import overlap

        return overlap.make_chunked_grad_sync(cfg, axis_name)
    comp = compressors.get_compressor(
        cfg.method, ratio=cfg.ratio, threshold=cfg.threshold,
        qstates=cfg.qstates, block_size=cfg.block_size,
        terngrad_chunk=cfg.resolved_terngrad_chunk, rank=cfg.rank,
    )
    if comp.name == "powersgd":
        # stateful warm-started path; the factors ARE the wire form, so
        # simulate and wire modes share it
        return _with_guard(
            _make_powersgd_sync(cfg, axis_name, group_offset=group_offset))
    if cfg.mode == "wire" and comp.name != "none":
        # Dense (method=None) has no sparse representation — the simulate
        # path's full-size psum IS its wire format, so fall through.
        from tpu_compressed_dp.ops import wire

        wire_sync = wire.make_wire_grad_sync(cfg, axis_name,
                                             group_offset=group_offset)

        def sync_wire(grads: Any, ef: Any, comp_state: Any, key: jax.Array):
            out, new_ef, stats = wire_sync(grads, ef, key)
            return out, new_ef, comp_state, stats

        return _with_guard(sync_wire)
    per_worker_rng = not cfg.resolved_shared_mask
    bits_per_elem = compressors.payload_bits_per_elem(
        comp.name, qstates=cfg.qstates, shared_mask=cfg.resolved_shared_mask,
        block_size=cfg.block_size,
    )

    def sent_count(comp_flat: jax.Array) -> jax.Array:
        # Sparsifiers transmit only surviving coordinates; quantizers
        # (terngrad/qsgd) and identity carry every element — at a reduced
        # per-element width accounted by `bits_per_elem`.
        if not comp.is_sparsifier:
            return jnp.asarray(float(comp_flat.shape[0]), jnp.float32)
        if comp.name == "randomk":
            # bill the keep count, not count_nonzero: the wire form transports
            # exactly `keep` value slots (indices implied by the shared seed,
            # sparsified_ddp.py:412) — a selected-but-zero coordinate still
            # travels.  Keeps simulate and wire accounting identical.
            return jnp.asarray(
                float(compressors.randomk_keep_count(
                    comp_flat.shape[0], cfg.ratio)), jnp.float32)
        if comp.name == "blocktopk":
            # whole blocks travel (zeros inside a selected block included);
            # capped at n — the wire path psums small/keep-all leaves dense
            kb = compressors.blocktopk_keep_blocks(
                comp_flat.shape[0], cfg.ratio, cfg.block_size)
            return jnp.asarray(
                float(min(kb * cfg.block_size, comp_flat.shape[0])), jnp.float32)
        return jnp.count_nonzero(comp_flat).astype(jnp.float32)

    def sent_bits(comp_flat: jax.Array, sent: jax.Array) -> jax.Array:
        # blocktopk's keep-all/small leaves psum dense on the wire — no
        # block indices travel — so bill them 32 bits/elem, matching the
        # wire engine's measured payload (stats agree across modes for the
        # sparsifiers; quantizer wire bits additionally carry scale/padding
        # overhead this analytic projection amortises away)
        if comp.name == "blocktopk":
            n = comp_flat.shape[0]
            kb = compressors.blocktopk_keep_blocks(n, cfg.ratio, cfg.block_size)
            width = 32.0 if kb * cfg.block_size >= n else bits_per_elem
            return sent * width
        return sent * bits_per_elem

    def compress_flat(flat: jax.Array, key: jax.Array, index: int) -> jax.Array:
        # index is the GLOBAL group index (group_offset shifts a chunk's
        # local indices), so chunked and whole-tree syncs draw identical
        # per-group randomness
        k = compressors.leaf_key(key, index + group_offset,
                                 per_worker_rng and comp.needs_rng, axis_name)
        return comp.fn(flat, k)

    def sync(grads: Any, ef: Any, comp_state: Any, key: jax.Array
             ) -> Tuple[Any, Any, Any, Dict[str, jax.Array]]:
        world = jax.lax.psum(1, axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        use_ef = cfg.error_feedback
        ef_leaves = jax.tree.leaves(ef) if use_ef else [None] * len(leaves)

        # One operator application + one collective per group: layerwise =
        # per parameter tensor (`core.py:176`), entiremodel = the whole
        # flattened gradient (`core.py:229`), bucketed = the reference DDP's
        # static 25 MB buckets.  Per-group psums are left unfused; XLA
        # coalesces/schedules them.
        groups = make_leaf_groups(
            [g.size * g.dtype.itemsize for g in leaves],
            cfg.granularity, cfg.bucket_mb * BUCKET_MB)
        out_leaves = [None] * len(leaves)
        new_ef_leaves = [None] * len(leaves)
        sent_total = jnp.asarray(0.0, jnp.float32)
        bits_total = jnp.asarray(0.0, jnp.float32)
        bits_psum = jnp.asarray(0.0, jnp.float32)
        bits_ag = jnp.asarray(0.0, jnp.float32)
        bits_a2a = jnp.asarray(0.0, jnp.float32)
        bits_ici = jnp.asarray(0.0, jnp.float32)
        bits_dcn = jnp.asarray(0.0, jnp.float32)
        bits_dcn_route = jnp.asarray(0.0, jnp.float32)
        dense_total = 0.0
        for gi, idxs in enumerate(groups):
            flat = group_concat(leaves, idxs)
            with obs_trace.phase("ef"):
                acc = flat + group_concat(ef_leaves, idxs) if use_ef else flat
            n_g = flat.shape[0]
            with obs_trace.phase("compress"):
                # fused epilogue: threshold-mask + compress + residual +
                # nonzero count in ONE pass over the accumulated gradient
                # (pallas_call boundaries block XLA from fusing the
                # where/subtract/count chain around the threshold kernel).
                # fp32-gated so the psum payload dtype matches the unfused
                # path.  Every |g| >= t selection rides the same kernel:
                # top-k (histogram threshold), threshold-V (the static V),
                # adaptive (2|g| >= max ⟺ |g| >= max/2, exact in binary fp).
                fuse_t = None
                if acc.dtype == jnp.float32 and kernels.use_fused_sparsify(n_g):
                    if comp.name == "topk":
                        keep = compressors.topk_keep_count(n_g, cfg.ratio)
                        fuse_t = kernels.topk_threshold(jnp.abs(acc), keep)
                    elif comp.name == "thresholdv":
                        fuse_t = jnp.float32(cfg.threshold)
                    elif comp.name == "adaptive_threshold":
                        fuse_t = 0.5 * jnp.max(jnp.abs(acc))
                if fuse_t is not None:
                    comp_flat, new_ef_flat, group_sent = kernels.fused_sparsify(
                        acc, fuse_t, want_ef=use_ef)
                    group_bits = group_sent * bits_per_elem
                else:
                    comp_flat = compress_flat(acc, key, gi)
                    new_ef_flat = acc - comp_flat if use_ef else None
                    group_sent = sent_count(comp_flat)
                    group_bits = sent_bits(comp_flat, group_sent)
            with obs_trace.phase("reduce"):
                reduced = jax.lax.psum(comp_flat, axis_name) / world
            with obs_trace.phase("return"):
                group_split(reduced, leaves, idxs, out_leaves)
                if use_ef:
                    group_split(new_ef_flat, leaves, idxs, new_ef_leaves,
                                dtype=jnp.float32)
            transport = wire_transport(comp.name, n_g, cfg)
            if transport == "sharded" and world > 1:
                # counterfactual like the rest of simulate billing: bill the
                # fixed-capacity route/return buffers the sharded wire form
                # WOULD move (static, like the wire engine's measured bits).
                # W=1 matches the wire engine's degradation to the allgather
                # combine (below), keeping the two engines' accounting equal.
                route_b, ret_b = _sharded_group_bits(comp.name, n_g, world, cfg)
                group_bits = jnp.asarray(route_b + ret_b, jnp.float32)
                bits_a2a = bits_a2a + route_b
                bits_ag = bits_ag + ret_b
            elif transport == "hierarchical" and world > 1:
                # per-FABRIC counterfactual: the flat collective-kind buckets
                # stay whole-world-only (their (W-1)/W arithmetic would lie
                # about grouped collectives)
                ici_b, rt_b, ret_b = _hier_group_bits(comp.name, n_g, world,
                                                      cfg)
                group_bits = jnp.asarray(ici_b + rt_b + ret_b, jnp.float32)
                bits_ici = bits_ici + ici_b
                bits_dcn = bits_dcn + rt_b + ret_b
                bits_dcn_route = bits_dcn_route + rt_b
            elif transport == "psum":
                bits_psum = bits_psum + group_bits
            else:
                bits_ag = bits_ag + group_bits
            sent_total = sent_total + group_sent
            bits_total = bits_total + group_bits
            dense_total += float(n_g)

        out = jax.tree.unflatten(treedef, out_leaves)
        new_ef = jax.tree.unflatten(treedef, new_ef_leaves) if use_ef else ()
        stats = {
            "sent_elems": sent_total,
            "sent_bits": bits_total,
            "sent_bits_psum": bits_psum,
            "sent_bits_allgather": bits_ag,
            "sent_bits_alltoall": bits_a2a,
            "sent_bits_ici": bits_ici,
            "sent_bits_dcn": bits_dcn,
            "sent_bits_dcn_route": bits_dcn_route,
            "dense_elems": jnp.asarray(dense_total, jnp.float32),
            "num_collectives": jnp.asarray(float(len(groups)), jnp.float32),
        }
        return out, new_ef, comp_state, stats

    return _with_guard(sync)


def _make_powersgd_sync(cfg: CompressionConfig, axis_name, *,
                        group_offset: int = 0):
    """The stateful PowerSGD engine behind :func:`make_grad_sync`.

    Per group: one warm-started power-iteration step against the persistent
    ``Q`` (``comp['q<gi>']``), two psums (``P`` then ``Q``), reconstruct the
    worker-mean low-rank gradient, fold the local deviation into the EF
    residual.  Groups whose factors would cost >= dense psum the full vector
    instead (exact; no state).  Every payload rides the psum ring —
    ``sent_bits_allgather`` is structurally zero for this method.

    ``check_sync`` (the ``check_reduction`` analog): the factor psums are
    only meaningful when every worker iterates in the SAME basis, so the
    guard verifies the warm-start ``Q`` agrees bitwise across workers before
    compressing and reports ``comm/sync_agree`` (1.0 = agreement) — a
    diverged warm start (e.g. mis-sharded restore) would otherwise corrupt
    gradients as silently as misaligned Random-K indices.
    """
    from tpu_compressed_dp.ops import lowrank

    if not cfg.error_feedback:
        # the rank-r projection is biased and the residual carries real
        # gradient mass every step (unlike the unbiased quantizers);
        # training with it discarded silently underperforms — Vogels et al.
        # always run PowerSGD with EF.  Legitimate EF-off uses exist
        # (linearity analysis, payload benchmarking), hence a warning, not
        # an error.
        import warnings

        warnings.warn(
            "method='powersgd' without error_feedback=True discards the "
            "low-rank residual every step; training quality degrades "
            "silently — enable EF (Vogels et al. always do)",
            stacklevel=2)

    def sync(grads: Any, ef: Any, comp_state: Any, key: jax.Array
             ) -> Tuple[Any, Any, Any, Dict[str, jax.Array]]:
        world = jax.lax.psum(1, axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        use_ef = cfg.error_feedback
        ef_leaves = jax.tree.leaves(ef) if use_ef else [None] * len(leaves)
        groups = make_leaf_groups(
            [g.size * g.dtype.itemsize for g in leaves],
            cfg.granularity, cfg.bucket_mb * BUCKET_MB)
        out_leaves = [None] * len(leaves)
        new_ef_leaves = [None] * len(leaves)
        new_comp = {}
        sent_total = 0.0
        bits_total = 0.0
        n_coll = 0
        dense_total = 0.0
        agrees = []
        for gi, idxs in enumerate(groups):
            flat = group_concat(leaves, idxs)
            with obs_trace.phase("ef"):
                acc = flat + group_concat(ef_leaves, idxs) if use_ef else flat
                acc = acc.astype(jnp.float32)
            n_g = flat.shape[0]
            if lowrank.powersgd_dims(n_g, cfg.rank) is None:
                # factors would cost >= the dense vector: psum dense (exact)
                with obs_trace.phase("reduce"):
                    recon = jax.lax.psum(acc, axis_name) / world
                new_ef_flat = jnp.zeros_like(acc) if use_ef else None
                group_sent, group_bits = float(n_g), 32.0 * n_g
                n_coll += 1
            else:
                qk = f"q{gi + group_offset}"  # global key: chunk-invariant
                if not isinstance(comp_state, dict) or qk not in comp_state:
                    raise ValueError(
                        f"powersgd sync needs warm-start state {qk!r}; build "
                        "TrainState.comp with init_comp_state(grads_like, "
                        "cfg[, num_devices]) for this gradient tree")
                q_in = comp_state[qk]
                if cfg.check_sync:
                    # pmax/pmin, not psum/world: summing W identical fp32
                    # values is only exact when the reduction stays on
                    # power-of-two partials (odd-count partial sums round),
                    # so a mean-based bitwise compare false-alarms; max==min
                    # is order-free and exact
                    spread = (jax.lax.pmax(q_in, axis_name)
                              - jax.lax.pmin(q_in, axis_name))
                    agrees.append(
                        (jnp.max(jnp.abs(spread)) == 0.0).astype(jnp.float32))
                # the low-rank factor iteration interleaves compression
                # (matmuls against Q) with its two psums — one scope covers
                # the compress+reduce pair (xprof still splits the psums out
                # by op name inside it)
                with obs_trace.phase("compress"):
                    recon, q_new, group_sent, group_bits = (
                        lowrank.powersgd_group_sync(
                            acc, q_in, cfg.rank, axis_name, world))
                new_comp[qk] = q_new
                new_ef_flat = acc - recon if use_ef else None
                n_coll += 2  # P-psum + Q-psum
            with obs_trace.phase("return"):
                group_split(recon, leaves, idxs, out_leaves)
                if use_ef:
                    group_split(new_ef_flat, leaves, idxs, new_ef_leaves,
                                dtype=jnp.float32)
            sent_total += group_sent
            bits_total += group_bits
            dense_total += float(n_g)

        out = jax.tree.unflatten(treedef, out_leaves)
        new_ef = jax.tree.unflatten(treedef, new_ef_leaves) if use_ef else ()
        stats = {
            "sent_elems": jnp.asarray(sent_total, jnp.float32),
            "sent_bits": jnp.asarray(bits_total, jnp.float32),
            "sent_bits_psum": jnp.asarray(bits_total, jnp.float32),
            "sent_bits_allgather": jnp.asarray(0.0, jnp.float32),
            "sent_bits_alltoall": jnp.asarray(0.0, jnp.float32),
            "sent_bits_ici": jnp.asarray(0.0, jnp.float32),
            "sent_bits_dcn": jnp.asarray(0.0, jnp.float32),
            "sent_bits_dcn_route": jnp.asarray(0.0, jnp.float32),
            "dense_elems": jnp.asarray(dense_total, jnp.float32),
            "num_collectives": jnp.asarray(float(n_coll), jnp.float32),
        }
        if agrees:
            stats["sync_agree"] = jnp.min(jnp.stack(agrees))
        return out, new_ef, new_comp if new_comp else (), stats

    return sync


def make_partitioned_grad_sync(cfg: CompressionConfig, sync_axes,
                               leaf_axes) -> Any:
    """Compressed sync for gradient trees whose leaves are sharded over
    different subsets of model axes (tensor / pipeline parallelism, and
    their composition).

    Compression masks are data-dependent, so flattening leaves with
    DIFFERENT replication signatures together would give ranks that share
    one leaf but not another different masks over the shared sections and
    silently de-synchronise replicated parameters.  ``leaf_axes`` — aligned
    with ``jax.tree.leaves`` order — gives each leaf the tuple of model
    axes it is sharded over (``()`` = fully replicated); leaves sync in one
    group PER SIGNATURE: within a group every rank pair that shares the
    group's data either shares all of it (identical inputs -> identical
    masks) or none (independent shards).  Comm stats report model-wide
    totals: each group's per-rank stats psum over exactly its signature's
    axes.

    Compressor state is per signature: a ``{'sig<i>': sub}`` dict in the
    sorted-signature order (:func:`init_comp_state_partitioned`), ``()``
    when stateless.
    """
    base_sync = make_grad_sync(cfg, axis_name=sync_axes)
    leaf_axes = [tuple(a) for a in leaf_axes]
    sigs = sorted(set(leaf_axes))  # deterministic group order
    sig_of = {s: i for i, s in enumerate(sigs)}
    group_of = [sig_of[a] for a in leaf_axes]

    def split(tree):
        leaves = jax.tree.leaves(tree)
        return [[l for l, g in zip(leaves, group_of) if g == gi]
                for gi in range(len(sigs))]

    def merge(like, groups):
        its = [iter(g) for g in groups]
        leaves = [next(its[g]) for g in group_of]
        return jax.tree.unflatten(jax.tree.structure(like), leaves)

    def sync(grads, ef, comp, key, ok=None):
        use_ef = cfg.error_feedback
        g_groups = split(grads)
        e_groups = split(ef) if use_ef else [() for _ in sigs]
        keys = jax.random.split(key, len(sigs))
        out_g, out_e, comm = [], [], None
        new_comp = {}
        for gi, sig in enumerate(sigs):
            sub_comp = (comp.get(f"sig{gi}", ())
                        if isinstance(comp, dict) else ())
            s_g, s_e, s_comp, s_comm = base_sync(
                g_groups[gi], e_groups[gi] if use_ef else (), sub_comp,
                keys[gi], ok=ok)
            if s_comp != ():
                new_comp[f"sig{gi}"] = s_comp
            out_g.append(s_g)
            out_e.append(s_e)
            if sig:
                # Diagnostics (sync_agree, guard/nonfinite) are 0/1 verdicts,
                # not additive volumes: psum over the signature axes (or
                # summing across groups below) would inflate a unanimous
                # value to the rank count — reduce them with their own
                # collective instead.
                s_comm = {k: (_DIAG_STATS[k][0](v, sig) if k in _DIAG_STATS
                              else jax.lax.psum(v, sig))
                          for k, v in s_comm.items()}
            comm = s_comm if comm is None else merge_stat_dicts(comm, s_comm)
        synced = merge(grads, out_g)
        new_ef = merge(ef, out_e) if use_ef else ()
        return synced, new_ef, new_comp if new_comp else (), comm

    return sync


def make_grouped_grad_sync(cfg: CompressionConfig, sync_axes, is_sharded,
                           shard_axis):
    """Binary convenience wrapper over :func:`make_partitioned_grad_sync`:
    leaves are either replicated or sharded over ``shard_axis`` (a name or
    tuple of names)."""
    axes = (shard_axis,) if isinstance(shard_axis, str) else tuple(shard_axis)
    leaf_axes = [axes if s else () for s in is_sharded]
    return make_partitioned_grad_sync(cfg, sync_axes, leaf_axes)
