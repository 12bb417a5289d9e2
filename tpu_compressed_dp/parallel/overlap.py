"""Chunk-pipelined gradient synchronization: hide compressed collectives
behind backward + optimizer compute.

The reference's ``RandomKSparsifiedDDP`` earns its wall-clock wins by
overlapping bucket reductions with the backward pass via reverse-order
autograd hooks (`sparsified_ddp.py:279-281`, `ddp.py:429-456`).  This
framework traces the whole sync into one jitted step and leaned on XLA's
latency-hiding scheduler — but the compiled evidence
(``benchmarks/overlap_hlo_r5.txt``) shows XLA's all-reduce COMBINER merges
every per-group collective into ONE late all-reduce that depends on the
entire backward pass: only 24–39 % of the step's compute is scheduled after
it, so the sync runs largely exposed at the step tail.  Pipelining the
reduce matters as much as shrinking it (Near-Optimal Sparse Allreduce,
arXiv:2201.07598).

This module is the TPU-native answer: decompose the sync into up to
``cfg.sync_overlap`` independent **chunk syncs**, issued in
reverse-topological order (the LAST parameters' gradients — produced FIRST
by the backward pass — sync first):

  * **Chunk boundaries align with reduction-group boundaries** of the
    configured granularity (the same ``make_leaf_groups`` bucket-assignment
    the engines use), and each chunk's engine gets the chunk's global
    ``group_offset`` — so per-group compression operators, RNG streams
    (``leaf_key``), PowerSGD warm-start keys (``q<gi>``) and transports are
    BITWISE identical to the single-dispatch sync.  ``sync_overlap`` changes
    the schedule, never the numerics (tests/test_overlap.py).
  * **A minimal dependency chain** (`lax.optimization_barrier`) ties chunk
    ``i+1``'s gradient inputs to one of chunk ``i``'s reduced outputs.  The
    barrier is a runtime identity (numerics unchanged) but makes the chunk
    collectives mutually dependent, which (a) defeats the all-reduce
    combiner — the K collectives stay K separate instructions — and
    (b) pins the issue order to the reverse-topological chunk order.  The
    collectives serialise on the interconnect (they share the links anyway,
    exactly like the reference's bucket queue); every OTHER edge is real
    data flow, so XLA remains free to run the rest of the backward pass and
    the other chunks' optimizer slices while a chunk's collective is in
    flight.
  * **Per-chunk optimizer interleave** (:func:`make_overlap_sync_apply`,
    used by ``train/step.py``): chunk ``i``'s slice of ``optimizer.apply``
    runs while chunk ``i+1``'s collective is in flight.  Per-leaf SGD
    updates are independent, so the sliced apply is bitwise the whole-tree
    apply.
  * **Guard composition**: the finiteness vote (``ok``) is computed ONCE in
    the step factory, before any chunk dispatches; each chunk's engine then
    applies the standard gate (zeroed inputs, EF/comp held bitwise — see
    ``parallel/dp.py:_with_guard``), preserving the bitwise-hold invariant
    of the step guard across the chunked schedule.

Measured, not asserted: ``tools/overlap_evidence.py`` AOT-compiles the real
train step for a v5e topology and reads ``compute_after_frac`` off the
scheduled module (per-chunk collectives labelled by their
``tcdp.chunk<ii>`` scopes); ``--assert-frac`` gates it.  Results land in
``benchmarks/overlap_hlo_r8.txt``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from tpu_compressed_dp.obs import trace as obs_trace

__all__ = ["ChunkPlan", "plan_chunks", "grad_availability", "issue_order",
           "make_chunked_grad_sync", "make_overlap_sync_apply",
           "hideable_byte_fraction"]


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """One chunk of the gradient tree, in PARAMETER order (chunk 0 holds the
    first leaves; issue order is the reverse).  ``[leaf_lo, leaf_hi)`` is a
    contiguous leaf range whose boundaries coincide with reduction-group
    boundaries; ``group_offset`` is the global index of the chunk's first
    group (the engines' RNG / warm-start key base)."""

    index: int
    leaf_lo: int
    leaf_hi: int
    group_offset: int
    n_groups: int
    n_bytes: int


def plan_chunks(byte_sizes: Sequence[int], cfg) -> List[ChunkPlan]:
    """Partition the tree's leaves into ``<= cfg.sync_overlap`` contiguous,
    byte-balanced chunks whose boundaries align with the granularity's
    reduction-group boundaries.

    Reuses the engines' own bucket-assignment (``make_leaf_groups``) so the
    per-group structure inside each chunk reproduces the whole-tree grouping
    exactly: greedy bucket packing is Markov in the current bucket's fill,
    and every chunk starts at a group boundary (fill = 0), so re-packing the
    chunk's leaf span yields the same groups the whole-tree packing assigned
    to that span.  ``granularity='entiremodel'`` has one group and therefore
    one chunk — the knob degrades to the single-dispatch sync there.
    """
    from tpu_compressed_dp.parallel.dp import BUCKET_MB, make_leaf_groups

    byte_sizes = list(byte_sizes)
    groups = make_leaf_groups(byte_sizes, cfg.granularity,
                              cfg.bucket_mb * BUCKET_MB)
    if not groups:
        return []
    k = max(1, min(int(cfg.sync_overlap), len(groups)))
    group_bytes = [float(sum(byte_sizes[i] for i in g)) for g in groups]
    total = sum(group_bytes) or 1.0
    plans: List[ChunkPlan] = []
    gi = 0
    cum = 0.0
    leaf_lo = 0
    for c in range(k):
        start_g = gi
        target = (c + 1) * total / k
        # take at least one group; keep taking while under the proportional
        # cut AND enough groups remain to give every later chunk one
        while gi < len(groups) and (
                gi == start_g
                or (cum + group_bytes[gi] <= target
                    and len(groups) - gi > k - c - 1)):
            cum += group_bytes[gi]
            gi += 1
        leaf_hi = groups[gi - 1][-1] + 1
        plans.append(ChunkPlan(
            index=c, leaf_lo=leaf_lo, leaf_hi=leaf_hi, group_offset=start_g,
            n_groups=gi - start_g,
            n_bytes=int(sum(group_bytes[start_g:gi]))))
        leaf_lo = leaf_hi
    assert gi == len(groups) and leaf_lo == len(byte_sizes)
    return plans


def hideable_byte_fraction(plans: Sequence[ChunkPlan]) -> float:
    """Fraction of the sync's bytes the chunk schedule can bury under
    remaining compute — the adaptive controller's budget scaler
    (:func:`tpu_compressed_dp.control.signals.hideable_budget_ms`).

    Chunks issue in reverse-parameter order; the LAST-issued chunk (chunk 0,
    the first parameters) completes at the head of the optimizer tail with
    the least compute left to hide behind, so its bytes are counted exposed
    and everything else hideable.  A single-chunk plan (``sync_overlap=1``,
    or entiremodel granularity) therefore yields 0.0 — nothing pipelines,
    matching the one-late-all-reduce behaviour the overlap evidence
    measured.
    """
    plans = list(plans)
    total = float(sum(p.n_bytes for p in plans))
    if total <= 0.0 or len(plans) < 2:
        return 0.0
    exposed = float(min(plans, key=lambda p: p.index).n_bytes)
    return max(0.0, 1.0 - exposed / total)


def _comp_slice(comp: Any, plan: ChunkPlan) -> Any:
    """The chunk's slice of the persistent compressor state: the global
    ``q<gi>`` entries of its groups (PowerSGD warm starts), ``()`` when the
    chunk holds none (stateless methods, dense-fallback-only chunks)."""
    if not isinstance(comp, dict):
        return ()
    sub = {f"q{g}": comp[f"q{g}"]
           for g in range(plan.group_offset, plan.group_offset + plan.n_groups)
           if f"q{g}" in comp}
    return sub if sub else ()


#: Elementwise / metadata primitives the step factory (and the chain
#: itself) applies to gradients AFTER the backward pass produced them —
#: loss-scale division, ``astype(f32) * grad_scale``, chaos ``select_n``,
#: clipping muls, the optimization-barrier tie.  A leaf's availability is
#: its last producer that is NOT one of these: the ``tree.map`` cosmetics
#: are emitted in LEAF order (alphabetical for flax dicts), which would
#: otherwise mask the backward's true production order.
_CHEAP_OPS = frozenset({
    "convert_element_type", "mul", "div", "select_n", "broadcast_in_dim",
    "reshape", "squeeze", "expand_dims", "transpose", "copy", "neg",
    "stop_gradient", "optimization_barrier",
})


def grad_availability(leaves: Sequence[Any]) -> Optional[List[int]]:
    """Best-effort per-leaf gradient *production rank*, read off the ambient
    jit trace: the index of the equation that really produced each leaf
    (walking back through :data:`_CHEAP_OPS`), i.e. WHEN in the backward
    pass the gradient becomes available.

    Flax flattens params alphabetically, which is NOT backward-production
    order — resnet-style models put the stem (``prep``, grad ready LAST)
    after the classifier (``linear``, ready FIRST), so a leaf-order
    heuristic anchors the chunk chain's head at the very end of the
    backward pass (measured: first-collective compute_after_frac 34 % vs
    60 %+ with true availability order).  Reading the trace frame is
    version-sensitive (``jax._src``); any surprise degrades to ``None`` and
    the caller falls back to reversed leaf order.
    """
    try:
        from jax._src.core import Var
        from jax._src.interpreters import partial_eval as pe

        first = next((t for t in leaves
                      if isinstance(t, pe.DynamicJaxprTracer)), None)
        if first is None:
            return None
        frame = first._trace.frame
        producer: Dict[Any, Any] = {}
        for i, eqn in enumerate(frame.eqns):
            for v in eqn.outvars:
                producer[v] = (i, eqn)
        memo: Dict[Any, int] = {}

        def avail(v0) -> int:
            stack = [(v0, False)]
            while stack:
                u, expanded = stack.pop()
                if u in memo:
                    continue
                p = producer.get(u)
                if p is None:
                    memo[u] = -1  # trace input / constant: available at t=0
                    continue
                i, eqn = p
                if eqn.primitive.name not in _CHEAP_OPS:
                    memo[u] = i
                    continue
                ins = [w for w in eqn.invars if isinstance(w, Var)]
                # follow only the DATA path: a cheap op combining the leaf
                # with a broadcast scalar (global clip factor, loss scale)
                # must not inherit that scalar's (very late, whole-tree)
                # rank — it would collapse every leaf to one rank and
                # degrade issue_order to a tie
                same = [w for w in ins
                        if getattr(w.aval, "shape", None) == u.aval.shape]
                if same:
                    ins = same
                if expanded:
                    memo[u] = max((memo.get(w, -1) for w in ins), default=-1)
                else:
                    stack.append((u, True))
                    stack.extend((w, False) for w in ins if w not in memo)
            return memo[v0]

        ranks = []
        for t in leaves:
            v = (frame.tracer_to_var.get(id(t))
                 if isinstance(t, pe.DynamicJaxprTracer) else None)
            ranks.append(avail(v) if v is not None else -1)
        return ranks
    except Exception:
        return None


def issue_order(plans: List[ChunkPlan],
                ranks: Optional[Sequence[int]] = None) -> List[ChunkPlan]:
    """Chunk dispatch (and chain) order.

    With per-leaf production ``ranks`` (:func:`grad_availability`): sort by
    each chunk's availability — the MAX rank over its leaves, i.e. the
    moment its last gradient lands — earliest first, so the chain head's
    collective can be scheduled while most of the backward pass still runs
    and each later chunk's collective finds fresh compute to hide behind.
    Without ranks: reverse leaf order (the LAST parameters' gradients are
    produced FIRST by the backward pass), treating pytree leaf order as
    forward-topological — true for list-like layer stacks, approximate for
    alphabetically-sorted flax dicts.  Rank ties break toward the SAME
    reversed order, so degenerate rankings (e.g. every leaf behind one
    global factor) degrade to the fallback, never to forward order."""
    if ranks is not None:
        return sorted(plans,
                      key=lambda p: (max(ranks[p.leaf_lo:p.leaf_hi]),
                                     -p.index))
    return list(reversed(plans))


def _chain(token: Optional[jax.Array], sub_leaves: List[jax.Array]):
    """Tie this chunk's inputs to the previous chunk's reduced output via an
    optimization barrier (runtime identity).  The resulting dependency edge
    is what keeps the chunk collectives K separate, ordered instructions:
    XLA's all-reduce combiner only merges independent collectives, and the
    scheduler must respect the chain.  Everything else the chunk reads
    (gradient leaves, EF, warm starts) keeps its real producers, so the
    remaining backward pass and other chunks' update slices stay free to
    overlap the in-flight collective."""
    if token is None or not sub_leaves:
        return sub_leaves
    tied = jax.lax.optimization_barrier((token, *sub_leaves))
    return list(tied[1:])


def make_chunked_grad_sync(cfg, axis_name: str = "data"):
    """Chunk-pipelined ``sync(grads, ef, comp, key[, ok])`` with the exact
    contract of :func:`tpu_compressed_dp.parallel.dp.make_grad_sync` — the
    dispatch target for ``cfg.sync_overlap > 1``.

    Bitwise-identical outputs to ``sync_overlap=1`` for every method ×
    mode × transport × EF combination: only the dependency/schedule
    structure changes (see the module docstring).
    """

    def sync(grads: Any, ef: Any, comp: Any, key: jax.Array,
             ok: Optional[jax.Array] = None):
        from tpu_compressed_dp.parallel import dp

        leaves, treedef = jax.tree.flatten(grads)
        plans = plan_chunks([g.size * g.dtype.itemsize for g in leaves], cfg)
        if len(plans) <= 1:
            # single group (entiremodel / one-leaf trees) or empty tree:
            # chunking is structureless — run the plain engine
            single = dp.make_grad_sync(cfg, axis_name, chunking=False)
            return single(grads, ef, comp, key, ok=ok)
        use_ef = cfg.error_feedback
        ef_leaves = jax.tree.leaves(ef) if use_ef else None
        out_leaves: List[Any] = [None] * len(leaves)
        new_ef_leaves: List[Any] = [None] * len(leaves)
        new_comp: Dict[str, Any] = {}
        stats: Optional[Dict[str, Any]] = None
        token = None
        # availability-ordered issue: the chunk whose last gradient lands
        # earliest in the backward pass dispatches (and heads the chain)
        # first, so its collective can start while the rest of the backward
        # still runs
        ranks = grad_availability(leaves)
        for ci, pl in enumerate(issue_order(plans, ranks)):
            sub_sync = dp.make_grad_sync(cfg, axis_name,
                                         group_offset=pl.group_offset,
                                         chunking=False)
            sub = _chain(token, leaves[pl.leaf_lo:pl.leaf_hi])
            sub_ef = ef_leaves[pl.leaf_lo:pl.leaf_hi] if use_ef else ()
            with obs_trace.chunk(ci):
                o, e, c, s = sub_sync(sub, sub_ef, _comp_slice(comp, pl),
                                      key, ok=ok)
            out_leaves[pl.leaf_lo:pl.leaf_hi] = list(o)
            if use_ef:
                new_ef_leaves[pl.leaf_lo:pl.leaf_hi] = list(e)
            if isinstance(c, dict):
                new_comp.update(c)
            stats = s if stats is None else dp.merge_stat_dicts(stats, s)
            token = o[0] if len(o) else token
        out = jax.tree.unflatten(treedef, out_leaves)
        new_ef = jax.tree.unflatten(treedef, new_ef_leaves) if use_ef else ()
        return out, new_ef, new_comp if new_comp else (), stats

    return sync


def make_overlap_sync_apply(cfg, optimizer, axis_name: str = "data"):
    """Fused chunk-pipelined sync + per-chunk optimizer apply for the pure
    data-parallel train step (``train/step.py``).

    Returns ``fused(params, grads, ef, comp, opt_state, key, step[, ok]) ->
    (new_params, new_opt_state, new_ef, new_comp, stats)``.  Chunk ``i``'s
    slice of ``optimizer.apply`` is traced immediately after chunk ``i``'s
    reduce — and BEFORE chunk ``i+1``'s collective is chained in — so the
    scheduler can run it while that collective is in flight.  Per-leaf SGD
    updates are independent and the schedule-valued hyper-parameters are
    functions of ``step`` alone, so the sliced apply is bitwise the
    whole-tree ``optimizer.apply(params, synced, opt_state, step)``.

    The caller computes the guard vote ``ok`` ONCE before this runs; the
    per-chunk engines gate EF/comp and zero the collective inputs
    (``_with_guard``), and the caller still discards the returned
    params/opt via ``select_tree`` on a vetoed step — the produced updates
    are compression noise by then, exactly as in the unfused path.

    ``clip_sent_norm`` needs the GLOBAL synced-gradient norm — a barrier
    across all chunks — so the step factory falls back to chunked-sync +
    whole-tree apply when it is set.
    """

    def fused(params: Any, grads: Any, ef: Any, comp: Any, opt_state: Any,
              key: jax.Array, step: jax.Array,
              ok: Optional[jax.Array] = None):
        from tpu_compressed_dp.parallel import dp

        p_leaves, p_tree = jax.tree.flatten(params)
        g_leaves = jax.tree.leaves(grads)
        buf_leaves = jax.tree.leaves(opt_state["momentum"])
        mask_leaves = (jax.tree.leaves(optimizer.wd_mask)
                       if optimizer.wd_mask is not None
                       else [True] * len(p_leaves))
        plans = plan_chunks([g.size * g.dtype.itemsize for g in g_leaves],
                            cfg)
        if len(plans) <= 1:
            single = dp.make_grad_sync(cfg, axis_name, chunking=False)
            synced, new_ef, new_comp, stats = single(grads, ef, comp, key,
                                                     ok=ok)
            with obs_trace.phase("update"):
                new_params, new_opt = optimizer.apply(params, synced,
                                                      opt_state, step)
            return new_params, new_opt, new_ef, new_comp, stats
        use_ef = cfg.error_feedback
        ef_leaves = jax.tree.leaves(ef) if use_ef else None
        new_p: List[Any] = [None] * len(p_leaves)
        new_b: List[Any] = [None] * len(p_leaves)
        new_ef_leaves: List[Any] = [None] * len(p_leaves)
        new_comp: Dict[str, Any] = {}
        stats: Optional[Dict[str, Any]] = None
        token = None
        ranks = grad_availability(g_leaves)
        for ci, pl in enumerate(issue_order(plans, ranks)):
            lo, hi = pl.leaf_lo, pl.leaf_hi
            sub_sync = dp.make_grad_sync(cfg, axis_name,
                                         group_offset=pl.group_offset,
                                         chunking=False)
            sub = _chain(token, g_leaves[lo:hi])
            sub_ef = ef_leaves[lo:hi] if use_ef else ()
            with obs_trace.chunk(ci):
                o, e, c, s = sub_sync(sub, sub_ef, _comp_slice(comp, pl),
                                      key, ok=ok)
                with obs_trace.phase("update"):
                    # the chunk's slice of the optimizer: plain-list pytrees
                    # align leaf-for-leaf with the full flatten order
                    sub_opt = dataclasses.replace(
                        optimizer, wd_mask=list(mask_leaves[lo:hi]))
                    p_c, o_c = sub_opt.apply(
                        p_leaves[lo:hi], list(o),
                        {"momentum": buf_leaves[lo:hi]}, step)
            new_p[lo:hi] = list(p_c)
            new_b[lo:hi] = list(o_c["momentum"])
            if use_ef:
                new_ef_leaves[lo:hi] = list(e)
            if isinstance(c, dict):
                new_comp.update(c)
            stats = s if stats is None else dp.merge_stat_dicts(stats, s)
            # chain off the chunk's REDUCED gradient (not its updated
            # params): the update slices must stay off the collective chain
            # so they remain free to overlap later chunks' collectives
            token = o[0] if len(o) else token
        new_params = jax.tree.unflatten(p_tree, new_p)
        new_opt = {"momentum": jax.tree.unflatten(p_tree, new_b)}
        g_tree = jax.tree.structure(grads)
        new_ef = jax.tree.unflatten(g_tree, new_ef_leaves) if use_ef else ()
        return new_params, new_opt, new_ef, new_comp if new_comp else (), \
            stats

    return fused
