"""Pass 1 — SPMD safety analysis over traced jaxprs.

The compressed-DP contract is structural: every worker must execute the
*identical* ordered collective program every step, or the fleet deadlocks
(a collective issued by some workers only) or silently diverges (stateful
compressors like PowerSGD make a one-step mismatch sticky).  Runtime
drills catch these on an 8-device mesh in minutes; this pass catches them
at trace time in seconds by walking the ClosedJaxprs of both sync engines
and all three step factories:

  * **TCDP001 — collectives under divergent control flow.**  A collective
    inside only one ``cond`` branch, or inside a ``while`` whose predicate
    derives from float data (loss values, gradient norms — anything that
    can disagree across workers), is the elastic-deadlock shape.  Loops
    with counter-only predicates (``fori_loop``) and ``scan`` (static trip
    count) are symmetric by construction and pass.
  * **TCDP002 — collective-signature determinism.**  The ordered
    (primitive, axis names, operand shapes) sequence must be identical
    across re-traces of one config, equal as a multiset between the
    chunk-pipelined and single-dispatch schedules (the bitwise-equality
    claim of tests/test_overlap.py), and identical between the simulate
    and wire engines where the equivalence tests claim it (dense psum).
  * **TCDP003 — donation that cannot alias.**  Every donated input leaf
    must find a shape/dtype-matching output to alias into; a donated
    buffer with no destination is a wasted donation and a
    read-after-donate hazard on real hardware.
  * **TCDP004 — overlap chunk plan / chain integrity.**  Chunk plans must
    partition the leaf range with strictly increasing, distinct group
    offsets (distinct RNG streams / PowerSGD warm-start keys per chunk),
    and the traced chunked sync must carry ``optimization_barrier`` links
    with a collective ancestor between consecutive chunks — the
    issue-order invariant PR 5's schedule evidence relies on.
  * **TCDP005 — per-config jaxpr size budget.**  Every traced config must
    stay under a fixed equation budget (~3x the measured quick-matrix
    maximum).  The failure this catches is *accidental unrolling*: a
    Python loop over leaves, chunks or devices that should be a
    ``scan``/``fori_loop`` multiplies the trace ~10x, blowing compile
    time and (on the fused-kernel paths) emitting one Pallas call per
    iteration instead of one per payload.

The fused compressor kernels (``ops/kernels.py``) add one more axis to
the matrix: representative fused-path configs are traced under
``pallas_mode`` off AND force, and the ordered collective signature must
be identical between the two — the kernel family is pure local compute,
so toggling it may never add, drop or reorder a collective.

Everything here is pure tracing (``jax.make_jaxpr`` / ``jax.eval_shape``)
— no compilation, no devices beyond the virtual CPU mesh — so the full
matrix runs on CPU in seconds (``tools/tcdp_lint.py``; the quick profile
gates tier-1 via tests/test_lint.py).
"""

from __future__ import annotations

import collections
import itertools
from typing import (Any, Callable, Dict, Iterable, List, Optional, Sequence,
                    Set, Tuple)

from tpu_compressed_dp.analysis.report import Finding

__all__ = [
    "COLLECTIVE_PRIMS", "collective_signature", "check_control_flow",
    "check_signature_match", "check_donation", "check_chunk_plan",
    "check_barrier_chain", "count_eqns", "check_jaxpr_budget",
    "EQN_BUDGET_SYNC", "EQN_BUDGET_STEP", "trace_sync", "run_spmd_pass",
    "ENGINE_METHODS",
]

#: primitives that hit the interconnect — any of these inside divergent
#: control flow is a cross-worker deadlock in waiting
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "ppermute", "pbroadcast",
    "all_gather", "all_gather_invariant", "all_to_all",
    "reduce_scatter", "psum_scatter",
})

ENGINE_METHODS = (None, "topk", "blocktopk", "randomk", "thresholdv",
                  "adaptive_threshold", "terngrad", "qsgd", "powersgd")

#: signature element: (primitive, axis names, input avals)
Sig = Tuple[str, Tuple[str, ...], Tuple[str, ...]]


# ---------------------------------------------------------- jaxpr plumbing

def _sub_jaxprs(eqn) -> Iterable[Any]:
    """Inner (open) jaxprs of one equation — pjit bodies, cond branches,
    while cond/body, scan bodies, shard_map bodies, custom_* calls."""
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(x, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(x, "eqns"):
                yield x


def _is_var(v) -> bool:
    """True for jaxpr Vars (hashable, traceable to a producer) — excludes
    Literals, which also carry ``.aval`` but are constants."""
    from jax.extend.core import Literal
    return hasattr(v, "aval") and not isinstance(v, Literal)


def _axes_of(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _sig_of(eqn) -> Sig:
    return (eqn.primitive.name, _axes_of(eqn),
            tuple(v.aval.str_short() for v in eqn.invars
                  if hasattr(v, "aval")))


def collective_signature(jaxpr) -> List[Sig]:
    """Ordered collective program of a (Closed)Jaxpr, recursing into every
    sub-jaxpr in equation order."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Sig] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            out.append(_sig_of(eqn))
        for sub in _sub_jaxprs(eqn):
            out.extend(collective_signature(sub))
    return out


def _influencing_invars(jaxpr) -> Set[int]:
    """Indices of ``jaxpr.invars`` the outputs transitively depend on."""
    producers: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn
    needed: Set[Any] = set()
    frontier = [v for v in jaxpr.outvars if _is_var(v)]
    while frontier:
        v = frontier.pop()
        if v in needed:
            continue
        needed.add(v)
        eqn = producers.get(v)
        if eqn is not None:
            frontier.extend(iv for iv in eqn.invars if _is_var(iv))
    return {i for i, iv in enumerate(jaxpr.invars) if iv in needed}


def _slice_touches_float(jaxpr, roots) -> bool:
    """True when the backward slice from ``roots`` crosses any
    floating-point value — i.e. the quantity is data-derived, not a
    counter."""
    import jax.numpy as jnp

    producers: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producers[ov] = eqn
    seen: Set[Any] = set()
    frontier = [v for v in roots if _is_var(v)]
    while frontier:
        v = frontier.pop()
        if v in seen:
            continue
        seen.add(v)
        if jnp.issubdtype(v.aval.dtype, jnp.inexact):
            return True
        eqn = producers.get(v)
        if eqn is not None:
            frontier.extend(iv for iv in eqn.invars if _is_var(iv))
    return False


def _while_predicate_data_dependent(eqn) -> bool:
    """Heuristic: a ``while`` predicate is worker-divergent when its
    backward slice (over the loop-carried values feeding it, at the init
    site and through one body application) touches float data.  A pure
    counter loop (``fori_loop``: int carry updated from literals) passes."""
    import jax.numpy as jnp
    from jax.extend.core import Literal

    cond_closed = eqn.params["cond_jaxpr"]
    cj = getattr(cond_closed, "jaxpr", cond_closed)
    n_cc = int(eqn.params.get("cond_nconsts", 0))
    n_bc = int(eqn.params.get("body_nconsts", 0))
    needed = _influencing_invars(cj)
    carry_positions = [i - n_cc for i in needed if i >= n_cc]
    # init operands feeding the predicate
    for i in needed:
        outer_idx = i if i < n_cc else n_cc + n_bc + (i - n_cc)
        v = eqn.invars[outer_idx]
        if isinstance(v, Literal):
            continue
        aval = v.aval
        if jnp.issubdtype(aval.dtype, jnp.inexact) or aval.ndim > 0:
            return True
    # one body application: do the predicate-feeding carry outputs derive
    # from float data?
    body_closed = eqn.params["body_jaxpr"]
    bj = getattr(body_closed, "jaxpr", body_closed)
    roots = [bj.outvars[p] for p in carry_positions
             if p < len(bj.outvars) and _is_var(bj.outvars[p])]
    return _slice_touches_float(bj, roots)


# ------------------------------------------------------------------ checks

def check_control_flow(jaxpr, *, config: str = "") -> List[Finding]:
    """TCDP001 over one (Closed)Jaxpr, recursively."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Finding] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [tuple(collective_signature(b)) for b in branches]
            if len({s for s in sigs}) > 1:
                detail = [f"branch{i}: {len(s)} collectives"
                          for i, s in enumerate(sigs)]
                out.append(Finding(
                    code="TCDP001", config=config,
                    message="collective program differs across cond "
                            f"branches ({', '.join(detail)}) — workers "
                            "taking different branches deadlock"))
        elif name == "while":
            body = eqn.params.get("body_jaxpr")
            cond = eqn.params.get("cond_jaxpr")
            n_coll = (len(collective_signature(body)) if body else 0) + (
                len(collective_signature(cond)) if cond else 0)
            if n_coll and _while_predicate_data_dependent(eqn):
                out.append(Finding(
                    code="TCDP001", config=config,
                    message=f"{n_coll} collective(s) inside a while loop "
                            "with a data-dependent predicate — trip "
                            "counts can diverge across workers"))
        for sub in _sub_jaxprs(eqn):
            out.extend(check_control_flow(sub, config=config))
    return out


def check_signature_match(sig_a: Sequence[Sig], sig_b: Sequence[Sig],
                          label_a: str, label_b: str, *, config: str = "",
                          ordered: bool = True) -> List[Finding]:
    """TCDP002: compare two collective programs, ordered (retrace / engine
    pair) or as multisets (chunked vs single dispatch, where only the
    schedule may differ)."""
    if ordered:
        same = list(sig_a) == list(sig_b)
    else:
        same = (collections.Counter(sig_a) == collections.Counter(sig_b))
    if same:
        return []
    only_a = collections.Counter(sig_a) - collections.Counter(sig_b)
    only_b = collections.Counter(sig_b) - collections.Counter(sig_a)
    detail = ""
    if only_a or only_b:
        detail = (f"; only in {label_a}: {sorted(only_a)[:3]}"
                  f"; only in {label_b}: {sorted(only_b)[:3]}")
    else:
        detail = "; same multiset, different order"
    return [Finding(
        code="TCDP002", config=config,
        message=f"collective signature of {label_a} ({len(sig_a)} colls) != "
                f"{label_b} ({len(sig_b)} colls){detail}")]


def check_donation(fn: Callable, args: Sequence[Any],
                   donate_argnums: Sequence[int], *, config: str = ""
                   ) -> List[Finding]:
    """TCDP003: every donated input leaf must have a shape/dtype-matching
    output leaf left to alias into (multiset matching, XLA's own rule)."""
    import jax

    out_shapes = jax.eval_shape(fn, *args)
    budget = collections.Counter(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree.leaves(out_shapes))
    findings: List[Finding] = []
    for argnum in donate_argnums:
        for leaf in jax.tree.leaves(
                jax.eval_shape(lambda a: a, args[argnum])):
            key = (tuple(leaf.shape), str(leaf.dtype))
            if budget[key] > 0:
                budget[key] -= 1
            else:
                findings.append(Finding(
                    code="TCDP003", config=config,
                    message=f"donated arg {argnum} leaf "
                            f"{leaf.dtype}{list(leaf.shape)} has no "
                            "matching output to alias into"))
    return findings


def check_chunk_plan(plans: Sequence[Any], *, n_leaves: int, n_groups: int,
                     config: str = "") -> List[Finding]:
    """TCDP004 (plan level): chunks partition ``[0, n_leaves)`` in order,
    group offsets are distinct/strictly increasing and consistent with the
    per-chunk group counts — the invariant that gives every chunk its own
    RNG stream and PowerSGD warm-start keys."""
    out: List[Finding] = []

    def bad(msg: str) -> None:
        out.append(Finding(code="TCDP004", config=config,
                           message=f"chunk plan: {msg}"))

    if not plans:
        if n_leaves:
            bad(f"empty plan for {n_leaves} leaves")
        return out
    offs = [p.group_offset for p in plans]
    if len(set(offs)) != len(offs) or offs != sorted(offs):
        bad(f"group offsets not distinct/increasing: {offs}")
    expect = 0
    for p in plans:
        if p.group_offset != expect:
            bad(f"chunk {p.index} group_offset {p.group_offset} != "
                f"running group count {expect} — RNG/warm-start streams "
                "would collide or skip")
            break
        expect += p.n_groups
    if expect != n_groups and not out:
        bad(f"plan covers {expect} groups, tree has {n_groups}")
    lo = 0
    for p in plans:
        if p.leaf_lo != lo:
            bad(f"chunk {p.index} leaf range [{p.leaf_lo},{p.leaf_hi}) "
                f"does not continue at {lo} — chunks must partition the "
                "leaf order")
            break
        lo = p.leaf_hi
    if lo != n_leaves and not any("leaf range" in f.message for f in out):
        bad(f"chunks end at leaf {lo}, tree has {n_leaves}")
    return out


def check_barrier_chain(jaxpr, *, n_chunks: int, config: str = ""
                        ) -> List[Finding]:
    """TCDP004 (jaxpr level): a ``K``-chunk sync must carry ``K-1``
    ``optimization_barrier`` links, each with a collective ancestor — the
    dependency chain that keeps the chunk collectives K separate, ordered
    instructions (defeating XLA's all-reduce combiner)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    chained = 0

    def scan_scope(j) -> None:
        nonlocal total, chained
        producers: Dict[Any, Any] = {}
        for eqn in j.eqns:
            for ov in eqn.outvars:
                producers[ov] = eqn
        for eqn in j.eqns:
            if eqn.primitive.name == "optimization_barrier":
                total += 1
                seen: Set[Any] = set()
                frontier = [v for v in eqn.invars if _is_var(v)]
                hit = False
                while frontier and not hit:
                    v = frontier.pop()
                    if v in seen:
                        continue
                    seen.add(v)
                    p = producers.get(v)
                    if p is None:
                        continue
                    if (p.primitive.name in COLLECTIVE_PRIMS
                            or any(collective_signature(s)
                                   for s in _sub_jaxprs(p))):
                        hit = True
                        break
                    frontier.extend(iv for iv in p.invars
                                    if _is_var(iv))
                chained += bool(hit)
            for sub in _sub_jaxprs(eqn):
                scan_scope(sub)

    scan_scope(jaxpr)
    need = max(0, int(n_chunks) - 1)
    if total < need or chained < need:
        return [Finding(
            code="TCDP004", config=config,
            message=f"{n_chunks}-chunk sync carries {total} "
                    f"optimization_barrier(s), {chained} with a collective "
                    f"ancestor; need >= {need} chained barriers to pin "
                    "chunk issue order")]
    return []


#: TCDP005 budgets — measured 2026-08 quick-matrix maxima (~500 eqns for a
#: sync trace, ~1530 for the LM step) with ~3x headroom.  An unrolled
#: 11-leaf loop multiplies a trace ~10x, so it trips the budget long
#: before trace time becomes painful.  Default-mode traces only: force
#: mode off-TPU runs kernels under the Pallas interpreter, which inlines
#: kernel bodies into the jaxpr and is not what ships.
EQN_BUDGET_SYNC = 1500
EQN_BUDGET_STEP = 4500


def count_eqns(jaxpr) -> int:
    """Total equation count of a (Closed)Jaxpr, recursing into every
    sub-jaxpr — the size measure TCDP005 budgets.  Loop bodies count ONCE
    (a ``scan`` over K chunks adds its body once), which is exactly why
    the budget separates rolled from unrolled programs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in _sub_jaxprs(eqn):
            n += count_eqns(sub)
    return n


def check_jaxpr_budget(jaxpr, *, budget: int, config: str = ""
                       ) -> List[Finding]:
    """TCDP005: one traced config must fit its equation budget."""
    n = count_eqns(jaxpr)
    if n > budget:
        return [Finding(
            code="TCDP005", config=config,
            message=f"traced jaxpr holds {n} equations, budget {budget} — "
                    "a Python loop over leaves/chunks/devices is probably "
                    "unrolling into the trace (use scan/fori_loop, or raise "
                    "the budget with a justification if growth is real)")]
    return []


# -------------------------------------------------------- tracing the tree

def _mesh(n: int):
    from tpu_compressed_dp.parallel.mesh import make_data_mesh
    return make_data_mesh(n)


def _grads():
    import jax.numpy as jnp
    return {"w": jnp.zeros((64, 8)), "b": jnp.zeros((8,)),
            "v": jnp.zeros((32, 4))}


def trace_sync(cfg, mesh, *, chunked: bool = False):
    """Trace one engine config under shard_map to a ClosedJaxpr (returns
    ``(closed_jaxpr, n_leaves, n_groups, plans)``; plans is None unless
    ``chunked``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_compressed_dp.parallel import dp, overlap

    grads = _grads()
    leaves = jax.tree.leaves(grads)
    byte_sizes = [l.size * l.dtype.itemsize for l in leaves]
    groups = dp.make_leaf_groups(byte_sizes, cfg.granularity,
                                 cfg.bucket_mb * dp.BUCKET_MB)
    plans = overlap.plan_chunks(byte_sizes, cfg) if chunked else None
    sync = (overlap.make_chunked_grad_sync(cfg) if chunked
            else dp.make_grad_sync(cfg))
    ef = (jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
          if cfg.error_feedback else ())
    comp = dp.init_comp_state(grads, cfg)

    def f(g, e, c, k):
        return sync(g, e, c, k, ok=jnp.asarray(True))

    # trace-only probe: the outputs are per-worker values that P() does not
    # describe, so the replication check is off (the collective program is
    # the same either way)
    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P(), P(), P()),
                       out_specs=P(), check_vma=False)
    closed = jax.make_jaxpr(sm)(grads, ef, comp, jax.random.key(0))
    return closed, len(leaves), len(groups), plans


def _engine_configs(profile: str):
    from tpu_compressed_dp.parallel.dp import CompressionConfig

    def mk(m, mode, transport, gran, **kw):
        ef = kw.pop("error_feedback", m not in (None, "terngrad", "qsgd"))
        if transport == "hierarchical":
            # 2x2 virtual mesh on the lint pass's 4-device data axis —
            # exercises both the grouped ICI psums and the grouped DCN
            # route/return collectives
            kw.setdefault("dp_pods", 2)
        return CompressionConfig(method=m, granularity=gran, mode=mode,
                                 transport=transport, ratio=0.25,
                                 error_feedback=ef, check_sync=True, **kw)

    if profile == "full":
        return [mk(m, mode, tr, gran) for m, mode, tr, gran in
                itertools.product(ENGINE_METHODS, ("simulate", "wire"),
                                  ("allgather", "sharded", "hierarchical"),
                                  ("layerwise", "entiremodel", "bucketed"))]
    # quick: each method once on the wire path, plus transport/granularity
    # variants for the index-carrying representative
    cfgs = [mk(m, "wire", "allgather", "bucketed") for m in ENGINE_METHODS]
    cfgs += [mk("topk", "wire", "sharded", "bucketed"),
             mk("topk", "wire", "hierarchical", "bucketed"),
             mk("thresholdv", "wire", "hierarchical", "entiremodel"),
             mk("topk", "simulate", "hierarchical", "bucketed"),
             mk("topk", "wire", "allgather", "layerwise"),
             mk("topk", "wire", "allgather", "entiremodel"),
             mk("topk", "simulate", "allgather", "bucketed")]
    return cfgs


def _cfg_label(cfg, suffix: str = "") -> str:
    lab = (f"{cfg.method or 'none'}/{cfg.mode}/{cfg.transport}/"
           f"{cfg.granularity}/ef={int(cfg.error_feedback)}")
    return f"{lab}{suffix}"


def _chunk_configs(profile: str):
    from tpu_compressed_dp.parallel.dp import CompressionConfig

    methods = (ENGINE_METHODS if profile == "full"
               else (None, "topk", "powersgd"))
    return [CompressionConfig(method=m, granularity="layerwise", mode="wire",
                              transport="allgather", ratio=0.25,
                              error_feedback=m not in (None, "terngrad",
                                                       "qsgd"),
                              check_sync=True, sync_overlap=3)
            for m in methods]


def _check_engines(profile: str, mesh) -> Tuple[List[Finding], int]:
    findings: List[Finding] = []
    n = 0
    sig_cache: Dict[str, List[Sig]] = {}
    for cfg in _engine_configs(profile):
        label = _cfg_label(cfg)
        closed, _, _, _ = trace_sync(cfg, mesh)
        closed2, _, _, _ = trace_sync(cfg, mesh)
        n += 2
        findings += check_control_flow(closed, config=label)
        findings += check_jaxpr_budget(closed, budget=EQN_BUDGET_SYNC,
                                       config=label)
        sig = collective_signature(closed)
        findings += check_signature_match(
            sig, collective_signature(closed2), "trace#1", "trace#2",
            config=label)
        sig_cache[label] = sig
    # simulate == wire where the equivalence tests claim it: the dense psum
    # path (method None) is shared by construction
    for tr in ("allgather",):
        a = sig_cache.get(f"none/simulate/{tr}/bucketed/ef=0")
        b = sig_cache.get(f"none/wire/{tr}/bucketed/ef=0")
        if a is not None and b is not None:
            findings += check_signature_match(
                a, b, "simulate engine", "wire engine",
                config=f"none/{tr}/bucketed")
    # chunk-pipelined schedule: same collectives, chained issue order
    import dataclasses
    for cfg in _chunk_configs(profile):
        label = _cfg_label(cfg, suffix=f"/overlap={cfg.sync_overlap}")
        chunked, n_leaves, n_groups, plans = trace_sync(cfg, mesh,
                                                        chunked=True)
        single, _, _, _ = trace_sync(
            dataclasses.replace(cfg, sync_overlap=1), mesh)
        n += 2
        findings += check_control_flow(chunked, config=label)
        findings += check_jaxpr_budget(chunked, budget=EQN_BUDGET_SYNC,
                                       config=label)
        findings += check_chunk_plan(plans, n_leaves=n_leaves,
                                     n_groups=n_groups, config=label)
        findings += check_signature_match(
            collective_signature(chunked), collective_signature(single),
            "chunked", "single-dispatch", config=label, ordered=False)
        findings += check_barrier_chain(chunked, n_chunks=len(plans),
                                        config=label)
    findings_p, n_p = _check_pallas_variants(profile, mesh)
    return findings + findings_p, n + n_p


def _pallas_variant_configs(profile: str):
    """Fused-kernel representatives: one per kernel family (select+pack on
    allgather, bucket-route on sharded/hierarchical, quantize+pack for
    terngrad/qsgd) — the paths where ``pallas_mode`` changes the emitted
    step program."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig

    def mk(m, transport, **kw):
        if transport == "hierarchical":
            kw.setdefault("dp_pods", 2)
        return CompressionConfig(method=m, granularity="entiremodel",
                                 mode="wire", transport=transport,
                                 ratio=0.25,
                                 error_feedback=m not in ("terngrad", "qsgd"),
                                 check_sync=True, **kw)

    # quick: one select+pack path and one quantize+pack path (the force
    # traces inline interpreted kernel bodies, so each pair costs ~1 s —
    # the quick gate rides tier-1's wall budget); full: every family x
    # transport representative
    cfgs = [mk("topk", "allgather"), mk("terngrad", "allgather")]
    if profile == "full":
        cfgs += [mk("topk", "sharded"), mk("qsgd", "allgather"),
                 mk("topk", "hierarchical"), mk("blocktopk", "sharded"),
                 mk("thresholdv", "hierarchical"),
                 mk("adaptive_threshold", "allgather")]
    return cfgs


def _check_pallas_variants(profile: str, mesh) -> Tuple[List[Finding], int]:
    """TCDP002 across the ``pallas_mode`` toggle: the fused kernels are
    pure local compute, so forcing them on (or off) may never add, drop
    or reorder a collective relative to the XLA fallback chain.  Traced
    only — ``make_jaxpr`` abstract-evals the pallas_call, so this pins the
    TPU dispatch shape from the CPU lint pass."""
    from tpu_compressed_dp.ops import kernels

    findings: List[Finding] = []
    n = 0
    for cfg in _pallas_variant_configs(profile):
        label = _cfg_label(cfg, suffix="/pallas")
        prev = kernels.pallas_mode()
        try:
            kernels.set_pallas_mode("off")
            off_closed, _, _, _ = trace_sync(cfg, mesh)
            kernels.set_pallas_mode("force")
            on_closed, _, _, _ = trace_sync(cfg, mesh)
        finally:
            kernels.set_pallas_mode(prev)
        n += 2
        findings += check_control_flow(on_closed, config=label)
        findings += check_signature_match(
            collective_signature(off_closed), collective_signature(on_closed),
            "pallas=off", "pallas=force", config=label)
        # budget the off trace only: force off-TPU interprets, inlining
        # kernel bodies the shipped program never holds
        findings += check_jaxpr_budget(off_closed, budget=EQN_BUDGET_SYNC,
                                       config=label)
    return findings, n


def _check_train_step(profile: str) -> Tuple[List[Finding], int]:
    """Trace the pure-DP train step factory (donation on, guard on, and an
    overlap variant) and run all four checks."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    import flax.linen as nn
    from tpu_compressed_dp.models.common import init_model, make_apply_fn
    from tpu_compressed_dp.parallel.dp import (CompressionConfig,
                                               init_comp_state,
                                               init_ef_state)
    from tpu_compressed_dp.train.guard import GuardConfig, init_guard_state
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.state import TrainState
    from tpu_compressed_dp.train.step import make_train_step

    class _Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))

    mesh = _mesh(4)
    module = _Tiny()
    params, stats = init_model(module, jax.random.key(0),
                               jnp.zeros((1, 8, 8, 3), jnp.float32))
    opt = SGD(lr=0.05, momentum=0.9)
    apply_fn = make_apply_fn(module)
    batch = {"input": jnp.zeros((8, 8, 8, 3), jnp.float32),
             "target": jnp.zeros((8,), jnp.int32)}

    cfgs = [CompressionConfig(method="topk", ratio=0.25,
                              error_feedback=True),
            CompressionConfig(method="topk", ratio=0.25, error_feedback=True,
                              sync_overlap=3, granularity="layerwise")]
    if profile == "full":
        cfgs += [CompressionConfig(method=None),
                 CompressionConfig(method="powersgd", rank=2,
                                   error_feedback=True),
                 CompressionConfig(method="qsgd", mode="wire")]

    findings: List[Finding] = []
    n = 0
    guard_cfg = GuardConfig()
    for cfg in cfgs:
        label = _cfg_label(cfg, suffix=f"/step(overlap={cfg.sync_overlap})")
        ef = init_ef_state(params, cfg, num_devices=mesh.shape["data"])
        comp = init_comp_state(params, cfg, num_devices=mesh.shape["data"])
        state = TrainState.create(params, stats, opt.init(params), ef,
                                  jax.random.key(1), comp=comp,
                                  guard=init_guard_state(guard_cfg))
        step = make_train_step(apply_fn, opt, cfg, mesh, grad_scale=1.0,
                               donate=True, guard_cfg=guard_cfg)
        closed = jax.make_jaxpr(step)(state, batch)
        n += 1
        findings += check_control_flow(closed, config=label)
        findings += check_jaxpr_budget(closed, budget=EQN_BUDGET_STEP,
                                       config=label)
        findings += check_donation(step, (state, batch), (0,), config=label)
        if profile == "full":
            closed2 = jax.make_jaxpr(step)(state, batch)
            n += 1
            findings += check_signature_match(
                collective_signature(closed), collective_signature(closed2),
                "trace#1", "trace#2", config=label)
        if cfg.sync_overlap > 1:
            from tpu_compressed_dp.parallel.dp import (BUCKET_MB,
                                                       make_leaf_groups)
            from tpu_compressed_dp.parallel.overlap import plan_chunks
            byte_sizes = [l.size * l.dtype.itemsize
                          for l in jax.tree.leaves(params)]
            plans = plan_chunks(byte_sizes, cfg)
            findings += check_chunk_plan(
                plans, n_leaves=len(byte_sizes),
                n_groups=len(make_leaf_groups(byte_sizes, cfg.granularity,
                                              cfg.bucket_mb * BUCKET_MB)),
                config=label)
            findings += check_barrier_chain(closed, n_chunks=len(plans),
                                            config=label)
    # adaptive-controller rung pair: a rung switch swaps one trace-cached
    # step variant for another, and TCDP002 pins the only thing allowed to
    # change — the k-dependent operand SHAPES.  The ordered (primitive,
    # axis) program must be identical across rungs, or a mid-run switch
    # would reorder/add collectives and desync any worker that traced the
    # other rung.
    from tpu_compressed_dp.control import (ControlConfig, comp_for_rung,
                                           init_control_state)

    ctrl = ControlConfig(method="topk", rungs=(0.25, 0.125))
    rung_sigs = {}
    for rung in (0, 1):
        rcfg = comp_for_rung(cfgs[0], ctrl, rung)
        label = _cfg_label(rcfg, suffix=f"/step(rung={rung})")
        ef = init_ef_state(params, rcfg, num_devices=mesh.shape["data"])
        comp = init_comp_state(params, rcfg, num_devices=mesh.shape["data"])
        state = TrainState.create(params, stats, opt.init(params), ef,
                                  jax.random.key(1), comp=comp,
                                  guard=init_guard_state(guard_cfg),
                                  control=init_control_state(ctrl))
        step = make_train_step(apply_fn, opt, rcfg, mesh, grad_scale=1.0,
                               donate=True, guard_cfg=guard_cfg)
        closed = jax.make_jaxpr(step)(state, batch)
        n += 1
        findings += check_control_flow(closed, config=label)
        findings += check_jaxpr_budget(closed, budget=EQN_BUDGET_STEP,
                                       config=label)
        findings += check_donation(step, (state, batch), (0,), config=label)
        rung_sigs[rung] = collective_signature(closed)
    findings += check_signature_match(
        [s[:2] for s in rung_sigs[0]], [s[:2] for s in rung_sigs[1]],
        "rung0 (prim, axes)", "rung1 (prim, axes)",
        config="topk/step(rung-pair)")
    return findings, n


def _check_lm_step(profile: str) -> Tuple[List[Finding], int]:
    import jax

    from tpu_compressed_dp.models import transformer as tf
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.lm_step import (init_lm_ef_state,
                                                 make_lm_mesh,
                                                 make_lm_train_step)
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.state import TrainState

    cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                         n_kv_heads=2, ffn_hidden=64, dtype=jax.numpy.float32)
    mesh = make_lm_mesh(2, 2, 2)
    params = tf.init_llama(cfg, jax.random.key(0))
    opt = SGD(lr=0.1, momentum=0.9)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.05, error_feedback=True)
    state = TrainState.create(params, {}, opt.init(params),
                              init_lm_ef_state(cfg, params, comp, mesh),
                              jax.random.key(1))
    step = make_lm_train_step(cfg, opt, comp, mesh, donate=True)
    batch = {"input": jax.numpy.zeros((4, 16), jax.numpy.int32),
             "target": jax.numpy.zeros((4, 16), jax.numpy.int32)}
    label = "lm_step/topk/entiremodel/ef=1"
    closed = jax.make_jaxpr(step)(state, batch)
    findings = check_control_flow(closed, config=label)
    findings += check_jaxpr_budget(closed, budget=EQN_BUDGET_STEP,
                                   config=label)
    findings += check_donation(step, (state, batch), (0,), config=label)
    n = 1
    if profile == "full":
        closed2 = jax.make_jaxpr(step)(state, batch)
        n += 1
        findings += check_signature_match(
            collective_signature(closed), collective_signature(closed2),
            "trace#1", "trace#2", config=label)
    return findings, n


def _check_pp_step(profile: str) -> Tuple[List[Finding], int]:
    import jax

    from tpu_compressed_dp.models import transformer as tf
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.pp_step import (init_pp_ef_state,
                                                 make_pp_mesh,
                                                 make_pp_train_step,
                                                 stack_layer_params)
    from tpu_compressed_dp.train.state import TrainState

    cfg = tf.LlamaConfig(vocab_size=64, dim=32, n_layers=4, n_heads=4,
                         n_kv_heads=2, ffn_hidden=64, dtype=jax.numpy.float32)
    mesh = make_pp_mesh(2, 2)
    comp = CompressionConfig(method="topk", granularity="entiremodel",
                             ratio=0.05, error_feedback=True)
    params = stack_layer_params(tf.init_llama(cfg, jax.random.key(0)))
    opt = SGD(lr=0.1, momentum=0.9)
    state = TrainState.create(params, {}, opt.init(params),
                              init_pp_ef_state(cfg, params, comp, mesh),
                              jax.random.key(3))
    step = make_pp_train_step(cfg, opt, comp, mesh, microbatches=2,
                              donate=True)
    batch = {"input": jax.numpy.zeros((8, 16), jax.numpy.int32),
             "target": jax.numpy.zeros((8, 16), jax.numpy.int32)}
    label = "pp_step/topk/entiremodel/ef=1"
    closed = jax.make_jaxpr(step)(state, batch)
    findings = check_control_flow(closed, config=label)
    findings += check_jaxpr_budget(closed, budget=EQN_BUDGET_STEP,
                                   config=label)
    findings += check_donation(step, (state, batch), (0,), config=label)
    n = 1
    if profile == "full":
        closed2 = jax.make_jaxpr(step)(state, batch)
        n += 1
        findings += check_signature_match(
            collective_signature(closed), collective_signature(closed2),
            "trace#1", "trace#2", config=label)
    return findings, n


def run_spmd_pass(profile: str = "quick") -> Tuple[List[Finding],
                                                   Dict[str, int]]:
    """Trace the real tree and run every check.  ``profile='quick'`` is the
    tier-1 gate (each method + the structural variants); ``'full'`` is the
    CLI's complete method x mode x transport x granularity matrix."""
    import jax

    if len(jax.devices()) < 4:
        raise RuntimeError(
            "tcdp-lint pass 1 needs >= 4 devices (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = _mesh(4)
    findings: List[Finding] = []
    traced = 0
    for part in (lambda: _check_engines(profile, mesh),
                 lambda: _check_train_step(profile),
                 lambda: _check_lm_step(profile),
                 lambda: _check_pp_step(profile)):
        f, n = part()
        findings += f
        traced += n
    return findings, {"configs_traced": traced}
