"""Stage-accounted profile of the element-wise wire Top-K sync chain.

The element Top-K wire path (`ops/wire.py:_leaf_sync_topk`) has been the
framework's slowest mode for three rounds (~2.4x dense at the 125M LM
config).  Round 4's diagnosis named four element-granular stages — threshold,
payload gather, scatter-add reconstruction, EF scatter — without individual
numbers on the current code.  This tool produces those numbers the trustworthy
way (round-4 memory: standalone op timings at this scale thrash the allocator
and lie): a ladder of CUMULATIVE prefix chains, each jitted with donated
inputs and run under `shard_map` over a 1-device data axis exactly like the
harness step; per-stage cost is the difference between consecutive rungs.
Every rung returns a scalar that data-depends on all its stages so XLA cannot
DCE a stage out of a longer rung.

Usage (on the TPU chip):
    python tools/wire_profile.py --n 125000000 --ratio 0.01 [--iters 30]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

if __package__ in (None, ""):  # script run: repo root onto sys.path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from tpu_compressed_dp.ops import compressors, kernels, wire


def _stage_chain(upto: str, n: int, keep: int, axis_name: str = "data"):
    """Build a chain running stages up to and including `upto`.

    Stage order: mag -> threshold -> pack -> gather -> combine -> ef.
    Returns (out_scalar,) so everything stays live.
    """

    def chain(flat: jax.Array):
        mag = jnp.abs(flat).astype(jnp.float32)
        out = jnp.sum(mag[:8])
        if upto == "mag":
            return out
        t = kernels.topk_threshold(mag, keep)
        out = out + t
        if upto == "threshold":
            return out
        mask = mag >= t
        idx = wire.packed_indices_from_mask(mask, keep)
        out = out + jnp.sum(idx[:8].astype(jnp.float32))
        if upto == "pack":
            return out
        payload = wire._sorted_gather(flat, idx)
        out = out + jnp.sum(payload[:8])
        if upto == "gather":
            return out
        world = jax.lax.psum(1, axis_name)
        g_vals = wire._all_gather(payload, axis_name)
        g_idx = wire._all_gather(idx, axis_name)
        dense = wire._scatter_combine(flat.shape, flat.dtype, g_idx, g_vals,
                                      world)
        out = out + jnp.sum(dense[:8])
        if upto == "combine":
            return out
        # the residual as `_leaf_sync_topk` makes it (the threshold leaves
        # at least `keep` survivors): one streamed pass, no scatter
        upto_last = jnp.arange(n, dtype=jnp.int32) <= idx[keep - 1]
        new_ef = jnp.where((mag >= t) & upto_last, 0, flat)
        out = out + jnp.sum(new_ef[:8])
        return out

    return chain


def _pack_sub_chain(upto: str, n: int, keep: int):
    """Sub-stages of the SHIPPED packed_indices_from_mask (row starts from
    a scan over the ranks, one row gather + bf16 MXU tri-matmul), cumulative from the
    threshold rung.  Mirrors ops/wire.py — update both together."""

    def chain(flat: jax.Array):
        lanes = 128
        mag = jnp.abs(flat).astype(jnp.float32)
        t = kernels.topk_threshold(mag, keep)
        mask = mag >= t
        pad = (-n) % lanes
        m2 = jnp.pad(mask, (0, pad)).reshape(-1, lanes)
        row_counts = jnp.sum(m2, axis=1, dtype=jnp.int32)
        out = jnp.sum(row_counts[:8].astype(jnp.float32))
        if upto == "p_rowcounts":
            return out
        row_ends = jnp.cumsum(row_counts)
        ends_hist = jnp.zeros((keep + 1,), jnp.int32).at[
            jnp.minimum(row_ends, keep)].add(
                1, indices_are_sorted=True, mode="promise_in_bounds")
        out = out + jnp.sum(ends_hist[:8].astype(jnp.float32))
        if upto == "p_hist":
            return out
        row_of = jnp.cumsum(ends_hist)[:keep]
        valid = row_of < m2.shape[0]
        row_of = jnp.where(valid, row_of, m2.shape[0] - 1)
        out = out + jnp.sum(row_of[:8].astype(jnp.float32))
        if upto == "p_rowof":
            return out
        ranks = jnp.arange(1, keep + 1, dtype=jnp.int32)
        within = ranks - kernels.run_starts(row_of)
        out = out + jnp.sum(within[:8].astype(jnp.float32))
        if upto == "p_starts":
            return out
        rows = wire._sorted_gather(m2, row_of).astype(jnp.bfloat16)
        out = out + jnp.sum(rows[:8].astype(jnp.float32))
        if upto == "p_rowgather":
            return out
        tri = jnp.tril(jnp.ones((lanes, lanes), jnp.bfloat16))
        prefix = jax.lax.dot(rows, tri.T,
                             preferred_element_type=jnp.float32)
        hit = (prefix >= within[:, None].astype(jnp.float32)) & (rows > 0)
        col = jnp.argmax(hit, axis=1).astype(jnp.int32)
        idx = jnp.where(valid, row_of * lanes + col, 0)
        return out + jnp.sum(idx[:8].astype(jnp.float32))

    return chain


PACK_SUBS = ["p_rowcounts", "p_hist", "p_rowof", "p_starts",
             "p_rowgather", "p_matmul"]


def _pack_scatter_chain(n: int, keep: int, axis_name: str = "data"):
    """EXPERIMENT: replace pack+gather+EF with one elementwise slot
    computation + a sorted full-tensor scatter-add.

    Every element's payload slot is computable without any per-rank gather:
    ``slot = row_start[row] + in_row_prefix - 1`` (in-row prefix = one MXU
    tri-matmul over the full mask).  Dead elements alias the most recent
    live slot with a 0 contribution, keeping the flattened slot sequence
    nondecreasing, so ONE scatter-add with ``indices_are_sorted=True``
    emits the packed (values, indices) payload in a single streaming pass —
    if XLA's TPU scatter lowering honours the hint.  EF is elementwise.
    """

    def chain(flat: jax.Array):
        lanes = 128
        mag = jnp.abs(flat).astype(jnp.float32)
        t = kernels.topk_threshold(mag, keep)
        pad = (-n) % lanes
        m2 = jnp.pad(mag >= t, (0, pad)).reshape(-1, lanes)
        cnt = jnp.sum(m2, axis=1, dtype=jnp.int32)
        row_end = jnp.cumsum(cnt)
        row_start = row_end - cnt
        tri = jnp.tril(jnp.ones((lanes, lanes), jnp.float32))
        prefix = (m2.astype(jnp.float32) @ tri.T).astype(jnp.int32)  # inclusive
        slot = row_start[:, None] + jnp.maximum(prefix - 1, 0)
        slot = jnp.minimum(slot, keep)          # overflow + tail -> slot `keep`
        live = m2 & (slot < keep) & (prefix > 0)
        sf = slot.reshape(-1)
        acc_pad = jnp.pad(flat, (0, pad))
        pos = jnp.arange(n + pad, dtype=jnp.int32)
        contrib_v = jnp.where(live.reshape(-1), acc_pad, 0.0)
        contrib_i = jnp.where(live.reshape(-1), pos, 0)
        vals = jnp.zeros((keep + 1,), flat.dtype).at[sf].add(
            contrib_v, indices_are_sorted=True, mode="promise_in_bounds")[:keep]
        idx = jnp.zeros((keep + 1,), jnp.int32).at[sf].add(
            contrib_i, indices_are_sorted=True, mode="promise_in_bounds")[:keep]
        new_ef = jnp.where(mag >= t, 0.0, flat)          # elementwise EF
        world = jax.lax.psum(1, axis_name)
        g_vals = wire._all_gather(vals, axis_name)
        g_idx = wire._all_gather(idx, axis_name)
        dense = (jnp.zeros(flat.shape, flat.dtype)
                 .at[g_idx.reshape(-1)].add(g_vals.reshape(-1)) / world)
        return jnp.sum(dense[:8]) + jnp.sum(new_ef[:8]) + jnp.sum(vals[:8])

    return chain


def _sharded_chain(upto: str, n: int, keep: int, cfg, axis_name: str = "data"):
    """Stage ladder for the OWNER-SHARDED transport (transport='sharded'):
    mag -> threshold -> select_pack (the shipped `wire._select_pack`
    dispatch: one fused Pallas pass or the XLA mask/pack/gather chain,
    depending on `kernels.pallas_mode()`) -> route (dispatch-aware bucket
    build + all_to_all) -> reduce (owner scatter-add) -> return (shard
    all_gather + scatter/concat) -> ef.  Mirrors
    ops/wire_sharded.sharded_combine — update both together.  On one device the collectives are self-copies, so the route/
    return rungs price the bucketisation and reduction machinery, not link
    time — the same caveat as the base ladder's all_gather rungs."""
    from tpu_compressed_dp.ops import wire_sharded

    def chain(flat: jax.Array):
        mag = jnp.abs(flat).astype(jnp.float32)
        out = jnp.sum(mag[:8])
        if upto == "mag":
            return out
        t = kernels.topk_threshold(mag, keep)
        out = out + t
        if upto == "threshold":
            return out
        vals, idx, _cnt = wire._select_pack(flat, mag, t, keep)
        out = (out + jnp.sum(idx[:8].astype(jnp.float32))
               + jnp.sum(vals[:8]))
        if upto == "select_pack":
            return out
        world = jax.lax.psum(1, axis_name)
        plan = wire_sharded.make_shard_plan(
            n, keep, world, 1, cfg.shard_route_factor, cfg.shard_return_factor)
        W, cap, shard_n = plan.world, plan.cap_dest, plan.shard_n
        slot, accepted, dest = wire_sharded._per_dest_slots(idx, None, plan)
        local = (idx - dest * shard_n).astype(jnp.int32)
        if kernels.use_bucket_route(idx.shape[0], W, cap):
            bvals, bidx = kernels.fused_bucket_route(
                vals, idx, dest, W, cap, shard_n)
        else:
            bvals = jnp.zeros((W * cap + 1,), flat.dtype
                              ).at[slot].add(vals)[:-1].reshape(W, cap)
            bidx = jnp.full((W * cap + 1,), shard_n, jnp.int32
                            ).at[slot].set(local)[:-1].reshape(W, cap)
        rvals = jax.lax.all_to_all(bvals, axis_name, 0, 0)
        ridx = jax.lax.all_to_all(bidx, axis_name, 0, 0)
        out = out + jnp.sum(rvals[0, :8])
        if upto == "route":
            return out
        shard = jnp.zeros((shard_n + 1,), flat.dtype)
        occ = jnp.zeros((shard_n + 1,), jnp.int32)
        if W <= 16:
            for w in range(W):
                shard = shard.at[ridx[w]].add(
                    rvals[w], indices_are_sorted=True,
                    mode="promise_in_bounds")
                occ = occ.at[ridx[w]].add(
                    1, indices_are_sorted=True, mode="promise_in_bounds")
        else:
            shard = shard.at[ridx.reshape(-1)].add(rvals.reshape(-1))
            occ = occ.at[ridx.reshape(-1)].add(1)
        shard, occ = shard[:shard_n], occ[:shard_n]
        out = out + jnp.sum(shard[:8])
        if upto == "reduce":
            return out
        if plan.dense_return:
            dense = wire._all_gather(shard, axis_name).reshape(-1)[:n] / world
        else:
            mask = occ > 0
            rix = wire.packed_indices_from_mask(mask, plan.cap_ret)
            rvalid = (jnp.arange(1, plan.cap_ret + 1, dtype=jnp.int32)
                      <= jnp.minimum(jnp.sum(mask, dtype=jnp.int32),
                                     plan.cap_ret))
            sel = jnp.where(rvalid, shard.at[rix].get(
                mode="promise_in_bounds"), 0)
            g_v = wire._all_gather(sel, axis_name)
            g_i = wire._all_gather(jnp.where(rvalid, rix, 0), axis_name)
            offs = jnp.arange(W, dtype=jnp.int32)[:, None] * shard_n
            dense = (jnp.zeros((W * shard_n,), flat.dtype)
                     .at[(g_i + offs).reshape(-1)].add(g_v.reshape(-1))
                     [:n] / world)
        out = out + jnp.sum(dense[:8])
        if upto == "return":
            return out
        new_ef = flat.at[idx].set(0, indices_are_sorted=True,
                                  unique_indices=True,
                                  mode="promise_in_bounds")
        return out + jnp.sum(new_ef[:8])

    return chain


def _hier_chain(upto: str, n: int, keep: int, cfg, axis_name: str = "data"):
    """Stage ladder for the HIERARCHICAL transport (transport=
    'hierarchical'): mag -> threshold -> pack (the shipped
    `wire._select_pack` dispatch + scatter the dense
    contribution) -> ici_reduce (intra-pod dense psum) -> recompress (pod
    union pack + per-chip slab slice) -> dcn_route (the grouped owner-
    sharded exchange across pods) -> return (the second intra-pod psum
    summing disjoint slab partials) -> ef.  Mirrors ops/wire._hier_combine
    — update both together.  Run with --devices >= dp_pods*2 (forced host
    devices) so the grouped collectives exist; on fewer devices than pods
    the plan constructor raises."""
    from tpu_compressed_dp.ops import wire_sharded

    def chain(flat: jax.Array):
        mag = jnp.abs(flat).astype(jnp.float32)
        out = jnp.sum(mag[:8])
        if upto == "mag":
            return out
        t = kernels.topk_threshold(mag, keep)
        out = out + t
        if upto == "threshold":
            return out
        vals, idx, _cnt = wire._select_pack(flat, mag, t, keep)
        contrib = jnp.zeros((n,), flat.dtype).at[idx].set(
            vals, indices_are_sorted=True, unique_indices=True,
            mode="promise_in_bounds")
        out = out + jnp.sum(contrib[:8])
        if upto == "pack":
            return out
        world = jax.lax.psum(1, axis_name)
        plan = wire_sharded.make_hier_plan(
            n, keep, world, cfg.dp_pods, cfg.hier_route_factor_ici,
            cfg.hier_route_factor_dcn)
        pods, chips = plan.pods, plan.chips
        ici_groups, dcn_groups = wire_sharded.hier_axis_groups(world, pods)
        pod_sum = (jax.lax.psum(contrib, axis_name,
                                axis_index_groups=ici_groups)
                   if chips > 1 else contrib)
        out = out + jnp.sum(pod_sum[:8])
        if upto == "ici_reduce":
            return out
        cap = plan.cap_union
        mask = pod_sum != 0
        nnz = jnp.sum(mask, dtype=jnp.int32)
        uidx = wire.packed_indices_from_mask(mask, cap)
        uvalid = (jnp.arange(1, cap + 1, dtype=jnp.int32)
                  <= jnp.minimum(nnz, cap))
        uvals = jnp.where(
            uvalid, pod_sum.at[uidx].get(mode="promise_in_bounds"), 0.0)
        uidx = jnp.where(uvalid, uidx, 0)
        c_rank = jax.lax.axis_index(axis_name) % chips
        s_vals = jax.lax.dynamic_slice_in_dim(
            uvals, c_rank * plan.slab, plan.slab)
        s_idx = jax.lax.dynamic_slice_in_dim(
            uidx, c_rank * plan.slab, plan.slab)
        s_valid = jax.lax.dynamic_slice_in_dim(
            uvalid, c_rank * plan.slab, plan.slab)
        out = out + jnp.sum(s_vals[:8])
        if upto == "recompress":
            return out
        dense_u, _, _, _, _ = wire_sharded.sharded_combine(
            s_vals, s_idx, plan.dcn, axis_name, valid=s_valid,
            axis_index_groups=dcn_groups)
        partial = dense_u[:n]
        out = out + jnp.sum(partial[:8])
        if upto == "dcn_route":
            return out
        total = (jax.lax.psum(partial, axis_name,
                              axis_index_groups=ici_groups)
                 if chips > 1 else partial)
        out = out + jnp.sum(total[:8]) / world
        if upto == "return":
            return out
        new_ef = flat.at[idx].set(0, indices_are_sorted=True,
                                  unique_indices=True,
                                  mode="promise_in_bounds")
        return out + jnp.sum(new_ef[:8])

    return chain


def _dispatch_chain(upto: str, n: int, keep: int, axis_name: str = "data"):
    """Ladder over the SHIPPED select+pack dispatch (`wire._select_pack`):
    one rung covers select+pack+gather, because that is exactly what the
    fused kernel collapses.  Under ``pallas off`` the rung lowers to the
    XLA mask -> `packed_indices_from_mask` -> `_sorted_gather` chain; under
    auto/force it is one `kernels.fused_select_pack` call — so timing the
    SAME ladder under both modes prices the toggle on identical stage
    boundaries (the `--compare` table)."""

    def chain(flat: jax.Array):
        mag = jnp.abs(flat).astype(jnp.float32)
        out = jnp.sum(mag[:8])
        if upto == "mag":
            return out
        t = kernels.topk_threshold(mag, keep)
        out = out + t
        if upto == "threshold":
            return out
        vals, idx, count = wire._select_pack(flat, mag, t, keep)
        out = (out + jnp.sum(vals[:8])
               + jnp.sum(idx[:8].astype(jnp.float32))
               + count.astype(jnp.float32))
        if upto == "select_pack":
            return out
        world = jax.lax.psum(1, axis_name)
        g_vals = wire._all_gather(vals, axis_name)
        g_idx = wire._all_gather(idx, axis_name)
        dense = wire._scatter_combine(flat.shape, flat.dtype, g_idx, g_vals,
                                      world)
        out = out + jnp.sum(dense[:8])
        if upto == "combine":
            return out
        new_ef = flat.at[idx].set(0, indices_are_sorted=True,
                                  unique_indices=True,
                                  mode="promise_in_bounds")
        return out + jnp.sum(new_ef[:8])

    return chain


STAGES = ["mag", "threshold", "pack", "gather", "combine", "ef"]
DISPATCH_STAGES = ["mag", "threshold", "select_pack", "combine", "ef"]
SHARDED_STAGES = ["mag", "threshold", "select_pack", "route", "reduce",
                  "return", "ef"]
HIER_STAGES = ["mag", "threshold", "pack", "ici_reduce", "recompress",
               "dcn_route", "return", "ef"]


def time_fn(fn, x, iters: int, warmup_s: float = 3.0):
    """Time-based warmup with a value fetch per burst (`jax.device_get`
    waits for the result, so it is the timing barrier)."""
    t_end = time.time() + warmup_s
    while time.time() < t_end:
        jax.device_get(fn(x))
    t0 = time.time()
    for _ in range(iters):
        out = fn(x)
    jax.device_get(out)
    return (time.time() - t0) / iters


def main(argv=None):
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=125_000_000)
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--subs", action="store_true",
                    help="also profile packed_indices_from_mask sub-stages")
    ap.add_argument("--pack2", action="store_true",
                    help="run the (negative-result) full-scatter formulation")
    ap.add_argument("--compare", action="store_true",
                    help="price the fused-kernel toggle: time the shipped "
                         "_select_pack ladder under pallas off AND "
                         "--pallas_mode, print XLA vs Pallas columns per "
                         "stage (intended home: the TPU chip — forcing "
                         "off-TPU runs kernels interpreted, which is a "
                         "correctness rehearsal, not a timing)")
    ap.add_argument("--pallas_mode", default="force",
                    choices=["auto", "force"],
                    help="the non-off column of --compare")
    ap.add_argument("--transport", default="allgather",
                    choices=["allgather", "sharded", "hierarchical"],
                    help="profile the flat all_gather combine, the "
                         "owner-sharded route/reduce/return chain, or the "
                         "two-level ici-reduce/recompress/dcn-route ladder")
    ap.add_argument("--devices", type=int, default=1,
                    help="mesh size for the ladder (sharded bucket geometry "
                         "scales with W; >1 needs forced host devices)")
    ap.add_argument("--shard_route_factor", type=float, default=1.25)
    ap.add_argument("--shard_return_factor", type=float, default=1.25)
    ap.add_argument("--dp_pods", type=int, default=2,
                    help="hierarchical: DCN axis of the dp_pods x dp_chips "
                         "virtual mesh (must divide --devices)")
    ap.add_argument("--hier_route_factor_ici", type=float, default=1.25)
    ap.add_argument("--hier_route_factor_dcn", type=float, default=1.25)
    args = ap.parse_args(argv)

    n = args.n
    keep = compressors.topk_keep_count(n, args.ratio)
    mesh = Mesh(np.array(jax.devices()[:args.devices]), ("data",))
    x = jax.device_put(
        jax.random.normal(jax.random.key(args.seed), (n,), jnp.float32))

    if args.transport == "sharded":
        from tpu_compressed_dp.parallel.dp import CompressionConfig

        cfg = CompressionConfig(
            method="topk", mode="wire", transport="sharded", ratio=args.ratio,
            shard_route_factor=args.shard_route_factor,
            shard_return_factor=args.shard_return_factor)
        stages = SHARDED_STAGES
        build = lambda st: _sharded_chain(st, n, keep, cfg)
    elif args.transport == "hierarchical":
        from tpu_compressed_dp.parallel.dp import CompressionConfig

        cfg = CompressionConfig(
            method="topk", mode="wire", transport="hierarchical",
            ratio=args.ratio, dp_pods=args.dp_pods,
            hier_route_factor_ici=args.hier_route_factor_ici,
            hier_route_factor_dcn=args.hier_route_factor_dcn)
        stages = HIER_STAGES
        build = lambda st: _hier_chain(st, n, keep, cfg)
    else:
        stages = STAGES
        build = lambda st: _stage_chain(st, n, keep)

    print(f"# wire Top-K stage ladder [{args.transport}]: n={n} keep={keep} "
          f"({100*keep/n:.2f}%) device={jax.devices()[0].platform} "
          f"W={args.devices}")
    prev = 0.0
    rows = []
    for st in stages:
        fn = jax.jit(shard_map(
            build(st),
            mesh=mesh, in_specs=P(), out_specs=P()))
        dt = time_fn(fn, x, args.iters)
        rows.append((st, dt * 1e3, (dt - prev) * 1e3))
        print(f"{st:10s} cumulative {dt*1e3:8.2f} ms   stage {max((dt-prev)*1e3, 0.0):8.2f} ms")
        prev = dt
    total = rows[-1][1]
    print(f"# chain total {total:.2f} ms; element-granular random-access "
          f"stages = gather+combine (ef is one streamed pass)")
    if args.subs:
        prev = rows[1][1] / 1e3   # threshold rung is the sub-ladder's base
        print("# pack sub-stages (cumulative from threshold rung):")
        for st in PACK_SUBS:
            fn = jax.jit(shard_map(_pack_sub_chain(st, n, keep),
                                   mesh=mesh, in_specs=P(), out_specs=P()))
            dt = time_fn(fn, x, args.iters)
            print(f"{st:14s} cumulative {dt*1e3:8.2f} ms   "
                  f"stage {max((dt-prev)*1e3, 0.0):8.2f} ms")
            prev = dt
    if args.pack2:
        fn = jax.jit(shard_map(_pack_scatter_chain(n, keep),
                               mesh=mesh, in_specs=P(), out_specs=P()))
        dt = time_fn(fn, x, args.iters)
        print(f"pack2-scatter-formulation full chain {dt*1e3:8.2f} ms "
              f"(vs ladder total {total:.2f} ms)")
    if args.compare:
        # same ladder, two dispatch modes: re-jit per mode because the
        # pallas decision is made at trace time inside _select_pack
        cols = {}
        prev_mode = kernels.pallas_mode()
        try:
            for mode in ("off", args.pallas_mode):
                kernels.set_pallas_mode(mode)
                cum = []
                for st in DISPATCH_STAGES:
                    fn = jax.jit(shard_map(_dispatch_chain(st, n, keep),
                                           mesh=mesh, in_specs=P(),
                                           out_specs=P()))
                    cum.append(time_fn(fn, x, args.iters) * 1e3)
                cols[mode] = cum
        finally:
            kernels.set_pallas_mode(prev_mode)
        xla, pal = cols["off"], cols[args.pallas_mode]
        print(f"# pallas compare [_select_pack ladder]: per-stage ms, "
              f"pallas=off vs pallas={args.pallas_mode}")
        print(f"{'stage':12s} {'xla_ms':>9s} {'pallas_ms':>9s} "
              f"{'delta_ms':>9s}")
        px = pp = 0.0
        for st, cx, cp in zip(DISPATCH_STAGES, xla, pal):
            sx, sp = max(cx - px, 0.0), max(cp - pp, 0.0)
            print(f"{st:12s} {sx:9.2f} {sp:9.2f} {sp - sx:+9.2f}")
            px, pp = cx, cp
        print(f"{'total':12s} {xla[-1]:9.2f} {pal[-1]:9.2f} "
              f"{pal[-1] - xla[-1]:+9.2f}")
    return rows


if __name__ == "__main__":
    main()
