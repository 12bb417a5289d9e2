"""The wire sync's per-size chains (`ops/wire.py:make_wire_grad_sync`): the
reduction groups that share a flat size, a dtype and a transport sync in one
traced chain: short members as one stack over a leading member axis, long
ones on their own buffers with their threshold searches in one loop.  Every
member's result must be bitwise what a chain of its own gives, and the number
of chains is the number of distinct parts, whatever the number of leaves.

Tier-1: small trees, virtual devices, kernels under the interpreter where a
case forces them; the ResNet trees are abstract and nothing of them is
compiled.
"""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpu_compressed_dp.models import resnet
from tpu_compressed_dp.ops import kernels, wire
from tpu_compressed_dp.parallel.dp import CompressionConfig, make_leaf_groups
from tpu_compressed_dp.parallel.mesh import make_data_mesh

BIG = kernels.MIN_PALLAS_ELEMS
# repeated and unique sizes on both sides of MIN_PALLAS_ELEMS
SHAPES = {"a1": (256,), "a2": (16, 16), "a3": (256,), "b": (100,),
          "c1": (BIG // 128, 128), "c2": (BIG,), "d": (BIG + 4464,)}
PARTS = 4     # 256 x 3, 100, BIG x 2, BIG + 4464


def tpu_dispatch(monkeypatch, interpret):
    """The TPU's dispatch (kernels at or above MIN_PALLAS_ELEMS) on the CPU."""
    monkeypatch.setattr(kernels, "_dispatch_to_pallas", lambda n: n >= BIG)
    monkeypatch.setattr(kernels, "_auto_interpret", lambda: interpret)


def make_tree(world, seed):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.standard_normal((world,) + s), jnp.float32)
            for k, s in SHAPES.items()}


CASES = [
    ("topk-ef", dict(method="topk", ratio=0.01, error_feedback=True), True),
    ("topk", dict(method="topk", ratio=0.01), True),
    ("randomk-ef", dict(method="randomk", ratio=0.05, error_feedback=True,
                        shared_mask=True), False),
    ("blocktopk-ef", dict(method="blocktopk", ratio=0.05, block_size=8,
                          error_feedback=True), True),
    ("terngrad", dict(method="terngrad"), False),
]


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("name,kw,forced", CASES, ids=[c[0] for c in CASES])
def test_batched_chain_is_bitwise_the_per_leaf_chain(monkeypatch, world, name,
                                                     kw, forced):
    if forced:
        tpu_dispatch(monkeypatch, interpret=True)
    cfg = CompressionConfig(mode="wire", granularity="layerwise", **kw)
    grads = make_tree(world, seed=3)
    resid = (make_tree(world, seed=4) if cfg.error_feedback else ())
    names = sorted(SHAPES)      # the order of jax.tree.flatten, so of groups
    whole = wire.make_wire_grad_sync(cfg, "data")
    # a tree of one leaf is a chain of one; the offset gives it the group's key
    alone = [wire.make_wire_grad_sync(cfg, "data", group_offset=gi)
             for gi in range(len(names))]

    def both(g, e):
        g = jax.tree.map(lambda x: x[0], g)
        e = jax.tree.map(lambda x: x[0], e)
        key = jax.random.key(11)
        out = whole(g, e, key)
        ref = [alone[gi]({k: g[k]}, {k: e[k]} if e else (), key)
               for gi, k in enumerate(names)]
        return jax.tree.map(lambda x: x[None], (out, ref))

    spec = jax.tree.map(lambda _: P("data"), (grads, resid))
    (out, new_ef, stats), ref = jax.jit(shard_map(
        both, mesh=make_data_mesh(world), in_specs=spec, out_specs=P("data"),
        check_vma=False))(grads, resid)

    assert float(stats["sync_chains"][0]) == PARTS
    assert float(stats["num_collectives"][0]) == len(names)
    for gi, k in enumerate(names):
        r_out, r_ef, r_stats = ref[gi]
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(r_out[k]))
        if cfg.error_feedback:
            np.testing.assert_array_equal(np.asarray(new_ef[k]),
                                          np.asarray(r_ef[k]))
        assert float(r_stats["sync_chains"][0]) == 1.0
    for stat in ("sent_bits", "sent_elems", "sent_bits_allgather",
                 "sent_bits_psum", "topk_surplus_dropped", "topk_underfull"):
        assert (stat in stats) == (stat in ref[0][2])
        if stat in stats:
            np.testing.assert_array_equal(
                np.asarray(stats[stat]),
                sum(np.asarray(r[2][stat], np.float64) for r in ref))
    if name == "topk":
        assert "topk_surplus_dropped" in stats
    if name.startswith("topk"):
        assert float(stats["topk_underfull"][0]) == 0.0     # finite gradients


def abstract_params(arch):
    model = getattr(resnet, arch)(num_classes=1000)
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jnp.zeros((1, 64, 64, 3)))
    return variables["params"]


TOPK_LW = CompressionConfig(method="topk", ratio=0.01, mode="wire",
                            granularity="layerwise", error_feedback=True)


@pytest.mark.parametrize("arch,leaves", [("resnet50", 161), ("resnet152", 467)])
def test_resnet_trees_sync_in_22_chains(arch, leaves):
    params = jax.tree.leaves(abstract_params(arch))
    groups = make_leaf_groups([4 * p.size for p in params], "layerwise", 0)
    parts = wire.chain_parts(params, groups, "topk", TOPK_LW)
    assert len(groups) == leaves and len(parts) == 22
    assert sorted(gi for members in parts.values() for gi in members) == list(range(leaves))
    assert all(len({params[gi].size for gi in members}) == 1
               for members in parts.values())
    # the transports without a member axis part the same way and never stack
    sharded = dataclasses.replace(TOPK_LW, transport="sharded")
    assert len(wire.chain_parts(params, groups, "topk", sharded)) == 22
    assert wire._stacks(256, "allgather") and not wire._stacks(BIG, "allgather")
    assert not wire._stacks(256, "sharded")


def primitive_counts(jaxpr, counts=None):
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            primitive_counts(sub, counts)
    return counts


def test_resnet50_sync_has_one_loop_per_large_part(monkeypatch):
    # traced, never compiled or run; not interpreted, so each kernel stays
    # one `pallas_call` equation
    tpu_dispatch(monkeypatch, interpret=False)
    params = abstract_params("resnet50")
    sizes = collections.Counter(p.size for p in jax.tree.leaves(params))
    large = sum(n >= BIG for n in sizes)
    long_leaves = sum(c for n, c in sizes.items() if n >= BIG)
    assert (large, long_leaves) == (10, 42)
    sync = wire.make_wire_grad_sync(TOPK_LW, "data")
    ef = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    jaxpr = jax.make_jaxpr(shard_map(
        lambda g, e: sync(g, e, jax.random.key(0)), mesh=make_data_mesh(1),
        in_specs=P(), out_specs=P(), check_vma=False))(params, ef)
    counts = primitive_counts(jaxpr.jaxpr)
    # the refinement rounds of a part's members advance together: one loop
    # a part of long leaves, each member's count kernel inside it
    assert 0 < counts["scan"] + counts["while"] <= large
    # the short leaves' exact thresholds sort member by member (a stacked
    # sort is slower on the chip), and the longest leaves sample theirs
    assert 161 - long_leaves <= counts["top_k"] <= 161
    # per long leaf a count kernel in its part's loop and a select-and-pack,
    # and the sampled first round of the longest
    assert 2 * long_leaves <= counts["pallas_call"] <= 3 * long_leaves
    # the short leaves' chains are traced once a part, not once a leaf
    assert counts["cumsum"] <= 2 * (22 - large) + 2 * long_leaves


# --- the allgather Top-K residual as one streamed pass, and the payload's
# --- bucket starts from a scan over the ranks (no keep-sized scatter, gather)

T = 1.0     # the threshold the residual cases are built around
RESIDUAL_CASES = ["ties_straddle_last", "surplus_after_last", "count_eq_keep",
                  "underfull", "underfull_first_survives"]


def residual_case(case, n, keep, seed):
    """A vector whose survivors of ``|x| >= T`` are placed for ``case``; the
    rest is noise under ``T / 2``."""
    rng = np.random.default_rng(seed)
    acc = rng.uniform(-0.5, 0.5, n).astype(np.float32)
    count = {"ties_straddle_last": keep + 3, "surplus_after_last": keep + 7,
             "count_eq_keep": keep, "underfull": keep - 3,
             "underfull_first_survives": keep - 3}[case]
    where = np.sort(rng.choice(np.arange(1, n), count, replace=False))
    if case == "underfull_first_survives":
        where[0] = 0
    acc[where] = rng.uniform(1.25, 2.0, count) * rng.choice([-1.0, 1.0], count)
    if case == "ties_straddle_last":
        # survivors keep-2 .. keep+2 by index sit exactly at the threshold:
        # two of them travel, three stay behind
        acc[where[keep - 2:]] = T * rng.choice([-1.0, 1.0], 5)
    return acc, count


def residual_oracle(acc, keep):
    """The first ``keep`` survivors by ascending index zeroed: what the
    parent's ``acc.at[idx].set(0)`` gives wherever the mask is full, and
    every survivor zeroed where it is not."""
    out = acc.copy()
    out[np.flatnonzero(np.abs(acc) >= T)[:keep]] = 0
    return out


def leaf_sync(accs, keep):
    """`_leaf_sync_topk` of each row of ``accs`` at threshold T, one worker:
    (dense, residual, count), and the parent's scatter form of the residual
    from the same packed indices."""
    def one(acc):
        dense, new_ef, _, count = wire._leaf_sync_topk(
            acc, keep, "data", 1, True, t=jnp.float32(T))
        mag = jnp.abs(acc).astype(jnp.float32)
        idx = wire._select_pack(acc, mag, jnp.float32(T), keep)[1]
        return dense, new_ef, count, acc.at[idx].set(0)

    return jax.jit(shard_map(jax.vmap(one), mesh=make_data_mesh(1),
                             in_specs=P(), out_specs=P(),
                             check_vma=False))(jnp.asarray(accs))


def check_residuals(accs, keep, cases, outs):
    dense, new_ef, count, scattered = map(np.asarray, outs)
    for j, case in enumerate(cases):
        acc = accs[j]
        want = residual_oracle(acc, keep)
        np.testing.assert_array_equal(new_ef[j], want, err_msg=case)
        survivors = np.flatnonzero(np.abs(acc) >= T)
        assert count[j] == len(survivors)
        if len(survivors) >= keep:
            # the parent's form, bitwise; the surplus stays in the residual
            np.testing.assert_array_equal(new_ef[j], scattered[j], err_msg=case)
            np.testing.assert_array_equal(new_ef[j][survivors[keep:]],
                                          acc[survivors[keep:]])
            # what travelled and what stayed add up to the gradient
            np.testing.assert_array_equal(dense[j] + new_ef[j], acc,
                                          err_msg=case)
        else:
            # (the payload's padding slots all name index 0: `dense[0]` is
            # not the residual's business)
            assert not np.any(np.abs(new_ef[j]) >= T)
            assert (new_ef[j][0] == acc[0]) == (abs(acc[0]) < T)


@pytest.mark.parametrize("case", RESIDUAL_CASES)
def test_streamed_residual_long_leaf_is_the_scatter_form(monkeypatch, case):
    tpu_dispatch(monkeypatch, interpret=True)
    n, keep = BIG + 4464, 700
    assert kernels.use_select_pack(n, keep)
    acc, _ = residual_case(case, n, keep, seed=5)
    check_residuals(acc[None], keep, [case], leaf_sync(acc[None], keep))


@pytest.mark.parametrize("n,keep", [(256, 13), (1000, 10), (4224, 43)])
def test_streamed_residual_short_stack_is_the_scatter_form(n, keep):
    # one member a case, all under one `jax.vmap` as `sync` stacks them
    assert not kernels.use_select_pack(n, keep)
    accs = np.stack([residual_case(case, n, keep, seed=7 + j)[0]
                     for j, case in enumerate(RESIDUAL_CASES)])
    check_residuals(accs, keep, RESIDUAL_CASES, leaf_sync(accs, keep))


def bucket_counts(case, buckets, rng):
    """Survivors a bucket (kernel segment, mask row) for ``case``."""
    counts = rng.integers(1, 9, buckets)
    if case == "empty_buckets":
        counts[rng.choice(buckets, buckets // 2, replace=False)] = 0
        counts[[3, 4, 5]] = 0           # a run of them, and the last one
        counts[-1] = 0
    elif case == "first_bucket_empty":
        counts[0] = 0
    elif case == "one_bucket":
        counts[:] = 0
        counts[buckets // 2] = 60
    elif case == "no_survivor":
        counts[:] = 0
    return counts.astype(np.int32)


BUCKET_CASES = ["empty_buckets", "first_bucket_empty", "one_bucket",
                "no_survivor", "every_bucket"]


@pytest.mark.parametrize("keep_over_count", [False, True])
@pytest.mark.parametrize("case", BUCKET_CASES)
def test_select_pack_payload_starts_without_gather(case, keep_over_count):
    rng = np.random.default_rng(17)
    nseg, seg = 12, kernels._SEG
    counts = bucket_counts(case, nseg, rng)
    total = int(counts.sum())
    keep = total + 5 if keep_over_count else max(total - 4, 1)
    # staging buffers as the kernel leaves them: a segment's survivors
    # compacted to its front, anything behind them
    vals = rng.standard_normal((nseg, seg)).astype(np.float32)
    idx = rng.integers(0, 1 << 20, (nseg, seg)).astype(np.int32)
    want_v = np.concatenate([vals[s, :c] for s, c in enumerate(counts)])[:keep]
    want_i = np.concatenate([idx[s, :c] for s, c in enumerate(counts)])[:keep]
    pad = keep - len(want_v)
    pvals, pidx, got_total = jax.jit(kernels._select_pack_payload, static_argnums=3)(
        jnp.asarray(vals.reshape(-1, 128)), jnp.asarray(idx.reshape(-1, 128)),
        jnp.asarray(counts), keep)
    np.testing.assert_array_equal(np.asarray(pvals), np.pad(want_v, (0, pad)))
    np.testing.assert_array_equal(np.asarray(pidx), np.pad(want_i, (0, pad)))
    assert int(got_total) == total
    # the replaced expression itself, the clamped ranks beyond the count too
    ends = np.cumsum(counts)
    seg_of = np.minimum(np.searchsorted(ends, np.arange(1, keep + 1)), nseg - 1)
    np.testing.assert_array_equal(
        np.asarray(kernels.run_starts(jnp.asarray(seg_of, jnp.int32))),
        (ends - counts)[seg_of])


@pytest.mark.parametrize("keep_over_count", [False, True])
@pytest.mark.parametrize("case", BUCKET_CASES)
def test_packed_indices_from_mask_starts_without_gather(case, keep_over_count):
    rng = np.random.default_rng(19)
    rows, n = 12, 12 * 128 - 40          # a last row that is padded
    counts = bucket_counts(case, rows, rng)
    mask = np.zeros(rows * 128, bool)
    for r, c in enumerate(counts):
        lanes = 128 - 40 if r == rows - 1 else 128
        mask[r * 128 + rng.choice(lanes, c, replace=False)] = True
    mask = mask[:n]
    total = int(mask.sum())
    keep = total + 5 if keep_over_count else max(total - 4, 1)
    # under a vmap too, as the short leaves' stacks run it
    masks = np.stack([mask, mask[::-1]])
    got = np.asarray(jax.jit(jax.vmap(
        lambda m: wire.packed_indices_from_mask(m, keep)))(jnp.asarray(masks)))
    for m, g in zip(masks, got):
        want = np.flatnonzero(m)[:keep]
        np.testing.assert_array_equal(g, np.pad(want, (0, keep - len(want))))


@pytest.mark.parametrize("underfull", [False, True])
def test_topk_underfull_counts_the_groups_under_keep(monkeypatch, underfull):
    from tpu_compressed_dp.obs import registry

    cfg = CompressionConfig(mode="wire", granularity="layerwise", method="topk",
                            ratio=0.05, error_feedback=True)
    rng = np.random.default_rng(23)
    grads = {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
             for k, s in {"a1": (256,), "a2": (16, 16), "b": (100,)}.items()}
    resid = jax.tree.map(jnp.zeros_like, grads)
    keep_b = wire.compressors.topk_keep_count(100, cfg.ratio)
    mags_b = np.sort(np.abs(np.asarray(grads["b"])))
    if underfull:
        # the one group of 100 elements gets a threshold one survivor short
        real = kernels.topk_thresholds
        short = jnp.float32(mags_b[-(keep_b - 1)])
        monkeypatch.setattr(
            kernels, "topk_thresholds",
            lambda mags, keep: ([short] * len(mags) if mags[0].shape[0] == 100
                                else real(mags, keep)))
    sync = wire.make_wire_grad_sync(cfg, "data")
    _, new_ef, stats = jax.jit(shard_map(
        lambda g, e: sync(g, e, jax.random.key(0)), mesh=make_data_mesh(1),
        in_specs=P(), out_specs=P(), check_vma=False))(grads, resid)
    assert float(stats["topk_underfull"]) == float(underfull)
    assert registry.undeclared(["comm/topk_underfull"]) == []
    assert "topk_surplus_dropped" not in stats       # EF on: reabsorbed
    b = np.asarray(grads["b"])
    sent = np.flatnonzero(np.asarray(new_ef["b"]) == 0)
    assert len(sent) == keep_b - underfull
    np.testing.assert_array_equal(
        sent, np.sort(np.argsort(-np.abs(b), kind="stable")[:len(sent)]))


# --- more survivors at the threshold than `keep`: the surplus stays in the
# --- residual, or is counted where there is none; nothing is lost or doubled

@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("error_feedback", [True, False], ids=["ef", "no-ef"])
def test_surplus_at_the_threshold_stays_or_is_counted(monkeypatch, world,
                                                      error_feedback):
    tpu_dispatch(monkeypatch, interpret=True)
    cfg = CompressionConfig(mode="wire", granularity="layerwise", method="topk",
                            ratio=0.01, error_feedback=error_feedback)
    shapes = {"long": (BIG + 4464,), "short": (1000,)}
    assert kernels.use_select_pack(BIG + 4464, 700)
    rng = np.random.default_rng(29)

    def halves():
        # multiples of 1/2: many coordinates share the keep-th magnitude, and
        # the workers' sums and their mean over four are exact in float32
        return {k: jnp.asarray(np.round(2 * rng.standard_normal((world,) + s)) / 2,
                               jnp.float32) for k, s in shapes.items()}

    grads = halves()
    resid = halves() if error_feedback else ()
    sync = wire.make_wire_grad_sync(cfg, "data")

    def one(g, e):
        out = sync(jax.tree.map(lambda x: x[0], g),
                   jax.tree.map(lambda x: x[0], e), jax.random.key(0))
        return jax.tree.map(lambda x: x[None], out)

    spec = jax.tree.map(lambda _: P("data"), (grads, resid))
    out, new_ef, stats = jax.jit(shard_map(
        one, mesh=make_data_mesh(world), in_specs=spec, out_specs=P("data"),
        check_vma=False))(grads, resid)

    surplus = np.zeros(world)
    for k in shapes:
        acc = np.asarray(grads[k]) + (np.asarray(resid[k]) if error_feedback else 0)
        keep = wire.compressors.topk_keep_count(acc.shape[1], cfg.ratio)
        want_ef = acc.copy()
        for w in range(world):
            t = np.sort(np.abs(acc[w]))[-keep]
            survivors = np.flatnonzero(np.abs(acc[w]) >= t)
            assert len(survivors) > keep            # the case: ties at t
            surplus[w] += len(survivors) - keep
            want_ef[w, survivors[:keep]] = 0        # cut by ascending index
        # what travelled and what stayed behind add up to the gradient
        for w in range(world):
            np.testing.assert_array_equal(np.asarray(out[k])[w],
                                          (acc - want_ef).sum(0) / world)
        if error_feedback:
            np.testing.assert_array_equal(np.asarray(new_ef[k]), want_ef)
    if error_feedback:
        assert "topk_surplus_dropped" not in stats      # reabsorbed
    else:
        np.testing.assert_array_equal(np.asarray(stats["topk_surplus_dropped"]),
                                      surplus)
    np.testing.assert_array_equal(np.asarray(stats["topk_underfull"]), 0.0)
