"""The wire sync's per-size chains (`ops/wire.py:make_wire_grad_sync`): the
reduction groups that share a flat size, a dtype and a transport sync in one
traced chain: short members as one stack over a leading member axis, long
ones on their own buffers with their threshold searches in one loop.  Every
member's result must be bitwise what a chain of its own gives, and the number
of chains is the number of distinct parts, whatever the number of leaves.

Tier-1 (tests/test_wire.py is marked slow as a whole): small trees, virtual
devices, kernels under the interpreter where a case forces them; the ResNet
trees are abstract and nothing of them is compiled.
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
                 "sent_bits_psum", "topk_surplus_dropped"):
        assert (stat in stats) == (stat in ref[0][2])
        if stat in stats:
            np.testing.assert_array_equal(
                np.asarray(stats[stat]),
                sum(np.asarray(r[2][stat], np.float64) for r in ref))
    if name == "topk":
        assert "topk_surplus_dropped" in stats


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
