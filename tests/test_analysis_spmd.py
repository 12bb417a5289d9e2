"""Unit tests for tcdp-lint pass 1 (tpu_compressed_dp/analysis/spmd.py).

Each TCDP00x check must fire on a seeded synthetic jaxpr and stay silent on
the matching clean shape.  The real-tree gate (quick profile at zero
findings) lives in tests/test_lint.py.
"""

import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from tpu_compressed_dp.analysis.spmd import (check_barrier_chain,
                                             check_chunk_plan,
                                             check_control_flow,
                                             check_donation,
                                             check_jaxpr_budget,
                                             check_signature_match,
                                             collective_signature,
                                             count_eqns)
from tpu_compressed_dp.parallel.mesh import make_data_mesh

pytestmark = pytest.mark.quick


@pytest.fixture(scope="module")
def mesh():
    return make_data_mesh(4)


def _smap(fn, mesh, n_in=1):
    # trace-only fixtures: check_vma off so a psum of the replicated probe
    # input stays a psum eqn and all_gather results may leave through P()
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * n_in, out_specs=P(),
                         check_vma=False)


def _codes(findings):
    return [f.code for f in findings]


class TestControlFlow:
    def test_cond_branch_asymmetry_fires(self, mesh):
        def f(x):
            return jax.lax.cond(x[0] > 0.0,
                                lambda v: jax.lax.psum(v, "data"),
                                lambda v: v, x)

        jx = jax.make_jaxpr(_smap(f, mesh))(jnp.ones((4,)))
        assert _codes(check_control_flow(jx, config="fix")) == ["TCDP001"]

    def test_symmetric_cond_passes(self, mesh):
        def f(x):
            return jax.lax.cond(x[0] > 0.0,
                                lambda v: jax.lax.psum(v, "data"),
                                lambda v: jax.lax.psum(2.0 * v, "data"), x)

        jx = jax.make_jaxpr(_smap(f, mesh))(jnp.ones((4,)))
        assert check_control_flow(jx) == []

    def test_data_predicated_while_fires(self, mesh):
        def f(x):
            def body(v):
                return jax.lax.psum(v, "data") * 0.4

            return jax.lax.while_loop(lambda v: jnp.sum(v) > 1.0, body, x)

        jx = jax.make_jaxpr(_smap(f, mesh))(jnp.ones((4,)))
        assert _codes(check_control_flow(jx)) == ["TCDP001"]

    def test_counter_loop_with_collective_passes(self, mesh):
        def f(x):
            return jax.lax.fori_loop(
                0, 3, lambda i, v: jax.lax.psum(v, "data") * 0.3, x)

        jx = jax.make_jaxpr(_smap(f, mesh))(jnp.ones((4,)))
        assert check_control_flow(jx) == []

    def test_scan_with_collective_passes(self, mesh):
        # static trip count: the pp schedule's ppermute-in-scan shape
        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "data") * 0.25, ()

            out, _ = jax.lax.scan(body, x, None, length=3)
            return out

        jx = jax.make_jaxpr(_smap(f, mesh))(jnp.ones((4,)))
        assert check_control_flow(jx) == []


class TestSignature:
    def _sig(self, fn, mesh, *args):
        return collective_signature(jax.make_jaxpr(_smap(
            fn, mesh, n_in=len(args)))(*args))

    def test_signature_sees_through_containers(self, mesh):
        def f(x):
            return jax.jit(lambda v: jax.lax.psum(v, "data"))(x)

        sig = self._sig(f, mesh, jnp.ones((4,)))
        assert [s[0] for s in sig] == ["psum"]
        assert sig[0][1] == ("data",)

    def test_retrace_match_and_mismatch(self, mesh):
        def f(x):
            return jax.lax.psum(x, "data")

        def g(x):
            return jax.lax.all_gather(x, "data")

        a = self._sig(f, mesh, jnp.ones((4,)))
        b = self._sig(g, mesh, jnp.ones((4,)))
        assert check_signature_match(a, a, "t1", "t2") == []
        assert _codes(check_signature_match(a, b, "t1", "t2")) == ["TCDP002"]

    def test_multiset_mode_ignores_order(self, mesh):
        def f(x):
            return jax.lax.psum(x, "data"), jax.lax.all_gather(x, "data")

        def g(x):
            return jax.lax.all_gather(x, "data"), jax.lax.psum(x, "data")

        a = self._sig(f, mesh, jnp.ones((4,)))
        b = self._sig(g, mesh, jnp.ones((4,)))
        assert check_signature_match(a, b, "f", "g", ordered=False) == []
        assert _codes(check_signature_match(a, b, "f", "g")) == ["TCDP002"]


class TestDonation:
    def test_unmatchable_donation_fires(self):
        def f(x):
            return jnp.sum(x)  # scalar out: nothing to alias f32[8] into

        out = check_donation(f, (jnp.ones((8,)),), (0,))
        assert _codes(out) == ["TCDP003"]

    def test_matching_donation_passes(self):
        def f(x):
            return x * 2.0

        assert check_donation(f, (jnp.ones((8,)),), (0,)) == []

    def test_pytree_donation_multiset(self):
        def f(state):
            return {"a": state["a"] + 1.0}  # drops state["b"]

        state = {"a": jnp.ones((4,)), "b": jnp.ones((3, 2))}
        out = check_donation(f, (state,), (0,))
        assert _codes(out) == ["TCDP003"]
        assert "[3, 2]" in out[0].message


def _plan(index, lo, hi, goff, ng):
    return types.SimpleNamespace(index=index, leaf_lo=lo, leaf_hi=hi,
                                 group_offset=goff, n_groups=ng)


class TestChunkPlan:
    def test_valid_plan_passes(self):
        plans = [_plan(0, 0, 2, 0, 2), _plan(1, 2, 5, 2, 3)]
        assert check_chunk_plan(plans, n_leaves=5, n_groups=5) == []

    def test_duplicate_group_offset_fires(self):
        plans = [_plan(0, 0, 2, 0, 2), _plan(1, 2, 5, 0, 3)]
        out = check_chunk_plan(plans, n_leaves=5, n_groups=5)
        assert "TCDP004" in _codes(out)

    def test_leaf_gap_fires(self):
        plans = [_plan(0, 0, 2, 0, 2), _plan(1, 3, 5, 2, 2)]
        out = check_chunk_plan(plans, n_leaves=5, n_groups=4)
        assert "TCDP004" in _codes(out)


class TestJaxprBudget:
    def test_unrolled_loop_fires(self):
        # the TCDP005 failure shape: a Python loop over "leaves" stamping
        # its body into the trace once per iteration
        def f(x):
            for _ in range(64):
                x = jnp.sin(x) * 2.0 + 1.0
            return x

        jx = jax.make_jaxpr(f)(jnp.ones((4,)))
        out = check_jaxpr_budget(jx, budget=100, config="fix")
        assert _codes(out) == ["TCDP005"]
        assert "budget 100" in out[0].message

    def test_rolled_loop_passes(self):
        # the same computation as a fori_loop counts its body ONCE
        def f(x):
            return jax.lax.fori_loop(
                0, 64, lambda i, v: jnp.sin(v) * 2.0 + 1.0, x)

        jx = jax.make_jaxpr(f)(jnp.ones((4,)))
        assert count_eqns(jx) < 64
        assert check_jaxpr_budget(jx, budget=100) == []

    def test_count_recurses_into_subjaxprs(self):
        def f(x):
            return jax.jit(lambda v: jnp.sin(v) + jnp.cos(v))(x)

        jx = jax.make_jaxpr(f)(jnp.ones((4,)))
        assert count_eqns(jx) >= 3  # pjit eqn + sin + cos + add inside


class TestBarrierChain:
    def test_unchained_chunks_fire(self, mesh):
        def f(x, y):
            return jax.lax.psum(x, "data"), jax.lax.psum(y, "data")

        jx = jax.make_jaxpr(_smap(f, mesh, n_in=2))(jnp.ones((4,)),
                                                    jnp.ones((4,)))
        assert _codes(check_barrier_chain(jx, n_chunks=2)) == ["TCDP004"]

    def test_chained_chunks_pass(self, mesh):
        def f(x, y):
            a = jax.lax.psum(x, "data")
            # the overlap engine's issue-order link: chunk 2's input passes
            # through a barrier fed by chunk 1's collective
            a2, y2 = jax.lax.optimization_barrier((a, y))
            return a2, jax.lax.psum(y2, "data")

        jx = jax.make_jaxpr(_smap(f, mesh, n_in=2))(jnp.ones((4,)),
                                                    jnp.ones((4,)))
        assert check_barrier_chain(jx, n_chunks=2) == []
