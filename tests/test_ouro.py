"""The looped language model (Ouro: tied passes, sandwich norms, exit gate)
on the LM path, held to its plain reference ``benchmark/reference/ouro.py``
at a tiny width on the CPU: the step's loss, per-pass losses, exit masses and
every leaf's gradient over two steps; the tied gradient against an untied,
unrolled copy; the faults the benchmark's comparison must catch; and that one
pass without sandwich norms and gate is still the plain decoder."""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.models import transformer as tf
from tpu_compressed_dp.parallel.dp import CompressionConfig
from tpu_compressed_dp.train import lm_step
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.state import TrainState

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(path):
    spec = importlib.util.spec_from_file_location(
        "ref_" + os.path.basename(path)[:-3], os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ouro = load("benchmark/reference/ouro.py")
sgd = load("benchmark/reference/optim/sgd.py")

CFG = {"hidden_size": 32, "intermediate_size": 64, "vocab_size": 96,
       "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
       "num_hidden_layers": 2, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
       "total_ut_steps": 4, "exit_beta": 0.1, "initializer_range": 0.2,
       "seq_len": 16}
OPT = {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-3, "nesterov": False}
LC = tf.LlamaConfig(vocab_size=96, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
                    ffn_hidden=64, rope_theta=1e6, norm_eps=1e-6,
                    dtype=jnp.float32, remat=True, n_passes=4,
                    sandwich_norm=True, exit_gate=True)
PLAIN = tf.LlamaConfig(vocab_size=96, dim=32, n_layers=2, n_heads=2,
                       n_kv_heads=2, ffn_hidden=64, dtype=jnp.float32)


def batches(n, rows=4, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (n, rows, CFG["seq_len"] + 1))
    return [(b[:, :-1].astype(np.int32), b[:, 1:].astype(np.int32)) for b in ids]


def program_steps(lc, params, data, workers=2):
    """[(metrics, momentum leaves, aux leaves)] of the program's first steps."""
    mesh = lm_step.make_lm_mesh(workers, 1, 1)
    opt = SGD(lr=OPT["lr"], momentum=OPT["momentum"],
              weight_decay=OPT["weight_decay"])
    comp = CompressionConfig(method=None)
    step = lm_step.make_lm_train_step(lc, opt, comp, mesh, donate=False)
    state = TrainState.create(params, lm_step.init_lm_model_aux(lc),
                              opt.init(params), (), jax.random.key(1))
    out = []
    for x, y in data:
        state, metrics = step(state, {"input": jnp.asarray(x),
                                      "target": jnp.asarray(y)})
        out.append(({k: float(v) for k, v in metrics.items()},
                    [np.asarray(l) for l in jax.tree.leaves(
                        state.opt_state["momentum"])],
                    [np.asarray(l) for l in jax.tree.leaves(state.batch_stats)],
                    [np.asarray(l) for l in jax.tree.leaves(state.params)]))
    return out


def reference_steps(params, data, cfg=CFG, loss_and_grad=None):
    """The same steps in the reference: [(loss, aux leaves, grads, momentum)]."""
    grad = loss_and_grad or ouro.make_loss_and_grad(cfg)
    leaves, treedef = jax.tree.flatten(params)
    p = [np.asarray(l) for l in leaves]
    buf = sgd.init(p)
    out = []
    for x, y in data:
        (loss, aux), g = grad(jax.tree.unflatten(treedef, p), jnp.asarray(x),
                              jnp.asarray(y))
        g = [np.asarray(l) for l in jax.tree.leaves(g)]
        p, buf = sgd.update(p, buf, g, OPT)
        out.append((float(loss), [np.asarray(a) for a in jax.tree.leaves(aux)],
                    g, buf, p))
    return out


@pytest.fixture(scope="module")
def params():
    return ouro.make_params(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def two_steps(params):
    data = batches(2)
    return program_steps(LC, params, data), reference_steps(params, data)


def test_the_reference_tree_is_the_programs(params):
    want = jax.eval_shape(lambda: tf.init_llama(LC, jax.random.key(0)))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape, want))
    assert len(jax.tree.leaves(params)) == 11 * CFG["num_hidden_layers"] + 5
    specs = tf.param_specs(LC)
    from jax.sharding import PartitionSpec

    assert (jax.tree.structure(specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
            == jax.tree.structure(params))


def test_the_published_sizes_count_as_the_issue_reckons():
    """Ouro-2.6B cut to 6 layers: 509,661,185 parameters in 71 leaves, and
    15.1 TFLOP forward a 4,096-token sequence."""
    import json

    with open(os.path.join(ROOT, "benchmark/configs/ouro_2p6b.json")) as f:
        cfg = json.load(f)
    sizes = [int(np.prod(s)) for s in jax.tree.leaves(
        ouro.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))]
    assert (sum(sizes), len(sizes), max(sizes)) == (509_661_185, 71, 100_663_296)
    assert sum(sizes) == cfg["parameters"] and len(sizes) == cfg["parameter_leaves"]
    want = 4 * (6 * (2 * 51_380_224 + 2 * 4096 * 2048)
                + 2 * 2048 * 49_152 + 2 * 2048) * 4096
    assert ouro.forward_flops_per_sample(cfg) == want
    assert ouro.attention_flops_per_sample(cfg) == 4 * 6 * 16 * 6 * 4096 ** 2 * 128
    lc = tf.ouro_2p6b()
    for field, key in (("vocab_size", "vocab_size"), ("dim", "hidden_size"),
                       ("n_heads", "num_attention_heads"), ("head_dim", "head_dim"),
                       ("n_kv_heads", "num_key_value_heads"),
                       ("ffn", "intermediate_size"), ("n_passes", "total_ut_steps"),
                       ("norm_eps", "rms_norm_eps"), ("rope_theta", "rope_theta")):
        assert getattr(lc, field) == cfg[key], field
    assert lc.n_layers == cfg["published"]["num_hidden_layers"]


@pytest.mark.parametrize("step", [0, 1])
def test_loss_pass_losses_and_exit_masses_follow_the_reference(two_steps, step):
    (metrics, _, aux, _), (loss, ref_aux, _, _, _) = (s[step] for s in two_steps)
    assert metrics["loss"] == pytest.approx(loss, rel=2e-5)
    entropy, mass, pass_loss = ref_aux
    for r in range(4):
        assert metrics[f"loss/pass{r + 1}"] == pytest.approx(pass_loss[r], rel=2e-5)
        assert metrics[f"model/exit_mass{r + 1}"] == pytest.approx(mass[r], abs=2e-6)
    assert metrics["model/exit_entropy"] == pytest.approx(float(entropy), rel=2e-5)
    assert sum(metrics[f"model/exit_mass{r}"] for r in range(1, 5)) == pytest.approx(1.0)
    # the state's auxiliary slot keeps them for the benchmark's probe
    for kept, ref in zip(aux, ref_aux):
        np.testing.assert_allclose(kept, ref, rtol=2e-5, atol=2e-6)
    assert ouro.model_numbers(aux, ref_aux, CFG, {})["pass_loss_gap"] < 2e-5


@pytest.mark.parametrize("step", [0, 1])
def test_every_leafs_gradient_follows_the_reference(two_steps, params, step):
    """Through the optimizer state, as the benchmark reads it: the momentum
    after step 1 is the gradient plus the decay, after step 2 the two mixed."""
    prog, ref = (s[step] for s in two_steps)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for name, got, want in zip(names, prog[1], ref[3]):
        scale = max(float(np.max(np.abs(want))), 1e-6)
        np.testing.assert_allclose(got, want, atol=3e-5 * scale, err_msg=name)
    for name, got, want in zip(names, prog[3], ref[4]):
        np.testing.assert_allclose(got, want, atol=5e-6, err_msg=name)


def test_the_tied_gradient_is_the_sum_over_an_untied_unrolled_copy(params, two_steps):
    """Four copies of the weights, one a pass, written out with no scan: the
    gradients of the four copies add up to the program's gradient of its one
    set, leaf by leaf, and no single copy's does."""
    x, y = batches(2)[0]
    copies = [params] * 4
    g = jax.grad(lambda c: ouro.loss_fn(c, jnp.asarray(x), jnp.asarray(y),
                                        CFG)[0])(copies)
    per_pass = [[np.asarray(l) for l in jax.tree.leaves(c)] for c in g]
    summed = [sum(ls) for ls in zip(*per_pass)]
    wd_p = [OPT["weight_decay"] * np.asarray(l) for l in jax.tree.leaves(params)]
    tied = [m - d for m, d in zip(two_steps[0][0][1], wd_p)]
    for got, want in zip(tied, summed):
        np.testing.assert_allclose(got, want,
                                   atol=3e-5 * max(np.max(np.abs(want)), 1e-6))
    # a pass whose weights are not tied to the others' gives another gradient
    matrices = np.array([l.ndim > 1 for l in summed])
    for one in per_pass:
        gap = np.array([np.linalg.norm(a - b) / np.linalg.norm(b)
                        for a, b in zip(one, summed)])
        assert np.median(gap[matrices]) > 0.3


def test_a_dropped_pass_is_seen(params, two_steps):
    """Three passes for four: the loss moves, and the per-pass numbers no
    longer line up with the reference's."""
    data = batches(1)
    metrics, _, aux, _ = program_steps(
        dataclasses.replace(LC, n_passes=3), params, data)[0]
    loss, ref_aux = two_steps[1][0][0], two_steps[1][0][1]
    assert abs(metrics["loss"] - loss) / loss > 1e-3
    assert ouro.model_numbers(aux, ref_aux, CFG, {}) == {
        "pass_loss_gap": float("inf"), "exit_mass_gap": float("inf")}


def test_a_loss_without_the_exit_weighting_is_seen(params, two_steps, monkeypatch):
    """Every pass weighted alike, whatever the gate says."""
    def unweighted(nll, gate, beta):
        _, stats = tf.exit_weighted_loss(nll, jnp.zeros_like(gate), beta)
        return jnp.mean(nll), dict(stats, exit_mass=jnp.full(
            (nll.shape[0],), 1.0 / nll.shape[0]))

    monkeypatch.setattr(lm_step, "exit_weighted_loss", unweighted)
    metrics, momentum, aux, _ = program_steps(LC, params, batches(1))[0]
    loss, ref_aux = two_steps[1][0][0], two_steps[1][0][1]
    assert abs(metrics["loss"] - loss) / loss > 1e-3
    assert ouro.model_numbers(aux, ref_aux, CFG, {})["exit_mass_gap"] > 0.05
    gate_w = [i for i, (p, _) in enumerate(
        jax.tree_util.tree_flatten_with_path(params)[0])
        if jax.tree_util.keystr(p) == "['exit_gate']['w']"][0]
    assert np.all(momentum[gate_w] == OPT["weight_decay"] * np.asarray(
        params["exit_gate"]["w"]))          # the gate learns nothing


def test_exit_distribution_sums_to_one_and_a_saturated_gate_is_finite():
    gate = jnp.asarray([[40.0, -40.0, 0.3], [-2.0, 50.0, -0.1],
                        [0.5, 0.5, 90.0], [3.0, 3.0, 3.0]])
    nll = jnp.ones((4, 3))
    (loss, stats), grads = jax.value_and_grad(
        lambda g: tf.exit_weighted_loss(nll, g, 0.1), has_aux=True)(gate)
    p = jnp.exp(tf.exit_log_probs(gate))
    np.testing.assert_allclose(np.sum(p, axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p, ouro.exit_distribution(gate), atol=1e-7)
    np.testing.assert_allclose(stats["exit_mass"], np.mean(p, axis=1), rtol=1e-6)
    assert np.isfinite(float(loss)) and np.all(np.isfinite(grads))
    assert np.isfinite(float(stats["exit_entropy"]))
    assert np.all(np.asarray(grads[-1]) == 0)     # the last pass's gate is not read


def test_one_pass_without_sandwich_and_gate_is_the_plain_decoder():
    """The looped settings at their defaults leave the decoder as it was: no
    scan in its jaxpr, no new leaves, nothing kept in the auxiliary slot, and
    asking for all passes returns the one pass, bit for bit."""
    params = tf.init_llama(PLAIN, jax.random.key(3))
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    assert set(params["layers"][0]) == {"attn_norm", "mlp_norm", "wq", "wk", "wv",
                                        "wo", "w_gate", "w_up", "w_down"}
    assert lm_step.init_lm_model_aux(PLAIN) == {}
    x = jnp.asarray(batches(1)[0][0])
    plain = tf.apply_llama(PLAIN, params, x)
    every, gate = tf.apply_llama(PLAIN, params, x, all_passes=True)
    assert gate is None and every.shape == (1,) + plain.shape
    assert np.array_equal(np.asarray(every[0]), np.asarray(plain))
    jaxpr = str(jax.make_jaxpr(lambda p: tf.apply_llama(PLAIN, p, x))(params))
    assert "scan" not in jaxpr and "while" not in jaxpr
    looped = dataclasses.replace(PLAIN, n_passes=2)
    assert "scan" in str(jax.make_jaxpr(
        lambda p: tf.apply_llama(looped, p, x))(params))
    # two tied passes by hand: the second run of the one-pass model on the
    # first's hidden states is what the scan computes
    h2 = tf.apply_llama(looped, params, x, return_hidden=True)
    both, _ = tf.apply_llama(looped, params, x, return_hidden=True, all_passes=True)
    assert np.array_equal(np.asarray(both[1]), np.asarray(h2))
    h1 = tf.apply_llama(PLAIN, params, x, return_hidden=True)
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(h1), atol=1e-6)


def test_the_plain_step_keeps_its_metrics_and_an_empty_auxiliary_slot():
    params = tf.init_llama(PLAIN, jax.random.key(3))
    metrics, _, aux, _ = program_steps(PLAIN, params, batches(1))[0]
    assert aux == []
    assert not [k for k in metrics if k.startswith(("loss/", "model/"))]
    x, y = (jnp.asarray(a) for a in batches(1)[0])
    want = tf.vocab_parallel_xent(tf.apply_llama(PLAIN, params, x), y)
    assert metrics["loss"] == pytest.approx(float(want), rel=1e-6)


@pytest.mark.parametrize("chunk", [32, 40, 2048])
def test_fused_head_with_per_token_weights_matches_the_unfused_path(chunk):
    """Forward and every cotangent (hidden states, head, the weights) of
    sum_tokens w * CE, through the fused head against whole logits; the vocab
    of 96 in chunks of 32 (whole), 40 (a padded last chunk) and one."""
    ks = jax.random.split(jax.random.key(5), 4)
    h = jax.random.normal(ks[0], (4, 3, 16, 32), jnp.float32)
    w = jax.random.normal(ks[1], (32, 96), jnp.float32) * 0.2
    y = jax.random.randint(ks[2], (4, 3, 16), 0, 96)
    wts = jax.random.uniform(ks[3], (4, 3, 16))

    def fused(h, w, wts):
        return jnp.sum(wts * tf.fused_head_xent_tokens(h, w, y, None, chunk))

    def unfused(h, w, wts):
        return jnp.sum(wts * tf.vocab_parallel_xent_tokens(h @ w, y))

    (vf, gf), (vu, gu) = (jax.value_and_grad(f, (0, 1, 2))(h, w, wts)
                          for f in (fused, unfused))
    assert float(vf) == pytest.approx(float(vu), rel=1e-5)
    for a, b, name in zip(gf, gu, ("dh", "dw", "dweights")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)
    # the mean over tokens is the function the plain step calls
    assert float(tf.fused_head_xent(h, w, y, None, chunk)) == pytest.approx(
        float(tf.vocab_parallel_xent(h @ w, y)), rel=1e-6)


def head_inputs(n, d, v, dtype, seed=7):
    """Seeded rows for the head: a target on the last id, weights with zeros."""
    ks = jax.random.split(jax.random.key(seed), 4)
    h = (jax.random.normal(ks[0], (n, d), jnp.float32) * 0.5).astype(dtype)
    w = (jax.random.normal(ks[1], (d, v), jnp.float32) * 0.2).astype(dtype)
    y = jax.random.randint(ks[2], (n,), 0, v).at[0].set(v - 1).at[n - 1].set(v - 1)
    wts = jax.random.uniform(ks[3], (n,)).at[1::5].set(0.0)
    return h, w, y, wts


def wsum_heads():
    """{name: (h, w, y, wts) -> (3 sum(wts nll), nll)}: the weighted-sum entry,
    the per-token entry and the unfused chain in float32, under a scalar
    cotangent that is not 1."""
    def per_token(nll_of):
        def f(h, w, y, wts):
            nll = nll_of(h, w, y)
            return 3.0 * jnp.sum(wts * nll), nll
        return f

    def wsum(h, w, y, wts):
        s, nll = tf.fused_head_xent_wsum(h, w, y, wts)
        return 3.0 * s, nll

    return {"wsum": wsum,
            "tokens": per_token(tf.fused_head_xent_tokens),
            "unfused": per_token(lambda h, w, y: tf.vocab_parallel_xent_tokens(
                h.astype(jnp.float32) @ w.astype(jnp.float32), y))}


def value_and_grads(f, h, w, y, wts):
    (s, nll), grads = jax.value_and_grad(
        lambda h, w, wts: f(h, w, y, wts), (0, 1, 2), has_aux=True)(h, w, wts)
    return [np.asarray(a.astype(jnp.float32)) for a in (s, nll) + grads]


# rows, vocabulary, (rows a block, rows a sub-block) or None for the module's
# own 1,024 over 512
WSUM_CASES = {
    "one_block_of_all_rows": (12, 50, None),
    "two_sub_blocks_whole": (16, 96, (16, 8)),
    "rows_not_whole_blocks": (45, 50, (16, 8)),
    "one_padded_block": (13, 130, (16, 8)),
    "the_modules_own_blocks_padded": (1100, 50, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(WSUM_CASES))
def test_the_weighted_sum_head_matches_the_per_token_head_and_the_unfused_chain(
        case, dtype, monkeypatch):
    """Both outputs and dh, dW, d weights of 3 sum(w nll): the entry whose
    forward makes the gradients against the entry that recomputes the logits
    and against whole logits; rows that are not whole blocks, a vocabulary
    that is no multiple of 128, a target on the last id, weights with zeros."""
    n, v, blocks = WSUM_CASES[case]
    if blocks:
        monkeypatch.setattr(tf, "_FHW_ROWS", blocks[0])
        monkeypatch.setattr(tf, "_FHW_SUB", blocks[1])
    args = head_inputs(n, 16, v, jnp.dtype(dtype))
    got, tokens, unfused = (value_and_grads(f, *args)
                            for f in wsum_heads().values())
    assert got[2].dtype == got[3].dtype == np.float32
    names = ("value", "nll", "dh", "dw", "dweights")
    # float32: the tolerances of the per-token entry's own tests; bfloat16:
    # two roundings of dz and of the results, of the largest entry
    rel, atol = (1e-5, 2e-5) if dtype == "float32" else (1e-2, None)
    for name, a, b, c in zip(names, got, tokens, unfused):
        for other, want in (("tokens", b), ("unfused", c)):
            tol = atol if atol else 2.0 ** -6 * float(np.max(np.abs(want)))
            if name in ("value", "nll", "dweights"):
                np.testing.assert_allclose(a, want, rtol=rel, atol=tol,
                                           err_msg=f"{name} against {other}")
            else:
                np.testing.assert_allclose(a, want, atol=tol,
                                           err_msg=f"{name} against {other}")
    assert np.all(got[2][1::5] == 0)        # a weight of zero: no dh
    # the primal alone returns the same two
    s, nll = tf.fused_head_xent_wsum(*args)
    assert 3.0 * float(s) == pytest.approx(float(got[0]), rel=1e-6)
    np.testing.assert_array_equal(np.asarray(nll), got[1])


def test_the_weighted_sum_head_over_a_vocabulary_in_two_shards():
    """A 2-way ``tensor`` axis: v = 50 in shards of 25; dh of the replicated
    hidden states comes back summed over the shards, dW in shards."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tpu_compressed_dp.parallel.mesh import make_mesh

    h, w, y, wts = head_inputs(24, 16, 50, jnp.float32)
    y = y.at[2].set(25).at[3].set(24)        # the shards' edge, either side
    mesh = make_mesh((2,), ("tensor",))

    def local(h, w, y, wts):
        (s, nll), grads = jax.value_and_grad(
            lambda h, w, wts: tf.fused_head_xent_wsum(h, w, y, 3.0 * wts,
                                                      "tensor"),
            (0, 1, 2), has_aux=True)(h, w, wts)
        return (s, nll) + grads

    got = shard_map(local, mesh=mesh,
                    in_specs=(P(), P(None, "tensor"), P(), P()),
                    out_specs=(P(), P(), P(), P(None, "tensor"), P()))(h, w, y, wts)
    want = value_and_grads(wsum_heads()["unfused"], h, w, y, wts)
    for name, a, b in zip(("value", "nll", "dh", "dw", "dweights"), got, want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5, atol=2e-5,
                                   err_msg=name)


def head_products(jaxpr, scope="", times=1):
    """Multiply-adds of the ``dot_general``s of a jaxpr, loops unrolled:
    ``(all, those under tcdp.head_xent)``."""
    total = inside = 0
    for eqn in jaxpr.eqns:
        stack = scope + "/" + str(eqn.source_info.name_stack)
        n = times * eqn.params.get("length", 1) if eqn.primitive.name == "scan" else times
        if eqn.primitive.name == "dot_general":
            (ca, _), (ba, _) = eqn.params["dimension_numbers"]
            a, out = eqn.invars[0].aval, eqn.outvars[0].aval
            work = times * int(np.prod(out.shape)) * int(
                np.prod([a.shape[i] for i in ca]))
            total += work
            inside += work if "tcdp.head_xent" in stack else 0
        for sub in jax.tree.leaves(list(eqn.params.values()),
                                   is_leaf=lambda x: hasattr(x, "eqns")
                                   or hasattr(x, "jaxpr")):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                a, b = head_products(sub, stack, n)
                total, inside = total + a, inside + b
    return total, inside


def test_the_weighted_sum_head_makes_one_product_alone_and_three_differentiated():
    """Counted in the jaxpr: without differentiation the logits once; under
    ``grad`` the logits, dh and dW; the per-token entry the logits twice."""
    n, d, v = 40, 16, 48
    h, w, y, wts = head_inputs(n, d, v, jnp.float32)
    one = n * d * v
    primal = jax.make_jaxpr(
        lambda h, w, wts: tf.fused_head_xent_wsum(h, w, y, wts))(h, w, wts)
    assert str(primal).count("dot_general") == 1
    assert head_products(primal.jaxpr)[0] == one

    def grads(head):
        return jax.make_jaxpr(jax.grad(
            lambda h, w, wts: head(h, w, y, wts)[0], (0, 1, 2)))(h, w, wts)

    heads = wsum_heads()
    assert head_products(grads(heads["wsum"]).jaxpr)[0] == 3 * one
    assert head_products(grads(heads["tokens"]).jaxpr)[0] == 4 * one


@pytest.fixture
def fused_step(monkeypatch):
    """The tiny step through its fused branch, which its sizes would not take."""
    monkeypatch.setattr(lm_step, "use_fused_head_xent", lambda *a, **k: True)


def parents_head(h, w, ys, weights, tensor_axis):
    """The head as the step called it before: the per-token entry, its
    cotangent the weights."""
    nll = tf.fused_head_xent_tokens(h, w, ys, tensor_axis)
    return jnp.sum(weights * nll), nll


def test_the_fused_looped_step_follows_the_reference_and_the_per_token_head(
        params, two_steps, fused_step, monkeypatch):
    """Loss, per-pass losses, exit masses and every leaf's first gradient of
    the exit-gated step through the weighted-sum head: against the reference
    and the unfused step to their tests' tolerances, and against the same
    step with the per-token head in the new entry's place."""
    data = batches(2)
    got = program_steps(LC, params, data)
    monkeypatch.setattr(lm_step, "fused_head_xent_wsum", parents_head)
    parent = program_steps(LC, params, data)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for step in range(2):
        metrics, momentum, aux, new_params = got[step]
        loss, ref_aux, _, ref_momentum, ref_params = two_steps[1][step]
        assert metrics["loss"] == pytest.approx(loss, rel=2e-5)
        for kept, ref in zip(aux, ref_aux):
            np.testing.assert_allclose(kept, ref, rtol=2e-5, atol=2e-6)
        assert ouro.model_numbers(aux, ref_aux, CFG, {})["pass_loss_gap"] < 2e-5
        assert ouro.model_numbers(aux, ref_aux, CFG, {})["exit_mass_gap"] < 2e-6
        for other, (o_metrics, o_momentum, o_aux, _) in (
                ("unfused", two_steps[0][step]), ("per-token", parent[step])):
            assert metrics == pytest.approx(o_metrics, rel=2e-5, abs=2e-6), other
            for name, a, b, ref in zip(names, momentum, o_momentum, ref_momentum):
                scale = max(float(np.max(np.abs(ref))), 1e-6)
                np.testing.assert_allclose(a, b, atol=3e-5 * scale,
                                           err_msg=f"{name} against {other}")
                np.testing.assert_allclose(a, ref, atol=3e-5 * scale, err_msg=name)
        for name, a, ref in zip(names, new_params, ref_params):
            np.testing.assert_allclose(a, ref, atol=5e-6, err_msg=name)


def test_the_fused_looped_step_multiplies_by_the_head_three_times_where_four_stood(
        params, fused_step, monkeypatch):
    """Engagement, static: under ``tcdp.head_xent`` the differentiated loss
    holds three products of [rows of every pass, width, vocabulary], and four
    with the per-token head in the new entry's place."""
    x, y = (jnp.asarray(a) for a in batches(1)[0])
    one = LC.n_passes * x.size * LC.dim * LC.vocab_size

    def head_work():
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = lm_step.make_lm_mesh(1, 1, 1)
        loss = shard_map(
            lambda p, x, y: lm_step.llama_loss(LC, p, x, y, 1)[0], mesh=mesh,
            in_specs=(tf.param_specs(LC), P(), P()), out_specs=P(),
            check_vma=False)
        return head_products(jax.make_jaxpr(jax.grad(loss))(params, x, y).jaxpr)[1]

    assert head_work() == 3 * one
    monkeypatch.setattr(lm_step, "fused_head_xent_wsum", parents_head)
    assert head_work() == 4 * one


@pytest.mark.parametrize("fused", [False, True])
def test_the_looped_step_runs_sharded_over_seq_and_tensor(params, two_steps, fused,
                                                          monkeypatch):
    """dp 2 x sp 2 x tp 2 on the 8 virtual devices: the same loss and per-pass
    numbers as the reference (the vocab-parallel per-token losses, the ring
    attention and the gate all see shards), through whole logits and through
    the weighted-sum head over its vocabulary shards."""
    if fused:
        monkeypatch.setattr(lm_step, "use_fused_head_xent", lambda *a, **k: True)
    mesh = lm_step.make_lm_mesh(2, 2, 2)
    opt = SGD(lr=OPT["lr"], momentum=OPT["momentum"],
              weight_decay=OPT["weight_decay"])
    comp = CompressionConfig(method=None)
    step = lm_step.make_lm_train_step(LC, opt, comp, mesh, donate=False)
    state = TrainState.create(params, lm_step.init_lm_model_aux(LC),
                              opt.init(params), (), jax.random.key(1))
    state = lm_step.place_lm_state(state, LC, comp, mesh)
    x, y = batches(2)[0]
    state, metrics = step(state, {"input": jnp.asarray(x), "target": jnp.asarray(y)})
    loss, ref_aux, grads = two_steps[1][0][0], two_steps[1][0][1], two_steps[1][0][2]
    assert float(metrics["loss"]) == pytest.approx(loss, rel=2e-5)
    np.testing.assert_allclose(np.asarray(state.batch_stats["pass_loss"]),
                               ref_aux[2], rtol=2e-5)
    wd_p = [OPT["weight_decay"] * np.asarray(l) for l in jax.tree.leaves(params)]
    for m, d, g in zip(jax.tree.leaves(state.opt_state["momentum"]), wd_p, grads):
        np.testing.assert_allclose(np.asarray(m) - d, g,
                                   atol=5e-5 * max(np.max(np.abs(g)), 1e-6))


def test_the_harness_trains_the_preset_at_a_tiny_size(capsys):
    from tpu_compressed_dp.harness import lm

    summary = lm.main(["--preset", "ouro_2p6b", "--dim", "32", "--layers", "1",
                       "--heads", "2", "--kv_heads", "2", "--ffn", "64",
                       "--vocab", "64", "--dp", "2", "--steps", "12",
                       "--seq_len", "32", "--global_batch", "4",
                       "--log_every", "6", "--lr", "0.3", "--warmup_steps", "2"])
    assert np.isfinite(summary["loss"])
    assert {"loss/pass1", "loss/pass4"} <= set(summary)
    table = capsys.readouterr().out
    assert "loss/pass4" in table


def test_the_pipeline_step_refuses_a_looped_model():
    from tpu_compressed_dp.train.pp_step import make_pp_mesh, make_pp_train_step

    with pytest.raises(NotImplementedError, match="looped"):
        make_pp_train_step(LC, SGD(lr=0.1), CompressionConfig(method=None),
                           make_pp_mesh(2, 2, 1, 1), microbatches=2)
