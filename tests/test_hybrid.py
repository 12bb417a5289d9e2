"""The hybrid decoder (Mamba-2, rope-less attention and LatentMoE layers by a
pattern, one multi-token-prediction module; ``models/hybrid.py``) on the LM
path, held to its plain reference ``benchmark/reference/nemotron_h.py`` at a
tiny width on the CPU: the loss, the model's numbers and every leaf's
gradient; the chunked scan against the recurrence; the shares of every mixer
adding up to the uncut layer; the expert layer drop-less whatever the load;
the MTP module's targets."""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.models import hybrid as hy
from tpu_compressed_dp.ops import ssd
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


ref = load("benchmark/reference/nemotron_h.py")   # puts benchmark/ on the path
builder = load("benchmark/programs/hybrid_dp.py")

# the uncut tiny model, in the configuration file's keys
FULL = {"hidden_size": 32, "norm_eps": 1e-5, "hybrid_override_pattern": "ME*EM",
        "num_hidden_layers": 4, "mamba_num_heads": 8, "mamba_head_dim": 4,
        "n_groups": 4, "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 8,
        "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "n_routed_experts": 16, "num_experts_per_tok": 6, "moe_latent_size": 16,
        "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 40,
        "routed_scaling_factor": 5, "norm_topk_prob": True, "vocab_size": 96,
        "mtp_hybrid_override_pattern": "*E", "mtp_loss_weight": 0.1,
        "initializer_range": 0.2, "seq_len": 16}
# one share of four of every mixer, as a chip of the deployment holds it
HELD = dict(FULL, mamba_num_heads=2, n_groups=1, num_attention_heads=1,
            num_key_value_heads=1, n_routed_experts=4, first_expert=4,
            published={k: FULL[k] for k in (
                "num_hidden_layers", "mamba_num_heads", "n_groups",
                "num_attention_heads", "num_key_value_heads", "n_routed_experts",
                "vocab_size")})
OPT = {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-3, "nesterov": False}


def settings(cfg, **variant):
    """The program's settings for a configuration's keys, by the benchmark
    builder's own mapping (float32 here unless a variant says otherwise)."""
    return builder.hybrid_config({"compute_dtype": "float32", **cfg}, **variant)


@pytest.fixture(autouse=True)
def tiles_of_8_rows(monkeypatch):
    """So that an expert's rows at this size fill several tiles."""
    monkeypatch.setattr(hy, "EXPERT_TILE", 8)


def batch(rows=2, seed=0, cfg=HELD):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, cfg["seq_len"] + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def program_loss_and_grad(hc, params, x, y):
    (_, (loss, aux)), grads = jax.value_and_grad(
        lambda p: (lambda out: (out[0], out[1:]))(hc.loss(p, x, y, {})),
        has_aux=True)(params)
    return loss, aux, grads


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ------------------------------------------------- program against reference

@pytest.mark.parametrize("cfg", [HELD, FULL], ids=["held", "uncut"])
def test_the_program_follows_the_reference_in_float32(cfg):
    """Loss, the two cross-entropies, each expert layer's rows and mass, and
    every leaf's gradient."""
    hc = settings(cfg)
    params = ref.make_params(cfg, jax.random.key(3))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape,
                            jax.eval_shape(lambda: hc.init(jax.random.key(0)))))
    x, y = batch(cfg=cfg)
    loss, aux, grads = program_loss_and_grad(hc, params, x, y)
    (rloss, raux), rgrads = ref.make_loss_and_grad(cfg)(params, x, y)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    for k in ("loss", "expert_rows", "route_mass"):
        np.testing.assert_allclose(aux[k], raux[k], rtol=1e-4, atol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(rgrads)):
        assert rel(g, r) < 1e-4 or float(jnp.max(jnp.abs(g - r))) < 1e-7, (
            jax.tree_util.keystr(path), rel(g, r))
    # the balancing bias is a buffer: it gets no gradient
    assert all(float(jnp.max(jnp.abs(l["e_bias"]))) == 0.0
               for l in grads["layers"] if "e_bias" in l)


def test_the_step_in_bf16_stays_within_its_bands():
    """The step as the benchmark builds it (bf16 compute, float32 masters)
    through ``make_lm_train_step`` on two workers: loss to 2e-3, the kept
    numbers as stated, the weight tensors' first gradient within 3 % of the
    float32 reference's in the median and 25 % at worst."""
    hc = settings(HELD, dtype=jnp.bfloat16)
    params = ref.make_params(HELD, jax.random.key(5))
    x, y = batch(rows=4, seed=1)
    mesh = lm_step.make_lm_mesh(2, 1, 1)
    opt = SGD(lr=OPT["lr"], momentum=OPT["momentum"],
              weight_decay=OPT["weight_decay"])
    comp = CompressionConfig(method=None)
    step = lm_step.make_lm_train_step(hc, opt, comp, mesh, donate=False)
    state = TrainState.create(params, lm_step.init_lm_model_aux(hc),
                              opt.init(params), (), jax.random.key(1))
    new, metrics = step(state, {"input": x, "target": y})
    grad = ref.make_loss_and_grad(HELD)
    halves = [grad(params, x[s], y[s]) for s in (slice(0, 2), slice(2, 4))]
    rloss = np.mean([float(l) for (l, _), _ in halves])
    assert float(metrics["loss"]) == pytest.approx(rloss, rel=2e-3)
    rmtp = np.mean([float(a["loss"][1]) for (_, a), _ in halves])
    assert float(metrics["loss/mtp"]) == pytest.approx(rmtp, rel=2e-3)
    assert float(new.batch_stats["loss"][1]) == float(metrics["loss/mtp"])
    rrows = np.mean([np.asarray(a["expert_rows"]) for (_, a), _ in halves], axis=0)
    assert np.max(np.abs(np.asarray(new.batch_stats["expert_rows"]) - rrows)) <= 1.0
    # momentum after one step from zero = gradient + weight decay x parameter
    rg = jax.tree.map(lambda a, b: (a + b) / 2, halves[0][1], halves[1][1])
    gaps = []
    for m, p, r in zip(*(jax.tree.leaves(t) for t in (
            new.opt_state["momentum"], params, rg))):
        if m.ndim > 1 and float(jnp.linalg.norm(r)) > 0:
            gaps.append(rel(m - OPT["weight_decay"] * p, r))
    assert np.median(gaps) < 0.03 and max(gaps) < 0.25, (np.median(gaps), max(gaps))


# ----------------------------------------------------------------- the scan

def scan_inputs(t=32, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    b, h, p, g, n = 2, 4, 8, 2, 16
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (b, t, g, n)),
            jax.random.normal(k[4], (b, t, g, n)), jnp.ones((h,)))


@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_scan_is_the_recurrence_forward_and_backward(chunk):
    args = scan_inputs()
    want = ssd.ssd_sequential_scan(*args)
    np.testing.assert_allclose(ssd.ssd_chunked_scan(*args, chunk), want,
                               rtol=1e-4, atol=1e-4)
    f = lambda *v: jnp.sum(jnp.sin(ssd.ssd_chunked_scan(*v, chunk)))
    r = lambda *v: jnp.sum(jnp.sin(ssd.ssd_sequential_scan(*v)))
    for got, exp in zip(jax.grad(f, argnums=range(6))(*args),
                        jax.grad(r, argnums=range(6))(*args)):
        assert rel(got, exp) < 1e-4


def test_a_sequence_that_is_not_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="not a multiple"):
        ssd.ssd_chunked_scan(*scan_inputs(t=20), 8)
    hc = settings(HELD)
    params = hc.init(jax.random.key(0))
    with pytest.raises(ValueError, match="whole number"):
        hy.apply_hybrid(hc, params, jnp.zeros((1, 12), jnp.int32))


def test_the_convolution_is_causal_and_carries_its_bias():
    x = jax.random.normal(jax.random.key(0), (1, 9, 3))
    w = jax.random.normal(jax.random.key(1), (4, 3))
    b = jnp.asarray([0.5, -1.0, 2.0])
    y = ssd.causal_depthwise_conv(x, w, b)
    padded = np.concatenate([np.zeros((1, 3, 3)), np.asarray(x)], axis=1)
    want = np.asarray(b) + sum(padded[:, k:k + 9] * np.asarray(w)[k] for k in range(4))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    later = x.at[:, 5:].set(0.0)
    np.testing.assert_array_equal(ssd.causal_depthwise_conv(later, w, b)[:, :5],
                                  y[:, :5])


# ------------------------------------------------------- the shares add up

def layer_params(kind, cfg, seed=7):
    shapes = ref._layer_shapes(cfg, kind)
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return {n: (jnp.ones(s) if n.endswith("norm") and n != "gate_norm"
                else jax.random.normal(k, s) * 0.3)
            for k, (n, s) in zip(keys, sorted(shapes.items()))}


def hidden(cfg, seed=9):
    return jax.random.normal(jax.random.key(seed), (2, cfg["seq_len"],
                                                    cfg["hidden_size"]))


def test_mamba_head_shares_add_up_to_the_uncut_mixer():
    p, h = layer_params("M", FULL), hidden(FULL)
    want, _ = ref.layer("M", p, h, FULL)
    s, hs = ref._sizes(FULL), ref._sizes(HELD)
    hc = settings(HELD)
    total = jnp.zeros_like(h)
    for k in range(4):
        heads = slice(k * hs["h"], (k + 1) * hs["h"])
        inner = slice(k * hs["inner"], (k + 1) * hs["inner"])
        bc = lambda off: np.arange(off + k * hs["g"] * s["n"],
                                   off + (k + 1) * hs["g"] * s["n"])
        conv = np.concatenate([np.arange(inner.start, inner.stop),
                               bc(s["inner"]), bc(s["inner"] + s["g"] * s["n"])])
        cols = np.concatenate([np.arange(inner.start, inner.stop),
                               s["inner"] + conv,
                               s["inner"] + s["conv"] + np.arange(heads.start, heads.stop)])
        share = {"norm": p["norm"], "w_in": p["w_in"][:, cols],
                 "conv_w": p["conv_w"][:, conv], "conv_b": p["conv_b"][conv],
                 "dt_bias": p["dt_bias"][heads], "a_log": p["a_log"][heads],
                 "d_skip": p["d_skip"][heads], "gate_norm": p["gate_norm"][inner],
                 "w_out": p["w_out"][inner]}
        out, _ = hy._layer(hc, "M", share, h)
        total = total + (out - h)
    np.testing.assert_allclose(total, want - h, rtol=2e-4, atol=2e-5)


def test_attention_head_shares_add_up_to_the_uncut_mixer():
    p, h = layer_params("*", FULL), hidden(FULL)
    want, _ = ref.layer("*", p, h, FULL)
    hc = settings(HELD)
    hd = FULL["head_dim"]
    total = jnp.zeros_like(h)
    for k in range(4):           # query head k reads key/value head k // 2
        q = slice(k * hd, (k + 1) * hd)
        kv = slice((k // 2) * hd, (k // 2 + 1) * hd)
        share = {"norm": p["norm"], "wq": p["wq"][:, q], "wk": p["wk"][:, kv],
                 "wv": p["wv"][:, kv], "wo": p["wo"][q]}
        out, _ = hy._layer(hc, "*", share, h)
        total = total + (out - h)
    np.testing.assert_allclose(total, want - h, rtol=2e-4, atol=2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts: the routed sums in the latent added, then
    the up-projection and the shared expert counted once."""
    p, h = layer_params("E", FULL), hidden(FULL)
    p["e_bias"] = p["e_bias"] * 0.1
    want, wstats = ref.layer("E", p, h, FULL)
    x = hy._rms_norm(h, p["norm"], FULL["norm_eps"]).reshape(-1, FULL["hidden_size"])
    u = x @ p["w_down_lat"]
    routed, rows, mass = 0.0, [], 0.0
    for k in range(4):
        hc = settings(dict(HELD, first_expert=4 * k))
        idx, w = hy.route(hc, p, x)
        wts, order, counts = hy.dispatch(hc, idx, w)
        routed = routed + hy.grouped_experts(
            u, p["w1"][4 * k:4 * k + 4], p["w2"][4 * k:4 * k + 4], wts, order,
            counts, hy.EXPERT_TILE)
        rows.append(counts)
        mass += float(jnp.mean(jnp.sum(wts, axis=-1)))
    out = routed @ p["w_up_lat"] + jnp.square(jax.nn.relu(x @ p["ws1"])) @ p["ws2"]
    np.testing.assert_allclose(out.reshape(h.shape), want - h, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(wstats["rows"]))
    assert int(np.sum(np.concatenate(rows))) == x.shape[0] * FULL["num_experts_per_tok"]
    assert mass == pytest.approx(5.0, rel=1e-5)       # the shares keep all of it


# --------------------------------------------------------------- drop-less

def bias_to_held_expert_2():
    """A balancing bias under which every token chooses expert 6 (held expert
    2 of ``HELD``'s 4..7) and five experts no share of ``HELD`` holds."""
    return jnp.full((16,), -10.0).at[jnp.asarray([6, 0, 1, 2, 3, 12])].set(10.0)


def test_every_token_sent_to_one_held_expert_is_computed():
    """A planted balancing bias sends every token to held expert 2 (and to
    five absent ones): its 32 rows fill four tiles, none is dropped, and the
    layer still equals the reference, forward and gradient."""
    cfg = HELD
    hc = settings(cfg)
    p, h = layer_params("E", cfg), hidden(cfg)
    p["e_bias"] = bias_to_held_expert_2()
    x = hy._rms_norm(h, p["norm"], cfg["norm_eps"]).reshape(-1, cfg["hidden_size"])
    idx, w = hy.route(hc, p, x)
    _, _, counts = hy.dispatch(hc, idx, w)
    np.testing.assert_array_equal(counts, [0, 0, x.shape[0], 0])
    out, stats = hy._layer(hc, "E", p, h)
    want, wstats = ref.layer("E", p, h, cfg)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(stats["rows"], wstats["rows"])
    g = jax.grad(lambda q: jnp.sum(jnp.sin(hy._layer(hc, "E", q, h)[0])))(p)
    r = jax.grad(lambda q: jnp.sum(jnp.sin(ref.layer("E", q, h, cfg)[0])))(p)
    for name in g:
        assert rel(g[name], r[name]) < 2e-4 or float(
            jnp.max(jnp.abs(g[name] - r[name]))) < 1e-6, name


def test_the_routed_weights_are_normalised_over_all_the_chosen_and_scaled():
    hc = settings(HELD)
    p = layer_params("E", HELD)
    x = jax.random.normal(jax.random.key(2), (24, HELD["hidden_size"]))
    idx, w = hy.route(hc, p, x)
    assert idx.shape == (24, 6) and int(jnp.max(idx)) < 16
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 5.0, rtol=1e-5)
    wts, _, counts = hy.dispatch(hc, idx, w)
    held = (np.asarray(idx) >= 4) & (np.asarray(idx) < 8)
    assert int(jnp.sum(counts)) == int(held.sum())
    np.testing.assert_allclose(jnp.sum(wts), np.asarray(w)[held].sum(), rtol=1e-5)


# ------------------------------------- the routing kept across the checkpoint

STACK = "EME"                       # two expert layers around another mixer


def expert_stack(case):
    """The settings, layers and input of a small stack whose expert layers
    route by ``case``: ``seeded`` by a small seeded balancing bias,
    ``one_takes_all`` by a planted one that sends every token to held expert
    2 (48 rows: six tiles) and leaves the three others with no row."""
    hc = settings(HELD)
    layers = [layer_params(k, HELD, seed=7 + i) for i, k in enumerate(STACK)]
    for lp in layers:
        if "e_bias" in lp:
            lp["e_bias"] = (lp["e_bias"] * 0.1 if case == "seeded"
                            else bias_to_held_expert_2())
    h = jax.random.normal(jax.random.key(9), (3, HELD["seq_len"],
                                              HELD["hidden_size"]))
    return hc, layers, h


def stack_grad(run):
    """The jitted gradient, by the layers' parameters and the input, of a
    scalar of ``run(layers, h)``'s hidden states."""
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a))), argnums=(0, 1)))


def checkpointed(hc):
    return lambda layers, h: hy._run_layers(hc, STACK, layers, h)[0]


@pytest.mark.parametrize("case", ["seeded", "one_takes_all"])
def test_the_checkpointed_stack_has_the_plain_stacks_gradients(case):
    """Through ``_run_layers`` (every layer under ``jax.checkpoint``, the
    routing and the routed sum kept) as through the same layers one after
    the other with no checkpoint: every parameter's gradient and the
    input's, to float32 rounding (the two programs order their sums apart)."""
    hc, layers, h = expert_stack(case)
    rows = [st["rows"] for st in hy._run_layers(hc, STACK, layers, h)[1]]
    if case == "one_takes_all":
        np.testing.assert_array_equal(rows, [[0, 0, 48, 0]] * 2)
    else:
        assert all(float(jnp.max(r)) > hy.EXPERT_TILE for r in rows)

    def plain(layers, h):
        for kind, lp in zip(STACK, layers):
            h, _ = hy._layer(hc, kind, lp, h)
        return h

    got, want = (stack_grad(f)(layers, h) for f in (checkpointed(hc), plain))
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        assert rel(g, w) < 1e-5 or float(jnp.max(jnp.abs(w))) == 0.0, (
            jax.tree_util.keystr(path), rel(g, w))


def test_the_chosen_logits_sigmoid_is_the_chosen_scores_bit_for_bit():
    """``route`` takes the sigmoid of the gathered logits; gathering from the
    sigmoid over all the experts (the form that made the backward ask for
    all of it) gives the same ids and the same weights, bit for bit."""
    hc = settings(HELD)
    p = layer_params("E", HELD)
    assert float(jnp.min(jnp.abs(p["e_bias"]))) > 0.0
    x = jax.random.normal(jax.random.key(2), (48, HELD["hidden_size"])) * 3.0

    def gathered_scores(lp, x):
        s = jax.nn.sigmoid(jnp.dot(x, lp["router"],
                                   precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + lp["e_bias"], hc.top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, hc.routed_scale * chosen / (
            jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)

    for run in (lambda f: f, jax.jit):
        idx, w = run(lambda lp, x: hy.route(hc, lp, x))(p, x)
        widx, ww = run(gathered_scores)(p, x)
        np.testing.assert_array_equal(idx, widx)
        np.testing.assert_array_equal(w, ww)
    # the bias took part in the choice: without it other experts are chosen
    assert not np.array_equal(idx, hy.route(hc, dict(p, e_bias=0 * p["e_bias"]), x)[0])


@pytest.mark.parametrize("kept, runs", [(hy._KEPT, 1), (("routed",), 2)],
                         ids=["routing_kept", "routed_sum_alone"])
def test_the_backward_runs_no_second_router_top_k_or_sort(monkeypatch, kept, runs):
    """Counted in the compiled gradient of the small stack: an expert layer's
    router product, ``top_k`` and sort run once, the checkpoint's second run
    reading what the first kept.  With the routed sum alone kept (the names
    of the routing taken off the policy) each runs twice: the count sees it."""
    monkeypatch.setattr(hy, "_KEPT", kept)
    hc, layers, h = expert_stack("seeded")
    text = stack_grad(checkpointed(hc)).lower(layers, h).compile().as_text()
    scoped = [l for l in text.splitlines() if "tcdp.moe_dispatch" in l]
    tokens, experts = h.shape[0] * h.shape[1], HELD["published"]["n_routed_experts"]
    n = STACK.count("E") * runs
    assert sum('custom_call_target="TopK"' in l for l in scoped) == n
    assert sum(" sort(" in l for l in scoped) == n
    # the forward product makes [tokens, experts]; the backward's two (for
    # the input and for the router's weight) make other shapes
    assert sum(f" f32[{tokens},{experts}]" in l.split(" dot(")[0]
               for l in scoped if " dot(" in l) == n


# --------------------------------------------------------------------- MTP

def test_mtp_targets_are_shifted_by_two_and_the_last_position_is_left_out():
    hc = settings(HELD)
    params = ref.make_params(HELD, jax.random.key(11))
    x, y = batch(seed=4)
    _, _, aux = hc.loss(params, x, y, {})
    hs, _ = hy.apply_hybrid(hc, params, x, next_tokens=y)
    logz = jax.nn.log_softmax(hs[1] @ params["lm_head"], axis=-1)
    nll = -jnp.take_along_axis(logz[:, :-1], y[:, 1:, None], axis=-1)[..., 0]
    assert float(aux["loss"][1]) == pytest.approx(float(jnp.mean(nll)), rel=1e-5)
    # ... and of the T positions only the first T - 1 count: the last one's
    # hidden state gets no gradient from the loss
    def mtp_loss(bump):
        hs2 = hs.at[1, :, -1].add(bump)
        nll2 = hy.fused_head_xent_tokens(
            hs2, params["lm_head"], jnp.stack([y, jnp.roll(y, -1, axis=1)]))
        return jnp.mean(nll2[1][:, :-1])
    assert float(jnp.max(jnp.abs(jax.grad(mtp_loss)(jnp.zeros(hs.shape[-1]))))) == 0.0
    # the module reads the NEXT token's embedding: another y_0 moves it
    moved = y.at[:, 0].set((y[:, 0] + 1) % HELD["vocab_size"])
    assert float(hc.loss(params, x, moved, {})[2]["loss"][1]) != float(aux["loss"][1])


# ------------------------------------------------------------ the settings

def test_the_stage_preset_counts_what_the_issue_counts():
    hc = hy.nemotron3_super_stage()
    shapes = jax.tree.leaves(hy.hybrid_param_shapes(hc),
                             is_leaf=lambda s: isinstance(s, tuple))
    assert sum(int(np.prod(s)) for s in shapes) == 915_161_056
    assert hc.pattern.count("M") == 5 and hc.pattern.count("E") == 5
    assert hc.init_aux()["expert_rows"].shape == (6, 8)


@pytest.mark.parametrize("bad", [
    dict(mamba_heads_held=3), dict(n_heads_held=3, n_kv_heads_held=2),
    dict(first_expert=14), dict(pattern="MX")])
def test_settings_that_are_no_share_are_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(settings(HELD), **bad)


def test_the_step_refuses_a_tensor_or_sequence_axis():
    hc = settings(HELD)
    with pytest.raises(ValueError, match="tensor"):
        hc.validate_mesh(2)
    with pytest.raises(ValueError, match="sequence"):
        hc.loss(None, None, None, {"seq": 2, "tensor": 1})
