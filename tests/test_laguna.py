"""The ``laguna`` layer kinds of the hybrid decoder (``models/hybrid.py``: full
and sliding-window gated attention with a head count by layer type, a dense
and a sparse SwiGLU feed-forward) on the LM path, held to their plain
reference ``benchmark/reference/laguna.py`` at a tiny width on the CPU: logits,
loss, the model's numbers and every leaf's gradient; the YaRN table against
its closed form; the window; the expert shares adding up to the uncut layer;
the stage preset's count."""

import dataclasses
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.models import hybrid as hy
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


ref = load("benchmark/reference/laguna.py")   # puts benchmark/ on the path
builder = load("benchmark/programs/laguna_dp.py")

# the uncut tiny model, in the configuration file's keys: 2 full and 3 window
# layers, 6 / 8 query heads on 2 key/value heads, a window of 16, top-4 of 16
FULL = {"hidden_size": 32, "rms_norm_eps": 1e-6, "num_hidden_layers": 5,
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 4, "attention_factor": 1.2, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "intermediate_size": 64, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
        "moe_routed_scaling_factor": 2.5, "vocab_size": 96,
        "initializer_range": 0.2, "seq_len": 48}
# one share of four of the routed experts, as a chip of the deployment holds it
HELD = dict(FULL, num_experts=4, first_expert=4,
            published={k: FULL[k] for k in ("num_hidden_layers", "num_experts",
                                            "vocab_size")})
OPT = {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-3, "nesterov": False}


def settings(cfg, **variant):
    """The program's settings for a configuration's keys, by the benchmark
    builder's own mapping (float32 here unless a variant says otherwise)."""
    return builder.laguna_config({"compute_dtype": "float32", **cfg}, **variant)


@pytest.fixture(autouse=True)
def tiles_of_8_rows(monkeypatch):
    """So that an expert's rows at this size fill several tiles."""
    monkeypatch.setattr(hy, "EXPERT_TILE", 8)


def batch(rows=2, seed=0, cfg=HELD):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, cfg["seq_len"] + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ------------------------------------------------- program against reference

@pytest.mark.parametrize("cfg", [HELD, FULL], ids=["held", "uncut"])
def test_the_program_follows_the_reference_in_float32(cfg):
    """Logits, loss, each sparse layer's rows and mass, every leaf's gradient."""
    hc = settings(cfg)
    params = ref.make_params(cfg, jax.random.key(3))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape,
                            jax.eval_shape(lambda: hc.init(jax.random.key(0)))))
    x, y = batch(cfg=cfg)
    hf, _ = hy.apply_hybrid(hc, params, x)
    np.testing.assert_allclose(hf @ params["lm_head"], ref.logits_fn(params, x, cfg),
                               rtol=2e-4, atol=2e-5)
    (_, (loss, aux)), grads = jax.value_and_grad(
        lambda p: (lambda out: (out[0], out[1:]))(hc.loss(p, x, y, {})),
        has_aux=True)(params)
    (rloss, raux), rgrads = ref.make_loss_and_grad(cfg)(params, x, y)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    assert set(aux) == set(raux) and aux["loss"].shape == (1,)
    for k in aux:
        np.testing.assert_allclose(aux[k], raux[k], rtol=1e-4, atol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(rgrads)):
        assert rel(g, r) < 1e-4 or float(jnp.max(jnp.abs(g - r))) < 1e-7, (
            jax.tree_util.keystr(path), rel(g, r))


def test_the_step_in_bf16_stays_within_its_bands():
    """The step as the benchmark builds it (bf16 compute, float32 masters)
    through ``make_lm_train_step`` on two workers, against the float32
    reference: loss to 3e-3, the rows a held expert received but for a few
    last-choice flips, the weight tensors' first gradient within 6 % in the
    median (ten sublayers 32 wide average little of bf16's rounding away:
    3.4 % in the mean here, 12 % at the float32 tests' initializer range of
    0.2; a lost leaf reads 1.0), and the step's own counters in its metrics."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    held = dict(HELD, initializer_range=0.05)
    hc = settings(held, dtype=jnp.bfloat16)
    mesh = lm_step.make_lm_mesh(2, 1, 1, devices=jax.devices()[:2])
    opt = SGD(**OPT)
    comp = CompressionConfig(method=None)
    step = lm_step.make_lm_train_step(hc, opt, comp, mesh, donate=False)
    params = ref.make_params(held, jax.random.key(5))
    state = TrainState.create(
        params, lm_step.init_lm_model_aux(hc), opt.init(params),
        lm_step.init_lm_ef_state(hc, params, comp, mesh), jax.random.key(1))
    x, y = batch(rows=4, seed=2)
    dat = NamedSharding(mesh, P("data", "seq"))
    new, metrics = step(state, {"input": jax.device_put(x, dat),
                                "target": jax.device_put(y, dat)})
    grad = ref.make_loss_and_grad(held)
    halves = [grad(params, x[i:i + 2], y[i:i + 2]) for i in (0, 2)]
    rloss = np.mean([float(h[0][0]) for h in halves])
    assert float(metrics["loss"]) == pytest.approx(rloss, rel=3e-3)
    assert "loss/mtp" not in metrics and float(metrics["loss/lm"]) == pytest.approx(
        float(metrics["loss"]), rel=1e-6)
    rows = np.mean([np.asarray(h[0][1]["expert_rows"]) for h in halves], axis=0)
    got = np.asarray(new.batch_stats["expert_rows"])
    assert got.shape == rows.shape == (4, 4)
    assert np.max(np.abs(got - rows)) <= 3
    assert float(metrics["model/expert_rows"]) == pytest.approx(float(np.mean(got)))
    # momentum after the first step is the gradient (plus the decay's term)
    rg = jax.tree.map(lambda a, b: (a + b) / 2, halves[0][1], halves[1][1])
    gaps = [rel(m - OPT["weight_decay"] * p, g) for m, p, g in zip(
        jax.tree.leaves(new.opt_state["momentum"]), jax.tree.leaves(params),
        jax.tree.leaves(rg)) if g.ndim > 1]
    assert np.median(gaps) < 0.06 and max(gaps) < 0.6, (np.median(gaps), max(gaps))


# ------------------------------------------------------------------- rotary

def xs2_yarn_closed_form():
    """ISSUE 41's closed form with Laguna-XS.2's constants, in float64."""
    c = np.arange(32, dtype=np.float64)
    inv = 500000.0 ** (-2 * c / 64)
    low = math.floor(64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(500000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(500000)))
    assert (low, high) == (5, 16)
    ramp = np.clip((c - low) / (high - low), 0, 1)
    return inv * (1 - ramp) + inv / 64 * ramp


def test_the_yarn_table_is_the_closed_form():
    """The stage preset's full-attention frequencies, cos and sin against the
    closed form; the benchmark's configuration file gives the same settings;
    the window layers' are the default rotary's on all 128 channels."""
    stage = hy.laguna_xs2_stage()
    want = xs2_yarn_closed_form()
    inv = stage.rotary_full.inv_freq()
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv, want, rtol=1e-7)
    assert inv[5] == np.float32(want[5]) and inv[20] == np.float32(want[20])
    np.testing.assert_allclose(inv[16:], want[16:], rtol=1e-7)      # wholly interpolated
    np.testing.assert_allclose(inv[:6] * 1.0, 500000.0 ** (-2 * np.arange(6) / 64),
                               rtol=1e-7)                          # untouched
    cos, sin = hy.rotary_tables(stage.rotary_full, 64)
    angle = np.arange(64, dtype=np.float32)[:, None] * inv[None, :]
    np.testing.assert_allclose(cos, 1.4158883083359672 * np.cos(angle), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sin, 1.4158883083359672 * np.sin(angle), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(stage.rotary_window.inv_freq(),
                               10000.0 ** (-2 * np.arange(64) / 128), rtol=1e-7)
    with open(os.path.join(ROOT, "benchmark/configs/laguna_xs2.json")) as f:
        cfg = json.load(f)
    assert builder.laguna_config(cfg) == stage
    np.testing.assert_array_equal(
        ref.inv_frequencies(cfg["rope_parameters"]["full_attention"], 128), inv)


def test_only_the_rotary_channels_turn_and_position_zero_is_scaled_alone():
    rot = hy.Rotary(theta=500000.0, dim=8, attention_factor=1.5)
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 16))
    out = hy._rotate(x, *hy.rotary_tables(rot, 5))
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[:, 0, :, :8], 1.5 * x[:, 0, :, :8], rtol=1e-6)
    # a pair (c, c + 4) turns together: its length is kept up to the factor
    np.testing.assert_allclose(out[..., 1] ** 2 + out[..., 5] ** 2,
                               2.25 * (x[..., 1] ** 2 + x[..., 5] ** 2), rtol=1e-5)


# ------------------------------------------------------------------- mixers

def sublayer_params(index, cfg=FULL, seed=7):
    return ref.make_params(cfg, jax.random.key(seed))["layers"][index]


def hidden(cfg=FULL, seed=9):
    return jax.random.normal(jax.random.key(seed), (2, cfg["seq_len"], cfg["hidden_size"]))


@pytest.mark.parametrize("index, kind, reach", [(0, "F", None), (2, "W", 16)],
                         ids=["full", "window"])
def test_a_query_sees_its_window_and_no_further(index, kind, reach):
    """Moving token 3 moves every later row of a full layer, and of a window
    layer the rows 3 .. 3 + 15 alone; no row before it."""
    hc, p, h = settings(FULL), sublayer_params(index), hidden()
    run = lambda h: hy._layer(hc, kind, p, h)[0]
    moved = np.asarray(jnp.max(jnp.abs(
        run(h.at[:, 3].add(1.0)) - run(h)), axis=(0, 2))) > 1e-7
    last = FULL["seq_len"] if reach is None else 3 + reach
    assert not moved[:3].any() and moved[3:last].all() and not moved[last:].any()


def test_every_layer_type_has_its_own_head_count_gate_and_norms():
    hc = settings(FULL)
    shapes = hy.hybrid_param_shapes(hc)["layers"]
    assert hc.pattern == "FDWEWEWEFE" and "mtp" not in hy.hybrid_param_shapes(hc)
    assert [s["wq"][1] // 16 for s in shapes[0::2]] == [6, 8, 8, 8, 6]
    assert [s["w_head_gate"] for s in shapes[0::2]] == [(32, 6), (32, 8)] + [(32, 8)] * 2 + [(32, 6)]
    assert all(s["wk"] == (32, 32) and s["q_norm"] == s["k_norm"] == (16,)
               for s in shapes[0::2])
    # a closed gate closes the head: x > 0 and a gate column of -1e3 make
    # sigmoid(x W_g) 0 on heads 1..5, and only head 0's rows of W_o count
    p, x = sublayer_params(0), jnp.abs(hidden()) + 0.1
    shut = dict(p, w_head_gate=p["w_head_gate"].at[:, 1:].set(-1e3))
    np.testing.assert_allclose(
        hy._gated_attention_mixer(hc, "F", shut, x),
        hy._gated_attention_mixer(hc, "F", dict(shut, wo=shut["wo"].at[16:].set(0.0)), x),
        atol=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts: the routed parts added, the shared expert
    counted once, are the uncut reference's sparse sublayer."""
    p, h = sublayer_params(3), hidden()
    want, wstats = ref.sublayer("sparse", p, h, FULL)
    x = hy._rms_norm(h, p["norm"], FULL["rms_norm_eps"]).reshape(-1, FULL["hidden_size"])
    routed, rows, mass = 0.0, [], 0.0
    for k in range(8):
        hc = settings(dict(HELD, num_experts=2, first_expert=2 * k))
        idx, w = hy.route(hc, p, x)
        wts, order, counts = hy.dispatch(hc, idx, w)
        sl = slice(2 * k, 2 * k + 2)
        routed = routed + hy.gated_experts(x, p["wg"][sl], p["wu"][sl], p["wd"][sl],
                                           wts, order, counts, hy.EXPERT_TILE)
        rows.append(counts)
        mass += float(jnp.mean(jnp.sum(wts, axis=-1)))
    out = routed + hy._swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"], jnp.float32)
    np.testing.assert_allclose(out.reshape(h.shape), want - h, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(rows), np.asarray(wstats["rows"]))
    assert int(np.sum(np.concatenate(rows))) == x.shape[0] * FULL["num_experts_per_tok"]
    assert mass == pytest.approx(2.5, rel=1e-5)       # the shares keep all of it


@pytest.mark.parametrize("windows", [False, True], ids=["rows", "windows"])
def test_the_gated_product_is_dropless_and_has_the_dense_forms_gradients(
        windows, monkeypatch):
    """Every token on one held expert (a router column that wins everywhere):
    its rows fill many tiles, all are computed, and the three weight
    gradients and the rows' are those of the dense masked form; the same with
    the accumulators carried as windows of a row, as rows wider than
    ``ROW_SCATTER_MAX`` are."""
    if windows:
        monkeypatch.setattr(hy, "ROW_SCATTER_MAX", 16)
        monkeypatch.setattr(hy, "ROW_WINDOW", 8)
        assert hy._accumulator_shape((96, 32)) == (96, 4, 8)
    else:       # the Laguna cell's rows go as windows, the hybrid cell's as they are
        assert hy._accumulator_shape((16384, 2048)) == (16384, 16, 128)
        assert hy._accumulator_shape((8192, 1024)) == (8192, 1024)
        assert hy._accumulator_shape((96, 32)) == (96, 32)
    p, h = sublayer_params(3), hidden()
    p = dict(p, router=p["router"].at[:, 5].set(0.0) + jnp.zeros((32, 16)).at[:, 5].set(9.0))
    hc = settings(dict(HELD, num_experts=4, first_expert=4))
    x = jnp.abs(hy._rms_norm(h, p["norm"], 1e-6)).reshape(-1, 32)
    idx, w = hy.route(hc, p, x)
    wts, order, counts = hy.dispatch(hc, idx, w)
    assert int(counts[1]) == x.shape[0] > 8 * hy.EXPERT_TILE
    sl = slice(4, 8)

    def tiled(x, wg, wu, wd):
        return jnp.sum(hy.gated_experts(x, wg, wu, wd, wts, order, counts,
                                        hy.EXPERT_TILE) ** 2)

    def dense(x, wg, wu, wd):
        y = sum(wts[:, e, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
                for e in range(4))
        return jnp.sum(y ** 2)

    args = (x, p["wg"][sl], p["wu"][sl], p["wd"][sl])
    assert float(tiled(*args)) == pytest.approx(float(dense(*args)), rel=1e-5)
    for a, b in zip(jax.grad(tiled, (0, 1, 2, 3))(*args),
                    jax.grad(dense, (0, 1, 2, 3))(*args)):
        assert rel(a, b) < 1e-4


# --------------------------------------------------------------- the preset

def test_the_stage_preset_counts_what_the_issue_counts():
    stage = hy.laguna_xs2_stage()
    sizes = [int(np.prod(s)) for s in jax.tree.leaves(
        hy.hybrid_param_shapes(stage), is_leaf=lambda s: isinstance(s, tuple))]
    assert (sum(sizes), len(sizes)) == (691_625_216, 79)
    per_sublayer = [sum(int(np.prod(s)) for s in l.values())
                    for l in hy.hybrid_param_shapes(stage)["layers"]]
    assert per_sublayer == [29_460_736, 50_333_696] + [37_882_112, 104_335_360] * 3 + [
        29_460_736, 104_335_360]
    uncut = dataclasses.replace(
        stage, vocab_held=100352, experts_held=256,
        pattern="".join(("W" if l % 4 else "F") + ("E" if l else "D")
                        for l in range(40)))
    total = sum(int(np.prod(s)) for s in jax.tree.leaves(
        hy.hybrid_param_shapes(uncut), is_leaf=lambda s: isinstance(s, tuple)))
    assert uncut.pattern.count("F") == 10 and len(uncut.pattern) == 80
    assert round(total / 1e9, 2) == 33.44               # the published 33.4 B
    init = jax.eval_shape(lambda: hy.init_hybrid(hy.tiny_laguna(), jax.random.key(0)))
    assert set(init) == {"embed", "layers", "final_norm", "lm_head"}


def test_the_nemotron_presets_tree_did_not_move():
    """The other family's preset keeps its leaves and their order."""
    shapes = hy.hybrid_param_shapes(hy.nemotron3_super_stage())
    sizes = [int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple))]
    assert (sum(sizes), len(sizes)) == (915_161_056, 116)
    assert sorted(shapes["layers"][1]) == ["e_bias", "norm", "router", "w1", "w2",
                                           "w_down_lat", "w_up_lat", "ws1", "ws2"]


@pytest.mark.parametrize("bad", [
    {"pattern": "FX"}, {"full_heads": 0}, {"window": 0}, {"dense_ffn": 0},
    {"window_heads": 7}, {"rotary_window": None},
    {"rotary_full": hy.Rotary(theta=1e4, dim=32)}])
def test_settings_that_name_no_layer_are_refused(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(hy.tiny_laguna(), **bad)


def test_the_harness_knows_the_presets():
    from tpu_compressed_dp.harness import lm as lm_harness

    assert lm_harness.PRESETS["laguna_xs2"]() == hy.laguna_xs2_stage()
    args = lm_harness.build_parser().parse_args(
        ["--preset", "tiny_laguna", "--fp32", "--vocab", "128"])
    cfg = lm_harness.build_config(args)
    assert (cfg.pattern, cfg.vocab_held, cfg.dtype) == ("FDWEWEWEFE", 128, jnp.float32)
