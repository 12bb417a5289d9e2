"""The decoder-hybrid-decoder (``models/sambay.py``: Mamba-1 selective scans,
differential attention in a window, over the whole sequence and across layers,
Gated Memory Units, a tied head) on the LM path, held to its plain reference
``benchmark/reference/phi4flash.py`` at a tiny width on the CPU: logits, loss,
the model's numbers and every leaf's gradient; the selective scan against the
recurrence as written; the tensors that cross layers and their cotangents;
the two-softmax form; the tied head; ``lam0`` by published index; the stage
preset's count."""

import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compressed_dp.models import sambay as sy
from tpu_compressed_dp.models.transformer import fused_head_xent_tokens
from tpu_compressed_dp.ops import selective_scan as sscan
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


ref = load("benchmark/reference/phi4flash.py")   # puts benchmark/ on the path
builder = load("benchmark/programs/phi4flash_dp.py")

# the tiny model in the configuration file's keys: hidden 64, the eight
# layers 14-21 of 32 (S W S F G X G X), 4 query and 2 key/value heads of 16,
# a window of 8, 64 tokens, the scan in chunks of 16
TINY = {"hidden_size": 64, "intermediate_size": 128, "layer_norm_eps": 1e-5,
        "mb_per_layer": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 8, "first_layer": 14, "sliding_window": 8,
        "vocab_size": 96, "mamba_d_state": 4, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_dt_rank": 4, "scan_chunk": 16,
        "published": {"num_hidden_layers": 32, "vocab_size": 768},
        "initializer_range": 0.2, "seq_len": 64}
OPT = {"lr": 0.1, "momentum": 0.9, "weight_decay": 1e-3, "nesterov": False}


def settings(cfg=TINY, **variant):
    """The program's settings for a configuration's keys, by the benchmark
    builder's own mapping (float32 here unless a variant says otherwise)."""
    return builder.phi4flash_config({"compute_dtype": "float32", **cfg}, **variant)


def batch(rows=2, seed=0, cfg=TINY):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, cfg["seq_len"] + 1)).astype(np.int32)
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def loss_and_grads(hc, params, x, y):
    return jax.value_and_grad(
        lambda p: (lambda out: (out[0], out[1:]))(hc.loss(p, x, y, {})),
        has_aux=True)(params)


# ------------------------------------------------- program against reference

def test_the_program_follows_the_reference_in_float32():
    """Logits, loss, every attention layer's lam, every Mamba-1 layer's rms of
    m, every leaf's gradient."""
    hc = settings()
    assert hc.pattern == "SWSFGXGX" and hc.producers() == {4: 2, 5: 3, 6: 2, 7: 3}
    params = ref.make_params(TINY, jax.random.key(3))
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape,
                            jax.eval_shape(lambda: hc.init(jax.random.key(0)))))
    x, y = batch()
    hf, _ = sy.apply_sambay(hc, params, x)
    np.testing.assert_allclose(hf @ params["embed"].T, ref.logits_fn(params, x, TINY),
                               rtol=2e-4, atol=2e-5)
    (_, (loss, aux)), grads = loss_and_grads(hc, params, x, y)
    (rloss, raux), rgrads = ref.make_loss_and_grad(TINY)(params, x, y)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    assert set(aux) == set(raux) and aux["loss"].shape == (1,)
    assert aux["diff_lambda"].shape == (4,) and aux["memory_rms"].shape == (2,)
    for k in aux:
        np.testing.assert_allclose(aux[k], raux[k], rtol=1e-4, atol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(rgrads))
    for (path, g), r in zip(flat, jax.tree.leaves(rgrads)):
        assert float(jnp.max(jnp.abs(r))) > 0, jax.tree_util.keystr(path)
        assert rel(g, r) < 2e-4 or float(jnp.max(jnp.abs(g - r))) < 1e-7, (
            jax.tree_util.keystr(path), rel(g, r))


def test_the_step_in_bf16_stays_within_its_bands():
    """The step as the benchmark builds it (bf16 compute, float32 masters)
    through ``make_lm_train_step`` on two workers, against the float32
    reference: loss to 3e-3, lam to 1e-6 (it is read from the float32
    masters), the rms of m to 2 %, the weight tensors' first gradient within
    6 % in the median (a lost leaf reads 1.0), and the step's own counters."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = dict(TINY, initializer_range=0.05)
    hc = settings(cfg, dtype=jnp.bfloat16)
    mesh = lm_step.make_lm_mesh(2, 1, 1, devices=jax.devices()[:2])
    opt, comp = SGD(**OPT), CompressionConfig(method=None)
    step = lm_step.make_lm_train_step(hc, opt, comp, mesh, donate=False)
    params = ref.make_params(cfg, jax.random.key(5))
    state = TrainState.create(
        params, lm_step.init_lm_model_aux(hc), opt.init(params),
        lm_step.init_lm_ef_state(hc, params, comp, mesh), jax.random.key(1))
    x, y = batch(rows=4, seed=2)
    dat = NamedSharding(mesh, P("data", "seq"))
    new, metrics = step(state, {"input": jax.device_put(x, dat),
                                "target": jax.device_put(y, dat)})
    grad = ref.make_loss_and_grad(cfg)
    halves = [grad(params, x[i:i + 2], y[i:i + 2]) for i in (0, 2)]
    rloss = np.mean([float(h[0][0]) for h in halves])
    assert float(metrics["loss"]) == pytest.approx(rloss, rel=3e-3)
    assert float(metrics["loss/lm"]) == pytest.approx(float(metrics["loss"]), rel=1e-6)
    lam = np.asarray(halves[0][0][1]["diff_lambda"])
    np.testing.assert_allclose(new.batch_stats["diff_lambda"], lam, rtol=1e-6)
    assert float(metrics["model/diff_lambda"]) == pytest.approx(float(np.mean(lam)), rel=1e-6)
    rms = np.mean([np.asarray(h[0][1]["memory_rms"]) for h in halves], axis=0)
    np.testing.assert_allclose(new.batch_stats["memory_rms"], rms, rtol=0.02)
    rg = jax.tree.map(lambda a, b: (a + b) / 2, halves[0][1], halves[1][1])
    gaps = [rel(m - OPT["weight_decay"] * p, g) for m, p, g in zip(
        jax.tree.leaves(new.opt_state["momentum"]), jax.tree.leaves(params),
        jax.tree.leaves(rg)) if g.ndim > 1]
    assert np.median(gaps) < 0.06 and max(gaps) < 0.6, (np.median(gaps), max(gaps))


# --------------------------------------------------------- the selective scan

def scan_operands(seed, bsz=2, t=64, c=24, n=4, dt_scale=1.0, a_scale=1.0):
    k = jax.random.split(jax.random.key(seed), 9)
    return dict(
        u=jax.random.normal(k[0], (bsz, t, c)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (bsz, t, c))) * dt_scale,
        a=-jnp.exp(jax.random.normal(k[2], (c, n))) * a_scale,
        b=jax.random.normal(k[3], (bsz, t, n)), c=jax.random.normal(k[4], (bsz, t, n)),
        d=jax.random.normal(k[5], (c,)), state=jax.random.normal(k[6], (bsz, n, c)),
        wy=jax.random.normal(k[7], (bsz, t, c)), ws=jax.random.normal(k[8], (bsz, n, c)))


def scan_loss(scan, wy, ws):
    def f(*operands):
        y, last = scan(*operands)
        return jnp.sum(y * wy) + jnp.sum(last * ws)
    return f


@pytest.mark.parametrize("case", ["a_state_handed_in", "no_state",
                                  "cotangent_at_the_chunk_edges_only",
                                  "one_chunk", "large_dt"])
def test_the_selective_scan_is_the_recurrence_forward_and_backward(case):
    """The chunked scan with its own backward against the recurrence as
    written, a token a step, differentiated by reverse mode: y, the last
    state and all seven gradients; with a state handed in and without; with
    the output's cotangent on the first and last token of every chunk alone
    (what crosses a boundary is then all there is); with one chunk; and with
    ``dt A`` so large over a chunk (-1.6 a token, exp(-205) over 128) that a
    split ``exp(cum_t) exp(-cum_s)`` would overflow."""
    chunk = 16
    if case == "large_dt":
        ops, chunk = scan_operands(2, t=256, n=4), 128
        ops["dt"] = jnp.full_like(ops["dt"], 0.1)
        ops["a"] = jnp.full_like(ops["a"], -16.0)
    else:
        ops = scan_operands(1, t=16 if case == "one_chunk" else 64)
    wy, ws = ops.pop("wy"), ops.pop("ws")
    if case == "cotangent_at_the_chunk_edges_only":
        edge = (jnp.arange(64) % 16 == 0) | (jnp.arange(64) % 16 == 15)
        wy, ws = wy * edge[None, :, None], ws * 0.0
    if case == "no_state":
        ops["state"] = None
    names = [k for k in ops if ops[k] is not None]
    chunked = lambda u, dt, a, b, c, d, state=None: sscan.selective_scan(
        u, dt, a, b, c, d, chunk, state)
    args = [ops[k] for k in names]
    got, want = (jax.value_and_grad(scan_loss(f, wy, ws), argnums=tuple(range(len(args))))(
        *args) for f in (chunked, sscan.selective_scan_sequential))
    assert np.isfinite(float(got[0])) and float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, w in zip(names, got[1], want[1]):
        assert np.all(np.isfinite(np.asarray(g))), name
        assert rel(g, w) < 1e-5, (name, rel(g, w))
    y, last = chunked(*args)
    y_w, last_w = sscan.selective_scan_sequential(*args)
    np.testing.assert_allclose(y, y_w, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, last_w, rtol=1e-5, atol=1e-6)


def test_two_halves_with_the_state_handed_on_are_the_whole():
    ops = scan_operands(4)
    half = lambda v, i: v[:, 32 * i:32 * (i + 1)]
    seq = ("u", "dt", "b", "c")
    whole, _ = sscan.selective_scan(*(ops[k] for k in ("u", "dt", "a", "b", "c", "d")), 16)
    state, parts = None, []
    for i in (0, 1):
        part = {k: half(ops[k], i) if k in seq else ops[k] for k in ops}
        y, state = sscan.selective_scan(*(part[k] for k in ("u", "dt", "a", "b", "c", "d")),
                                        16, state)
        parts.append(y)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        sscan.selective_scan(*(half(ops[k], 0)[:, :20] if k in seq else ops[k]
                               for k in ("u", "dt", "a", "b", "c", "d")), 16)


@pytest.mark.parametrize("case", ["a_state_handed_in", "no_state", "one_block"])
def test_the_scans_kernels_are_the_xla_build(monkeypatch, case):
    """``selective_scan_fwd`` and ``selective_scan_bwd`` under the Pallas
    interpreter against the XLA build of the same chunked arithmetic and
    against the recurrence as written: y, the last state, all seven
    gradients; over two blocks of channels (dB and dC summed over them in the
    kernel, the state and dA kept block by block) and over one."""
    monkeypatch.setattr(sscan, "_KERNEL_BLOCK", 128 if case != "one_block" else 256)
    ops = scan_operands(6, c=256, n=8)
    ops["dt"] = ops["dt"] * 0.3
    wy, ws = ops.pop("wy"), ops.pop("ws")
    if case == "no_state":
        ops["state"] = None
    names = [k for k in ops if ops[k] is not None]
    args = [ops[k] for k in names]
    build = lambda impl: lambda u, dt, a, b, c, d, state=None: sscan.selective_scan(
        u, dt, a, b, c, d, 16, state, impl)
    got, xla, want = (jax.value_and_grad(scan_loss(f, wy, ws),
                                         argnums=tuple(range(len(args))))(*args)
                      for f in (build("interpret"), build("xla"),
                                sscan.selective_scan_sequential))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, x, w in zip(names, got[1], xla[1], want[1]):
        assert rel(g, x) < 1e-5 and rel(g, w) < 1e-5, (name, rel(g, x), rel(g, w))
    for g, w in zip(build("interpret")(*args), build("xla")(*args)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_which_build_of_the_scan_is_taken(monkeypatch):
    """The kernels on the TPU where the shapes are whole tiles, the XLA build
    elsewhere; the switch off, on (a compile for a described chip) and under
    the interpreter."""
    monkeypatch.delenv("TPU_CDP_SCAN_KERNEL", raising=False)
    assert sscan._pick_impl(5120, 16, 128) == "xla"            # the CPU
    monkeypatch.setenv("TPU_CDP_SCAN_KERNEL", "1")
    assert sscan._pick_impl(5120, 16, 128) == "pallas"
    assert sscan._pick_impl(5120, 4, 128) == "xla"             # 4 states: no tile
    assert sscan._pick_impl(5000, 16, 128) == "xla"
    assert sscan._pick_impl(256, 8, 16) == "pallas"
    monkeypatch.setenv("TPU_CDP_SCAN_KERNEL", "interpret")
    assert sscan._pick_impl(5120, 16, 128) == "interpret"
    monkeypatch.setenv("TPU_CDP_SCAN_KERNEL", "0")
    assert sscan._pick_impl(5120, 16, 128) == "xla"


def test_the_model_through_the_kernels_follows_the_reference(monkeypatch):
    """The whole model with the scan's kernels under the interpreter (8
    states, so that the state's rows are a tile): loss and every gradient
    leaf against the float32 reference."""
    monkeypatch.setenv("TPU_CDP_SCAN_KERNEL", "interpret")
    cfg = dict(TINY, mamba_d_state=8)
    hc = settings(cfg)
    assert sscan._pick_impl(hc.d_inner, hc.ssm_state, hc.chunk) == "interpret"
    params = ref.make_params(cfg, jax.random.key(4))
    x, y = batch(rows=1, cfg=cfg)
    (_, (loss, aux)), grads = loss_and_grads(hc, params, x, y)
    (rloss, raux), rgrads = ref.make_loss_and_grad(cfg)(params, x, y)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-5)
    np.testing.assert_allclose(aux["memory_rms"], raux["memory_rms"], rtol=1e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(rgrads)):
        assert rel(g, r) < 2e-4 or float(jnp.max(jnp.abs(g - r))) < 1e-7, (
            jax.tree_util.keystr(path), rel(g, r))


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler refuses here
    what it would refuse on the chip.  Described inside the fixture, never at
    import: only the worker that runs this file loads libtpu."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_scans_kernels_compile_for_v5e_at_the_cells_size(one_chip):
    """Forward and backward pass Mosaic for a v5e at 8,192 tokens, 5,120
    channels, 16 states, chunks of 128: a compile, not a run."""
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype,
                                                               sharding=one_chip)
    t, c, n = 8192, 5120, 16
    args = (sd((1, t, c), jnp.bfloat16), sd((1, t, c)), sd((c, n)),
            sd((1, t, n), jnp.bfloat16), sd((1, t, n), jnp.bfloat16), sd((c,)))
    loss = lambda *x: jnp.sum(sscan.selective_scan(*x, 128, impl="pallas")[0].astype(
        jnp.float32))
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    # nothing of [T, state, channels]: the temporaries are the lane-broadcast
    # B and C and the per-lane partial sums of dB and dC, [T, 16, 128] each
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_no_state_of_every_token_is_alive_in_the_models_gradient():
    """Nothing of [T, state, channels] in the traced gradient of the model's
    loss: a chunk's [chunk, state, channels] blocks are the largest the scan
    makes, forward or backward; the kept boundary states are [T / chunk, ...]."""
    hc = settings()
    params = ref.make_params(TINY, jax.random.key(3))
    x, y = batch(rows=1)
    jaxpr = jax.make_jaxpr(lambda p: loss_and_grads(hc, p, x, y)[1])(params)
    t, n, c = TINY["seq_len"], hc.ssm_state, hc.d_inner
    rows = {math.prod(a.shape[:-2]) for a in _avals(jaxpr.jaxpr)
            if getattr(a, "shape", ())[-2:] == (n, c)}
    assert max(rows) == hc.chunk and t not in rows and t // hc.chunk in rows


def test_the_layers_checkpoint_keeps_the_scan_and_does_not_run_it_again(monkeypatch):
    """The scan's forward runs once a Mamba-1 layer in a gradient: the
    rematerialised layer reads the kept output and boundary states.  Counted
    as loops in the lowered gradient: a scan's forward is two (the chunks',
    and in it the tokens'), and a checkpoint that kept nothing has two more a
    Mamba-1 layer."""
    hc = settings()
    params = ref.make_params(TINY, jax.random.key(3))
    x, y = batch(rows=1)
    loops = lambda: jax.jit(lambda p: loss_and_grads(hc, p, x, y)[1]).lower(
        params).as_text().count("stablehlo.while")
    kept = loops()
    monkeypatch.setattr(sscan, "KEPT_NAMES", ())
    assert loops() - kept == 2 * hc.count("S")


# ------------------------------------------------- what crosses layer borders

def _with_zeros(params, hc, rows, t):
    """The parameters with a zero ``eps`` at every producer and reader of a
    handed tensor: its gradient is that tensor's cotangent at that place."""
    src = hc.producers()
    layers = [dict(lp) for lp in params["layers"]]
    for i, kind in enumerate(hc.pattern):
        if i in src or i in src.values():
            if kind in "SG":
                layers[i]["eps_m"] = jnp.zeros((rows, t, hc.d_inner))
            else:
                layers[i]["eps_k"] = jnp.zeros((rows, t, hc.n_kv_heads, hc.head_dim))
                layers[i]["eps_v"] = jnp.zeros((rows, t, hc.n_kv_heads // 2, 2 * hc.head_dim))
    return dict(params, layers=layers)


def _tap_handed_tensors(monkeypatch):
    mamba, gmu, attn = sy._mamba_mixer, sy._gmu_mixer, sy._attention_mixer

    def tapped_mamba(cfg, lp, x):
        out, m = mamba(cfg, lp, x)
        return out, m + lp["eps_m"] if "eps_m" in lp else m

    def tapped_attention(cfg, kind, layer, lp, x, handed=None):
        if kind == "X":
            handed = (handed[0] + lp["eps_k"], handed[1] + lp["eps_v"])
        out, kv, lam = attn(cfg, kind, layer, lp, x, handed)
        if kind != "X" and "eps_k" in lp:
            kv = (kv[0] + lp["eps_k"], kv[1] + lp["eps_v"])
        return out, kv, lam

    monkeypatch.setattr(sy, "_mamba_mixer", tapped_mamba)
    monkeypatch.setattr(sy, "_gmu_mixer",
                        lambda cfg, lp, x, m: gmu(cfg, lp, x, m + lp["eps_m"]))
    monkeypatch.setattr(sy, "_attention_mixer", tapped_attention)


@pytest.mark.parametrize("pattern", ["SWSFGXGX", "SWSFGX", "SFGXGXGX"])
def test_the_cotangents_of_m_k_and_v_are_the_sums_over_their_readers(monkeypatch, pattern):
    """m's cotangent at its Mamba-1 layer is the sum of the cotangents at the
    Gated Memory Units that read it, K's and V's at their attention layer the
    sum over the cross-attention layers: with two readers, with one (the
    model with a reader removed differs by that reader's term), with three."""
    cfg = dict(TINY, num_hidden_layers=len(pattern))
    hc = settings(cfg, pattern=pattern)
    _tap_handed_tensors(monkeypatch)
    x, y = batch()
    params = _with_zeros(hc.init(jax.random.key(2)), hc, *x.shape)
    _, grads = loss_and_grads(hc, params, x, y)
    src = hc.producers()
    for producer in set(src.values()):
        readers = [i for i in src if src[i] == producer]
        assert len(readers) == pattern.count("G")
        for key in ("eps_m",) if pattern[producer] == "S" else ("eps_k", "eps_v"):
            total = grads["layers"][producer][key]
            terms = [grads["layers"][i][key] for i in readers]
            assert all(float(jnp.max(jnp.abs(g))) > 0 for g in terms)
            assert rel(total, sum(terms)) < 1e-5, (producer, key)
            if len(terms) > 1:      # no one reader's term is the whole
                assert rel(total, terms[0]) > 1e-2


def test_a_reader_before_its_producer_is_refused():
    for pattern, what in (("GS", "scan output"), ("XF", "keys and values"),
                          ("SGXF", "keys and values"), ("WXG", "scan output")):
        with pytest.raises(ValueError, match=what):
            sy.SambaYConfig(pattern=pattern)
    with pytest.raises(ValueError, match="a layer is S, W, F, G or X"):
        sy.SambaYConfig(pattern="SM")
    # the reference refuses a cut that leaves a reader without its producer
    cut = dict(TINY, first_layer=18, num_hidden_layers=2)
    with pytest.raises(ValueError, match="reads an earlier layer"):
        ref.loss_fn(ref.make_params(cut, jax.random.key(0)), *batch(cfg=cut), cut)
    # an X reads the NEAREST attention layer before it, a G the nearest scan
    assert sy.SambaYConfig(pattern="SWSFGXSWGX").producers() == {4: 2, 5: 3, 8: 6, 9: 7}


# ------------------------------------------------------ differential attention

def two_softmaxes(q, k, v, lam, lam0, sub_w, window, eps):
    """The materialised form: both [T, T] softmaxes of every pair."""
    bsz, t, h, hd = q.shape
    per = h // k.shape[2]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (i - j < (window or t))
    out = np.zeros((bsz, t, h // 2, 2 * hd))
    for b in range(bsz):
        for pair in range(h // 2):
            maps = []
            for s in (0, 1):
                scores = q[b, :, 2 * pair + s] @ k[b, :, 2 * (pair // per) + s].T / math.sqrt(hd)
                scores = np.where(seen, scores, -np.inf)
                p = np.exp(scores - scores.max(-1, keepdims=True))
                maps.append(p / p.sum(-1, keepdims=True))
            o = (maps[0] - lam * maps[1]) @ v[b, :, pair // per]
            o = o / np.sqrt(np.mean(o * o, axis=-1, keepdims=True) + eps)
            out[b, :, pair] = o * sub_w * (1.0 - lam0)
    return out.reshape(bsz, t, h * hd)


@pytest.mark.parametrize("kind, window", [("full", None), ("banded", 8), ("cross", None)])
def test_differential_attention_is_the_two_softmax_form(kind, window):
    """Full, banded, and with keys and values that another layer made (their
    sequence's own, causal): 8 query heads on 4 key heads, so two query pairs
    read each key/value pair."""
    hc = sy.SambaYConfig(dim=128, pattern="SF", n_heads=8, n_kv_heads=4, head_dim=16,
                         window=8, dtype=jnp.float32)
    ks = jax.random.split(jax.random.key(9), 4)
    q = jax.random.normal(ks[0], (2, 32, 8, 16))
    k = jax.random.normal(ks[1], (2, 32, 4, 16)) * (3.0 if kind == "cross" else 1.0)
    v = jax.random.normal(ks[2], (2, 32, 2, 32))
    sub_w = 1.0 + 0.1 * jax.random.normal(ks[3], (32,))
    lam, lam0 = 0.731, sy.lambda_init(17)
    got = sy.differential_attention(hc, q, k, v, jnp.float32(lam), lam0, sub_w,
                                    window, "attn")
    want = two_softmaxes(*(np.asarray(a, np.float64) for a in (q, k, v)), lam, lam0,
                         np.asarray(sub_w, np.float64), window, hc.norm_eps)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_lam0_follows_the_published_index():
    """``lam0(l) = 0.8 - 0.6 exp(-0.3 l)`` of the published index: layers
    15, 17, 19 and 21 here, not 1, 3, 5 and 7; the (1 - lam0) factor with it."""
    assert sy.lambda_init(0) == pytest.approx(0.2)
    assert sy.lambda_init(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
    assert ref.lambda_init(17) == sy.lambda_init(17)
    params = ref.make_params(TINY, jax.random.key(3))
    x, y = batch()
    at = lambda first: np.asarray(
        settings(first_layer=first).loss(params, x, y, {})[2]["diff_lambda"])
    learned = lambda lp: float(jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
                               - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])))
    for first in (14, 0):
        want = [learned(params["layers"][i]) + sy.lambda_init(first + i)
                for i in (1, 3, 5, 7)]
        np.testing.assert_allclose(at(first), want, rtol=1e-6)
    assert np.min(np.abs(at(14) - at(0))) > 0.05
    # held to the reference: its lam0 is the published index's too
    raux = ref.loss_fn(params, x, y, TINY)[1]
    np.testing.assert_allclose(at(14), raux["diff_lambda"], rtol=1e-6)
    shifted = ref.loss_fn(params, x, y, dict(TINY, first_layer=14, published=dict(
        TINY["published"], num_hidden_layers=32)))[1]
    np.testing.assert_allclose(shifted["diff_lambda"], raux["diff_lambda"])


# ------------------------------------------------------------------ the head

def test_the_tied_heads_gradient_is_the_gathers_scatter_plus_the_heads_product():
    hc = settings()
    params = hc.init(jax.random.key(7))
    x, y = batch()

    def untied(e_in, e_head):
        hf, _ = sy.apply_sambay(hc, dict(params, embed=e_in), x)
        return jnp.mean(fused_head_xent_tokens(hf[None], e_head.T, y[None]))

    g_in, g_head = jax.grad(untied, (0, 1))(params["embed"], params["embed"])
    _, grads = loss_and_grads(hc, params, x, y)
    assert rel(grads["embed"], g_in + g_head) < 1e-5
    seen = np.zeros(TINY["vocab_size"], bool)
    seen[np.asarray(x).ravel()] = True
    assert not np.any(np.asarray(g_in)[~seen]) and np.all(np.any(np.asarray(g_in)[seen], axis=1))
    assert np.all(np.any(np.asarray(g_head), axis=1))      # the head's reaches every id
    assert rel(grads["embed"], g_head) > 1e-2 and rel(grads["embed"], g_in) > 1e-2
    assert "lm_head" not in params


# ---------------------------------------------------------------- the preset

def test_the_stage_preset_is_the_configuration_files_and_counts_its_parameters():
    with open(os.path.join(ROOT, "benchmark/configs/phi4_mini_flash.json")) as f:
        cfg = json.load(f)
    stage = sy.phi4_mini_flash_stage()
    assert builder.phi4flash_config(cfg) == stage
    assert stage.pattern == builder.layer_pattern(cfg) == "SWSFGXGX"
    assert [k for _, k in ref.layer_kinds(cfg)] == [
        "mamba", "window", "mamba", "full", "gmu", "cross", "gmu", "cross"]
    shapes = jax.tree.leaves(sy.sambay_param_shapes(stage),
                             is_leaf=lambda s: isinstance(s, tuple))
    assert sum(math.prod(s) for s in shapes) == cfg["parameters"] == 893_728_256
    assert len(shapes) == cfg["parameter_leaves"]
    uncut = dict(cfg, num_hidden_layers=32, first_layer=0, vocab_size=200064)
    assert builder.layer_pattern(uncut) == sy.SambaYConfig().pattern
    full = jax.tree.leaves(ref.param_shapes(uncut), is_leaf=lambda s: isinstance(s, tuple))
    assert sum(math.prod(s) for s in full) == 3_852_562_944
    tiny = sy.tiny_phi4flash()
    assert tiny.pattern == stage.pattern and tiny.first_layer == stage.first_layer
    with pytest.raises(ValueError, match="no tensor axis"):
        stage.validate_mesh(2)
    with pytest.raises(ValueError, match="no sequence axis"):
        tiny.loss(None, None, None, {"seq": 2})
