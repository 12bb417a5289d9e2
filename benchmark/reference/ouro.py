"""Plain reference for the Ouro configurations (looped language models,
arXiv:2510.25741; huggingface.co/ByteDance/Ouro-2.6B): forward, the
exit-weighted loss over the passes and its gradient in straightforward
``jax.numpy`` float32.  Imports nothing of the program under test.  A
configuration names this file under ``"reference"``; the host half of a step
(exchange, optimizer) is in ``steps.py``, ``sync/`` and ``optim/``.

The model, as the configuration's ``assumed`` block states it:

    h_0 = E[tokens]
    block:  h = h + N2(Attn(N1 h));  h = h + N4(W_down(silu(W_gate x) * W_up x)), x = N3 h
            (N*: RMSNorm with its own scale; Attn: causal, rotary over the
            whole head on the pairs (2i, 2i+1), no biases)
    pass r = 1..R:  h_r = Nf(Stack(h_{r-1}))      one Stack, one Nf, every pass
                    logits_r = h_r W_head;  lambda_r = sigmoid(h_r . w_g + b_g)
    per token:  p_r = lambda_r prod_{j<r} (1 - lambda_j)  (r < R),
                p_R = prod_{j<R} (1 - lambda_j)
    loss = mean_tokens[ sum_r p_r CE(logits_r, y) + beta sum_r p_r log p_r ]

Every layer-pass and every pass's head is written out one after another (no
scan: a weight's gradient is the sum that reverse-mode differentiation makes
of its R uses), attention is a masked softmax over the whole [T, T] of every
head, and the logits are whole.  The one departure: a ``jax.checkpoint``
around each layer-pass and around each pass's head, so that at the timed size
(4,096 tokens, 49,152 words) the float32 computation fits beside itself.

``precision`` selects the arithmetic of every product (projections, attention
scores and mixing, feed-forward, head, gate): ``float32`` (the reference), or
the emulated ``bfloat16`` / ``fp8`` used by the control, which holds every
operand and result, the residual stream, and every cotangent on the way back,
in that type and keeps float32 accumulation inside each product.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


# ------------------------------------------------------------------ structure

def param_shapes(cfg):
    """Nested dict of parameter shapes: 7 matrices and 4 norm scales a layer;
    embedding, head, final norm, the exit gate's weight and bias."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = {"wq": (d, heads), "wk": (d, kv), "wv": (d, kv), "wo": (heads, d),
             "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
             **{n: (d,) for n in NORMS}}
    return {"embed": (v, d),
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])],
            "final_norm": (d,), "lm_head": (d, v),
            "exit_gate": {"w": (d,), "b": (1,)}}


def make_params(cfg, key):
    """Seeded float32 weights: normal(0, initializer_range) matrices, embedding
    and gate weight, unit norm scales, zero gate bias.  One traced function, so
    one device program."""
    shapes = param_shapes(cfg)
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        leaf = path[-1].key
        if leaf in NORMS or leaf == "final_norm":
            out.append(jnp.ones(shape, jnp.float32))
        elif leaf == "b":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * cfg["initializer_range"])
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------------- forward

def _quantize(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":           # e4m3 with a per-tensor scale
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        # clipped: past 448 the type has only NaN, and a rounded scale can
        # carry the largest element a hair over
        return jnp.clip(x * s, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / s
    raise ValueError(f"unknown precision {precision!r}")


def _round_to(x, precision):
    """``x`` as a tensor of ``precision`` would hold it, and its cotangent on
    the way back likewise."""
    if precision == "float32":
        return x

    @jax.custom_vjp
    def rounded(v):
        return _quantize(v, precision)

    rounded.defvjp(lambda v: (_quantize(v, precision), None),
                   lambda _, g: (_quantize(g, precision),))
    return rounded(x)


def _product(spec, a, b, precision):
    return _round_to(jnp.einsum(spec, _round_to(a, precision),
                                _round_to(b, precision), precision=HIGHEST),
                     precision)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [b, heads, T, hd]: each pair (2i, 2i+1) turned by t * theta^(-2i/hd)."""
    t, hd = x.shape[-2:]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(p, x, cfg, precision):
    b, t, _ = x.shape
    hd = cfg["head_dim"]
    split = lambda y: y.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
    q, k, v = (split(_product("btd,de->bte", x, p[w], precision))
               for w in ("wq", "wk", "wv"))
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(y, rep, axis=1) for y in (k, v))
    q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    scores = _product("bhqd,bhkd->bhqk", q, k, precision) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = _product("bhqk,bhkd->bhqd", probs, v, precision)
    return _product("bte,ed->btd", mixed.transpose(0, 2, 1, 3).reshape(b, t, -1),
                    p["wo"], precision)


def _block(p, h, cfg, precision):
    eps = cfg["rms_norm_eps"]
    rnd = lambda y: _round_to(y, precision)
    a = _attention(p, rnd(_rms_norm(h, p["attn_norm"], eps)), cfg, precision)
    h = rnd(h + rnd(_rms_norm(a, p["attn_post_norm"], eps)))
    x = rnd(_rms_norm(h, p["mlp_norm"], eps))
    gated = rnd(jax.nn.silu(_product("btd,df->btf", x, p["w_gate"], precision))
                * _product("btd,df->btf", x, p["w_up"], precision))
    m = _product("btf,fd->btd", gated, p["w_down"], precision)
    return rnd(h + rnd(_rms_norm(m, p["mlp_post_norm"], eps)))


def _head(h, w_head, gate, labels, precision):
    """One pass's per-token cross-entropy [b, T] and gate logits [b, T]."""
    logits = _product("btd,dv->btv", h, w_head, precision)
    logz = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]
    return nll, _product("btd,d->bt", h, gate["w"], precision) + gate["b"]


def exit_distribution(gate_logits):
    """[R, ...] gate logits -> [R, ...] probabilities of leaving after each
    pass; the last pass takes what is left and its own gate is not read."""
    lam = jax.nn.sigmoid(gate_logits)
    stayed = jnp.cumprod(1.0 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stayed[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, stayed[-1:]], axis=0)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """(loss, {per-pass mean cross-entropy, mean exit masses, mean entropy}).
    ``params`` is the model's one set of weights; a list of one set a pass is
    the untied copy the tests hold the tied gradient against."""
    rnd = lambda y: _round_to(y, precision)
    untied = isinstance(params, (list, tuple))
    h = rnd((params[0] if untied else params)["embed"])[tokens]
    nlls, gates = [], []
    for r in range(cfg["total_ut_steps"]):
        of_pass = params[r] if untied else params
        for p in of_pass["layers"]:
            h = jax.checkpoint(partial(_block, cfg=cfg, precision=precision))(p, h)
        h = rnd(_rms_norm(h, of_pass["final_norm"], cfg["rms_norm_eps"]))
        nll, gate = jax.checkpoint(partial(_head, precision=precision))(
            h, of_pass["lm_head"], of_pass["exit_gate"], labels)
        nlls.append(nll)
        gates.append(gate)
    nll, p = jnp.stack(nlls), exit_distribution(jnp.stack(gates))
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    loss = jnp.mean(jnp.sum(p * nll + cfg["exit_beta"] * plogp, axis=0))
    return loss, {"pass_loss": jnp.mean(nll, axis=(1, 2)),
                  "exit_mass": jnp.mean(p, axis=(1, 2)),
                  "exit_entropy": -jnp.mean(jnp.sum(plogp, axis=0))}


def make_loss_and_grad(cfg, precision="float32"):
    """jitted (params, tokens, labels) -> ((loss, per-pass numbers), grads)."""
    return jax.jit(jax.value_and_grad(
        partial(loss_fn, cfg=cfg, precision=precision), has_aux=True))


# ------------------------------------- what the benchmark asks of a model file

def _tokens(cfg):
    return cfg["seq_len"]


def forward_flops_per_sample(cfg) -> float:
    """A sample is a sequence.  Per token and pass: 2 x the layers' matrix
    parameters, the causal half of attention's two products (2 x T x heads x
    head size), the head and the gate.  Recomputation is not counted."""
    shapes = param_shapes(cfg)
    layer = sum(int(np.prod(shapes["layers"][0][w])) for w in MATRICES)
    attn = 2.0 * _tokens(cfg) * cfg["num_attention_heads"] * cfg["head_dim"]
    d, v = shapes["lm_head"]
    per_token = (cfg["num_hidden_layers"] * (2.0 * layer + attn)
                 + 2.0 * d * v + 2.0 * d)
    return cfg["total_ut_steps"] * per_token * _tokens(cfg)


def attention_flops_per_sample(cfg) -> float:
    """What the attention kernels of one training step on one sequence must
    do, whatever implements them: per head and layer-pass two products forward
    (scores, mixing) and four backward (dV, dP, dQ, dK), each the causal half
    of T x T x head size multiply-adds.  A score block computed again in the
    backward, or a forward run again under rematerialisation, is not counted."""
    t = _tokens(cfg)
    product = 2.0 * (t * t / 2.0) * cfg["head_dim"]
    return (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
            * cfg["num_attention_heads"] * 6.0 * product)


def aux_as_probed(aux1, cfg) -> list:
    """The per-pass numbers of one step in the form the builder's probe reads
    them from the program's state: as they are."""
    return [np.asarray(a, np.float64) for a in aux1]


def model_numbers(prog_aux1, ref_aux1, cfg, params: dict) -> dict:
    """The numbers only this model has, from the first step's per-pass
    numbers (leaves in tree order: exit entropy, exit masses, pass losses).

    pass_loss_gap  worst of the passes' mean cross-entropies, relative: a pass
                   left out, run on other weights or fed something else than
                   the pass before it moves its own loss
    exit_mass_gap  worst of the mean exit masses, absolute (they sum to 1): a
                   gate read at the wrong pass, or a last pass that does not
                   take what is left, moves where the tokens leave
    """
    _, mass_p, loss_p = (np.asarray(a, np.float64) for a in prog_aux1)
    _, mass_r, loss_r = (np.asarray(a, np.float64) for a in ref_aux1)
    if mass_p.shape != mass_r.shape:       # another number of passes was run
        return {"pass_loss_gap": float("inf"), "exit_mass_gap": float("inf")}
    return {"pass_loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r))),
            "exit_mass_gap": float(np.max(np.abs(mass_p - mass_r)))}
