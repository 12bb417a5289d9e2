"""Plain reference for the ``phi4flash`` configurations (a decoder-hybrid-
decoder: Mamba-1 selective scans and differential attention, windowed and
full, then Gated Memory Units and cross-attention that read one earlier
layer's scan output and one earlier layer's keys and values;
huggingface.co/microsoft/Phi-4-mini-flash-reasoning, arXiv:2507.06607,
arXiv:2410.05258): forward, loss and gradient in straightforward ``jax.numpy``
float32 at ``highest``.  Imports nothing of the program under test (the
rounding helpers are the Ouro reference's).  A configuration names this file
under ``"reference"``; the host half of a step is in ``steps.py``, ``sync/``,
``optim/``.

The model, as the configuration's ``assumed`` block states it.  Hidden d,
LayerNorm (mean and variance, scale and bias, eps ``layer_norm_eps``), no
position embedding.  Layer l of the PUBLISHED ``n`` (``published.
num_hidden_layers``); this file runs layers ``first_layer`` ...
``first_layer + num_hidden_layers - 1``:

    a = h + Mixer_l(LN1_l(h));   h' = a + MLP_l(LN2_l(a))
    after the last: hf = LN_f(h);  logits = hf E^T (tied);  loss = mean CE

  which mixer (``mb_per_layer`` 2): l < n/2: l even Mamba-1 (S), l odd
        differential attention in a window (W); l = n/2: Mamba-1 that hands on
        its scan output m; l = n/2 + 1: differential attention over the whole
        sequence that hands on its K and V (F); beyond: l even a Gated Memory
        Unit on m (G), l odd differential cross-attention on that K and V (X)
  S     [u, z] = x W_in; u = silu(conv4(u) + b_conv) (causal, depthwise);
        [r, B, C] = u W_x; dt = softplus(r W_dt + b_dt); A = -exp(A_log)
        [d_in, N];  S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n]
        + dt_t[c] B_t[n] u_t[c];  m_t[c] = sum_n S_t[c, n] C_t[n] + D[c] u_t[c];
        out = (m * silu(z)) W_out
  G     out = (m * silu(x W_1)) W_2, m the handed-on scan output, ungated
  W F X [q, k, v] = x W_qkv + b_qkv (X: q = x W_q + b_q, k and v handed on);
        heads of ``head_dim`` pair up: q1_i, q2_i = query heads 2i, 2i + 1;
        k1_j, k2_j = key heads 2j, 2j + 1; v_j = value heads 2j, 2j + 1 side
        by side; query pair i reads pair i // (query pairs / key pairs);
        P1 = softmax(q1 k1^T / sqrt(head_dim)), P2 likewise, under the mask
        (W: i - window < j <= i; F, X: j <= i);
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),
        lam0(l) = 0.8 - 0.6 exp(-0.3 l), l the published index;
        o_i = RMSNorm(P1 v - lam P2 v) w_sub (1 - lam0(l)), eps layer_norm_eps;
        out = concat_i(o_i) W_o + b_o
  MLP   [g, u] = x W_gu; out = (u * silu(g)) W_down

**Shares.**  ``num_hidden_layers``, ``first_layer`` and ``vocab_size`` say
what is HELD, ``published`` what the model has: a contiguous run of whole
layers and the first ``vocab_size`` ids.  With the three at their published
values (and ``first_layer`` 0) this file is the uncut model.

The recurrence is a ``lax.scan`` over single tokens; attention a masked
softmax, both softmaxes of a pair materialised.  Departures, so that float32
fits at the timed size (8,192 tokens): the sequences of a batch one after
another, each layer and the head under ``jax.checkpoint``, the recurrence
``SCAN_SEGMENT`` tokens at a time under one more, and attention
``QUERY_BLOCK`` query rows at a time, each block against the keys its rows can
see, under the same mask.

``precision``: ``float32`` (the reference), or the emulated ``bfloat16`` /
``fp8`` of the control: every product's operands and result, every
elementwise result that the stated precision would hold in the compute type,
the residual stream and every cotangent on the way back are held in that
type; the norms' statistics, ``dt``, the decay, the state and ``m``'s sum,
the softmaxes, ``lam``, the sub-norm and the loss stay float32, as the
configuration states.
"""

from __future__ import annotations

import math
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from reference.ouro import HIGHEST, _product, _round_to  # noqa: E402

QUERY_BLOCK = 256
SCAN_SEGMENT = 256
_LAMBDAS = ("lq1", "lk1", "lq2", "lk2")


# ------------------------------------------------------------------ structure

def _published(cfg, key):
    return cfg.get("published", {}).get(key, cfg[key])


def layer_kinds(cfg) -> list:
    """[(published index, kind)] of the held layers; kind one of ``mamba``,
    ``window``, ``full``, ``gmu``, ``cross``."""
    n, per = _published(cfg, "num_hidden_layers"), cfg["mb_per_layer"]
    first = cfg.get("first_layer", 0)
    out = []
    for l in range(first, first + cfg["num_hidden_layers"]):
        if l <= n // 2:
            kind = "mamba" if l % per == 0 else "window"
        elif l == n // 2 + 1:
            kind = "full"
        else:
            kind = "gmu" if l % per == 0 else "cross"
        out.append((l, kind))
    return out


def _sizes(cfg):
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    d_in = cfg["mamba_expand"] * d
    return {"d": d, "hd": hd, "d_in": d_in, "n": cfg["mamba_d_state"],
            "r": cfg["mamba_dt_rank"], "kc": cfg["mamba_d_conv"],
            "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd, "f": cfg["intermediate_size"]}


def _layer_shapes(cfg, kind):
    s = _sizes(cfg)
    d, di, n, r = s["d"], s["d_in"], s["n"], s["r"]
    shapes = {"norm1_w": (d,), "norm1_b": (d,), "norm2_w": (d,), "norm2_b": (d,),
              "w_gu": (d, 2 * s["f"]), "w_down": (s["f"], d)}
    if kind == "mamba":
        shapes.update(w_in=(d, 2 * di), conv_w=(s["kc"], di), conv_b=(di,),
                      w_x=(di, r + 2 * n), w_dt=(r, di), b_dt=(di,),
                      a_log=(di, n), d_skip=(di,), w_out=(di, d))
    elif kind == "gmu":
        shapes.update(w_1=(d, di), w_2=(di, d))
    else:
        if kind == "cross":
            shapes.update(w_q=(d, s["q"]), b_q=(s["q"],))
        else:
            shapes.update(w_qkv=(d, s["q"] + 2 * s["kv"]),
                          b_qkv=(s["q"] + 2 * s["kv"],))
        shapes.update(w_o=(s["q"], d), b_o=(d,), sub_norm=(2 * s["hd"],),
                      **{k: (s["hd"],) for k in _LAMBDAS})
    return shapes


def param_shapes(cfg):
    """Nested dict of parameter shapes: one dict a layer (its mixer's, its
    feed-forward's and its two norms'); the embedding, which is the head too,
    and the final norm."""
    d = cfg["hidden_size"]
    return {"embed": (cfg["vocab_size"], d),
            "layers": [_layer_shapes(cfg, k) for _, k in layer_kinds(cfg)],
            "final_norm_w": (d,), "final_norm_b": (d,)}


def make_params(cfg, key):
    """Seeded float32 weights (the configuration's ``assumed.init``):
    normal(0, initializer_range) matrices and embedding; ``A_log[c, n] =
    ln(n + 1)``; ``D`` 1; ``b_dt`` the inverse softplus of a log-uniform draw
    in [0.001, 0.1] floored at 1e-4; ``W_dt`` uniform(+-dt_rank^-1/2); the
    convolution uniform(+-d_conv^-1/2); the lambda vectors normal(0, 0.1);
    norm scales 1, every other bias 0.  One traced function."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = path[-1].key
        if name in ("norm1_w", "norm2_w", "final_norm_w", "sub_norm", "d_skip"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name in ("norm1_b", "norm2_b", "final_norm_b", "b_qkv", "b_q", "b_o"):
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "a_log":
            leaf = jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
        elif name == "b_dt":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.001), math.log(0.1))), 1e-4)
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name in ("w_dt", "conv_w", "conv_b"):
            bound = (cfg["mamba_dt_rank"] if name == "w_dt"
                     else cfg["mamba_d_conv"]) ** -0.5
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name in _LAMBDAS:
            leaf = jax.random.normal(k, shape, jnp.float32) * 0.1
        else:
            leaf = jax.random.normal(k, shape, jnp.float32) * cfg["initializer_range"]
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------- mixers

def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _conv(u, w, b):
    """``y[t] = b + sum_k w[k] u[t - (K - 1) + k]``, zeros before the start."""
    k, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(padded[:, i:i + t] * w[i] for i in range(k))


def selective_scan(u, dt, a, b, c, d):
    """The recurrence as written, a token a step.  ``u``, ``dt`` [bsz, T, C],
    ``a`` [C, N], ``b``, ``c`` [bsz, T, N], ``d`` [C] -> m [bsz, T, C].
    ``SCAN_SEGMENT`` tokens at a time under ``jax.checkpoint``: the gradient
    then keeps the state at each segment's start and a segment's per-token
    states while it is differentiated, not all T of them (2.7 GB each of
    three at the timed size)."""
    def step(s, xs):
        ut, dtt, bt, ct = xs
        s = (jnp.exp(dtt[:, :, None] * a) * s
             + (dtt * ut)[:, :, None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(step, s, xs)

    t = u.shape[1]
    seg = math.gcd(t, SCAN_SEGMENT)
    by_segment = lambda v: v.swapaxes(0, 1).reshape((t // seg, seg) + v.shape[:1] + v.shape[2:])
    zero = jnp.zeros((u.shape[0],) + a.shape, jnp.float32)
    _, m = jax.lax.scan(segment, zero, tuple(by_segment(v) for v in (u, dt, b, c)))
    return m.reshape((t,) + m.shape[2:]).swapaxes(0, 1) + d * u


def _gated(m, gate, precision):
    rnd = lambda y: _round_to(y, precision)
    return rnd(m * jax.nn.silu(gate))


def _mamba(p, x, cfg, precision):
    """(the mixer's output, the scan's output m before its gate)."""
    rnd = lambda y: _round_to(y, precision)
    s = _sizes(cfg)
    u, z = jnp.split(_product("btd,de->bte", x, p["w_in"], precision), 2, axis=-1)
    u = rnd(jax.nn.silu(rnd(_conv(u, rnd(p["conv_w"]), rnd(p["conv_b"])))))
    rank, b, c = jnp.split(_product("bte,ef->btf", u, p["w_x"], precision),
                           [s["r"], s["r"] + s["n"]], axis=-1)
    # float32 from here to m: the step's own accumulation of r W_dt, the
    # softplus, the decay, the state and m's sum
    dt = jax.nn.softplus(jnp.einsum("btr,rc->btc", rank, rnd(p["w_dt"]),
                                    precision=HIGHEST) + p["b_dt"])
    m = rnd(selective_scan(u, dt, -jnp.exp(p["a_log"]), b, c, p["d_skip"]))
    return _product("bte,ed->btd", _gated(m, z, precision), p["w_out"], precision), m


def _gmu(p, x, m, precision):
    gate = _product("btd,de->bte", x, p["w_1"], precision)
    return _product("bte,ed->btd", _gated(m, gate, precision), p["w_2"], precision)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _attend(q, k, v, lam, window, precision):
    """q [b, J, R, 2, T, hd] (key pair J's R query pairs, both of each), k
    [b, J, 2, T, hd], v [b, J, T, 2 hd] -> P1 v - lam P2 v [b, J, R, T, 2 hd]:
    two masked softmaxes a pair, ``QUERY_BLOCK`` query rows at a time against
    the keys those rows can see."""
    t, hd = q.shape[4], q.shape[5]
    blk = math.gcd(t, QUERY_BLOCK)
    span = t if window is None else min(t, blk + window)

    @jax.checkpoint
    def rows(first):
        start = 0 if window is None else jnp.clip(first + blk - span, 0, t - span)
        q_blk = jax.lax.dynamic_slice_in_dim(q, first, blk, axis=4)
        k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=3)
        v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
        i = first + jnp.arange(blk)[:, None]
        j = start + jnp.arange(span)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        scores = _product("bjrsqd,bjskd->bjrsqk", q_blk, k_blk, precision) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = _product("bjrsqk,bjke->bjrsqe", probs, v_blk, precision)
        return o[:, :, :, 0] - lam * o[:, :, :, 1]

    out = jax.lax.map(rows, jnp.arange(0, t, blk))       # [blocks, b, J, R, blk, 2 hd]
    return jnp.moveaxis(out, 0, 3).reshape(q.shape[:3] + (t, 2 * hd))


def _attention(p, x, cfg, kind, layer, handed, precision):
    """(the mixer's output, (k, v) as a cross layer reads them, lam)."""
    rnd = lambda y: _round_to(y, precision)
    s = _sizes(cfg)
    bsz, t, _ = x.shape
    hd, nq, nkv = s["hd"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if kind == "cross":
        q = rnd(_product("btd,de->bte", x, p["w_q"], precision) + rnd(p["b_q"]))
        k, v = handed
    else:
        qkv = rnd(_product("btd,de->bte", x, p["w_qkv"], precision) + rnd(p["b_qkv"]))
        q, k, v = jnp.split(qkv, [s["q"], s["q"] + s["kv"]], axis=-1)
    lam0 = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"]))
           + lam0)
    pairs, per = nkv // 2, nq // nkv
    # [b, T, heads, hd] -> key pair, (query pair of it,) which of the two, T, hd
    qh = q.reshape(bsz, t, pairs, per, 2, hd).transpose(0, 2, 3, 4, 1, 5)
    kh = k.reshape(bsz, t, pairs, 2, hd).transpose(0, 2, 3, 1, 4)
    vh = v.reshape(bsz, t, pairs, 2 * hd).transpose(0, 2, 1, 3)
    o = _attend(qh, kh, vh, lam, cfg["sliding_window"] if kind == "window" else None,
                precision)                                    # [b, J, R, T, 2 hd]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg["layer_norm_eps"]) * p["sub_norm"] * (1.0 - lam0)
    o = rnd(o.transpose(0, 3, 1, 2, 4).reshape(bsz, t, nq * hd))
    return (rnd(_product("bte,ed->btd", o, p["w_o"], precision) + rnd(p["b_o"])),
            (k, v), lam)


def _mlp(p, x, precision):
    g, u = jnp.split(_product("btd,df->btf", x, p["w_gu"], precision), 2, axis=-1)
    return _product("btf,fd->btd", _round_to(u * _round_to(jax.nn.silu(g), precision),
                                             precision), p["w_down"], precision)


# ---------------------------------------------------------------------- model

def layer(kind, index, p, h, handed, cfg, precision="float32"):
    """(h', what the layer makes that later layers read, its number: a Mamba-1
    layer the root mean square of m, an attention layer its lam)."""
    rnd = lambda y: _round_to(y, precision)
    eps = cfg["layer_norm_eps"]
    x = rnd(_layer_norm(h, p["norm1_w"], p["norm1_b"], eps))
    made, stat = None, None
    if kind == "mamba":
        out, made = _mamba(p, x, cfg, precision)
        stat = jnp.sqrt(jnp.mean(jnp.square(made)))
    elif kind == "gmu":
        out = _gmu(p, x, handed, precision)
    else:
        out, made, stat = _attention(p, x, cfg, kind, index, handed, precision)
    a = rnd(h + out)
    x = rnd(_layer_norm(a, p["norm2_w"], p["norm2_b"], eps))
    return rnd(a + _mlp(p, x, precision)), made, stat


@partial(jax.checkpoint, static_argnums=(3,))
def _head(h, embed, labels, precision):
    """Per-token cross-entropy [b, T] through whole logits on the embedding."""
    logz = jax.nn.log_softmax(_product("btd,vd->btv", h, embed, precision), axis=-1)
    return -jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]


def hidden_states(params, tokens, cfg, precision="float32"):
    """(final-normed hidden states [b, T, d], every attention layer's lam,
    every Mamba-1 layer's root mean square of m)."""
    h = _round_to(params["embed"], precision)[tokens]
    memory, keys_values, lams, rms = None, None, [], []
    for (index, kind), p in zip(layer_kinds(cfg), params["layers"]):
        handed = {"gmu": memory, "cross": keys_values}.get(kind)
        if kind in ("gmu", "cross") and handed is None:
            raise ValueError(f"layer {index} ({kind}) reads an earlier layer's "
                             "tensors and none of that kind is held before it")
        h, made, stat = jax.checkpoint(
            partial(layer, kind, index, cfg=cfg, precision=precision))(p, h, handed)
        if kind == "mamba":
            memory = made
            rms.append(stat)
        elif kind != "gmu":
            if kind != "cross":
                keys_values = made
            lams.append(stat)
    hf = _round_to(_layer_norm(h, params["final_norm_w"], params["final_norm_b"],
                               cfg["layer_norm_eps"]), precision)
    return hf, jnp.stack(lams), jnp.stack(rms)


def logits_fn(params, tokens, cfg):
    """Whole logits [b, T, vocabulary held], float32: for the tests."""
    hf, _, _ = hidden_states(params, tokens, cfg)
    return jnp.einsum("btd,vd->btv", hf, params["embed"], precision=HIGHEST)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """(loss, {the attention layers' lam, the mean cross-entropy, the Mamba-1
    layers' root mean square of m}).  One sequence of the batch at a time
    (nothing in the model looks across sequences): the cross-entropies are
    summed, the numbers averaged over the sequences (lam does not depend on
    them; the root mean squares through their squares)."""
    @jax.checkpoint
    def one(sequence):
        tok, lab = sequence
        hf, lams, rms = hidden_states(params, tok[None], cfg, precision)
        return (jnp.sum(_head(hf, params["embed"], lab[None], precision)),
                lams, jnp.square(rms))

    sums, lams, squares = jax.lax.map(one, (tokens, labels))
    loss = jnp.sum(sums) / tokens.size
    return loss, {"diff_lambda": jnp.mean(lams, axis=0), "loss": loss[None],
                  "memory_rms": jnp.sqrt(jnp.mean(squares, axis=0))}


def make_loss_and_grad(cfg, precision="float32"):
    """jitted (params, tokens, labels) -> ((loss, the model's numbers), grads)."""
    return jax.jit(jax.value_and_grad(
        partial(loss_fn, cfg=cfg, precision=precision), has_aux=True))


# ------------------------------------- what the benchmark asks of a model file

#: the 2-D leaves that are not a product's right-hand side
_NOT_PRODUCTS = ("conv_w", "a_log")


def _causal_pairs(cfg, kind) -> float:
    """(query, key) pairs one softmax of one layer of ``kind`` scores in a
    sequence: the causal half of T x T, or the band (T x window less the
    first window's missing triangle)."""
    t = cfg["seq_len"]
    if kind != "window":
        return t * t / 2.0
    w = min(cfg["sliding_window"], t)
    return t * w - w * (w - 1) / 2.0


def _score_flops(cfg, kind) -> float:
    """Forward operations of the attention products of every layer of
    ``kind``, a sequence: a softmax map a query head, keys of ``head_dim``
    and values of twice that; nothing padded."""
    hd = _sizes(cfg)["hd"]
    layers = sum(1 for _, k in layer_kinds(cfg) if k == kind)
    return (layers * cfg["num_attention_heads"] * 2.0 * (hd + 2 * hd)
            * _causal_pairs(cfg, kind))


def _ssd_flops_per_token(cfg) -> float:
    """One Mamba-1 layer's convolution and scan, a token: a multiply-add a
    tap; per channel and state index dt x A, the exponential (counted 1), the
    state's multiply-add, the input's product and the output's multiply-add;
    per channel dt x u and D x u."""
    s = _sizes(cfg)
    return s["d_in"] * (2.0 * s["kc"] + 3.0 + 7.0 * s["n"])


def forward_flops_per_sample(cfg) -> float:
    """A sample is a sequence.  Per token every layer's matrices and the head
    (the tied embedding, once), the convolutions and the scans; per sequence
    the attention products over the pairs each layer's mask admits.
    Recomputation is not counted."""
    t = cfg["seq_len"]
    per_token = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    for shapes in param_shapes(cfg)["layers"]:
        per_token += 2.0 * sum(int(np.prod(s)) for k, s in shapes.items()
                               if len(s) == 2 and k not in _NOT_PRODUCTS)
    return (per_token * t + ssd_flops_per_sample(cfg)
            + sum(_score_flops(cfg, k) for k in ("window", "full", "cross")))


def attention_flops_per_sample(cfg) -> float:
    """What the FULL self-attention layers' kernels of one training step on
    one sequence must do: per softmax map and layer two products forward and
    four backward over the causal half of T x T, q and k of ``head_dim``, v of
    twice that."""
    return 3.0 * _score_flops(cfg, "full")


def window_attention_flops_per_sample(cfg) -> float:
    """The same of the WINDOW layers: the band's pairs."""
    return 3.0 * _score_flops(cfg, "window")


def cross_attention_flops_per_sample(cfg) -> float:
    """The same of the CROSS layers (causal: a query sees the handed-on keys
    at or before it)."""
    return 3.0 * _score_flops(cfg, "cross")


def ssd_flops_per_sample(cfg) -> float:
    """Forward operations of the convolution and the scan over one sequence,
    all Mamba-1 layers, whatever implements them."""
    layers = sum(1 for _, k in layer_kinds(cfg) if k == "mamba")
    return layers * _ssd_flops_per_token(cfg) * cfg["seq_len"]


def ssd_min_bytes_per_sample(cfg) -> float:
    """Least bytes the convolution and the scan of one sequence move forward,
    all Mamba-1 layers: the convolution's input read and m written in the
    compute type's 2 bytes, dt read in float32, B and C read in 2 bytes.  z,
    the gate and the projections are outside the scope."""
    s = _sizes(cfg)
    layers = sum(1 for _, k in layer_kinds(cfg) if k == "mamba")
    return layers * (s["d_in"] * (2.0 + 4.0 + 2.0) + 2 * s["n"] * 2.0) * cfg["seq_len"]


def aux_as_probed(aux1, cfg) -> list:
    """The model's numbers of one step in the form the builder's probe reads
    them from the program's state: as they are (leaves in tree order)."""
    return [np.asarray(a, np.float64) for a in aux1]


def model_numbers(prog_aux1, ref_aux1, cfg, params: dict) -> dict:
    """The numbers only this model has, from the first step's (leaves in tree
    order: lam [attention layers], the loss, rms of m [Mamba-1 layers]).

    diff_lambda_gap  worst attention layer's lam, relative: lam0 by another
                     index, or a lambda vector read wrongly, moves it by tenths
    memory_rms_gap   worst Mamba-1 layer's root mean square of its scan
                     output, relative: another decay, time step or state
                     moves what every Gated Memory Unit reads
    """
    lam_p, _, rms_p = (np.asarray(a, np.float64) for a in prog_aux1)
    lam_r, _, rms_r = (np.asarray(a, np.float64) for a in ref_aux1)
    if lam_p.shape != lam_r.shape or rms_p.shape != rms_r.shape:
        return {k: float("inf") for k in ("diff_lambda_gap", "memory_rms_gap")}
    return {"diff_lambda_gap": float(np.max(np.abs(lam_p - lam_r) / np.abs(lam_r))),
            "memory_rms_gap": float(np.max(np.abs(rms_p - rms_r) / rms_r))}
