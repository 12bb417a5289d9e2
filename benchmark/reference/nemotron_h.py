"""Plain reference for the ``nemotron_h`` configurations (hybrid decoders of
Mamba-2, attention and LatentMoE layers with a multi-token-prediction module;
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16): forward, the
two-term loss and its gradient in straightforward ``jax.numpy`` float32 at
``highest``.  Imports nothing of the program under test (the rounding helpers
are the Ouro reference's).  A configuration names this file under
``"reference"``; the host half of a step is in ``steps.py``, ``sync/``, ``optim/``.

The model, as the configuration's ``assumed`` block states it.  Hidden d,
RMSNorm eps ``norm_eps``, no biases but the convolution's.  Layer l of the
first ``num_hidden_layers`` characters of ``hybrid_override_pattern``:

    h = h + Mixer_l(RMSNorm_l(h));   hf = RMSNorm_f(h);   logits = hf W_head

  M  [z, xBC, dt] = x W_in;  xBC = silu(causal_depthwise_conv(xBC) + b_conv);
     xBC -> x [T, H, P], B, C [T, G, N];  dt = softplus(dt + dt_bias);
     A = -exp(A_log);  per head, with its group's B and C:
         S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
     y = GroupRMSNorm(y * silu(z)) * w  (over each group's channels; the gate
     before the norm);  out = y W_out
  *  q, k, v = x W_q, x W_k, x W_v; causal softmax(q k^T / sqrt(head)) v with
     grouped keys and values, NO position embedding; out = o W_o
  E  s = sigmoid(x W_r) over all published experts, float32; the top
     ``num_experts_per_tok`` of s + b_corr (a buffer: zero, no gradient);
     w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20);
     u = x W_dn;  f_e(u) = relu(u W1_e)^2 W2_e;
     out = (sum over the chosen AND HELD e of w_e f_e(u)) W_up + relu(x Ws1)^2 Ws2
  MTP  g = [RMSNorm_e(E[y_i]) ; RMSNorm_h(hf_i)] W_eh, the layers of
     ``mtp_hybrid_override_pattern``, RMSNorm_m, the trunk's head; y_i is the
     token after position i and the module's target the one after that.
  loss = mean CE(logits, y_i) + mtp_loss_weight x mean over the T - 1
     positions that have one of CE(logits_mtp, y_{i+1})

**Shares.**  The counting keys (``mamba_num_heads``, ``n_groups``,
``num_attention_heads``, ``num_key_value_heads``, ``n_routed_experts``,
``vocab_size``) say what is HELD; ``published`` says the model's.  The router
is as wide as ``published.n_routed_experts`` and chooses among all of them;
experts ``first_expert`` ... + ``n_routed_experts`` are held, and what the
others would add is left out.  With every count at its published value this
file is the uncut model.

The recurrence is a scan over tokens, one at a time; attention a masked
softmax; every held expert a dense product over all tokens, masked by its
weights; the logits whole.  Departures, so that float32 fits at the timed size
(8,192 tokens): ``jax.checkpoint`` around each layer, each expert and the
head; the recurrence checkpointed in blocks of ``SCAN_BLOCK`` tokens (a saved
state a token would be 8.6 GB a layer); attention's softmax a block of
``QUERY_BLOCK`` query rows at a time (each row still sees all its keys).

``precision``: ``float32`` (the reference), or the emulated ``bfloat16`` /
``fp8`` of the control: every product's operands and result, every
elementwise result that the stated precision would hold in the compute type,
the residual stream and every cotangent on the way back are held in that
type; the router's scores, the recurrence's decay and state, softplus, the
norms' statistics and the losses stay float32, as the configuration states.
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

from reference.ouro import HIGHEST, _product, _rms_norm, _round_to  # noqa: E402

SCAN_BLOCK = 128
QUERY_BLOCK = 1024
OUT_PROJ = ("w_out", "wo", "w_up_lat", "ws2")   # write a mixer's output to the stream


# ------------------------------------------------------------------ structure

def _pattern(cfg) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _published(cfg, key):
    return cfg.get("published", {}).get(key, cfg[key])


def _sizes(cfg) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": h, "p": p, "g": g, "n": n, "inner": h * p,
            "conv": h * p + 2 * g * n}


def _layer_shapes(cfg, kind):
    d = cfg["hidden_size"]
    if kind == "M":
        s = _sizes(cfg)
        return {"norm": (d,), "w_in": (d, s["inner"] + s["conv"] + s["h"]),
                "conv_w": (cfg["conv_kernel"], s["conv"]), "conv_b": (s["conv"],),
                "dt_bias": (s["h"],), "a_log": (s["h"],), "d_skip": (s["h"],),
                "gate_norm": (s["inner"],), "w_out": (s["inner"], d)}
    if kind == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return {"norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "wo": (q, d)}
    if kind != "E":
        raise ValueError(f"unknown layer kind {kind!r}")
    e, lat, f = cfg["n_routed_experts"], cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    routed = _published(cfg, "n_routed_experts")
    sh = cfg["moe_shared_expert_intermediate_size"]
    return {"norm": (d,), "router": (d, routed), "e_bias": (routed,),
            "w_down_lat": (d, lat), "w_up_lat": (lat, d),
            "w1": (e, lat, f), "w2": (e, f, lat), "ws1": (d, sh), "ws2": (sh, d)}


def param_shapes(cfg):
    """Nested dict of parameter shapes: a dict a layer by its kind; embedding,
    head and final norm; the MTP module's two norms, its 2d -> d projection,
    its layers and its final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, d),
            "layers": [_layer_shapes(cfg, k) for k in _pattern(cfg)],
            "final_norm": (d,), "lm_head": (d, v),
            "mtp": {"embed_norm": (d,), "hidden_norm": (d,), "w_eh": (2 * d, d),
                    "layers": [_layer_shapes(cfg, k)
                               for k in cfg["mtp_hybrid_override_pattern"]],
                    "final_norm": (d,)}}


def make_params(cfg, key):
    """Seeded float32 weights as the ``assumed`` block states them.  One
    traced function, so one device program."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    std = cfg["initializer_range"]
    depth = _published(cfg, "num_hidden_layers")
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = path[-1].key
        if name.endswith("norm") or name == "d_skip":
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "e_bias":
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "a_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi)),
                             cfg["time_step_floor"])
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(cfg["conv_kernel"])
            leaf = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            leaf = jax.random.normal(k, shape, jnp.float32) * std
            if name in OUT_PROJ:
                leaf = leaf / math.sqrt(2.0 * depth)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------------- mixers

def _recurrence(x, dt, a, b, c):
    """x [B, T, H, P], dt [B, T, H], a [H], b and c [B, T, H, N] (each head its
    group's), float32: y_t = S_t C_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T, one token at a time."""
    def step(state, xs):
        xt, dtt, bt, ct = xs
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    bsz, t, h, p = x.shape
    blk = math.gcd(t, SCAN_BLOCK)
    by_block = lambda v: v.swapaxes(0, 1).reshape((t // blk, blk) + v.shape[:1] + v.shape[2:])
    _, y = jax.lax.scan(block, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
                        tuple(by_block(v) for v in (x, dt, b, c)))
    return y.reshape((t, bsz, h, p)).swapaxes(0, 1)


def _mamba(p, x, cfg, precision):
    rnd = lambda y: _round_to(y, precision)
    s = _sizes(cfg)
    bsz, t, _ = x.shape
    z, xbc, dtr = jnp.split(_product("btd,de->bte", x, p["w_in"], precision),
                            [s["inner"], s["inner"] + s["conv"]], axis=-1)
    taps = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, k:k + t] * p["conv_w"][k] for k in range(taps))
    xbc = rnd(jax.nn.silu(rnd(conv)))
    xs, b, c = jnp.split(xbc, [s["inner"], s["inner"] + s["g"] * s["n"]], axis=-1)
    xs = xs.reshape(bsz, t, s["h"], s["p"])
    per = s["h"] // s["g"]
    heads = lambda v: jnp.repeat(v.reshape(bsz, t, s["g"], s["n"]), per, axis=2)
    dt = jax.nn.softplus(dtr + p["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(p["a_log"]), heads(b), heads(c))
    y = rnd(y + p["d_skip"][:, None] * xs)
    g = (y.reshape(bsz, t, s["g"], -1)
         * jax.nn.silu(z).reshape(bsz, t, s["g"], -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg["norm_eps"])
    return _product("bte,ed->btd", rnd(g.reshape(bsz, t, -1) * p["gate_norm"]),
                    p["w_out"], precision)


def _attention(p, x, cfg, precision):
    b, t, _ = x.shape
    hd = cfg["head_dim"]
    split = lambda y: y.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
    q, k, v = (split(_product("btd,de->bte", x, p[w], precision))
               for w in ("wq", "wk", "wv"))
    rep = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(y, rep, axis=1) for y in (k, v))

    @jax.checkpoint
    def rows(q_blk, first):
        scores = _product("bhqd,bhkd->bhqk", q_blk, k, precision) / np.sqrt(hd)
        causal = (first + jnp.arange(q_blk.shape[2]))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _product("bhqk,bhkd->bhqd", probs, v, precision)

    blk = min(QUERY_BLOCK, t)
    mixed = jnp.concatenate([rows(q[:, :, i:i + blk], i) for i in range(0, t, blk)],
                            axis=2)
    return _product("bte,ed->btd", mixed.transpose(0, 2, 1, 3).reshape(b, t, -1),
                    p["wo"], precision)


def routing(p, x, cfg):
    """x [N, d] -> (``wts`` [N, held]: each token's weight on each held
    expert, 0 where it did not choose it; ``hit`` [N, held] bool)."""
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, p["router"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p["e_bias"]),
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen
    if cfg["norm_topk_prob"]:
        w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    w = cfg["routed_scaling_factor"] * w
    held = (idx[:, :, None]
            == cfg.get("first_expert", 0) + jnp.arange(cfg["n_routed_experts"]))
    return jnp.sum(jnp.where(held, w[:, :, None], 0.0), axis=1), jnp.any(held, axis=1)


def _moe(p, x, cfg, precision):
    rnd = lambda y: _round_to(y, precision)
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    wts, hit = routing(p, x2, cfg)
    u = _product("nd,dl->nl", x2, p["w_down_lat"], precision)

    @jax.checkpoint
    def expert(w1, w2, weight):
        f = rnd(jnp.square(jax.nn.relu(_product("nl,lf->nf", u, w1, precision))))
        return weight[:, None] * _product("nf,fl->nl", f, w2, precision)

    routed = sum(expert(p["w1"][e], p["w2"][e], wts[:, e])
                 for e in range(cfg["n_routed_experts"]))
    out = _product("nl,ld->nd", rnd(routed), p["w_up_lat"], precision)
    f = rnd(jnp.square(jax.nn.relu(_product("nd,df->nf", x2, p["ws1"], precision))))
    shared = _product("nf,fd->nd", f, p["ws2"], precision)
    stats = {"rows": jnp.sum(hit, axis=0).astype(jnp.float32),
             "mass": jnp.mean(jnp.sum(wts, axis=-1))}
    return rnd(out + shared).reshape(b, t, d), stats


def layer(kind, p, h, cfg, precision="float32"):
    """(h + Mixer(RMSNorm(h)), the routing's numbers of an expert layer)."""
    rnd = lambda y: _round_to(y, precision)
    x = rnd(_rms_norm(h, p["norm"], cfg["norm_eps"]))
    stats = {}
    if kind == "M":
        out = _mamba(p, x, cfg, precision)
    elif kind == "*":
        out = _attention(p, x, cfg, precision)
    else:
        out, stats = _moe(p, x, cfg, precision)
    return rnd(h + out), stats


def _layers(pattern, layers, h, cfg, precision):
    stats = []
    for kind, p in zip(pattern, layers):
        h, st = jax.checkpoint(partial(layer, kind, cfg=cfg, precision=precision))(p, h)
        if st:
            stats.append(st)
    return h, stats


@partial(jax.checkpoint, static_argnums=(3,))
def _head(h, w_head, labels, precision):
    """Per-token cross-entropy [b, T] through whole logits."""
    logz = jax.nn.log_softmax(_product("btd,dv->btv", h, w_head, precision), axis=-1)
    return -jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """(loss, {the two mean cross-entropies, each expert layer's rows a held
    expert and kept weight mass})."""
    rnd = lambda y: _round_to(y, precision)
    eps = cfg["norm_eps"]
    embed = rnd(params["embed"])
    h, stats = _layers(_pattern(cfg), params["layers"], embed[tokens], cfg, precision)
    hf = rnd(_rms_norm(h, params["final_norm"], eps))
    lm = jnp.mean(_head(hf, params["lm_head"], labels, precision))

    mp = params["mtp"]
    joined = jnp.concatenate([rnd(_rms_norm(embed[labels], mp["embed_norm"], eps)),
                              rnd(_rms_norm(hf, mp["hidden_norm"], eps))], axis=-1)
    g, mstats = _layers(cfg["mtp_hybrid_override_pattern"], mp["layers"],
                        _product("bte,ed->btd", joined, mp["w_eh"], precision),
                        cfg, precision)
    hm = rnd(_rms_norm(g, mp["final_norm"], eps))
    # position i predicts the token after next; the last has none
    mtp = jnp.mean(_head(hm[:, :-1], params["lm_head"], labels[:, 1:], precision))
    stats = stats + mstats
    return lm + cfg["mtp_loss_weight"] * mtp, {
        "loss": jnp.stack([lm, mtp]),
        "expert_rows": jnp.stack([s["rows"] for s in stats]),
        "route_mass": jnp.stack([s["mass"] for s in stats])}


def make_loss_and_grad(cfg, precision="float32"):
    """jitted (params, tokens, labels) -> ((loss, the model's numbers), grads)."""
    return jax.jit(jax.value_and_grad(
        partial(loss_fn, cfg=cfg, precision=precision), has_aux=True))


# ------------------------------------- what the benchmark asks of a model file

def _matrix_flops(cfg, kind) -> float:
    """2 x the matrix parameters a token meets in a layer; of the routed
    experts the ones a token chooses among those held, at uniform routing."""
    shapes = _layer_shapes(cfg, kind)
    dense = sum(int(np.prod(s)) for n, s in shapes.items()
                if len(s) == 2 and n != "conv_w")
    if kind == "E":
        chosen_here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                       / _published(cfg, "n_routed_experts"))
        dense += chosen_here * 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
    return 2.0 * dense


def _ssd_flops_per_token(cfg) -> float:
    """The chunked scan at the published chunk L, a token and layer: per
    group the C B^T block (2 L N); per head the masked mix with x (2 L P), the
    chunk's state (2 P N) and its readout (2 P N); the convolution's taps."""
    s, chunk = _sizes(cfg), cfg["chunk_size"]
    return (s["g"] * 2.0 * chunk * s["n"]
            + s["h"] * (2.0 * chunk * s["p"] + 4.0 * s["p"] * s["n"])
            + 2.0 * cfg["conv_kernel"] * s["conv"])


def _kinds(cfg) -> str:
    return _pattern(cfg) + cfg["mtp_hybrid_override_pattern"]


def forward_flops_per_sample(cfg) -> float:
    """A sample is a sequence.  Per token: every layer's matrices (trunk and
    MTP module), the scan, the causal half of attention's two products, the
    head twice (trunk and MTP) and the 2d -> d projection.  Recomputation is
    not counted."""
    t, d = cfg["seq_len"], cfg["hidden_size"]
    per_token = 2 * 2.0 * d * cfg["vocab_size"] + 2.0 * 2 * d * d
    for kind in _kinds(cfg):
        per_token += _matrix_flops(cfg, kind)
        if kind == "M":
            per_token += _ssd_flops_per_token(cfg)
        if kind == "*":
            per_token += 2.0 * t * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_token * t


def attention_flops_per_sample(cfg) -> float:
    """What the attention kernels of one training step on one sequence must
    do: per held head and attention layer two products forward and four
    backward, each the causal half of T x T x head size multiply-adds."""
    t = cfg["seq_len"]
    product = 2.0 * (t * t / 2.0) * cfg["head_dim"]
    return _kinds(cfg).count("*") * cfg["num_attention_heads"] * 6.0 * product


def ssd_flops_per_sample(cfg) -> float:
    """Forward operations of the convolution and the scan over one sequence,
    all Mamba layers, at the published chunk size, whatever implements them."""
    return _kinds(cfg).count("M") * _ssd_flops_per_token(cfg) * cfg["seq_len"]


def ssd_min_bytes_per_sample(cfg) -> float:
    """Least bytes the convolution and the scan of one sequence move forward,
    all Mamba layers, in the compute type's 2 bytes: xBC and dt read once, y
    written once."""
    s = _sizes(cfg)
    return (_kinds(cfg).count("M") * 2.0 * (s["conv"] + s["h"] + s["inner"])
            * cfg["seq_len"])


def aux_as_probed(aux1, cfg) -> list:
    """The model's numbers of one step in the form the builder's probe reads
    them from the program's state: as they are (leaves in tree order)."""
    return [np.asarray(a, np.float64) for a in aux1]


def model_numbers(prog_aux1, ref_aux1, cfg, params: dict) -> dict:
    """The numbers only this model has, from the first step's (leaves in tree
    order: expert rows [layers, held], the two losses, route mass [layers]).

    mtp_loss_gap     the MTP module's mean cross-entropy, relative: a module
                     left out, fed the wrong token's embedding or held to the
                     wrong target moves it, whatever the trunk's loss does
    expert_rows_gap  worst held expert's row count, over the reference's count
                     for that expert or its layer's mean count, whichever is
                     larger (an expert that two tokens chose would read 1.0
                     for one token's flip): a router that scores, biases or
                     chooses otherwise, or a skipped expert, moves whole loads
    route_mass_gap   worst layer's mean over tokens of the routed weights that
                     fell on held experts (all held: the scaling factor),
                     relative: unnormalised or unscaled weights
    """
    rows_p, loss_p, mass_p = (np.asarray(a, np.float64) for a in prog_aux1)
    rows_r, loss_r, mass_r = (np.asarray(a, np.float64) for a in ref_aux1)
    if rows_p.shape != rows_r.shape:
        return {k: float("inf") for k in
                ("mtp_loss_gap", "expert_rows_gap", "route_mass_gap")}
    return {"mtp_loss_gap": float(abs(loss_p[1] - loss_r[1]) / abs(loss_r[1])),
            "expert_rows_gap": float(np.max(
                np.abs(rows_p - rows_r)
                / np.maximum(rows_r, np.mean(rows_r, axis=1, keepdims=True)))),
            "route_mass_gap": float(np.max(np.abs(mass_p - mass_r) / mass_r))}
