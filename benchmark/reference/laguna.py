"""Plain reference for the ``laguna`` configurations (decoders that mix full
and sliding-window attention, with a different head count by layer type, a
per-head output gate, one dense and then sparse SwiGLU feed-forwards;
huggingface.co/poolside/Laguna-XS.2): forward, loss and gradient in
straightforward ``jax.numpy`` float32 at ``highest``.  Imports nothing of the
program under test (the rounding helpers are the Ouro reference's).  A
configuration names this file under ``"reference"``; the host half of a step
is in ``steps.py``, ``sync/``, ``optim/``.

The model, as the configuration's ``assumed`` block states it.  Hidden d,
RMSNorm eps ``rms_norm_eps``, no biases.  Layer l of the first
``num_hidden_layers``:

    a = h + Attn_l(RMSNorm(h));   h' = a + FF_l(RMSNorm(a))
    after the last: hf = RMSNorm_f(h);  logits = hf W_head;  loss = mean CE

  Attn  H_l = ``num_attention_heads_per_layer[l]`` query heads on
        ``num_key_value_heads`` key/value heads of ``head_dim``;
        q, k <- RMSNorm_head(q), RMSNorm_head(k) (one scale vector each);
        rotary by ``rope_parameters[layer_types[l]]``: the first
        ``partial_rotary_factor x head_dim`` channels in the pairs
        (c, c + half), ``inv_c = theta^(-2c / rot)``, for ``yarn`` blended
        with ``inv_c / factor`` by the ramp between the channels of
        ``beta_fast`` and ``beta_slow`` turns in
        ``original_max_position_embeddings``, cos and sin times
        ``attention_factor``;  query head j reads key/value head
        j // (H_l / KV);  softmax(q k^T / sqrt(head_dim)) v over j <= i
        (``full_attention``) or i - ``sliding_window`` < j <= i
        (``sliding_attention``);  g = sigmoid(x W_g) [T, H_l];
        out = concat_h(g_h o_h) W_o
  FF    ``mlp_layer_types[l]`` ``dense``: (silu(x W_gate) * (x W_up)) W_down;
        ``sparse``: s = sigmoid(x W_r) over all published experts, float32;
        the ``num_experts_per_tok`` largest; w_e = ``moe_routed_scaling_factor``
        s_e / (sum of the chosen s + 1e-20);  f_e the same gated form;
        out = (sum over the chosen AND HELD e of w_e f_e(x)) + f_shared(x)

The parameter tree has two entries a layer, the attention sublayer's and the
feed-forward's, each with its norm.

**Shares.**  ``num_experts`` and ``vocab_size`` say what is HELD,
``published`` what the model has.  The router is as wide as
``published.num_experts`` and chooses among all of them; experts
``first_expert`` ... + ``num_experts`` are held, and what the others would add
is left out.  With both at their published values this file is the uncut model.

Attention is a masked softmax, the band an explicit mask; every held expert a
dense product over all tokens, masked by its weights; the logits whole.
Departures, so that float32 fits at the timed size (two sequences of 8,192
tokens; compiled for the chip whole, the batch took 15.1 GB): the sequences
of a batch one after another, each under ``jax.checkpoint``, and again
around each sublayer, each expert and the head; attention's softmax a block
of ``QUERY_BLOCK`` query rows at a time, each block against the keys its rows
can see (all of them in a full layer, the ``QUERY_BLOCK + sliding_window`` up
to the block's last row in a window layer), under the same mask.

``precision``: ``float32`` (the reference), or the emulated ``bfloat16`` /
``fp8`` of the control: every product's operands and result, every
elementwise result that the stated precision would hold in the compute type,
the residual stream and every cotangent on the way back are held in that
type; the router's scores, the norms' statistics, the rotary tables, the
gate's sigmoid and the loss stay float32, as the configuration states.
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

QUERY_BLOCK = 256


# ------------------------------------------------------------------ structure

def _published(cfg, key):
    return cfg.get("published", {}).get(key, cfg[key])


def _layers(cfg):
    """[(attention type, query heads, feed-forward type)] of the held layers."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def _attention_shapes(cfg, heads):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    return {"norm": (d,), "wq": (d, heads * hd), "wk": (d, kv), "wv": (d, kv),
            "q_norm": (hd,), "k_norm": (hd,), "w_head_gate": (d, heads),
            "wo": (heads * hd, d)}


def _ff_shapes(cfg, kind):
    d = cfg["hidden_size"]
    if kind == "dense":
        f = cfg["intermediate_size"]
        return {"norm": (d,), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    sh = cfg["shared_expert_intermediate_size"]
    return {"norm": (d,), "router": (d, _published(cfg, "num_experts")),
            "wg": (e, d, f), "wu": (e, d, f), "wd": (e, f, d),
            "ws_gate": (d, sh), "ws_up": (d, sh), "ws_down": (sh, d)}


def param_shapes(cfg):
    """Nested dict of parameter shapes: two dicts a layer (attention, then
    feed-forward); embedding, final norm and head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = []
    for _, heads, ff in _layers(cfg):
        layers += [_attention_shapes(cfg, heads), _ff_shapes(cfg, ff)]
    return {"embed": (v, d), "layers": layers, "final_norm": (d,), "lm_head": (d, v)}


def make_params(cfg, key):
    """Seeded float32 weights: normal(0, initializer_range) matrices and
    embedding, unit norm scales.  One traced function, so one device program."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        if path[-1].key.endswith("norm"):
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * cfg["initializer_range"])
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------ attention

def inv_frequencies(rope: dict, head_dim: int) -> np.ndarray:
    """[rot / 2] float32 (from float64) of one ``rope_parameters`` entry."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    c = np.arange(rot // 2, dtype=np.float64)
    inv = theta ** (-2.0 * c / rot)
    if rope["rope_type"] == "yarn":
        span = rope["original_max_position_embeddings"]
        channel = lambda turns: rot * math.log(span / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
        low = max(math.floor(channel(rope["beta_fast"])), 0)
        high = min(math.ceil(channel(rope["beta_slow"])), rot - 1)
        ramp = np.clip((c - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv * (1 - ramp) + inv / rope["factor"] * ramp
    elif rope["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    return inv.astype(np.float32)


def _rotary(x, rope, head_dim):
    """x [b, T, heads, hd]: channel c of the first ``rot`` turned with channel
    c + rot / 2 by position x inv_c; cos and sin times ``attention_factor``."""
    inv = inv_frequencies(rope, head_dim)
    half = inv.shape[0]
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    factor = rope.get("attention_factor", 1.0) if rope["rope_type"] == "yarn" else 1.0
    cos, sin = (factor * f(angle)[:, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attend(q, k, v, window, precision):
    """q [b, KV, R, T, hd] (the R query heads of each key/value head), k and v
    [b, KV, T, hd] -> [b, KV, R, T, hd]: the masked softmax, ``QUERY_BLOCK``
    query rows at a time against the keys those rows can see."""
    t, hd = q.shape[3], q.shape[4]
    blk = math.gcd(t, QUERY_BLOCK)
    span = t if window is None else min(t, blk + window)

    @jax.checkpoint
    def rows(first):
        start = 0 if window is None else jnp.clip(first + blk - span, 0, t - span)
        q_blk = jax.lax.dynamic_slice_in_dim(q, first, blk, axis=3)
        k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=2)
        i = first + jnp.arange(blk)[:, None]
        j = start + jnp.arange(span)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        scores = _product("bgrqd,bgkd->bgrqk", q_blk, k_blk, precision) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _product("bgrqk,bgkd->bgrqd", probs, v_blk, precision)

    out = jax.lax.map(rows, jnp.arange(0, t, blk))        # [blocks, b, KV, R, blk, hd]
    return jnp.moveaxis(out, 0, 3).reshape(q.shape)


def _attention(p, x, cfg, kind, precision):
    rnd = lambda y: _round_to(y, precision)
    b, t, _ = x.shape
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    rope = cfg["rope_parameters"][kind]
    heads = lambda w: _product("btd,de->bte", x, p[w], precision).reshape(b, t, -1, hd)
    q = rnd(_rotary(_rms_norm(heads("wq"), p["q_norm"], cfg["rms_norm_eps"]), rope, hd))
    k = rnd(_rotary(_rms_norm(heads("wk"), p["k_norm"], cfg["rms_norm_eps"]), rope, hd))
    v = heads("wv")
    n_heads = q.shape[2]
    by_group = lambda y: y.transpose(0, 2, 1, 3)                     # [b, heads, T, hd]
    window = {"full_attention": None, "sliding_attention": cfg["sliding_window"]}[kind]
    o = _attend(by_group(q).reshape(b, kv, n_heads // kv, t, hd),
                by_group(k), by_group(v), window, precision)
    o = o.reshape(b, n_heads, t, hd).transpose(0, 2, 1, 3)          # [b, T, heads, hd]
    gate = jax.nn.sigmoid(_product("btd,dh->bth", x, p["w_head_gate"], precision))
    return _product("bte,ed->btd", rnd(o * gate[..., None]).reshape(b, t, -1),
                    p["wo"], precision)


# --------------------------------------------------------------- feed-forward

def _swiglu(x, w_gate, w_up, w_down, spec, precision):
    """spec: the row index's letter(s), as in "bt" or "n"."""
    rnd = lambda y: _round_to(y, precision)
    hidden = rnd(rnd(jax.nn.silu(_product(f"{spec}d,df->{spec}f", x, w_gate, precision)))
                 * _product(f"{spec}d,df->{spec}f", x, w_up, precision))
    return _product(f"{spec}f,fd->{spec}d", hidden, w_down, precision)


def routing(p, x, cfg):
    """x [N, d] -> (``wts`` [N, held]: each token's weight on each held
    expert, 0 where it did not choose it; ``hit`` [N, held] bool)."""
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", x, p["router"], precision=HIGHEST))
    chosen, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = cfg["moe_routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    held = (idx[:, :, None]
            == cfg.get("first_expert", 0) + jnp.arange(cfg["num_experts"]))
    return jnp.sum(jnp.where(held, w[:, :, None], 0.0), axis=1), jnp.any(held, axis=1)


def _moe(p, x, cfg, precision):
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    wts, hit = routing(p, x2, cfg)

    @jax.checkpoint
    def expert(wg, wu, wd, weight):
        return weight[:, None] * _swiglu(x2, wg, wu, wd, "n", precision)

    routed = sum(expert(p["wg"][e], p["wu"][e], p["wd"][e], wts[:, e])
                 for e in range(cfg["num_experts"]))
    shared = _swiglu(x2, p["ws_gate"], p["ws_up"], p["ws_down"], "n", precision)
    stats = {"rows": jnp.sum(hit, axis=0).astype(jnp.float32),
             "mass": jnp.mean(jnp.sum(wts, axis=-1))}
    return _round_to(_round_to(routed, precision) + shared, precision).reshape(b, t, d), stats


# ---------------------------------------------------------------------- model

def sublayer(kind, p, h, cfg, precision="float32"):
    """(h + F(RMSNorm(h)), the routing's numbers of a sparse feed-forward);
    ``kind`` an attention type or a feed-forward type."""
    rnd = lambda y: _round_to(y, precision)
    x = rnd(_rms_norm(h, p["norm"], cfg["rms_norm_eps"]))
    stats = {}
    if kind in ("full_attention", "sliding_attention"):
        out = _attention(p, x, cfg, kind, precision)
    elif kind == "dense":
        out = _swiglu(x, p["w_gate"], p["w_up"], p["w_down"], "bt", precision)
    elif kind == "sparse":
        out, stats = _moe(p, x, cfg, precision)
    else:
        raise ValueError(f"unknown sublayer kind {kind!r}")
    return rnd(h + out), stats


def sublayer_kinds(cfg) -> list:
    return [k for attn, _, ff in _layers(cfg) for k in (attn, ff)]


@partial(jax.checkpoint, static_argnums=(3,))
def _head(h, w_head, labels, precision):
    """Per-token cross-entropy [b, T] through whole logits."""
    logz = jax.nn.log_softmax(_product("btd,dv->btv", h, w_head, precision), axis=-1)
    return -jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]


def hidden_states(params, tokens, cfg, precision="float32"):
    """(final-normed hidden states [b, T, d], each sparse layer's numbers)."""
    h = _round_to(params["embed"], precision)[tokens]
    stats = []
    for kind, p in zip(sublayer_kinds(cfg), params["layers"]):
        h, st = jax.checkpoint(partial(sublayer, kind, cfg=cfg, precision=precision))(p, h)
        if st:
            stats.append(st)
    return _round_to(_rms_norm(h, params["final_norm"], cfg["rms_norm_eps"]),
                     precision), stats


def logits_fn(params, tokens, cfg):
    """Whole logits [b, T, vocabulary held], float32: for the tests."""
    hf, _ = hidden_states(params, tokens, cfg)
    return jnp.einsum("btd,dv->btv", hf, params["lm_head"], precision=HIGHEST)


def loss_fn(params, tokens, labels, cfg, precision="float32"):
    """(loss, {the mean cross-entropy, each sparse layer's rows a held expert
    and kept weight mass}).  One sequence of the batch at a time (nothing in
    the model looks across sequences): the cross-entropies are summed, the
    rows added and the masses averaged over them."""
    @jax.checkpoint
    def one(sequence):
        tok, lab = sequence
        hf, stats = hidden_states(params, tok[None], cfg, precision)
        return (jnp.sum(_head(hf, params["lm_head"], lab[None], precision)),
                jnp.stack([s["rows"] for s in stats]),
                jnp.stack([s["mass"] for s in stats]))

    sums, rows, mass = jax.lax.map(one, (tokens, labels))
    loss = jnp.sum(sums) / tokens.size
    return loss, {"loss": loss[None], "expert_rows": jnp.sum(rows, axis=0),
                  "route_mass": jnp.mean(mass, axis=0)}


def make_loss_and_grad(cfg, precision="float32"):
    """jitted (params, tokens, labels) -> ((loss, the model's numbers), grads)."""
    return jax.jit(jax.value_and_grad(
        partial(loss_fn, cfg=cfg, precision=precision), has_aux=True))


# ------------------------------------- what the benchmark asks of a model file

def experts_flops_per_row(cfg) -> float:
    """Forward operations of one routed row through one expert: three
    products of hidden x expert width."""
    return 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _causal_pairs(cfg, kind) -> float:
    """(query, key) pairs a head of one layer of ``kind`` scores in a
    sequence: the causal half of T x T, or the band (T x window less the
    first window's missing triangle)."""
    t = cfg["seq_len"]
    if kind == "full_attention":
        return t * t / 2.0
    w = min(cfg["sliding_window"], t)
    return t * w - w * (w - 1) / 2.0


def _score_flops(cfg, kind) -> float:
    """Forward operations of the two attention products of every layer of
    ``kind``, a sequence."""
    return sum(heads * 2 * 2.0 * _causal_pairs(cfg, kind) * cfg["head_dim"]
               for attn, heads, _ in _layers(cfg) if attn == kind)


def forward_flops_per_sample(cfg) -> float:
    """A sample is a sequence.  Per token every sublayer's matrices (of the
    routed experts the ones a token chooses among those held, at uniform
    routing), the head; per sequence the attention products over the pairs
    each layer's mask admits.  Recomputation is not counted."""
    t, d = cfg["seq_len"], cfg["hidden_size"]
    per_token = 2.0 * d * cfg["vocab_size"]
    for shapes, kind in zip(param_shapes(cfg)["layers"], sublayer_kinds(cfg)):
        per_token += 2.0 * sum(int(np.prod(s)) for s in shapes.values() if len(s) == 2)
        if kind == "sparse":
            per_token += (cfg["num_experts_per_tok"] * cfg["num_experts"]
                          / _published(cfg, "num_experts")) * experts_flops_per_row(cfg)
    return per_token * t + sum(_score_flops(cfg, k)
                               for k in ("full_attention", "sliding_attention"))


def attention_flops_per_sample(cfg) -> float:
    """What the FULL layers' attention kernels of one training step on one
    sequence must do: per head and layer two products forward and four
    backward, each the causal half of T x T x head size multiply-adds."""
    return 3.0 * _score_flops(cfg, "full_attention")


def window_attention_flops_per_sample(cfg) -> float:
    """The same of the WINDOW layers: the band's pairs, T x window less the
    first window's triangle."""
    return 3.0 * _score_flops(cfg, "sliding_attention")


def aux_as_probed(aux1, cfg) -> list:
    """The model's numbers of one step in the form the builder's probe reads
    them from the program's state: as they are (leaves in tree order)."""
    return [np.asarray(a, np.float64) for a in aux1]


def model_numbers(prog_aux1, ref_aux1, cfg, params: dict) -> dict:
    """The numbers only this model has, from the first step's (leaves in tree
    order: expert rows [layers, held], the loss, route mass [layers]).

    expert_rows_gap  worst held expert's row count, over the reference's count
                     for that expert or its layer's mean count, whichever is
                     larger: a router that scores or chooses otherwise, or a
                     skipped expert, moves whole loads
    route_mass_gap   worst layer's mean over tokens of the routed weights that
                     fell on held experts (all held: the scaling factor),
                     relative: unnormalised or unscaled weights
    """
    rows_p, _, mass_p = (np.asarray(a, np.float64) for a in prog_aux1)
    rows_r, _, mass_r = (np.asarray(a, np.float64) for a in ref_aux1)
    if rows_p.shape != rows_r.shape:
        return {k: float("inf") for k in ("expert_rows_gap", "route_mass_gap")}
    return {"expert_rows_gap": float(np.max(
                np.abs(rows_p - rows_r)
                / np.maximum(rows_r, np.mean(rows_r, axis=1, keepdims=True)))),
            "route_mass_gap": float(np.max(np.abs(mass_p - mass_r) / mass_r))}
