"""Decoder-hybrid-decoder (arXiv:2507.06607, "SambaY"; the ``phi4flash``
family): a self-decoder of Mamba-1 and differential-attention layers, then a
cross-decoder whose mixers READ WHAT EARLIER LAYERS MADE: Gated Memory Units
on one Mamba-1 layer's scan output, cross-attention on one attention layer's
keys and values.  One character a layer:

  ``S``  a Mamba-1 mixer (:mod:`tpu_compressed_dp.ops.selective_scan`):
         ``[u, z] = x W_in``; ``u = silu(conv4(u) + b)``; ``[r, B, C] = u W_x``;
         ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)`` [channels, state];
         the selective scan gives ``m``; ``out = (m * silu(z)) W_out``;
  ``W``  differential attention (arXiv:2410.05258) in a window: a query sees
         itself and the ``window - 1`` before it;
  ``F``  the same over the whole sequence;
  ``G``  a Gated Memory Unit: ``out = (m * silu(x W_1)) W_2`` with the ``m``
         (before its gate) of the nearest ``S`` layer before it;
  ``X``  differential cross-attention: its own queries on the keys and values
         of the nearest ``W`` or ``F`` layer before it.

Layer ``l``: ``a = h + Mixer_l(LN1_l(h))``, ``h' = a + MLP_l(LN2_l(a))`` with
LayerNorm (mean and variance, scale and bias) and a SwiGLU
``(u * silu(g)) W_down``, ``[g, u] = x W_gu``; after the last the final
LayerNorm and the TIED head (the embedding, transposed).  No position
embedding: the state-space layers carry position.

**Differential attention.**  Heads pair up: query heads ``(2i, 2i + 1)`` are
``q1_i, q2_i``, key heads ``(2j, 2j + 1)`` are ``k1_j, k2_j`` and the value
heads ``(2j, 2j + 1)`` side by side are ``v_j``, twice a head wide; query pair
``i`` reads key/value pair ``i // (query pairs / key pairs)``.  ``o_i =
(softmax(q1 k1^T s) - lam softmax(q2 k2^T s)) v`` under the layer's mask, ``s
= head size ^ -1/2``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``
from four learned vectors a layer, ``lam0(l) = 0.8 - 0.6 exp(-0.3 l)`` with
``l`` the PUBLISHED layer index (``first_layer`` + the layer's place here);
then ``RMSNorm(o_i) * w_sub * (1 - lam0(l))`` and the output projection.  Each
of the two softmaxes a query pair is one call's head through
:func:`tpu_compressed_dp.ops.ring_attention.ring_attention`, whose values may
be wider than its keys.

**What crosses layers.**  ``m`` leaves its ``S`` layer, keys and values their
attention layer, as outputs of the layer's checkpoint (kept, not made again),
and enter every later reader as inputs; the cotangents of all the readers sum
into the producer's backward.  A ``G`` or ``X`` with no producer before it is
refused at construction.

A sibling of :mod:`tpu_compressed_dp.models.hybrid` and not more kinds of its
pattern: a layer there is ``h + Mixer(RMSNorm(h))`` of ``h`` alone, with an
untied head; here every layer is two sublayers under LayerNorm with a bias,
carries tensors past its neighbours, and ties the head: every line of the
runner, of the parameter table and of the loss would fork.  Shared with it:
the convolution, the scopes ``tcdp.ssm`` / ``tcdp.ssd`` / ``tcdp.attn`` /
``tcdp.attn_window`` / ``tcdp.mlp``, the fused head, the flash kernels, and
the settings' protocol into :func:`tpu_compressed_dp.train.lm_step.make_lm_train_step`.

Runs on the ``data`` axis of the LM mesh only, every layer whole; the
vocabulary may be a held slice (``vocab_held`` ids).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tpu_compressed_dp.models.transformer import fused_head_xent_tokens
from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.ops import selective_scan as sscan
from tpu_compressed_dp.ops.ring_attention import ring_attention
from tpu_compressed_dp.ops.ssd import causal_depthwise_conv

Array = jax.Array

__all__ = ["SambaYConfig", "published_pattern", "phi4_mini_flash_stage", "tiny_phi4flash",
           "init_sambay", "sambay_param_shapes", "apply_sambay", "sambay_loss",
           "lambda_init", "differential_attention"]

_F32 = jnp.float32
_ATTENTION = "WFX"


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064          # published; ids [0, vocab_held) are held
    vocab_held: int = 200064
    dim: int = 2560
    pattern: str = "SW" * 8 + "SF" + "GX" * 7   # one character a layer: published_pattern(32)
    first_layer: int = 0              # published index of the first layer held
    norm_eps: float = 1e-5
    # Mamba-1 mixer and Gated Memory Unit
    d_inner: int = 5120
    ssm_state: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # differential attention
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    lambda_std: float = 0.1
    ffn: int = 10240
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    def __post_init__(self):
        if set(self.pattern) - set("SWFGX"):
            raise ValueError(f"pattern {self.pattern!r}: a layer is S, W, F, G or X")
        if (self.n_heads % 2 or self.n_kv_heads % 2
                or self.n_heads % self.n_kv_heads):
            raise ValueError("differential attention pairs its heads: an even "
                             "number of query and of key/value heads, the "
                             "first a multiple of the second")
        if "W" in self.pattern and self.window <= 0:
            raise ValueError("a W layer needs its window")
        self.producers()

    def producers(self) -> Dict[int, int]:
        """{a G or X layer's place: the place of the layer whose tensors it
        reads}: the nearest S before a G, the nearest W or F before an X."""
        found, last = {}, {}
        for i, kind in enumerate(self.pattern):
            if kind in "GX":
                src = last.get("S" if kind == "G" else "A")
                if src is None:
                    raise ValueError(
                        f"layer {i} ({kind}) of {self.pattern!r} reads "
                        + ("a Mamba-1 layer's scan output" if kind == "G" else
                           "an attention layer's keys and values")
                        + " and none comes before it")
                found[i] = src
            elif kind == "S":
                last["S"] = i
            else:
                last["A"] = i
        return found

    # ---- what the LM step asks of a model's settings ----
    def validate_mesh(self, tensor_size: int) -> None:
        if tensor_size != 1:
            raise ValueError("the decoder-hybrid-decoder has no tensor axis: "
                             "every layer is whole on every chip")

    def init(self, key: Array) -> Dict[str, Any]:
        return init_sambay(self, key)

    def param_specs(self) -> Dict[str, Any]:
        return jax.tree.map(lambda _: P(), sambay_param_shapes(self),
                            is_leaf=lambda s: isinstance(s, tuple))

    def init_aux(self) -> Dict[str, Array]:
        return {"loss": jnp.zeros((1,), _F32),
                "diff_lambda": jnp.zeros((self.count(_ATTENTION),), _F32),
                "memory_rms": jnp.zeros((self.count("S"),), _F32)}

    def loss(self, params, x: Array, y: Array, mesh_shape) -> Tuple[Array, Array, Dict]:
        if mesh_shape.get("seq", 1) != 1:
            raise ValueError("the decoder-hybrid-decoder has no sequence axis: "
                             "the scan carries its state through the whole "
                             "sequence")
        return sambay_loss(self, params, x, y)

    def aux_metrics(self, aux: Dict[str, Array]) -> Dict[str, Array]:
        return {"loss/lm": aux["loss"][0],
                "model/diff_lambda": jnp.mean(aux["diff_lambda"]),
                "model/memory_rms": jnp.mean(aux["memory_rms"])}

    def count(self, kinds: str) -> int:
        return sum(self.pattern.count(k) for k in kinds)


def published_pattern(n_layers: int) -> str:
    """The family's layout for a model of ``n_layers`` (one Mamba block every
    two layers): below the middle Mamba-1 and window attention turn about,
    layer ``n / 2`` is the Mamba-1 and ``n / 2 + 1`` the full attention that
    hand on, beyond them Gated Memory Units and cross-attention turn about."""
    half = n_layers // 2
    return "".join(
        ("S" if l % 2 == 0 else "W") if l <= half else
        "F" if l == half + 1 else ("G" if l % 2 == 0 else "X")
        for l in range(n_layers))


def phi4_mini_flash_stage() -> SambaYConfig:
    """microsoft/Phi-4-mini-flash-reasoning (huggingface.co/microsoft/
    Phi-4-mini-flash-reasoning config.json, 3.8 B): the third pipeline stage
    of four, layers 14-21 of 32 (the hand-over: Mamba-1, window, the Mamba-1
    and the full attention that hand on, then two GMUs and two
    cross-attentions), every layer whole, with embedding, final norm and the
    tied head over 1/8 of the vocabulary."""
    return SambaYConfig(vocab_held=25008, pattern=published_pattern(32)[14:22],
                        first_layer=14)


def tiny_phi4flash(vocab: int = 256, dim: int = 64) -> SambaYConfig:
    """Smoke/test scale: the same eight kinds of layer, ``first_layer`` kept."""
    return SambaYConfig(
        vocab_size=vocab, vocab_held=vocab, dim=dim,
        pattern=published_pattern(32)[14:22], first_layer=14, d_inner=2 * dim, ssm_state=8, dt_rank=max(dim // 16, 1),
        chunk=16, n_heads=4, n_kv_heads=2, head_dim=16, window=8, ffn=2 * dim)


# --------------------------------------------------------------- parameters

def _layer_shapes(cfg: SambaYConfig, kind: str) -> Dict[str, tuple]:
    d, di, n, r = cfg.dim, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    q, kv, hd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim, cfg.head_dim
    shapes = {"norm1_w": (d,), "norm1_b": (d,), "norm2_w": (d,), "norm2_b": (d,),
              "w_gu": (d, 2 * cfg.ffn), "w_down": (cfg.ffn, d)}
    if kind == "S":
        shapes.update(w_in=(d, 2 * di), conv_w=(cfg.conv_kernel, di), conv_b=(di,),
                      w_x=(di, r + 2 * n), w_dt=(r, di), b_dt=(di,),
                      a_log=(di, n), d_skip=(di,), w_out=(di, d))
    elif kind == "G":
        shapes.update(w_1=(d, di), w_2=(di, d))
    else:
        if kind == "X":
            shapes.update(w_q=(d, q), b_q=(q,))
        else:
            shapes.update(w_qkv=(d, q + 2 * kv), b_qkv=(q + 2 * kv,))
        shapes.update(w_o=(q, d), b_o=(d,), lq1=(hd,), lk1=(hd,), lq2=(hd,),
                      lk2=(hd,), sub_norm=(2 * hd,))
    return shapes


def sambay_param_shapes(cfg: SambaYConfig) -> Dict[str, Any]:
    d = cfg.dim
    return {"embed": (cfg.vocab_held, d),
            "layers": [_layer_shapes(cfg, k) for k in cfg.pattern],
            "final_norm_w": (d,), "final_norm_b": (d,)}


_ONES = ("norm1_w", "norm2_w", "final_norm_w", "sub_norm", "d_skip")
_ZEROS = ("norm1_b", "norm2_b", "final_norm_b", "b_qkv", "b_q", "b_o")


def init_sambay(cfg: SambaYConfig, key: Array) -> Dict[str, Any]:
    """float32 masters: normal(0, init_std) matrices and embedding;
    ``A_log[c, n] = ln(n + 1)``, ``D`` 1, the time steps log-uniform in
    [time_step_min, time_step_max] through the inverse softplus, ``W_dt``
    uniform(+-dt_rank^-1/2), the convolution uniform(+-conv_kernel^-1/2), the
    lambda vectors normal(0, lambda_std), norm scales 1 and every other bias
    0."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        sambay_param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = path[-1].key
        if name in _ONES:
            leaf = jnp.ones(shape, _F32)
        elif name in _ZEROS:
            leaf = jnp.zeros(shape, _F32)
        elif name == "a_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=_F32)), shape)
        elif name == "b_dt":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, _F32, lo, hi)),
                             cfg.time_step_floor)
            leaf = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
        elif name in ("w_dt", "conv_w", "conv_b"):
            bound = (cfg.dt_rank if name == "w_dt" else cfg.conv_kernel) ** -0.5
            leaf = jax.random.uniform(k, shape, _F32, -bound, bound)
        elif name in ("lq1", "lk1", "lq2", "lk2"):
            leaf = jax.random.normal(k, shape, _F32) * cfg.lambda_std
        else:
            leaf = jax.random.normal(k, shape, _F32) * cfg.init_std
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------------- mixers
# _decay_matrix, _sub_norm and _out_scale are functions of their own because
# they are where the benchmark's cell test plants its faults (a decay by
# channel only, the sub-norm or the (1 - lam0) factor dropped).

def _layer_norm(x: Array, w: Array, b: Array, eps: float) -> Array:
    """Mean and variance over the channels in float32; ``x``'s type out."""
    xf = x.astype(_F32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _decay_matrix(a_log: Array) -> Array:
    """``A`` [channels, state]: one decay rate for every channel and state
    index."""
    return -jnp.exp(a_log.astype(_F32))


def _mamba_mixer(cfg: SambaYConfig, lp, x: Array) -> Tuple[Array, Array]:
    """(the mixer's output, the scan's output ``m`` before its gate)."""
    dt_ = cfg.dtype
    n, r = cfg.ssm_state, cfg.dt_rank
    u, z = jnp.split(x @ lp["w_in"].astype(dt_), 2, axis=-1)
    with obs_trace.phase("ssd"):
        u = jax.nn.silu(causal_depthwise_conv(u, lp["conv_w"], lp["conv_b"]))
    rank, b, c = jnp.split(u @ lp["w_x"].astype(dt_), [r, r + n], axis=-1)
    dt = jax.nn.softplus(jnp.dot(rank, lp["w_dt"].astype(dt_),
                                 preferred_element_type=_F32) + lp["b_dt"])
    with obs_trace.phase("ssd"):
        m, _ = sscan.selective_scan(u, dt, _decay_matrix(lp["a_log"]), b, c,
                                    lp["d_skip"], cfg.chunk)
    return _gated(m, z, dt_) @ lp["w_out"].astype(dt_), m


def _gated(m: Array, gate: Array, dtype) -> Array:
    return (m.astype(_F32) * jax.nn.silu(gate.astype(_F32))).astype(dtype)


def _gmu_mixer(cfg: SambaYConfig, lp, x: Array, m: Array) -> Array:
    """``m``: its Mamba-1 layer's scan output, before that layer's own gate."""
    dt_ = cfg.dtype
    with obs_trace.phase("gmu"):
        return _gated(m, x @ lp["w_1"].astype(dt_), dt_) @ lp["w_2"].astype(dt_)


def lambda_init(layer: int) -> float:
    """``lam0`` of the published layer index ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _sub_norm(o: Array, w: Array, eps: float) -> Array:
    """RMSNorm over a pair's output, in float32."""
    return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w


def differential_attention(cfg: SambaYConfig, q: Array, k: Array, v: Array,
                           lam: Array, lam0: float, sub_w: Array,
                           window: Optional[int], scope: str) -> Array:
    """``q`` [B, T, H, hd], ``k`` [B, T, KV, hd], ``v`` [B, T, KV / 2, 2 hd]
    -> the sub-normed pair outputs side by side, [B, T, H / 2 x 2 hd]."""
    bsz, t, h, hd = q.shape
    per = h // k.shape[2]                # query heads a key head, pairs a pair
    head = jnp.arange(h)
    # softmax map 2i + s: query head 2i + s on key head 2 (i // per) + s and
    # value pair i // per
    by_head = lambda y: y.transpose(0, 2, 1, 3)
    km = by_head(k)[:, 2 * (head // (2 * per)) + head % 2]
    vm = by_head(v)[:, head // (2 * per)]
    with obs_trace.phase(scope):
        o = ring_attention(by_head(q), km, vm, scale=hd ** -0.5, window=window)
    o = by_head(o).astype(_F32).reshape(bsz, t, h // 2, 2, 2 * hd)
    o = o[:, :, :, 0] - lam * o[:, :, :, 1]
    o = _sub_norm(o, sub_w, cfg.norm_eps) * _out_scale(lam0)
    return o.reshape(bsz, t, h * hd).astype(cfg.dtype)


def _out_scale(lam0: float) -> float:
    return 1.0 - lam0


def _lambda(lp, lam0: float) -> Array:
    dot = lambda a, b: jnp.sum(lp[a].astype(_F32) * lp[b].astype(_F32))
    return jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + lam0


def _attention_mixer(cfg: SambaYConfig, kind: str, layer: int, lp, x: Array,
                     handed=None):
    """(the mixer's output, its keys and values as a later X reads them, its
    ``lam``).  An X layer reads ``handed`` = (k, v) and hands nothing on."""
    dt_ = cfg.dtype
    bsz, t, _ = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    if kind == "X":
        q = x @ lp["w_q"].astype(dt_) + lp["b_q"].astype(dt_)
        k, v = handed
    else:
        qkv = x @ lp["w_qkv"].astype(dt_) + lp["b_qkv"].astype(dt_)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
        k = k.reshape(bsz, t, nkv, hd)
        v = v.reshape(bsz, t, nkv // 2, 2 * hd)
    lam0 = lambda_init(layer)
    lam = _lambda(lp, lam0)
    scope = {"W": "attn_window", "F": "attn", "X": "attn_cross"}[kind]
    o = differential_attention(cfg, q.reshape(bsz, t, nq, hd), k, v, lam, lam0,
                               lp["sub_norm"], cfg.window if kind == "W" else None,
                               scope)
    return o @ lp["w_o"].astype(dt_) + lp["b_o"].astype(dt_), (k, v), lam


def _mlp(cfg: SambaYConfig, lp, x: Array) -> Array:
    dt_ = cfg.dtype
    with obs_trace.phase("mlp"):
        g, u = jnp.split(x @ lp["w_gu"].astype(dt_), 2, axis=-1)
        return (u * jax.nn.silu(g)) @ lp["w_down"].astype(dt_)


def _layer(cfg: SambaYConfig, kind: str, layer: int, hands_on: bool, lp,
           h: Array, handed):
    """``a = h + Mixer(LN1(h))``, ``h' = a + MLP(LN2(a))``: (``h'``, what the
    layer hands on to later layers if ``hands_on`` (an S layer its ``m``, an
    attention layer its keys and values), its number for the auxiliary slot:
    an S layer the root mean square of ``m``, an attention layer its ``lam``).
    ``handed`` is what a G or X layer reads of its producer."""
    x = _layer_norm(h, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
    out, stat = None, None
    if kind == "S":
        with obs_trace.phase("ssm"):
            mixed, out = _mamba_mixer(cfg, lp, x)
        stat = jnp.sqrt(jnp.mean(jnp.square(out.astype(_F32))))
    elif kind == "G":
        mixed = _gmu_mixer(cfg, lp, x, handed)
    else:
        mixed, out, stat = _attention_mixer(cfg, kind, layer, lp, x, handed)
    a = h + mixed
    h = a + _mlp(cfg, lp, _layer_norm(a, lp["norm2_w"], lp["norm2_b"], cfg.norm_eps))
    return h, (out if hands_on else None), stat


def _run_layers(cfg: SambaYConfig, layers, h: Array):
    """Every layer under its own checkpoint: of an S layer the scan's output
    and chunk-boundary states are kept (the backward does not run the scan's
    forward again), of every other layer nothing but what it hands on.  The
    handed tensors are outputs of their producer's checkpoint and inputs of
    each reader's: they stay alive from the one to the last of the others,
    and nothing global holds them."""
    layer = jax.checkpoint(
        _layer, static_argnums=(0, 1, 2, 3),
        policy=jax.checkpoint_policies.save_only_these_names(*sscan.KEPT_NAMES))
    src = cfg.producers()
    wanted = set(src.values())
    made, stats = {}, {"S": [], "A": []}
    for i, (kind, lp) in enumerate(zip(cfg.pattern, layers)):
        h, out, stat = layer(cfg, kind, cfg.first_layer + i, i in wanted, lp, h,
                             made.get(src.get(i)))
        if i in wanted:
            made[i] = out
        if stat is not None:
            stats["S" if kind == "S" else "A"].append(stat)
    return h, stats


def apply_sambay(cfg: SambaYConfig, params, tokens: Array):
    """``tokens`` [B, T] -> the final-normed hidden states [B, T, D] and the
    layers' numbers.  The head is the loss's (:func:`sambay_loss`), which
    never makes whole logits."""
    if "S" in cfg.pattern and tokens.shape[1] % cfg.chunk:
        raise ValueError(f"{tokens.shape[1]} tokens are not a whole number of "
                         f"the scan's chunks of {cfg.chunk}")
    with obs_trace.phase("stack"):
        h, stats = _run_layers(cfg, params["layers"],
                               params["embed"].astype(cfg.dtype)[tokens])
        hf = _layer_norm(h, params["final_norm_w"], params["final_norm_b"],
                         cfg.norm_eps)
    return hf, stats


def sambay_loss(cfg: SambaYConfig, params, x: Array, y: Array):
    """``(loss, loss, aux)`` for inputs ``x`` and next tokens ``y`` [B, T]:
    the mean cross-entropy over the held ids through the fused head on the
    embedding transposed (tied: the embedding's gradient is the gather's
    scatter plus the head's product).  ``aux``: the loss, every attention
    layer's ``lam``, every Mamba-1 layer's root mean square of ``m``."""
    hf, stats = apply_sambay(cfg, params, x)
    with obs_trace.phase("head_xent"):
        nll = fused_head_xent_tokens(hf[None], params["embed"].astype(cfg.dtype).T,
                                     y[None])
    loss = jnp.mean(nll[0])
    stack = lambda xs: jnp.stack(xs) if xs else jnp.zeros((0,), _F32)
    return loss, loss, {"loss": loss[None], "diff_lambda": stack(stats["A"]),
                        "memory_rms": stack(stats["S"])}
