"""Hybrid decoder: a stack whose layers each hold ONE mixer, chosen by a
per-layer pattern (the ``nemotron_h`` family, arXiv:2504.03624; the
``laguna`` family, whose layer is two such entries, an attention sublayer and
a feed-forward one):

  ``M``  a Mamba-2 mixer (:mod:`tpu_compressed_dp.ops.ssd`): input projection,
         causal depthwise convolution, the selective state-space recurrence as
         a chunked scan, gate, group-wise RMSNorm, output projection;
  ``*``  causal attention with grouped keys and values and NO position
         embedding (the state-space layers carry position);
  ``E``  a LatentMoE layer: a float32 sigmoid router over all the routed
         experts, the top ``top_k`` normalised and scaled, the experts
         (squared-ReLU, ungated) computed in a latent narrower than the hidden
         state between two projections all tokens share, and a full-width
         shared expert beside them.  ``moe_latent`` 0 leaves the latent
         out (the experts read and write the hidden state), ``moe_gated``
         makes every expert and the shared one ``(silu(x Wg) * (x Wu)) Wd``,
         ``router_bias`` False leaves the balancing bias out;
  ``F``  causal attention over the whole sequence with ``full_heads`` query
         heads: RMSNorm over each head of q and k, rotary embedding
         (:class:`Rotary`: on the first ``dim`` channels of a head, YaRN
         frequencies and a cos/sin factor if stated), a per-head sigmoid gate
         from the sublayer's input on the kernel's output;
  ``W``  the same with ``window_heads`` query heads, its own rotary, and a
         sliding window: a query sees itself and the ``window - 1`` before it
         (the banded flash kernels, :mod:`tpu_compressed_dp.ops.flash_attention`);
  ``D``  a dense gated feed-forward, ``(silu(x Wg) * (x Wu)) Wd``.

Layer ``l``: ``h = h + Mixer_l(RMSNorm_l(h))``; after the last the final norm
and the untied head.  One multi-token-prediction module (DeepSeek-V3's form)
reads the trunk's output and the next token's embedding, runs the layers of
``mtp_pattern`` and predicts the token after next through the trunk's head.

**Shares.**  The settings state the published counts AND what this program
holds of them: a slice of the Mamba heads with their groups, of the attention
heads with their key/value heads, ``experts_held`` of the ``n_routed_experts``
from ``first_expert`` on, ``vocab_held`` ids.  A held share computes exactly
what that slice of the whole layer computes (the group-wise norm closes over
a group's heads, so a share of whole groups is exact); the router keeps all
its outputs, its ``top_k`` choices and the normalisation over all of them, and
the sum runs over the chosen experts that are held.  What the absent shares
would add is left out, and nothing stands in for them.

The expert layer is drop-less: the rows of the held experts are found by a
sort, laid out expert by expert in tiles of ``EXPERT_TILE`` rows, and a loop
over the occupied tiles alone (static shapes, a trip count read from the
routing) runs the two products and scatter-adds the weighted rows back.  Every
token a router sends to a held expert is computed, whatever the load.

This module runs on the ``data`` axis of the LM mesh only (``tensor`` and
``seq`` of size 1): an expert axis with its exchange, and a sequence axis
through the scan, are not written.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from tpu_compressed_dp.models.transformer import (_rms_norm,
                                                  fused_head_xent_tokens)
from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.ops.ring_attention import ring_attention
from tpu_compressed_dp.ops.ssd import (causal_depthwise_conv, ssd_chunked_scan,
                                       varying_like)

Array = jax.Array

__all__ = ["HybridConfig", "Rotary", "nemotron3_super_stage", "tiny_hybrid",
           "laguna_xs2_stage", "tiny_laguna", "init_hybrid",
           "hybrid_param_specs", "apply_hybrid", "hybrid_loss", "route",
           "dispatch", "grouped_experts", "gated_experts", "rotary_tables"]

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of attention layer's rotary embedding: the first ``dim``
    channels of a head turn in the pairs ``(c, c + dim / 2)`` by
    ``position * inv_c``, the rest pass untouched.  ``inv_c =
    theta^(-2c / dim)``; with ``yarn_factor`` > 1 the YaRN blend of that and
    ``inv_c / yarn_factor`` by a ramp between the channels that make
    ``beta_fast`` and ``beta_slow`` turns in ``yarn_original`` positions.
    ``attention_factor`` multiplies cos and sin."""
    theta: float
    dim: int
    yarn_factor: float = 1.0
    yarn_original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> np.ndarray:
        """[dim / 2] float32, from float64."""
        half = self.dim // 2
        inv = self.theta ** (-2.0 * np.arange(half, dtype=np.float64) / self.dim)
        if self.yarn_factor > 1.0:
            turns = lambda n: (self.dim * math.log(
                self.yarn_original / (n * 2.0 * math.pi))) / (2.0 * math.log(self.theta))
            low = max(math.floor(turns(self.beta_fast)), 0)
            high = min(math.ceil(turns(self.beta_slow)), self.dim - 1)
            ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                           / max(high - low, 1e-3), 0.0, 1.0)
            inv = inv * (1.0 - ramp) + inv / self.yarn_factor * ramp
        return inv.astype(np.float32)


def rotary_tables(rot: Rotary, t: int) -> Tuple[Array, Array]:
    """(cos, sin) [t, dim / 2] float32 of positions 0..t-1."""
    ang = jnp.arange(t, dtype=_F32)[:, None] * jnp.asarray(rot.inv_freq())[None, :]
    return rot.attention_factor * jnp.cos(ang), rot.attention_factor * jnp.sin(ang)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 131072          # published; ids [0, vocab_held) are held
    vocab_held: int = 131072
    dim: int = 4096
    pattern: str = "MEMEMEM*EME"      # one character a layer
    n_layers_published: int = 88      # scales the output projections' init
    norm_eps: float = 1e-5
    # Mamba-2 mixer: published heads and groups, and the slice held
    mamba_heads: int = 128
    mamba_heads_held: int = 128
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    mamba_groups_held: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    n_heads: int = 32
    n_heads_held: int = 32
    n_kv_heads: int = 2
    n_kv_heads_held: int = 2
    head_dim: int = 128
    # gated attention with rotary embedding (F over the whole sequence, W in a
    # window), on the n_kv_heads_held key/value heads: every head is held
    full_heads: int = 0
    window_heads: int = 0
    window: int = 0
    rotary_full: Optional[Rotary] = None
    rotary_window: Optional[Rotary] = None
    # dense gated feed-forward (D)
    dense_ffn: int = 0
    # LatentMoE (moe_latent 0: no latent; moe_gated: SwiGLU experts)
    n_routed_experts: int = 512
    experts_held: int = 512
    first_expert: int = 0
    top_k: int = 22
    moe_latent: int = 1024
    moe_ffn: int = 2688
    shared_ffn: int = 5376
    routed_scale: float = 5.0
    moe_gated: bool = False
    router_bias: bool = True
    # multi-token prediction: the module's layers and its loss's weight
    mtp_pattern: str = "*E"
    mtp_loss_weight: float = 0.1
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02
    # the mixers' output projections start divided by sqrt(2 x published layers)
    rescale_out_proj: bool = True

    def __post_init__(self):
        kinds = set(self.pattern + self.mtp_pattern)
        if kinds - set("M*EFWD"):
            raise ValueError(f"pattern {self.pattern!r}/{self.mtp_pattern!r}: "
                             "a layer is M, *, E, F, W or D")
        for kind, heads, rot in (("F", self.full_heads, self.rotary_full),
                                 ("W", self.window_heads, self.rotary_window)):
            if kind in kinds and (
                    rot is None or heads <= 0 or heads % self.n_kv_heads_held
                    or rot.dim % 2 or not 0 < rot.dim <= self.head_dim):
                raise ValueError(f"a {kind} layer needs its query heads (a "
                                 "multiple of the key/value heads held) and "
                                 "its rotary embedding")
        if "W" in kinds and self.window <= 0:
            raise ValueError("a W layer needs its window")
        if "D" in kinds and self.dense_ffn <= 0:
            raise ValueError("a D layer needs its width")
        per = self.mamba_heads // self.mamba_groups
        if (self.mamba_heads % self.mamba_groups
                or self.mamba_heads_held != per * self.mamba_groups_held):
            raise ValueError("a Mamba share holds whole groups with their heads")
        if (self.n_heads % self.n_kv_heads
                or self.n_heads_held % self.n_kv_heads_held
                or (self.n_heads // self.n_kv_heads)
                % (self.n_heads_held // self.n_kv_heads_held)):
            raise ValueError("an attention share holds query heads with the "
                             "key/value head they read (a key/value head "
                             "may serve several shares)")
        if not 0 <= self.first_expert <= self.n_routed_experts - self.experts_held:
            raise ValueError("the held experts lie outside the routed ones")

    # ---- what the LM step asks of a model's settings (LlamaConfig too) ----
    def validate_mesh(self, tensor_size: int) -> None:
        if tensor_size != 1:
            raise ValueError("the hybrid decoder has no tensor axis: its "
                             "shares are stated by the settings, not sharded")

    def init(self, key: Array) -> Dict[str, Any]:
        return init_hybrid(self, key)

    def param_specs(self) -> Dict[str, Any]:
        return hybrid_param_specs(self)

    def init_aux(self) -> Dict[str, Array]:
        n_moe = (self.pattern + self.mtp_pattern).count("E")
        return {"loss": jnp.zeros((2 if self.mtp_pattern else 1,), _F32),
                "expert_rows": jnp.zeros((n_moe, self.experts_held), _F32),
                "route_mass": jnp.zeros((n_moe,), _F32)}

    def loss(self, params, x: Array, y: Array, mesh_shape) -> Tuple[Array, Array, Dict]:
        if mesh_shape.get("seq", 1) != 1:
            raise ValueError("the hybrid decoder has no sequence axis: the "
                             "scan carries its state through the whole sequence")
        return hybrid_loss(self, params, x, y)

    def aux_metrics(self, aux: Dict[str, Array]) -> Dict[str, Array]:
        mtp = {"loss/mtp": aux["loss"][1]} if self.mtp_pattern else {}
        return {"loss/lm": aux["loss"][0], **mtp,
                "model/expert_rows": jnp.mean(aux["expert_rows"]),
                "model/expert_rows_max": jnp.max(aux["expert_rows"]),
                "model/route_mass": jnp.mean(aux["route_mass"])}

    # ---- sizes ----
    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads_held * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_groups_held * self.ssm_state


def nemotron3_super_stage() -> HybridConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B (huggingface.co/nvidia/
    NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json): the first pipeline
    stage of eight (11 of the 88 layers, with embedding, head and the MTP
    module), each mixer one of 4 chips' share by heads, 8 of the 512 routed
    experts (one of an expert-parallel group of 64), 1/8 of the vocabulary."""
    return HybridConfig(vocab_held=16384, mamba_heads_held=32,
                        mamba_groups_held=2, n_heads_held=8, n_kv_heads_held=1,
                        experts_held=8)


def tiny_hybrid(vocab: int = 256, dim: int = 64) -> HybridConfig:
    """Smoke/test scale: every kind of layer, every share a quarter."""
    return HybridConfig(
        vocab_size=vocab, vocab_held=vocab, dim=dim, pattern="ME*E",
        n_layers_published=4, mamba_heads=8, mamba_heads_held=8,
        mamba_head_dim=8, mamba_groups=4, mamba_groups_held=4, ssm_state=16,
        chunk=16, n_heads=4, n_heads_held=4, n_kv_heads=2, n_kv_heads_held=2,
        head_dim=16, n_routed_experts=16, experts_held=16, top_k=4,
        moe_latent=32, moe_ffn=48, shared_ffn=96)


def laguna_xs2_stage() -> HybridConfig:
    """poolside Laguna-XS.2 (huggingface.co/poolside/Laguna-XS.2 config.json,
    33.4B-A3B): the first pipeline stage of eight (layers 0-4 of 40, with
    embedding, final norm and head).  A layer is two entries: attention
    (full, window, window, window, full: 48 / 64 query heads on 8 key/value
    heads, every head held) and feed-forward (layer 0 dense, then top-8 of
    256 SwiGLU experts with one shared expert: 32 held, one of an
    expert-parallel group of 8), 1/8 of the vocabulary."""
    return HybridConfig(
        vocab_size=100352, vocab_held=12544, dim=2048, pattern="FDWEWEWEFE",
        n_layers_published=40, norm_eps=1e-6, n_kv_heads=8, n_kv_heads_held=8,
        head_dim=128, full_heads=48, window_heads=64, window=512,
        rotary_full=Rotary(theta=500000.0, dim=64, yarn_factor=64.0,
                           yarn_original=4096, beta_fast=64.0, beta_slow=1.0,
                           attention_factor=1.4158883083359672),
        rotary_window=Rotary(theta=10000.0, dim=128), dense_ffn=8192,
        n_routed_experts=256, experts_held=32, top_k=8, moe_latent=0,
        moe_ffn=512, shared_ffn=512, routed_scale=2.5, moe_gated=True,
        router_bias=False, mtp_pattern="", rescale_out_proj=False)


def tiny_laguna(vocab: int = 256, dim: int = 64) -> HybridConfig:
    """Smoke/test scale of the ``laguna`` layer kinds: 2 full and 3 window
    layers, 6 / 8 query heads on 2 key/value heads, a window of 16, top-4 of
    16 experts, every expert held."""
    return HybridConfig(
        vocab_size=vocab, vocab_held=vocab, dim=dim, pattern="FDWEWEWEFE",
        n_layers_published=5, norm_eps=1e-6, n_kv_heads=2, n_kv_heads_held=2,
        head_dim=16, full_heads=6, window_heads=8, window=16,
        rotary_full=Rotary(theta=500000.0, dim=8, yarn_factor=8.0,
                           yarn_original=16, beta_fast=4.0, beta_slow=1.0,
                           attention_factor=1.2),
        rotary_window=Rotary(theta=10000.0, dim=16), dense_ffn=128,
        n_routed_experts=16, experts_held=16, top_k=4, moe_latent=0,
        moe_ffn=32, shared_ffn=32, routed_scale=2.5, moe_gated=True,
        router_bias=False, mtp_pattern="", rescale_out_proj=False)


# --------------------------------------------------------------- parameters

def _layer_shapes(cfg: HybridConfig, kind: str) -> Dict[str, tuple]:
    d = cfg.dim
    if kind == "M":
        hh, inner = cfg.mamba_heads_held, cfg.mamba_inner
        return {"norm": (d,), "w_in": (d, inner + cfg.conv_dim + hh),
                "conv_w": (cfg.conv_kernel, cfg.conv_dim),
                "conv_b": (cfg.conv_dim,), "dt_bias": (hh,), "a_log": (hh,),
                "d_skip": (hh,), "gate_norm": (inner,), "w_out": (inner, d)}
    if kind == "*":
        q, kv = cfg.n_heads_held * cfg.head_dim, cfg.n_kv_heads_held * cfg.head_dim
        return {"norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "wo": (q, d)}
    if kind in ("F", "W"):
        q = (cfg.full_heads if kind == "F" else cfg.window_heads) * cfg.head_dim
        kv = cfg.n_kv_heads_held * cfg.head_dim
        return {"norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,),
                "w_head_gate": (d, q // cfg.head_dim), "wo": (q, d)}
    if kind == "D":
        return {"norm": (d,), "w_gate": (d, cfg.dense_ffn),
                "w_up": (d, cfg.dense_ffn), "w_down": (cfg.dense_ffn, d)}
    e, lat, f, sh = cfg.experts_held, cfg.moe_latent, cfg.moe_ffn, cfg.shared_ffn
    width = lat or d                      # what the experts read and write
    shapes = {"norm": (d,), "router": (d, cfg.n_routed_experts)}
    if cfg.router_bias:
        shapes["e_bias"] = (cfg.n_routed_experts,)
    if lat:
        shapes.update(w_down_lat=(d, lat), w_up_lat=(lat, d))
    if cfg.moe_gated:
        shapes.update(wg=(e, width, f), wu=(e, width, f), wd=(e, f, width),
                      ws_gate=(d, sh), ws_up=(d, sh), ws_down=(sh, d))
    else:
        shapes.update(w1=(e, width, f), w2=(e, f, width), ws1=(d, sh), ws2=(sh, d))
    return shapes


def hybrid_param_shapes(cfg: HybridConfig) -> Dict[str, Any]:
    d, v = cfg.dim, cfg.vocab_held
    shapes = {"embed": (v, d),
              "layers": [_layer_shapes(cfg, k) for k in cfg.pattern],
              "final_norm": (d,), "lm_head": (d, v)}
    if cfg.mtp_pattern:
        shapes["mtp"] = {
            "embed_norm": (d,), "hidden_norm": (d,), "w_eh": (2 * d, d),
            "layers": [_layer_shapes(cfg, k) for k in cfg.mtp_pattern],
            "final_norm": (d,)}
    return shapes


#: the matrices that write a mixer's output to the residual stream
_OUT_PROJ = ("w_out", "wo", "w_up_lat", "ws2")


def init_hybrid(cfg: HybridConfig, key: Array) -> Dict[str, Any]:
    """float32 masters: normal(0, init_std) matrices and embedding, the
    mixers' output projections divided by sqrt(2 x published layers)
    (``rescale_out_proj``); the
    recurrence's ``A`` log-uniform in [1, 16], its time steps log-uniform in
    [time_step_min, time_step_max] through the inverse softplus, ``D`` and
    the norm scales 1, the convolution as PyTorch's Conv1d starts, the
    router's balancing bias 0."""
    shapes = hybrid_param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = path[-1].key
        if name.endswith("norm") or name == "d_skip":
            leaf = jnp.ones(shape, _F32)
        elif name == "e_bias":
            leaf = jnp.zeros(shape, _F32)
        elif name == "a_log":
            leaf = jnp.log(jax.random.uniform(k, shape, _F32, 1.0, 16.0))
        elif name == "dt_bias":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, _F32, lo, hi)),
                             cfg.time_step_floor)
            leaf = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
        elif name in ("conv_w", "conv_b"):
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            leaf = jax.random.uniform(k, shape, _F32, -bound, bound)
        else:
            leaf = jax.random.normal(k, shape, _F32) * cfg.init_std
            if cfg.rescale_out_proj and name in _OUT_PROJ:
                leaf = leaf / math.sqrt(2.0 * cfg.n_layers_published)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def hybrid_param_specs(cfg: HybridConfig) -> Dict[str, Any]:
    """Every leaf replicated: the shares are the settings' (module docstring)."""
    return jax.tree.map(lambda _: P(), hybrid_param_shapes(cfg),
                        is_leaf=lambda s: isinstance(s, tuple))


# ------------------------------------------------------------------- mixers

def _gated_group_norm(y: Array, z: Array, w: Array, groups: int, eps: float) -> Array:
    """``RMSNorm(y * silu(z)) * w`` over each group's channels (the gate
    BEFORE the norm), in float32."""
    g = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).reshape(
        y.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(y.shape) * w


def _mamba_mixer(cfg: HybridConfig, lp, x: Array) -> Array:
    dt_ = cfg.dtype
    bsz, t, _ = x.shape
    hh, hp = cfg.mamba_heads_held, cfg.mamba_head_dim
    gh, n, inner = cfg.mamba_groups_held, cfg.ssm_state, cfg.mamba_inner
    z, xbc, dtr = jnp.split(x @ lp["w_in"].astype(dt_),
                            [inner, inner + cfg.conv_dim], axis=-1)
    with obs_trace.phase("ssd"):
        xbc = jax.nn.silu(causal_depthwise_conv(xbc, lp["conv_w"], lp["conv_b"]))
        xs, b, c = jnp.split(xbc, [inner, inner + gh * n], axis=-1)
        y = ssd_chunked_scan(
            xs.reshape(bsz, t, hh, hp),
            jax.nn.softplus(dtr.astype(_F32) + lp["dt_bias"]),
            -jnp.exp(lp["a_log"]), b.reshape(bsz, t, gh, n),
            c.reshape(bsz, t, gh, n), lp["d_skip"], cfg.chunk)
    y = _gated_group_norm(y.reshape(bsz, t, inner), z, lp["gate_norm"], gh,
                          cfg.norm_eps).astype(dt_)
    return y @ lp["w_out"].astype(dt_)


def _attention_mixer(cfg: HybridConfig, lp, x: Array) -> Array:
    dt_ = cfg.dtype
    bsz, t, _ = x.shape
    heads = lambda w: (x @ lp[w].astype(dt_)).reshape(
        bsz, t, -1, cfg.head_dim).transpose(0, 2, 1, 3)
    with obs_trace.phase("attn"):
        o = ring_attention(heads("wq"), heads("wk"), heads("wv"))
    return o.transpose(0, 2, 1, 3).reshape(bsz, t, -1) @ lp["wo"].astype(dt_)


def _rotate(x: Array, cos: Array, sin: Array) -> Array:
    """``x`` [B, T, H, D]: the first ``2 * cos.shape[-1]`` channels turned in
    the pairs (c, c + half), in float32; the rest as they are."""
    half = cos.shape[-1]
    xf = x.astype(_F32)
    x1, x2 = xf[..., :half], xf[..., half:2 * half]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., 2 * half:]],
        axis=-1).astype(x.dtype)


def _gated_attention_mixer(cfg: HybridConfig, kind: str, lp, x: Array) -> Array:
    """The ``laguna`` attention sublayer: per-head RMSNorm of q and k, rotary
    embedding, grouped keys and values, the whole sequence (F) or a window
    (W), and a sigmoid gate a head from the sublayer's input."""
    dt_ = cfg.dtype
    bsz, t, _ = x.shape
    rot, window = ((cfg.rotary_full, None) if kind == "F"
                   else (cfg.rotary_window, cfg.window))
    heads = lambda w: (x @ lp[w].astype(dt_)).reshape(bsz, t, -1, cfg.head_dim)
    cos, sin = rotary_tables(rot, t)
    q = _rotate(_rms_norm(heads("wq"), lp["q_norm"], cfg.norm_eps), cos, sin)
    k = _rotate(_rms_norm(heads("wk"), lp["k_norm"], cfg.norm_eps), cos, sin)
    by_head = lambda y: y.transpose(0, 2, 1, 3)
    with obs_trace.phase("attn" if window is None else "attn_window"):
        o = ring_attention(by_head(q), by_head(k), by_head(heads("wv")),
                           window=window)
    gate = jax.nn.sigmoid((x @ lp["w_head_gate"].astype(dt_)).astype(_F32))
    o = (by_head(o) * gate[..., None]).astype(dt_)
    return o.reshape(bsz, t, -1) @ lp["wo"].astype(dt_)


def _swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array, dtype) -> Array:
    return (jax.nn.silu(x @ w_gate.astype(dtype)) * (x @ w_up.astype(dtype))
            ) @ w_down.astype(dtype)


def route(cfg: HybridConfig, lp, x: Array) -> Tuple[Array, Array]:
    """``x`` [N, D] -> (ids [N, top_k] of the chosen experts among all the
    routed ones, their weights [N, top_k] float32): sigmoid scores in
    float32, the choice by score plus the balancing bias where the layer has
    one (a buffer: no gradient), the weights the chosen scores normalised and
    scaled."""
    logits = jnp.dot(x.astype(_F32), lp["router"], precision=_HIGHEST)
    scores = jax.nn.sigmoid(logits)
    if "e_bias" in lp:
        scores = scores + jax.lax.stop_gradient(lp["e_bias"])
    _, idx = jax.lax.top_k(scores, cfg.top_k)
    # the chosen scores from the chosen LOGITS (the same function of the same
    # numbers as a gather from the scores): the backward then reads the ids
    # and top_k logits a token, and nothing over all the experts
    idx = checkpoint_name(idx, "route_ids")
    chosen = jax.nn.sigmoid(checkpoint_name(
        jnp.take_along_axis(logits, idx, axis=-1), "route_logits"))
    return idx, cfg.routed_scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def dispatch(cfg: HybridConfig, idx: Array, w: Array):
    """The held experts' rows from the router's choices: ``wts`` [N, E_held]
    (a token's weight on each held expert, 0 where not chosen), ``order``
    [E_held * N] (a stable sort: the chosen (expert, token) pairs first,
    expert by expert, as ``expert * N + token``) and ``counts`` [E_held]."""
    held = (idx - cfg.first_expert)[:, :, None] == jnp.arange(cfg.experts_held)
    hit = jnp.any(held, axis=1)                                   # [N, E_held]
    wts = jnp.sum(jnp.where(held, w[:, :, None], 0.0), axis=1)
    order = jnp.argsort(~hit.T.reshape(-1), stable=True).astype(jnp.int32)
    return (wts, checkpoint_name(order, "route_order"),
            checkpoint_name(jnp.sum(hit, axis=0, dtype=jnp.int32), "route_counts"))


# Rows a step of the grouped product takes.  At uniform routing an expert's
# rows (tokens x top_k / experts: 352 at 8,192 tokens, 22 of 512) fill one
# tile, and a tile pays once for what does not depend on its rows: two slices
# of the expert's weights and the read-modify-write of its two float32
# gradients (44 MB).
EXPERT_TILE = 512


def _tile_layout(counts, tile: int):
    """Every expert's rows start on a tile: (the tile after each expert's
    last, each expert's first tile, its first place in ``order``)."""
    tiles = (counts + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    return ends, ends - tiles, jnp.cumsum(counts) - counts


def _tile_rows(t, layout, order, counts, tile: int, n: int):
    """Tile ``t`` of the expert-by-expert layout: (its expert, its rows'
    tokens [tile]).  The stable sort left an expert's tokens ascending, and
    a row that does not exist gets a place past the last token, so the ids
    are sorted, unique and, where no row is, out of range: a gather fills
    such a row with zeros and a scatter drops it (:func:`_rows`,
    :func:`_add_rows`), with no serial pass over possible duplicates."""
    ends, first_tile, first_row = layout
    e = jnp.minimum(jnp.sum(t >= ends), counts.shape[0] - 1)
    r = (t - first_tile[e]) * tile + jnp.arange(tile)
    valid = r < counts[e]
    pair = order[jnp.where(valid, first_row[e] + r, 0)]
    return e, jnp.where(valid, pair - e * n, n + r)


def _rows(x: Array, tok: Array) -> Array:
    return x.at[tok].get(mode="fill", fill_value=0, indices_are_sorted=True,
                         unique_indices=True)


def _add_rows(x: Array, tok: Array, rows: Array) -> Array:
    return x.at[tok].add(rows.reshape((-1,) + x.shape[1:]), mode="drop",
                         indices_are_sorted=True, unique_indices=True)


# Rows wider than this are scatter-added as [width / ROW_WINDOW, ROW_WINDOW]
# windows into an accumulator carried in that shape.  On the chip a tile of
# 512 float32 rows of 2,048 added into [16384, 2048] takes 1,213 us, into
# [16384, 16, 128] 66 us (a token's row is then whole (8, 128) tiles, 8 KB in
# one piece, where in two dimensions it is one sublane of each of 16 tiles);
# 1,024-wide rows into [8192, 1024] take 78 us as they are and keep that form
# (PERF.md, PR 41).  Gathers of rows do not have the cliff (23-31 us either way).
ROW_SCATTER_MAX = 1024
ROW_WINDOW = 128


def _accumulator_shape(shape: Tuple[int, int]) -> Tuple[int, ...]:
    n, width = shape
    if width > ROW_SCATTER_MAX and width % ROW_WINDOW == 0:
        return (n, width // ROW_WINDOW, ROW_WINDOW)
    return (n, width)


def _compute_copies(ws, dtype):
    """The experts' weights in the compute type, made ONCE before the loop
    over tiles: without the barrier the compiler sinks the casts into the
    loop and converts an expert's 11 MB of float32 again for every tile."""
    return jax.lax.optimization_barrier(tuple(w.astype(dtype) for w in ws))


def _expert_hidden(rows, wb, e, dtype, gated: bool):
    """One tile through an expert's first product(s): (what the backward
    needs of it, the hidden rows).  Squared ReLU: ``relu(rows W1)``, its
    square.  Gated: ``silu(rows Wg) * (rows Wu)`` and both pre-activations."""
    pre = jnp.dot(rows, wb[0][e], preferred_element_type=_F32).astype(dtype)
    if not gated:
        r = jax.nn.relu(pre)
        return r, r * r
    up = jnp.dot(rows, wb[1][e], preferred_element_type=_F32).astype(dtype)
    return (pre, up), jax.nn.silu(pre) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(u: Array, ws: Tuple[Array, ...], wts: Array, order: Array,
             counts: Array, tile: int, gated: bool) -> Array:
    return _ge_fwd(u, ws, wts, order, counts, tile, gated)[0]


def grouped_experts(u: Array, w1: Array, w2: Array, wts: Array, order: Array,
                    counts: Array, tile: int) -> Array:
    """``sum_e wts[n, e] relu(u_n W1_e)^2 W2_e`` over the held experts a token
    chose, [N, latent] float32.  ``u`` [N, latent] in the compute type,
    ``w1`` [E, latent, F] and ``w2`` [E, F, latent] the float32 masters (their
    gradients leave in float32); the routing as :func:`dispatch` gives it.
    A loop over the occupied tiles: a token's row is computed once for every
    held expert it chose, however many chose one expert."""
    return _grouped(u, (w1, w2), wts, order, counts, tile, False)


def gated_experts(u: Array, wg: Array, wu: Array, wd: Array, wts: Array,
                  order: Array, counts: Array, tile: int) -> Array:
    """:func:`grouped_experts` with ``(silu(u_n Wg_e) * (u_n Wu_e)) Wd_e`` an
    expert: ``wg``, ``wu`` [E, width, F] and ``wd`` [E, F, width]."""
    return _grouped(u, (wg, wu, wd), wts, order, counts, tile, True)


def _ge_fwd(u, ws, wts, order, counts, tile, gated):
    n, dtype = u.shape[0], u.dtype
    wb = _compute_copies(ws, dtype)
    layout, by_expert = _tile_layout(counts, tile), wts.T

    def body(t, acc):
        with obs_trace.phase("moe_dispatch"):
            e, tok = _tile_rows(t, layout, order, counts, tile, n)
            rw, rows = _rows(by_expert[e], tok), _rows(u, tok)
        _, hidden = _expert_hidden(rows, wb, e, dtype, gated)
        y = jnp.dot(hidden, wb[-1][e], preferred_element_type=_F32)
        with obs_trace.phase("moe_dispatch"):
            return _add_rows(acc, tok, rw[:, None] * y)

    acc = jax.lax.fori_loop(
        0, layout[0][-1], body,
        varying_like(jnp.zeros(_accumulator_shape(u.shape), _F32), u, *ws, wts, order))
    return acc.reshape(u.shape), (u, ws, wts, order, counts)


def _ge_bwd(tile, gated, res, g):
    u, ws, wts, order, counts = res
    n, dtype = u.shape[0], u.dtype
    wb = _compute_copies(ws, dtype)
    layout, by_expert = _tile_layout(counts, tile), wts.T
    add = lambda dw, e, a, b: dw.at[e].add(
        jnp.dot(a.T, b, preferred_element_type=_F32))
    back = lambda a, w: jnp.dot(a, w.T, preferred_element_type=_F32)

    def body(t, carry):
        du, dws, dwts = carry                      # dwts expert-major, [E, N]
        with obs_trace.phase("moe_dispatch"):
            e, tok = _tile_rows(t, layout, order, counts, tile, n)
            rw, rows, gy = _rows(by_expert[e], tok), _rows(u, tok), _rows(g, tok)
        kept, hidden = _expert_hidden(rows, wb, e, dtype, gated)
        y = jnp.dot(hidden, wb[-1][e], preferred_element_type=_F32)
        drw = jnp.sum(gy * y, axis=-1)             # 0 on the rows that do not exist
        dy = (gy * rw[:, None]).astype(dtype)
        d_last = add(dws[-1], e, hidden, dy)
        dh = back(dy, wb[-1][e])
        if not gated:
            dpre = (2.0 * dh * kept).astype(dtype)  # relu' is in r
            dws = (add(dws[0], e, rows, dpre), d_last)
            drows = back(dpre, wb[0][e])
        else:
            pre, up = (x.astype(_F32) for x in kept)
            sig = jax.nn.sigmoid(pre)
            dpre = (dh * up * sig * (1.0 + pre * (1.0 - sig))).astype(dtype)
            dup = (dh * pre * sig).astype(dtype)
            dws = (add(dws[0], e, rows, dpre), add(dws[1], e, rows, dup), d_last)
            drows = back(dpre, wb[0][e]) + back(dup, wb[1][e])
        with obs_trace.phase("moe_dispatch"):
            return (_add_rows(du, tok, drows), dws,
                    dwts.at[e].set(_add_rows(dwts[e], tok, drw)))

    zeros = (jnp.zeros(_accumulator_shape(u.shape), _F32),
             tuple(jnp.zeros(w.shape, _F32) for w in ws),
             jnp.zeros(by_expert.shape, _F32))
    du, dws, dwts = jax.lax.fori_loop(
        0, layout[0][-1], body, varying_like(zeros, u, *ws, wts, order, g))
    return du.reshape(u.shape).astype(dtype), dws, dwts.T, None, None


_grouped.defvjp(_ge_fwd, _ge_bwd)


def _moe_mixer(cfg: HybridConfig, lp, x: Array) -> Tuple[Array, Dict[str, Array]]:
    dt_ = cfg.dtype
    bsz, t, d = x.shape
    x2 = x.reshape(bsz * t, d)
    with obs_trace.phase("moe_dispatch"):
        idx, w = route(cfg, lp, x2)
        wts, order, counts = dispatch(cfg, idx, w)
    u = x2 @ lp["w_down_lat"].astype(dt_) if cfg.moe_latent else x2
    with obs_trace.phase("experts"):
        # kept across the layer's checkpoint: the backward's recomputation
        # of the layer does not run the loop over tiles again
        if cfg.moe_gated:
            routed = gated_experts(u, lp["wg"], lp["wu"], lp["wd"], wts, order,
                                   counts, EXPERT_TILE)
        else:
            routed = grouped_experts(u, lp["w1"], lp["w2"], wts, order, counts,
                                     EXPERT_TILE)
        routed = checkpoint_name(routed, "routed")
    out = routed.astype(dt_)
    if cfg.moe_latent:
        out = out @ lp["w_up_lat"].astype(dt_)
    if cfg.moe_gated:
        shared = _swiglu(x2, lp["ws_gate"], lp["ws_up"], lp["ws_down"], dt_)
    else:
        shared = jnp.square(jax.nn.relu(x2 @ lp["ws1"].astype(dt_))) @ lp["ws2"].astype(dt_)
    stats = {"rows": counts.astype(_F32),
             "mass": jnp.mean(jnp.sum(wts, axis=-1))}
    return (out + shared).reshape(bsz, t, d), stats


def _layer(cfg: HybridConfig, kind: str, lp, h: Array):
    """``h + Mixer(RMSNorm(h))`` and, of an expert layer, its routing's
    numbers: the rows each held expert received and the routed weight mass
    kept (a token's weights on the held experts it chose, summed; the mean)."""
    x = _rms_norm(h, lp["norm"], cfg.norm_eps)
    if kind == "M":
        with obs_trace.phase("ssm"):
            return h + _mamba_mixer(cfg, lp, x), {}
    if kind == "*":
        return h + _attention_mixer(cfg, lp, x), {}
    if kind in ("F", "W"):
        return h + _gated_attention_mixer(cfg, kind, lp, x), {}
    if kind == "D":
        with obs_trace.phase("mlp"):
            return h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                               cfg.dtype), {}
    with obs_trace.phase("moe"):
        out, stats = _moe_mixer(cfg, lp, x)
        return h + out, stats


#: what an expert layer keeps across its checkpoint, by `checkpoint_name`
_KEPT = ("routed", "route_ids", "route_logits", "route_order", "route_counts")


def _run_layers(cfg: HybridConfig, pattern: str, layers, h: Array):
    # every layer rematerialised in the backward: none of a layer's [L, L]
    # blocks or projections outlives its own backward; of an expert layer
    # the routed sum is kept (33 MB at 8,192 tokens) and the routing that
    # made it (ids, chosen logits, order, counts: 1.7 MB), so the second run
    # has no router product, no top_k and no sort
    layer = jax.checkpoint(
        _layer, static_argnums=(0, 1),
        policy=jax.checkpoint_policies.save_only_these_names(*_KEPT))
    stats = []
    for kind, lp in zip(pattern, layers):
        h, st = layer(cfg, kind, lp, h)
        if st:
            stats.append(st)
    return h, stats


def apply_hybrid(cfg: HybridConfig, params, tokens: Array,
                 next_tokens: Optional[Array] = None):
    """``tokens`` [B, T] -> the trunk's final-normed hidden states [B, T, D]
    and the expert layers' routing numbers; with ``next_tokens`` (the ids one
    to the right) the MTP module's final-normed hidden states too, stacked
    [2, B, T, D]: position i of the second predicts token i + 2.  The head is
    the loss's (:func:`hybrid_loss`), which never makes whole logits."""
    if "M" in cfg.pattern + cfg.mtp_pattern and tokens.shape[1] % cfg.chunk:
        raise ValueError(f"{tokens.shape[1]} tokens are not a whole number of "
                         f"the scan's chunks of {cfg.chunk}")
    dt_ = cfg.dtype
    embed = params["embed"].astype(dt_)
    with obs_trace.phase("stack"):
        h, stats = _run_layers(cfg, cfg.pattern, params["layers"], embed[tokens])
        hf = _rms_norm(h, params["final_norm"], cfg.norm_eps)
    if next_tokens is None or not cfg.mtp_pattern:
        return hf, stats
    mp = params["mtp"]
    with obs_trace.phase("mtp"):
        g = jnp.concatenate(
            [_rms_norm(embed[next_tokens], mp["embed_norm"], cfg.norm_eps),
             _rms_norm(hf, mp["hidden_norm"], cfg.norm_eps)],
            axis=-1) @ mp["w_eh"].astype(dt_)
        g, mstats = _run_layers(cfg, cfg.mtp_pattern, mp["layers"], g)
        hm = _rms_norm(g, mp["final_norm"], cfg.norm_eps)
    return jnp.stack([hf, hm]), stats + mstats


def hybrid_loss(cfg: HybridConfig, params, x: Array, y: Array):
    """``(loss, loss, aux)`` for inputs ``x`` and next tokens ``y`` [B, T]:
    mean CE(trunk, y_i) + mtp_loss_weight x mean over the T - 1 positions that
    have one of CE(MTP, y_{i+1}), both through one fused head over the
    stacked hidden states; without an MTP module (``mtp_pattern`` empty) the
    first term alone.  ``aux``: the mean losses, and of every expert
    layer the rows each held expert received and the weight mass kept (with
    every expert held, ``routed_scale``)."""
    hs, stats = apply_hybrid(cfg, params, x, next_tokens=y)
    if cfg.mtp_pattern:
        ys = jnp.stack([y, jnp.roll(y, -1, axis=1)])
    else:                     # the trunk alone, through the same fused head
        hs, ys = hs[None], y[None]
    with obs_trace.phase("head_xent"):
        nll = fused_head_xent_tokens(hs, params["lm_head"].astype(cfg.dtype), ys)
    loss = lm = jnp.mean(nll[0])
    losses = [lm]
    if cfg.mtp_pattern:
        mtp = jnp.mean(nll[1][:, :-1])
        loss, losses = lm + cfg.mtp_loss_weight * mtp, [lm, mtp]
    aux = {"loss": jnp.stack(losses),
           "expert_rows": jnp.stack([s["rows"] for s in stats]),
           "route_mass": jnp.stack([s["mass"] for s in stats])}
    return loss, loss, aux
