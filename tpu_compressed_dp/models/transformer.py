"""Llama-family decoder-only transformer, written for manual mesh parallelism.

Net-new model family relative to the reference (its zoo is CNNs: ResNet-9 /
AlexNet / VGG-16 / ResNet-50, SURVEY.md §2) — required by the BASELINE.json
stretch config "Llama-3-8B pretrain — entire-model Top-K grad compression
over ICI".  Architecture: RMSNorm pre-norm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, untied LM head.

One decoder, by settings of :class:`LlamaConfig`: the Llama family above;
Switch-style routed experts (``n_experts``); and looped language models
(Ouro, arXiv:2510.25741): ``n_passes`` runs of the whole stack over its own
output with one set of weights (a ``lax.scan`` over passes, so the compiled
step holds one stack's layer bodies and each weight's gradient sums over
its uses in the backward scan's carry), ``sandwich_norm`` (a second RMSNorm
on each branch's output, four a block), and ``exit_gate`` (a per-token
sigmoid gate after every pass, from which :func:`exit_weighted_loss` makes
the exit distribution that weights the passes' cross-entropies).  One pass
with neither is the plain decoder, bit for bit.

The fused head (the LM head's product and the softmax cross-entropy without
the [N, V] logits in HBM) has two entries.  A loss that is a weighted sum of
token cross-entropies whose weights exist before the head runs (the looped
model's: its exit distribution comes from the gate, not from the head)
takes :func:`fused_head_xent_wsum`: blocked over rows, its forward makes
``dh`` and ``dW`` from the one set of logits, three products of [N, D, V] a
step.  Any other caller (a per-token cotangent that is known only in the
backward: the mean over tokens behind further arithmetic, a second loss on
the same tokens) takes :func:`fused_head_xent_tokens`: blocked over the
vocabulary, it recomputes each chunk's logits in the backward, four products.

Parallelism design (TPU-first, megatron-style over a named mesh):
  * ``tensor`` axis — attention heads and MLP hidden are column-sharded, the
    output projections row-sharded (one ``psum`` each per layer); the LM head
    is vocab-sharded and the loss is computed vocab-parallel (no logit
    all-gather ever materialises the [B, T, V] tensor).
  * ``seq`` axis — activations are sequence-sharded; attention runs as a
    ring over the axis (:mod:`tpu_compressed_dp.ops.ring_attention`).
  * ``data`` axis — batch sharding; gradient sync (with compression) psums
    over data x seq, handled by the train step, not the model.

``apply`` is written as per-device code: it works unsharded (axis names
``None``) and inside ``shard_map`` (axis names set), so a single-device run,
a test on the virtual CPU mesh, and a pod run share one implementation.
Parameters are a plain nested dict with a parallel tree of
``PartitionSpec``s from :func:`param_specs`.

A stack whose layers each hold one mixer alone (a Mamba-2 mixer, attention
without position embedding, or a drop-less latent expert layer, by a per-layer
pattern, with a multi-token-prediction module) is another module,
:mod:`tpu_compressed_dp.models.hybrid`; it trains through the same LM step.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.ops.ring_attention import ring_attention
from tpu_compressed_dp.ops.ssd import varying_like

Array = jax.Array

__all__ = ["LlamaConfig", "llama3_8b", "ouro_2p6b", "tiny_llama", "init_llama",
           "param_specs", "apply_llama", "vocab_parallel_xent",
           "vocab_parallel_xent_tokens",
           "fused_head_xent", "fused_head_xent_tokens", "fused_head_xent_wsum",
           "exit_log_probs", "exit_distribution", "exit_stats",
           "exit_weighted_loss"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_hidden: Optional[int] = None  # default: SwiGLU 8/3 * dim rounded to 256
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # Mixture-of-experts (0 = dense FFN everywhere).  Experts shard over the
    # tensor axis: activations are replicated across it in this layout, so
    # expert-parallel dispatch needs no all_to_all — each tensor rank runs
    # its local experts on all tokens (Switch-style top-1, fixed capacity)
    # and one psum combines.
    n_experts: int = 0
    moe_every: int = 2            # MoE FFN on every k-th layer (1 = all)
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # load-balance aux loss weight
    # rematerialise each layer in backward (jax.checkpoint): activation
    # memory drops from O(L) to O(1) layers at ~1/3 extra FLOPs — the knob
    # that buys long-context training headroom
    remat: bool = False
    # Looped language model (module docstring): the stack and the final norm
    # run n_passes times with tied weights, each pass on the normed output
    # of the one before; sandwich_norm adds the post-branch RMSNorms;
    # exit_gate adds the per-token exit gate, whose distribution over the
    # passes weights their losses (exit_beta: weight of its entropy bonus)
    n_passes: int = 1
    sandwich_norm: bool = False
    exit_gate: bool = False
    exit_beta: float = 0.1
    init_std: Optional[float] = None  # None: 1/sqrt(fan_in) matrices

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn(self) -> int:
        if self.ffn_hidden is not None:
            return self.ffn_hidden
        h = int(8 * self.dim / 3)
        return ((h + 255) // 256) * 256

    def validate_mesh(self, tensor_size: int) -> None:
        if self.n_kv_heads % tensor_size or self.n_heads % tensor_size:
            raise ValueError(
                f"heads ({self.n_heads}/{self.n_kv_heads}) must divide by "
                f"tensor axis size {tensor_size}"
            )
        if self.ffn % tensor_size or self.vocab_size % tensor_size:
            raise ValueError(
                f"ffn ({self.ffn}) and vocab ({self.vocab_size}) must divide "
                f"by tensor axis size {tensor_size}"
            )
        if self.n_experts and self.n_experts % tensor_size:
            raise ValueError(
                f"n_experts ({self.n_experts}) must divide by tensor axis "
                f"size {tensor_size}"
            )

    def is_moe_layer(self, i: int) -> bool:
        return bool(self.n_experts) and (i % max(self.moe_every, 1) ==
                                         max(self.moe_every, 1) - 1)

    # What the LM step (train/lm_step.py) asks of a model's settings; the
    # hybrid decoder's (models/hybrid.py) answer the same four and bring
    # their loss too, where this decoder's is the step module's ``llama_loss``.
    def init(self, key: Array) -> Dict[str, Any]:
        return init_llama(self, key)

    def param_specs(self) -> Dict[str, Any]:
        return param_specs(self)

    def init_aux(self) -> Dict[str, Array]:
        """The state's auxiliary slot: a config with an exit gate keeps its
        last step's per-pass losses, mean exit masses and exit entropy
        there; every other config keeps nothing."""
        if not self.exit_gate:
            return {}
        return {"pass_loss": jnp.zeros((self.n_passes,), jnp.float32),
                "exit_mass": jnp.zeros((self.n_passes,), jnp.float32),
                "exit_entropy": jnp.zeros((), jnp.float32)}

    def aux_metrics(self, aux: Dict[str, Array]) -> Dict[str, Array]:
        """A looped model's per-pass numbers as step metrics."""
        metrics = {}
        for r in range(self.n_passes if aux else 0):
            metrics[f"loss/pass{r + 1}"] = aux["pass_loss"][r]
            metrics[f"model/exit_mass{r + 1}"] = aux["exit_mass"][r]
        if aux:
            metrics["model/exit_entropy"] = aux["exit_entropy"]
        return metrics


def llama3_8b() -> LlamaConfig:
    """The BASELINE.json stretch target."""
    return LlamaConfig(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                       n_kv_heads=8, ffn_hidden=14336, rope_theta=500000.0)


def ouro_2p6b() -> LlamaConfig:
    """Ouro-2.6B (huggingface.co/ByteDance/Ouro-2.6B config.json): 48 layers
    run four times with tied weights, 16 heads of 128, SwiGLU 5632.  Four
    passes deep over one pass of parameters, so remat is not a knob here."""
    return LlamaConfig(vocab_size=49152, dim=2048, n_layers=48, n_heads=16,
                       n_kv_heads=16, ffn_hidden=5632, rope_theta=1e6,
                       norm_eps=1e-6, remat=True, n_passes=4,
                       sandwich_norm=True, exit_gate=True, init_std=0.02)


def tiny_llama(vocab: int = 256, dim: int = 64, layers: int = 2) -> LlamaConfig:
    """Smoke/test scale."""
    return LlamaConfig(vocab_size=vocab, dim=dim, n_layers=layers, n_heads=4,
                       n_kv_heads=2, ffn_hidden=128)


def init_llama(cfg: LlamaConfig, key: Array) -> Dict[str, Any]:
    """fp32 master parameters (cast to ``cfg.dtype`` at use)."""
    def dense(key, fan_in, shape):
        w = jax.random.normal(key, shape, jnp.float32)
        if cfg.init_std is not None:
            return w * cfg.init_std
        return w / math.sqrt(fan_in)

    keys = jax.random.split(key, cfg.n_layers + 3)
    hd = cfg.head_dim
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 8)
        layer = {
            "attn_norm": jnp.ones((cfg.dim,), jnp.float32),
            "wq": dense(k[0], cfg.dim, (cfg.dim, cfg.n_heads * hd)),
            "wk": dense(k[1], cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
            "wv": dense(k[2], cfg.dim, (cfg.dim, cfg.n_kv_heads * hd)),
            "wo": dense(k[3], cfg.n_heads * hd, (cfg.n_heads * hd, cfg.dim)),
            "mlp_norm": jnp.ones((cfg.dim,), jnp.float32),
        }
        if cfg.sandwich_norm:
            layer["attn_post_norm"] = jnp.ones((cfg.dim,), jnp.float32)
            layer["mlp_post_norm"] = jnp.ones((cfg.dim,), jnp.float32)
        if cfg.is_moe_layer(i):
            e = cfg.n_experts
            layer.update({
                "router": dense(k[7], cfg.dim, (cfg.dim, e)),
                "w_gate": dense(k[4], cfg.dim, (e, cfg.dim, cfg.ffn)),
                "w_up": dense(k[5], cfg.dim, (e, cfg.dim, cfg.ffn)),
                "w_down": dense(k[6], cfg.ffn, (e, cfg.ffn, cfg.dim)),
            })
        else:
            layer.update({
                "w_gate": dense(k[4], cfg.dim, (cfg.dim, cfg.ffn)),
                "w_up": dense(k[5], cfg.dim, (cfg.dim, cfg.ffn)),
                "w_down": dense(k[6], cfg.ffn, (cfg.ffn, cfg.dim)),
            })
        layers.append(layer)
    params = {
        "embed": jax.random.normal(keys[-3], (cfg.vocab_size, cfg.dim), jnp.float32) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": dense(keys[-2], cfg.dim, (cfg.dim, cfg.vocab_size)),
    }
    if cfg.exit_gate:
        params["exit_gate"] = {"w": dense(keys[-1], cfg.dim, (cfg.dim,)),
                               "b": jnp.zeros((1,), jnp.float32)}
    return params


def param_specs(cfg: LlamaConfig, tensor_axis: str = "tensor") -> Dict[str, Any]:
    """PartitionSpec tree matching :func:`init_llama`'s structure.

    Column-parallel: qkv, gate/up, lm_head (output dim over tensor);
    row-parallel: wo, w_down (input dim over tensor); everything else
    replicated.  No ``data``/``seq`` entries: params are replicated across
    those axes (their grads are what the compressed sync reduces).
    """
    t = tensor_axis
    layers = []
    for i in range(cfg.n_layers):
        layer = {
            "attn_norm": P(), "mlp_norm": P(),
            "wq": P(None, t), "wk": P(None, t), "wv": P(None, t),
            "wo": P(t, None),
        }
        if cfg.sandwich_norm:
            layer.update({"attn_post_norm": P(), "mlp_post_norm": P()})
        if cfg.is_moe_layer(i):
            # expert parallelism: the leading expert dim shards over the
            # tensor axis (router replicated — every rank routes all tokens)
            layer.update({
                "router": P(),
                "w_gate": P(t, None, None), "w_up": P(t, None, None),
                "w_down": P(t, None, None),
            })
        else:
            layer.update({
                "w_gate": P(None, t), "w_up": P(None, t),
                "w_down": P(t, None),
            })
        layers.append(layer)
    specs = {
        "embed": P(),
        "layers": layers,
        "final_norm": P(),
        "lm_head": P(None, t),
    }
    if cfg.exit_gate:
        specs["exit_gate"] = {"w": P(), "b": P()}
    return specs


def _rms_norm(x: Array, w: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def _rope(x: Array, pos: Array, theta: float) -> Array:
    """Rotary embedding; x: [B, H, T, D], pos: [T] global positions."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]  # [T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _psum_if(x: Array, axis: Optional[str]) -> Array:
    return jax.lax.psum(x, axis) if axis is not None else x


def _moe_ffn(cfg: LlamaConfig, lp: Dict[str, Any], x: Array,
             tensor_axis: Optional[str]) -> Tuple[Array, Array]:
    """Switch-style top-1 MoE FFN, experts sharded over the tensor axis.

    Activations are replicated across the tensor axis in this layout, so
    expert parallelism needs no all_to_all: every rank routes all tokens
    (replicated router), dispatches them into its *local* experts' fixed
    ``capacity`` slots via one-hot einsums (static shapes), and the combined
    outputs psum across the axis.  Tokens over capacity fall through to the
    residual stream (Switch semantics).  Capacity is per (data, seq) shard —
    each worker's local tokens compete for ``ceil(local_tokens/E * cf)``
    slots, so drop patterns depend on the mesh (as in any expert-parallel
    system); results equal the unsharded layer exactly in the drop-free
    regime (``cf >= E``).  Returns (out, load-balance aux).
    """
    dt = cfg.dtype
    b, t, d = x.shape
    n = b * t
    e = cfg.n_experts
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(
        (xf @ lp["router"].astype(dt)).astype(jnp.float32), axis=-1)  # [N, E]
    top = jnp.argmax(probs, axis=-1)
    top_p = jnp.max(probs, axis=-1)
    onehot = jax.nn.one_hot(top, e, dtype=jnp.float32)
    # load-balance aux (Switch Transformer eq. 4): E * sum_e f_e * P_e
    aux = e * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))

    cap = max(int(math.ceil(n / e * cfg.capacity_factor)), 1)
    pos = jnp.cumsum(onehot, axis=0) * onehot            # 1-based queue rank
    within = (pos > 0) & (pos <= cap)
    disp = (within[..., None] &
            (pos[..., None] == (1.0 + jnp.arange(cap))[None, None, :])
            ).astype(dt)                                  # [N, E, cap]
    combine = disp * top_p[:, None, None].astype(dt)

    if tensor_axis is not None:
        e_local = lp["w_gate"].shape[0]  # static: the local shard size
        off = jax.lax.axis_index(tensor_axis) * e_local
        disp = jax.lax.dynamic_slice_in_dim(disp, off, e_local, axis=1)
        combine = jax.lax.dynamic_slice_in_dim(combine, off, e_local, axis=1)

    xe = jnp.einsum("nec,nd->ecd", disp, xf)             # [E_l, cap, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, lp["w_up"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", h, lp["w_down"].astype(dt))
    out = _psum_if(jnp.einsum("ecd,nec->nd", ye, combine), tensor_axis)
    return out.reshape(b, t, d), aux


def apply_llama(
    cfg: LlamaConfig,
    params: Dict[str, Any],
    tokens: Array,
    *,
    tensor_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
    with_aux: bool = False,
    return_hidden: bool = False,
    all_passes: bool = False,
):
    """Per-device forward: ``tokens`` [B_local, T_local] -> logits
    [B_local, T_local, V_local] (vocab-sharded when ``tensor_axis`` is set).

    Feed the result to :func:`vocab_parallel_xent`; an explicit logit
    all-gather is deliberately not offered (a [B,T,V] global tensor is the
    thing this layout exists to avoid).  With ``with_aux`` the return is
    ``(logits, aux)`` where aux is the mean MoE load-balance loss (0.0 for
    dense configs).  ``return_hidden`` skips the head and yields the
    final-normed hidden states instead of logits — the input
    :func:`fused_head_xent` wants (it owns the head matmul).

    A looped config (``cfg.n_passes`` > 1) returns its last pass's output:
    without an exit gate that pass is the model.  With ``all_passes`` the
    output gains a leading axis of ``n_passes`` and
    the return becomes ``(out, gate[, aux])``: ``gate`` [n_passes, B, T] are
    the exit gate's float32 logits (None without ``cfg.exit_gate``), the
    inputs of :func:`exit_weighted_loss`.
    """
    dt = cfg.dtype
    hd = cfg.head_dim

    if seq_axis is not None:
        t_local = tokens.shape[1]
        pos = jax.lax.axis_index(seq_axis) * t_local + jnp.arange(t_local)
    else:
        pos = jnp.arange(tokens.shape[1])

    h = params["embed"].astype(dt)[tokens]  # [B, T, D]

    def layer_fn(h, lp, is_moe):
        x = _rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q = (x @ lp["wq"].astype(dt))  # [B, T, Hl*hd] (heads tensor-local)
        k = (x @ lp["wk"].astype(dt))
        v = (x @ lp["wv"].astype(dt))
        b, t = x.shape[:2]
        q = q.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)  # [B, Hl, T, hd]
        k = k.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, -1, hd).transpose(0, 2, 1, 3)
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
        with obs_trace.phase("attn"):
            o = ring_attention(q, k, v, axis_name=seq_axis)  # [B, Hl, T, hd]
        o = o.transpose(0, 2, 1, 3).reshape(b, t, -1)
        attn_out = _psum_if(o @ lp["wo"].astype(dt), tensor_axis)  # row-parallel
        if cfg.sandwich_norm:
            attn_out = _rms_norm(attn_out, lp["attn_post_norm"], cfg.norm_eps)
        h = h + attn_out

        x = _rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        if is_moe:
            mlp_out, aux = _moe_ffn(cfg, lp, x, tensor_axis)
        else:
            gate = jax.nn.silu(x @ lp["w_gate"].astype(dt))
            up = x @ lp["w_up"].astype(dt)
            mlp_out = _psum_if((gate * up) @ lp["w_down"].astype(dt), tensor_axis)
            aux = jnp.zeros((), jnp.float32)
        if cfg.sandwich_norm:
            mlp_out = _rms_norm(mlp_out, lp["mlp_post_norm"], cfg.norm_eps)
        return h + mlp_out, aux

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, static_argnums=(2,))

    def stack_fn(h):
        """One pass: the layers in order, then the final norm."""
        aux_total = jnp.zeros((), jnp.float32)
        n_moe = 0
        for li, lp in enumerate(params["layers"]):
            is_moe = cfg.is_moe_layer(li)
            h, aux = layer_fn(h, lp, is_moe)
            if is_moe:
                aux_total = aux_total + aux
                n_moe += 1
        return (_rms_norm(h, params["final_norm"], cfg.norm_eps),
                aux_total / max(n_moe, 1))

    with obs_trace.phase("stack"):
        if cfg.n_passes == 1:
            h, aux = stack_fn(h)
            hs = h[None]
        else:
            # the weights are constants of the scan: its backward sums each
            # one's gradient over the passes in the carry
            def one_pass(h, _):
                h, aux = stack_fn(h)
                return h, (h, aux)

            h, (hs, auxs) = jax.lax.scan(one_pass, h, None,
                                        length=cfg.n_passes)
            aux = jnp.mean(auxs)

    out = hs if all_passes else h
    if not return_hidden:
        out = out @ params["lm_head"].astype(dt)  # [..., B, T, V_local]
    ret = (out,)
    if all_passes:
        gate = None
        if cfg.exit_gate:
            with obs_trace.phase("exit"):
                g = params["exit_gate"]
                gate = jnp.einsum("rbtd,d->rbt", hs.astype(jnp.float32),
                                  g["w"]) + g["b"]
        ret += (gate,)
    if with_aux:
        ret += (aux,)
    return ret if len(ret) > 1 else out


def exit_log_probs(gate: Array) -> Array:
    """Log of a looped model's exit distribution from its gate's logits
    ``gate`` [R, ...]: with ``lambda_r = sigmoid(gate_r)`` a token exits
    after pass r with probability ``p_r = lambda_r prod_{j<r} (1 -
    lambda_j)``, the last pass taking what is left (its own gate is not
    read).  Kept in logs, so a saturated gate gives 0 and not NaN."""
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate[:-1]), axis=0)  # log prod(1-l)
    zero = jnp.zeros_like(gate[:1])
    return (jnp.concatenate([zero, stayed], axis=0)
            + jnp.concatenate([jax.nn.log_sigmoid(gate[:-1]), zero], axis=0))


def exit_distribution(gate: Array):
    """``(p, plogp)`` of gate logits ``gate`` [R, ...] (float32): the exit
    distribution ``p`` [R, ...] of :func:`exit_log_probs` and each token's
    ``sum_r p_r log p_r`` [...], its entropy's negative."""
    log_p = exit_log_probs(gate)
    p = jnp.exp(log_p)
    return p, jnp.sum(p * log_p, axis=0)


def exit_stats(nll: Array, p: Array, plogp: Array) -> Dict[str, Array]:
    """What the looped step reports and keeps: every pass's mean
    cross-entropy, mean exit mass, and the mean entropy."""
    tokens = tuple(range(1, nll.ndim))
    return {"pass_loss": jnp.mean(nll, axis=tokens),
            "exit_mass": jnp.mean(p, axis=tokens),
            "exit_entropy": -jnp.mean(plogp)}


def exit_weighted_loss(nll: Array, gate: Array, beta: float):
    """The looped model's loss from its passes' per-token cross-entropies
    ``nll`` [R, ...] and gate logits ``gate`` [R, ...] (float32): per token
    ``sum_r p_r nll_r + beta sum_r p_r log p_r`` with ``p`` of
    :func:`exit_log_probs`, the expected loss less ``beta`` times the exit
    distribution's entropy; the mean over tokens.  Returns ``(loss,
    stats)`` with :func:`exit_stats`.  The fused step takes the same loss
    apart, ``sum(p / tokens * nll) + beta mean(plogp)``, the first term from
    :func:`fused_head_xent_wsum`."""
    p, plogp = exit_distribution(gate)
    return (jnp.mean(jnp.sum(p * nll, axis=0) + beta * plogp),
            exit_stats(nll, p, plogp))


# Fused head+xent defaults by SHAPE (r5).  Measured on chip:
#   * 125M / 32k vocab / seq 1024 (logits 0.5 GB): ~5% SLOWER than the
#     unfused chain (115.3k vs 120.8k tok/s) — XLA fuses the one-shot
#     logits+softmax-xent well and the scan adds recompute;
#   * llama3_8b shapes, 2 layers / 128k vocab / seq 8192 (logits 2.1 GB):
#     the unfused chain needs 21.9 GB HBM (OOM on a 16 GB v5e) while the
#     fused path runs at 14.4k tok/s / MFU 0.71 — the [N, V] logits and
#     AD's saved softmax inputs never materialise.
# So: auto-enable when the bf16 logits buffer would exceed 1 GiB (the
# crossover sits well below the OOM cliff and above the 5%-regret regime);
# TPU_CDP_FUSED_XENT=1/0 forces either way.  Numerics: slightly MORE
# precise than the unfused path at bf16 (fp32 logits inside the scan).
_FUSED_XENT = os.environ.get("TPU_CDP_FUSED_XENT", "")
_FUSED_XENT_AUTO_BYTES = 1 << 30


def use_fused_head_xent(n_tokens: int = 0, vocab: int = 0,
                        itemsize: int = 2) -> bool:
    """Whether the LM loss should take the fused chunked-logsumexp path.

    ``n_tokens``/``vocab`` are the per-worker logits dimensions at the call
    site (0 = unknown: auto resolves to off, preserving the pre-r5
    default for callers that cannot size the buffer); ``itemsize`` is the
    logits dtype width in bytes (``jnp.dtype(cfg.dtype).itemsize`` — fp32
    configs materialise a 2x larger buffer than the old hardcoded bf16
    estimate, so the crossover fired at twice the intended size, ADVICE r5).
    """
    if _FUSED_XENT in ("0", "1"):
        return _FUSED_XENT == "1"
    return n_tokens * vocab * itemsize > _FUSED_XENT_AUTO_BYTES


def _fhx_chunks(v_local: int, chunk: int):
    """(chunk_size, n_chunks, v_padded) — pad the vocab up to whole chunks
    (zero weight columns; masked to -inf in the running logsumexp)."""
    c = min(chunk, v_local)
    nc = -(-v_local // c)
    return c, nc, nc * c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_head_xent_tokens(h: Array, w: Array, targets: Array,
                           tensor_axis: Optional[str] = None,
                           chunk: int = 2048) -> Array:
    """Per-token next-token cross-entropy STRAIGHT from hidden states — the
    LM head matmul and the softmax-xent fused through a running logsumexp
    over vocab chunks, so the [N, V] logits (and AD's saved probabilities —
    at the r4 LM config ~0.5-1.5 GB/step of HBM traffic) never materialise.

    ``h`` [..., D], ``w`` [D, V_local] (vocab-sharded under
    ``tensor_axis``), ``targets`` [...] global ids; returns float32 losses
    shaped like ``targets``, so a caller can weight them token by token (the
    looped model's exit-weighted loss stacks its passes' hidden states into
    one call, and the head's weight gradient sums over them in the
    backward's float32 accumulation).  Numerically equal to the per-token
    terms of ``vocab_parallel_xent(h @ w, targets)`` (same max-shift, same
    psum structure); the hand-written VJP recomputes each chunk's logits in
    the backward (flash-attention discipline: trade one extra matmul pass
    for the activation storage).

    The entry for ANY cotangent: a chunk's probabilities need the row's
    whole logsumexp, known after the last chunk, so the chunks are walked
    twice and the head costs four products of [N, D, V].  A caller whose
    loss is ``sum(weights * nll)`` with weights that do not depend on the
    head takes :func:`fused_head_xent_wsum`, which costs three.
    """
    nll, _ = _fhx_fwd(h, w, targets, tensor_axis, chunk)
    return nll


def fused_head_xent(h: Array, w: Array, targets: Array,
                    tensor_axis: Optional[str] = None,
                    chunk: int = 2048) -> Array:
    """Mean of :func:`fused_head_xent_tokens` over the tokens."""
    return jnp.mean(fused_head_xent_tokens(h, w, targets, tensor_axis, chunk))


def _match_vma(ct: Array, primal: Array) -> Array:
    """A cotangent's varying mesh axes must match its primal's: wherever the
    primal is REPLICATED over an axis the computation varies on (h across
    the vocab-sharded tensor axis; lm_head across pipeline stages), the
    true cotangent is the SUM of the per-shard partials.  The unfused path
    gets these psums inserted automatically as transposes of the implicit
    pvary where replicated values meet varying operands; a custom VJP must
    place them by hand."""
    extra = tuple(sorted(jax.typeof(ct).vma - jax.typeof(primal).vma))
    return jax.lax.psum(ct, extra) if extra else ct


def _fhx_scan_stats(h2, w, targets1, off, v_local, c, nc):
    """Running (m, l, zt) over vocab chunks; w pre-padded to [D, nc*c]."""
    n = h2.shape[0]
    w3 = w.reshape(w.shape[0], nc, c)

    def body(carry, xs):
        m, l, zt = carry
        w_c, ci = xs
        z = jax.lax.dot_general(
            h2, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [N, c]
        col = ci * c + jnp.arange(c)
        z = jnp.where(col[None, :] < v_local, z, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(z, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(z - m_new[:, None]), axis=-1)
        lt = targets1 - off - ci * c
        # membership needs BOTH chunk bounds and this shard's true vocab:
        # a target owned by the next shard can alias into this shard's pad
        # window (lt in [0, c) but targets1 - off >= v_local), where the
        # masked -inf logit would poison zt through the psum
        in_chunk = (lt >= 0) & (lt < c) & (targets1 - off < v_local)
        zc = jnp.take_along_axis(
            z, jnp.clip(lt, 0, c - 1)[:, None], axis=-1)[:, 0]
        zt = zt + jnp.where(in_chunk, zc, 0.0)
        return (m_new, l, zt), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    # inside shard_map the body outputs are device-varying (they derive from
    # the varying h/w/targets — targets can vary on axes h does not, e.g.
    # pipe in the deferred-head uneven fallback); pcast the replicated init
    # so scan's carry types match
    vma = tuple(sorted(jax.typeof(h2).vma
                       | jax.typeof(w).vma
                       | jax.typeof(targets1).vma))
    if vma:
        init = tuple(jax.lax.pcast(v, vma, to="varying") for v in init)
    (m, l, zt), _ = jax.lax.scan(
        body, init, (w3.transpose(1, 0, 2), jnp.arange(nc)))
    return m, l, zt


def _fhx_fwd(h, w, targets, tensor_axis, chunk):
    d = h.shape[-1]
    v_local = w.shape[-1]
    h2 = h.reshape(-1, d)
    targets1 = targets.reshape(-1)
    n = h2.shape[0]
    c, nc, v_pad = _fhx_chunks(v_local, chunk)
    w_p = jnp.pad(w, ((0, 0), (0, v_pad - v_local)))
    off = (jax.lax.axis_index(tensor_axis) * v_local
           if tensor_axis is not None else 0)
    m, l, zt = _fhx_scan_stats(h2, w_p, targets1, off, v_local, c, nc)
    if tensor_axis is not None:
        m_g = jax.lax.pmax(m, tensor_axis)
        l = jax.lax.psum(l * jnp.exp(m - m_g), tensor_axis)
        zt = jax.lax.psum(zt, tensor_axis)
        m = m_g
    lse = m + jnp.log(l)
    return (lse - zt).reshape(targets.shape), (h, w, targets, lse)


def _fhx_bwd(tensor_axis, chunk, res, g):
    import numpy as np

    h, w, targets, lse = res
    d = h.shape[-1]
    v_local = w.shape[-1]
    h2 = h.reshape(-1, d)
    targets1 = targets.reshape(-1)
    n = h2.shape[0]
    c, nc, v_pad = _fhx_chunks(v_local, chunk)
    w_p = jnp.pad(w, ((0, 0), (0, v_pad - v_local)))
    off = (jax.lax.axis_index(tensor_axis) * v_local
           if tensor_axis is not None else 0)
    # pad columns need no mask: their z = h @ 0 gives p = exp(-lse) != 0,
    # but that feeds dh only through w_c == 0 (inert) and dw only in the
    # sliced-off pad columns; the onehot never lands there (targets are
    # within the true vocab)
    dnll = g.reshape(-1).astype(jnp.float32)[:, None]   # a token's own
    w3 = w_p.reshape(d, nc, c).transpose(1, 0, 2)

    def body(dh, xs):
        w_c, ci = xs
        z = jax.lax.dot_general(
            h2, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(z - lse[:, None])                     # [N, c]
        lt = targets1 - off - ci * c
        # same shard-membership guard as the forward (a pad-window alias
        # would subtract the onehot from a zero-weight column — inert for
        # dh/dw, but keep the two masks identical by construction)
        lt = jnp.where(targets1 - off < v_local, lt, -1)
        onehot = (jnp.arange(c)[None, :] == lt[:, None])
        dz = ((p - onehot.astype(jnp.float32)) * dnll).astype(w_c.dtype)
        dh = dh + jax.lax.dot_general(
            dz, w_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_c = jax.lax.dot_general(
            h2, dz, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [d, c]
        return dh, dw_c

    dh0 = jnp.zeros((n, d), jnp.float32)
    vma = tuple(sorted(jax.typeof(h2).vma
                       | jax.typeof(w_p).vma
                       | jax.typeof(lse).vma
                       | jax.typeof(targets1).vma
                       | jax.typeof(dnll).vma))
    if vma:
        dh0 = jax.lax.pcast(dh0, vma, to="varying")
    dh, dw_stack = jax.lax.scan(body, dh0, (w3, jnp.arange(nc)))
    dw = dw_stack.transpose(1, 0, 2).reshape(d, v_pad)[:, :v_local]

    dh = _match_vma(dh, h)
    dw = _match_vma(dw, w)
    dt_ct = np.zeros(targets1.shape, dtype=jax.dtypes.float0)
    return (dh.reshape(h.shape).astype(h.dtype), dw.astype(w.dtype),
            dt_ct.reshape(targets.shape))


fused_head_xent_tokens.defvjp(_fhx_fwd, _fhx_bwd)


# The weighted-sum entry's row blocks: ``dz``, ``dh`` and ``dW += h^T dz``
# over _FHW_ROWS rows, the logits over sub-blocks of _FHW_SUB rows.  At
# 2,048 wide and a vocabulary of 49,152 a sub-block's float32 logits are 96
# MiB, which the TPU compiler keeps in its fast memory (whole blocks of 1,024
# rows do not fit: 69.5 ms a call of the head alone where this shape takes
# 59.6), and the ``dW`` product runs at 256 FLOP a byte (the chip's ridge is
# 240); blocks of 2,048 rows take 1.8 ms less and put 59 MB on the step's
# peak (measured on a v5e, PR 45).
_FHW_ROWS = 1024
_FHW_SUB = 512


def _fhw_blocks(n: int):
    """(rows of a sub-block, sub-blocks a block, blocks) for ``n`` rows."""
    sub = min(_FHW_SUB, n)
    per = min(_FHW_ROWS // _FHW_SUB, -(-n // sub))
    return sub, per, -(-n // (sub * per))


def _fhw_rows(h, w, targets, weights, tensor_axis, with_grads):
    """The row-blocked head: ``nll`` shaped like ``targets`` and,
    ``with_grads``, the gradients of ``sum(weights * nll)``: ``dh`` like
    ``h`` and ``dW`` [D, V_local] float32 (both per vocabulary shard)."""
    d = h.shape[-1]
    v_local = w.shape[-1]
    h2 = h.reshape(-1, d)
    n = h2.shape[0]
    sub, per, nb = _fhw_blocks(n)
    pad = nb * per * sub - n
    off = (jax.lax.axis_index(tensor_axis) * v_local
           if tensor_axis is not None else 0)
    # a target of another shard reads no logit here and no onehot: -1
    lt = targets.reshape(-1) - off
    lt = jnp.where((lt >= 0) & (lt < v_local), lt, -1)
    blocks = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                           constant_values=c)
                   .reshape((nb, per, sub) + a.shape[1:])
                   for a, c in ((h2, 0), (lt, -1), (weights.reshape(-1), 0)))

    def sub_block(_, xs):
        h_s, lt_s, wt_s = xs
        z = jax.lax.dot_general(
            h_s, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [sub, V_local]
        onehot = jnp.arange(v_local)[None, :] == lt_s[:, None]
        m = jnp.max(z, axis=-1)
        zt = jnp.sum(jnp.where(onehot, z, 0.0), axis=-1)
        if tensor_axis is not None:
            m = jax.lax.pmax(m, tensor_axis)
            zt = jax.lax.psum(zt, tensor_axis)
        l = jnp.sum(jnp.exp(z - m[:, None]), axis=-1)
        lse = m + jnp.log(_psum_if(l, tensor_axis))
        nll = lse - zt
        if not with_grads:
            return None, nll
        dz = ((jnp.exp(z - lse[:, None]) - onehot.astype(jnp.float32))
              * wt_s[:, None]).astype(w.dtype)
        return None, (nll, dz)

    if not with_grads:
        _, nll = jax.lax.scan(
            sub_block, None, tuple(a.reshape((-1,) + a.shape[2:])
                                   for a in blocks))
        return nll.reshape(-1)[:n].reshape(targets.shape)

    def block(dw, xs):
        # dz has one reader inside the sub-blocks' loop, its place in the
        # stack: with the dh product there it was written twice
        _, (nll, dz) = jax.lax.scan(sub_block, None, xs)
        dz = dz.reshape(-1, v_local)                      # [rows, V_local]
        dh = jax.lax.dot_general(
            dz, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_b = jax.lax.dot_general(
            xs[0].reshape(-1, d), dz, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [D, V_local]
        return (dw_b if dw is None else dw + dw_b), (nll, dh.astype(h.dtype))

    # the first block's product starts the sum: a carry of zeros is 4 D V
    # bytes that the compiler allocates before the stack has run
    dw, (nll, dh) = block(None, tuple(a[0] for a in blocks))
    nll, dh = nll[None], dh[None]
    if nb > 1:
        dw, (nll_r, dh_r) = jax.lax.scan(block, dw,
                                         tuple(a[1:] for a in blocks))
        nll, dh = jnp.concatenate([nll, nll_r]), jnp.concatenate([dh, dh_r])
    return (nll.reshape(-1)[:n].reshape(targets.shape),
            dh.reshape(-1, d)[:n].reshape(h.shape), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_head_xent_wsum(h: Array, w: Array, targets: Array, weights: Array,
                         tensor_axis: Optional[str] = None):
    """``(sum(weights * nll), nll)`` of the per-token cross-entropies ``nll``
    that :func:`fused_head_xent_tokens` returns, for a loss that is a
    weighted sum of them with weights known before the head runs (the looped
    model's exit distribution over its tokens).  ``h`` [..., D], ``w`` [D,
    V_local], ``targets`` [...], ``weights`` [...] float32.

    Blocked over ROWS where that entry is blocked over the vocabulary: a
    block's logits are whole, so its logsumexp is exact at once and ``dz``,
    ``dh`` and the block's share of ``dW`` follow from the one product.
    Differentiated, the forward rule makes the gradients (three products of
    [N, D, V] where the other entry's two passes over the chunks take four)
    and keeps ``dh``, ``dW`` and ``nll``, not ``h`` or ``w``; the backward
    scales them by the first output's scalar cotangent.  The same float32
    logits, bfloat16 ``dz`` and float32 accumulations as the other entry;
    the order of the sums differs.  Called without differentiation it
    computes ``nll`` alone, one product.

    The SECOND output carries no gradient: the rule ignores its cotangent.
    Read it under ``stop_gradient`` (a statistic, as the looped model's
    ``pass_loss``)."""
    nll = _fhw_rows(h, w, targets, weights, tensor_axis, False)
    return jnp.sum(weights * nll), nll


def _fhw_fwd(h, w, targets, weights, tensor_axis):
    nll, dh, dw = _fhw_rows(h, w, targets, weights, tensor_axis, True)
    # the backward needs of the primals only where each varies: empty stubs
    stubs = tuple(varying_like(jnp.zeros((0,), a.dtype), a)
                  for a in (h, w, weights))
    # dW leaves in w's dtype together with dh: the stack's backward waits for
    # dh, so the float32 sum is not what lives through it to the update
    dh, dw = jax.lax.optimization_barrier((dh, dw.astype(w.dtype)))
    return (jnp.sum(weights * nll), nll), (dh, dw, nll, stubs)


def _fhw_bwd(tensor_axis, res, cts):
    import numpy as np

    dh, dw, nll, primals = res
    g = cts[0]
    # scaled first, summed over shards after: the cotangent may vary where
    # the primal does not
    dh, dw, dweights = (
        _match_vma(ct.astype(jnp.float32) * g, primal).astype(primal.dtype)
        for ct, primal in zip((dh, dw, nll), primals))
    return dh, dw, np.zeros(nll.shape, jax.dtypes.float0), dweights


fused_head_xent_wsum.defvjp(_fhw_fwd, _fhw_bwd)


def vocab_parallel_xent_tokens(
    local_logits: Array,
    targets: Array,
    *,
    tensor_axis: Optional[str] = None,
) -> Array:
    """Per-token next-token cross-entropy from vocab-sharded logits.

    ``local_logits`` [..., V_local], ``targets`` [...] global token ids.
    The three reductions (max, sum-exp, target logit) psum over the tensor
    axis — megatron's vocab-parallel loss, sized O(B*T) on the wire instead
    of O(B*T*V).
    """
    z = local_logits.astype(jnp.float32)
    v_local = z.shape[-1]
    # the stabilising max cancels out of the gradient — stop_gradient keeps
    # AD away from pmax (which has no differentiation rule)
    if tensor_axis is not None:
        off = jax.lax.axis_index(tensor_axis) * v_local
        zmax = jax.lax.pmax(jnp.max(jax.lax.stop_gradient(z), axis=-1), tensor_axis)
    else:
        off = 0
        zmax = jnp.max(jax.lax.stop_gradient(z), axis=-1)
    sumexp = jnp.sum(jnp.exp(z - zmax[..., None]), axis=-1)
    local_t = targets - off
    in_shard = (local_t >= 0) & (local_t < v_local)
    zt = jnp.take_along_axis(
        z, jnp.clip(local_t, 0, v_local - 1)[..., None], axis=-1
    )[..., 0]
    zt = jnp.where(in_shard, zt, 0.0)
    if tensor_axis is not None:
        sumexp = jax.lax.psum(sumexp, tensor_axis)
        zt = jax.lax.psum(zt, tensor_axis)
    return jnp.log(sumexp) + zmax - zt


def vocab_parallel_xent(
    local_logits: Array,
    targets: Array,
    *,
    tensor_axis: Optional[str] = None,
) -> Array:
    """Mean of :func:`vocab_parallel_xent_tokens` over the tokens."""
    return jnp.mean(vocab_parallel_xent_tokens(
        local_logits, targets, tensor_axis=tensor_axis))
