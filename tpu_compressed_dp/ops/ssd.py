"""The Mamba-2 mixer's sequence operators: the causal depthwise convolution
and the selective state-space recurrence as a chunked scan (state-space
duality, arXiv:2405.21060 section 6), in ``jax.numpy`` einsums.

The recurrence, per head ``h`` with its group's ``B`` and ``C``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: [head size, state]
    y_t = S_t C_t + D x_t

Chunked: inside a chunk of ``L`` tokens the output is a masked, decayed
``(C B^T) x`` product (quadratic in ``L``, on the MXU); each chunk leaves a
state, the states are carried from chunk to chunk by a ``lax.scan`` of
``T / L`` steps, and each token reads the state its chunk started from.  The
decays, their cumulative sums and the carried state are float32 whatever the
operands' type; the backward is reverse-mode differentiation of the same
einsums, so a layer under ``jax.checkpoint`` keeps none of the ``[L, L]``
blocks.

All of this rests on ONE scalar decay a head (``exp(dt_t A)`` with ``A`` [H]):
a chunk's decays are then an ``[L, L]`` matrix and the recurrence is matrix
products.  Mamba-1's decay is one number for every channel and state index
(``A`` [channels, state]), for which that form does not exist: its scan,
carried element by element with a backward of its own, is
:mod:`tpu_compressed_dp.ops.selective_scan`, which shares this module's
convolution.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

__all__ = ["causal_depthwise_conv", "ssd_chunked_scan", "ssd_sequential_scan",
           "varying_like"]


def varying_like(x, *refs: Array):
    """``x`` (a pytree of loop-carry initialisers) marked as varying over the
    mesh axes any of ``refs`` varies on: inside ``shard_map`` a loop's carry
    must enter with the type its body returns.  A leaf that varies on some of
    them already (a state or a cotangent handed in) gets the rest."""
    want = set().union(*(jax.typeof(r).vma for r in refs))

    def one(v):
        missing = tuple(sorted(want - set(jax.typeof(v).vma)))
        return jax.lax.pcast(v, missing, to="varying") if missing else v

    return jax.tree.map(one, x)


def causal_depthwise_conv(x: Array, w: Array, b: Array) -> Array:
    """``y[t] = b + sum_k w[k] * x[t - (K - 1) + k]`` channel by channel,
    zeros before the sequence's start.  ``x`` [B, T, C], ``w`` [K, C] (tap
    ``K - 1`` multiplies the current token), ``b`` [C]."""
    k = w.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = b.astype(x.dtype)
    for i in range(k):
        y = y + padded[:, i:i + t] * w[i].astype(x.dtype)
    return y


def _expand_groups(bc: Array, heads: int) -> Array:
    """[..., G, N] -> [..., H, N]: head ``h`` reads group ``h // (H / G)``."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssd_chunked_scan(x: Array, dt: Array, a: Array, b: Array, c: Array,
                     d: Array, chunk: int) -> Array:
    """``x`` [B, T, H, P], ``dt`` [B, T, H] (positive, float32), ``a`` [H]
    (negative, float32), ``b`` and ``c`` [B, T, G, N] with ``H % G == 0``,
    ``d`` [H]; returns ``y`` [B, T, H, P] in ``x``'s type.  ``T`` must be a
    whole number of chunks: the caller pads or refuses."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the "
                         f"scan's chunk {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    nc, per, n = t // chunk, h // g, b.shape[-1]
    dtype = x.dtype
    f32 = jnp.float32

    # heads lead and a chunk's tokens are the minor dimensions, so that the
    # [L, L] blocks and the [L, P] tiles are what the MXU is fed
    xh = x.reshape(bsz, nc, chunk, h, p).transpose(0, 1, 3, 2, 4)     # [B, nc, H, L, P]
    dth = dt.astype(f32).reshape(bsz, nc, chunk, h).transpose(0, 1, 3, 2)
    bg = b.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)     # [B, nc, G, L, N]
    cg = c.reshape(bsz, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    # log-decay of each step and its running sum inside the chunk
    cum = jnp.cumsum(dth * a.astype(f32)[:, None], axis=-1)          # [B, nc, H, L]
    total = cum[..., -1]                                             # [B, nc, H]

    # inside a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s) dt_s x_s
    scores = jnp.einsum("bnglk,bngsk->bngls", cg, bg,
                        preferred_element_type=f32)                  # [B, nc, G, L, L]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                             # [B, nc, H, L, L]
    mix = (jnp.repeat(scores, per, axis=2) * decay
           * dth[..., None, :]).astype(dtype)
    y = jnp.einsum("bnhls,bnhsp->bnhlp", mix, xh, preferred_element_type=f32)

    # what each chunk adds to the state: sum_s exp(total - cum_s) dt_s x_s B_s^T
    tail = (jnp.exp(total[..., None] - cum) * dth).astype(dtype)     # [B, nc, H, L]
    added = jnp.einsum(
        "bngrsp,bngsk->bngrpk",
        (xh * tail[..., None]).reshape(bsz, nc, g, per, chunk, p), bg,
        preferred_element_type=f32)                                  # [B, nc, G, R, P, N]

    # from chunk to chunk: S_n = exp(total_n) S_{n-1} + added_n, float32
    def carry(state, xs):
        tot, add = xs
        return jnp.exp(tot)[..., None, None] * state + add, state

    totg = total.reshape(bsz, nc, g, per)
    zero = varying_like(jnp.zeros((bsz, g, per, p, n), f32), totg, added)
    _, before = jax.lax.scan(carry, zero,
                             (totg.swapaxes(0, 1), added.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)             # the state each chunk starts from
    read = jnp.einsum("bnglk,bngrpk->bngrlp", cg, before.astype(dtype),
                      preferred_element_type=f32).reshape(bsz, nc, h, chunk, p)
    y = y + read * jnp.exp(cum)[..., None]
    y = y.transpose(0, 1, 3, 2, 4).reshape(bsz, t, h, p)
    return (y + d.astype(f32)[:, None] * x.astype(f32)).astype(dtype)


def ssd_sequential_scan(x: Array, dt: Array, a: Array, b: Array, c: Array,
                        d: Array) -> Array:
    """The recurrence as written, one token at a time, in float32: what the
    chunked form is tested against."""
    f32 = jnp.float32
    h = x.shape[2]
    bh, ch = _expand_groups(b.astype(f32), h), _expand_groups(c.astype(f32), h)
    xf, dtf = x.astype(f32), dt.astype(f32)

    def step(state, xs):
        xt, dtt, bt, ct = xs                                 # [B,H,P] [B,H] [B,H,N] [B,H,N]
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    zero = varying_like(
        jnp.zeros(xf.shape[:1] + xf.shape[2:] + bh.shape[-1:], f32), xf, dtf, bh, ch)
    _, y = jax.lax.scan(step, zero, tuple(
        v.swapaxes(0, 1) for v in (xf, dtf, bh, ch)))
    return (y.swapaxes(0, 1) + d.astype(f32)[:, None] * xf).astype(x.dtype)
