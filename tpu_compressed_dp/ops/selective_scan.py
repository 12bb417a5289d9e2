"""The Mamba-1 selective scan (arXiv:2312.00752), forward and a backward of
its own.

The recurrence, per channel ``c`` and state index ``n``:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]

The decay is one number for every channel AND state index (5,120 x 16 in
Phi-4-mini-flash), so the chunked matrix form of :mod:`tpu_compressed_dp.ops.ssd`
(one scalar decay a head: a chunk's decays are an ``[L, L]`` matrix) does not
exist here: the state is carried element by element through every token.

What is kept and what is made again.  The sequence is cut into chunks of
``chunk`` tokens.  The forward carries the float32 state from chunk to chunk
and keeps the state each chunk STARTED from (``[T / chunk, N, C]``: 21 MB at
8,192 tokens, 5,120 channels, chunks of 128) and nothing else of the states.
The backward (``custom_vjp``) walks the chunks from the last to the first,
makes a chunk's states again from the state it started from, and carries the
state's cotangent backwards through the same decays.  Nothing of size
``[T, C, N]`` is ever alive: a chunk's ``[chunk, N, C]`` blocks are (42 MB).
Differentiating a plain ``lax.scan`` over the tokens, or an associative scan,
keeps ``[T, C, N]`` float32 (2.7 GB a layer pass at those sizes).

``exp(dt A)`` is taken as it stands, a step at a time: a chunk's decays are
never written as ``exp(cum_t) exp(-cum_s)``, which overflows where ``dt A``
is large over a whole chunk.

The state is laid out ``[N, C]``, the channels on the lanes.  Two builds of
the same chunked arithmetic:

  XLA      (every backend; what the kernels are tested against).  The only
           sequential parts are the two recurrences (``S = a S + x`` forward,
           ``G = g + a G`` backward); a chunk's decays, inputs, outputs and
           every reduction of the backward are whole-chunk array operations
           around them, on ``[chunk, N, C]`` blocks in HBM.
  Pallas   (the TPU; ``TPU_CDP_SCAN_KERNEL=0`` switches it off, ``=1`` takes
           it whatever the backend, which is how a compile for a described
           chip gets it, ``=interpret`` runs it under the Pallas interpreter).  ``selective_scan_fwd`` and ``selective_scan_bwd``
           walk a grid of (sequence, chunk, block of ``_KERNEL_BLOCK``
           channels) with the ``[N, block]`` state in VMEM from chunk to
           chunk; the backward makes a chunk's states again into VMEM scratch
           and walks them back.  Nothing ``[chunk, N, C]`` reaches HBM: the
           kernels read u, dt, the cotangent and B and C (broadcast over 128
           lanes outside, so that a token's column meets the state with no
           relayout) and write y, the boundary states and the gradients; dB
           and dC leave as per-lane partial sums ``[T, N, 128]``, summed over
           the channel blocks in the kernel and over the lanes outside.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_compressed_dp.ops.ssd import varying_like

Array = jax.Array

__all__ = ["selective_scan", "selective_scan_sequential", "scan_kernel_fits",
           "KEPT_NAMES"]

_F32 = jnp.float32
# how many tokens a trip of the sequential loops takes: the loop's own cost a
# trip is of the order of a token's arithmetic
_UNROLL = 8

# channels a grid step of the kernels takes: the state, its cotangent and the
# backward's accumulator of dA are [N, block] each in registers or VMEM
_KERNEL_BLOCK = 512
_LANES = 128
_VMEM_LIMIT = 40 * 1024 * 1024

#: what a layer's checkpoint has to keep so that its backward does not run
#: the scan's forward again: the output and the chunk-boundary states
KEPT_NAMES = ("scan_out", "scan_states")


def _chunk_terms(dt, u, a_t, b):
    """A chunk's decays and inputs, [B, L, N, C] float32."""
    decay = jnp.exp(dt[:, :, None, :] * a_t)
    return decay, (dt * u)[:, :, None, :] * b[..., None]


def _chunk_states(s0, decay, x):
    """``S_t = decay_t S_{t-1} + x_t`` through one chunk: (the last state
    [B, N, C], every state [B, L, N, C])."""
    def step(s, xs):
        s = xs[0] * s + xs[1]
        return s, s

    last, states = jax.lax.scan(
        step, s0, (decay.swapaxes(0, 1), x.swapaxes(0, 1)), unroll=_UNROLL)
    return last, states.swapaxes(0, 1)


def _by_chunk(x, nc):
    """[B, T, ...] -> [nc, B, L, ...]."""
    return x.reshape((x.shape[0], nc, -1) + x.shape[2:]).swapaxes(0, 1)


def _from_chunks(x):
    """[nc, B, L, ...] -> [B, T, ...]."""
    x = x.swapaxes(0, 1)
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def _operands(u, dt, a, b, c, chunk):
    nc = u.shape[1] // chunk
    return (nc, a.astype(_F32).T,
            tuple(_by_chunk(v.astype(_F32), nc) for v in (u, dt, b, c)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(u, dt, a, b, c, d, s0, chunk, impl):
    return _scan_fwd(u, dt, a, b, c, d, s0, chunk, impl)[0]


def _scan_fwd(u, dt, a, b, c, d, s0, chunk, impl):
    if impl != "xla":
        y, last, starts = _kernel_forward(u, dt, a, b, c, d, s0, chunk,
                                          impl == "interpret")
        return ((checkpoint_name(y, "scan_out"), last),
                (u, dt, a, b, c, d, checkpoint_name(starts, "scan_states")))
    nc, a_t, (uc, dtc, bc, cc) = _operands(u, dt, a, b, c, chunk)

    def body(s, xs):
        u_, dt_, b_, c_ = xs
        last, states = _chunk_states(s, *_chunk_terms(dt_, u_, a_t, b_))
        y = jnp.sum(states * c_[..., None], axis=2) + d.astype(_F32) * u_
        return last, (y.astype(u.dtype), s)

    last, (y, starts) = jax.lax.scan(body, s0, (uc, dtc, bc, cc))
    y = checkpoint_name(_from_chunks(y), "scan_out")
    starts = checkpoint_name(starts, "scan_states")
    return (y, last), (u, dt, a, b, c, d, starts)


def _scan_bwd(chunk, impl, res, cts):
    u, dt, a, b, c, d, starts = res
    dy, dlast = cts
    if impl != "xla":
        return _kernel_backward(u, dt, a, b, c, d, starts, dy, dlast, chunk,
                                impl == "interpret")
    nc, a_t, (uc, dtc, bc, cc) = _operands(u, dt, a, b, c, chunk)
    dyc = _by_chunk(dy.astype(_F32), nc)
    df = d.astype(_F32)

    def body(carry, xs):
        g_next, da_acc, dd_acc = carry      # g_next: dL/dS_last by way of the future
        u_, dt_, b_, c_, dy_, s0 = xs
        decay, x = _chunk_terms(dt_, u_, a_t, b_)
        _, states = _chunk_states(s0, decay, x)
        before = jnp.concatenate([s0[:, None], states[:, :-1]], axis=1)

        # G_t = dy_t C_t + decay_{t+1} G_{t+1}, from the chunk's last token back
        def step(g, xs):
            g = xs[0] + g
            return xs[1] * g, g

        g_out, gs = jax.lax.scan(
            step, g_next,
            ((dy_[:, :, None, :] * c_[..., None]).swapaxes(0, 1),
             decay.swapaxes(0, 1)), reverse=True, unroll=_UNROLL)
        gs = gs.swapaxes(0, 1)                                   # [B, L, N, C]
        dc = jnp.sum(states * dy_[:, :, None, :], axis=3)        # [B, L, N]
        db = jnp.sum(gs * (dt_ * u_)[:, :, None, :], axis=3)
        ddtu = jnp.sum(gs * b_[..., None], axis=2)               # [B, L, C]
        dlog = gs * before * decay                               # dL/d(dt_t A)
        ddt = jnp.sum(dlog * a_t, axis=2) + ddtu * u_
        du = ddtu * dt_ + df * dy_
        da_acc = da_acc + jnp.sum(dlog * dt_[:, :, None, :], axis=(0, 1))
        dd_acc = dd_acc + jnp.sum(dy_ * u_, axis=(0, 1))
        return (g_out, da_acc, dd_acc), (du, ddt, db, dc)

    zeros = varying_like((jnp.zeros(a_t.shape, _F32), jnp.zeros(d.shape, _F32)),
                         u, dt, a, b, c, dy)
    g0 = varying_like(dlast.astype(_F32), u, dt, a, b, c, dy)
    (ds0, da_t, dd), (du, ddt, db, dc) = jax.lax.scan(
        body, (g0,) + zeros, (uc, dtc, bc, cc, dyc, starts), reverse=True)
    return (_from_chunks(du).astype(u.dtype), _from_chunks(ddt).astype(dt.dtype),
            da_t.T.astype(a.dtype), _from_chunks(db).astype(b.dtype),
            _from_chunks(dc).astype(c.dtype), dd.astype(d.dtype), ds0)


_scan.defvjp(_scan_fwd, _scan_bwd)


# ------------------------------------------------------------------ kernels

def _vma(*xs: Array):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _tile_lanes(x, reps: int):
    """A ``[N, 128]`` block (a token's B or C, the same in every lane) under
    ``reps`` x 128 lanes: the same vregs again."""
    return jnp.concatenate([x] * reps, axis=1) if reps > 1 else x


def _fold_lanes(x, reps: int):
    """``[N, reps x 128]`` -> ``[N, 128]``: the lane tiles added up."""
    out = x[:, :_LANES]
    for i in range(1, reps):
        out = out + x[:, i * _LANES:(i + 1) * _LANES]
    return out


def _row(ref, t):
    return ref[0, pl.ds(t, 1), :]                         # [1, block]


def _fwd_kernel(chunk, u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                y_ref, starts_ref, last_ref, s_scr):
    """One chunk of one block of channels: the state comes from ``s_scr``
    (the sequence's first chunk: from ``s0``), goes out as the chunk's
    boundary state, and is carried through the chunk's tokens."""
    ti, cb = pl.program_id(1), pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        s_scr[cb] = s0_ref[0, 0]

    a, dcoef = a_ref[0], d_ref[0]
    reps = a.shape[1] // _LANES
    s = s_scr[cb]
    starts_ref[0, 0, 0] = s

    def body(t, s):
        dt_t, u_t = _row(dt_ref, t), _row(u_ref, t)
        s = (jnp.exp(dt_t * a) * s
             + (dt_t * u_t) * _tile_lanes(b_ref[0, t], reps))
        y_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(s * _tile_lanes(c_ref[0, t], reps), axis=0, keepdims=True)
            + dcoef * u_t)
        return s

    s = jax.lax.fori_loop(0, chunk, body, s)
    s_scr[cb] = s

    @pl.when(ti == pl.num_programs(1) - 1)
    def _():
        last_ref[0, cb] = s


def _bwd_kernel(chunk, u_ref, dt_ref, dy_ref, b_ref, c_ref, a_ref, d_ref,
                starts_ref, dlast_ref, du_ref, ddt_ref, dbp_ref, dcp_ref,
                da_ref, dd_ref, ds0_ref, st_scr, g_scr, da_scr, dd_scr):
    """One chunk of one block of channels, the chunks from the last to the
    first (the index maps turn the grid's second axis round).  The chunk's
    states are made again from its boundary state into ``st_scr`` (row ``t +
    1`` is the state after token ``t``, row 0 the boundary state); then
    ``G_t = dy_t C_t + decay_{t+1} G_{t+1}`` walks back through them.  dB and
    dC accumulate over the channel blocks in their output blocks, dA and dD
    over chunks and sequences in scratch."""
    bi, ti, cb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    a, dcoef = a_ref[0], d_ref[0]
    reps = a.shape[1] // _LANES

    @pl.when((bi == 0) & (ti == 0))
    def _():
        da_scr[cb] = jnp.zeros_like(a)
        dd_scr[cb] = jnp.zeros_like(dcoef)

    @pl.when(ti == 0)
    def _():
        g_scr[cb] = dlast_ref[0, 0]

    @pl.when(cb == 0)
    def _():
        dbp_ref[...] = jnp.zeros_like(dbp_ref)
        dcp_ref[...] = jnp.zeros_like(dcp_ref)

    st_scr[0] = starts_ref[0, 0, 0]

    def forward(t, s):
        dt_t = _row(dt_ref, t)
        s = (jnp.exp(dt_t * a) * s
             + (dt_t * _row(u_ref, t)) * _tile_lanes(b_ref[0, t], reps))
        st_scr[t + 1] = s
        return s

    jax.lax.fori_loop(0, chunk, forward, st_scr[0])

    def backward(i, carry):
        g, da, dd = carry
        t = chunk - 1 - i
        dt_t, u_t, dy_t = _row(dt_ref, t), _row(u_ref, t), _row(dy_ref, t)
        decay = jnp.exp(dt_t * a)
        g = g + dy_t * _tile_lanes(c_ref[0, t], reps)               # G_t
        dcp_ref[0, t] = dcp_ref[0, t] + _fold_lanes(st_scr[t + 1] * dy_t, reps)
        dbp_ref[0, t] = dbp_ref[0, t] + _fold_lanes(g * (dt_t * u_t), reps)
        ddtu = jnp.sum(g * _tile_lanes(b_ref[0, t], reps), axis=0, keepdims=True)
        dlog = g * st_scr[t] * decay                               # dL/d(dt_t A)
        ddt_ref[0, pl.ds(t, 1), :] = (jnp.sum(dlog * a, axis=0, keepdims=True)
                                      + ddtu * u_t)
        du_ref[0, pl.ds(t, 1), :] = ddtu * dt_t + dcoef * dy_t
        return decay * g, da + dlog * dt_t, dd + dy_t * u_t

    g, da, dd = jax.lax.fori_loop(0, chunk, backward,
                                  (g_scr[cb], da_scr[cb], dd_scr[cb]))
    g_scr[cb] = g
    da_scr[cb] = da
    dd_scr[cb] = dd

    @pl.when(ti == pl.num_programs(1) - 1)
    def _():
        ds0_ref[0, cb] = g

    @pl.when((bi == pl.num_programs(0) - 1) & (ti == pl.num_programs(1) - 1))
    def _():
        da_ref[cb] = da
        dd_ref[cb] = dd


def _blocked(x, block: int):
    """[..., N, C] -> [..., C / block, N, block]: a block of channels is a
    leading index, which a kernel may take dynamically."""
    lead, (n, c) = x.shape[:-2], x.shape[-2:]
    return jnp.moveaxis(x.reshape(lead + (n, c // block, block)), -2, -3)


def _unblocked(x):
    """[..., C / block, N, block] -> [..., N, C]."""
    x = jnp.moveaxis(x, -3, -2)
    return x.reshape(x.shape[:-2] + (-1,))


def _kernel_operands(u, dt, a, b, c, d):
    bsz, t, ch = u.shape
    n = a.shape[1]
    block = min(_KERNEL_BLOCK, ch)
    lanes = lambda v: jnp.broadcast_to(v.astype(_F32)[..., None], (bsz, t, n, _LANES))
    return (block, u.astype(_F32), dt.astype(_F32), lanes(b), lanes(c),
            _blocked(a.astype(_F32).T, block),
            _blocked(d.astype(_F32)[None], block))


def _kernel_specs(ch, n, chunk, block, rev):
    """Block specs of what both kernels read, on the grid (sequence, chunk,
    block of channels); ``rev`` turns a grid step into its chunk."""
    seq = pl.BlockSpec((1, chunk, block), lambda b, i, k: (b, rev(i), k))
    col = pl.BlockSpec((1, chunk, n, _LANES), lambda b, i, k: (b, rev(i), 0, 0))
    a_spec = pl.BlockSpec((1, n, block), lambda b, i, k: (k, 0, 0))
    d_spec = pl.BlockSpec((1, 1, block), lambda b, i, k: (k, 0, 0))
    state = pl.BlockSpec((1, 1, n, block), lambda b, i, k: (b, k, 0, 0))
    starts = pl.BlockSpec((1, 1, 1, n, block), lambda b, i, k: (b, rev(i), k, 0, 0))
    whole = pl.BlockSpec((1, ch // block, n, block), lambda b, i, k: (b, 0, 0, 0))
    return seq, col, a_spec, d_spec, state, starts, whole


def _compiler_params():
    # every axis in order: the state goes from chunk to chunk, dB and dC
    # accumulate from block to block, dA from sequence to sequence
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _kernel_forward(u, dt, a, b, c, d, s0, chunk, interpret):
    """(y [B, T, C] in u's type, the last state [B, N, C], the boundary
    states [B, T / chunk, C / block, N, block])."""
    bsz, t, ch = u.shape
    n, nc = a.shape[1], t // chunk
    block, uf, dtf, bl, cl, ab, db = _kernel_operands(u, dt, a, b, c, d)
    nb = ch // block
    seq, col, a_spec, d_spec, state, starts, whole = _kernel_specs(
        ch, n, chunk, block, lambda i: i)
    vma = _vma(u, dt, a, b, c, d, s0)
    y, starts_out, last = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk),
        grid=(bsz, nc, nb),
        in_specs=[seq, seq, col, col, a_spec, d_spec, state],
        out_specs=[seq, starts, whole],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, ch), _F32, vma=vma),
                   jax.ShapeDtypeStruct((bsz, nc, nb, n, block), _F32, vma=vma),
                   jax.ShapeDtypeStruct((bsz, nb, n, block), _F32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((nb, n, block), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="selective_scan_fwd",
    )(uf, dtf, bl, cl, ab, db, _blocked(s0.astype(_F32), block))
    return y.astype(u.dtype), _unblocked(last), starts_out


def _kernel_backward(u, dt, a, b, c, d, starts, dy, dlast, chunk, interpret):
    bsz, t, ch = u.shape
    n, nc = a.shape[1], t // chunk
    block, uf, dtf, bl, cl, ab, db = _kernel_operands(u, dt, a, b, c, d)
    nb = ch // block
    seq, col, a_spec, d_spec, state, starts_spec, whole = _kernel_specs(
        ch, n, chunk, block, lambda i: nc - 1 - i)
    vma = _vma(u, dt, a, b, c, d, starts, dy, dlast)
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, _F32, vma=vma)
    total = lambda rows: pl.BlockSpec((nb, rows, block), lambda b, i, k: (0, 0, 0))
    du, ddt, dbp, dcp, da, dd, ds0 = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk),
        grid=(bsz, nc, nb),
        in_specs=[seq, seq, seq, col, col, a_spec, d_spec, starts_spec, state],
        out_specs=[seq, seq, col, col, total(n), total(1), whole],
        out_shape=[shape(bsz, t, ch), shape(bsz, t, ch),
                   shape(bsz, t, n, _LANES), shape(bsz, t, n, _LANES),
                   shape(nb, n, block), shape(nb, 1, block),
                   shape(bsz, nb, n, block)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, n, block), _F32),
                        pltpu.VMEM((nb, n, block), _F32),
                        pltpu.VMEM((nb, n, block), _F32),
                        pltpu.VMEM((nb, 1, block), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="selective_scan_bwd",
    )(uf, dtf, dy.astype(_F32), bl, cl, ab, db, starts,
      _blocked(dlast.astype(_F32), block))
    return (du.astype(u.dtype), ddt.astype(dt.dtype),
            _unblocked(da).T.astype(a.dtype),
            jnp.sum(dbp, axis=-1).astype(b.dtype),
            jnp.sum(dcp, axis=-1).astype(c.dtype),
            _unblocked(dd)[0].astype(d.dtype), _unblocked(ds0))


def scan_kernel_fits(channels: int, states: int, chunk: int) -> bool:
    """Shapes the kernels take: whole blocks of channels on the lanes, the
    state's rows and a chunk's tokens whole sublane tiles."""
    return (channels % min(_KERNEL_BLOCK, channels) == 0
            and min(_KERNEL_BLOCK, channels) % _LANES == 0
            and states % 8 == 0 and chunk % 8 == 0)


def _pick_impl(channels: int, states: int, chunk: int) -> str:
    mode = os.environ.get("TPU_CDP_SCAN_KERNEL", "")
    wanted = mode in ("1", "interpret") or (mode != "0" and jax.default_backend() == "tpu")
    if not (wanted and scan_kernel_fits(channels, states, chunk)):
        return "xla"
    return "interpret" if mode == "interpret" else "pallas"


def selective_scan(u: Array, dt: Array, a: Array, b: Array, c: Array, d: Array,
                   chunk: int, state: Optional[Array] = None,
                   impl: Optional[str] = None) -> Tuple[Array, Array]:
    """``u`` [B, T, C], ``dt`` [B, T, C] (positive, float32), ``a`` [C, N]
    (negative, float32), ``b`` and ``c`` [B, T, N], ``d`` [C]; ``state``
    [B, N, C] float32 is the state before the first token (zeros if None).
    Returns ``(y [B, T, C] in u's type, the state after the last token
    [B, N, C] float32)``.  ``T`` must be a whole number of chunks.  ``impl``:
    ``"xla"``, ``"pallas"`` or ``"interpret"`` (the kernels under the Pallas
    interpreter, for the tests); None lets the backend and the shapes decide."""
    if u.shape[1] % chunk:
        raise ValueError(f"sequence length {u.shape[1]} is not a multiple of "
                         f"the scan's chunk {chunk}")
    if impl is None:
        impl = _pick_impl(a.shape[0], a.shape[1], chunk)
    if state is None:
        state = jnp.zeros((u.shape[0], a.shape[1], a.shape[0]), _F32)
    # outside the custom_vjp: the state's cotangent varies as the operands do
    state = varying_like(state.astype(_F32), u, dt, a, b, c)
    return _scan(u, dt, a, b, c, d, state, chunk, impl)


def selective_scan_sequential(u, dt, a, b, c, d, state=None):
    """The recurrence as written, one token at a time, in float32: what
    :func:`selective_scan` is tested against (its gradients by reverse-mode
    differentiation of this loop)."""
    uf, dtf, bf, cf = (v.astype(_F32) for v in (u, dt, b, c))
    a_t = a.astype(_F32).T
    if state is None:
        state = jnp.zeros((u.shape[0],) + a_t.shape, _F32)

    def step(s, xs):
        ut, dtt, bt, ct = xs                                # [B,C] [B,C] [B,N] [B,N]
        s = (jnp.exp(dtt[:, None, :] * a_t) * s
             + (dtt * ut)[:, None, :] * bt[:, :, None])
        return s, jnp.sum(s * ct[:, :, None], axis=1)

    last, y = jax.lax.scan(step, varying_like(state, uf, dtf, bf, cf), tuple(
        v.swapaxes(0, 1) for v in (uf, dtf, bf, cf)))
    return (y.swapaxes(0, 1) + d.astype(_F32) * uf).astype(u.dtype), last
