#!/usr/bin/env python3
"""The attention kernels alone, on the chip: ms a call, bit-for-bit outputs and
each output's distance from the exact answer, for several copies of
``ops/flash_attention.py`` in one process.

    git archive <parent commit> | tar -x -C bench_checkout/parent
    chiprun -- python3 tools/flash_attn_bench.py [label=path/to/flash_attention.py ...]

With no arguments the sides are ``parent`` (``bench_checkout/parent``'s
module) and ``tree`` (this checkout's).  The first side is the reference: every
other side's ``o``, ``lse``, dq, dk and dv are compared with its, bit for bit,
at each shape's own softmax scale.  A shape with a third element is a
sliding-window call (``window``): sides whose module takes no window (PR 40's
and older) sit it out, and the first side that takes one is its reference.
A side is one file, loaded by path (the
module imports nothing of its package), so a mechanism is timed alone by
handing in a copy of the module that holds only it.

Sides whose blocks differ sum in another order and cannot be bit for bit, so
beside that verdict every row carries, per output, the largest and the
root-mean-square difference from the first side (``gap_to_first``) and, over
the first two heads, from a float32 masked softmax at ``highest`` precision
and its gradients (``gap_to_exact``): two sides equally far from the exact
answer compute the same thing.  The exit code is 1 unless every side is bit
for bit the first.

``fwd_us_a_pair`` and ``bwd_us_a_pair`` divide a call by the pairs it
computes, ``pair_rows`` a side: (q block, kv block) pairs for the
whole-sequence kernels, the (sub-block, sub-block) pairs ("quarters") of the
band kernels' static walk for a call with a window.

A forward is ``_fa_fwd`` (pad, kernel, slice); a backward is ``_fa_bwd``
(``delta``, the lane packing, kernel, slices).  Each is timed as a jitted
``lax.fori_loop`` of ``--calls`` calls whose carry runs through one element of
an operand, so nothing is hoisted and no pass is added; the least of
``--reps`` timings counts.  Run by no benchmark cell and no tier-1 test;
``--rehearse`` runs two tiny shapes under the Pallas interpreter on the CPU to
check the control flow (its times mean nothing and are not printed).
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULE = "tpu_compressed_dp/ops/flash_attention.py"
SHAPES = [
    ((1, 16, 4096, 128), "bfloat16"),   # ouro_2p6b_dense_staged's call
    ((1, 8, 8192, 128), "bfloat16"),    # nemotron3_super_dense_staged's
    ((2, 12, 1024, 64), "bfloat16"),
    ((1, 4, 4096, 128), "float32"),
    ((1, 48, 8192, 128), "bfloat16"),         # laguna_xs2_dense_staged's full layers, a sequence
    ((1, 64, 8192, 128), "bfloat16", 512),    # ... and its window layers
    ((1, 40, 8192, 128), "bfloat16", 512),    # phi4_mini_flash_dense_staged's: 40 maps, keys padded to 128
]
REHEARSAL_SHAPES = [((1, 2, 1024, 64), "bfloat16"), ((1, 1, 1024, 64), "float32"),
                    ((1, 2, 1024, 64), "bfloat16", 300)]
NAMES = ("o", "lse", "dq", "dk", "dv")
EXACT_HEADS = 2


def load(label: str, path: str):
    spec = importlib.util.spec_from_file_location(f"flash_attention_{label}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operands(shape, dtype):
    keys = jax.random.split(jax.random.key(40), 4)
    return tuple((jax.random.normal(k, shape, jnp.float32) * 0.5).astype(dtype)
                 for k in keys)


def live_pairs(fa, t, window=None):
    """(pairs a head's kernels compute, rows of one).  Whole-sequence kernels
    (and PR 45's and older with a window): the (q block, kv block) pairs not
    wholly above the diagonal nor wholly behind the band, at the module's own
    blocks.  Band kernels: the (sub-block, sub-block) pairs of their static
    walk, quarters of a block pair where a block is walked in halves; every
    grid step computes the same number, clamped blocks included."""
    bq, bk = fa._pick_blocks(t)
    if window is not None and window < t and hasattr(fa, "_band_geometry"):
        sub, n_back, reach = fa._band_geometry(bq, window, t // bq)
        n_sub = bq // sub
        return t // bq * sum(min(reach, n_back * n_sub + a) + 1
                             for a in range(n_sub)), sub
    first = lambda qi: 0 if window is None else max(qi * bq - window + 1, 0) // bk
    return sum(-(-(qi + 1) * bq // bk) - first(qi) for qi in range(t // bq)), bq


def takes_window(fa) -> bool:
    return "window" in inspect.signature(fa._fa_fwd).parameters


def band(window) -> dict:
    return {} if window is None else {"window": window}


def outputs(fa, interpret, window=None):
    def run(q, k, v, do):
        o, res = fa._fa_fwd(q, k, v, None, interpret, **band(window))
        return (o, res[4]) + tuple(fa._fa_bwd(None, interpret, res, do, **band(window)))
    return jax.jit(run)


def exact(q, k, v, do, window=None):
    """(o, lse, dq, dk, dv) of the plain form in float32 at ``highest``: the
    whole [T, T] of a head with the diagonal and the band as one mask, a head
    at a time (at 8,192 tokens a head's scores are 256 MB)."""
    t, d = q.shape[-2:]

    def head(q, k, v):
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        s = jnp.dot(q, k.T, precision="highest") / np.sqrt(d)
        lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
        p = jnp.where(seen, jnp.exp(s - lse[:, None]), 0.0)
        return jnp.dot(p, v, precision="highest"), lse

    @jax.jit
    def one(q, k, v, do):
        (o, lse), vjp = jax.vjp(head, q, k, v)
        return (o, lse) + vjp((do, jnp.zeros_like(lse)))

    b, h = q.shape[:2]
    heads = [one(*(x[i, j].astype(jnp.float32) for x in (q, k, v, do)))
             for i in range(b) for j in range(h)]
    return [np.stack([np.asarray(hd[n]) for hd in heads]).reshape(
        (b, h) + heads[0][n].shape) for n in range(len(NAMES))]


def gaps(got, want) -> dict:
    """{output: [largest, root-mean-square]} of the differences, over the
    heads ``want`` holds."""
    out = {}
    for n, a, b in zip(NAMES, got, want):
        diff = a[:, :b.shape[1]].astype(np.float64) - b
        out[n] = [float(np.abs(diff).max()), float(np.sqrt(np.mean(diff ** 2)))]
    return out


def loops(fa, calls, interpret, window=None):
    def fwd(q, k, v):
        def body(_, q):
            o, _ = fa._fa_fwd(q, k, v, None, interpret, **band(window))
            return q.at[0, 0, 0, 0].add((o[0, 0, 0, 0] * 0).astype(q.dtype))
        return jax.lax.fori_loop(0, calls, body, q)

    def bwd(q, k, v, o, lse, do):
        def body(_, do):
            dq, dk, dv = fa._fa_bwd(None, interpret, (q, k, v, o, lse), do,
                                    **band(window))
            probe = dq[0, 0, 0, 0] + dk[0, 0, 0, 0] + dv[0, 0, 0, 0]
            return do.at[0, 0, 0, 0].add((probe * 0).astype(do.dtype))
        return jax.lax.fori_loop(0, calls, body, do)

    return jax.jit(fwd), jax.jit(bwd)


def ms_a_call(fn, args, calls, reps):
    jax.block_until_ready(fn(*args))          # compile
    jax.block_until_ready(fn(*args))          # and ramp the chip
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sides", nargs="*", metavar="label=path",
                    help="copies of flash_attention.py; the first is the reference")
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--windowed", action="store_true",
                    help="only the shapes with a window")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "flash_attn_bench.json"))
    args = ap.parse_args(argv)
    sides = [s.split("=", 1) for s in args.sides] or [
        ["parent", os.path.join(ROOT, "bench_checkout", "parent", MODULE)],
        ["tree", os.path.join(ROOT, MODULE)]]
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print(f"flash_attn_bench: needs a TPU, found {device.platform}; "
              "--rehearse checks the control flow on the CPU", file=sys.stderr)
        return 2
    calls = 2 if args.rehearse else args.calls
    modules = [(label, load(label, path)) for label, path in sides]
    rows = []
    for shape, dtype, *window in REHEARSAL_SHAPES if args.rehearse else SHAPES:
        window = window[0] if window else None
        if args.windowed and window is None:
            continue
        q, k, v, do = operands(shape, getattr(jnp, dtype))
        reference = None
        truth = exact(*(x[:, :EXACT_HEADS] for x in (q, k, v, do)), window)
        for label, fa in modules:
            if window is not None and not takes_window(fa):
                continue
            got = outputs(fa, args.rehearse, window)(q, k, v, do)
            host = [np.asarray(x.astype(jnp.float32)) for x in got]
            reference = reference or host
            row = {"shape": list(shape), "dtype": dtype, "window": window, "side": label,
                   "bitwise": {n: bool(np.array_equal(a, b))
                               for n, a, b in zip(NAMES, host, reference)},
                   "gap_to_first": gaps(host, reference),
                   "gap_to_exact": gaps(host, truth)}
            fwd, bwd = loops(fa, calls, args.rehearse, window)
            fwd_ms = ms_a_call(fwd, (q, k, v), calls, args.reps)
            bwd_ms = ms_a_call(bwd, (q, k, v, got[0], got[1], do), calls, args.reps)
            if not args.rehearse:
                pairs, rows_a_pair = live_pairs(fa, shape[2], window)
                pairs *= shape[0] * shape[1]
                row.update(fwd_ms=fwd_ms, bwd_ms=bwd_ms, pair_rows=rows_a_pair,
                           fwd_us_a_pair=fwd_ms * 1e3 / pairs,
                           bwd_us_a_pair=bwd_ms * 1e3 / pairs)
            rows.append(row)
            print(json.dumps(row), flush=True)
    result = {"device": device.device_kind, "platform": device.platform,
              "calls": calls, "reps": args.reps, "rows": rows}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    return 0 if all(all(r["bitwise"].values()) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
