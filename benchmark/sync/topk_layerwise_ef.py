"""Sync semantics ``topk_layerwise_ef``: per parameter tensor, each worker adds
its residual to its gradient, sends the ``keep`` largest magnitudes as (value,
index) pairs and keeps the rest as its new residual; the applied gradient is
the mean over workers of the decoded payloads.  The traffic file's
``semantics`` block states the same in words.  Interface: see ``dense.py``.
"""

from __future__ import annotations

import math

import numpy as np

KINDS = ("mean_grad1", "resid1", "dparam")
# with error feedback the applied gradient is sparse and its norm on a small
# leaf is the largest of a few values: what travelled plus what stayed behind
# (the mean local gradient) and the residual are compared instead


def accepts(compression: dict) -> None:
    want = {"method": "topk", "granularity": "layerwise", "mode": "wire",
            "error_feedback": True}
    got = {k: compression.get(k) for k in want}
    if got != want:
        raise SystemExit(f"sync 'topk_layerwise_ef' states {want}; the traffic file "
                         f"has {got}: name the benchmark/sync/<name>.py that states "
                         "its semantics")
    if not 0.0 < compression.get("ratio", 0.0) < 1.0:
        raise SystemExit("topk_layerwise_ef needs 0 < ratio < 1")


def keep_count(n: int, ratio: float) -> int:
    """Coordinates a Top-K of ``ratio`` keeps of ``n``: those at or above the
    ceil(n(1-ratio))-th smallest magnitude, i.e. n - ceil(n(1-ratio)) + 1."""
    return max(1, n - max(1, math.ceil(n * (1.0 - ratio))) + 1)


def split(acc: np.ndarray, ratio: float):
    """(sent, residual) of one flat leaf: the keep largest magnitudes travel."""
    n = acc.size
    keep = keep_count(n, ratio)
    if keep >= n:
        return acc, np.zeros_like(acc)
    mag = np.abs(acc)
    thr = np.partition(mag, n - keep)[n - keep]
    sel = mag >= thr
    return np.where(sel, acc, 0).astype(acc.dtype), np.where(sel, 0, acc).astype(acc.dtype)


def init(leaves, world: int, compression: dict):
    return [[np.zeros_like(l) for l in leaves] for _ in range(world)]


def exchange(grads, state, compression: dict):
    # leaf by leaf, each worker's gradient leaf released once it is split, so
    # the applied list grows as the gradient lists go
    world, ratio = len(grads), compression["ratio"]
    applied = []
    for i in range(len(grads[0])):
        acc = np.zeros_like(grads[0][i])
        for w, g in enumerate(grads):
            gl, g[i] = g[i], None
            sent, res = split((gl + state[w][i]).ravel(), ratio)
            state[w][i] = res.reshape(gl.shape)
            acc += sent.reshape(gl.shape) / np.float32(world)
        applied.append(acc)
    return applied, state


def reference_trees(state) -> dict:
    # generators: the norms are taken a leaf at a time, and no list is made
    world = len(state)
    return {"resid1": (sum(state[w][i] for w in range(world)) / world
                       for i in range(len(state[0])))}


def program_trees(g1, ef1) -> dict:
    # what travelled plus what stayed behind is the mean local gradient
    return {"mean_grad1": (g + e.mean(axis=0) for g, e in zip(g1, ef1)),
            "resid1": (e.mean(axis=0) for e in ef1)}


def wire_bits(sizes, compression: dict) -> int:
    """One worker's payload: keep x (32-bit value + 32-bit index) per leaf."""
    return sum(64 * keep_count(n, compression["ratio"]) for n in sizes)


def exact_checks(g1, ef1, compression: dict, counters: dict, sizes) -> dict:
    """On the applied gradient ``g1`` and each worker's residual ``ef1`` (leaves
    [world, ...]) after the first step; the residual before it was zero, so
    gradient + residual is the gradient.

    sent_count_mismatch  leaves x workers whose sent set is not exactly keep
    resid_overlap        sent coordinates whose residual is not exactly 0
    order_violations     unsent coordinates before a worker's last sent index
                         with a magnitude above that worker's smallest sent one
    surplus              unsent coordinates after it at or above that magnitude
                         (ties the threshold admits; reported, no limit 0)
    wire_bits_diff       the step's own comm/sent_bits against ``wire_bits``
    """
    ratio = compression["ratio"]
    mismatch = overlap = violations = surplus = 0
    for g, e in zip(g1, ef1):
        world = e.shape[0]
        g, e = g.ravel(), e.reshape(world, -1)
        n = g.size
        keep = keep_count(n, ratio)
        applied = g != 0
        if keep >= n:
            mismatch += int(np.count_nonzero(e))
            continue
        sent = applied[None, :] & (e == 0)              # [world, n]
        solo = sent & (sent.sum(axis=0) == 1)[None, :]
        for w in range(world):
            idx = np.flatnonzero(sent[w])
            if idx.size != keep:
                mismatch += 1
            if idx.size == 0 or not solo[w].any():
                continue
            overlap += int(np.count_nonzero(e[w, idx]))
            smallest = np.min(np.abs(g[solo[w]])) * world
            mag = np.abs(e[w])
            violations += int(np.count_nonzero(mag[:idx[-1]] > smallest))
            surplus += int(np.count_nonzero(mag[idx[-1] + 1:] >= smallest))
    return {"sent_count_mismatch": mismatch, "resid_overlap": overlap,
            "order_violations": violations, "surplus": surplus,
            "wire_bits_diff": abs(counters.get("comm/sent_bits", -1.0)
                                  - wire_bits(sizes, compression))}
