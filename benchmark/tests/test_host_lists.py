"""The comparison's host side: how many lists of the model's size it holds at
once, and that what it computes in place and block by block is what the plain
expressions compute.  CPU, seconds; no model runs: the gradient is a cheap
function of the parameters, and the program's side of ``raw`` is made here by
the allocating form of the same steps, so every gap must read nought.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_host_lists.py -q
"""

from __future__ import annotations

import os
import sys
import tracemalloc
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from reference import steps  # noqa: E402
from reference.optim import sgd  # noqa: E402

OPT = {"kind": "sgd", "lr": 0.01, "momentum": 0.9, "weight_decay": 1e-4,
       "nesterov": False}
TOPK = {"method": "topk", "ratio": 0.01, "granularity": "layerwise",
        "mode": "wire", "error_feedback": True}
# 16 weight tensors of 2^20 elements and a vector: 64 MiB a list
SHAPES = [(512, 2048)] * 16 + [(2048,)]
STEPS = 3


def gradient_of(leaves):
    return [np.float32(0.01) * l + np.float32(0.001) for l in leaves]


class Model:
    """A model file's interface with no model behind it."""
    param_shapes = staticmethod(lambda cfg: list(SHAPES))
    model_numbers = staticmethod(lambda *a: {})
    aux_as_probed = staticmethod(lambda aux, cfg: aux)

    @staticmethod
    def make_loss_and_grad(cfg, precision="float32"):
        return lambda params, inputs, labels: (
            (np.float32(1.0), []), gradient_of([np.asarray(l) for l in params]))


def fake_cell(sync_name: str):
    sync = run.load_module(f"benchmark/sync/{sync_name}.py")
    comp = TOPK if sync_name != "dense" else {"method": None}
    names = [f"{k}{s}" for k in sync.KINDS for s in ("_gap", "_median_gap")]
    names += ["loss1_gap", "loss_gap", "compiles_in_window", "failed_steps"]
    names += list(sync.exact_checks([], [], comp, {}, []))
    return types.SimpleNamespace(
        name="fake", chips=1, cfg={"optimizer": OPT}, traffic={"compression": comp},
        limits={n: 1e-6 for n in names}, check_params={}, model=Model,
        optim=run.load_module("benchmark/reference/optim/sgd.py"), sync=sync)


def fake_raw(cell, seed=0):
    """What ``drive_first_steps`` would have read of a program that is right:
    the same steps by the allocating forms."""
    rng = np.random.default_rng(seed)
    p0 = [rng.standard_normal(s, dtype=np.float32) for s in SHAPES]
    comp = cell.traffic["compression"]
    p, buf = p0, [np.zeros_like(l) for l in p0]
    resid = [np.zeros_like(l) for l in p0] if comp["method"] else None
    probe1 = None
    for t in range(STEPS):
        g = gradient_of(p)
        if resid is not None:
            pairs = [cell.sync.split((a + r).ravel(), comp["ratio"])
                     for a, r in zip(g, resid)]
            g = [sent.reshape(l.shape) for (sent, _), l in zip(pairs, p)]
            resid = [res.reshape(l.shape) for (_, res), l in zip(pairs, p)]
        p, buf = sgd.update(p, buf, g, OPT)
        if t == 0:
            probe1 = {"opt": [b.copy() for b in buf], "aux": [],
                      "ef": resid and [r[None].copy() for r in resid]}
    sizes = [l.size for l in p0]
    bits = cell.sync.wire_bits(sizes, comp)
    return {"p0": p0, "probe1": probe1, "p3": p, "loss": [1.0] * STEPS,
            "counters": {} if bits is None else {"comm/sent_bits": float(bits)},
            "first": [(np.zeros((1, 8), np.int32), np.zeros((1, 8), np.int32))] * STEPS}


@pytest.mark.parametrize("sync_name", ["dense", "topk_layerwise_ef"])
def test_the_comparison_holds_under_six_lists_of_the_model(sync_name):
    """The peak of traced bytes from the moment ``raw`` is whole, in lists of
    the model's float32 size.  Before the program's side was reduced first and
    the reference updated in place this read 10.3 lists in the dense semantics
    (11.3 with the parameters the probe then copied out after step 1 as well):
    ``p0``, the probe's two, ``p3``, and the reference's parameters, momentum,
    gradient, applied and mean gradient with the update's two new lists.  Now
    dense holds four (``p0``, parameters, momentum, gradient) and Top-K five
    (the residual), and a few leaves of temporaries."""
    cell = fake_cell(sync_name)
    model_bytes = 4 * sum(int(np.prod(s)) for s in SHAPES)
    tracemalloc.start()
    try:
        raw = fake_raw(cell)
        tracemalloc.reset_peak()
        rows = run.judge(cell, raw, {"compiles_in_window": 0, "failed_steps": 0})
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert all(ok for *_, ok in rows), [r for r in rows if not r[-1]]
    assert "probe1" not in raw and "p3" not in raw       # reduced, and let go
    lists = peak / model_bytes
    print(f"{sync_name}: {lists:.2f} lists at the peak")
    assert lists < 6.0


def test_update_in_place_is_bitwise_the_allocating_form():
    """``sgd.update`` is the allocating form, as it stood before the steps worked
    in place (tier-1's tests/test_ouro.py still calls it)."""
    rng = np.random.default_rng(1)
    shapes = [(1,), (2048,), (3, 5, 7), (1200, 2048)]     # the last: two blocks and a part
    p = [rng.standard_normal(s, dtype=np.float32) for s in shapes]
    want_p, want_b = [l.copy() for l in p], [np.zeros_like(l) for l in p]
    buf = sgd.init(p)
    for _ in range(STEPS):
        g = [rng.standard_normal(s, dtype=np.float32) for s in shapes]
        g[-1].setflags(write=False)       # a gradient off the device is read-only,
        g[2] = np.asfortranarray(g[2])    # and a convolution kernel's not in C order
        want_p, want_b = sgd.update(want_p, want_b, g, OPT)
        got_p, got_b = sgd.update_in_place(p, buf, g, OPT)
        assert got_p is p and got_b is buf
        for got, want in zip(p + buf, want_p + want_b):
            assert got.dtype == np.float32
            assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    wd = np.float32(OPT["weight_decay"])
    want_g = [b - wd * q for b, q in zip(want_b, want_p)]
    got_g = sgd.first_gradient(p, buf, OPT)
    assert got_g is buf
    for got, want in zip(got_g, want_g):
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    with pytest.raises(ValueError, match="in place"):
        sgd.update_in_place([np.ones((4, 4), np.float32).T[:2]],
                            [np.zeros((2, 4), np.float32)],
                            [np.ones((2, 4), np.float32)], OPT)


@pytest.mark.parametrize("n", [1, 10_000_000, 30_000_001])
def test_leaf_norms_equal_the_float64_copys_norm_and_make_no_copy(n):
    rng = np.random.default_rng(n)
    x = rng.standard_normal(n, dtype=np.float32)
    y = rng.standard_normal(n, dtype=np.float32)
    want = np.linalg.norm(x.astype(np.float64))
    want_diff = np.linalg.norm((x - y).astype(np.float64))
    tracemalloc.start()
    try:
        got = steps.leaf_norms([x.reshape(-1, 1)])[0]
        got_diff = steps.diff_norms([x], [y])[0]
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert abs(got - want) <= 1e-12 * want
    assert abs(got_diff - want_diff) <= 1e-12 * want_diff
    # one block of scratch, never a leaf: a float64 copy of 3e7 elements is 240 MB
    assert peak < 8 * steps.BLOCK
