"""The comparison that decides ``correct``.

Both sides followed the same first three steps from the same seeded weights on
the same batches: the program through the loop and the compiled step that the
window then drives, the reference in plain float32.  Norms are compared leaf by
leaf as the gap between the two norms, against the reference's norm of that
leaf or of the median leaf, whichever is larger (some gradients are all but
zero).  Of each kind of norm two numbers are held to a limit: the worst weight
tensor's gap, which a lost leaf or an unchanged state drives to 1, and the
median weight tensor's, which is steady from seed to seed and which a lower
precision, a dropped momentum or a part of the batch left out moves at once.
What only one model or one sync semantics can say (batch statistics; exact
counts on the payload) comes from their own files.
"""

from __future__ import annotations

import math

import numpy as np

from reference.steps import diff_norms, leaf_norms


def leaf_gaps(prog: np.ndarray, refr: np.ndarray) -> np.ndarray:
    """Per leaf: |program's norm - reference's norm| over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    return np.abs(prog - refr) / np.maximum(refr, np.median(refr))


def program_readings(optim, sync, opt_cfg: dict, compression: dict, raw: dict,
                     sizes) -> dict:
    """What the step left in its state, reduced to per-leaf norms, the sync
    semantics' exact counts (``exact``) and the model's statistics (``aux1``).
    ``raw`` is what ``drive_first_steps`` read: ``p0`` the parameters before
    the first step, ``p3`` after the last, ``probe1`` what the builder's
    ``probe`` read after the first (optimizer state, residual: [world, ...]
    leaves or None).  ``p3`` and ``probe1`` are taken out of ``raw`` and each
    list is let go once it is reduced, so that the reference starts with no
    list of the program's beside ``p0``: a side is reduced once."""
    p0, probe1 = raw["p0"], raw.pop("probe1")
    out = {"loss": list(raw["loss"]), "aux1": probe1["aux"],
           "dparam": diff_norms(raw.pop("p3"), p0)}
    # in place: the optimizer state's list becomes the gradient's
    g1 = optim.first_gradient(p0, probe1.pop("opt"), opt_cfg)
    out["grad1"] = out["mean_grad1"] = leaf_norms(g1)
    for kind, tree in sync.program_trees(g1, probe1["ef"]).items():
        out[kind] = leaf_norms(tree)
    out["exact"] = sync.exact_checks(g1, probe1["ef"], compression,
                                     raw["counters"], sizes)
    return out


def gap_numbers(prog: dict, refr: dict, kinds) -> dict:
    """Every number that is a gap between the two sides' first steps, but for
    the model's own."""
    m = refr["matrices"]
    rel = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], refr["loss"])]
    numbers = {"loss1_gap": rel[0], "loss_gap": max(rel)}
    for kind in kinds:
        # the weight tensors (convolution and classifier kernels, 99.8 % of the
        # parameters); the vectors (BN scale and bias, the classifier's bias)
        # are left out: their gradients are sums of up to 2.8 M signed bf16
        # terms that all but cancel, and in sound runs their norms miss the
        # reference's by 17 to 41 %, as the fp8 control's do (PERF.md section 2)
        gaps = leaf_gaps(np.asarray(prog[kind]), np.asarray(refr[kind]))[m]
        numbers[f"{kind}_gap"] = float(np.max(gaps))
        numbers[f"{kind}_median_gap"] = float(np.median(gaps))
    return numbers


def compare(numbers: dict, limits: dict) -> list:
    """[(name, value, limit, ok)].  Every number compared has a limit and
    every limit a number: a cell whose files state a check that nothing
    computes is an error, not a pass."""
    missing = set(numbers) ^ set(limits)
    if missing:
        raise KeyError("compared numbers and limits differ: " + ", ".join(sorted(missing)))
    rows = []
    for name, value in numbers.items():
        limit = limits[name]
        ok = math.isfinite(value) and value <= limit
        rows.append((name, float(value), float(limit), bool(ok)))
    return rows
