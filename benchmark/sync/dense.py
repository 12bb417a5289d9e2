"""Sync semantics ``dense``: every worker's gradient travels whole and the
applied gradient is their mean (an all-reduce over the data axis).

A traffic file names its semantics under ``"sync"``; the harness loads
``benchmark/sync/<name>.py``.  Every such module gives the reference's side of
the exchange (numpy, imports nothing of the program), the checks that need no
tolerance on what the program's step left in its state, and the bits one
worker sends a step.  ``accepts`` refuses a ``compression`` block the module
does not implement, so that a method nobody wrote the semantics of cannot be
followed as if it were this one.
"""

from __future__ import annotations

import numpy as np

KINDS = ("grad1", "dparam")     # the per-leaf norms compared in this semantics


def accepts(compression: dict) -> None:
    if compression.get("method") is not None:
        raise SystemExit("sync 'dense' states an uncompressed exchange; the traffic "
                         f"file asks for method {compression['method']!r}: name the "
                         "benchmark/sync/<name>.py that states its semantics")


def init(leaves, world: int, compression: dict):
    return None


def exchange(grads, state, compression: dict):
    """``grads[w][i]``: worker w's gradient of leaf i.  -> (applied, state).
    An exchange may release ``grads[w][i]`` once it is done with it (a list of
    the model's size is host memory): the caller reads ``grads`` before."""
    world = len(grads)
    if world == 1:      # the mean of one is itself: no second list
        return grads[0], state
    return [sum(g[i] for g in grads) / np.float32(world)
            for i in range(len(grads[0]))], state


def reference_trees(state) -> dict:
    """Further per-leaf trees of the reference's first step to take norms of."""
    return {}


def program_trees(g1, ef1) -> dict:
    """The same trees from the program's state after its first step."""
    return {}


def exact_checks(g1, ef1, compression: dict, counters: dict, sizes) -> dict:
    return {}


def wire_bits(sizes, compression: dict):
    return None
