"""Reference optimizer ``sgd`` (a configuration's ``optimizer.kind``): SGD with
momentum and weight decay added to the gradient, as torch.optim.SGD does.
numpy; imports nothing of the program.

``update`` is the plain statement: it builds new lists.  The reference's
steps call ``update_in_place``, and the comparison ``first_gradient``: they
write into the lists they are given, block by block through one scratch
buffer, because a second list of the model's size, or a leaf-sized temporary,
is host memory and fresh pages that the arithmetic does not need.  The float32
operations and their order are ``update``'s, so the results are its bit for
bit (``benchmark/tests/test_host_lists.py``).
"""

from __future__ import annotations

import numpy as np

BLOCK = 1 << 20     # elements of scratch: 4 MB of float32 stays in the cache


def init(leaves):
    return [np.zeros(l.shape, l.dtype) for l in leaves]


def update(params, buf, applied, opt: dict):
    """-> (params, buf) after one step on the applied gradient, as new lists."""
    lr, mu, wd = (np.float32(opt[k]) for k in ("lr", "momentum", "weight_decay"))
    if opt.get("nesterov"):
        raise NotImplementedError("reference sgd: nesterov")
    buf = [mu * b + (g + wd * p) for b, g, p in zip(buf, applied, params)]
    return [p - lr * b for p, b in zip(params, buf)], buf


def _blocks(*leaves):
    """Matching flat blocks of leaves of one shape.  A block of a C-contiguous
    leaf is a view: writing to it writes to the leaf."""
    flat = [np.asarray(l).reshape(-1) for l in leaves]
    for i in range(0, flat[0].size, BLOCK):
        yield [f[i:i + BLOCK] for f in flat]


def _writable(*lists):
    for leaf in (l for leaves in lists for l in leaves):
        if not (leaf.flags.c_contiguous and leaf.flags.writeable):
            raise ValueError("reference sgd works in place: it needs writable, "
                             "C-contiguous leaves")


def update_in_place(params, buf, applied, opt: dict):
    """``update`` written into ``params`` and ``buf``, which it returns."""
    lr, mu, wd = (np.float32(opt[k]) for k in ("lr", "momentum", "weight_decay"))
    if opt.get("nesterov"):
        raise NotImplementedError("reference sgd: nesterov")
    _writable(params, buf)
    scratch = np.empty(BLOCK, np.float32)
    for leaves in zip(params, buf, applied):
        for p, b, g in _blocks(*leaves):
            s = scratch[:p.size]
            np.multiply(wd, p, out=s)
            np.add(g, s, out=s)
            np.multiply(mu, b, out=b)
            np.add(b, s, out=b)
            np.multiply(lr, b, out=s)
            np.subtract(p, s, out=p)
    return params, buf


def first_gradient(p0, state1, opt: dict):
    """The first gradient as the optimizer got it, from the program's optimizer
    state after one step: the momentum buffer less the weight decay it added,
    ``state1 - wd * p0``, written over ``state1`` and returned."""
    wd = np.float32(opt["weight_decay"])
    _writable(state1)
    scratch = np.empty(BLOCK, np.float32)
    for leaves in zip(state1, p0):
        for b, p in _blocks(*leaves):
            s = scratch[:p.size]
            np.multiply(wd, p, out=s)
            np.subtract(b, s, out=b)
    return state1
