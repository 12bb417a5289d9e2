"""Reference optimizer ``sgd`` (a configuration's ``optimizer.kind``): SGD with
momentum and weight decay added to the gradient, as torch.optim.SGD does.
numpy; imports nothing of the program."""

from __future__ import annotations

import numpy as np


def init(leaves):
    return [np.zeros_like(l) for l in leaves]


def update(params, buf, applied, opt: dict):
    """-> (params, buf) after one step on the applied gradient."""
    lr, mu, wd = (np.float32(opt[k]) for k in ("lr", "momentum", "weight_decay"))
    if opt.get("nesterov"):
        raise NotImplementedError("reference sgd: nesterov")
    buf = [mu * b + (g + wd * p) for b, g, p in zip(buf, applied, params)]
    return [p - lr * b for p, b in zip(params, buf)], buf


def first_gradient(p0, state1, opt: dict):
    """The first gradient as the optimizer got it, from the program's optimizer
    state after one step: the momentum buffer less the weight decay it added."""
    return [b - np.float32(opt["weight_decay"]) * p for b, p in zip(state1, p0)]
