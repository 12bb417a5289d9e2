"""The reference's first training steps: the configuration's model reference
(``cfg["reference"]``: loss and gradient in plain float32), the exchange the
traffic file's ``sync`` states and the optimizer ``cfg["optimizer"]["kind"]``
names, put together on the host in numpy.  Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(leaves) -> np.ndarray:
    return np.array([float(np.sqrt(np.sum(np.square(np.asarray(l, np.float64)))))
                     for l in leaves])


def train_steps(model, optim, sync, cfg, compression, params0, batches, world,
                precision="float32"):
    """Follow the trainer's first ``len(batches)`` steps from ``params0``.

    ``batches`` are global (inputs [world*b, ...], labels); worker ``w`` owns
    rows ``[w*b, (w+1)*b)``.  Returns per-step losses (mean over workers),
    per-leaf norms of the first gradient as the optimizer receives it
    (``grad1``), of the mean local gradient (``mean_grad1``), of whatever else
    the sync semantics compares, and of the parameter change after the last
    step (``dparam``); ``aux1``, the model's auxiliary outputs of the first
    step averaged over workers (leaves in tree order); ``matrices``, which
    leaves are weight tensors.  ``precision`` other than float32 computes the
    model in that lower precision: the control.
    """
    grad = model.make_loss_and_grad(cfg, precision)
    leaves0, treedef = jax.tree.flatten(
        jax.tree.map(lambda a: np.asarray(a, np.float32), params0))
    p = [l.copy() for l in leaves0]
    opt_state = optim.init(p)
    sync_state = sync.init(p, world, compression)
    out = {"loss": [], "matrices": np.array([l.ndim > 1 for l in p])}
    for t, (inputs, labels) in enumerate(batches):
        rows = inputs.shape[0] // world
        dev_params = jax.tree.unflatten(treedef, [jnp.asarray(l) for l in p])
        losses, grads, aux = [], [], None
        for w in range(world):
            sl = slice(w * rows, (w + 1) * rows)
            (loss, a), g = grad(dev_params, jnp.asarray(inputs[sl]),
                                jnp.asarray(labels[sl], jnp.int32))
            losses.append(float(loss))
            grads.append([np.asarray(l, np.float32) for l in jax.tree.leaves(g)])
            if t == 0:      # the trainer averages the workers' auxiliary statistics
                a = [np.asarray(l, np.float64) / world for l in jax.tree.leaves(a)]
                aux = a if aux is None else [x + y for x, y in zip(aux, a)]
        del dev_params
        applied, sync_state = sync.exchange(grads, sync_state, compression)
        out["loss"].append(float(np.mean(losses)))
        if t == 0:
            out["aux1"] = aux
            out["grad1"] = leaf_norms(applied)
            out["mean_grad1"] = leaf_norms(
                [sum(g[i] for g in grads) / world for i in range(len(p))])
            for kind, tree in sync.reference_trees(sync_state).items():
                out[kind] = leaf_norms(tree)
        del grads
        p, opt_state = optim.update(p, opt_state, applied, cfg["optimizer"])
    out["dparam"] = leaf_norms([a - b for a, b in zip(p, leaves0)])
    return out
