"""The reference's first training steps: the configuration's model reference
(``cfg["reference"]``: loss and gradient in plain float32), the exchange the
traffic file's ``sync`` states and the optimizer ``cfg["optimizer"]["kind"]``
names, put together on the host in numpy.  Imports nothing of the program.

A whole-model list costs 4 B a parameter of host memory, and a page the process
has not touched before costs more time than the arithmetic on it.  So the
steps keep one parameter list, one optimizer state and one gradient list a
worker, update in place, and take norms block by block: nothing here makes a
second copy of a list, or a float64 copy of a leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 20     # elements a pass holds at once: 4 MB of float32 stays in the cache


def blocks(*leaves):
    """Matching flat blocks of leaves of one shape.  A block of a C-contiguous
    leaf is a view: writing to it writes to the leaf."""
    flat = [np.asarray(l).reshape(-1) for l in leaves]
    for i in range(0, flat[0].size, BLOCK):
        yield [f[i:i + BLOCK] for f in flat]


def _norm(parts) -> float:
    # float64 accumulation with no float64 copy: einsum casts as it reads
    return float(np.sqrt(sum(float(np.einsum("i,i->", x, x, dtype=np.float64))
                             for x in parts)))


def leaf_norms(leaves) -> np.ndarray:
    """The Euclidean norm of every leaf, accumulated in float64.  ``leaves``
    may be a generator: one leaf is alive at a time."""
    return np.array([_norm(x for x, in blocks(l)) for l in leaves])


def diff_norms(after, before) -> np.ndarray:
    """``leaf_norms`` of ``after - before`` (the float32 difference, as numpy
    subtracts two float32 leaves) with no list and no leaf of differences."""
    scratch = np.empty(BLOCK, np.float32)
    return np.array([_norm(np.subtract(a, b, out=scratch[:a.size])
                           for a, b in blocks(*pair))
                     for pair in zip(after, before)])


def train_steps(model, optim, sync, cfg, compression, params0, batches, world,
                precision="float32"):
    """Follow the trainer's first ``len(batches)`` steps from ``params0``,
    which is read and never written.

    ``batches`` are global (inputs [world*b, ...], labels); worker ``w`` owns
    rows ``[w*b, (w+1)*b)``.  Returns per-step losses (mean over workers),
    per-leaf norms of the first gradient as the optimizer receives it
    (``grad1``), of the mean local gradient (``mean_grad1``), of whatever else
    the sync semantics compares, and of the parameter change after the last
    step (``dparam``); ``aux1``, the model's auxiliary outputs of the first
    step averaged over workers (leaves in tree order); ``matrices``, which
    leaves are weight tensors.  Norms and small vectors only: no list of the
    model's size outlives the call.  ``precision`` other than float32 computes
    the model in that lower precision: the control.
    """
    grad = model.make_loss_and_grad(cfg, precision)
    leaves0, treedef = jax.tree.flatten(
        jax.tree.map(lambda a: np.asarray(a, np.float32), params0))
    # the one copy: the steps update it in place, and ``leaves0`` stays for dparam
    p = [np.array(l, order="C") for l in leaves0]
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
            del g           # the host's copy is the only one from here
            if t == 0:      # the trainer averages the workers' auxiliary statistics
                a = [np.asarray(l, np.float64) / world for l in jax.tree.leaves(a)]
                aux = a if aux is None else [x + y for x, y in zip(aux, a)]
        del dev_params
        out["loss"].append(float(np.mean(losses)))
        if t == 0:
            out["aux1"] = aux
            # one worker's mean is its own gradient; more are summed a leaf at a time
            out["mean_grad1"] = leaf_norms(grads[0] if world == 1 else (
                sum(g[i] for g in grads) / world for i in range(len(p))))
        applied, sync_state = sync.exchange(grads, sync_state, compression)
        del grads           # the exchange has released each leaf it was done with
        if t == 0:
            out["grad1"] = leaf_norms(applied)
            for kind, tree in sync.reference_trees(sync_state).items():
                out[kind] = leaf_norms(tree)
        p, opt_state = optim.update_in_place(p, opt_state, applied, cfg["optimizer"])
        del applied
    out["dparam"] = diff_norms(p, leaves0)
    return out
