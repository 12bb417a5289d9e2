"""Plain reference for the ResNet configurations: forward, loss, gradient and
the first training steps in straightforward ``jax.numpy`` float32.

Follows arXiv:1512.03385 (bottleneck v1, stride on the 3x3 as torchvision has
it, which is what the reference trainer of the paper under test used), with
train-mode BatchNorm over the worker's own rows (the trainer does not
synchronise BN statistics).  Imports nothing of the program under test.  A
configuration names this file under ``"reference"``; the host half of a step
(exchange, optimizer) is in ``steps.py``, ``sync/`` and ``optim/``.

``precision`` selects the arithmetic of every convolution and the classifier:
``float32`` (the reference), or the emulated ``bfloat16`` / ``fp8`` used by the
control, which holds every operand and result, and every cotangent on the way
back, in that type and keeps float32 accumulation inside each product.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
MEAN = (0.485 * 255, 0.456 * 255, 0.406 * 255)
STD = (0.229 * 255, 0.224 * 255, 0.225 * 255)


# ------------------------------------------------------------------ structure

def blocks(cfg):
    """[(name, cin, mid, cout, stride, downsample)] for every bottleneck."""
    out, cin = [], cfg["stem_width"]
    for s, (n, cout) in enumerate(zip(cfg["stage_blocks"], cfg["stage_widths"])):
        mid = cout // cfg["bottleneck_expansion"]
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            out.append((f"layer{s + 1}_{b}", cin, mid, cout, stride,
                        b == 0 and (stride != 1 or cin != cout)))
            cin = cout
    return out


def convs(cfg):
    """[(path, kernel, cin, cout, stride, input_hw)] of every convolution, in
    forward order; the FLOP function and the weight generator read this."""
    hw = cfg["image_size"]
    out = [(("conv1",), 7, 3, cfg["stem_width"], 2, hw)]
    hw = hw // 2 // 2                       # stem stride, then the max-pool
    for name, cin, mid, cout, stride, ds in blocks(cfg):
        out.append(((name, "conv1"), 1, cin, mid, 1, hw))
        out.append(((name, "conv2"), 3, mid, mid, stride, hw))
        if ds:
            out.append(((name, "ds_conv"), 1, cin, cout, stride, hw))
        hw //= stride
        out.append(((name, "conv3"), 1, mid, cout, 1, hw))
    return out


def param_shapes(cfg):
    """Nested dict of parameter shapes; leaf names as the paper's layers are
    usually spelled (conv/bn per block, ``fc``)."""
    tree = {}

    def put(path, leaf, shape):
        node = tree
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = shape

    for path, k, cin, cout, _, _ in convs(cfg):
        put(path, "kernel", (k, k, cin, cout))
        bn = path[:-1] + ("ds_bn" if path[-1] == "ds_conv"
                          else path[-1].replace("conv", "bn"),)
        put(bn, "scale", (cout,))
        put(bn, "bias", (cout,))
    put(("fc",), "kernel", (cfg["stage_widths"][-1], cfg["num_classes"]))
    put(("fc",), "bias", (cfg["num_classes"],))
    return tree


def make_params(cfg, key):
    """Seeded float32 weights: He-normal (fan-out) kernels, unit BN scale,
    LeCun-normal classifier.  One traced function, so one device program."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        leaf = path[-1].key
        if leaf == "scale":
            out.append(jnp.ones(shape, jnp.float32))
        elif leaf == "bias":
            out.append(jnp.zeros(shape, jnp.float32))
        elif len(shape) == 4:
            fan_out = shape[0] * shape[1] * shape[3]
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * math.sqrt(2.0 / fan_out))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * math.sqrt(1.0 / shape[0]))
    return jax.tree.unflatten(treedef, out)


# -------------------------------------------------------------------- forward

def _quantize(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":           # e4m3 with a per-tensor scale
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        # clipped: past 448 the type has only NaN, and a rounded scale can
        # carry the largest element a hair over
        return jnp.clip(x * s, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / s
    raise ValueError(f"unknown precision {precision!r}")


def _round_to(x, precision):
    """``x`` as a tensor of ``precision`` would hold it, and its cotangent on
    the way back likewise: what computing in that precision does to every
    operand and result, with float32 accumulation inside each product."""
    if precision == "float32":
        return x

    @jax.custom_vjp
    def rounded(v):
        return _quantize(v, precision)

    rounded.defvjp(lambda v: (_quantize(v, precision), None),
                   lambda _, g: (_quantize(g, precision),))
    return rounded(x)


def _conv(x, w, stride, precision):
    p = w.shape[0] // 2
    return _round_to(jax.lax.conv_general_dilated(
        _round_to(x, precision), _round_to(w, precision), (stride, stride),
        ((p, p), (p, p)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST), precision)


def _bn(x, p, precision):
    """Train-mode BatchNorm; also returns the batch statistics it used."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    return _round_to(y, precision), {"mean": mean, "var": var}


def _bottleneck(p, x, stride, precision):
    stats = {}
    out, stats["bn1"] = _bn(_conv(x, p["conv1"]["kernel"], 1, precision), p["bn1"], precision)
    out, stats["bn2"] = _bn(_conv(jax.nn.relu(out), p["conv2"]["kernel"], stride, precision),
                            p["bn2"], precision)
    out, stats["bn3"] = _bn(_conv(jax.nn.relu(out), p["conv3"]["kernel"], 1, precision),
                            p["bn3"], precision)
    if "ds_conv" in p:
        x, stats["ds_bn"] = _bn(_conv(x, p["ds_conv"]["kernel"], stride, precision),
                                p["ds_bn"], precision)
    return _round_to(jax.nn.relu(out + x), precision), stats


def forward(params, images_u8, cfg, precision="float32"):
    """Train-mode (logits float32, batch statistics of every BatchNorm) of
    uint8 NHWC images."""
    x = (images_u8.astype(jnp.float32) - jnp.asarray(MEAN, jnp.float32)) \
        / jnp.asarray(STD, jnp.float32)
    stats = {}
    x, stats["bn1"] = _bn(_conv(x, params["conv1"]["kernel"], 2, precision),
                          params["bn1"], precision)
    x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, _, stride, _ in blocks(cfg):
        # one block's activations live at a time: float32 at the timed batch
        # would not fit beside itself otherwise
        x, stats[name] = jax.checkpoint(partial(
            _bottleneck, stride=stride, precision=precision))(params[name], x)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(_round_to(x, precision),
                     _round_to(params["fc"]["kernel"], precision),
                     precision=HIGHEST) + params["fc"]["bias"]
    return logits, stats


def loss_fn(params, images_u8, labels, cfg, precision="float32"):
    logits, stats = forward(params, images_u8, cfg, precision)
    logz = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logz, labels[:, None], axis=1)), stats


def make_loss_and_grad(cfg, precision="float32"):
    """jitted (params, images, labels) -> ((loss, batch statistics), grads)."""
    return jax.jit(jax.value_and_grad(
        partial(loss_fn, cfg=cfg, precision=precision), has_aux=True))


# ------------------------------------- what the benchmark asks of a model file

def forward_flops_per_sample(cfg) -> float:
    """2 x multiply-accumulates of every convolution and the classifier."""
    total = 0.0
    for _, k, cin, cout, stride, hw in convs(cfg):
        out_hw = hw // stride
        total += 2.0 * k * k * cin * cout * out_hw * out_hw
    return total + 2.0 * cfg["stage_widths"][-1] * cfg["num_classes"]


def bn_forward_order(cfg) -> list:
    """Position in tree order (sorted names, as ``jax.tree.leaves`` walks the
    statistics) of every BatchNorm layer, listed in forward order."""
    tree = ["bn1"]
    fwd = ["bn1"]
    for name, _, _, _, _, ds in blocks(cfg):
        layers = ["bn1", "bn2", "bn3"] + (["ds_bn"] if ds else [])
        fwd += [f"{name}/{l}" for l in layers]
        tree += [f"{name}/{l}" for l in sorted(layers)]
    tree = sorted(tree, key=lambda n: n.split("/"))
    return [tree.index(n) for n in fwd]


def bn_var_gaps(prog_stats, ref_stats, cfg) -> np.ndarray:
    """Per BatchNorm layer, in forward order: mean over channels of |program's
    batch variance - reference's| over the reference's.  ``*_stats`` are
    leaves in tree order, (mean, var) of every layer.  A forward quantity
    averaged over 11 k to 2.8 M values a channel: steady from seed to seed
    where gradients at seeded weights are not, and it grows with the rounding
    error of every operand upstream, so it tells one precision from the next."""
    out = []
    for vp, vr in zip(prog_stats[1::2], ref_stats[1::2]):
        vp, vr = np.asarray(vp, np.float64), np.asarray(vr, np.float64)
        out.append(float(np.mean(np.abs(vp - vr) / np.maximum(vr, 1e-12))))
    return np.array(out)[bn_forward_order(cfg)]


def aux_as_probed(aux1, cfg) -> list:
    """Batch statistics of one step in the form the builder's probe reads them
    from the program: running statistics after that step from (0, 1)."""
    keep = np.float64(cfg["bn_momentum"])
    return [keep * (i % 2) + (1.0 - keep) * np.asarray(a, np.float64)
            for i, a in enumerate(aux1)]


def model_numbers(prog_aux1, ref_aux1, cfg, params: dict) -> dict:
    """The numbers only this model has.  ``prog_aux1``: the program's BatchNorm
    running statistics after one step from (0, 1); ``ref_aux1``: the
    reference's batch statistics of that step; ``params``: the limits file's.

    bn_var_gap         mean over the first ``bn_early_layers`` layers (the stem
                       and the first block), where rounding has not compounded
    bn_var_median_gap  median over the first ``bn_median_layers`` layers in
                       forward order: every stage of ResNet-50; in ResNet-152
                       rounding compounds past them until bf16's own gap is
                       half of fp8's and no limit separates the two
    """
    keep = np.float64(cfg["bn_momentum"])
    # undo the moving average: running = keep * (0 | 1) + (1 - keep) * batch
    batch1 = [(np.asarray(r, np.float64) - keep * (i % 2)) / (1.0 - keep)
              for i, r in enumerate(prog_aux1)]
    gaps = bn_var_gaps(batch1, ref_aux1, cfg)
    return {"bn_var_gap": float(np.mean(gaps[:params["bn_early_layers"]])),
            "bn_var_median_gap": float(np.median(gaps[:params["bn_median_layers"]]))}
