"""Program builder ``image_dp`` (a configuration names it under ``"program"``):
the data-parallel image trainer, built from a cell's configuration and traffic
files.

The builders are the only files of the benchmark that import the program.
This one takes the model, the optimizer, the sync engine, the train step, the
loop and the loader from it, and gives them the benchmark's own seeded weights
(the configuration's reference makes them) and inputs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P



@dataclasses.dataclass
class Program:
    mesh: object
    train_step: object          # the program's jitted step (state, batch)
    run_epoch: object           # the program's loop
    make_state: object          # seed -> TrainState laid out on the mesh
    make_pool: object           # (seed, n) -> list of resident global batches
    make_loader: object         # seed -> the program's TrainLoader
    global_batch: int
    probe: object               # state -> what the comparison reads of it, on the host
    constants: dict             # thresholds of the program that a reader needs


def build(cfg: dict, traffic: dict, devices, model) -> Program:
    from tpu_compressed_dp.data import imagenet as data
    from tpu_compressed_dp.harness.loop import run_train_epoch
    from tpu_compressed_dp.models import resnet as resnet_mod
    from tpu_compressed_dp.models.common import make_normalizing_apply_fn
    from tpu_compressed_dp.ops import kernels
    from tpu_compressed_dp.parallel.dp import (CompressionConfig,
                                               init_comp_state, init_ef_state)
    from tpu_compressed_dp.parallel.mesh import make_data_mesh
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.state import TrainState
    from tpu_compressed_dp.train.step import make_train_step

    world = int(traffic["chips"])
    mesh = make_data_mesh(world, devices=devices)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["compute_dtype"]]
    module = getattr(resnet_mod, cfg["arch"])(
        num_classes=cfg["num_classes"], dtype=dtype, width=cfg["stem_width"])
    apply_fn = make_normalizing_apply_fn(module, data.IMAGENET_MEAN,
                                         data.IMAGENET_STD)
    o = cfg["optimizer"]
    opt = SGD(lr=o["lr"], momentum=o["momentum"], nesterov=o["nesterov"],
              weight_decay=o["weight_decay"])
    comp = CompressionConfig(**traffic["compression"])
    train_step = make_train_step(apply_fn, opt, comp, mesh, grad_scale=1.0)

    size, batch = cfg["image_size"], cfg["per_chip_batch"] * world
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    # the program's own tree, to hold the benchmark's weights to its shapes
    want = jax.eval_shape(
        lambda: module.init({"params": jax.random.key(0)},
                            jnp.zeros((1, size, size, 3), jnp.float32),
                            train=False))

    def state_from_seed(seed):
        params = model.make_params(cfg, jax.random.key(seed))
        got = jax.tree.map(lambda a: a.shape, params)
        exp = jax.tree.map(lambda a: a.shape, want["params"])
        if got != exp:
            raise ValueError("the configuration's parameter tree is not the "
                             "program's: " + str(set(map(str, jax.tree.leaves(got)))
                                                 ^ set(map(str, jax.tree.leaves(exp))))[:300])
        stats = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.ones(a.shape, a.dtype)
            if path[-1].key == "var" else jnp.zeros(a.shape, a.dtype),
            want["batch_stats"])
        return TrainState.create(
            params, stats, opt.init(params), init_ef_state(params, comp, world),
            jax.random.key(seed + 1), comp=init_comp_state(params, comp, world))

    abstract = jax.eval_shape(state_from_seed, 0)
    shardings = dataclasses.replace(
        jax.tree.map(lambda _: rep, abstract),
        ef=jax.tree.map(lambda _: dat, abstract.ef),
        comp=jax.tree.map(lambda _: dat, abstract.comp))
    make_state = jax.jit(state_from_seed, out_shardings=shardings)

    def pool_from_seed(seed, n):
        # every image its own colour and coarse pattern under pixel noise:
        # rows of pure noise would look alike to the network after pooling,
        # and batch statistics over look-alikes are ill-conditioned
        out, cells = [], 8
        for k in jax.random.split(jax.random.key(seed), n):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            base = jax.random.uniform(k1, (batch, 1, 1, 3), minval=64., maxval=192.)
            coarse = jax.random.uniform(k2, (batch, cells, cells, 3), minval=-48., maxval=48.)
            coarse = jnp.repeat(jnp.repeat(coarse, size // cells, axis=1),
                                size // cells, axis=2)
            noise = jax.random.uniform(k3, (batch, size, size, 3), minval=-16., maxval=16.)
            out.append({
                "input": jnp.clip(base + coarse + noise, 0, 255).astype(jnp.uint8),
                "target": jax.random.randint(k4, (batch,), 0,
                                             cfg["num_classes"], jnp.int32)})
        return out

    def make_pool(seed, n):
        return jax.jit(pool_from_seed, static_argnums=1,
                       out_shardings=dat)(seed, n)

    def make_loader(seed):
        f = traffic["feed"]
        ds = data.SyntheticImages(f["dataset_images"], cfg["num_classes"],
                                  seed=f["dataset_seed"], base_size=f["source_px"])
        return data.TrainLoader(ds, batch, size, min_scale=f["min_scale"],
                                seed=seed, workers=f["workers"])

    def probe(state, params_only=False):
        # copies: on a host backend device_get may alias a buffer the step donates.
        # In C order whatever layout the device's copy came in: the comparison
        # works on these lists in place
        get = lambda tree: [np.array(l, order="C")
                            for l in jax.device_get(jax.tree.leaves(tree))]
        if params_only:
            return {"params": get(state.params)}
        return {"opt": get(state.opt_state["momentum"]),
                "aux": get(state.batch_stats),
                "ef": get(state.ef) if state.ef != () else None}

    return Program(mesh, train_step, run_train_epoch, make_state, make_pool,
                   make_loader, batch, probe,
                   {"select_pack_min_elems": kernels.MIN_PALLAS_ELEMS})
