"""Program builder ``lm_dp`` (a configuration names it under ``"program"``):
the data-parallel language-model trainer, built from a cell's configuration
and traffic files.

The builders are the only files of the benchmark that import the program.
This one takes the decoder, the optimizer, the sync engine, the LM train step
and the loop from it, and gives them the benchmark's own seeded weights (the
configuration's reference makes them) and token batches.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from programs.image_dp import Program


def llama_config(cfg: dict, **variant):
    """The program's decoder settings for a configuration file's keys (the
    published ``config.json``'s names)."""
    from tpu_compressed_dp.models.transformer import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["compute_dtype"]]
    settings = dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], ffn_hidden=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype=dtype, remat=True, n_passes=cfg["total_ut_steps"],
        sandwich_norm=True, exit_gate=True, exit_beta=cfg["exit_beta"])
    settings.update(variant)
    lc = LlamaConfig(**settings)
    if lc.head_dim != cfg["head_dim"]:
        raise ValueError("the program's head size is hidden_size / heads; the "
                         f"configuration states {cfg['head_dim']}")
    return lc


def make_step(cfg: dict, traffic: dict, mesh, **variant):
    """(decoder settings, optimizer, compression, the program's jitted step)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.lm_step import make_lm_train_step
    from tpu_compressed_dp.train.optim import SGD

    lc = llama_config(cfg, **variant)
    o = cfg["optimizer"]
    opt = SGD(lr=o["lr"], momentum=o["momentum"], nesterov=o["nesterov"],
              weight_decay=o["weight_decay"])
    comp = CompressionConfig(**traffic["compression"])
    return lc, opt, comp, make_lm_train_step(lc, opt, comp, mesh)


def build(cfg: dict, traffic: dict, devices, model) -> Program:
    from tpu_compressed_dp.harness.loop import run_train_epoch
    from tpu_compressed_dp.models.transformer import init_llama
    from tpu_compressed_dp.train.lm_step import (init_lm_comp_state,
                                                 init_lm_ef_state,
                                                 init_lm_model_aux,
                                                 lm_state_specs, make_lm_mesh)
    from tpu_compressed_dp.train.state import TrainState

    world = int(traffic["chips"])
    mesh = make_lm_mesh(world, 1, 1, devices=devices)
    lc, opt, comp, train_step = make_step(cfg, traffic, mesh)
    seq, batch = cfg["seq_len"], cfg["per_chip_batch"] * world
    # the program's own tree, to hold the benchmark's weights to its shapes
    want = jax.eval_shape(lambda: init_llama(lc, jax.random.key(0)))

    def state_from_seed(seed):
        params = model.make_params(cfg, jax.random.key(seed))
        got = jax.tree.map(lambda a: a.shape, params)
        exp = jax.tree.map(lambda a: a.shape, want)
        if got != exp:
            raise ValueError("the configuration's parameter tree is not the "
                             "program's: " + str(set(map(str, jax.tree.leaves(got)))
                                                 ^ set(map(str, jax.tree.leaves(exp))))[:300])
        return TrainState.create(
            params, init_lm_model_aux(lc), opt.init(params),
            init_lm_ef_state(lc, params, comp, mesh), jax.random.key(seed + 1),
            comp=init_lm_comp_state(lc, params, comp, mesh))

    is_spec = lambda s: isinstance(s, P)
    specs = lm_state_specs(lc, comp)
    abstract = jax.eval_shape(state_from_seed, 0)
    # a spec stands for its whole field: give every leaf its own sharding
    shardings = dataclasses.replace(abstract, **{
        f.name: jax.tree.map(
            lambda spec, sub: jax.tree.map(lambda _: NamedSharding(mesh, spec), sub),
            getattr(specs, f.name), getattr(abstract, f.name), is_leaf=is_spec)
        for f in dataclasses.fields(abstract)})
    make_state = jax.jit(state_from_seed, out_shardings=shardings)
    dat = NamedSharding(mesh, P("data", "seq"))

    def pool_from_seed(seed, n):
        # token ids drawn uniformly from the vocabulary; the targets are the
        # ids shifted by one
        out = []
        for k in jax.random.split(jax.random.key(seed), n):
            ids = jax.random.randint(k, (batch, seq + 1), 0, cfg["vocab_size"],
                                     jnp.int32)
            out.append({"input": ids[:, :-1], "target": ids[:, 1:]})
        return out

    def make_pool(seed, n):
        return jax.jit(pool_from_seed, static_argnums=1,
                       out_shardings=dat)(seed, n)

    def make_loader(seed):
        raise NotImplementedError("the LM builder has staged token batches only")

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
                   make_loader, batch, probe, {})
