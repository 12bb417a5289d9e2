"""Program builder ``phi4flash_dp`` (a configuration names it under
``"program"``): the data-parallel trainer of a ``phi4flash``
decoder-hybrid-decoder (Mamba-1 selective scans, differential attention in a
window and over the whole sequence, Gated Memory Units and cross-attention on
earlier layers' tensors, a tied head), built from a cell's configuration and
traffic files.

The program's LM train step, loop, sync and optimizer, as ``hybrid_dp`` takes
them (``build`` is that builder's, on this module's ``make_step``); what
differs is the decoder (``models/sambay.py``) and the mapping from the
published ``config.json``'s keys to its settings: which mixer a published
layer index has, and the Mamba-1 sizes the config leaves to the family's
defaults (the configuration file states them under ``mamba_*``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from programs.image_dp import Program

def layer_pattern(cfg: dict) -> str:
    """One character a held layer: the model's layout for the published depth
    (``mb_per_layer`` 2, the only layout the family has), from the published
    index ``first_layer`` on."""
    from tpu_compressed_dp.models.sambay import published_pattern

    if cfg["mb_per_layer"] != 2:
        raise ValueError("the decoder-hybrid-decoder's layout is one Mamba "
                         "block every two layers")
    n = cfg.get("published", {}).get("num_hidden_layers", cfg["num_hidden_layers"])
    first = cfg.get("first_layer", 0)
    return published_pattern(n)[first:first + cfg["num_hidden_layers"]]


def phi4flash_config(cfg: dict, **variant):
    """The program's decoder settings for a configuration file's keys (the
    published ``config.json``'s names, and ``mamba_*`` for what it leaves to
    the family's defaults)."""
    from tpu_compressed_dp.models.sambay import SambaYConfig

    pub = lambda key: cfg.get("published", {}).get(key, cfg[key])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["compute_dtype"]]
    settings = dict(
        vocab_size=pub("vocab_size"), vocab_held=cfg["vocab_size"],
        dim=cfg["hidden_size"], pattern=layer_pattern(cfg),
        first_layer=cfg.get("first_layer", 0), norm_eps=cfg["layer_norm_eps"],
        d_inner=cfg["mamba_expand"] * cfg["hidden_size"],
        ssm_state=cfg["mamba_d_state"], dt_rank=cfg["mamba_dt_rank"],
        conv_kernel=cfg["mamba_d_conv"], chunk=cfg.get("scan_chunk", 128),
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        window=cfg["sliding_window"], ffn=cfg["intermediate_size"], dtype=dtype,
        init_std=cfg["initializer_range"])
    settings.update(variant)
    return SambaYConfig(**settings)


def make_step(cfg: dict, traffic: dict, mesh, **variant):
    """(decoder settings, optimizer, compression, the program's jitted step)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.lm_step import make_lm_train_step
    from tpu_compressed_dp.train.optim import SGD

    hc = phi4flash_config(cfg, **variant)
    o = cfg["optimizer"]
    opt = SGD(lr=o["lr"], momentum=o["momentum"], nesterov=o["nesterov"],
              weight_decay=o["weight_decay"])
    comp = CompressionConfig(**traffic["compression"])
    return hc, opt, comp, make_lm_train_step(hc, opt, comp, mesh)


def build(cfg: dict, traffic: dict, devices, model) -> Program:
    from tpu_compressed_dp.harness.loop import run_train_epoch
    from tpu_compressed_dp.train.lm_step import (init_lm_comp_state,
                                                 init_lm_ef_state,
                                                 init_lm_model_aux,
                                                 lm_state_specs, make_lm_mesh)
    from tpu_compressed_dp.train.state import TrainState

    world = int(traffic["chips"])
    mesh = make_lm_mesh(world, 1, 1, devices=devices)
    hc, opt, comp, train_step = make_step(cfg, traffic, mesh)
    seq, batch = cfg["seq_len"], cfg["per_chip_batch"] * world
    # the program's own tree, to hold the benchmark's weights to its shapes
    want = jax.eval_shape(lambda: hc.init(jax.random.key(0)))

    def state_from_seed(seed):
        params = model.make_params(cfg, jax.random.key(seed))
        got = jax.tree.map(lambda a: a.shape, params)
        exp = jax.tree.map(lambda a: a.shape, want)
        if got != exp:
            raise ValueError("the configuration's parameter tree is not the "
                             "program's: " + str(set(map(str, jax.tree.leaves(got)))
                                                 ^ set(map(str, jax.tree.leaves(exp))))[:300])
        return TrainState.create(
            params, init_lm_model_aux(hc), opt.init(params),
            init_lm_ef_state(hc, params, comp, mesh), jax.random.key(seed + 1),
            comp=init_lm_comp_state(hc, params, comp, mesh))

    is_spec = lambda s: isinstance(s, P)
    specs = lm_state_specs(hc, comp)
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
        # token ids drawn uniformly from the held slice of the vocabulary; the
        # targets are the ids shifted by one
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
        raise NotImplementedError("the LM builders have staged token batches only")

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
