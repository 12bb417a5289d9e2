"""Program builder ``hybrid_dp`` (a configuration names it under
``"program"``): the data-parallel trainer of a hybrid decoder (Mamba-2,
attention and LatentMoE layers by a pattern, with a multi-token-prediction
module), built from a cell's configuration and traffic files.

The same LM train step, loop, sync and optimizer as ``lm_dp``; what differs is
the model's settings and the token batches.  The configuration's counting keys
state what this chip HOLDS (heads, groups, experts, vocabulary), its
``published`` block what the model has: both go to the program's settings.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from programs.image_dp import Program


def hybrid_config(cfg: dict, **variant):
    """The program's decoder settings for a configuration file's keys (the
    published ``config.json``'s names)."""
    from tpu_compressed_dp.models.hybrid import HybridConfig

    pub = lambda key: cfg.get("published", {}).get(key, cfg[key])
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["compute_dtype"]]
    settings = dict(
        vocab_size=pub("vocab_size"), vocab_held=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        n_layers_published=pub("num_hidden_layers"), norm_eps=cfg["norm_eps"],
        mamba_heads=pub("mamba_num_heads"), mamba_heads_held=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], mamba_groups=pub("n_groups"),
        mamba_groups_held=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_heads=pub("num_attention_heads"), n_heads_held=cfg["num_attention_heads"],
        n_kv_heads=pub("num_key_value_heads"),
        n_kv_heads_held=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_routed_experts=pub("n_routed_experts"),
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg.get("first_expert", 0), top_k=cfg["num_experts_per_tok"],
        moe_latent=cfg["moe_latent_size"], moe_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        mtp_pattern=cfg["mtp_hybrid_override_pattern"],
        mtp_loss_weight=cfg["mtp_loss_weight"], dtype=dtype,
        init_std=cfg["initializer_range"])
    settings.update(variant)
    return HybridConfig(**settings)


def make_step(cfg: dict, traffic: dict, mesh, **variant):
    """(decoder settings, optimizer, compression, the program's jitted step)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.lm_step import make_lm_train_step
    from tpu_compressed_dp.train.optim import SGD

    hc = hybrid_config(cfg, **variant)
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
        # targets are the ids shifted by one, and the MTP module's the targets
        # shifted by one more (its last position has none and is left out)
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
