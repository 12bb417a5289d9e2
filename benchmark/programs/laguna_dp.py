"""Program builder ``laguna_dp`` (a configuration names it under
``"program"``): the data-parallel trainer of a ``laguna`` decoder (full and
sliding-window gated attention with a head count by layer type, one dense and
then sparse SwiGLU feed-forwards), built from a cell's configuration and
traffic files.

The program's hybrid decoder, LM train step, loop, sync and optimizer, as
``hybrid_dp`` takes them; what differs is the mapping from the published
``config.json``'s keys to the decoder's settings (a layer there is two of the
decoder's pattern entries) and one number kept for a reader: the rows the
held experts computed a step of the last epoch run
(``constants["expert_rows_per_step"]``, from the step's own
``model/expert_rows``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from programs.image_dp import Program

_KIND = {"full_attention": "F", "sliding_attention": "W", "dense": "D", "sparse": "E"}


def _rotary(rope: dict, head_dim: int):
    from tpu_compressed_dp.models.hybrid import Rotary

    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope["rope_type"] == "default":
        return Rotary(theta=float(rope["rope_theta"]), dim=dim)
    return Rotary(theta=float(rope["rope_theta"]), dim=dim,
                  yarn_factor=float(rope["factor"]),
                  yarn_original=rope["original_max_position_embeddings"],
                  beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
                  attention_factor=float(rope["attention_factor"]))


def laguna_config(cfg: dict, **variant):
    """The program's decoder settings for a configuration file's keys (the
    published ``config.json``'s names)."""
    from tpu_compressed_dp.models.hybrid import HybridConfig

    pub = lambda key: cfg.get("published", {}).get(key, cfg[key])
    n = cfg["num_hidden_layers"]
    attn, ffs = cfg["layer_types"][:n], cfg["mlp_layer_types"][:n]

    def heads_of(kind):
        found = {h for k, h in zip(attn, cfg["num_attention_heads_per_layer"]) if k == kind}
        if len(found) > 1:
            raise ValueError(f"{kind} layers of {sorted(found)} heads: one head "
                             "count a layer type")
        return found.pop() if found else 0

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["compute_dtype"]]
    rope = cfg["rope_parameters"]
    settings = dict(
        vocab_size=pub("vocab_size"), vocab_held=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        pattern="".join(_KIND[a] + _KIND[f] for a, f in zip(attn, ffs)),
        n_layers_published=pub("num_hidden_layers"), norm_eps=cfg["rms_norm_eps"],
        n_kv_heads=cfg["num_key_value_heads"],
        n_kv_heads_held=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        full_heads=heads_of("full_attention"),
        window_heads=heads_of("sliding_attention"),
        window=cfg["sliding_window"],
        rotary_full=_rotary(rope["full_attention"], cfg["head_dim"]),
        rotary_window=_rotary(rope["sliding_attention"], cfg["head_dim"]),
        dense_ffn=cfg["intermediate_size"],
        n_routed_experts=pub("num_experts"), experts_held=cfg["num_experts"],
        first_expert=cfg.get("first_expert", 0), top_k=cfg["num_experts_per_tok"],
        moe_latent=0, moe_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["shared_expert_intermediate_size"],
        routed_scale=float(cfg["moe_routed_scaling_factor"]), moe_gated=True,
        router_bias=False, mtp_pattern="", rescale_out_proj=False, dtype=dtype,
        init_std=cfg["initializer_range"])
    settings.update(variant)
    return HybridConfig(**settings)


def make_step(cfg: dict, traffic: dict, mesh, **variant):
    """(decoder settings, optimizer, compression, the program's jitted step)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.lm_step import make_lm_train_step
    from tpu_compressed_dp.train.optim import SGD

    hc = laguna_config(cfg, **variant)
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

    constants = {}
    expert_slots = hc.pattern.count("E") * hc.experts_held

    def run_epoch(step, state, batches):
        # the loop's own accumulator has the step's counters once it returns:
        # the mean rows a held expert received, times the held experts of
        # every sparse layer, is what the grouped product computed a step
        state, acc = run_train_epoch(step, state, batches)
        if acc.steps:
            constants["expert_rows_per_step"] = (
                acc.mean("model/expert_rows") * expert_slots)
        return state, acc

    return Program(mesh, train_step, run_epoch, make_state, make_pool,
                   make_loader, batch, probe, constants)
