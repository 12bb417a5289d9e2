"""Jitted LM pretrain step over a (data, seq, tensor) mesh.

Composes the three parallelism axes the Llama stretch config needs
(BASELINE.json; none exist in the reference, SURVEY.md §2.2):

  * ``data`` — batch sharding; gradients compress-then-psum across it (and
    across ``seq``), via the same sync engine as the CNN harnesses
    (:func:`tpu_compressed_dp.parallel.dp.make_grad_sync` — layerwise or
    entire-model, all six methods, simulate or wire, error feedback).
  * ``seq`` — sequence sharding; attention runs as a ring
    (:mod:`tpu_compressed_dp.ops.ring_attention`).  A (data, seq) pair is one
    "compression worker": each holds a distinct micro-slice of tokens, so the
    gradient reduction spans the combined ``("data", "seq")`` axes.
  * ``tensor`` — megatron-style sharded layers inside the model
    (:mod:`tpu_compressed_dp.models.transformer`); TP-internal reductions
    (attention/MLP output psums, vocab-parallel loss, replicated-param
    cotangents) are exact and uncompressed, mirroring how the reference
    compressed only the *data-parallel* gradient exchange.

Everything is one ``shard_map`` over the full mesh: tensor-sharded params
arrive as local shards, replicated params are marked device-varying over
(data, seq) (same pcast trick as train/step.py) so the compressed sync — not
shard_map's AD — owns the data-axis reduction.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_compressed_dp.models.transformer import (
    LlamaConfig,
    apply_llama,
    exit_distribution,
    exit_stats,
    exit_weighted_loss,
    fused_head_xent,
    fused_head_xent_wsum,
    use_fused_head_xent,
    vocab_parallel_xent,
    vocab_parallel_xent_tokens,
)
from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.parallel.dp import (
    CompressionConfig,
    make_grouped_grad_sync,
    make_sharded_clip,
)
from tpu_compressed_dp.train import guard as guard_mod
from tpu_compressed_dp.train.guard import GuardConfig
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.train.step import optimizer_lr
from tpu_compressed_dp.utils import chaos as chaos_mod

Array = jax.Array

__all__ = ["make_lm_train_step", "init_lm_ef_state", "init_lm_comp_state",
           "init_lm_model_aux", "lm_state_specs", "make_lm_mesh", "llama_loss"]

LM_AXES = ("data", "seq", "tensor")


def make_lm_mesh(data: int, seq: int = 1, tensor: int = 1,
                 devices=None) -> Mesh:
    from tpu_compressed_dp.parallel.mesh import make_mesh

    return make_mesh((data, seq, tensor), LM_AXES, devices=devices)


# The step takes a model through its settings object (``LlamaConfig``,
# ``models.hybrid.HybridConfig``): ``validate_mesh(tensor_size)``,
# ``init(key)``, ``param_specs()``, ``init_aux()``, ``aux_metrics(aux)`` and,
# inside the step's shard_map, its loss (:func:`_model_loss`).
ModelConfig = Any


def init_lm_model_aux(cfg: ModelConfig) -> Dict[str, Array]:
    """The state's auxiliary slot (``TrainState.batch_stats``) for the LM
    step: what the model's last step leaves there (a looped model its
    per-pass losses and exit masses, a hybrid one its two losses and its
    expert layers' routing numbers; most models nothing)."""
    return cfg.init_aux()


def llama_loss(cfg: LlamaConfig, params, x: Array, y: Array, tensor_size: int):
    """Per-worker ``(loss to differentiate, cross-entropy, auxiliary
    numbers)`` of inputs ``x`` and targets ``y`` [B, T], inside the LM step's
    ``shard_map`` (axes ``seq`` and ``tensor``)."""
    # per-worker logits buffer: local tokens x vocab shard (V/tp) at the
    # config's logits width (bf16 OR fp32 — ADVICE r5); an exit gate makes
    # one of every pass
    fused = use_fused_head_xent(
        (cfg.n_passes if cfg.exit_gate else 1) * x.shape[0] * x.shape[1],
        cfg.vocab_size // tensor_size, jnp.dtype(cfg.dtype).itemsize)
    model_aux = {}
    if cfg.exit_gate:
        # every pass's hidden states through the one head, each token's
        # losses weighted by its exit distribution
        out, gate, aux = apply_llama(
            cfg, params, x, tensor_axis="tensor", seq_axis="seq",
            with_aux=True, return_hidden=fused, all_passes=True)
        ys = jnp.broadcast_to(y, (cfg.n_passes,) + y.shape)
        if fused:
            # the loss is a weighted sum of the head's cross-entropies and
            # the weights are known before it: its forward makes dh and dW
            with obs_trace.phase("exit"):
                p, plogp = exit_distribution(gate)
            with obs_trace.phase("head_xent"):
                xent, nll = fused_head_xent_wsum(
                    out, params["lm_head"].astype(cfg.dtype), ys, p / y.size,
                    "tensor")
            with obs_trace.phase("exit"):
                xent = xent + cfg.exit_beta * jnp.mean(plogp)
                model_aux = exit_stats(jax.lax.stop_gradient(nll), p, plogp)
        else:
            with obs_trace.phase("head_xent"):
                nll = vocab_parallel_xent_tokens(out, ys, tensor_axis="tensor")
            with obs_trace.phase("exit"):
                xent, model_aux = exit_weighted_loss(nll, gate, cfg.exit_beta)
    elif fused:
        # head matmul + softmax-xent fused through a chunked running
        # logsumexp: the [B,T,V] logits (and AD's saved softmax inputs) never
        # materialise in HBM
        h, aux = apply_llama(cfg, params, x, tensor_axis="tensor",
                             seq_axis="seq", with_aux=True, return_hidden=True)
        with obs_trace.phase("head_xent"):
            xent = fused_head_xent(
                h, params["lm_head"].astype(cfg.dtype), y, "tensor")
    else:
        logits, aux = apply_llama(cfg, params, x, tensor_axis="tensor",
                                  seq_axis="seq", with_aux=True)
        with obs_trace.phase("head_xent"):
            xent = vocab_parallel_xent(logits, y, tensor_axis="tensor")
    return xent + cfg.moe_aux_weight * aux, xent, model_aux


def _model_loss(cfg: ModelConfig, params, x: Array, y: Array, mesh_shape):
    """``(loss to differentiate, loss to report, auxiliary numbers)``.  A
    model's settings bring their loss, ``loss(params, x, y, mesh_shape)``;
    the plain decoder's is :func:`llama_loss` in this module, which
    ``models/`` does not import (its tests and the benchmark's plant their
    faults on this module's names)."""
    if isinstance(cfg, LlamaConfig):
        return llama_loss(cfg, params, x, y, mesh_shape["tensor"])
    return cfg.loss(params, x, y, mesh_shape)


def init_lm_ef_state(cfg: ModelConfig, params: Any, comp: CompressionConfig,
                     mesh: Mesh) -> Any:
    """EF residual with a leading (data*seq) worker axis; tensor-sharded dims
    follow the param's own sharding (each tensor shard keeps its own
    residual slice)."""
    if not comp.error_feedback:
        return ()
    workers = mesh.shape["data"] * mesh.shape["seq"]
    return jax.tree.map(
        lambda p: jnp.zeros((workers,) + p.shape, jnp.float32), params
    )


def _ef_specs(pspecs: Any) -> Any:
    return jax.tree.map(
        lambda s: P(("data", "seq"), *s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _lm_is_sharded(cfg: ModelConfig):
    pspec_leaves = jax.tree.leaves(
        cfg.param_specs(), is_leaf=lambda x: isinstance(x, P))
    return [any(ax == "tensor" for ax in spec) for spec in pspec_leaves]


def init_lm_comp_state(cfg: ModelConfig, params: Any, comp: CompressionConfig,
                       mesh: Mesh) -> Any:
    """Compressor state (PowerSGD warm-start Q) for the LM step, with the
    same signature grouping ``make_lm_train_step``'s grouped sync uses and a
    leading (data*seq) worker axis like :func:`init_lm_ef_state`.

    Tensor-sharded parameter groups sync on per-shard flats whose sizes this
    (global-shape) init cannot see, so stateful compression currently
    requires ``tensor == 1``; replicated-signature groups are what the DP
    sync engine compresses anyway.
    """
    from tpu_compressed_dp.ops.compressors import canonical_name
    from tpu_compressed_dp.parallel.dp import init_comp_state_grouped

    if canonical_name(comp.method) != "powersgd":
        return ()
    if mesh.shape.get("tensor", 1) > 1:
        raise NotImplementedError(
            "powersgd over tensor-sharded params needs shard-local warm "
            "starts; run it on a (data[, seq]) mesh (tensor=1)")
    workers = mesh.shape["data"] * mesh.shape["seq"]
    return init_comp_state_grouped(
        params, comp, _lm_is_sharded(cfg), "tensor", workers)


def lm_state_specs(cfg: ModelConfig, comp: CompressionConfig) -> TrainState:
    """PartitionSpec pytree for the LM TrainState (shard_map in/out specs)."""
    pspecs = cfg.param_specs()
    return TrainState(
        step=P(),
        params=pspecs,
        batch_stats=P(),
        opt_state={"momentum": pspecs},
        ef=_ef_specs(pspecs) if comp.error_feedback else P(),
        rng=P(),
        # compressor state (powersgd warm-start Q): leading (data, seq)
        # worker axis, inner dims unsharded — build with
        # init_comp_state_grouped(..., num_devices=data*seq)
        comp=P(("data", "seq")),
        # step-guard state: replicated (the finiteness vote makes it
        # identical on every worker)
        guard=P(),
        # adaptive-compression control state: replicated, host-mutated only
        control=P(),
    )


def place_lm_state(state: TrainState, cfg: ModelConfig, comp: CompressionConfig,
                   mesh: Mesh) -> TrainState:
    """Shard a (restored) TrainState onto the 3-D mesh per lm_state_specs —
    the LM analog of ``TrainState.with_mesh_sharding`` (checkpoint restore
    lands everything on one device)."""
    return state.place_with_specs(lm_state_specs(cfg, comp), mesh)


def make_lm_train_step(
    cfg: ModelConfig,
    optimizer: SGD,
    comp_cfg: CompressionConfig,
    mesh: Mesh,
    *,
    clip_norm: float = 0.0,
    clip_sent_norm: float = 0.0,
    donate: bool = True,
    guard_cfg: Optional[GuardConfig] = None,
    chaos: Optional["chaos_mod.ChaosConfig"] = None,
):
    """Build ``train_step(state, batch) -> (state, metrics)``.

    ``batch``: ``{'input': [B, T] int32, 'target': [B, T] int32}``, ``B``
    divisible by the data axis, ``T`` by the seq axis.

    ``clip_norm`` / ``clip_sent_norm``: the EF-with-momentum stabilisers of
    :func:`tpu_compressed_dp.train.step.make_train_step` (local-gradient /
    post-aggregation L2 clip).  Norms span the FULL model gradient: squared
    norms of tensor-SHARDED leaves psum over the tensor axis; replicated
    leaves (already psum'd by shard_map AD) count once.

    ``guard_cfg`` / ``chaos``: the step guard and fault injection of
    :func:`tpu_compressed_dp.train.step.make_train_step`.  The finiteness
    vote spans the WHOLE mesh (data, seq, tensor): a NaN on one tensor
    shard's gradient slice must veto the update on every replica, or the
    tensor-sharded params would de-synchronise.  Chaos targets one
    (data, seq) compression worker across all its tensor shards.

    ``comp_cfg.sync_overlap > 1`` chunk-pipelines each replication
    signature's sync (the grouped wrapper's base engines dispatch through
    :mod:`tpu_compressed_dp.parallel.overlap`): K reverse-topological chunk
    collectives per signature, interleavable with the remaining backward.
    The per-chunk optimizer interleave stays a pure-DP
    (:func:`~tpu_compressed_dp.train.step.make_train_step`) optimisation —
    signature groups interleave leaves across chunk boundaries here, so the
    update runs whole-tree after the chunked sync.
    """
    cfg.validate_mesh(mesh.shape["tensor"])
    from tpu_compressed_dp.ops.compressors import canonical_name

    if (canonical_name(comp_cfg.method) == "powersgd"
            and mesh.shape["tensor"] > 1):
        # same limitation init_lm_comp_state documents, guarded at the
        # factory so direct API users get the real reason, not a generic
        # missing-warm-start error for state no init can build
        raise NotImplementedError(
            "powersgd over tensor-sharded params needs shard-local warm "
            "starts; run it on a (data[, seq]) mesh (tensor=1)")
    sync_axes = ("data", "seq")
    n_workers = mesh.shape["data"] * mesh.shape["seq"]

    # Tensor-sharded and tensor-replicated leaves sync as separate groups so
    # data-dependent compression masks cannot de-synchronise replicated
    # params across tensor shards (see make_grouped_grad_sync); the same
    # grouping drives init_lm_comp_state so warm-start state lines up.
    is_sharded = _lm_is_sharded(cfg)
    grad_sync = make_grouped_grad_sync(comp_cfg, sync_axes, is_sharded, "tensor")

    clip_tree = make_sharded_clip(is_sharded, "tensor")
    guarded = guard_cfg is not None
    inject = chaos is not None and chaos.injects_in_graph
    if inject and chaos.worker >= n_workers:
        # silently-never-firing injection would fake a passing drill
        raise ValueError(
            f"chaos worker {chaos.worker} out of range for {n_workers} "
            "(data x seq) workers")

    def local_step(state: TrainState, x: Array, y: Array):
        comp_key = jax.random.fold_in(state.rng, state.step)
        ls_scale = (state.guard.loss_scale if guarded
                    else jnp.asarray(1.0, jnp.float32))

        def loss_fn(params):
            total, xent, model_aux = _model_loss(cfg, params, x, y, mesh.shape)
            # backprop at loss_scale x (identity unguarded/fp32); the raw
            # loss rides along for metrics/vote
            return total * ls_scale, (xent, model_aux)

        varying = jax.tree.map(
            lambda p: jax.lax.pcast(p, sync_axes, to="varying"), state.params
        )
        with obs_trace.phase("grad"):
            (_, (loss, model_aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(varying)
        if inject:
            loss, grads = chaos_mod.inject(
                chaos, state.step, guard_mod.worker_index(sync_axes), loss,
                grads)
        ok = None
        if guarded:
            # vote over the FULL mesh: tensor-sharded gradient slices differ
            # per shard, and every replica must take the identical branch
            ok = guard_mod.finite_vote(
                guard_mod.tree_all_finite(loss, grads), LM_AXES)
            grads = jax.tree.map(lambda g: g / ls_scale, grads)
        if clip_norm > 0.0:
            grads = clip_tree(grads, clip_norm)

        ef_local = jax.tree.map(lambda e: e[0], state.ef)
        comp_local = jax.tree.map(lambda c: c[0], state.comp)
        synced, new_ef, new_comp, comm = grad_sync(
            grads, ef_local, comp_local, comp_key, ok=ok)
        new_ef = jax.tree.map(lambda e: e[None], new_ef)
        new_comp = jax.tree.map(lambda c: c[None], new_comp)
        if clip_sent_norm > 0.0:
            synced = clip_tree(synced, clip_sent_norm)

        new_step = state.step + 1
        # guard-aware LR rewind: schedules key off the applied-update count
        sched_step = guard_mod.schedule_step(guard_cfg, state.guard, new_step)
        with obs_trace.phase("update"):
            new_params, new_opt = optimizer.apply(state.params, synced,
                                                  state.opt_state, sched_step)
        new_guard = state.guard
        if guarded:
            new_params = guard_mod.select_tree(ok, new_params, state.params)
            new_opt = guard_mod.select_tree(ok, new_opt, state.opt_state)
            new_guard = guard_mod.update_guard(guard_cfg, state.guard, ok,
                                               new_step)
            loss = jnp.where(ok, loss, 0.0)
        ntok = jnp.asarray(x.shape[0] * x.shape[1], jnp.float32)
        metrics = {
            "loss": jax.lax.pmean(loss, sync_axes),
            "tokens": jax.lax.psum(ntok, sync_axes),
            "lr": optimizer_lr(optimizer, sched_step),
        }
        # the model's own numbers (a looped model's per-pass losses, a
        # hybrid one's routing): step metrics, and kept in the state's
        # auxiliary slot (where a CNN keeps its batch statistics)
        model_aux = jax.tree.map(lambda v: jax.lax.pmean(v, sync_axes),
                                 model_aux)
        metrics.update(cfg.aux_metrics(model_aux))
        if guarded:
            metrics.update(guard_mod.guard_metrics(new_guard))
        for k, v in comm.items():
            metrics[k if k.startswith("guard/") else f"comm/{k}"] = (
                jax.lax.pmean(v, sync_axes))

        return dataclasses.replace(
            state, step=new_step, params=new_params, opt_state=new_opt,
            batch_stats=model_aux or state.batch_stats,
            ef=new_ef, comp=new_comp, guard=new_guard,
        ), metrics

    state_spec = lm_state_specs(cfg, comp_cfg)
    data_spec = P("data", "seq")
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, data_spec, data_spec),
        out_specs=(state_spec, P()),
    )
    jitted = partial(jax.jit, donate_argnums=(0,) if donate else ())(
        lambda state, x, y: sharded(state, x, y)
    )

    def train_step(state: TrainState, batch: Dict[str, Array]):
        for leaf in jax.tree.leaves(state.ef):
            if leaf.ndim < 1 or leaf.shape[0] != n_workers:
                raise ValueError(
                    f"LM EF residual needs leading axis {n_workers} "
                    f"(data x seq workers); got {leaf.shape} — build with "
                    "init_lm_ef_state(cfg, params, comp, mesh)"
                )
        if guarded and state.guard == ():
            raise ValueError(
                "guard_cfg set but state.guard is empty; build it with "
                "init_guard_state(guard_cfg)")
        return jitted(state, batch["input"], batch["target"])

    return train_step


def make_lm_eval_step(cfg: LlamaConfig, mesh: Mesh):
    """``eval_step(state, batch) -> {'loss': mean nll, 'tokens': count}``."""
    cfg.validate_mesh(mesh.shape["tensor"])

    def local_eval(params, x: Array, y: Array):
        logits = apply_llama(cfg, params, x, tensor_axis="tensor", seq_axis="seq")
        loss = vocab_parallel_xent(logits, y, tensor_axis="tensor")
        return {
            "loss": jax.lax.pmean(loss, ("data", "seq")),
            "tokens": jax.lax.psum(
                jnp.asarray(x.shape[0] * x.shape[1], jnp.float32), ("data", "seq")
            ),
        }

    pspecs = cfg.param_specs()
    sharded = jax.shard_map(
        local_eval, mesh=mesh,
        in_specs=(pspecs, P("data", "seq"), P("data", "seq")),
        out_specs=P(),
    )

    @jax.jit
    def eval_step(state: TrainState, batch: Dict[str, Array]):
        return sharded(state.params, batch["input"], batch["target"])

    return eval_step
