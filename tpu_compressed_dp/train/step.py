"""The jitted train/eval step: forward, backward, compress, psum, update.

This one compiled function replaces the reference's entire per-batch control
flow — ``run_batches`` body (`CIFAR10/core.py:306-321`), the compression comm
calls (`core.py:175-301`), the DDP hook/bucket machinery (`ddp.py:394-488`),
and the optimizer step (`torch_backend.py:132-135`).  It runs under
``shard_map`` over a ``('data',)`` mesh: parameters and optimizer state are
replicated, the batch is sharded on its leading axis, gradients are
compressed locally and reduced with ``lax.psum`` — XLA schedules the
collectives to overlap with compute, which is the TPU-native answer to the
reference's reverse-order bucket overlap (`sparsified_ddp.py:279-281`).

Gradient scale protocol: each reference worker compresses the gradient of a
*summed* loss over its own full batch (512 for CIFAR) and the results are
allreduce-averaged (`core.py:217-222`).  We compute the local *mean* gradient
and multiply by ``grad_scale`` before compression.  The default is 1.0
(mean-gradient scale); to reproduce the paper protocol — in particular for the
scale-sensitive Threshold-V operator — the harnesses pass
``grad_scale=<global batch size>``, pairing it with
``lr = schedule/batch_size, wd = 5e-4*batch_size`` exactly as `dawn.py:142-148`,
so the synced gradient equals the global summed-loss gradient when
compression is off.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.parallel.dp import CompressionConfig, make_grad_sync
from tpu_compressed_dp.train import guard as guard_mod
from tpu_compressed_dp.train.guard import GuardConfig
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.utils import chaos as chaos_mod

Array = jax.Array

# Adapter each model family provides:
#   apply_fn(params, batch_stats, x, train, rngs) -> (logits, new_batch_stats)
ApplyFn = Callable[[Any, Any, Array, bool, Dict[str, Array]], Tuple[Array, Any]]

__all__ = ["make_train_step", "make_eval_step", "cross_entropy_sum"]


def cross_entropy_per_example(logits: Array, labels: Array) -> Array:
    """Per-example softmax cross-entropy (`nn.CrossEntropyLoss(reduction='none')`,
    `dawn.py:85`).  Out-of-range labels (eval padding) contribute 0."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32))
    safe = jnp.clip(labels, 0, logits.shape[-1] - 1)
    ll = jnp.take_along_axis(logz, safe[:, None], axis=1)[:, 0]
    return jnp.where((labels >= 0) & (labels < logits.shape[-1]), -ll, 0.0)


def cross_entropy_sum(logits: Array, labels: Array) -> Array:
    """Summed softmax cross-entropy (`core.py:310`)."""
    return jnp.sum(cross_entropy_per_example(logits, labels))


def make_train_step(
    apply_fn: ApplyFn,
    optimizer: SGD,
    comp_cfg: CompressionConfig,
    mesh: Mesh,
    *,
    grad_scale: float = 1.0,
    clip_norm: float = 0.0,
    clip_sent_norm: float = 0.0,
    axis_name: str = "data",
    donate: bool = True,
    guard_cfg: Optional[GuardConfig] = None,
    chaos: Optional["chaos_mod.ChaosConfig"] = None,
):
    """Build ``train_step(state, batch) -> (state, metrics)``, jitted over ``mesh``.

    ``batch`` is ``{'input': [B, ...], 'target': [B]}`` with ``B`` divisible by
    the mesh's data-axis size; metrics are global (psum-reduced) scalars.

    ``clip_norm`` (mean-loss units; 0 = off) clips each worker's local
    gradient by L2 norm *before* error-feedback accumulation — the DGC-style
    stabiliser for sparsified training with momentum.  Root-cause analysis
    (`tools/ef_bisect.py`, `benchmarks/ef_momentum_bisect_r2.txt`): EF defers
    ~1/k steps of gradient mass per coordinate, and that delay times the
    momentum gain 1/(1-mu) diverges under the dawn protocol's peak lr — for
    the reference's own update rule too (torch repro of
    `sparsified_ddp.py:408-413` + momentum SGD NaNs identically).  Clipping
    bounds the re-injected residual and restores stable training.

    ``clip_sent_norm`` (same units; 0 = off) clips the *synced* gradient
    after aggregation, which bounds the ~1/k-step residual spike itself —
    local clipping cannot (the residual accumulates clipped inflow for 1/k
    steps and still releases it at once).  For Random-K + EF + momentum the
    bisect shows clip-sent ~20x lower final loss than clip-local alone;
    combine both for the most robust protocol.

    ``guard_cfg`` (None = off) arms the in-graph step guard
    (:mod:`tpu_compressed_dp.train.guard`): a cross-worker finiteness vote
    over loss + gradients gates the whole update — on a bad step
    params/opt_state/batch_stats/ef/comp are held bitwise, the dynamic loss
    scale backs off, and the skip counters advance; ``state.guard`` must be
    built with ``init_guard_state(guard_cfg)``.  The loss is multiplied by
    the live scale before backprop and the gradients divided by it after
    the vote (so a scale overflow is itself caught by the vote).

    ``chaos`` (None = off) traces deterministic fault injection into the
    step (:mod:`tpu_compressed_dp.utils.chaos`): NaN/Inf into one worker's
    gradients or loss at step-counter-chosen steps — the adversary the
    guard is tested against (tools/chaos_drill.py).

    ``comp_cfg.sync_overlap > 1`` chunk-pipelines the gradient sync
    (:mod:`tpu_compressed_dp.parallel.overlap`): the sync decomposes into K
    reverse-topological chunk collectives the scheduler interleaves with
    the remaining backward pass, and — when ``clip_sent_norm`` is off —
    each chunk's slice of the optimizer update is traced right after its
    reduce so it can run while the next chunk's collective is in flight.
    Bitwise-identical numerics either way; ``clip_sent_norm > 0`` needs the
    global synced-gradient norm (a barrier over all chunks), so that path
    keeps the whole-tree update after the chunked sync.
    """
    grad_sync = make_grad_sync(comp_cfg, axis_name)
    fused_overlap = None
    if (comp_cfg.sync_overlap > 1 and clip_sent_norm == 0.0
            and isinstance(optimizer, SGD)):
        # the per-chunk interleave slices the optimizer leaf-for-leaf and
        # reaches into opt_state["momentum"]/wd_mask — SGD's shape; any
        # other optimizer keeps chunked sync + whole-tree apply
        from tpu_compressed_dp.parallel import overlap as overlap_mod

        fused_overlap = overlap_mod.make_overlap_sync_apply(
            comp_cfg, optimizer, axis_name)
    guarded = guard_cfg is not None
    inject = chaos is not None and chaos.injects_in_graph
    if inject and chaos.worker >= mesh.shape[axis_name]:
        # an out-of-range worker would silently never fire — the drill
        # would then "pass" against faults that never happened
        raise ValueError(
            f"chaos worker {chaos.worker} out of range for "
            f"{mesh.shape[axis_name]} data-parallel workers")

    def local_step(state: TrainState, x: Array, y: Array):
        step_key = jax.random.fold_in(state.rng, state.step)
        comp_key, drop_key = jax.random.split(step_key)
        drop_key = jax.random.fold_in(drop_key, jax.lax.axis_index(axis_name))
        ls_scale = (state.guard.loss_scale if guarded
                    else jnp.asarray(1.0, jnp.float32))

        def loss_fn(params):
            logits, new_bs = apply_fn(params, state.batch_stats, x, True, {"dropout": drop_key})
            loss = cross_entropy_sum(logits, y) / x.shape[0]  # local mean
            # backprop the SCALED loss (identity when unguarded/fp32): the
            # whole backward pass runs at loss_scale x, keeping tiny
            # half-precision cotangents above the representable floor
            return loss * ls_scale, (new_bs, logits, loss)

        # shard_map's AD would transparently psum gradients of replicated
        # params — but the whole point of this framework is to compress each
        # worker's gradient *before* the reduction.  Mark the params as
        # device-varying so jax.grad yields the per-worker local gradient and
        # the (possibly compressed) psum stays under our control in grad_sync.
        varying_params = jax.tree.map(lambda p: _to_varying(p, axis_name), state.params)
        with obs_trace.phase("grad"):
            (_, (new_bs, logits, loss)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(varying_params)

        scaled = jax.tree.map(lambda g: g.astype(jnp.float32) * grad_scale, grads)
        if inject:
            loss, scaled = chaos_mod.inject(
                chaos, state.step, guard_mod.worker_index(axis_name), loss,
                scaled)
        ok = None
        if guarded:
            # vote BEFORE unscaling: an inf that the loss scale itself
            # manufactured is exactly what dynamic backoff must see
            ok = guard_mod.finite_vote(
                guard_mod.tree_all_finite(loss, scaled), axis_name)
            scaled = jax.tree.map(lambda g: g / ls_scale, scaled)
        if clip_norm > 0.0:
            # local-gradient clip at mean-loss scale: ||scaled|| / grad_scale
            # <= clip_norm after this (threshold stays protocol-invariant
            # under the summed-loss grad_scale pairing)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(scaled)))
            factor = jnp.minimum(1.0, clip_norm * grad_scale / jnp.maximum(gnorm, 1e-20))
            scaled = jax.tree.map(lambda g: g * factor, scaled)
        # EF residual and compressor state are per-worker state (the
        # reference's per-rank epsilon, sparsified_ddp.py:222; PowerSGD's
        # warm-start Q): stored with a leading device axis, sharded over the
        # mesh; squeeze the local slice here.
        ef_local = jax.tree.map(lambda e: e[0], state.ef)
        comp_local = jax.tree.map(lambda c: c[0], state.comp)
        new_step = state.step + 1
        # guard-aware LR rewind: schedules see the applied-update count, so
        # vetoed steps don't fast-forward the schedule clock
        sched_step = guard_mod.schedule_step(guard_cfg, state.guard, new_step)
        if fused_overlap is not None:
            # chunk-pipelined sync + per-chunk optimizer interleave: chunk
            # i's update slice runs while chunk i+1's collective is in
            # flight (the vote `ok` was computed once, above, before any
            # chunk dispatches)
            new_params, new_opt, new_ef, new_comp, comm = fused_overlap(
                state.params, scaled, ef_local, comp_local, state.opt_state,
                comp_key, sched_step, ok=ok)
        else:
            synced, new_ef, new_comp, comm = grad_sync(
                scaled, ef_local, comp_local, comp_key, ok=ok)
            if clip_sent_norm > 0.0:
                snorm = jnp.sqrt(
                    sum(jnp.sum(g * g) for g in jax.tree.leaves(synced)))
                sfactor = jnp.minimum(
                    1.0,
                    clip_sent_norm * grad_scale / jnp.maximum(snorm, 1e-20))
                synced = jax.tree.map(lambda g: g * sfactor, synced)
            with obs_trace.phase("update"):
                new_params, new_opt = optimizer.apply(
                    state.params, synced, state.opt_state, sched_step)
        new_ef = jax.tree.map(lambda e: e[None], new_ef)
        new_comp = jax.tree.map(lambda c: c[None], new_comp)

        # BN running stats are computed from the local shard; average them so
        # the replicated state stays consistent.  Normalisation itself still
        # used local batch statistics, matching the reference's non-synced BN
        # (SURVEY.md §7 "BatchNorm under DP").
        new_bs = jax.lax.pmean(new_bs, axis_name) if new_bs else new_bs

        new_guard = state.guard
        if guarded:
            # the vetoed branch holds EVERYTHING the step would have mutated
            # (ef/comp were held inside grad_sync); only the step counter,
            # the RNG stream (derived from it) and the guard's own
            # bookkeeping advance
            new_params = guard_mod.select_tree(ok, new_params, state.params)
            new_opt = guard_mod.select_tree(ok, new_opt, state.opt_state)
            new_bs = guard_mod.select_tree(ok, new_bs, state.batch_stats)
            new_guard = guard_mod.update_guard(guard_cfg, state.guard, ok,
                                               new_step)
            # a nonfinite loss would poison the epoch mean; report 0 for the
            # skipped step (its count still contributes — honest step totals)
            loss = jnp.where(ok, loss, 0.0)

        local_bs = jnp.asarray(x.shape[0], jnp.float32)
        correct = jnp.sum(jnp.argmax(logits, axis=1) == y).astype(jnp.float32)
        metrics = {
            "loss": jax.lax.psum(loss * local_bs, axis_name) / jax.lax.psum(local_bs, axis_name),
            "correct": jax.lax.psum(correct, axis_name),
            "count": jax.lax.psum(local_bs, axis_name),
            "lr": optimizer_lr(optimizer, sched_step),
        }
        if guarded:
            metrics.update(guard_mod.guard_metrics(new_guard))
        for k, v in comm.items():
            # guard/* stats are already-global diagnostics, not comm volumes
            metrics[k if k.startswith("guard/") else f"comm/{k}"] = (
                jax.lax.pmean(v, axis_name))

        new_state = dataclasses.replace(
            state,
            step=new_step,
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt,
            ef=new_ef,
            comp=new_comp,
            guard=new_guard,
        )
        return new_state, metrics

    state_spec = TrainState(
        step=P(), params=P(), batch_stats=P(), opt_state=P(), ef=P(axis_name),
        rng=P(), comp=P(axis_name), guard=P(), control=P(),
    )
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, P(axis_name), P(axis_name)),
        out_specs=(state_spec, P()),
    )

    jitted = partial(jax.jit, donate_argnums=(0,) if donate else ())(
        lambda state, x, y: sharded(state, x, y)
    )
    n_dev = mesh.shape[axis_name]

    def train_step(state: TrainState, batch: Dict[str, Array]):
        if comp_cfg.error_feedback and state.ef == ():
            raise ValueError(
                "error_feedback=True but state.ef is empty; build it with "
                f"init_ef_state(params, cfg, num_devices={n_dev})")
        if guarded and state.guard == ():
            raise ValueError(
                "guard_cfg set but state.guard is empty; build it with "
                "init_guard_state(guard_cfg)")
        for field, hint in (("ef", "init_ef_state(params, cfg"),
                            ("comp", "init_comp_state(params, cfg")):
            for leaf in jax.tree.leaves(getattr(state, field)):
                if leaf.ndim < 1 or leaf.shape[0] != n_dev:
                    raise ValueError(
                        f"{field} leaves need a leading device axis of size "
                        f"{n_dev} (got shape {leaf.shape}); build them with "
                        f"{hint}, num_devices={n_dev})"
                    )
        return jitted(state, batch["input"], batch["target"])

    return train_step


def _to_varying(x: Array, axis_name: str) -> Array:
    """Mark a replicated value as device-varying (identity on the forward pass,
    blocks the automatic psum on the backward pass)."""
    return jax.lax.pcast(x, axis_name, to="varying")


def optimizer_lr(optimizer: SGD, step: Array) -> Array:
    lr = optimizer.lr
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


def make_eval_step(apply_fn: ApplyFn, mesh: Mesh, *, axis_name: str = "data"):
    """Build ``eval_step(state, batch) -> {loss_sum, correct, correct5, count}``
    (global sums).

    Equivalent of the reference's eval pass (`core.py:326`) and the global
    metric reduction of ``distributed_predict`` (`train_imagenet_nv.py:523-542`).
    ``batch`` may carry a ``'mask'`` array (1.0 = real example, 0.0 = padding);
    padded examples contribute to no metric — the TPU answer to the
    reference's uneven-final-batch problem (`DistValSampler`,
    `dataloader.py:133-161`, hands ranks possibly-empty batches; we pad to a
    static shape instead so XLA sees one shape per image size).
    """

    def local_eval(state: TrainState, x: Array, y: Array, mask: Array):
        logits, _ = apply_fn(state.params, state.batch_stats, x, False, {})
        loss = jnp.sum(cross_entropy_per_example(logits, y) * mask)
        correct1 = jnp.sum((jnp.argmax(logits, axis=1) == y) * mask)
        top5 = jax.lax.top_k(logits, min(5, logits.shape[-1]))[1]
        correct5 = jnp.sum(jnp.any(top5 == y[:, None], axis=1) * mask)
        return {
            "loss_sum": jax.lax.psum(loss, axis_name),
            "correct": jax.lax.psum(correct1, axis_name),
            "correct5": jax.lax.psum(correct5, axis_name),
            "count": jax.lax.psum(jnp.sum(mask), axis_name),
        }

    state_spec = TrainState(
        step=P(), params=P(), batch_stats=P(), opt_state=P(), ef=P(axis_name),
        rng=P(), comp=P(axis_name), guard=P(), control=P(),
    )
    sharded = jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(state_spec, P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(),
    )

    @jax.jit
    def eval_step(state: TrainState, batch: Dict[str, Array]):
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones((batch["target"].shape[0],), jnp.float32)
        return sharded(state, batch["input"], batch["target"], mask)

    return eval_step
