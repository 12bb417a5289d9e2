"""CIFAR-10 DAWNBench harness — the `CIFAR10/dawn.py` equivalent.

Protocol parity (`dawn.py:98-155`): batch 512; 24 epochs (40 for Random-K /
Threshold-V, `dawn.py:105-108`); ``PiecewiseLinear([0, 5, epochs],
[0, 0.4, 0])`` evaluated at fractional epochs, divided by batch size
(`dawn.py:110,142`); weight decay ``5e-4 * batch_size``; optional Nesterov
momentum (`dawn.py:144-148`); Crop/FlipLR/Cutout augmentation; TSV + table
logging.  Gradients are compressed at summed-loss scale via
``grad_scale=batch_size`` (see train/step.py docstring).

Differences from the reference (intended behaviour, SURVEY.md §2.3):
  * ``--network resnet9`` actually selects ResNet-9 (the reference compared
    against the misspelling 'Resent9' and crashed on its own default);
  * the entire-model path works;
  * rendezvous/mesh come from JAX (no --master_address/--rank plumbing needed
    single-host; multi-host rendezvous flags exist but per-process batch
    sharding is not wired up yet — the harness refuses rather than mis-feeds).

Run: ``python -m tpu_compressed_dp.harness.dawn --synthetic --epochs 2``
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.data import cifar10 as data
from tpu_compressed_dp.harness.loop import (add_adaptive_args,
                                            add_checkpoint_args,
                                            add_robustness_args,
                                            add_stream_args,
                                            add_telemetry_args,
                                            add_topology_args,
                                            build_control,
                                            build_elastic, build_robustness,
                                            control_summary,
                                            elastic_distributed_init,
                                            flight_update, job_scoped,
                                            make_event_stream,
                                            make_flight_recorder,
                                            make_heartbeat,
                                            make_preemption, make_stream,
                                            preempt_exit, profile_trace,
                                            prom_labels,
                                            stream_rejoin_params,
                                            train_epoch)
from tpu_compressed_dp.models import alexnet as alexnet_mod
from tpu_compressed_dp.models import resnet9 as resnet9_mod
from tpu_compressed_dp.models import vgg as vgg_mod
from tpu_compressed_dp.models.common import (
    init_model,
    make_apply_fn,
    make_normalizing_apply_fn,
)
from tpu_compressed_dp.parallel.dp import (CompressionConfig, init_comp_state,
                                           init_ef_state)
from tpu_compressed_dp.parallel.mesh import (make_data_mesh,
                                             setup_compile_cache)
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.guard import init_guard_state
from tpu_compressed_dp.train.schedules import piecewise_linear
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.train.step import make_eval_step, make_train_step
from tpu_compressed_dp.utils import resilience
from tpu_compressed_dp.utils.loggers import TableLogger, TSVLogger
from tpu_compressed_dp.utils.timer import Timer

def _scaled(ch: dict, scale: float) -> dict:
    return {k: max(8, int(v * scale)) for k, v in ch.items()}


def _fixed_width(name: str, ctor, s: float, dtype):
    # no width/dtype knob on these: refuse a non-default instead of silently
    # building full-width fp32 (would mislabel every downstream timing)
    if s != 1.0:
        raise ValueError(f"{name} does not support channels_scale")
    if dtype != jnp.float32:
        raise ValueError(f"{name} does not support --dtype (fp32 only)")
    return ctor()


MODELS = {
    # channels_scale reproduces the width ablations of the reference's
    # experiments.ipynb (half/double width nets, SURVEY.md §6) and keeps CPU
    # smoke tests fast.  dtype=bfloat16 is the TPU-native mixed-precision
    # posture (bf16 compute / fp32 masters; the reference's fp16util.py role).
    "resnet9": lambda s=1.0, dtype=jnp.float32: resnet9_mod.ResNet9(
        channels=_scaled({"prep": 64, "layer1": 128, "layer2": 256, "layer3": 512}, s),
        dtype=dtype,
    ),
    "alexnet": lambda s=1.0, dtype=jnp.float32: resnet9_mod.AlexNetGraph(
        channels=_scaled(
            {"prep": 64, "layer1": 192, "layer2": 384, "layer3": 256, "layer4": 256}, s
        ),
        dtype=dtype,
    ),
    "alexnet_module": lambda s=1.0, dtype=jnp.float32: _fixed_width(
        "alexnet_module", alexnet_mod.AlexNet, s, dtype),
    "vgg16": lambda s=1.0, dtype=jnp.float32: _fixed_width(
        "vgg16", vgg_mod.vgg16, s, dtype),
    # spec-built variants via the graph runtime (`core.py:136`-equivalent)
    "resnet9_graph": lambda s=1.0, dtype=jnp.float32: _graph_net("resnet9", s, dtype),
    "alexnet_graph": lambda s=1.0, dtype=jnp.float32: _graph_net("alexnet", s, dtype),
}


def _graph_net(kind: str, scale: float, dtype=jnp.float32):
    from tpu_compressed_dp.models import graph as graph_mod

    base = {"resnet9": {"prep": 64, "layer1": 128, "layer2": 256, "layer3": 512},
            "alexnet": {"prep": 64, "layer1": 192, "layer2": 384,
                        "layer3": 256, "layer4": 256}}[kind]
    ch = {k: max(int(v * scale), 8) for k, v in base.items()}
    spec = (graph_mod.resnet9_spec(channels=ch, dtype=dtype) if kind == "resnet9"
            else graph_mod.alexnet_spec(channels=ch, dtype=dtype))
    return graph_mod.GraphNet(spec)


def warmup_ratio_for_epoch(epoch: int, *, ratio: float, warmup_epochs: int,
                           method) -> float:
    """DGC-style sparsity warm-up: geometric decay ``ratio^((e+1)/N)`` toward
    ``ratio`` over the first ``warmup_epochs``, rounded to 2 significant
    digits so close epochs share a compile.  The single source of the
    schedule — the harness applies it per epoch and
    tools/time_to_accuracy.py integrates it into ``effective_sent_frac``."""
    from tpu_compressed_dp.ops.compressors import canonical_name

    if (warmup_epochs <= 0 or epoch >= warmup_epochs or method is None
            or canonical_name(method) not in ("topk", "randomk", "blocktopk")):
        return ratio
    r = ratio ** ((epoch + 1) / warmup_epochs)
    from math import floor, log10

    digits = -int(floor(log10(abs(r)))) + 1
    return min(1.0, round(r, digits))


def build_parser() -> argparse.ArgumentParser:
    # flag surface mirrors `dawn.py:8-20`
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data_dir", type=str, default="./data")
    p.add_argument("--log_dir", type=str, default=".")
    p.add_argument("--network", "-n", type=str, default="resnet9", choices=sorted(MODELS))
    p.add_argument("--compress", "-c", type=str, default="none",
                   choices=["none", "layerwise", "entiremodel", "bucketed"])
    p.add_argument("--method", type=str, default="none")
    p.add_argument("--ratio", "-K", type=float, default=0.5)
    p.add_argument("--threshold", "-V", type=float, default=0.001)
    p.add_argument("--qstates", "-Q", type=int, default=255)
    p.add_argument("--rank", type=int, default=4,
                   help="r for powersgd (per-group payload r*(m + n/m) fp32 "
                        "words on the psum ring)")
    p.add_argument("--block_size", type=int, default=256,
                   help="blocktopk: elements per contiguous block")
    p.add_argument("--bucket_mb", type=float, default=25.0,
                   help="bucketed granularity: capacity per bucket")
    p.add_argument("--wire_cap_ratio", type=float, default=0.05,
                   help="wire thresholdv/adaptive_threshold: transport "
                        "capacity as a fraction of elements (size via "
                        "comm/threshold_overflow)")
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--clip_norm", type=float, default=0.0,
                   help="local-gradient L2 clip (mean-loss units; 0=off) — the "
                        "DGC-style stabiliser for EF + momentum (see "
                        "tools/ef_bisect.py)")
    p.add_argument("--clip_sent_norm", type=float, default=0.0,
                   help="post-aggregation L2 clip of the synced gradient "
                        "(bounds the EF residual spike; see tools/ef_bisect.py)")
    p.add_argument("--mode", type=str, default="simulate", choices=["simulate", "wire"])
    p.add_argument("--transport", default="allgather",
                   choices=["allgather", "sharded", "hierarchical"],
                   help="wire combine for index-carrying sparsifiers: flat "
                        "all_gather (O(W*k)/chip), owner-sharded reduce "
                        "(O(k + n/W)/chip, ops/wire_sharded.py; size caps "
                        "via comm/shard_overflow), or the two-level "
                        "hierarchical reduce over a --dp_pods x chips "
                        "virtual mesh (O(k + n/W_pods) DCN bytes)")
    add_topology_args(p)
    p.add_argument("--error_feedback", action="store_true")
    p.add_argument("--overlap", type=int, default=1,
                   help="chunk-pipelined sync (parallel/overlap.py): split "
                        "the gradient sync into up to K reverse-topological "
                        "chunk collectives XLA interleaves with backward + "
                        "per-chunk optimizer compute; numerics unchanged "
                        "(1 = single dispatch)")
    p.add_argument("--ratio_warmup_epochs", type=int, default=0,
                   help="DGC-style sparsity warm-up (Lin et al., ICLR'18): "
                        "keep-ratio decays geometrically from ~dense to "
                        "--ratio over the first N epochs (epoch-level, one "
                        "recompile per distinct ratio).  Early training — "
                        "where EF x momentum spikes are most destructive — "
                        "runs near-dense; only topk/randomk/blocktopk")
    p.add_argument("--epochs", type=int, default=None, help="override the 24/40 rule")
    p.add_argument("--batch_size", type=int, default=512)
    p.add_argument("--peak_lr", type=float, default=0.4)
    p.add_argument("--lr_schedule", type=str, default="dawn",
                   choices=["dawn", "step"],
                   help="'dawn' = the CIFAR triangle (`dawn.py:110`); 'step' = "
                        "the reference's ImageNet shape (warmup to peak, flat, "
                        "peak/10 at 60%%, peak/100 at 85%% — `train.py:60-72`), "
                        "the regime the reference actually ran sparsified DDP "
                        "under.  EF + momentum needs 'step' with a ~10x lower "
                        "peak than dawn's (see benchmarks/ef_momentum_bisect_r3)")
    p.add_argument("--devices", type=int, default=None, help="mesh size (default: all)")
    p.add_argument("--synthetic", action="store_true", help="synthetic data smoke run")
    p.add_argument("--synthetic_hard", action="store_true",
                   help="non-saturating synthetic benchmark (dense ~0.9 test "
                        "acc under the 24-epoch protocol) for method x k "
                        "convergence sweeps")
    p.add_argument("--synthetic_n", type=int, default=2048, help="synthetic train-set size")
    p.add_argument("--dtype", type=str, default="float32",
                   choices=["float32", "bfloat16"],
                   help="compute dtype (params stay fp32 masters; bfloat16 "
                        "is the TPU answer to the reference's fp16util.py)")
    p.add_argument("--channels_scale", type=float, default=1.0,
                   help="width multiplier for the graph-family nets")
    p.add_argument("--seed", type=int, default=0)
    # robustness: shared --guard*/--chaos/--heartbeat surface
    add_robustness_args(p, check_note="checked at epoch end")
    # adaptive compression: shared --adaptive* surface (control/)
    add_adaptive_args(p)
    # checkpointing: shared --checkpoint_dir/--resume/--ckpt_every surface
    add_checkpoint_args(p, cadence_help="epochs between async checkpoint "
                                        "saves (requires --checkpoint_dir; "
                                        "0 = emergency/final saves only)")
    # delta state streaming: shared --stream* surface (stream/)
    add_stream_args(p, cadence_help="epochs between delta-stream appends "
                                    "(requires --stream_dir; 0 disables "
                                    "the periodic append)")
    # telemetry: shared --events/--prom surface (obs/export.py)
    add_telemetry_args(p)
    p.add_argument("--tensorboard", action="store_true",
                   help="write tensorboard scalars under <log_dir>/tb")
    p.add_argument("--profile_epoch", type=int, default=None,
                   help="jax.profiler-trace this epoch to <log_dir>/profile")
    # multi-host rendezvous (the reference's --master_address/--rank/--world_size)
    p.add_argument("--coordinator", type=str, default=None)
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    return p


def default_epochs(method: str) -> int:
    # `dawn.py:105-108`
    return 40 if method.lower() in ("randomk", "thresholdv") else 24


class ShardedBatches:
    """Per-process view of a deterministic global batch stream.

    The multi-host analog of ``DistributedSampler`` (`dataloader.py:33`):
    every process iterates the SAME global batches (identical seed -> identical
    shuffle + augmentation draws), slices its rank's contiguous shard, and
    assembles the global device array whose shards live on local devices
    (``make_global_batch``).  Identity pass-through single-process.  Eval
    batches are padded to the static batch size first so every rank's shard
    keeps one shape (`pad_batch` semantics).
    """

    def __init__(self, inner, mesh, pad_to: Optional[int] = None,
                 already_local: bool = False):
        self.inner = inner
        self.mesh = mesh
        self.pad_to = pad_to
        self.already_local = already_local  # inner yields rank-local slices

    def __len__(self):
        return len(self.inner)

    def __iter__(self):
        from tpu_compressed_dp.harness.loop import pad_batch
        from tpu_compressed_dp.parallel.mesh import make_global_batch

        rank, procs = jax.process_index(), jax.process_count()
        for b in self.inner:
            if self.pad_to is not None:
                b = pad_batch(b, self.pad_to)
            if procs == 1:
                yield b
                continue
            if self.already_local:
                local = {k: np.asarray(v) for k, v in b.items()}
            else:
                n = len(b["target"])
                per = n // procs
                local = {k: np.asarray(v)[rank * per:(rank + 1) * per]
                         for k, v in b.items()}
            yield make_global_batch(local, self.mesh)


def run(args) -> dict:
    # Pure CLI-flag consistency first, before any I/O or device work.
    if args.method.lower() != "none" and args.compress == "none":
        raise ValueError(
            f"--method {args.method} requires --compress layerwise|entiremodel "
            "(the reference silently trained dense here; we refuse instead)"
        )
    if getattr(args, "adaptive", False) and args.ratio_warmup_epochs > 0:
        raise ValueError(
            "--adaptive and --ratio_warmup_epochs both drive the keep-ratio; "
            "pick one (the controller's rung 0 is the static baseline, so "
            "adaptive runs start dense-ish on their own ladder)"
        )
    rejoin = elastic_distributed_init(args)
    mesh = make_data_mesh(args.devices)
    ndev = mesh.shape["data"]
    epochs = args.epochs if args.epochs is not None else default_epochs(args.method)
    bs = args.batch_size
    if bs % ndev:
        raise ValueError(f"batch_size {bs} not divisible by mesh size {ndev}")

    print(f"mesh: {ndev} devices; network={args.network} compress={args.compress} "
          f"method={args.method} epochs={epochs}")

    if args.synthetic_hard:
        dataset = data.synthetic_cifar10_hard(
            n_train=args.synthetic_n, n_test=max(args.synthetic_n // 4, bs))
    elif args.synthetic:
        dataset = data.synthetic_cifar10(
            n_train=args.synthetic_n, n_test=max(args.synthetic_n // 4, bs))
    else:
        dataset = data.load_cifar10(args.data_dir)

    # batches stay uint8 end-to-end; the compiled step normalises on device
    # (1 byte/pixel over the host->device wire instead of 4)
    train_x = data.pad(dataset["train"]["data"])
    test_x = dataset["test"]["data"]
    procs = jax.process_count()
    train_batches = data.Batches(
        train_x, dataset["train"]["labels"], bs, shuffle=True, augment=True,
        drop_last=True, seed=args.seed,
        shard=(jax.process_index(), procs) if procs > 1 else None)
    test_batches = data.Batches(test_x, dataset["test"]["labels"], bs,
                                shuffle=False, augment=False, drop_last=False)
    if procs > 1:
        # multi-process: every rank feeds its shard of the global batch
        # (bs % ndev == 0 was checked above; ndev counts global devices and
        # the process count divides it, so per-rank shards are equal-sized).
        # Train batches come rank-local from the sharded iterator (identical
        # RNG stream on all ranks, pixel work only for the local rows).
        train_batches = ShardedBatches(train_batches, mesh, already_local=True)
        test_batches = ShardedBatches(test_batches, mesh, pad_to=bs)

    module = MODELS[args.network](args.channels_scale,
                                  dtype=jnp.dtype(args.dtype).type)
    params, stats = init_model(module, jax.random.key(args.seed),
                               jnp.zeros((1, 32, 32, 3), jnp.float32))

    steps_per_epoch = len(train_batches)
    # `dawn.py:110`: ramp to peak at epoch 5, anneal to 0 at `epochs`.  For
    # short (smoke) runs the ramp point is pulled in so the knots stay strictly
    # increasing and the schedule still anneals to 0.
    ramp_ep = 5 if epochs > 5 else epochs / 2
    if args.lr_schedule == "step":
        # the ImageNet shape (`train.py:60-72`) expressed through the same
        # phase DSL the ImageNet harness uses: warmup -> flat peak -> /10 at
        # 60% -> /100 at 85%.  Warmup spans the first 1/8 of training (the
        # reference's 5-of-~35; a fixed 5 would cross the 60% boundary on
        # short runs and fold the knot sequence non-monotone)
        from tpu_compressed_dp.train.schedules import lr_phases_to_knots

        ramp_s = epochs / 8.0
        knots, vals = lr_phases_to_knots([
            {"ep": (0, ramp_s), "lr": (0.0, args.peak_lr)},
            {"ep": ramp_s, "lr": args.peak_lr},
            {"ep": 0.6 * epochs, "lr": args.peak_lr / 10.0},
            {"ep": 0.85 * epochs, "lr": args.peak_lr / 100.0},
        ])
        sched = piecewise_linear(knots, vals)
    else:
        sched = piecewise_linear([0, ramp_ep, epochs], [0, args.peak_lr, 0])
    lr = lambda step: sched(step / steps_per_epoch) / bs  # noqa: E731 (`dawn.py:142`)
    opt = SGD(
        lr=lr,
        momentum=args.momentum,
        nesterov=args.momentum > 0,
        weight_decay=5e-4 * bs,
    )

    def comp_for_ratio(ratio: float) -> CompressionConfig:
        return CompressionConfig(
            method=None if args.compress == "none" or args.method.lower() == "none" else args.method,
            granularity=args.compress if args.compress != "none" else "layerwise",
            mode=args.mode,
            ratio=ratio,
            threshold=args.threshold,
            qstates=args.qstates,
            block_size=args.block_size,
            bucket_mb=args.bucket_mb,
            wire_cap_ratio=args.wire_cap_ratio,
            transport=args.transport,
            dp_pods=args.dp_pods,
            hier_route_factor_ici=args.hier_route_factor_ici,
            hier_route_factor_dcn=args.hier_route_factor_dcn,
            rank=args.rank,
            error_feedback=args.error_feedback,
            sync_overlap=args.overlap,
        )

    comp = comp_for_ratio(args.ratio)

    def ratio_for_epoch(epoch: int) -> float:
        return warmup_ratio_for_epoch(
            epoch, ratio=args.ratio, warmup_epochs=args.ratio_warmup_epochs,
            method=comp.method)

    guard_cfg, chaos, crash = build_robustness(args, jnp.dtype(args.dtype))
    ctrl_cfg = build_control(args, comp)
    from tpu_compressed_dp.control import init_control_state

    state = TrainState.create(
        params, stats, opt.init(params), init_ef_state(params, comp, ndev),
        jax.random.key(args.seed + 1),
        comp=init_comp_state(params, comp, ndev),
        guard=init_guard_state(guard_cfg),
        control=init_control_state(ctrl_cfg),
    )
    apply_fn = make_normalizing_apply_fn(
        module,
        mean=np.asarray(data.CIFAR10_MEAN) * 255.0,
        std=np.asarray(data.CIFAR10_STD) * 255.0,
    )

    step_cache: dict = {}

    def train_step_for(comp_cfg: CompressionConfig):
        # keyed by the tunable knobs: everything else in comp_cfg is fixed
        # for the run, and (ratio, rank) is exactly what the warm-up
        # schedule and the adaptive controller's rung ladder vary — one
        # compile per visited rung, switches only at epoch boundaries
        key = (comp_cfg.ratio, comp_cfg.rank)
        if key not in step_cache:
            step_cache[key] = make_train_step(
                apply_fn, opt, comp_cfg, mesh,
                grad_scale=float(bs), clip_norm=args.clip_norm,
                clip_sent_norm=args.clip_sent_norm,
                guard_cfg=guard_cfg, chaos=chaos)
        return step_cache[key]

    eval_step = make_eval_step(apply_fn, mesh)

    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
    start_epoch = 0
    if args.resume:
        restorer = Checkpointer(args.resume)
        try:
            state, meta = restorer.restore(state)
        finally:
            restorer.close()
        start_epoch = int(meta.get("epoch", -1)) + 1
        print(f"resumed step {int(state.step)} from {args.resume} "
              f"(starting epoch {start_epoch})")
    # fresh or restored, the state is built on one device: lay it out as the
    # step's in_specs expect (EF and compressor rows over the data axis)
    # before the first call, so that call neither holds every worker's rows
    # on device 0 nor compiles for a layout no later call has.  (Several
    # processes: device_put cannot address the other hosts' devices; each
    # process hands jit its identical local copy, as before.)
    if procs == 1:
        state = state.with_mesh_sharding(mesh)

    # epoch summaries print master-only, like the reference's rank-0-gated
    # loggers (`logger.py:74-121`); metrics are globally reduced so every
    # rank computes identical numbers anyway
    rank0 = jax.process_index() == 0
    table, tsv = TableLogger(), TSVLogger()
    # No explicit device sync needed: run_train_epoch keeps metrics on device
    # during the epoch (async dispatch overlaps host batch prep with device
    # work) and its end-of-epoch device_get blocks on everything outstanding —
    # the role torch.cuda.synchronize played in `dawn.py:129`.
    timer = Timer()
    from tpu_compressed_dp.utils.loggers import TensorboardLogger

    tb = TensorboardLogger(
        os.path.join(args.log_dir, "tb")
        if args.log_dir and args.tensorboard and rank0 else None
    )
    hb = make_heartbeat(args)
    from tpu_compressed_dp.obs.export import telemetry_snapshot, write_prometheus
    from tpu_compressed_dp.obs.trace import StepTimeline
    from tpu_compressed_dp.utils import flops as flops_mod

    timeline = StepTimeline()
    events = make_event_stream(
        args, harness="dawn", network=args.network,
        method=args.method, compress=args.compress, mode=args.mode,
        transport=args.transport, batch_size=bs, devices=ndev, epochs=epochs)
    flight = make_flight_recorder(
        args, harness="dawn", network=args.network, method=args.method,
        compress=args.compress, devices=ndev)
    if flight is not None and chaos is not None:
        flight.note_chaos(chaos)
    if flight is not None and crash is not None:
        crash.flight = flight
    stream = make_stream(args, flight=flight, events=events)
    if ckpt is not None:
        ckpt.events = events
        ckpt.flight = flight
        # committed full checkpoints re-anchor the delta stream's window
        ckpt.stream = stream
    preempt = make_preemption()
    el = build_elastic(args, mesh, chaos=chaos, crash=crash, events=events,
                       flight=flight, stream=stream)
    if el is not None and rejoin is not None:
        # watchdog-relaunched host: adopt the running world's replicated
        # state from the re-elected coordinator's broadcast (EF rows start
        # at zero) and retrace the steps on the post-join mesh.  With
        # --stream_rejoin and a warm-committed epoch the params come off
        # the delta stream instead of the broadcast (the survivors'
        # barrier flushed it bitwise-equal to the live params before
        # admitting us, and published the warm bit in the commit).
        adopted_params, adopted_info = stream_rejoin_params(
            args, state, rejoin, flight=flight)
        state = el.join_world(state, rejoin, adopted_params=adopted_params,
                              adopted_info=adopted_info)
        mesh, ndev = el.mesh, el.world
        step_cache.clear()
        eval_step = make_eval_step(apply_fn, mesh)
    controller = None
    hide_frac = 1.0
    if ctrl_cfg is not None:
        from tpu_compressed_dp.control import Controller, comp_for_rung
        from tpu_compressed_dp.parallel.overlap import (hideable_byte_fraction,
                                                        plan_chunks)
        from tpu_compressed_dp.train.guard import schedule_step

        controller = Controller(ctrl_cfg, events=events)
        # the overlap schedule's hideable byte fraction scales the measured
        # compute into the per-update budget (signals.hideable_budget_ms);
        # ignored when --adaptive_budget_ms pins the budget
        hide_frac = hideable_byte_fraction(plan_chunks(
            [leaf.size * 4 for leaf in jax.tree_util.tree_leaves(params)],
            comp))
        print(f"adaptive: method={ctrl_cfg.method} knob={controller.knob} "
              f"rungs={ctrl_cfg.rungs} window={ctrl_cfg.window} "
              f"signal={ctrl_cfg.signal} hideable_frac={hide_frac:.3f}")
    # Per-chip forward FLOPs from XLA's cost model, once (the epoch loop
    # scales it by the measured step rate — utils/flops.py conventions:
    # train = 3x fwd, MFU vs the chip's bf16 peak, omitted off-TPU).  The
    # cost-model pass compiles the bare forward; skip it when nothing can
    # consume the result (no exporter and no known chip peak — the CPU
    # smoke-test case, where it would only slow every run down).
    want_flops = (events is not None or bool(args.prom)
                  or flops_mod.chip_peak_flops() is not None)
    fwd_flops = flops_mod.fwd_flops_xla(
        lambda p, s, x: apply_fn(p, s, x, True, {}),
        params, stats, jnp.zeros((bs // ndev, 32, 32, 3), jnp.float32)
    ) if want_flops else None
    prev_skipped = 0.0
    summary = {}
    # finally-guarded: GuardExceeded / ChaosCrash / any training failure must
    # not leak the heartbeat writer thread — an orphaned writer keeps
    # refreshing ts and turns a dead run into a stale-detection false
    # negative (the exact failure mode the watchdog reads this file for) —
    # nor a running profiler trace or an unterminated event stream
    try:
        cur_train, cur_test, cur_bs = train_batches, test_batches, bs
        epoch = start_epoch
        while epoch < epochs:
            # boundary check: a signal that landed during eval/logging stops
            # the run before the next epoch compiles/dispatches anything
            preempt.check(int(state.step))
            profiling = args.profile_epoch == epoch and args.log_dir
            # adaptive: the checkpointed rung picks the (trace-cached) step
            # variant; otherwise the DGC warm-up schedule picks the ratio
            train_step = train_step_for(
                comp_for_rung(comp, ctrl_cfg, int(state.control.rung))
                if controller is not None
                else comp_for_ratio(ratio_for_epoch(epoch)))
            try:
                with profile_trace(
                        os.path.join(args.log_dir, "profile") if profiling else None):
                    state, epoch_stats, acc = train_epoch(
                        train_step, eval_step, state, cur_train, cur_test,
                        timer, cur_bs, test_time_in_total=False,
                        crash=crash, step_offset=int(state.step),
                        guard_cfg=guard_cfg, timeline=timeline, world=ndev,
                        pods=args.dp_pods,
                        elastic=el, preempt=preempt, flight=flight,
                    )
            except Exception as err:
                failure = el.failure_from(err) if el is not None else None
                if failure is None:
                    if flight is not None and not isinstance(
                            err, resilience.Preempted):
                        # unconverted failure about to unwind the run: the
                        # dump here is the only evidence this rank leaves
                        # (guard/ckpt/elastic dumps fire on their own paths)
                        flight.observe(err, step=int(state.step))
                    raise
                # Coordinated abort: survivors remesh from the last live
                # TrainState (the pre-epoch buffers were donated away at
                # step 0, so run_train_epoch rides its local out on the
                # exception; dispatched steps drain to completion during
                # migration) and replay the rest of the epoch.  Rebuilding
                # the step cache on el.mesh is what recomputes the sharded
                # transport's owner partition; the batch views are trimmed
                # so the smaller world keeps dividing them.  Injectors fire
                # once per process, so the replay does not re-crash.
                state = getattr(err, "elastic_state", state)
                state = el.handle_failure(state, failure)
                mesh, ndev = el.mesh, el.world
                step_cache.clear()
                eval_step = make_eval_step(apply_fn, mesh)
                cur_bs = (bs // ndev) * ndev
                from tpu_compressed_dp.train.elastic import TrimBatches
                cur_train = TrimBatches(train_batches, cur_bs)
                cur_test = TrimBatches(test_batches, cur_bs)
                continue
            if el is not None:
                # epoch-boundary readmission of watchdog-relaunched hosts
                # parked in the rendezvous join barrier (no-op otherwise)
                state, grew = el.rejoin_barrier(state)
                if grew:
                    mesh, ndev = el.mesh, el.world
                    step_cache.clear()
                    eval_step = make_eval_step(apply_fn, mesh)
                    cur_bs = (bs // ndev) * ndev
                    from tpu_compressed_dp.train.elastic import TrimBatches
                    cur_train = TrimBatches(train_batches, cur_bs)
                    cur_test = TrimBatches(test_batches, cur_bs)
            if controller is not None:
                # decisions key off APPLIED updates (guard skips excluded),
                # and the tick lands BEFORE the epoch checkpoint: the saved
                # ControlState already contains this epoch's accumulation,
                # so a crash-relaunch replays the remaining windows bitwise
                # instead of losing this epoch's contribution
                applied = (schedule_step(guard_cfg, state.guard,
                                         int(state.step))
                           if guard_cfg is not None else int(state.step))
                wall_ms = (epoch_stats["train time"] * 1e3
                           / max(acc.steps, 1))
                old_rung = int(state.control.rung)
                new_control, _ = controller.tick(
                    state.control, applied=applied,
                    signals=controller.window_signals(
                        mean_bits=acc.mean("comm/sent_bits"),
                        measured_comm_ms=wall_ms,
                        compute_ms=wall_ms,
                        hideable_fraction=hide_frac))
                state = state.replace(control=new_control)
                new_rung = int(new_control.rung)
                if flight is not None:
                    flight.note_control({"epoch": epoch, "rung": new_rung,
                                         "applied": applied})
                if new_rung != old_rung and controller.knob == "rank":
                    # PowerSGD rank switch: re-seat the warm q columns at
                    # the new rank so the next rung's step variant starts
                    # from the learnt subspace, not a cold re-init
                    from tpu_compressed_dp.control import (comp_for_rung,
                                                           migrate_comp_state)
                    state = state.replace(comp=migrate_comp_state(
                        state.comp, params,
                        comp_for_rung(comp, ctrl_cfg, old_rung),
                        comp_for_rung(comp, ctrl_cfg, new_rung), ndev))
            if (ckpt is not None and args.ckpt_every > 0
                    and (epoch + 1) % args.ckpt_every == 0):
                # async: snapshot to host and return — the write overlaps
                # the next epoch; the next save (or preemption) barriers
                ckpt.save_async(state, {"epoch": epoch})
            if (stream is not None and args.stream_every > 0
                    and (epoch + 1) % args.stream_every == 0):
                # delta segment: codec on this thread, commit in the
                # background (stream/writer.py)
                stream.append_async(state.params, step=int(state.step))
            train_time = epoch_stats["train time"]
            examples = len(cur_train) * cur_bs
            thr = flops_mod.throughput_record(
                fwd_flops, acc.steps / max(train_time, 1e-9),
                examples_per_sec=examples / max(train_time, 1e-9))
            # spans drain ONCE per epoch and fan out to every consumer
            # (event stream, flight recorder's timing ring + phase profile)
            spans = timeline.drain()
            fgauges = flight_update(flight, spans=spans)
            if hb is not None:
                # last_good_step: the watchdog's "is it making progress" signal
                # — a wedged-but-alive run (skipping every step) beats but stops
                # advancing this field.  The telemetry snapshot adds step rate
                # + p95 latency for the watchdog's stall check.
                hb.update(
                    step=int(state.step),
                    last_good_step=(int(state.guard.last_good_step)
                                    if guard_cfg is not None else int(state.step)),
                    epoch=epoch,
                    telemetry=telemetry_snapshot(timeline),
                    **(ckpt.heartbeat_fields() if ckpt is not None else {}),
                    **(stream.heartbeat_fields() if stream is not None
                       else {}),
                    **({"elastic": el.metrics()} if el is not None else {}),
                    **(controller.heartbeat_fields(state.control)
                       if controller is not None else {}),
                    **({"straggler_skew_s": fgauges["straggler/skew_s"],
                        "straggler_rank": fgauges["straggler/rank"]}
                       if "straggler/skew_s" in fgauges else {}),
                )
            summary = {
                "epoch": epoch + 1,
                "lr": float(sched((epoch + 1))),
                **{k: (float(v) if isinstance(v, (int, float, np.floating)) else v)
                   for k, v in epoch_stats.items()},
                "img/s": round(thr.get("throughput/examples_per_sec", 0.0), 1),
            }
            if "throughput/mfu" in thr:
                summary["mfu"] = round(thr["throughput/mfu"], 4)
            summary.update(control_summary(controller, state.control))
            guard_last = {k: v for k, v in acc.last.items()
                          if k.startswith("guard/")}
            comm_means = {k: acc.mean(k) for k in acc.sums
                          if k.startswith("comm/")}
            control_stats = (controller.metrics(state.control)
                             if controller is not None else {})
            if events is not None:
                events.emit(
                    "epoch", epoch=epoch + 1, step=int(state.step),
                    metrics={k: v for k, v in summary.items()
                             if isinstance(v, (int, float))},
                    throughput=thr, comm=comm_means, guard=guard_last,
                    control=control_stats,
                    timeline=timeline.snapshot(),
                    step_spans=spans)
                skipped = guard_last.get("guard/skipped", 0.0)
                if skipped > prev_skipped:
                    events.emit("guard", epoch=epoch + 1,
                                step=int(state.step), **guard_last)
                prev_skipped = skipped
            if args.prom and rank0:
                write_prometheus(
                    {"loss": summary["train loss"], "lr": summary["lr"],
                     **thr, **comm_means, **guard_last, **control_stats,
                     **timeline.snapshot(),
                     **(ckpt.metrics() if ckpt is not None else {}),
                     **(stream.metrics() if stream is not None else {}),
                     **(el.metrics() if el is not None else {}),
                     **fgauges},
                    job_scoped(args, args.prom),
                    labels=prom_labels(args, harness="dawn"))
            if rank0:
                table.append(summary)
                tsv.append(summary)
                tb.update_examples_count(len(cur_train) * cur_bs)
                tb.log_metrics({f"losses/{k}": v for k, v in summary.items()
                                if k in ("train loss", "test loss", "train acc", "test acc")})
                tb.log_scalar("times/epoch_seconds", summary["train time"])
            epoch += 1
        if args.log_dir and rank0:
            tsv.save(args.log_dir)
    except resilience.Preempted as err:
        # SIGTERM/SIGINT landed: drain the in-flight async write, cut a
        # synchronous emergency checkpoint of the live state, and exit with
        # the watchdog's relaunch-immediately code (the finally below still
        # runs — ckpt.close after the emergency save is a no-op drain)
        state = getattr(err, "elastic_state", state)
        raise preempt_exit(err, ckpt=ckpt, state=state,
                           meta={"epoch": epoch - 1}, events=events,
                           flight=flight) from None
    finally:
        preempt.uninstall()
        tb.close()
        if ckpt is not None:
            ckpt.close()  # drains the background writer before events close
        if stream is not None:
            stream.close()  # drains the in-flight segment commit
        if events is not None:
            events.close()
        if hb is not None:
            hb.stop()
    return summary


def main(argv: Optional[list] = None):
    setup_compile_cache()
    args = build_parser().parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
