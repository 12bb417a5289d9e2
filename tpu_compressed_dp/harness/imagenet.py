"""ImageNet ResNet-50 harness — the `IMAGENET/training/train_imagenet_nv.py`
equivalent.

Feature parity (`train_imagenet_nv.py`):
  * phase-schedule mini-DSL mixing data phases (``ep/sz/bs/min_scale/
    rect_val/keep_dl``) and LR phases (``ep/lr`` scalar or ramp), per-batch LR
    granularity (`:545-651`); the default schedule is the reference's
    one-machine 93%-top-5 recipe (`train.py:60-72`);
  * progressive image resizing with per-phase loaders (``DataManager``); on
    TPU each new (bs, sz) is simply a new jit specialisation, pre-warmed at
    phase start the way the reference preloaded loaders (`:575-580`);
  * bf16 compute + fp32 master params (the fp16 + loss-scale-1024 machinery of
    `fp16util.py` collapses to a flax dtype policy on TPU — see models/resnet.py);
  * ``--init-bn0`` zero-gamma init, ``--no-bn-wd`` BN weight-decay exclusion
    (`:168,183-184`);
  * the full compression surface (layer-wise / entire-model x 6 methods,
    simulate / wire, error feedback) in the step (`:417-422`);
  * validation every epoch with global top-1/top-5 psum (the
    ``distributed_predict`` semantics, `:523-542`), rect-val supported;
  * Orbax checkpoint-if-best + phase-boundary saves, ``--resume`` (`:193-198,
    236-253`); the EF residual checkpoints too (fixes SURVEY.md §5 gap);
  * ``--short-epoch`` 10-batch truncation (`:74-75,399,491`) and
    ``--evaluate`` val-only mode (`:58-59,225-226`).

Gradient scale: the reference ImageNet step backpropagates the *mean* loss and
allreduce-averages (`:408,417-422`), so ``grad_scale=1.0`` here (the CIFAR
harness's summed-loss protocol does not apply).

Run (smoke): ``python -m tpu_compressed_dp.harness.imagenet --synthetic
--arch resnet18 --width 16 --num_classes 10 --short_epoch``
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.data import imagenet as data
from tpu_compressed_dp.harness.loop import (
    add_adaptive_args,
    add_robustness_args,
    add_stream_args,
    add_telemetry_args,
    add_topology_args,
    fabric_gauges,
    build_control,
    build_elastic,
    build_robustness,
    control_summary,
    elastic_distributed_init,
    flight_update,
    job_scoped,
    make_event_stream,
    make_flight_recorder,
    make_heartbeat,
    make_preemption,
    make_stream,
    prom_labels,
    stream_rejoin_params,
    comm_summary,
    guard_summary,
    pad_batch,
    preempt_exit,
    profile_trace,
    run_eval,
    run_train_epoch,
)
from tpu_compressed_dp.obs.export import telemetry_snapshot, write_prometheus
from tpu_compressed_dp.obs.trace import StepTimeline
from tpu_compressed_dp.utils import flops as flops_mod
from tpu_compressed_dp.models import resnet as resnet_mod
from tpu_compressed_dp.models.common import init_model, make_apply_fn
from tpu_compressed_dp.parallel.dp import (CompressionConfig, init_comp_state,
                                           init_ef_state)
from tpu_compressed_dp.parallel.mesh import (
    make_data_mesh,
    make_global_batch,
    setup_compile_cache,
)
from tpu_compressed_dp.train.optim import SGD, bn_wd_mask
from tpu_compressed_dp.train.guard import init_guard_state
from tpu_compressed_dp.train.schedules import phase_lr_schedule_variable_bs
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.train.step import make_eval_step, make_train_step
from tpu_compressed_dp.utils import resilience
from tpu_compressed_dp.utils.checkpoint import Checkpointer
from tpu_compressed_dp.utils.loggers import (
    FileLogger,
    TableLogger,
    TensorboardLogger,
    TSVLogger,
)
from tpu_compressed_dp.utils.meters import NetworkMeter
from tpu_compressed_dp.utils.timer import Timer

ARCHS = {
    "resnet18": resnet_mod.resnet18,
    "resnet34": resnet_mod.resnet34,
    "resnet50": resnet_mod.resnet50,
    "resnet101": resnet_mod.resnet101,
    "resnet152": resnet_mod.resnet152,
}


def one_machine_phases() -> List[dict]:
    """The reference's single-machine schedule — 93.00 top-5 in 109 min on
    8x V100 (`IMAGENET/train.py:55-72`): 128px/bs512 -> 224px/bs224 ->
    288px/bs128 with warmup and step decays.  ``bs`` here is the *global*
    batch (reference bs was per-GPU x 8 GPUs)."""
    lr = 1.0
    scale_224 = 224 / 512
    scale_288 = 128 / 512
    return [
        {"ep": 0, "sz": 128, "bs": 512 * 8},
        {"ep": (0, 5), "lr": (lr, lr * 2)},
        {"ep": 5, "lr": lr},
        {"ep": 14, "sz": 224, "bs": 224 * 8, "lr": lr * scale_224},
        {"ep": 16, "lr": lr / 10 * scale_224},
        {"ep": 27, "lr": lr / 100 * scale_224},
        {"ep": 32, "sz": 288, "bs": 128 * 8, "min_scale": 0.5, "rect_val": True,
         "lr": lr / 100 * scale_288},
        {"ep": (33, 35), "lr": lr / 1000 * scale_288},
    ]


def smoke_phases(bs: int = 64) -> List[dict]:
    """Tiny 3-epoch progressive-resize schedule for tests and CPU smoke."""
    return [
        {"ep": 0, "sz": 64, "bs": bs},
        {"ep": (0, 1), "lr": (0.1, 0.2)},
        {"ep": 1, "lr": 0.1},
        {"ep": 2, "sz": 96, "bs": bs // 2, "rect_val": True},
        {"ep": (2, 3), "lr": (0.01, 0.001)},
    ]


def data_phases(phases: List[dict]) -> List[dict]:
    return [p for p in phases if "sz" in p or p.get("keep_dl")]


def total_epochs(phases: List[dict]) -> int:
    """``Scheduler.tot_epochs`` (`train_imagenet_nv.py:607`): max epoch edge."""
    out = 0
    for p in phases:
        ep = p["ep"]
        out = max(out, int(max(ep) if isinstance(ep, (tuple, list)) else ep) + 0)
    return out if out > 0 else 1


class PhaseData:
    """``DataManager`` equivalent (`train_imagenet_nv.py:545-598`): owns the
    current train/val loaders, swapping them at phase-start epochs."""

    def __init__(self, dataset_train, dataset_val, phases: List[dict], *,
                 workers: int = 8, seed: int = 0, min_scale_default: float = 0.08,
                 ar_buckets: int = 8):
        raw = data_phases(phases)
        if not raw or raw[0]["ep"] != 0:
            raise ValueError("first data phase must start at ep 0")
        # Resolve keep_dl up front: each effective phase carries full
        # sz/bs/... settings (a keep_dl phase inherits from its predecessor,
        # `train_imagenet_nv.py:560-565`).
        self.phases: List[dict] = []
        for p in raw:
            merged = {**self.phases[-1], **p} if p.get("keep_dl") and self.phases else dict(p)
            self.phases.append(merged)
        self.ds_train, self.ds_val = dataset_train, dataset_val
        self.workers, self.seed = workers, seed
        self.min_scale_default = min_scale_default
        self.ar_buckets = ar_buckets
        self.cur: Optional[dict] = None
        self.train_loader = None
        self.val_loader = None
        self.val_bs = None

    def phase_at(self, epoch: int) -> dict:
        """The phase governing ``epoch`` (last phase with start <= epoch)."""
        out = self.phases[0]
        for p in self.phases:
            if p["ep"] <= epoch:
                out = p
        return out

    def set_epoch(self, epoch: int) -> bool:
        """Build/swap loaders for the phase governing ``epoch``; returns True
        on a swap (= new shapes are about to hit jit).  Works mid-phase too
        (resume from any epoch, not just phase starts)."""
        phase = self.phase_at(epoch)
        swapped = False
        if phase is not self.cur:
            sz, bs = int(phase["sz"]), int(phase["bs"])
            pi, pc = jax.process_index(), jax.process_count()
            self.train_loader = data.TrainLoader(
                self.ds_train, bs // pc, sz,
                min_scale=float(phase.get("min_scale", self.min_scale_default)),
                seed=self.seed, workers=self.workers,
                process_index=pi, process_count=pc,
            )
            self.val_bs = data.val_batch_size(sz, bs)
            # Rect-val hands each process differently-shaped local batches —
            # fine under the reference's per-process NCCL, incompatible with
            # one global SPMD array; multi-host falls back to square val.
            rect = bool(phase.get("rect_val", False)) and pc == 1
            self.val_loader = data.ValLoader(
                self.ds_val, self.val_bs // pc, sz,
                rect_val=rect,
                ar_buckets=self.ar_buckets, workers=self.workers,
                process_index=pi, process_count=pc,
            )
            self.cur = phase
            swapped = True
        self.train_loader.set_epoch(epoch)
        return swapped

    def epoch_batches(self, epochs: int) -> List[int]:
        """Per-epoch step counts for the step->epoch LR map."""
        pc = jax.process_count()
        out = []
        for e in range(epochs):
            bs = int(self.phase_at(e)["bs"]) // pc
            out.append(max((len(self.ds_train) // pc) // bs, 1))
        return out


def _normalizing_apply_fn(module):
    """uint8 NHWC batches normalised on device — the
    ``BatchTransformDataLoader.process_tensors`` trick (`dataloader.py:92-99`)
    via the shared adapter."""
    from tpu_compressed_dp.models.common import make_normalizing_apply_fn

    return make_normalizing_apply_fn(module, data.IMAGENET_MEAN, data.IMAGENET_STD)


def build_parser() -> argparse.ArgumentParser:
    # flag surface mirrors `train_imagenet_nv.py:39-91`
    p = argparse.ArgumentParser(description="ImageNet compressed-DP harness")
    p.add_argument("data", nargs="?", default=None, help="ImageFolder root with train/ and validation/")
    p.add_argument("--arch", "-a", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--width", type=int, default=64, help="stem width (64 = standard)")
    p.add_argument("--num_classes", type=int, default=1000)
    p.add_argument("--phases", type=str, default=None,
                   help="JSON phase list; default = reference one-machine schedule")
    p.add_argument("--lr_scale", type=float, default=1.0,
                   help="multiply all phase LRs (bs scaling)")
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight_decay", "--wd", type=float, default=1e-4)
    p.add_argument("--no_bn_wd", action="store_true", help="exclude BN params from wd")
    p.add_argument("--init_bn0", action="store_true", help="zero-init last-BN gammas")
    p.add_argument("--fp32", action="store_true", help="disable bf16 compute")
    p.add_argument("--compress", "-c", default="none", choices=["none", "layerwise", "entiremodel", "bucketed"])
    p.add_argument("--method", default="none")
    p.add_argument("--ratio", "-K", type=float, default=0.5)
    p.add_argument("--threshold", "-V", type=float, default=0.001)
    p.add_argument("--qstates", "-Q", type=int, default=255)
    p.add_argument("--rank", type=int, default=4,
                   help="r for powersgd (psum-ring low-rank factors)")
    p.add_argument("--block_size", type=int, default=256,
                   help="blocktopk: elements per contiguous block")
    p.add_argument("--bucket_mb", type=float, default=25.0,
                   help="bucketed granularity: capacity per bucket")
    p.add_argument("--mode", default="simulate", choices=["simulate", "wire"])
    p.add_argument("--transport", default="allgather",
                   choices=["allgather", "sharded", "hierarchical"],
                   help="wire combine for index-carrying sparsifiers: flat "
                        "all_gather (O(W*k)/chip), owner-sharded reduce "
                        "(O(k + n/W)/chip, ops/wire_sharded.py; size caps "
                        "via comm/shard_overflow), or the two-level "
                        "hierarchical reduce over a --dp_pods x chips "
                        "virtual mesh (dense intra-pod psum + sparse "
                        "inter-pod exchange, O(k + n/W_pods) DCN bytes)")
    add_topology_args(p)
    p.add_argument("--error_feedback", action="store_true")
    p.add_argument("--overlap", type=int, default=1,
                   help="chunk-pipelined sync (parallel/overlap.py): up to "
                        "K reverse-topological chunk collectives interleaved "
                        "with backward + per-chunk optimizer compute; "
                        "numerics unchanged (1 = single dispatch)")
    p.add_argument("--wire_cap_ratio", type=float, default=0.05,
                   help="wire thresholdv/adaptive_threshold transport "
                        "capacity (fraction of elements)")
    p.add_argument("--clip_norm", type=float, default=0.0,
                   help="local-gradient L2 clip (0=off) — EF+momentum "
                        "stabiliser (see tools/ef_bisect.py)")
    p.add_argument("--clip_sent_norm", type=float, default=0.0,
                   help="post-aggregation L2 clip of the synced gradient "
                        "(bounds the EF residual spike)")
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--seed", type=int, default=2147483647)  # `train_imagenet_nv.py:82`
    p.add_argument("--short_epoch", action="store_true", help="10-batch epochs")
    p.add_argument("--evaluate", action="store_true")
    p.add_argument("--resume", type=str, default=None)
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--best_floor", type=float, default=0.0,
                   help="min top-5 before checkpointing (reference used 93)")
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--synthetic_n", type=int, default=512)
    # robustness: shared --guard*/--chaos/--heartbeat surface
    add_robustness_args(p, check_note="checked at epoch end")
    # adaptive compression: shared --adaptive* surface (control/)
    add_adaptive_args(p)
    # delta state streaming: shared --stream* surface (stream/)
    add_stream_args(p, cadence_help="epochs between delta-stream appends "
                                    "(requires --stream_dir; 0 disables "
                                    "the periodic append)")
    # telemetry: shared --events/--prom surface (obs/export.py)
    add_telemetry_args(p)
    p.add_argument("--logdir", type=str, default=None)
    p.add_argument("--tensorboard", action="store_true",
                   help="write tensorboard scalars under <logdir>/tb")
    p.add_argument("--profile_epoch", type=int, default=None,
                   help="jax.profiler-trace this epoch to <logdir>/profile")
    # multi-host rendezvous
    p.add_argument("--coordinator", type=str, default=None)
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    return p


def _truncate(it, n: Optional[int]):
    if n is None:
        yield from it
        return
    for i, b in enumerate(it):
        if i >= n:
            break
        yield b


def run(args) -> Dict[str, float]:
    # CLI-flag consistency first, before any I/O or device work (same refusal
    # as the CIFAR harness; the reference silently trained dense here).
    if args.method.lower() != "none" and args.compress == "none":
        raise ValueError(
            f"--method {args.method} requires --compress layerwise|entiremodel"
        )
    rejoin = elastic_distributed_init(args)
    mesh = make_data_mesh(args.devices)
    ndev = mesh.shape["data"]

    if args.synthetic:
        ds_train = data.SyntheticImages(args.synthetic_n, args.num_classes, seed=0)
        ds_val = data.SyntheticImages(max(args.synthetic_n // 4, 64), args.num_classes, seed=7)
    else:
        if not args.data:
            raise ValueError("pass an ImageFolder root or --synthetic")
        ds_train = data.ImageFolder(f"{args.data}/train")
        ds_val = data.ImageFolder(f"{args.data}/validation")

    phases = json.loads(args.phases) if args.phases else (
        smoke_phases() if args.synthetic else one_machine_phases()
    )
    if args.lr_scale != 1.0:
        for p in phases:
            if "lr" in p:
                lr = p["lr"]
                p["lr"] = tuple(v * args.lr_scale for v in lr) if isinstance(
                    lr, (tuple, list)) else lr * args.lr_scale
    epochs = total_epochs(phases)

    pd = PhaseData(ds_train, ds_val, phases, workers=args.workers, seed=args.seed)
    epoch_batches = pd.epoch_batches(epochs)
    if args.short_epoch:
        epoch_batches = [min(n, 10) for n in epoch_batches]
    lr_sched = phase_lr_schedule_variable_bs(phases, epoch_batches)

    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    module = ARCHS[args.arch](num_classes=args.num_classes, bn0=args.init_bn0,
                              dtype=dtype, width=args.width)
    first_sz = int(pd.phases[0]["sz"])
    params, stats = init_model(module, jax.random.key(args.seed % (2**31)),
                               jnp.zeros((1, first_sz, first_sz, 3), jnp.float32))
    apply_fn = _normalizing_apply_fn(module)

    opt = SGD(
        lr=lr_sched, momentum=args.momentum, nesterov=False,
        weight_decay=args.weight_decay,
        wd_mask=bn_wd_mask(params) if args.no_bn_wd else None,
    )
    comp = CompressionConfig(
        method=None if args.compress == "none" or args.method.lower() == "none" else args.method,
        granularity=args.compress if args.compress != "none" else "layerwise",
        mode=args.mode, ratio=args.ratio, threshold=args.threshold,
        qstates=args.qstates, block_size=args.block_size,
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
    guard_cfg, chaos, crash = build_robustness(args, dtype)
    ctrl_cfg = build_control(args, comp)
    from tpu_compressed_dp.control import init_control_state

    state = TrainState.create(
        params, stats, opt.init(params), init_ef_state(params, comp, ndev),
        jax.random.key((args.seed + 1) % (2**31)),
        comp=init_comp_state(params, comp, ndev),
        guard=init_guard_state(guard_cfg),
        control=init_control_state(ctrl_cfg),
    )

    ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
    start_epoch = 0
    if args.resume:
        restore = Checkpointer(args.resume)
        state, meta = restore.restore(state)
        restore.close()
        start_epoch = int(meta.get("epoch", 0)) + 1
        if ckpt is not None and restore.best_metric is not None:
            # carry best-so-far forward so a worse epoch can't evict the true
            # best (the reference restores best_top5, `train_imagenet_nv.py:195-197`)
            ckpt.best_metric = restore.best_metric
        print(f"resumed step {int(state.step)} (epoch {start_epoch})")
    # fresh or restored, the state is built on one device: lay it out as the
    # step's in_specs expect before the first call (see harness/dawn.py)
    if jax.process_count() == 1:
        state = state.with_mesh_sharding(mesh)

    step_cache: Dict = {}

    def active_comp() -> CompressionConfig:
        """The compression config the next epoch should trace under: the
        controller's checkpointed rung when adaptive, the static one else."""
        if ctrl_cfg is None:
            return comp
        from tpu_compressed_dp.control import comp_for_rung
        return comp_for_rung(comp, ctrl_cfg, int(state.control.rung))

    def train_step_for(comp_cfg: CompressionConfig):
        # keyed by the tunable knobs (the rung ladder varies exactly these);
        # cleared wholesale on remesh — entries close over the current mesh
        key = (comp_cfg.ratio, comp_cfg.rank)
        if key not in step_cache:
            step_cache[key] = make_train_step(
                apply_fn, opt, comp_cfg, mesh, grad_scale=1.0,
                clip_norm=args.clip_norm,
                clip_sent_norm=args.clip_sent_norm,
                guard_cfg=guard_cfg, chaos=chaos)
        return step_cache[key]

    train_step = train_step_for(active_comp())
    eval_step = make_eval_step(apply_fn, mesh)

    def validate(state) -> Dict[str, float]:
        # pad to the *local* static batch, then form global arrays — every
        # process runs the same batch count (DistValSampler semantics).
        # After an elastic remesh the world may stop dividing the loader's
        # batch, so the static eval batch is the largest world-divisible
        # size (identical to local_bs on the launch mesh); surplus rows of
        # a full batch are trimmed, short batches are padded+masked.
        loader = pd.val_loader
        per = int(mesh.shape["data"]) // jax.process_count()
        eval_bs = max((loader.batch_size // per) * per, per)

        def batches():
            for b in _truncate(loader, 10 if args.short_epoch else None):
                b = {k: v[:eval_bs] for k, v in b.items()}
                yield make_global_batch(pad_batch(b, eval_bs), mesh)

        return run_eval(eval_step, state, batches(), eval_bs * jax.process_count())

    table, tsv = TableLogger(), TSVLogger()
    timer = Timer()
    t0 = time.time()
    summary: Dict[str, float] = {}
    is_master = jax.process_index() == 0
    tb = TensorboardLogger(
        os.path.join(args.logdir, "tb") if args.logdir and args.tensorboard else None,
        is_master=is_master,
    )
    flog = FileLogger(args.logdir if is_master else None, rank=jax.process_index(),
                      is_master=is_master)
    net_meter = NetworkMeter()
    hb = make_heartbeat(args)
    timeline = StepTimeline()
    events = make_event_stream(
        args, harness="imagenet", arch=args.arch, method=args.method,
        compress=args.compress, mode=args.mode, transport=args.transport,
        devices=ndev, epochs=epochs)
    flight = make_flight_recorder(
        args, harness="imagenet", arch=args.arch, method=args.method,
        compress=args.compress, devices=ndev)
    if flight is not None and chaos is not None:
        flight.note_chaos(chaos)
    if flight is not None and crash is not None:
        crash.flight = flight
    stream = make_stream(args, flight=flight, events=events)
    if ckpt is not None:
        ckpt.events = events   # save/rollback records on the run's stream
        ckpt.flight = flight
        # committed full checkpoints re-anchor the delta stream's window
        ckpt.stream = stream
    preempt = make_preemption()
    el = build_elastic(args, mesh, chaos=chaos, crash=crash, events=events,
                       flight=flight, stream=stream)
    if el is not None and rejoin is not None:
        # watchdog-relaunched host: the surviving world is mid-training.
        # Adopt its replicated state (broadcast from the re-elected
        # coordinator), zero EF rows, and train on the joined mesh — the
        # jitted steps built above targeted the fresh-init mesh and are
        # rebuilt against the post-join one.  With --stream_rejoin the
        # params adopt from the delta stream, not the broadcast.
        adopted_params, adopted_info = stream_rejoin_params(
            args, state, rejoin, flight=flight)
        state = el.join_world(state, rejoin, adopted_params=adopted_params,
                              adopted_info=adopted_info)
        mesh, ndev = el.mesh, el.world
        step_cache.clear()
        train_step = train_step_for(active_comp())
        eval_step = make_eval_step(apply_fn, mesh)
    controller = None
    hide_frac = 1.0
    if ctrl_cfg is not None:
        from tpu_compressed_dp.control import Controller
        from tpu_compressed_dp.parallel.overlap import (hideable_byte_fraction,
                                                        plan_chunks)
        from tpu_compressed_dp.train.guard import schedule_step

        controller = Controller(ctrl_cfg, events=events)
        hide_frac = hideable_byte_fraction(plan_chunks(
            [leaf.size * 4 for leaf in jax.tree_util.tree_leaves(params)],
            comp))
        print(f"adaptive: method={ctrl_cfg.method} knob={controller.knob} "
              f"rungs={ctrl_cfg.rungs} window={ctrl_cfg.window} "
              f"signal={ctrl_cfg.signal} hideable_frac={hide_frac:.3f}")
    # per-(size, batch) forward FLOPs from the XLA cost model — progressive
    # resizing changes the shape per phase, so cache per shape.  Skipped
    # entirely when nothing can consume the result (no exporter, no known
    # chip peak): the cost-model pass compiles the bare forward per phase.
    fwd_cache: Dict[tuple, Optional[float]] = {}
    want_flops = (events is not None or bool(args.prom)
                  or flops_mod.chip_peak_flops() is not None)

    def fwd_flops_for_phase(phase) -> Optional[float]:
        if not want_flops:
            return None
        sz, per_chip = int(phase["sz"]), max(int(phase["bs"]) // ndev, 1)
        key = (sz, per_chip)
        if key not in fwd_cache:
            fwd_cache[key] = flops_mod.fwd_flops_xla(
                lambda p, s, x: apply_fn(p, s, x, True, {}),
                state.params, state.batch_stats,
                jnp.zeros((per_chip, sz, sz, 3), jnp.float32))
        return fwd_cache[key]

    prev_skipped = 0.0
    fabric_g: dict = {}  # previous epoch's net/ per-fabric gauges
    # finally-guarded: GuardExceeded / ChaosCrash / any failure must not
    # leak the heartbeat writer thread (an orphaned writer keeps the ts
    # fresh and defeats staleness detection), the checkpoint manager, a
    # running profiler trace, or an unterminated event stream
    try:
        if args.evaluate:
            # a finished run evaluates at its final phase's resolution
            pd.set_epoch(min(start_epoch, epochs - 1))
            stats_val = validate(state)
            print(f"top1 {stats_val['acc']*100:.2f} top5 {stats_val['acc5']*100:.2f}")
            return stats_val

        epoch = start_epoch
        while epoch < epochs:
            # a SIGTERM between epochs cuts the emergency save here rather
            # than after another full epoch of (doomed) work
            preempt.check(int(state.step))
            swapped = pd.set_epoch(epoch)
            if swapped and ckpt and epoch > 0:
                # phase-boundary save (`train_imagenet_nv.py:251-253`);
                # async — the new phase's jit warmup hides the write
                ckpt.save_async(state, {"epoch": epoch - 1,
                                        "phase_boundary": True})

            def train_batches():
                # after a remesh the loader's batch may stop dividing the
                # world; trim each batch to the largest divisible row count
                per = int(mesh.shape["data"]) // jax.process_count()
                for b in _truncate(pd.train_loader, 10 if args.short_epoch else None):
                    rows = (len(b["target"]) // per) * per
                    if rows == 0:
                        continue
                    yield make_global_batch({k: v[:rows] for k, v in b.items()},
                                            mesh)

            profiling = args.profile_epoch == epoch and args.logdir
            try:
                with profile_trace(
                        os.path.join(args.logdir, "profile") if profiling else None):
                    state, acc = run_train_epoch(train_step, state, train_batches(),
                                                 crash=crash,
                                                 step_offset=int(state.step),
                                                 guard_cfg=guard_cfg,
                                                 timeline=timeline,
                                                 elastic=el,
                                                 preempt=preempt,
                                                 flight=flight)
            except Exception as err:  # noqa: BLE001 - converted or re-raised
                failure = el.failure_from(err) if el is not None else None
                if failure is None:
                    if flight is not None and not isinstance(
                            err, resilience.Preempted):
                        # unconverted failure about to unwind the run: the
                        # dump here is the only evidence this rank leaves
                        flight.observe(err, step=int(state.step))
                    raise
                # coordinated abort: remesh from the last live TrainState
                # (donation consumed the pre-epoch buffers; run_train_epoch
                # rides its local out on the exception), migrate EF/comp
                # onto the surviving mesh, rebuild the jitted steps (the
                # sharded transport's owner partition is a function of W
                # and recomputes at trace time), re-run the epoch's rest
                state = getattr(err, "elastic_state", state)
                state = el.handle_failure(state, failure)
                mesh, ndev = el.mesh, el.world
                step_cache.clear()
                train_step = train_step_for(active_comp())
                eval_step = make_eval_step(apply_fn, mesh)
                fwd_cache.clear()
                continue
            if el is not None:
                # epoch-boundary readmission: fold any watchdog-relaunched
                # host parked in the rendezvous join barrier into a new
                # world epoch (no-op single-process / no joins pending)
                state, grew = el.rejoin_barrier(state)
                if grew:
                    mesh, ndev = el.mesh, el.world
                    step_cache.clear()
                    train_step = train_step_for(active_comp())
                    eval_step = make_eval_step(apply_fn, mesh)
                    fwd_cache.clear()
            if (stream is not None and args.stream_every > 0
                    and (epoch + 1) % args.stream_every == 0):
                # delta segment: codec on this thread, commit in the
                # background (stream/writer.py)
                stream.append_async(state.params, step=int(state.step))
            # spans drain ONCE per epoch and fan out to every consumer
            # (event stream, flight recorder's timing ring + phase profile)
            spans = timeline.drain()
            fgauges = flight_update(flight, spans=spans)
            if hb is not None:
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
                    # last finished epoch's per-fabric billing: lets a
                    # fleet poll see the DCN demand without scraping prom
                    **({"net": fabric_g} if fabric_g else {}),
                    **({"straggler_skew_s": fgauges["straggler/skew_s"],
                        "straggler_rank": fgauges["straggler/rank"]}
                       if "straggler/skew_s" in fgauges else {}),
                )
            train_time = timer()
            if controller is not None:
                # decision tick at the epoch cadence, keyed to APPLIED
                # updates; lands before this epoch's save_if_best and the
                # next phase-boundary save, so the checkpointed ControlState
                # carries the accumulation (bitwise crash/resume)
                applied = (schedule_step(guard_cfg, state.guard,
                                         int(state.step))
                           if guard_cfg is not None else int(state.step))
                wall_ms = train_time * 1e3 / max(acc.steps, 1)
                old_rung = int(state.control.rung)
                # on a 2-level topology the modeled signal prices only the
                # DCN-billed share — the fabric --adaptive_bw_mbps budgets
                from tpu_compressed_dp.control.signals import \
                    billed_signal_bits

                new_control, _ = controller.tick(
                    state.control, applied=applied,
                    signals=controller.window_signals(
                        mean_bits=billed_signal_bits(
                            {k: acc.mean(k) for k in acc.sums
                             if k.startswith("comm/")}, args.dp_pods),
                        measured_comm_ms=wall_ms,
                        compute_ms=wall_ms,
                        hideable_fraction=hide_frac))
                state = state.replace(control=new_control)
                new_rung = int(new_control.rung)
                if flight is not None:
                    flight.note_control({"epoch": epoch, "rung": new_rung,
                                         "applied": applied})
                if new_rung != old_rung:
                    if controller.knob == "rank":
                        # PowerSGD rank switch: re-seat warm q columns at
                        # the new rank before the next epoch traces
                        from tpu_compressed_dp.control import (
                            comp_for_rung, migrate_comp_state)
                        state = state.replace(comp=migrate_comp_state(
                            state.comp, state.params,
                            comp_for_rung(comp, ctrl_cfg, old_rung),
                            comp_for_rung(comp, ctrl_cfg, new_rung), ndev))
                    train_step = train_step_for(active_comp())
            val_stats = validate(state)
            timer()
            top1, top5 = val_stats["acc"] * 100, val_stats["acc5"] * 100
            hours = (time.time() - t0) / 3600
            # `~~epoch\thours\ttop1\ttop5` event line (`train_imagenet_nv.py:232,243`)
            flog.event(f"~~{epoch}\t{hours:.5f}\t\t{top1:.3f}\t\t{top5:.3f}\n")
            examples = int(acc.sums.get("count", 0.0))
            img_s = examples / train_time if train_time > 0 else 0.0
            thr = flops_mod.throughput_record(
                fwd_flops_for_phase(pd.cur),
                acc.steps / max(train_time, 1e-9), examples_per_sec=img_s)
            summary = {
                "epoch": epoch, "train time": train_time,
                "train loss": acc.mean("loss"),
                "test loss": val_stats["loss"], "top1": top1, "top5": top5,
                "test acc": val_stats["acc"],  # TSVLogger's top1 column
                "total time": timer.total_time,
                "img/s": round(img_s, 1),
            }
            if "throughput/mfu" in thr:
                summary["mfu"] = round(thr["throughput/mfu"], 4)
            summary.update(comm_summary(acc))
            summary.update(guard_summary(acc))
            summary.update(control_summary(controller, state.control))
            comm_means = {k: acc.mean(k) for k in acc.sums
                          if k.startswith("comm/")}
            guard_last = {k: v for k, v in acc.last.items()
                          if k.startswith("guard/")}
            control_stats = (controller.metrics(state.control)
                             if controller is not None else {})
            # analytic per-chip link traffic at the epoch's measured rate,
            # method-aware (VERDICT r2 #2): shared transport-split arithmetic
            # with bench/sweep.py and the other harnesses
            from tpu_compressed_dp.utils.meters import per_chip_comm_bytes

            per_chip_b = per_chip_comm_bytes(comm_means, ndev, args.dp_pods)
            if per_chip_b is not None and train_time > 0:
                summary["comm MB/s"] = per_chip_b * acc.steps / train_time / 1e6
            # per-fabric net/ gauges (empty on a flat mesh): what the DCN
            # specifically must sustain — the signal a cross-pod budget is
            # set against (tools/control_report.py --bw columns)
            fabric_g = fabric_gauges(comm_means, ndev, args.dp_pods,
                                     acc.steps, train_time)
            table.append(summary)
            tsv.append(summary)
            if events is not None:
                events.emit(
                    "epoch", epoch=epoch, step=int(state.step),
                    metrics={k: v for k, v in summary.items()
                             if isinstance(v, (int, float))},
                    throughput=thr, comm=comm_means, guard=guard_last,
                    control=control_stats,
                    timeline=timeline.snapshot(),
                    step_spans=spans)
                skipped = guard_last.get("guard/skipped", 0.0)
                if skipped > prev_skipped:
                    events.emit("guard", epoch=epoch, step=int(state.step),
                                **guard_last)
                prev_skipped = skipped
            if args.prom and is_master:
                write_prometheus(
                    {"loss": summary["train loss"], **thr, **comm_means,
                     **fabric_g,
                     **guard_last, **control_stats, **timeline.snapshot(),
                     **(ckpt.metrics() if ckpt is not None else {}),
                     **(stream.metrics() if stream is not None else {}),
                     **(el.metrics() if el is not None else {}),
                     **fgauges},
                    job_scoped(args, args.prom),
                    labels=prom_labels(args, harness="imagenet"))
            # tensorboard: x-axis = cumulative examples (`logger.py:24-34`);
            # namespaces mirror the reference (losses/ times/ net/)
            tb.update_examples_count(examples)
            tb.log_scalar("losses/train_loss", acc.mean("loss"))
            tb.log_scalar("losses/test_loss", val_stats["loss"])
            tb.log_scalar("losses/top1", top1)
            tb.log_scalar("losses/top5", top5)
            tb.log_scalar("times/epoch_seconds", train_time)
            if examples and train_time > 0:
                tb.log_scalar("times/images_per_sec", img_s)
            if "throughput/mfu" in thr:
                tb.log_scalar("times/mfu", thr["throughput/mfu"])
            if per_chip_b is not None and train_time > 0:
                tb.log_scalar("net/payload_mb_per_step",
                              acc.mean("comm/sent_bits") / 8 / 1e6)
                tb.log_scalar("net/allreduce_gbps_per_chip",
                              per_chip_b * acc.steps / 1e9 / train_time)
            for k, v in fabric_g.items():
                tb.log_scalar(k, v)
            recv_g, sent_g = net_meter.update_bandwidth()
            tb.log_scalar("net/recv_gbit_s", recv_g)
            tb.log_scalar("net/transmit_gbit_s", sent_g)
            if "guard/nonfinite" in acc.sums:
                tb.log_scalar("guard/skip_rate", acc.mean("guard/nonfinite"))
                tb.log_scalar("guard/loss_scale",
                              acc.last.get("guard/loss_scale", 1.0))
                tb.log_scalar("guard/skipped", acc.last.get("guard/skipped", 0.0))
            if ckpt:
                ckpt.save_if_best(state, top5, floor=args.best_floor,
                                  meta={"epoch": epoch, "top1": top1, "top5": top5})
            epoch += 1
        if args.logdir:
            tsv.save(args.logdir)
    except resilience.Preempted as err:
        # SIGTERM/SIGINT landed: cut the emergency checkpoint (draining any
        # in-flight async write first) and exit PREEMPT_EXIT so the watchdog
        # relaunches immediately instead of burning its backoff/budget
        state = getattr(err, "elastic_state", state)
        raise preempt_exit(err, ckpt=ckpt, state=state,
                           meta={"epoch": epoch - 1},
                           events=events, flight=flight) from None
    finally:
        preempt.uninstall()
        tb.close()
        if ckpt:
            ckpt.close()   # drains the background writer before events close
        if stream is not None:
            stream.close()  # drains the in-flight segment commit
        if events is not None:
            events.close()
        if hb is not None:
            hb.stop()
    return summary


def main(argv: Optional[list] = None):
    setup_compile_cache()
    return run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
