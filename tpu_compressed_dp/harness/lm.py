"""Llama pretrain harness — the BASELINE.json stretch config driver.

No reference equivalent exists (the reference trains CNNs only); the flag
surface follows the CNN harnesses where concepts coincide (compression
config, checkpointing, logging) and adds the mesh/model axes.  The headline
configuration is ``--preset llama3_8b --compress entiremodel --method topk``:
entire-model Top-K gradient compression over ICI, with tensor and sequence
parallelism inside the chip mesh.

Smoke run (CPU, 8 virtual devices):
  ``python -m tpu_compressed_dp.harness.lm --preset tiny --dp 2 --sp 2
  --tp 2 --steps 20 --seq_len 64 --global_batch 8``
"""

from __future__ import annotations

import argparse
import contextlib
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.data import lm as lm_data
from tpu_compressed_dp.models import hybrid, sambay, transformer as tf
from tpu_compressed_dp.parallel.dp import CompressionConfig
from tpu_compressed_dp.parallel.mesh import setup_compile_cache
from tpu_compressed_dp.train.lm_step import (
    init_lm_ef_state,
    init_lm_model_aux,
    make_lm_mesh,
    make_lm_train_step,
)
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.schedules import piecewise_linear
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.utils import resilience
from tpu_compressed_dp.utils.checkpoint import Checkpointer
from tpu_compressed_dp.utils.loggers import TableLogger

PRESETS = {
    "tiny": tf.tiny_llama,
    "llama3_8b": tf.llama3_8b,
    # the looped LM (four tied passes, sandwich norms, exit gate); its 48
    # layers want --layers cut to what the mesh holds
    "ouro_2p6b": tf.ouro_2p6b,
    # hybrid decoders (models/hybrid.py: Mamba-2, attention and LatentMoE
    # layers by a pattern, one MTP module): one pipeline stage's share of
    # Nemotron-3-Super, and the smoke size
    "nemotron3_super": hybrid.nemotron3_super_stage,
    "tiny_hybrid": hybrid.tiny_hybrid,
    # the same decoder's gated full / sliding-window attention and SwiGLU
    # expert layers: one pipeline stage's share of Laguna-XS.2, and the smoke
    # size
    "laguna_xs2": hybrid.laguna_xs2_stage,
    "tiny_laguna": hybrid.tiny_laguna,
    # a decoder-hybrid-decoder (models/sambay.py: Mamba-1, differential
    # attention, Gated Memory Units and cross-attention on earlier layers'
    # tensors): the hand-over stage of Phi-4-mini-flash-reasoning, and the
    # smoke size
    "phi4_mini_flash": sambay.phi4_mini_flash_stage,
    "tiny_phi4flash": sambay.tiny_phi4flash,
}

_PATTERNED = (hybrid.HybridConfig, sambay.SambaYConfig)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Llama pretrain, compressed-DP over (data, seq, tensor) mesh")
    p.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    p.add_argument("--vocab", type=int, default=None)
    p.add_argument("--dim", type=int, default=None)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--heads", type=int, default=None)
    p.add_argument("--kv_heads", type=int, default=None)
    p.add_argument("--ffn", type=int, default=None)
    p.add_argument("--experts", type=int, default=None,
                   help="MoE expert count (0/unset = dense FFN)")
    p.add_argument("--moe_every", type=int, default=None)
    p.add_argument("--capacity_factor", type=float, default=None)
    p.add_argument("--fp32", action="store_true", help="disable bf16 compute")
    p.add_argument("--remat", action="store_true",
                   help="rematerialise layers in backward (jax.checkpoint)")
    # mesh
    p.add_argument("--dp", type=int, default=None, help="data axis size (default: all devices)")
    p.add_argument("--sp", type=int, default=1, help="sequence axis size")
    p.add_argument("--tp", type=int, default=1, help="tensor axis size")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (the (data, seq, pipe, tensor) "
                        "step; composes with --sp and --tp)")
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (--pp > 1 only)")
    # data/schedule
    p.add_argument("--corpus", type=str, default=None, help="byte-level text file; default synthetic")
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--global_batch", type=int, default=32)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup_steps", type=int, default=10)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight_decay", type=float, default=0.0)
    p.add_argument("--clip_norm", type=float, default=0.0,
                   help="local-gradient L2 clip (0=off) — EF+momentum "
                        "stabiliser (see tools/ef_bisect.py)")
    p.add_argument("--clip_sent_norm", type=float, default=0.0,
                   help="post-aggregation L2 clip of the synced gradient "
                        "(bounds the EF residual spike)")
    # compression (same surface as the CNN harnesses)
    p.add_argument("--compress", "-c", default="none", choices=["none", "layerwise", "entiremodel", "bucketed"])
    p.add_argument("--method", default="none")
    p.add_argument("--ratio", "-K", type=float, default=0.01)
    p.add_argument("--threshold", "-V", type=float, default=0.001)
    p.add_argument("--qstates", "-Q", type=int, default=255)
    p.add_argument("--rank", type=int, default=4,
                   help="r for powersgd (psum-ring low-rank factors)")
    p.add_argument("--block_size", type=int, default=256,
                   help="blocktopk: elements per contiguous block")
    p.add_argument("--bucket_mb", type=float, default=25.0,
                   help="bucketed granularity: capacity per bucket")
    p.add_argument("--wire_cap_ratio", type=float, default=0.05,
                   help="wire thresholdv/adaptive_threshold transport "
                        "capacity (fraction of elements)")
    p.add_argument("--mode", default="simulate", choices=["simulate", "wire"])
    p.add_argument("--transport", default="allgather",
                   choices=["allgather", "sharded", "hierarchical"],
                   help="wire combine for index-carrying sparsifiers: flat "
                        "all_gather (O(W*k)/chip), owner-sharded reduce "
                        "(O(k + n/W)/chip, ops/wire_sharded.py; size caps "
                        "via comm/shard_overflow), or the two-level "
                        "hierarchical reduce over a --dp_pods x chips "
                        "virtual mesh (O(k + n/W_pods) DCN bytes)")
    p.add_argument("--error_feedback", action="store_true")
    p.add_argument("--overlap", type=int, default=1,
                   help="chunk-pipelined sync (parallel/overlap.py): up to "
                        "K reverse-topological chunk collectives per "
                        "replication signature, interleaved with backward "
                        "compute; numerics unchanged (1 = single dispatch)")
    # robustness: shared --guard*/--chaos/--heartbeat surface
    from tpu_compressed_dp.harness.loop import (add_adaptive_args,
                                                add_robustness_args,
                                                add_stream_args,
                                                add_telemetry_args,
                                                add_topology_args)

    add_topology_args(p)
    add_robustness_args(p, check_note="checked every --log_every")
    # delta state streaming: shared --stream* surface (stream/)
    add_stream_args(p, cadence_help="steps between delta-stream appends "
                                    "(requires --stream_dir; 0 disables "
                                    "the periodic append)")
    # adaptive compression: shared --adaptive* surface (control/); the LM
    # loop's decision cadence is the --log_every metric-fetch window
    add_adaptive_args(p)
    # telemetry: shared --events/--prom surface (obs/export.py)
    add_telemetry_args(p)
    p.add_argument("--logdir", type=str, default=None,
                   help="output dir for profiler traces")
    p.add_argument("--profile_epoch", type=int, default=None,
                   help="jax.profiler-trace the Nth --log_every window of "
                        "steps to <logdir>/profile (the LM loop's 'epoch' "
                        "is one log window)")
    # plumbing
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log_every", type=int, default=10)
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--ckpt_every", type=int, default=0,
                   help="steps between async checkpoint saves (requires "
                        "--checkpoint_dir; 0 = final/emergency saves only)")
    p.add_argument("--resume", type=str, default=None)
    p.add_argument("--coordinator", type=str, default=None)
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    return p


def build_config(args):
    import dataclasses

    cfg = PRESETS[args.preset]()
    if isinstance(cfg, _PATTERNED):
        given = [n for n in ("layers", "heads", "kv_heads", "ffn", "experts")
                 if getattr(args, n) is not None]
        if given:
            raise ValueError(f"--{given[0]} does not apply to a hybrid preset: "
                             "its layers and shares are the preset's")
        overrides = {"dim": args.dim, "vocab_size": args.vocab,
                     "vocab_held": args.vocab,
                     "dtype": jnp.float32 if args.fp32 else None}
        return dataclasses.replace(
            cfg, **{k: v for k, v in overrides.items() if v is not None})
    overrides = {}
    for field, arg in [("vocab_size", args.vocab), ("dim", args.dim),
                       ("n_layers", args.layers), ("n_heads", args.heads),
                       ("n_kv_heads", args.kv_heads), ("ffn_hidden", args.ffn),
                       ("n_experts", args.experts), ("moe_every", args.moe_every),
                       ("capacity_factor", args.capacity_factor)]:
        if arg is not None:
            overrides[field] = arg
    if args.fp32:
        overrides["dtype"] = jnp.float32
    if args.remat:
        overrides["remat"] = True
    return dataclasses.replace(cfg, **overrides)


def run(args) -> Dict[str, float]:
    if args.method.lower() != "none" and args.compress == "none":
        raise ValueError(f"--method {args.method} requires --compress layerwise|entiremodel")
    from tpu_compressed_dp.harness.loop import elastic_distributed_init

    rejoin = elastic_distributed_init(args)
    ndev = len(jax.devices())
    pipelined = args.pp > 1
    dp = args.dp if args.dp is not None else ndev // (args.sp * args.tp * args.pp)
    if pipelined:
        from tpu_compressed_dp.train.pp_step import make_pp_mesh

        mesh = make_pp_mesh(dp, args.pp, args.tp, args.sp)
    else:
        mesh = make_lm_mesh(dp, args.sp, args.tp)
    cfg = build_config(args)
    cfg.validate_mesh(args.tp)
    if isinstance(cfg, _PATTERNED) and (pipelined or args.sp > 1
                                                 or args.corpus):
        raise ValueError("a hybrid preset runs on the data axis, on synthetic "
                         "tokens: no --pp, --sp or --corpus")

    if args.global_batch % (dp * (args.microbatches if pipelined else 1)):
        raise ValueError(f"--global_batch {args.global_batch} must divide by "
                         f"dp*microbatches")
    if args.seq_len % args.sp:
        raise ValueError(f"--seq_len {args.seq_len} must divide by sp={args.sp}")

    if args.corpus:
        ds = lm_data.ByteCorpus(args.corpus, args.seq_len, args.global_batch,
                                seed=args.seed)
        if ds.vocab != cfg.vocab_size:
            import dataclasses

            cfg = dataclasses.replace(cfg, vocab_size=ds.vocab)
    else:
        ds = lm_data.SyntheticTokens(getattr(cfg, "vocab_held", cfg.vocab_size),
                                     args.seq_len,
                                     args.global_batch, seed=args.seed)

    params = cfg.init(jax.random.key(args.seed))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    sched = piecewise_linear(
        [0, max(args.warmup_steps, 1), max(args.steps, args.warmup_steps + 1)],
        [0.0, args.lr, args.lr * 0.1],
    )
    opt = SGD(lr=sched, momentum=args.momentum, weight_decay=args.weight_decay)
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
    from tpu_compressed_dp.harness.loop import build_control, build_robustness
    from tpu_compressed_dp.train.guard import init_guard_state

    guard_cfg, chaos, crash = build_robustness(args, cfg.dtype)
    ctrl_cfg = build_control(args, comp)
    if ctrl_cfg is not None and pipelined:
        raise ValueError(
            "--adaptive supports the (data, seq, tensor) step; the pipeline "
            "step's stacked-layer layout has no rung-switch path yet")
    if ctrl_cfg is not None:
        from tpu_compressed_dp.control.rungs import ladder_knob
        if ladder_knob(ctrl_cfg.method) == "rank":
            raise ValueError(
                "--adaptive rank retuning (powersgd) is CNN-harness-only "
                "for now: the LM comp-state layout has no cross-rank "
                "migration path (use a ratio method, or static --rank)")
    from tpu_compressed_dp.control import init_control_state

    step_cache: Dict = {}

    def active_comp() -> CompressionConfig:
        """The compression config the NEXT step should trace under: the
        controller's checkpointed rung when adaptive, the static config
        otherwise."""
        if ctrl_cfg is None:
            return comp
        from tpu_compressed_dp.control import comp_for_rung
        return comp_for_rung(comp, ctrl_cfg, int(state.control.rung))

    def lm_step_for(comp_cfg: CompressionConfig):
        # keyed by the tunable knobs (the rung ladder varies exactly these);
        # cleared wholesale on remesh — entries close over the current mesh
        key = (comp_cfg.ratio, comp_cfg.rank)
        if key not in step_cache:
            step_cache[key] = make_lm_train_step(
                cfg, opt, comp_cfg, mesh,
                clip_norm=args.clip_norm,
                clip_sent_norm=args.clip_sent_norm,
                guard_cfg=guard_cfg, chaos=chaos)
        return step_cache[key]
    if pipelined:
        # NB make_pp_train_step rejects method='powersgd' (stacked-layer
        # params shard over pipe; no warm-start init exists for that layout)
        from tpu_compressed_dp.train.pp_step import (
            init_pp_ef_state, make_pp_train_step, stack_layer_params,
        )

        params = stack_layer_params(params)
        state = TrainState.create(
            params, {}, opt.init(params),
            init_pp_ef_state(cfg, params, comp, mesh),
            jax.random.key(args.seed + 1),
            guard=init_guard_state(guard_cfg),
        )
        train_step = make_pp_train_step(cfg, opt, comp, mesh,
                                        microbatches=args.microbatches,
                                        clip_norm=args.clip_norm,
                                        clip_sent_norm=args.clip_sent_norm,
                                        guard_cfg=guard_cfg, chaos=chaos)
        ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
        if args.resume:
            restore = Checkpointer(args.resume)
            state, meta = restore.restore(state)
            restore.close()
            print(f"resumed step {int(state.step)}")
        from tpu_compressed_dp.train.pp_step import place_pp_state

        # fresh or restored, the state is built on one device: shard it per
        # the step's specs before the first call (see harness/dawn.py)
        if jax.process_count() == 1:
            state = place_pp_state(state, cfg, comp, mesh)
    else:
        from tpu_compressed_dp.train.lm_step import init_lm_comp_state

        state = TrainState.create(
            params, init_lm_model_aux(cfg), opt.init(params),
            init_lm_ef_state(cfg, params, comp, mesh),
            jax.random.key(args.seed + 1),
            comp=init_lm_comp_state(cfg, params, comp, mesh),
            guard=init_guard_state(guard_cfg),
            control=init_control_state(ctrl_cfg),
        )
        ckpt = Checkpointer(args.checkpoint_dir) if args.checkpoint_dir else None
        if args.resume:
            restore = Checkpointer(args.resume)
            state, meta = restore.restore(state)
            restore.close()
            print(f"resumed step {int(state.step)}")
        from tpu_compressed_dp.train.lm_step import place_lm_state

        # fresh or restored, the state is built on one device: shard it per
        # the step's specs before the first call (see harness/dawn.py)
        if jax.process_count() == 1:
            state = place_lm_state(state, cfg, comp, mesh)

        train_step = lm_step_for(active_comp())
    mesh_str = (f"dp{dp}xsp{args.sp}xpp{args.pp}xtp{args.tp}(mb{args.microbatches})" if pipelined
                else f"dp{dp}xsp{args.sp}xtp{args.tp}")
    print(f"params={n_params/1e6:.1f}M mesh={mesh_str} "
          f"seq={args.seq_len} batch={args.global_batch} "
          f"method={comp.method or 'dense'}/{comp.granularity}/{comp.mode}")

    table = TableLogger()
    from tpu_compressed_dp.utils.meters import GuardMeter, per_chip_comm_bytes

    guard_meter = GuardMeter()
    from tpu_compressed_dp.harness.loop import (flight_update, job_scoped,
                                                make_event_stream,
                                                make_flight_recorder,
                                                make_heartbeat,
                                                make_preemption, make_stream,
                                                preempt_exit, profile_trace,
                                                prom_labels,
                                                stream_rejoin_params)
    from tpu_compressed_dp.obs.export import (telemetry_snapshot,
                                              write_prometheus)
    from tpu_compressed_dp.obs.trace import StepTimeline

    hb = make_heartbeat(args)
    timeline = StepTimeline()
    events = make_event_stream(
        args, harness="lm", preset=args.preset, mesh=mesh_str,
        method=comp.method or "none", compress=args.compress, mode=args.mode,
        transport=args.transport, seq_len=args.seq_len,
        global_batch=args.global_batch, steps=args.steps)
    flight = make_flight_recorder(
        args, harness="lm", preset=args.preset, mesh=mesh_str,
        method=comp.method or "none")
    if flight is not None and chaos is not None:
        flight.note_chaos(chaos)
    if flight is not None and crash is not None:
        crash.flight = flight
    if ckpt is not None:
        ckpt.events = events   # save/rollback records on the run's stream
        ckpt.flight = flight
    stream = make_stream(args, flight=flight, events=events)
    if ckpt is not None and stream is not None:
        # tee: a committed full checkpoint re-anchors the delta window
        ckpt.stream = stream
    preempt = make_preemption()
    if getattr(args, "elastic", False) and pipelined:
        # dp x sp and dp x tp remesh by deleting the dead DATA row (the
        # model shards are replicated across data rows); a pipeline stage
        # has no replica to recover from, so pp stays a checkpoint restart
        raise ValueError(
            "--elastic supports dp/dp x sp/dp x tp meshes; losing a worker "
            "of a pp mesh orphans a pipeline stage (that is a checkpoint "
            "restart, not a remesh)")
    from tpu_compressed_dp.harness.loop import build_elastic
    from tpu_compressed_dp.train.lm_step import place_lm_state

    el = build_elastic(args, mesh, chaos=chaos, crash=crash, events=events,
                       place=lambda s, m: place_lm_state(s, cfg, comp, m),
                       flight=flight, ef_axes=("data", "seq"), stream=stream)
    if el is not None and rejoin is not None:
        # watchdog-relaunched host: adopt the running world's replicated
        # state from the re-elected coordinator's broadcast (EF rows start
        # at zero) and retrace the step on the post-join mesh; a warm
        # joiner replays the delta stream instead of shipping params
        adopted_params, adopted_info = stream_rejoin_params(
            args, state, rejoin, flight=flight)
        state = el.join_world(state, rejoin, adopted_params=adopted_params,
                              adopted_info=adopted_info)
        mesh = el.mesh
        dp = el.world
        step_cache.clear()
        train_step = lm_step_for(active_comp())
    controller = None
    hide_frac = 1.0
    if ctrl_cfg is not None:
        from tpu_compressed_dp.control import Controller
        from tpu_compressed_dp.parallel.overlap import (hideable_byte_fraction,
                                                        plan_chunks)
        from tpu_compressed_dp.train.guard import schedule_step

        controller = Controller(ctrl_cfg, events=events)
        hide_frac = hideable_byte_fraction(plan_chunks(
            [leaf.size * 4 for leaf in jax.tree.leaves(params)], comp))
        print(f"adaptive: method={ctrl_cfg.method} knob={controller.knob} "
              f"rungs={ctrl_cfg.rungs} window={ctrl_cfg.window} "
              f"signal={ctrl_cfg.signal} hideable_frac={hide_frac:.3f}")
    # --profile_epoch: trace the Nth log window.  ExitStack (not a `with`)
    # because the window opens and closes mid-loop; the outer finally
    # guarantees the stop even when the loop raises inside the window —
    # the same leak-proofing profile_trace gives the CNN harnesses.
    prof = contextlib.ExitStack()
    prof_window = None
    if args.profile_epoch is not None and args.logdir:
        w0 = args.profile_epoch * args.log_every
        prof_window = (w0, w0 + args.log_every)
    t0 = time.time()
    tokens_done = 0.0
    summary: Dict[str, float] = {}
    start = int(state.step)
    timed_from = start
    world = dp * args.sp  # gradient-sync workers (transport arithmetic)
    prev_skipped = 0.0
    # finally-guarded: GuardExceeded / ChaosCrash must not leak the
    # heartbeat writer thread, the checkpoint manager, a running profiler
    # trace, or an unterminated event stream; the final save stays on the
    # clean path only
    try:
        rows = args.global_batch        # post-remesh: largest dp-divisible cut
        warm_until = start + 1          # compile-reset horizon (moves on remesh)
        step_i = start
        timeline.begin_call()           # the whole loop is one call
        while step_i < args.steps:
            try:
                if prof_window is not None and step_i == prof_window[0]:
                    prof.enter_context(
                        profile_trace(os.path.join(args.logdir, "profile")))
                if crash is not None:
                    crash.check(step_i)
                # after crash.check: crash=preempt self-SIGTERMs there, and
                # the flag must be observed within the same iteration
                preempt.check(step_i)
                if el is not None:
                    el.poll(step_i)
                with timeline.span("data_wait"):
                    batch = ds.batch(step_i)
                    if rows != args.global_batch:
                        batch = {k: v[:rows] for k, v in batch.items()}
                with timeline.span("to_device"):
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with timeline.span("dispatch"):
                    state, metrics = train_step(state, batch)
                timeline.step_done(metrics)
                if crash is not None:
                    # the mid-collective plane: the step's collectives are
                    # already in flight when this one fires
                    crash.check(step_i, phase="mid_collective")
                if prof_window is not None and step_i + 1 == prof_window[1]:
                    prof.close()
                if step_i <= warm_until:
                    # steady-state tokens/sec: the jitted step compiles TWICE (the
                    # donated-buffer layouts change the arg signature on call 2), so
                    # barrier-and-reset after each of the first two steps — one
                    # excluded step would leak the second compile (18s+ at 125M
                    # params) into the timed window
                    jax.device_get(metrics)
                    t0 = time.time()
                    timed_from = step_i + 1
                    timeline.resume()  # the compile drain is not data wait
                if (step_i + 1) % args.log_every == 0 or step_i == args.steps - 1:
                    with timeline.span("fetch"):
                        m = (el.bounded_get(metrics, step=step_i + 1)
                             if el is not None else jax.device_get(metrics))
                    # the fetch drained the device: the watcher is at most
                    # a few stamps behind, and the window's spans go out whole
                    timeline.flush()
                    # spans drain ONCE per window and fan out to every
                    # consumer; the flight rings fill BEFORE the wedge check
                    # so a GuardExceeded dump carries the streak history
                    spans = timeline.drain()
                    fgauges = flight_update(flight, step=step_i + 1,
                                            metrics=m, spans=spans)
                    if guard_cfg is not None:
                        # wedge check at log cadence (detection latency = log_every)
                        from tpu_compressed_dp.train.guard import check_guard_metrics

                        guard_meter.update(m, step_i + 1)
                        check_guard_metrics(m, guard_cfg, flight=flight)
                    if hb is not None:
                        hb.update(
                            step=step_i + 1,
                            last_good_step=(int(m["guard/last_good_step"])
                                            if guard_cfg is not None else step_i + 1),
                            telemetry=telemetry_snapshot(timeline),
                            **(ckpt.heartbeat_fields() if ckpt is not None
                               else {}),
                            **(stream.heartbeat_fields() if stream is not None
                               else {}),
                            **({"elastic": el.metrics()} if el is not None else {}),
                            **(controller.heartbeat_fields(state.control)
                               if controller is not None else {}),
                            **({"straggler_skew_s": fgauges["straggler/skew_s"],
                                "straggler_rank": fgauges["straggler/rank"]}
                               if "straggler/skew_s" in fgauges else {}),
                        )
                    steps_timed = step_i + 1 - timed_from
                    tokens_done = steps_timed * rows * args.seq_len
                    dt = time.time() - t0
                    summary = {
                        "step": step_i + 1,
                        "loss": float(m["loss"]),
                        "lr": float(m["lr"]),
                        # 0.0 until at least one post-compile step is in the window
                        "tok/s": round(tokens_done / dt, 1) if steps_timed > 0 else 0.0,
                    }
                    summary.update({k: float(m[k]) for k in sorted(m)
                                    if k.startswith(("loss/", "model/"))})
                    thr: Dict[str, float] = {}
                    if steps_timed > 0:
                        # MFU (VERDICT r2 #3): closed-form 6N + 12Lds per token
                        # (utils/flops.py), per chip, vs the chip's bf16 peak —
                        # per-chip fwd flops feed the shared throughput_record
                        # epilogue the CNN harnesses use
                        from tpu_compressed_dp.utils import flops as flops_mod

                        # a looped model runs its parameters n_passes times;
                        # of a hybrid's layers only the attention ones pay
                        # the 12 L d s term
                        passes = getattr(cfg, "n_passes", 1)
                        attn_layers = (cfg.n_layers if hasattr(cfg, "n_layers")
                                       else (cfg.pattern + getattr(cfg, "mtp_pattern", "")).count("*"))
                        tok_flops = flops_mod.transformer_train_flops_per_token(
                            n_params * passes, attn_layers * passes, cfg.dim,
                            args.seq_len)
                        n_chips = max(int(mesh.devices.size), 1)
                        tok_s = tokens_done / dt
                        fwd_per_chip = (tok_flops / 3.0) * (
                            rows * args.seq_len) / n_chips
                        thr = flops_mod.throughput_record(
                            fwd_per_chip, steps_timed / dt, tokens_per_sec=tok_s)
                        if "throughput/mfu" in thr:
                            summary["mfu"] = round(thr["throughput/mfu"], 4)
                    comm_m = {k: float(v) for k, v in m.items()
                              if k.startswith("comm/")}
                    if "comm/sent_elems" in m:
                        summary["sent frac"] = float(m["comm/sent_elems"]) / max(
                            float(m["comm/dense_elems"]), 1.0)
                        summary["wire frac"] = float(m["comm/sent_bits"]) / (
                            32.0 * max(float(m["comm/dense_elems"]), 1.0))
                        per_chip_b = per_chip_comm_bytes(comm_m, world,
                                                         args.dp_pods)
                        if per_chip_b is not None and steps_timed > 0:
                            summary["comm MB/s"] = round(
                                per_chip_b * (steps_timed / dt) / 1e6, 3)
                    guard_last = {k: float(v) for k, v in m.items()
                                  if k.startswith("guard/")}
                    if guard_cfg is not None:
                        gsum = guard_meter.summary()
                        summary["skipped"] = gsum.get("guard/skipped", 0.0)
                        summary["loss_scale"] = gsum.get("guard/loss_scale", 1.0)
                    control_stats: Dict[str, float] = {}
                    if controller is not None:
                        # decision tick at the log-window cadence, keyed to
                        # APPLIED updates; ticks before the checkpoint-save
                        # site below so the saved ControlState carries this
                        # window's accumulation (bitwise crash/resume)
                        applied = (schedule_step(guard_cfg, state.guard,
                                                 int(state.step))
                                   if guard_cfg is not None
                                   else int(state.step))
                        wall_ms = (dt * 1e3 / steps_timed
                                   if steps_timed > 0 else None)
                        if wall_ms is not None or (
                                ctrl_cfg.signal == "modeled"
                                and ctrl_cfg.budget_ms > 0):
                            old_rung = int(state.control.rung)
                            new_control, _ = controller.tick(
                                state.control, applied=applied,
                                signals=controller.window_signals(
                                    mean_bits=float(
                                        m.get("comm/sent_bits", 0.0)),
                                    measured_comm_ms=wall_ms,
                                    compute_ms=wall_ms,
                                    hideable_fraction=hide_frac))
                            state = state.replace(control=new_control)
                            if flight is not None:
                                flight.note_control(
                                    {"step": step_i + 1,
                                     "rung": int(new_control.rung),
                                     "applied": applied})
                            if int(new_control.rung) != old_rung:
                                # trace-cached rung switch: takes effect at
                                # the next step dispatch
                                train_step = lm_step_for(active_comp())
                        control_stats = controller.metrics(state.control)
                        summary["rung"] = control_stats["control/rung"]
                        summary[controller.knob] = control_stats["control/value"]
                    if events is not None:
                        events.emit(
                            "step", step=step_i + 1,
                            metrics={k: v for k, v in summary.items()
                                     if isinstance(v, (int, float))},
                            throughput=thr, comm=comm_m, guard=guard_last,
                            control=control_stats,
                            timeline=timeline.snapshot(),
                            step_spans=spans)
                        # delta-gate on the cumulative counter: one guard event
                        # per window that actually skipped, not one per window
                        # forever after the first skip
                        skipped_now = guard_last.get("guard/skipped", 0.0)
                        if skipped_now > prev_skipped:
                            events.emit("guard", step=step_i + 1, **guard_last)
                        prev_skipped = skipped_now
                    if args.prom and jax.process_index() == 0:
                        write_prometheus(
                            {"loss": summary["loss"], "lr": summary["lr"],
                             **thr, **comm_m, **guard_last, **control_stats,
                             **timeline.snapshot(),
                             **(ckpt.metrics() if ckpt is not None else {}),
                             **(stream.metrics() if stream is not None else {}),
                             **(el.metrics() if el is not None else {}),
                             **fgauges},
                            job_scoped(args, args.prom),
                            labels=prom_labels(args, harness="lm"))
                    table.append(summary)
                    # the log window's device_get drain + export work is not the
                    # next step's input-pipeline wait
                    timeline.resume()
                if el is not None and (step_i + 1) % args.log_every == 0:
                    # log-cadence readmission: fold any watchdog-relaunched
                    # host parked in the rendezvous join barrier into a new
                    # world epoch (no-op single-process / no joins pending)
                    state, grew = el.rejoin_barrier(state)
                    if grew:
                        mesh = el.mesh
                        dp = el.world
                        world = dp * args.sp
                        rows = (args.global_batch // dp) * dp
                        step_cache.clear()
                        train_step = lm_step_for(active_comp())
                        warm_until = step_i + 2  # compile pair on the new mesh
                        t0 = time.time()
                        timed_from = step_i + 1
                        timeline.resume()
            except Exception as err:  # noqa: BLE001 - converted or re-raised
                timeline.step_failed()
                failure = el.failure_from(err) if el is not None else None
                if failure is None:
                    if flight is not None and not isinstance(
                            err, resilience.Preempted):
                        # unconverted failure about to unwind the run: the
                        # dump here is the only evidence this rank leaves
                        flight.observe(err, step=step_i)
                    raise
                # coordinated abort + remesh.  Granularity is one step: a
                # pre-dispatch detection (gossip poll) retries the same
                # index untouched; a post-dispatch kill drains the in-flight
                # step during migration (single-process simulation — the
                # collectives do complete) and the index re-runs on the W-1
                # mesh.  Real multi-host discards in-flight work by process
                # exit instead.
                state = el.handle_failure(state, failure)
                mesh = el.mesh
                dp = el.world
                world = dp * args.sp
                rows = (args.global_batch // dp) * dp
                step_cache.clear()
                train_step = lm_step_for(active_comp())
                warm_until = step_i + 1     # fresh compile pair on the new mesh
                t0 = time.time()
                timed_from = step_i
                timeline.resume()
                continue
            if (ckpt is not None and args.ckpt_every
                    and (step_i + 1) % args.ckpt_every == 0):
                # async: snapshot to host, hand the Orbax write to the
                # background thread, keep stepping
                ckpt.save_async(state, {"step": step_i + 1})
            if (stream is not None and args.stream_every > 0
                    and (step_i + 1) % args.stream_every == 0):
                # delta stream: Top-K of (params - last streamed) on the
                # compressed wire codec; codec runs on this thread (window
                # accounting is ordered), the npz write goes to background
                stream.append_async(state.params, step=int(state.step))
            step_i += 1
        if ckpt:
            ckpt.save(state, {"step": int(state.step)})
    except resilience.Preempted as err:
        # SIGTERM/SIGINT landed: cut the emergency checkpoint (draining any
        # in-flight async write first) and exit PREEMPT_EXIT so the watchdog
        # relaunches immediately instead of burning its backoff/budget
        state = getattr(err, "elastic_state", state)
        raise preempt_exit(err, ckpt=ckpt, state=state,
                           meta={"step": int(state.step)},
                           events=events, flight=flight) from None
    finally:
        preempt.uninstall()
        prof.close()
        if stream is not None:
            stream.close()   # drain the in-flight delta append
        if ckpt:
            ckpt.close()   # drains the background writer before events close
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
