"""Epoch-level training loop shared by the CIFAR and ImageNet harnesses.

The framework equivalent of ``run_batches`` / ``train_epoch`` / ``train``
(`CIFAR10/core.py:303-341`): the per-batch body is entirely inside the jitted
train step, so the host loop only feeds batches and accumulates the already
globally-reduced metrics.
"""

from __future__ import annotations

import contextlib
import itertools
import os
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_compressed_dp.obs.trace import process_timeline
from tpu_compressed_dp.train.state import TrainState
from tpu_compressed_dp.utils.loggers import MetricAccumulator
from tpu_compressed_dp.utils.timer import Timer

__all__ = ["pad_batch", "run_train_epoch", "run_eval", "train_epoch",
           "comm_summary", "guard_summary", "control_summary",
           "fabric_gauges",
           "add_robustness_args", "add_adaptive_args", "add_topology_args",
           "add_telemetry_args", "job_scoped", "prom_labels",
           "add_checkpoint_args", "add_stream_args", "build_robustness",
           "build_control", "build_elastic", "elastic_distributed_init",
           "make_heartbeat", "make_event_stream", "make_flight_recorder",
           "make_stream", "stream_join_seq", "stream_rejoin_params",
           "flight_update", "make_preemption",
           "preempt_exit", "profile_trace"]


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str]):
    """``jax.profiler`` trace capture with a guaranteed stop.

    The harnesses used to copy-paste ``start_trace``/``stop_trace`` around
    the profiled epoch with no try/finally — an exception mid-epoch (e.g.
    ``GuardExceeded``) leaked a running trace, which keeps buffering
    profiler events for the rest of the process AND makes the next
    ``start_trace`` raise.  One context manager, used by all three
    harnesses; no-op (yields False) when ``trace_dir`` is falsy."""
    if not trace_dir:
        yield False
        return
    jax.profiler.start_trace(trace_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()


def add_telemetry_args(p) -> None:
    """The shared ``--events`` / ``--prom`` / ``--job_id`` CLI surface
    (obs/export.py)."""
    p.add_argument("--events", type=str, default=None,
                   help="JSONL telemetry event stream path (schema-versioned;"
                        " one record per step/epoch/guard event — feed to "
                        "tools/trace_report.py)")
    p.add_argument("--prom", type=str, default=None,
                   help="Prometheus textfile path, rewritten atomically at "
                        "each epoch/log window with the latest metrics")
    p.add_argument("--job_id", type=str,
                   default=os.environ.get("TCDP_JOB_ID") or None,
                   help="fleet job id (default: $TCDP_JOB_ID, exported by "
                        "tools/fleet.py): prefixes the --events/--prom/"
                        "--heartbeat file names (obs.export.job_scoped_path) "
                        "and labels the Prometheus exposition job=\"<id>\", "
                        "so jobs sharing one collector dir never clobber "
                        "each other")
    p.add_argument("--events_max_mb", type=float, default=0.0,
                   help="rotate the --events JSONL when the live file "
                        "would cross this many MB (atomic rename to "
                        "<path>.<seg>; records carry their segment index; "
                        "0 = unbounded)")
    p.add_argument("--flight_dir", type=str, default=None,
                   help="shared dir for the per-rank flight recorder "
                        "(obs/flight.py): ring-buffered telemetry, "
                        "blackbox.rank<R>.json dumps on failure paths, "
                        "live straggler/* gauges — feed the dir to "
                        "tools/postmortem.py after a crash")
    p.add_argument("--flight_capacity", type=int, default=256,
                   help="flight-recorder ring capacity per channel "
                        "(memory is O(channels x capacity))")


def add_topology_args(p) -> None:
    """The shared ``--dp_pods`` / ``--hier_route_factor_*`` CLI surface for
    ``--transport hierarchical`` (the dp_pods x dp_chips virtual mesh of
    parallel/dp.py)."""
    p.add_argument("--dp_pods", type=int, default=1,
                   help="hierarchical transport: pod count P of the "
                        "dp_pods x dp_chips virtual mesh (must divide the "
                        "data axis; 1 = flat).  Also splits the billed "
                        "comm arithmetic per fabric (net/dcn_* gauges)")
    p.add_argument("--hier_route_factor_ici", type=float, default=1.25,
                   help="hierarchical transport: intra-pod union capacity "
                        "in units of k (clips fold into EF)")
    p.add_argument("--hier_route_factor_dcn", type=float, default=1.25,
                   help="hierarchical transport: inter-pod bucket capacity "
                        "in units of slab/P (clips fold into EF)")


def fabric_gauges(comm_means: Dict[str, float], world: int, pods: int,
                  steps: int, seconds: float) -> Dict[str, float]:
    """Per-fabric ``net/`` gauges (obs/registry.py) from an epoch's mean
    ``comm/*`` metrics: DCN MB per step per chip, and the per-chip Gb/s
    each fabric must sustain at the measured step rate.  Empty on a flat
    mesh (``pods <= 1``) or when comm metrics are absent — the DCN split
    only means something on a 2-level topology."""
    from tpu_compressed_dp.utils.meters import per_fabric_comm_bytes

    if pods <= 1:
        return {}
    fabric = per_fabric_comm_bytes(comm_means, world, pods)
    if fabric is None:
        return {}
    ici_b, dcn_b = fabric
    out = {"net/dcn_mb_per_step": dcn_b / 1e6}
    if seconds > 0 and steps > 0:
        rate = steps / seconds
        out["net/dcn_gbps_per_chip"] = dcn_b * rate * 8 / 1e9
        out["net/ici_gbps_per_chip"] = ici_b * rate * 8 / 1e9
    return out


def job_scoped(args, path):
    """Apply the ``--job_id`` namespace to one telemetry path (no-op for
    single-job runs)."""
    from tpu_compressed_dp.obs.export import job_scoped_path

    return job_scoped_path(path, getattr(args, "job_id", None))


def prom_labels(args, **labels) -> Dict[str, str]:
    """The harness's Prometheus label set: the caller's labels plus
    ``job="<id>"`` under a fleet job id."""
    job = getattr(args, "job_id", None)
    if job:
        labels["job"] = job
    return labels


def make_event_stream(args, **meta):
    """The harnesses' ``--events`` setup: a started
    :class:`~tpu_compressed_dp.obs.export.EventStream` on the master rank
    (metrics are globally reduced, every rank would write identical
    records), or None.  The path and metadata are job-scoped under
    ``--job_id``."""
    if not getattr(args, "events", None) or jax.process_index() != 0:
        return None
    from tpu_compressed_dp.obs.export import EventStream

    if getattr(args, "job_id", None):
        meta = dict(meta, job=args.job_id)
    max_mb = getattr(args, "events_max_mb", 0.0) or 0.0
    return EventStream(job_scoped(args, args.events), meta=dict(meta),
                       max_bytes=int(max_mb * 1e6) if max_mb > 0 else None)


def make_flight_recorder(args, **meta):
    """The harnesses' ``--flight_dir`` setup: a per-rank
    :class:`~tpu_compressed_dp.obs.flight.FlightRecorder` (or None).  EVERY
    rank gets one — unlike the event stream, the whole point is per-rank
    evidence — writing bundles/profiles into the job-scoped shared dir."""
    if not getattr(args, "flight_dir", None):
        return None
    from tpu_compressed_dp.obs.flight import FlightRecorder

    directory = getattr(args, "flight_dir")
    if getattr(args, "job_id", None):
        directory = os.path.join(directory, args.job_id)
        meta = dict(meta, job=args.job_id)
    return FlightRecorder(rank=jax.process_index(),
                          capacity=getattr(args, "flight_capacity", 256),
                          directory=directory, meta=dict(meta))


def flight_update(flight, *, step=None, metrics=None, spans=None):
    """Per-epoch/window flight upkeep: feed the drained timeline spans and
    the window's fetched metrics into the rings, publish this rank's phase
    profile, and return the gauges (``flight/*`` counters + the live
    cross-rank ``straggler/*``) for the heartbeat/Prometheus payloads.
    ``{}`` when the recorder is off — callers can merge unconditionally."""
    if flight is None:
        return {}
    if spans:
        flight.note_spans(spans)
    if step is not None:
        flight.note_step(step, metrics or {})
    gauges = dict(flight.metrics())
    gauges.update(flight.publish())
    return gauges


def add_robustness_args(p, *, check_note: str) -> None:
    """The shared ``--guard*`` / ``--chaos`` / ``--heartbeat`` CLI surface
    (one definition for all three harnesses; ``check_note`` names the
    harness's wedge-check cadence in the --guard_max_skips help)."""
    p.add_argument("--guard", action="store_true",
                   help="arm the in-graph step guard: cross-worker "
                        "finiteness vote skips nonfinite steps, holds "
                        "params/ef/comp bitwise, dynamic loss scaling on "
                        "16-bit dtypes (train/guard.py)")
    p.add_argument("--guard_init_scale", type=float, default=2.0 ** 15)
    p.add_argument("--guard_backoff", type=float, default=0.5)
    p.add_argument("--guard_growth_interval", type=int, default=200)
    p.add_argument("--guard_max_skips", type=int, default=25,
                   help="raise GuardExceeded past this many CONSECUTIVE "
                        f"skipped steps ({check_note})")
    p.add_argument("--chaos", type=str, default=None,
                   help="deterministic fault injection, e.g. "
                        "'nan,target=grads,steps=3+7,worker=1' or "
                        "'crash=120' (utils/chaos.py; in-graph injection "
                        "auto-arms --guard)")
    p.add_argument("--heartbeat", type=str, default=None,
                   help="liveness JSON path (utils/resilience.Heartbeat); "
                        "payload carries step + last_good_step")
    p.add_argument("--heartbeat_interval", type=float, default=10.0)
    p.add_argument("--elastic", action="store_true",
                   help="survive peer death without a full-job restart: "
                        "detect (heartbeat gossip + bounded fetches), "
                        "remesh to W-1 with EF/PowerSGD migration, retry "
                        "(train/elastic.py)")
    p.add_argument("--elastic_dir", type=str, default=None,
                   help="shared per-rank heartbeat gossip directory "
                        "(omit = no gossip plane; chaos/fetch detection "
                        "still active)")
    p.add_argument("--peer_timeout", type=float, default=60.0,
                   help="seconds without a fresh peer heartbeat (or a "
                        "blocked metrics fetch) before declaring the peer "
                        "dead; --chaos peer_timeout=<s> overrides")
    p.add_argument("--elastic_ef", type=str, default="fold",
                   choices=("fold", "drop"),
                   help="departing worker's EF residual: fold into a "
                        "survivor (mass-conserving) or drop and count it "
                        "in elastic/dropped_ef_norm")
    p.add_argument("--elastic_min_world", type=int, default=2,
                   help="refuse to remesh below this many workers")


def add_adaptive_args(p) -> None:
    """The shared ``--adaptive*`` CLI surface: the closed-loop compression
    controller (tpu_compressed_dp/control/).  Decision cadence is the
    harness's metric-fetch window (epoch for CIFAR/ImageNet, log window for
    the LM harness) — the controller's own ``--adaptive_window`` counts
    APPLIED updates inside those fetches."""
    p.add_argument("--adaptive", action="store_true",
                   help="arm the closed-loop compression controller: retune "
                        "the compression knob (Top-K/Random-K ratio, "
                        "PowerSGD rank) along a precompiled rung ladder to "
                        "fit comm under the hideable-compute budget "
                        "(control/controller.py)")
    p.add_argument("--adaptive_window", type=int, default=8,
                   help="applied updates per control decision window")
    p.add_argument("--adaptive_deadband", type=float, default=0.25,
                   help="relative comm/budget deadband before a rung move")
    p.add_argument("--adaptive_rungs", type=str, default=None,
                   help="comma-separated explicit rung ladder (strictly "
                        "descending knob values; rung 0 is the static "
                        "baseline).  Default: halve the configured "
                        "ratio/rank per rung, 5 rungs deep")
    p.add_argument("--adaptive_budget_ms", type=float, default=0.0,
                   help="explicit per-update hideable-comm budget in ms; "
                        "0 = derive from measured compute x the overlap "
                        "schedule's hideable byte fraction")
    p.add_argument("--adaptive_bw_mbps", type=float, default=100.0,
                   help="modeled interconnect bandwidth (Mbit/s) used to "
                        "turn analytic sent-bits into comm ms under "
                        "--adaptive_signal modeled")
    p.add_argument("--adaptive_signal", type=str, default="modeled",
                   choices=("modeled", "measured"),
                   help="'modeled' prices comm from analytic sent-bits / "
                        "--adaptive_bw_mbps (bitwise replay-deterministic); "
                        "'measured' uses harness-observed wall times "
                        "(NOT replay-deterministic)")


def build_control(args, comp_cfg):
    """Resolve the ``--adaptive*`` CLI surface into a
    :class:`~tpu_compressed_dp.control.ControlConfig` (or None).

    Raises on a non-tunable compression method — silently running static
    under an --adaptive flag would invalidate any adaptive-vs-static
    comparison the run was launched for."""
    if not getattr(args, "adaptive", False):
        return None
    from tpu_compressed_dp.control import ControlConfig, build_ladder
    from tpu_compressed_dp.control.config import TUNABLE_METHODS
    from tpu_compressed_dp.control.rungs import ladder_knob
    from tpu_compressed_dp.ops.compressors import canonical_name

    method = (canonical_name(comp_cfg.method)
              if comp_cfg is not None and comp_cfg.method else None)
    if method not in TUNABLE_METHODS:
        raise SystemExit(
            f"--adaptive requires a tunable compression method "
            f"{TUNABLE_METHODS}, got {method!r}")
    if args.adaptive_rungs:
        knob = ladder_knob(method)
        cast = float if knob == "ratio" else int
        rungs = tuple(cast(v) for v in args.adaptive_rungs.split(","))
    else:
        rungs = build_ladder(method, comp_cfg.ratio, comp_cfg.rank)
    return ControlConfig(
        method=method, rungs=rungs,
        window=args.adaptive_window, deadband=args.adaptive_deadband,
        signal=args.adaptive_signal,
        bandwidth_mbps=args.adaptive_bw_mbps,
        budget_ms=args.adaptive_budget_ms)


def control_summary(controller, control) -> Dict[str, float]:
    """Epoch adaptive-control accounting for the harness summary line:
    the live rung index and knob value.  Empty when the controller is off."""
    if controller is None or control == ():
        return {}
    m = controller.metrics(control)
    return {"rung": m["control/rung"], controller.knob: m["control/value"]}


def make_heartbeat(args):
    """The harnesses' ``--heartbeat`` setup: a started Heartbeat, or None.
    The path is job-scoped under ``--job_id`` (two pool-sharing jobs must
    not clobber one liveness file) and the payload names the job so a
    fleet poll can attribute the verdict."""
    if not args.heartbeat:
        return None
    from tpu_compressed_dp.utils.resilience import Heartbeat

    payload = {"rank": jax.process_index()}
    if getattr(args, "job_id", None):
        payload["job"] = args.job_id
    return Heartbeat(job_scoped(args, args.heartbeat),
                     interval_s=args.heartbeat_interval, payload=payload)


def add_checkpoint_args(p, *, cadence_help: str) -> None:
    """The shared ``--checkpoint_dir`` / ``--resume`` / ``--ckpt_every`` CLI
    surface (``cadence_help`` names the harness's save cadence unit)."""
    p.add_argument("--checkpoint_dir", type=str, default=None,
                   help="Orbax checkpoint directory (async saves, "
                        "checksummed manifests, preemption emergency saves "
                        "— utils/checkpoint.py)")
    p.add_argument("--resume", type=str, default=None,
                   help="restore the newest verifiable checkpoint from this "
                        "directory before training")
    p.add_argument("--ckpt_every", type=int, default=1, help=cadence_help)


def add_stream_args(p, *, cadence_help: str) -> None:
    """The shared ``--stream*`` CLI surface: delta-compressed state
    streaming (stream/ — incremental checkpoints, warm rejoin, model
    push).  ``cadence_help`` names the harness's append cadence unit."""
    p.add_argument("--stream_dir", type=str, default=None,
                   help="delta state-stream directory (keyframe + Top-K "
                        "drift segments, manifest-checksummed; feeds warm "
                        "rejoin and tools/stream_serve.py consumers)")
    p.add_argument("--stream_every", type=int, default=1, help=cadence_help)
    p.add_argument("--stream_keyframe_every", type=int, default=8,
                   help="segments per stream window (one full keyframe, "
                        "Top-K deltas, one window-closing flush; the flush "
                        "makes keyframe+deltas == params bitwise)")
    p.add_argument("--stream_ratio", type=float, default=0.01,
                   help="Top-K density of each delta segment (fraction of "
                        "model coordinates)")
    p.add_argument("--stream_rejoin", action="store_true",
                   help="on a watchdog relaunch, catch up from the delta "
                        "stream instead of the survivors' full params "
                        "broadcast (falls back automatically when the "
                        "stream is absent or corrupt); requires "
                        "--stream_dir armed fleet-wide")


def make_stream(args, *, flight=None, events=None, log=print):
    """Resolve ``--stream_dir`` into a started
    :class:`~tpu_compressed_dp.stream.writer.StreamWriter` (or None).
    Single-writer discipline: only process 0 appends — every process
    holds the replicated params, and two writers would race the segment
    sequence."""
    if not getattr(args, "stream_dir", None):
        return None
    if jax.process_index() != 0:
        return None
    from tpu_compressed_dp.stream import StreamWriter

    return StreamWriter(args.stream_dir,
                        ratio=getattr(args, "stream_ratio", 0.01),
                        keyframe_every=getattr(args, "stream_keyframe_every",
                                               8),
                        flight=flight, events=events, log=log)


def stream_join_seq(args):
    """The joiner's pre-admission stream probe: the segment seq it can
    catch up to, or None when warm rejoin is off/unavailable.  Passed as
    ``stream_seq`` into the rendezvous join record so survivors take the
    params-skipping barrier (``ElasticRuntime.rejoin_barrier``) only for
    joiners that really can adopt from the stream — the probe runs a full
    verification catch-up, not just a head read."""
    if not (getattr(args, "stream_rejoin", False)
            and getattr(args, "stream_dir", None)):
        return None
    from tpu_compressed_dp.stream import (StreamCorrupt, StreamReader,
                                          is_stream_dir)

    if not is_stream_dir(args.stream_dir):
        return None
    try:
        reader = StreamReader(args.stream_dir)
        reader.catch_up()
    except StreamCorrupt as e:
        print(f"stream: rejoin probe failed ({e}); joining cold")
        return None
    return int(reader.applied_seq) if reader.applied_seq >= 0 else None


def stream_rejoin_params(args, state, decision=None, *, flight=None,
                         log=print):
    """Joiner-side warm rejoin: ``(adopted_params, info)`` for
    ``ElasticRuntime.join_world``, or ``(None, None)`` to take the
    survivors' full broadcast.  Runs AFTER admission, so the survivors'
    barrier flush (``StreamWriter.sync``) is already on disk and the
    reconstruction is bitwise the live params.  ``decision`` is the
    :class:`~tpu_compressed_dp.train.rendezvous.EpochDecision` the join
    returned: its committed ``warm`` bit is the fleet-wide agreement on
    the broadcast layout, so when it says cold the catch-up is skipped
    outright (``join_world`` would discard it anyway)."""
    if not (getattr(args, "stream_rejoin", False)
            and getattr(args, "stream_dir", None)):
        return None, None
    if decision is not None and not getattr(decision, "warm", False):
        log("stream: epoch committed a cold admission — skipping the "
            "warm-rejoin catch-up")
        return None, None
    from tpu_compressed_dp.stream import warm_rejoin

    adopted, info = warm_rejoin(state, args.stream_dir, log=log,
                                flight=flight)
    if info is None:
        return None, None
    return adopted.params, info


def make_preemption(log=print):
    """Install the SIGTERM/SIGINT preemption flag for a harness run.  Always
    pair with ``handler.uninstall()`` in the run's ``finally``."""
    from tpu_compressed_dp.utils.resilience import PreemptionHandler

    return PreemptionHandler(log=log).install()


def preempt_exit(err, *, ckpt=None, state=None, meta=None, events=None,
                 flight=None, log=print):
    """The harnesses' common preemption epilogue: drain any in-flight async
    checkpoint write (ignoring its failure — the emergency save is about to
    supersede it), cut a SYNCHRONOUS emergency checkpoint, emit a
    ``preempt`` event, dump the flight-recorder black box, and return the
    ``SystemExit`` carrying
    :data:`~tpu_compressed_dp.utils.resilience.PREEMPT_EXIT` for the caller
    to raise — the distinct code ``tools/watchdog.py --relaunch`` respawns
    immediately on (no backoff burn)."""
    from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

    saved = None
    if ckpt is not None and state is not None:
        try:
            ckpt.drain(raise_error=False)
            saved = ckpt.save(state, {**(meta or {}), "emergency": True})
        except Exception as save_err:
            log(f"preempt: emergency checkpoint FAILED: {save_err!r}")
    if flight is not None:
        # last write before the process dies: the postmortem's only
        # evidence that this rank exited on a reclaim, not a crash
        flight.observe(err, step=getattr(err, "step", None),
                       saved_step=saved)
    if events is not None:
        try:
            events.emit("preempt", step=getattr(err, "step", None),
                        signum=getattr(err, "signum", None), saved_step=saved)
        except Exception:
            pass
    log("preempt: "
        + (f"emergency checkpoint committed at step {saved}" if saved is not None
           else "no checkpoint directory — progress since the last save is lost")
        + f"; exiting {PREEMPT_EXIT} for immediate relaunch")
    return SystemExit(PREEMPT_EXIT)


def build_robustness(args, dtype):
    """Resolve the shared ``--guard*`` / ``--chaos`` CLI surface (all three
    harnesses) into ``(guard_cfg, chaos, crash_injector)``.

    In-graph chaos injection auto-arms the guard: injecting NaN without the
    guard poisons EF/compressor state permanently, which is only ever wanted
    as the explicit control arm of a drill (tools/chaos_drill.py constructs
    that case directly).  Loss scaling activates per ``dtype``
    (``GuardConfig.for_dtype``): dynamic on 16-bit floats, identity on fp32.
    """
    from tpu_compressed_dp.train.guard import GuardConfig, init_guard_state  # noqa: F401
    from tpu_compressed_dp.utils.chaos import ChaosConfig, maybe_crash_injector

    chaos = ChaosConfig.parse(args.chaos) if args.chaos else None
    want_guard = args.guard or (chaos is not None and chaos.injects_in_graph)
    if want_guard and not args.guard and jax.process_index() == 0:
        print("chaos: in-graph injection requested — arming the step guard")
    guard_cfg = GuardConfig.for_dtype(
        dtype,
        init_scale=args.guard_init_scale,
        backoff=args.guard_backoff,
        growth_interval=args.guard_growth_interval,
        max_consecutive_skips=args.guard_max_skips,
    ) if want_guard else None
    return guard_cfg, chaos, maybe_crash_injector(chaos)


def build_elastic(args, mesh, *, chaos=None, crash=None, events=None,
                  place=None, flight=None, stream=None, ef_axes=("data",)):
    """Resolve the ``--elastic*`` CLI surface into a started
    :class:`~tpu_compressed_dp.train.elastic.ElasticRuntime` (or None).

    The gossip plane only arms when ``--elastic_dir`` names the shared
    directory; the chaos-conversion and bounded-fetch detection planes are
    always on.  ``--chaos peer_timeout=<s>`` (the drill's knob) overrides
    ``--peer_timeout``.  ``crash`` (the armed CrashInjector) lets the
    runtime probe the ``during_remesh`` chaos phase so cascading failures
    are drillable; ``ef_axes`` names the mesh axes the gradient sync spans
    (the LM harness passes ``('data', 'seq')``).  Under a real
    multi-process run the rendezvous plane arms too (same shared
    directory), enabling the coordinated ``jax.distributed`` re-init on
    peer death (train/rendezvous.py).
    """
    if not getattr(args, "elastic", False):
        return None
    from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                 ElasticRuntime, PeerGossip)

    timeout = args.peer_timeout
    if chaos is not None and chaos.peer_timeout > 0:
        timeout = chaos.peer_timeout
    cfg = ElasticConfig(
        gossip_dir=args.elastic_dir, rank=jax.process_index(),
        peer_timeout_s=timeout, min_world=args.elastic_min_world,
        ef_policy=args.elastic_ef)
    gossip = None
    rendezvous = None
    if cfg.gossip_dir:
        # gossip is a PROCESS-level plane: one rank per host process, each
        # writing its own liveness file (ElasticRuntime.poll beats it).
        # Under the single-process simulation world == 1 — the simulated
        # per-device workers have no writers, so peer death there is the
        # chaos plane's job (drills simulate gossip peers directly).
        gossip = PeerGossip(cfg.gossip_dir, cfg.rank, jax.process_count(),
                            peer_timeout_s=cfg.peer_timeout_s)
        if jax.process_count() > 1:
            from tpu_compressed_dp.train.rendezvous import Rendezvous
            rendezvous = Rendezvous(cfg.gossip_dir, cfg.rank)
    # stream_armed is the FLEET-WIDE fact (--stream_dir is the same CLI on
    # every process); self.stream is held by process 0 only (make_stream),
    # so the warm-rejoin barrier layout must key on the former
    return ElasticRuntime(cfg, mesh, chaos=chaos, gossip=gossip,
                          events=events, place=place, crash=crash,
                          rendezvous=rendezvous, flight=flight,
                          stream=stream,
                          stream_armed=bool(getattr(args, "stream_dir",
                                                    None)),
                          ef_axes=tuple(ef_axes))


def elastic_distributed_init(args):
    """Multi-host rendezvous with elastic rejoin, replacing the harnesses'
    bare ``distributed_init`` call.

    A watchdog-relaunched host carries the running world's epoch in its
    environment (``TCDP_RENDEZVOUS_EPOCH``, exported by ``tools/watchdog.py
    --relaunch --elastic_dir``): instead of forming a fresh world from its
    stale ``--coordinator/--num_processes`` flags, it parks in the
    rendezvous join barrier until the survivors commit an epoch that
    readmits it, then initialises against the re-elected coordinator.
    Returns the :class:`~tpu_compressed_dp.train.rendezvous.EpochDecision`
    it joined under (the harness hands it to ``ElasticRuntime.join_world``
    to adopt the survivors' replicated state), or None on a fresh launch.
    A blown join deadline raises — the process exits nonzero and the
    watchdog's backoff is the park-and-retry loop.
    """
    from tpu_compressed_dp.parallel.mesh import distributed_init
    from tpu_compressed_dp.train.rendezvous import maybe_rejoin_from_env

    rank = getattr(args, "process_id", None)
    decision = maybe_rejoin_from_env(
        getattr(args, "elastic_dir", None),
        0 if rank is None else int(rank),
        deadline_s=4 * getattr(args, "peer_timeout", 60.0),
        stream_seq=stream_join_seq(args))
    if decision is not None:
        distributed_init(decision.address, decision.num_processes,
                         decision.process_id)
        return decision
    distributed_init(getattr(args, "coordinator", None),
                     getattr(args, "num_processes", None),
                     getattr(args, "process_id", None))
    return None


def comm_summary(acc: "MetricAccumulator") -> Dict[str, float]:
    """Epoch comm accounting (analytic bytes-on-wire, SURVEY.md §5): 'sent
    frac' = elements that travel; 'wire frac' = bits that travel vs a dense
    fp32 allreduce (catches quantizers, whose element count is dense but whose
    width is 2-9 bits).  Empty when compression metrics are absent."""
    if "comm/sent_elems" not in acc.sums:
        return {}
    dense = max(acc.mean("comm/dense_elems"), 1.0)
    return {
        "sent frac": acc.mean("comm/sent_elems") / dense,
        "wire frac": acc.mean("comm/sent_bits") / (32.0 * dense),
    }


def guard_summary(acc: "MetricAccumulator") -> Dict[str, float]:
    """Epoch step-guard accounting: 'skipped' = cumulative vetoed steps
    (end-of-epoch value of the monotone counter), 'loss scale' = the live
    dynamic loss scale.  Empty when the guard is off."""
    if "guard/nonfinite" not in acc.sums:
        return {}
    return {
        "skipped": acc.last.get("guard/skipped", 0.0),
        "loss scale": acc.last.get("guard/loss_scale", 1.0),
    }


def pad_batch(batch: Dict[str, np.ndarray], size: int) -> Dict[str, np.ndarray]:
    """Pad a (possibly short) final batch to a static ``size`` with a 0/1 mask,
    so every eval step sees one shape (no per-shape recompiles)."""
    n = len(batch["target"])
    if n == size and "mask" in batch:
        return batch
    mask = np.zeros((size,), np.float32)
    mask[:n] = 1.0
    if n == size:
        return {**batch, "mask": mask}
    pad_n = size - n
    x = np.concatenate([batch["input"], np.zeros((pad_n,) + batch["input"].shape[1:],
                                                 batch["input"].dtype)])
    y = np.concatenate([batch["target"], np.full((pad_n,), -1, batch["target"].dtype)])
    return {"input": x, "target": y, "mask": mask}


_EXHAUSTED = object()


def run_train_epoch(train_step, state: TrainState, batches: Iterable[Dict],
                    *, crash=None, step_offset: int = 0, guard_cfg=None,
                    timeline=None, elastic=None, preempt=None, flight=None,
                    ) -> Tuple[TrainState, MetricAccumulator]:
    # Metrics stay on device until the epoch ends: a per-step float() would
    # block host batch prep on the device and serialize the pipeline (JAX's
    # async dispatch is the overlap the reference engineered with side
    # streams).  The final device_get blocks, so epoch wall-times stay honest.
    #
    # ``crash`` (utils/chaos.CrashInjector) fires the host-side chaos fault
    # before dispatching the matching global step (= step_offset + i, the
    # attempted-step counter — the same numbering the in-graph injection
    # reads from TrainState.step).  ``guard_cfg`` arms the wedge check: the
    # consecutive-skip streak is inspected on the fetched metrics at epoch
    # end (per-step checks would force a device sync each step and
    # serialize the pipeline; detection latency here is one epoch, and the
    # raise lands inside run_with_recovery's retry loop like any failure).
    #
    # ``timeline`` (obs/trace.StepTimeline) takes each step's host spans
    # (the `next()` on the batch iterator, the host-to-device copy, the
    # step's call) and hands one of the step's outputs to its watcher
    # thread for the completion stamp; with none passed the spans go to the
    # process-wide timeline, so every caller leaves them behind.  Neither
    # the spans nor the stamps wait on the device in this thread.
    #
    # ``elastic`` (train/elastic.ElasticRuntime) adds the per-batch gossip
    # poll and the second crash check AFTER dispatch (phase
    # 'mid_collective': the step's collectives are in flight — the
    # deterministic stand-in for a peer dying inside an allreduce), and
    # bounds the epoch-end metrics fetch so a dead peer raises PeerFailed
    # instead of stalling the fetch forever.
    #
    # ``preempt`` (utils/resilience.PreemptionHandler) raises Preempted at
    # the first step boundary after SIGTERM/SIGINT landed; checked AFTER
    # crash.check so chaos' crash=preempt self-SIGTERM at step N is
    # observed within the same iteration, and the except below still rides
    # the live state out for the emergency save.
    acc = MetricAccumulator()
    step_metrics = []
    if timeline is None:
        timeline = process_timeline()
    # a new call: whatever happened since the previous epoch's last dispatch
    # (eval, checkpoint saves, loader swaps) stays out of step 0
    timeline.begin_call()
    batches = iter(batches)
    try:
        for i in itertools.count():
            with timeline.span("data_wait"):
                batch = next(batches, _EXHAUSTED)
            if batch is _EXHAUSTED:
                break
            if crash is not None:
                crash.check(step_offset + i)
            if preempt is not None:
                preempt.check(step_offset + i)
            if elastic is not None:
                elastic.poll(step_offset + i)
            with timeline.span("to_device"):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with timeline.span("dispatch"):
                state, metrics = train_step(state, batch)
            timeline.step_done(metrics)
            if crash is not None:
                crash.check(step_offset + i, phase="mid_collective")
            step_metrics.append(metrics)
    except Exception as err:
        timeline.step_failed()
        # donation consumed the caller's pre-epoch buffers at step 0, so
        # the only live TrainState is this frame's local — ride it out on
        # the exception for the elastic remesh handler (steps dispatched
        # before the failure drain to completion during state migration
        # under the single-process simulation; the rest of the epoch
        # re-runs on the surviving mesh)
        err.elastic_state = state
        raise
    with timeline.span("fetch"):
        if elastic is not None:
            fetched = elastic.bounded_get(step_metrics,
                                          step=step_offset + len(step_metrics))
        else:
            fetched = jax.device_get(step_metrics)
    timeline.end_call()
    for metrics in fetched:
        acc.update(metrics)
    if flight is not None:
        # ring the fetched (host) metrics BEFORE the guard inspects them:
        # when the wedge check raises, the streak history that tripped it is
        # already in the black box (O(capacity) host dicts, no device work)
        for j, metrics in enumerate(fetched):
            flight.note_step(step_offset + j, metrics)
    if guard_cfg is not None and fetched:
        from tpu_compressed_dp.train.guard import check_guard_metrics

        check_guard_metrics(fetched[-1], guard_cfg, flight=flight)
    return state, acc


def run_eval(eval_step, state: TrainState, batches: Iterable[Dict], batch_size: int) -> Dict[str, float]:
    sums = {"loss_sum": 0.0, "correct": 0.0, "correct5": 0.0, "count": 0.0}
    for batch in batches:
        padded = pad_batch(batch, batch_size)
        m = eval_step(state, {k: jnp.asarray(v) for k, v in padded.items()})
        for k in sums:
            sums[k] += float(m[k])
    n = max(sums["count"], 1.0)
    return {
        "loss": sums["loss_sum"] / n,
        "acc": sums["correct"] / n,
        "acc5": sums["correct5"] / n,
        "count": sums["count"],
    }


def train_epoch(
    train_step,
    eval_step,
    state: TrainState,
    train_batches,
    test_batches,
    timer: Timer,
    batch_size: int,
    test_time_in_total: bool = False,
    crash=None,
    step_offset: int = 0,
    guard_cfg=None,
    timeline=None,
    world: Optional[int] = None,
    pods: int = 1,
    elastic=None,
    preempt=None,
    flight=None,
) -> Tuple[TrainState, Dict[str, float], MetricAccumulator]:
    """One train + eval pass with the reference's epoch-summary shape
    (`core.py:324-331`).  ``crash``/``step_offset``/``guard_cfg``/
    ``timeline`` pass through to :func:`run_train_epoch`; with ``world``
    the summary gains the analytic per-chip comm rate ('comm MB/s', the
    transport-split arithmetic of ``utils.meters.per_chip_comm_bytes``).
    Also returns the epoch's :class:`MetricAccumulator` so callers can
    export raw metric means (event stream, Prometheus) without re-running
    the reduction."""
    state, train_acc = run_train_epoch(
        train_step, state, train_batches, crash=crash,
        step_offset=step_offset, guard_cfg=guard_cfg, timeline=timeline,
        elastic=elastic, preempt=preempt, flight=flight)
    train_time = timer()
    test_stats = run_eval(eval_step, state, test_batches, batch_size)
    test_time = timer(test_time_in_total)
    summary = {
        "train time": train_time,
        "train loss": train_acc.mean("loss"),
        "train acc": train_acc.mean("correct"),
        "test time": test_time,
        "test loss": test_stats["loss"],
        "test acc": test_stats["acc"],
        "total time": timer.total_time,
    }
    summary.update(comm_summary(train_acc))
    summary.update(guard_summary(train_acc))
    if world:
        from tpu_compressed_dp.utils.meters import per_chip_comm_bytes

        comm_means = {k: train_acc.mean(k) for k in train_acc.sums
                      if k.startswith("comm/")}
        comm_b = per_chip_comm_bytes(comm_means, world, pods)
        if comm_b is not None and train_time > 0:
            summary["comm MB/s"] = comm_b * train_acc.steps / train_time / 1e6
        gauges = fabric_gauges(comm_means, world, pods, train_acc.steps,
                               train_time)
        if gauges:
            summary["dcn MB/s"] = (gauges.get("net/dcn_gbps_per_chip", 0.0)
                                   * 1e3 / 8)
    return state, summary, train_acc
