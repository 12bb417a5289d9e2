#!/usr/bin/env python
"""Chaos drill: run the fault-injection matrix and assert the step guard's
invariants end to end.

What it proves (the ISSUE 3 acceptance criteria, each as a named drill):

  * ``skip_consistency`` — NaN injected into ONE worker's gradients at step k
    => the cross-worker vote vetoes the update everywhere: params, optimizer
    buffers, batch stats and EF residual are bitwise equal to their pre-step
    values, and every other step applies normally.
  * ``comp_hold`` — same, for the stateful compressor path (PowerSGD): the
    warm-start Q factors are held bitwise on the skipped step.
  * ``loss_scale`` — an Inf backs the dynamic loss scale off by
    ``backoff``; ``growth_interval`` consecutive good steps regrow it.
  * ``ef_identity`` — on non-skipped steps the EF identity holds through the
    guarded sync: world-mean(transmitted) + local residual change accounts
    for the full gradient, i.e. ``psum(acc - new_ef)/W == synced`` per
    worker (checked for the simulate and wire+sharded transports).
  * ``poison_control`` — the control arm: the SAME injection with the guard
    OFF poisons the parameters (proves the injection actually fires and the
    guard is what contains it).
  * ``max_skips`` — an every-step injection wedges the run; the host-side
    check raises GuardExceeded once the consecutive-skip streak passes
    ``max_consecutive_skips``.
  * ``crash_recovery`` — a host-crash injection mid-run recovers through
    ``run_with_recovery`` (Orbax restore + replay) to a final state bitwise
    identical to the uncrashed run — chaos is step-counter driven, so the
    replay reproduces the same faults.

Checkpoint drills (the ISSUE 9 acceptance rows — utils/checkpoint.py):

  * ``ckpt_preempt`` — ``crash=preempt`` delivers a REAL self-SIGTERM at
    step N; the loop drains the in-flight async save, cuts an emergency
    checkpoint and the relaunched run resumes to a final state bitwise
    identical to the uninterrupted one (an in-graph NaN injection landing
    after the preemption point proves the replay lines up).
  * ``ckpt_corrupt`` — a flipped payload byte in the latest checkpoint is
    caught by the manifest digest; restore walks back to the previous
    verifiable step (``ckpt/rollback_steps`` + ``ckpt_rollback`` event)
    instead of raising.
  * ``stream_corrupt`` — same discipline for the delta state stream
    (stream/): a flipped byte in a mid-window delta segment is caught by
    the segment manifest digest; the consumer walks BACK to its stored
    keyframe (bitwise) and re-converges bitwise at the next keyframe +
    window close.  A torn keyframe with no later anchor makes the stream
    unusable: ``warm_rejoin`` refuses it and the joiner falls back to the
    full Orbax restore path instead of adopting a half-applied state.

Elastic drills (the ISSUE 7 acceptance row — train/elastic.py):

  * ``elastic_gossip`` — heartbeat-directory failure detection: a silent
    peer is declared dead within ``peer_timeout``; a stale file of a dead
    prior incarnation never refreshes liveness; a restarted peer (higher
    incarnation) becomes a rejoin candidate.
  * ``elastic_remesh`` — ``crash=mid_collective`` kills worker w at step N;
    survivors convert the fault, remesh W -> W-1, and continue to
    completion.  Replicated state (params/opt/batch stats) is bitwise the
    pre-kill value; the EF migration matches the declared fold-or-drop
    semantics bitwise (fold: survivor row 0 += lost row, exact fp32; drop:
    ``elastic/dropped_ef_norm`` == the lost rows' L2, fp64-accumulated).
  * ``elastic_readmit`` — scale back up: the parked worker rejoins at a
    barrier with a zero EF row and PowerSGD factors broadcast-re-warmed
    from survivor row 0, then trains at full W again.
  * ``elastic_cascade`` — ``crash=during_remesh``: a second worker dies
    while survivors are inside ``handle_failure``; the dead set is unioned
    and the shrink restarts (one cascading remesh down to ``min_world``),
    and a union landing below ``min_world`` raises a clean PeerFailed
    naming every dead rank instead of wedging.
  * ``elastic_matrix`` — the kill-step x worker x EF-policy cross, plus a
    wire+sharded-transport variant (the owner partition recomputes at W-1).

Control drill (the ISSUE 11 acceptance row — control/):

  * ``control_resume`` — a crash-relaunch mid-decision-window resumes the
    adaptive compression controller bitwise: the checkpointed ControlState
    carries the open window's accumulators, so the relaunched run replays
    the same rung schedule and the same ``control_decision`` events, field
    for field, as the uninterrupted run.

Fleet drill (the ISSUE 12 acceptance row — fleet/ + tools/fleet.py):

  * ``fleet`` — three jobs, one 8-device pool: a high-priority arrival
    EVICTS one job (emergency checkpoint + exit 75, resumed when capacity
    clears) and SHRINKS an elastic one through the readmit barrier; freed
    slices bin-pack back (the evictee re-places, the shrunk job grows
    back to ``max_world``), every job finishes bitwise identical to a
    solo run of its applied-update/world trajectory, and every transition
    lands as ``fleet_*`` JSONL events + per-job Prometheus rollups.
  * ``fleet_matrix`` — the EF-policy cross (fold/drop) plus the rigid
    cell (no elastic slot => the planner preempts by eviction only).

Usage::

    python tools/chaos_drill.py --quick     # tier-1 smoke subset (~4 drills)
    python tools/chaos_drill.py             # full matrix (slow)
    python tools/chaos_drill.py --list      # quick/slow drill-row matrix

Exit code 0 = every invariant held.
"""

from __future__ import annotations

import os

if __name__ == "__main__":
    # standalone invocation: an 8-device virtual CPU mesh, set up before the
    # first jax import (importers — the test suite, whose conftest already
    # did this — get no side effects)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
            + " --xla_backend_optimization_level=0").strip()

import argparse
import dataclasses
import tempfile
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------- fixtures

def _mesh(n=8):
    from tpu_compressed_dp.parallel.mesh import make_data_mesh

    return make_data_mesh(n)


def _tiny_setup(mesh, comp_cfg, guard_cfg, chaos, *, momentum=0.9, seed=0,
                with_factory=False, control_cfg=None):
    """TinyMLP + optimizer + state + guarded train step on ``mesh``."""
    import flax.linen as nn

    from tpu_compressed_dp.control import init_control_state
    from tpu_compressed_dp.models.common import init_model, make_apply_fn
    from tpu_compressed_dp.parallel.dp import init_comp_state, init_ef_state
    from tpu_compressed_dp.train.guard import init_guard_state
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.state import TrainState
    from tpu_compressed_dp.train.step import make_train_step

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(4)(x)

    module = TinyMLP()
    params, stats = init_model(module, jax.random.key(seed),
                               jnp.zeros((1, 4, 4, 3), jnp.float32))
    opt = SGD(lr=0.05, momentum=momentum, nesterov=momentum > 0)
    ndev = mesh.shape["data"]
    state = TrainState.create(
        params, stats, opt.init(params),
        init_ef_state(params, comp_cfg, ndev), jax.random.key(seed + 1),
        comp=init_comp_state(params, comp_cfg, ndev),
        guard=init_guard_state(guard_cfg),
        control=init_control_state(control_cfg),
    )

    def step_for(m, cfg=comp_cfg):
        # the elastic drills rebuild the step over the W-1 mesh — same
        # module/opt/config, new world (the sharded transport's owner
        # partition recomputes at trace time); the control drill rebuilds
        # it per RUNG (same mesh, new compression config)
        return make_train_step(make_apply_fn(module), opt, cfg, m,
                               guard_cfg=guard_cfg, chaos=chaos, donate=False)

    step = step_for(mesh)
    if with_factory:
        return state, step, step_for
    return state, step


def _batch(seed=0, n=32):
    rng = np.random.RandomState(seed)
    return {
        "input": jnp.asarray(rng.randn(n, 4, 4, 3).astype(np.float32)),
        "target": jnp.asarray(rng.randint(0, 4, n).astype(np.int32)),
    }


def _snap(state, fields=("params", "opt_state", "batch_stats", "ef", "comp")):
    return {f: jax.tree.map(np.asarray, getattr(state, f)) for f in fields}


def _assert_bitwise(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: leaf not bitwise equal")


class _Recorder:
    """Minimal EventStream stand-in: records (kind, fields) in memory."""

    def __init__(self):
        self.events = []

    def emit(self, kind, **fields):
        self.events.append((kind, fields))


def _flip_byte_in_step(directory, step) -> str:
    """Flip one byte in the middle of the step's largest payload file —
    size-preserving, so only the manifest digest can catch it."""
    sdir = os.path.join(directory, str(step))
    target, size = None, -1
    for root, _, files in os.walk(sdir):
        for f in files:
            p = os.path.join(root, f)
            s = os.path.getsize(p)
            if s > size:
                target, size = p, s
    assert target is not None and size > 0, f"nothing to corrupt in {sdir}"
    with open(target, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    return target


# ------------------------------------------------------------------ drills

def drill_skip_consistency(mesh, *, kind="nan", target="grads", worker=2,
                           bad_step=2, n_steps=5) -> Dict:
    """One poisoned worker at one step => identical global skip; everything
    the step mutates held bitwise; all other steps applied."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.chaos import ChaosConfig

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True)
    gcfg = GuardConfig(loss_scaling=False, max_consecutive_skips=10)
    chaos = ChaosConfig(kind=kind, target=target, steps=(bad_step,),
                        worker=worker)
    state, step = _tiny_setup(mesh, comp, gcfg, chaos)
    batch = _batch()
    nonfinite = []
    for i in range(n_steps):
        pre = _snap(state) if i == bad_step else None
        state, m = step(state, batch)
        nonfinite.append(float(m["guard/nonfinite"]))
        if i == bad_step:
            _assert_bitwise(pre, _snap(state),
                            f"skip_consistency[{kind}/{target}] held state")
            assert float(m["guard/skip_streak"]) == 1.0
            assert float(m["guard/last_good_step"]) == bad_step
        assert np.isfinite(float(m["loss"]))
    expected = [1.0 if i == bad_step else 0.0 for i in range(n_steps)]
    assert nonfinite == expected, (nonfinite, expected)
    assert int(state.step) == n_steps
    for leaf in jax.tree.leaves(state.ef):
        assert np.all(np.isfinite(np.asarray(leaf))), "EF picked up poison"
    return {"nonfinite": nonfinite}


def drill_comp_hold(mesh) -> Dict:
    """PowerSGD warm-start Q (TrainState.comp) held bitwise on the vetoed
    step, mutated on good steps."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.chaos import ChaosConfig

    comp = CompressionConfig(method="powersgd", rank=2, error_feedback=True)
    gcfg = GuardConfig(loss_scaling=False)
    chaos = ChaosConfig(kind="inf", target="grads", steps=(1,), worker=0)
    state, step = _tiny_setup(mesh, comp, gcfg, chaos)
    batch = _batch()
    state, m = step(state, batch)
    assert float(m["guard/nonfinite"]) == 0.0
    pre = _snap(state, ("comp", "ef"))
    good_comp = {k: np.asarray(v) for k, v in state.comp.items()}
    state, m = step(state, batch)
    assert float(m["guard/nonfinite"]) == 1.0
    _assert_bitwise(pre, _snap(state, ("comp", "ef")), "comp_hold")
    state, m = step(state, batch)
    assert float(m["guard/nonfinite"]) == 0.0
    moved = any(not np.array_equal(np.asarray(state.comp[k]), good_comp[k])
                for k in good_comp)
    assert moved, "comp never updates on good steps?"
    return {}


def drill_loss_scale(mesh) -> Dict:
    """Backoff on the bad step, regrowth after growth_interval good steps."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.chaos import ChaosConfig

    comp = CompressionConfig(method=None)
    gcfg = GuardConfig(init_scale=1024.0, backoff=0.5, growth=2.0,
                       growth_interval=3, loss_scaling=True)
    chaos = ChaosConfig(kind="inf", target="loss", steps=(1,), worker=0)
    state, step = _tiny_setup(mesh, comp, gcfg, chaos, momentum=0.0)
    batch = _batch()
    scales = []
    for _ in range(6):
        state, m = step(state, batch)
        scales.append(float(m["guard/loss_scale"]))
    assert scales == [1024.0, 512.0, 512.0, 512.0, 1024.0, 1024.0], scales
    return {"scales": scales}


def drill_ef_identity(mesh, transport="allgather", mode="simulate") -> Dict:
    """transmitted + residual == gradient on a non-vetoed guarded sync:
    per worker, ``psum(acc - new_ef)/W == synced`` where acc = grad + ef."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from tpu_compressed_dp.parallel.dp import CompressionConfig, make_grad_sync

    cfg = CompressionConfig(method="topk", ratio=0.25, error_feedback=True,
                            mode=mode, transport=transport,
                            granularity="entiremodel")
    sync = make_grad_sync(cfg, "data")
    n = 512
    W = mesh.shape["data"]
    rng = np.random.RandomState(3)
    grads = jnp.asarray(rng.randn(W, n).astype(np.float32))
    efs = jnp.asarray(0.1 * rng.randn(W, n).astype(np.float32))

    def local(g, e):
        ok = jnp.asarray(True)
        synced, new_ef, _, _ = sync({"w": g[0]}, {"w": e[0]}, (),
                                    jax.random.key(0), ok=ok)
        sent = g[0] + e[0] - new_ef["w"]  # what this worker transmitted
        mean_sent = jax.lax.psum(sent, "data") / jax.lax.psum(1, "data")
        gap = jnp.max(jnp.abs(mean_sent - synced["w"]))
        return gap[None], new_ef["w"][None]

    gap, new_ef = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"))))(grads, efs)
    assert float(jnp.max(gap)) < 1e-5, float(jnp.max(gap))
    return {"max_gap": float(jnp.max(gap))}


def drill_poison_control(mesh) -> Dict:
    """Control arm: guard OFF, same injection => params DO go nonfinite
    (the injection is real; the guard is what contains it)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.utils.chaos import ChaosConfig

    comp = CompressionConfig(method=None)
    chaos = ChaosConfig(kind="nan", target="grads", steps=(1,), worker=4)
    state, step = _tiny_setup(mesh, comp, None, chaos, momentum=0.0)
    batch = _batch()
    for _ in range(2):
        state, m = step(state, batch)
    finite = all(np.all(np.isfinite(np.asarray(l)))
                 for l in jax.tree.leaves(state.params))
    assert not finite, "chaos injection did not fire"
    return {}


def drill_max_skips(mesh) -> Dict:
    """Every-step injection wedges the run; the host check raises."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import (GuardConfig, GuardExceeded,
                                               check_guard_metrics)
    from tpu_compressed_dp.utils.chaos import ChaosConfig

    comp = CompressionConfig(method=None)
    gcfg = GuardConfig(loss_scaling=False, max_consecutive_skips=3)
    chaos = ChaosConfig(kind="nan", target="grads", every=1, worker=0)
    state, step = _tiny_setup(mesh, comp, gcfg, chaos, momentum=0.0)
    batch = _batch()
    raised_at = None
    try:
        for i in range(8):
            state, m = step(state, batch)
            check_guard_metrics(jax.device_get(m), gcfg)
    except GuardExceeded:
        raised_at = i
    assert raised_at == 3, f"GuardExceeded at step {raised_at}, expected 3"
    return {"raised_at_step": raised_at}


def drill_crash_recovery(mesh, *, crash_at_step=5, chaos_spec=None) -> Dict:
    """Host-crash at step N + run_with_recovery == the uncrashed run,
    bitwise — including when in-graph chaos fires around the crash (the
    step-counter-driven injection replays identically after restore)."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils import resilience
    from tpu_compressed_dp.utils.chaos import ChaosConfig, CrashInjector
    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True)
    gcfg = GuardConfig(loss_scaling=False)
    chaos = (ChaosConfig.parse(chaos_spec) if chaos_spec
             else ChaosConfig(kind="nan", target="grads", steps=(3,), worker=1))
    epochs, steps_per_epoch = 4, 2
    batches = [_batch(seed=s) for s in range(steps_per_epoch)]

    def run(crash: Optional[CrashInjector], ckpt_dir: Optional[str]):
        state, step = _tiny_setup(mesh, comp, gcfg, chaos)
        ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None

        def epoch_fn(state, epoch):
            for i, b in enumerate(batches):
                if crash is not None:
                    crash.check(epoch * steps_per_epoch + i)
                state, _ = step(state, b)
            if ckpt:
                ckpt.save(state, {"epoch": epoch})
            return state

        if ckpt:
            final, info = resilience.run_with_recovery(
                epoch_fn, state, epochs, checkpointer=ckpt,
                on_restore=lambda s: s.with_mesh_sharding(mesh))
            ckpt.close()
        else:
            info = {"restores": 0}
            final = state
            for e in range(epochs):
                final = epoch_fn(final, e)
        return final, info

    clean, _ = run(None, None)
    with tempfile.TemporaryDirectory() as td:
        crashed, info = run(CrashInjector(crash_at_step),
                            os.path.join(td, "ck"))
    assert info["restores"] == 1, info
    _assert_bitwise(_snap(clean), _snap(crashed), "crash_recovery state")
    assert int(clean.step) == int(crashed.step) == epochs * steps_per_epoch
    for f in ("loss_scale", "skips", "total_skipped", "last_good_step"):
        assert np.array_equal(np.asarray(getattr(clean.guard, f)),
                              np.asarray(getattr(crashed.guard, f))), f
    return {"restores": info["restores"]}


def drill_ckpt_preempt(mesh, *, preempt_at_step=3, n_steps=6) -> Dict:
    """``crash=preempt`` (a real self-SIGTERM) mid-run => the loop cuts an
    emergency checkpoint (draining the in-flight async save first) and the
    relaunched run resumes to a final state bitwise identical to the
    uninterrupted one — including an in-graph NaN injection landing AFTER
    the preemption point, proving the replay lines up step-for-step."""
    import time

    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.chaos import ChaosConfig, CrashInjector
    from tpu_compressed_dp.utils.checkpoint import Checkpointer
    from tpu_compressed_dp.utils.resilience import (Preempted,
                                                    PreemptionHandler)

    comp = CompressionConfig(method="powersgd", rank=2, error_feedback=True)
    gcfg = GuardConfig(loss_scaling=False)
    # NaN at step 4 — AFTER the preempt at 3 — fires only in the resumed
    # half, so a misaligned replay cannot pass the bitwise check
    chaos = ChaosConfig(kind="nan", target="grads", steps=(4,), worker=2,
                        crash_at_step=preempt_at_step, crash_mode="preempt")
    batches = [_batch(seed=s) for s in range(n_steps)]

    clean, step = _tiny_setup(mesh, comp, gcfg, chaos)
    for i in range(n_steps):
        clean, _ = step(clean, batches[i])

    with tempfile.TemporaryDirectory() as td:
        state, step = _tiny_setup(mesh, comp, gcfg, chaos)
        ckpt = Checkpointer(td)
        crash = CrashInjector(chaos.crash_at_step, mode=chaos.crash_mode)
        handler = PreemptionHandler(log=lambda s: None).install()
        assert handler.installed, "drill must run on the main thread"
        preempted_at = None
        try:
            i = 0
            while i < n_steps:
                crash.check(i)          # preempt mode: self-SIGTERM, no raise
                if crash.fired and not handler.triggered:
                    # the signal lands within a few bytecodes; wait it out
                    # deterministically rather than racing the handler
                    for _ in range(1000):
                        if handler.triggered:
                            break
                        time.sleep(0.001)
                handler.check(i)
                state, _ = step(state, batches[i])
                i += 1
                if i % 2 == 0:
                    ckpt.save_async(state, {"step_i": i})
            raise AssertionError("preempt never fired")
        except Preempted as err:
            preempted_at = err.step
            # the emergency-save path: drain the in-flight async write,
            # then cut the final checkpoint synchronously
            ckpt.drain(raise_error=False)
            ckpt.save(state, {"step_i": i, "emergency": True})
            ckpt.close()
        finally:
            handler.uninstall()
        assert preempted_at == preempt_at_step, preempted_at

        # "relaunch": fresh process state, restore, run the remaining steps
        state2, step2 = _tiny_setup(mesh, comp, gcfg, chaos)
        ckpt2 = Checkpointer(td)
        state2, meta = ckpt2.restore(state2)
        ckpt2.close()
        state2 = state2.with_mesh_sharding(mesh)
        assert meta.get("emergency") is True, meta
        i = int(meta["step_i"])
        assert i == preempt_at_step, (i, meta)
        while i < n_steps:
            state2, _ = step2(state2, batches[i])
            i += 1

    _assert_bitwise(_snap(clean), _snap(state2), "ckpt_preempt state")
    assert int(clean.step) == int(state2.step) == n_steps
    for f in ("loss_scale", "skips", "total_skipped", "last_good_step"):
        assert np.array_equal(np.asarray(getattr(clean.guard, f)),
                              np.asarray(getattr(state2.guard, f))), f
    return {"preempted_at": preempted_at, "resumed_from": preempt_at_step,
            "bitwise": True}


def drill_ckpt_corrupt(mesh, *, n_steps=4) -> Dict:
    """A corrupted latest checkpoint (one flipped payload byte — the
    manifest digest is the only thing that can notice) => restore walks
    back to the newest verifiable step instead of raising, records
    ``ckpt/rollback_steps`` and emits a ``ckpt_rollback`` event."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True)
    gcfg = GuardConfig(loss_scaling=False)
    state, step = _tiny_setup(mesh, comp, gcfg, None)
    batch = _batch()
    with tempfile.TemporaryDirectory() as td:
        ckpt = Checkpointer(td)
        snaps = {}
        for i in range(n_steps):
            state, _ = step(state, batch)
            ckpt.save(state, {"step_i": i + 1})
            snaps[int(state.step)] = _snap(state)
        ckpt.close()

        _flip_byte_in_step(td, n_steps)   # newest step, now torn

        fresh, _ = _tiny_setup(mesh, comp, gcfg, None)
        ckpt2 = Checkpointer(td)
        ckpt2.events = _Recorder()
        restored, meta = ckpt2.restore(fresh)
        assert int(restored.step) == n_steps - 1, int(restored.step)
        assert int(meta["step_i"]) == n_steps - 1, meta
        _assert_bitwise(snaps[n_steps - 1], _snap(restored),
                        "ckpt_corrupt fallback state")
        assert ckpt2.metrics()["ckpt/rollback_steps"] == 1.0
        kinds = [k for k, _ in ckpt2.events.events]
        assert "ckpt_rollback" in kinds, kinds
        ckpt2.close()
    return {"rollback_steps": 1, "restored_step": n_steps - 1}


def drill_stream_corrupt(mesh, *, keyframe_every=4) -> Dict:
    """A flipped payload byte in a mid-window delta segment is caught by
    the segment manifest digest => the consumer walks back to its stored
    keyframe bitwise and re-converges bitwise once the next keyframe and
    window close land; a torn keyframe with no later anchor makes the
    stream unusable => ``warm_rejoin`` returns no adoption info and the
    joiner takes the full-restore path."""
    import copy

    from tpu_compressed_dp.stream.reader import StreamReader
    from tpu_compressed_dp.stream.rejoin import warm_rejoin
    from tpu_compressed_dp.stream.store import (StreamCorrupt,
                                                segment_payload_path)
    from tpu_compressed_dp.stream.writer import StreamWriter

    rng = np.random.RandomState(7)
    params = {"dense": {"kernel": rng.randn(48, 8).astype(np.float32)},
              "bias": rng.randn(64).astype(np.float32)}

    def advance():
        params["dense"]["kernel"] = (
            params["dense"]["kernel"]
            + rng.randn(48, 8).astype(np.float32) * 0.01)
        params["bias"] = (params["bias"]
                          + rng.randn(64).astype(np.float32) * 0.01)

    def flip(path):
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]))

    def quiet(*a, **k):
        pass

    @dataclasses.dataclass
    class Joiner:
        params: dict
        step: int

    with tempfile.TemporaryDirectory() as td:
        sd = os.path.join(td, "stream")
        w = StreamWriter(sd, ratio=0.25, keyframe_every=keyframe_every,
                         log=quiet)
        w.append(params, step=1)                    # seq 0: keyframe
        kf_params = copy.deepcopy(params)
        advance(); w.append(params, step=2)         # seq 1: delta
        advance(); w.append(params, step=3)         # seq 2: delta (mid-window)

        flip(segment_payload_path(sd, 2))           # torn delta

        r = StreamReader(sd, log=quiet)
        r.catch_up()
        # the digest notices; the consumer never serves the torn delta —
        # it reverts to the last keyframe's reconstruction, bitwise
        assert r.metrics()["stream/corrupt_segments"] == 1.0
        assert int(r.applied_seq) == 0 and int(r.applied_step) == 1
        _assert_bitwise(kf_params, r.params_like(kf_params),
                        "stream_corrupt walk-back")

        advance(); w.append(params, step=4)         # seq 3: flush (skipped —
        #                                             awaiting a keyframe)
        advance(); w.append(params, step=5)         # seq 4: fresh keyframe
        kf2 = copy.deepcopy(params)
        r.catch_up()
        assert int(r.applied_seq) == 4, int(r.applied_seq)
        _assert_bitwise(kf2, r.params_like(kf2), "stream_corrupt re-anchor")
        advance(); w.sync(params, step=6)           # window-closing flush
        r.catch_up()
        assert r.exact, "head not exact after sync"
        _assert_bitwise(params, r.params_like(params),
                        "stream_corrupt reconverged head")
        w.close()

        # half two: a torn KEYFRAME with no later anchor is unusable — the
        # reader raises and warm rejoin refuses to adopt anything
        sd2 = os.path.join(td, "stream2")
        params2 = {"w": rng.randn(128).astype(np.float32)}
        w2 = StreamWriter(sd2, ratio=0.25, keyframe_every=keyframe_every,
                          log=quiet)
        w2.append(params2, step=1)                  # seq 0: keyframe
        params2["w"] = params2["w"] + 0.5
        w2.append(params2, step=2)                  # seq 1: delta
        w2.close()
        flip(segment_payload_path(sd2, 0))
        try:
            StreamReader(sd2, log=quiet).catch_up()
            raise AssertionError("torn keyframe went unnoticed")
        except StreamCorrupt:
            pass
        joiner = Joiner(params=copy.deepcopy(params2), step=0)
        adopted, info = warm_rejoin(joiner, sd2, log=quiet)
        assert info is None and adopted is joiner, (
            "warm rejoin adopted from an unusable stream")
    return {"corrupt_segments": 1, "walkback_seq": 0, "reconverged": True,
            "keyframe_fallback": True}


def drill_control_resume(mesh, *, preempt_at_step=4, n_steps=9) -> Dict:
    """Crash-relaunch MID-decision-window resumes the adaptive controller
    bitwise: the saved ControlState (riding the checkpoint next to guard)
    carries the open window's accumulators, so the relaunched run replays
    the SAME rung schedule and the SAME ``control_decision`` events,
    field for field, as the uninterrupted run — the modeled signal makes
    every decision a pure function of checkpointed state."""
    from tpu_compressed_dp.control import (ControlConfig, Controller,
                                           comp_for_rung)
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.guard import GuardConfig
    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    base = CompressionConfig(method="topk", ratio=0.5, error_feedback=True)
    # window=3, preempt at 4 => the crash lands one update INTO a window;
    # modeled comm (1e6 bits @ 100 Mbit/s = 10 ms/update) >> the pinned
    # 0.5 ms budget, so the schedule is down, down, then hold at the floor
    ctrl_cfg = ControlConfig(method="topk", rungs=(0.5, 0.25, 0.125),
                             window=3, budget_ms=0.5)
    gcfg = GuardConfig(loss_scaling=False)
    batches = [_batch(seed=s) for s in range(n_steps)]
    bits_per_update = 1e6

    def span(state, step_for, controller, i0, i1):
        cache = {}
        for i in range(i0, i1):
            rung = int(np.asarray(state.control.rung))
            if rung not in cache:
                cache[rung] = step_for(mesh, comp_for_rung(base, ctrl_cfg,
                                                           rung))
            state, _ = cache[rung](state, batches[i])
            new_control, _ = controller.tick(
                state.control, applied=int(state.step),
                signals=controller.window_signals(mean_bits=bits_per_update))
            state = state.replace(control=new_control)
        return state

    def decisions(rec):
        return [(k, f) for k, f in rec.events if k == "control_decision"]

    # the uninterrupted run
    rec_clean = _Recorder()
    clean, _, step_for = _tiny_setup(mesh, base, gcfg, None,
                                     with_factory=True, control_cfg=ctrl_cfg)
    clean = span(clean, step_for, Controller(ctrl_cfg, events=rec_clean),
                 0, n_steps)

    with tempfile.TemporaryDirectory() as td:
        # first life: preempt mid-window, emergency save
        rec_a = _Recorder()
        s1, _, sf1 = _tiny_setup(mesh, base, gcfg, None, with_factory=True,
                                 control_cfg=ctrl_cfg)
        s1 = span(s1, sf1, Controller(ctrl_cfg, events=rec_a),
                  0, preempt_at_step)
        ckpt = Checkpointer(td)
        ckpt.save(s1, {"step_i": preempt_at_step, "emergency": True})
        ckpt.close()

        # "relaunch": fresh process state, restore, finish the run
        rec_b = _Recorder()
        s2, _, sf2 = _tiny_setup(mesh, base, gcfg, None, with_factory=True,
                                 control_cfg=ctrl_cfg)
        ckpt2 = Checkpointer(td)
        s2, meta = ckpt2.restore(s2)
        ckpt2.close()
        s2 = s2.with_mesh_sharding(mesh)
        assert int(meta["step_i"]) == preempt_at_step, meta
        # the open window's accumulation rode the checkpoint
        assert int(np.asarray(s2.control.win_updates)) == \
            preempt_at_step % ctrl_cfg.window, jax.device_get(s2.control)
        s2 = span(s2, sf2, Controller(ctrl_cfg, events=rec_b),
                  preempt_at_step, n_steps)

    fields = ("params", "opt_state", "batch_stats", "ef", "control")
    _assert_bitwise(_snap(clean, fields), _snap(s2, fields),
                    "control_resume state")
    assert int(clean.step) == int(s2.step) == n_steps
    # the decision STREAM is identical: pre-crash events + post-crash
    # events == the uninterrupted run's, field for field
    assert decisions(rec_a) + decisions(rec_b) == decisions(rec_clean), (
        decisions(rec_a) + decisions(rec_b), decisions(rec_clean))
    rungs = [f["rung_to"] for _, f in decisions(rec_clean)]
    dirs = [f["direction"] for _, f in decisions(rec_clean)]
    assert rungs == [1, 2, 2], rungs
    assert dirs == ["down", "down", "hold"], dirs
    return {"decisions": len(rungs), "rungs": rungs,
            "resumed_mid_window": True}


# ----------------------------------------------------------- elastic drills

def drill_elastic_gossip(mesh=None) -> Dict:
    """Heartbeat-gossip failure detection on a simulated clock: silence
    past the timeout => dead (and only then); a restart (higher
    incarnation) => rejoin candidate, never liveness of the dead life."""
    from tpu_compressed_dp.train.elastic import (PeerFailed, PeerGossip,
                                                 write_peer_heartbeat)

    clock = {"t": 1000.0}
    with tempfile.TemporaryDirectory() as td:
        g = PeerGossip(td, 0, 4, peer_timeout_s=5.0, now=lambda: clock["t"])
        for r in (1, 2, 3):
            write_peer_heartbeat(td, r, 0, ts=clock["t"])
        assert g.check() == {}, "fresh peers misread as dead"
        clock["t"] += 4.0                       # rank 2 goes silent here
        for r in (1, 3):
            write_peer_heartbeat(td, r, 1, ts=clock["t"])
        assert g.check() == {}, "silence below the timeout misread as death"
        clock["t"] += 4.0                       # rank 2 now 8s stale (> 5s)
        for r in (1, 3):
            write_peer_heartbeat(td, r, 2, ts=clock["t"])
        try:
            g.raise_if_dead(step=7)
            raise AssertionError("gossip missed the dead peer")
        except PeerFailed as pf:
            assert pf.failed == (2,) and pf.step == 7, pf
        assert g.dead == (2,)
        # the dead life's stale file keeps aging out; a RESTARTED rank 2
        # (higher incarnation) is a rejoin candidate, not a resurrection
        clock["t"] += 1.0
        write_peer_heartbeat(td, 2, 0, incarnation=1, ts=clock["t"])
        assert g.rejoin_candidates() == {2: 1}
        assert g.dead == (2,), "rejoin candidacy must not undeclare death"
        g.readmit(2)
        assert g.dead == () and g.check() == {}
    return {"detected": [2]}


def drill_elastic_remesh(mesh, *, kill_step=2, worker=3, policy="fold",
                         n_steps=5, transport="allgather",
                         mode="simulate") -> Dict:
    """Mid-collective kill of one worker => coordinated abort, W -> W-1
    remesh, bitwise EF fold-or-drop, and the run completes on survivors."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.elastic import ElasticConfig, ElasticRuntime
    from tpu_compressed_dp.utils.chaos import ChaosConfig, maybe_crash_injector

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True,
                             mode=mode, transport=transport,
                             granularity="entiremodel")
    chaos = ChaosConfig.parse(
        f"crash=mid_collective,crash_at_step={kill_step},worker={worker},"
        f"peer_timeout=30")
    crash = maybe_crash_injector(chaos)
    state, step, step_for = _tiny_setup(mesh, comp, None, chaos,
                                        with_factory=True)
    el = ElasticRuntime(ElasticConfig(ef_policy=policy), mesh, chaos=chaos,
                        log=lambda s: None)
    W = int(mesh.shape["data"])
    batch = _batch(n=56)                 # 56 divides both W=8 and W-1=7
    i, killed = 0, False
    while i < n_steps:
        try:
            crash.check(i)
            new_state, m = step(state, batch)
            crash.check(i, phase="mid_collective")
        except Exception as err:
            failure = el.failure_from(err)
            assert failure is not None, f"unconverted fault: {err!r}"
            assert failure.failed == (worker,) and failure.step == kill_step
            # donate=False: the pre-dispatch state is live — the abort
            # discards the in-flight step, exactly the declared semantics
            pre = _snap(state)
            old_ef = jax.device_get(state.ef)
            state = el.handle_failure(state, failure)
            post = _snap(state, ("params", "opt_state", "batch_stats"))
            _assert_bitwise({k: pre[k] for k in post}, post,
                            "elastic_remesh replicated state")
            dropped_sq = 0.0
            for la, lb in zip(jax.tree.leaves(old_ef),
                              jax.tree.leaves(jax.device_get(state.ef))):
                la, lb = np.asarray(la), np.asarray(lb)
                expect = np.delete(la, worker, axis=0)
                if policy == "fold":
                    expect = expect.copy()
                    expect[0] = expect[0] + la[worker]
                else:
                    dropped_sq += float(
                        np.sum(la[worker].astype(np.float64) ** 2))
                assert np.array_equal(expect, lb), \
                    f"EF {policy} migration not bitwise"
            if policy == "drop":
                assert el.dropped_ef_norm == float(np.sqrt(dropped_sq))
            else:
                assert el.dropped_ef_norm == 0.0
            assert el.world == W - 1 and el.parked == (worker,)
            step = step_for(el.mesh)     # owner partition recomputes here
            killed = True
            continue
        state = new_state
        i += 1
    assert killed, "mid-collective kill never fired"
    assert int(state.step) == n_steps
    assert el.remesh_count == 1 and el.peer_failures == 1
    assert set(el.metrics()) == {
        "elastic/peer_failures", "elastic/remesh_count",
        "elastic/dropped_ef_norm", "elastic/remesh_latency_ms",
        "elastic/remesh_ms", "stream/rejoin_bytes"}
    assert el.metrics()["elastic/remesh_ms"] >= el.remesh_latency_ms
    for leaf in jax.tree.leaves(state.ef):
        assert np.asarray(leaf).shape[0] == W - 1
    return {"world": el.world, "dropped_ef_norm": el.dropped_ef_norm}


def drill_elastic_readmit(mesh) -> Dict:
    """Scale-up re-admission: the parked worker rejoins with a zero EF row
    and PowerSGD factors broadcast-re-warmed from survivor row 0, then the
    run trains at full W again."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                 ElasticRuntime, PeerFailed)

    comp = CompressionConfig(method="powersgd", rank=2, error_feedback=True)
    state, step, step_for = _tiny_setup(mesh, comp, None, None,
                                        with_factory=True)
    el = ElasticRuntime(ElasticConfig(), mesh, log=lambda s: None)
    W = int(mesh.shape["data"])
    batch = _batch(n=56)
    state, _ = step(state, batch)        # warm the PowerSGD factors
    state = el.handle_failure(state, PeerFailed((2,), step=1, reason="drill"))
    assert el.world == W - 1 and el.parked == (2,)
    state, _ = step_for(el.mesh)(state, batch)   # one step on survivors
    state = el.readmit(state)
    assert el.world == W and el.parked == ()
    for leaf in jax.tree.leaves(jax.device_get(state.comp)):
        a = np.asarray(leaf)
        assert a.shape[0] == W
        assert np.array_equal(a[-1], a[0]), "comp re-warm not a broadcast"
    for leaf in jax.tree.leaves(jax.device_get(state.ef)):
        assert not np.any(np.asarray(leaf)[-1]), "rejoiner EF row not zero"
    state, _ = step_for(el.mesh)(state, batch)   # trains at full W again
    assert int(state.step) == 3
    return {"world": el.world, "readmits": el.readmit_count}


def drill_elastic_cascade(mesh) -> Dict:
    """``crash=during_remesh``: a SECOND worker dies while survivors are
    inside ``handle_failure``.  The runtime unions the dead set and
    restarts the shrink from the uncommitted mesh — one cascading remesh
    down to ``min_world`` — and a union that would land BELOW
    ``min_world`` raises a clean PeerFailed naming every dead rank
    (mesh untouched) instead of wedging or committing a stale world."""
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                 ElasticRuntime, PeerFailed)
    from tpu_compressed_dp.utils.chaos import ChaosConfig, maybe_crash_injector

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True,
                             mode="simulate", granularity="entiremodel")
    chaos = ChaosConfig.parse(
        "crash=during_remesh,crash_at_step=2,worker=5,peer_timeout=30")
    state, step, step_for = _tiny_setup(mesh, comp, None, None,
                                        with_factory=True)
    W = int(mesh.shape["data"])
    batch = _batch(n=48)                 # 48 divides W=8 and W-2=6

    # arm 1: the union (8 - 2 = 6) lands exactly ON min_world => one
    # cascading shrink commits
    el = ElasticRuntime(ElasticConfig(ef_policy="fold", min_world=W - 2),
                        mesh, chaos=chaos,
                        crash=maybe_crash_injector(chaos), log=lambda s: None)
    state, _ = step(state, batch)
    pre = _snap(state)
    old_ef = jax.device_get(state.ef)
    state = el.handle_failure(state, PeerFailed((3,), step=2, reason="drill"))
    assert el.world == W - 2 and el.parked == (3, 5), (el.world, el.parked)
    assert el.cascade_count == 1 and el.remesh_count == 1
    assert el.peer_failures == 2, el.peer_failures
    post = _snap(state, ("params", "opt_state", "batch_stats"))
    _assert_bitwise({k: pre[k] for k in post}, post,
                    "elastic_cascade replicated state")
    for la, lb in zip(jax.tree.leaves(old_ef),
                      jax.tree.leaves(jax.device_get(state.ef))):
        la, lb = np.asarray(la), np.asarray(lb)
        expect = np.delete(la, [3, 5], axis=0)
        # one fold of the UNION: row0 + sum(lost rows), matching migrate_ef
        expect[0] = expect[0] + la[[3, 5]].sum(axis=0)
        assert np.array_equal(expect, lb), "cascade EF fold not bitwise"
    state, _ = step_for(el.mesh)(state, batch)   # survivors keep training
    assert int(state.step) == 2

    # arm 2: the union would land BELOW min_world => a clean PeerFailed
    # naming both ranks, nothing committed
    chaos2 = ChaosConfig.parse(
        "crash=during_remesh,crash_at_step=2,worker=5,peer_timeout=30")
    state2, _ = _tiny_setup(mesh, comp, None, None)
    el2 = ElasticRuntime(ElasticConfig(ef_policy="fold", min_world=W - 1),
                         mesh, chaos=chaos2,
                         crash=maybe_crash_injector(chaos2),
                         log=lambda s: None)
    try:
        el2.handle_failure(state2, PeerFailed((3,), step=2, reason="drill"))
        raise AssertionError("below-min_world cascade did not raise")
    except PeerFailed as pf:
        assert pf.failed == (3, 5), pf
        assert "min_world" in (pf.reason or ""), pf
    assert el2.world == W and el2.remesh_count == 0, "stale world committed"
    return {"world": el.world, "cascades": el.cascade_count}


def drill_fleet(mesh, *, policy="fold", elastic=True) -> Dict:
    """Three jobs, one 8-device pool (the ISSUE 12 acceptance drill): a
    high-priority arrival EVICTS one job (emergency checkpoint, exit 75)
    and — when jobA is elastic — SHRINKS another through the readmit
    barrier; freed slices bin-pack back, and every job finishes bitwise
    identical to a solo run of the same applied-update/world trajectory.
    ``elastic=False`` runs the rigid cell: no shrink candidate, so the
    planner evicts instead (evict-only preemption path)."""
    from tpu_compressed_dp.fleet import FleetScheduler, JobController, JobSpec
    from tpu_compressed_dp.fleet import state as fstate
    from tpu_compressed_dp.obs.export import EventStream, read_events
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.parallel.mesh import make_data_mesh
    from tpu_compressed_dp.train.elastic import (ElasticConfig,
                                                 ElasticRuntime, PeerFailed)
    from tpu_compressed_dp.utils.checkpoint import Checkpointer
    from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True,
                             mode="simulate", granularity="entiremodel")
    pool = int(mesh.shape["data"])
    devs_all = list(mesh.devices.flat)   # pool id i -> physical device
    # targets chosen so jobA outlives jobC: the freed slices have a live
    # elastic job to grow back into (the readmit half of the shrink)
    targets = {"jobA": 8, "jobB": 5, "jobC": 3}
    batches = {j: [_batch(seed=base + i, n=12) for i in range(targets[j])]
               for j, base in (("jobA", 100), ("jobB", 200), ("jobC", 300))}
    specs = [
        JobSpec("jobA", ("sim",), priority=0,
                min_world=3 if elastic else 4, max_world=4,
                target_updates=targets["jobA"]),
        JobSpec("jobB", ("sim",), priority=0, min_world=3, max_world=3,
                target_updates=targets["jobB"]),
        JobSpec("jobC", ("sim",), priority=10, min_world=4, max_world=4,
                target_updates=targets["jobC"]),
    ]

    class _SimController(JobController):
        """In-process jobs: one training update per poll, shrink/grow
        through the job's own ElasticRuntime, eviction = a real emergency
        checkpoint + PREEMPT_EXIT, resume = restore on the newly granted
        slice.  Pool ids are capacity bookkeeping; each placement maps
        them onto the drill mesh's physical devices."""

        resizable = True

        def __init__(self, root):
            self.root = root
            self.jobs: Dict[str, Dict] = {}
            self.finals: Dict[str, Dict] = {}
            self.traj = []               # (job_id, kind, applied, world)

        def _ckpt_dir(self, job_id):
            return os.path.join(self.root, "ckpt", job_id)

        def start(self, spec, world, devices, *, resume):
            m = make_data_mesh(devices=tuple(devs_all[d] for d in devices))
            state, _, step_for = _tiny_setup(m, comp, None, None,
                                             with_factory=True)
            el = ElasticRuntime(ElasticConfig(ef_policy=policy), m,
                                log=lambda s: None)
            applied = 0
            if resume:
                ck = Checkpointer(self._ckpt_dir(spec.job_id))
                state, meta = ck.restore(state)
                ck.close()
                state = state.with_mesh_sharding(m)
                assert meta.get("emergency") is True, meta
                applied = int(meta["applied"])
            self.jobs[spec.job_id] = {
                "spec": spec, "state": state, "el": el,
                "step": step_for(m), "step_for": step_for,
                "applied": applied}

        def evict(self, job_id):
            j = self.jobs.pop(job_id)
            ck = Checkpointer(self._ckpt_dir(job_id))
            ck.save(j["state"], {"applied": j["applied"], "emergency": True})
            ck.close()
            return PREEMPT_EXIT

        def shrink(self, job_id, world):
            j = self.jobs[job_id]
            el = j["el"]
            self.traj.append((job_id, "shrink", j["applied"], world))
            while el.world > world:
                j["state"] = el.handle_failure(
                    j["state"], PeerFailed((el.world - 1,), step=j["applied"],
                                           reason="fleet preemption"))
            j["step"] = j["step_for"](el.mesh)

        def grow(self, job_id, world, new_devices):
            j = self.jobs[job_id]
            self.traj.append((job_id, "readmit", j["applied"], world))
            j["state"] = j["el"].readmit(j["state"])
            assert j["el"].world == world, (j["el"].world, world)
            j["step"] = j["step_for"](j["el"].mesh)

        def poll(self, job_id):
            j = self.jobs[job_id]
            j["state"], _ = j["step"](j["state"],
                                      batches[job_id][j["applied"]])
            j["applied"] += 1
            if j["applied"] >= targets[job_id]:
                self.finals[job_id] = _snap(j["state"])
                self.jobs.pop(job_id)
                return {"exit_code": 0, "applied_updates": j["applied"]}
            return {"exit_code": None, "applied_updates": j["applied"]}

    with tempfile.TemporaryDirectory() as td:
        ctrl = _SimController(td)
        events = EventStream(fstate.events_path(td))
        now = [0.0]

        def wall():
            now[0] += 1.0
            return now[0]

        sched = FleetScheduler(td, pool, ctrl, events=events, wall=wall,
                               log=lambda s: None)
        sched.submit(specs[0])
        sched.submit(specs[1])
        for t in range(64):
            if t == 3:
                sched.submit(specs[2])   # the high-priority arrival
            sched.tick()
            if sched.idle():
                break
        events.close()

        assert sched.idle(), "fleet never drained"
        for job_id, tgt in targets.items():
            job = sched.jobs[job_id]
            assert job.status == "done" and job.applied == tgt, \
                (job_id, job.status, job.applied)
        c = sched.counters
        want = ({"evictions": 1, "shrinks": 1, "readmits": 1} if elastic
                else {"evictions": 1, "shrinks": 0, "readmits": 0})
        for k, v in want.items():
            assert c[k] == v, (k, c[k], v)
        assert c["preemptions"] == 0 and c["failures"] == 0, c

        # every transition is on the wire: fleet_* JSONL events + per-job
        # Prometheus rollups with the job label
        kinds = {e["kind"] for e in read_events(fstate.events_path(td))}
        need = {"fleet_submit", "fleet_admit", "fleet_place", "fleet_evict",
                "fleet_finish"}
        if elastic:
            need |= {"fleet_shrink", "fleet_readmit"}
        assert need <= kinds, need - kinds
        for job_id in targets:
            prom = open(
                f"{fstate.prom_dir(td)}/{job_id}.fleet.prom").read()
            assert f'job="{job_id}"' in prom and "fleet_world" in prom
        assert "fleet_devices_free" in open(
            f"{fstate.prom_dir(td)}/fleet.prom").read()

        # bitwise acceptance: each job vs a solo run replaying the same
        # applied-update count and (for jobA) the same world trajectory
        traj = {}
        for job_id, kind, applied, world in ctrl.traj:
            traj.setdefault(job_id, []).append((applied, kind, world))
        solo_world = {"jobA": 4, "jobB": 3, "jobC": 4}
        for job_id, tgt in targets.items():
            m = make_data_mesh(
                devices=tuple(devs_all[:solo_world[job_id]]))
            state, _, step_for = _tiny_setup(m, comp, None, None,
                                             with_factory=True)
            el = ElasticRuntime(ElasticConfig(ef_policy=policy), m,
                                log=lambda s: None)
            step = step_for(m)
            for i in range(tgt):
                for at, kind, world in traj.get(job_id, ()):
                    if at != i:
                        continue
                    if kind == "shrink":
                        while el.world > world:
                            state = el.handle_failure(
                                state, PeerFailed((el.world - 1,), step=i,
                                                  reason="fleet preemption"))
                    else:
                        state = el.readmit(state)
                    step = step_for(el.mesh)
                state, _ = step(state, batches[job_id][i])
            _assert_bitwise(_snap(state), ctrl.finals[job_id],
                            f"fleet {job_id} vs solo")

    return {"world": pool, "evictions": c["evictions"],
            "shrinks": c["shrinks"], "readmits": c["readmits"],
            "bitwise": True}


def drill_forensics(mesh) -> Dict:
    """Every injected failure leaves a valid black box and the postmortem
    names the injected root cause — rank AND kind — from the bundles
    alone; a clean run leaves none and the recorder never perturbs the
    trajectory (bitwise with/without).

    Four simulated ranks share one flight dir per case, each failure
    raised through its REAL plane (guard wedge, mid-collective
    ChaosCrash, self-SIGTERM preemption, manifest verification):

      nan          chaos nan/grads worker=1 wedges the guard -> every
                   rank dumps ``guard_exceeded``; verdict names worker 1
      dead_peer    mid_collective kill of worker 2 -> the dying rank
                   dumps ``chaos_crash``, survivors ``peer_failed``;
                   verdict names rank 2
      preempt      a real SIGTERM on rank 0 (chaos crash=preempt through
                   PreemptionHandler) -> verdict ``preempt`` rank 0
      corruption   one flipped payload byte + explicit-step restore ->
                   ``ckpt_corrupt`` bundle; verdict ``corruption``
    """
    import time

    from tpu_compressed_dp.obs.flight import (FlightRecorder, read_bundles,
                                              validate_bundle)
    from tpu_compressed_dp.parallel.dp import CompressionConfig
    from tpu_compressed_dp.train.elastic import PeerFailed
    from tpu_compressed_dp.train.guard import GuardConfig, GuardExceeded
    from tpu_compressed_dp.utils.chaos import (ChaosConfig, ChaosCrash,
                                               CrashInjector)
    from tpu_compressed_dp.utils.checkpoint import (CheckpointCorrupt,
                                                    Checkpointer)
    from tpu_compressed_dp.utils.resilience import (Preempted,
                                                    PreemptionHandler)

    try:
        from tools.postmortem import classify, merge_timeline
    except ImportError:
        from postmortem import classify, merge_timeline

    comp = CompressionConfig(method="topk", ratio=0.25, error_feedback=True)
    ranks = 4

    def recorders(directory, chaos=None):
        out = []
        for r in range(ranks):
            fl = FlightRecorder(rank=r, capacity=32, directory=directory,
                                meta={"drill": "forensics"})
            if chaos is not None:
                fl.note_chaos(chaos)
            out.append(fl)
        return out

    def check_bundles(directory, expect_ranks):
        bundles = read_bundles(directory)
        assert sorted(bundles) == sorted(expect_ranks), (
            sorted(bundles), sorted(expect_ranks))
        for r, b in bundles.items():
            problems = validate_bundle(b)
            assert not problems, (r, problems)
        return bundles

    verdicts = {}

    # --- nan: chaos nan/grads on worker 1 wedges the guard everywhere
    gcfg = GuardConfig(loss_scaling=False, max_consecutive_skips=2)
    chaos = ChaosConfig(kind="nan", target="grads", every=1, worker=1)
    state, step = _tiny_setup(mesh, comp, gcfg, chaos)
    batch = _batch()
    for i in range(4):
        state, metrics = step(state, batch)
    m = jax.device_get(metrics)
    with tempfile.TemporaryDirectory() as td:
        for fl in recorders(td, chaos):
            fl.note_step(3, m)
            try:
                from tpu_compressed_dp.train.guard import check_guard_metrics
                check_guard_metrics(m, gcfg, flight=fl)
                raise AssertionError("guard did not wedge")
            except GuardExceeded:
                pass
        bundles = check_bundles(td, range(ranks))
        assert all(b["reason"] == "guard_exceeded"
                   for b in bundles.values()), bundles
        v = classify(bundles)
        assert (v["kind"], v["rank"]) == ("nan", 1), v
        assert merge_timeline(bundles), "empty merged timeline"
        verdicts["nan"] = v

    # --- dead_peer: mid-collective kill of worker 2; survivors raise
    # PeerFailed naming it, the dying rank's own injector self-reports
    chaos = ChaosConfig(crash_at_step=1, crash_mode="mid_collective",
                        worker=2)
    with tempfile.TemporaryDirectory() as td:
        fls = recorders(td, chaos)
        crash = CrashInjector(1, mode="mid_collective", worker=2)
        crash.flight = fls[2]
        try:
            crash.check(1, phase="mid_collective")
            raise AssertionError("injector did not fire")
        except ChaosCrash as err:
            fls[2].observe(err)
        for r in (0, 1, 3):
            fls[r].observe(PeerFailed((2,), step=1,
                                      reason="gossip heartbeat stale"))
        bundles = check_bundles(td, range(ranks))
        assert bundles[2]["reason"] == "chaos_crash", bundles[2]
        v = classify(bundles)
        assert (v["kind"], v["rank"]) == ("dead_peer", 2), v
        verdicts["dead_peer"] = v

    # --- preempt: a REAL self-SIGTERM on rank 0, observed through the
    # handler; peers raise PeerFailed — preempt must win the priority
    chaos = ChaosConfig(crash_at_step=0, crash_mode="preempt")
    with tempfile.TemporaryDirectory() as td:
        fls = recorders(td, chaos)
        crash = CrashInjector(0, mode="preempt")
        crash.flight = fls[0]
        handler = PreemptionHandler(log=lambda s: None).install()
        assert handler.installed, "drill must run on the main thread"
        try:
            crash.check(0)          # self-SIGTERM, no raise
            for _ in range(1000):   # signal lands within a few bytecodes
                if handler.triggered:
                    break
                time.sleep(0.001)
            handler.check(0)
            raise AssertionError("preempt never fired")
        except Preempted as err:
            fls[0].observe(err)
        finally:
            handler.uninstall()
        for r in (1, 2, 3):
            fls[r].observe(PeerFailed((0,), step=0, reason="peer exited"))
        bundles = check_bundles(td, range(ranks))
        assert bundles[0]["reason"] == "preempt", bundles[0]
        v = classify(bundles)
        assert (v["kind"], v["rank"]) == ("preempt", 0), v
        verdicts["preempt"] = v

    # --- corruption: flipped payload byte + explicit-step restore — the
    # manifest digest trips and the Checkpointer dumps before raising
    state, step = _tiny_setup(mesh, comp, GuardConfig(loss_scaling=False),
                              None)
    with tempfile.TemporaryDirectory() as td:
        ck_dir, fl_dir = os.path.join(td, "ck"), os.path.join(td, "fl")
        fls = recorders(fl_dir)
        ckpt = Checkpointer(ck_dir, flight=fls[0])
        state, _ = step(state, batch)
        ckpt.save(state, {"step_i": 1})
        ckpt.close()
        _flip_byte_in_step(ck_dir, 1)
        # any structure-matching target works; the restore raises on the
        # manifest digest before it rebuilds state
        ckpt2 = Checkpointer(ck_dir, flight=fls[0])
        try:
            ckpt2.restore(state, step=1)
            raise AssertionError("corrupt restore did not raise")
        except CheckpointCorrupt:
            pass
        finally:
            ckpt2.close()
        bundles = check_bundles(fl_dir, [0])
        assert bundles[0]["reason"] == "ckpt_corrupt", bundles[0]
        v = classify(bundles)
        assert v["kind"] == "corruption", v
        verdicts["corruption"] = v

    # --- control: a clean run dumps NOTHING, and recording is
    # trajectory-neutral (bitwise with vs without a recorder).  Same
    # compiled step + same start state for both trajectories — the only
    # difference is the host-side recorder, which is the claim under test.
    with tempfile.TemporaryDirectory() as td:
        plain = observed = state
        fls = recorders(td)
        for i in range(3):
            plain, _ = step(plain, batch)
            observed, m2 = step(observed, batch)
            for fl in fls:
                fl.note_step(i, jax.device_get(m2))
        for fl in fls:
            fl.publish()  # phase profiles are NOT bundles
        assert read_bundles(td) == {}, "clean run left blackbox bundles"
        _assert_bitwise(_snap(plain), _snap(observed), "forensics control")
    return {"verdicts": {k: v["kind"] for k, v in verdicts.items()},
            "ranks": {k: v["rank"] for k, v in verdicts.items()},
            "clean_bundles": 0, "bitwise": True}


# -------------------------------------------------------------------- main

QUICK = ["skip_consistency", "loss_scale", "max_skips", "crash_recovery",
         "elastic_gossip", "elastic_remesh", "ckpt_preempt", "ckpt_corrupt",
         "stream_corrupt", "control_resume", "fleet", "forensics"]
FULL = QUICK + ["comp_hold", "ef_identity", "poison_control",
                "skip_matrix", "ef_identity_sharded",
                "elastic_readmit", "elastic_cascade", "elastic_matrix",
                "fleet_matrix"]


def expand_rows(names) -> list:
    """The concrete drill rows a name list runs — matrix groups expand to
    their cells, everything else maps 1:1.  ``--list`` prints these and the
    tier-1 registration test (tests/test_chaos_drill.py) keys off them."""
    rows = []
    for name in names:
        if name == "skip_matrix":
            rows += [f"skip[{kind},{target},w{worker}]"
                     for kind in ("nan", "inf")
                     for target in ("grads", "loss")
                     for worker in (0, 7)]
        elif name == "elastic_matrix":
            rows += [f"elastic[{policy},w{worker},s{kill_step}]"
                     for policy in ("fold", "drop")
                     for worker in (0, 7)
                     for kill_step in (0, 3)]
            rows.append("elastic[sharded-wire]")
        elif name == "fleet_matrix":
            rows += ["fleet[fold]", "fleet[drop]", "fleet[rigid]"]
        else:
            rows.append(name)
    return rows


def run_drills(names, mesh=None) -> Dict[str, Dict]:
    mesh = mesh or _mesh()
    results = {}
    for name in names:
        if name == "skip_matrix":
            # the full kind x target x worker cross
            for kind in ("nan", "inf"):
                for target in ("grads", "loss"):
                    for worker in (0, 7):
                        key = f"skip[{kind},{target},w{worker}]"
                        results[key] = drill_skip_consistency(
                            mesh, kind=kind, target=target, worker=worker)
                        print(f"PASS {key}")
            continue
        if name == "elastic_matrix":
            # kill-step x worker x EF-policy cross, plus the wire+sharded
            # variant (owner partition recomputed over W-1)
            for policy in ("fold", "drop"):
                for worker in (0, 7):
                    for kill_step in (0, 3):
                        key = f"elastic[{policy},w{worker},s{kill_step}]"
                        results[key] = drill_elastic_remesh(
                            mesh, kill_step=kill_step, worker=worker,
                            policy=policy)
                        print(f"PASS {key}")
            key = "elastic[sharded-wire]"
            results[key] = drill_elastic_remesh(
                mesh, transport="sharded", mode="wire", worker=5,
                policy="fold")
            print(f"PASS {key}")
            continue
        if name == "fleet_matrix":
            # EF-policy cells through the shrink/readmit barrier, plus the
            # rigid cell (no shrink candidate => evict-only preemption)
            for key, kwargs in (("fleet[fold]", {"policy": "fold"}),
                                ("fleet[drop]", {"policy": "drop"}),
                                ("fleet[rigid]", {"elastic": False})):
                results[key] = drill_fleet(mesh, **kwargs)
                print(f"PASS {key}")
            continue
        if name == "ef_identity_sharded":
            results[name] = drill_ef_identity(mesh, transport="sharded",
                                              mode="wire")
        else:
            results[name] = globals()[f"drill_{name}"](mesh)
        print(f"PASS {name}")
    return results


def main(argv=None) -> int:
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--quick", action="store_true",
                   help="tier-1 smoke subset (skip_consistency, loss_scale, "
                        "max_skips, crash_recovery, elastic_gossip, "
                        "elastic_remesh, ckpt_preempt, ckpt_corrupt, "
                        "stream_corrupt, control_resume, fleet, forensics)")
    p.add_argument("--drill", action="append", default=None,
                   help="run only the named drill(s)")
    p.add_argument("--list", action="store_true",
                   help="print the quick/slow drill-row matrix (matrix "
                        "groups expanded to their cells) and exit")
    args = p.parse_args(argv)
    if args.list:
        # CI discovery surface: one row per concrete drill, tier-tagged.
        # tests/test_chaos_drill.py asserts every quick row is registered
        # here and collectible (a drill function exists for it).
        slow_only = [n for n in FULL if n not in QUICK]
        print("quick:")
        for row in expand_rows(QUICK):
            print(f"  {row}")
        print("slow:")
        for row in expand_rows(slow_only):
            print(f"  {row}")
        return 0
    names = args.drill or (QUICK if args.quick else FULL)
    run_drills(names)
    print(f"chaos drill: {len(names)} drill group(s) passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
