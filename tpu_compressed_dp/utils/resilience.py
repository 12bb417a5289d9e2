"""Failure detection and crash recovery.

The reference has neither (SURVEY.md §5): membership is fixed at launch,
crashes print a traceback (`train_imagenet_nv.py:704-716`), and spot-instance
recovery is "relaunch by hand" (`train.py:49`).  Net-new here:

  * ``Heartbeat`` — a background thread that writes ``{ts, step, payload}``
    to a JSON file at an interval; an external watchdog (or another host)
    reads it with :func:`read_heartbeat` / :func:`is_stale` to detect hung or
    dead workers.  Pure files, no control plane to operate.
  * ``run_with_recovery`` — wraps an epoch-style loop: on an exception it
    restores the latest checkpoint and replays from there, up to
    ``max_retries`` consecutive failures (progress between checkpoints
    resets the budget).  With Orbax checkpoints carrying the full
    ``TrainState`` (EF residual and RNG included), a replayed epoch is
    bitwise the run that would have happened without the crash.
  * ``PreemptionHandler`` — SIGTERM/SIGINT set a step-granularity flag; the
    harness loops poll it via :meth:`PreemptionHandler.check`, which raises
    :class:`Preempted` so the harness can drain any in-flight async
    checkpoint write, cut an emergency save, and exit with
    :data:`PREEMPT_EXIT` — the code ``tools/watchdog.py --relaunch``
    respawns immediately on (no backoff, no retry-budget burn).
  * ``spawn_supervised`` — the supervisor-side child launch shared by the
    watchdog and the fleet scheduler: composes the incarnation
    (``TCDP_RESTART_COUNT``) and elastic-rejoin (``TCDP_ELASTIC_DIR``,
    ``TCDP_RENDEZVOUS_*``) environment over the operator's own without
    clobbering it.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

__all__ = ["Heartbeat", "read_heartbeat", "is_stale", "check_heartbeat",
           "run_with_recovery", "Preempted", "PreemptionHandler",
           "PREEMPT_EXIT", "spawn_supervised"]

#: exit code of a preempted-and-checkpointed harness (EX_TEMPFAIL: "try
#: again") — distinct from both clean exit (0) and crash (1), so the
#: watchdog can relaunch immediately without burning backoff or budget
PREEMPT_EXIT = 75


class Preempted(Exception):
    """The preemption flag was observed at a step boundary.  An ``Exception``
    (not ``BaseException``) so ``run_train_epoch``'s handler still attaches
    the live ``elastic_state`` on the way out — but ``run_with_recovery``
    re-raises it explicitly: a preemption must trigger the emergency-save
    path, never a restore-and-replay retry."""

    def __init__(self, msg: str, *, step: Optional[int] = None,
                 signum: Optional[int] = None):
        super().__init__(msg)
        self.step = step
        self.signum = signum


class PreemptionHandler:
    """Signal-flag bridge between the platform's preemption notice and the
    step loop.

    >>> handler = PreemptionHandler().install()
    >>> handler.check(step)     # raises Preempted once SIGTERM/SIGINT landed
    >>> handler.uninstall()     # ALWAYS, in finally: restore prior handlers

    The Python-level signal handler only sets a :class:`threading.Event` —
    async-signal-safe, no I/O, no raise from arbitrary bytecode — and the
    loop converts it to :class:`Preempted` at the next step boundary, so the
    interrupted state is always a consistent between-steps ``TrainState``.

    ``signal.signal`` only works on the main thread; off it (a harness
    driven from a test runner's worker thread) ``install`` degrades to an
    inert handler (``installed`` False, ``check`` never raises) rather than
    crashing the run.
    """

    def __init__(self, *, signals=(signal.SIGTERM, signal.SIGINT),
                 log: Callable[[str], None] = print):
        self.signals = tuple(signals)
        self.log = log
        self.installed = False
        self._event = threading.Event()
        self.signum: Optional[int] = None
        self._prev: Dict[int, Any] = {}

    def install(self) -> "PreemptionHandler":
        try:
            for s in self.signals:
                self._prev[s] = signal.signal(s, self._on_signal)
            self.installed = True
        except ValueError:
            # not on the main thread: leave the process default in place
            self._prev.clear()
            self.installed = False
        return self

    def _on_signal(self, signum, frame) -> None:
        self.signum = signum
        self._event.set()

    @property
    def triggered(self) -> bool:
        return self._event.is_set()

    def check(self, step: Optional[int] = None) -> None:
        """Raise :class:`Preempted` if the flag is set (call once per step)."""
        if self._event.is_set():
            try:
                name = signal.Signals(self.signum).name
            except (ValueError, TypeError):
                name = str(self.signum)
            self.log(f"preempt: {name} received; stopping at step {step}")
            raise Preempted(f"preempted by {name}", step=step,
                            signum=self.signum)

    def uninstall(self) -> None:
        """Restore the previous handlers (mandatory in ``finally`` — a leaked
        handler would swallow the next process's Ctrl-C)."""
        if not self.installed:
            return
        for s, prev in self._prev.items():
            try:
                signal.signal(s, prev)
            except (ValueError, TypeError, OSError):
                pass
        self._prev.clear()
        self.installed = False


class Heartbeat:
    """Background liveness file writer.

    >>> hb = Heartbeat(path, interval_s=10)
    >>> hb.update(step=123)   # cheap; call from the train loop
    >>> hb.stop()

    Every record carries an ``incarnation`` — monotonically increasing
    across process restarts, seeded from ``TCDP_RESTART_COUNT`` (exported
    by ``tools/watchdog.py --relaunch``).  A restarted worker's first
    heartbeat therefore carries a HIGHER incarnation than any file its
    previous life left behind, so elastic peers can tell "this rank came
    back" from "this is the stale file of a dead prior life".
    """

    def __init__(self, path: str, interval_s: float = 10.0,
                 payload: Optional[Dict[str, Any]] = None,
                 incarnation: Optional[int] = None):
        self.path = path
        self.interval_s = interval_s
        self.payload = dict(payload or {})
        if incarnation is None:
            incarnation = int(os.environ.get("TCDP_RESTART_COUNT", "0") or 0)
        self.incarnation = int(incarnation)
        self._step = 0
        # update() runs on the train loop thread while _write() iterates the
        # payload on the writer thread: unsynchronised, json.dump raises
        # "dict changed size during iteration" intermittently (and the
        # writer thread died silently, turning a live worker into a
        # stale-heartbeat false positive).  The lock guards the mutation;
        # _write snapshots under it and serialises/writes outside it, so
        # the train loop never blocks on disk.
        self._lock = threading.Lock()
        #: first exception the writer thread hit (None = healthy); surfaced
        #: rather than swallowed so tests and watchdog wrappers can assert
        self.last_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._write()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def update(self, step: int, **payload) -> None:
        with self._lock:
            self._step = int(step)
            self.payload.update(payload)

    def _write(self) -> None:
        with self._lock:
            rec = {"ts": time.time(), "step": self._step,
                   "incarnation": self.incarnation, **self.payload}
        # pid-unique tmp name: two lives of a relaunched worker racing on
        # the same heartbeat path must not interleave writes into one tmp
        # file (the os.replace itself is atomic either way)
        tmp = f"{self.path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, self.path)  # atomic: readers never see partial JSON

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self._write()
            except Exception as e:  # e.g. disk full: record, keep beating
                with self._lock:
                    self.last_error = e

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=self.interval_s + 1)
        self._write()


def spawn_supervised(cmd: Sequence[str], *,
                     restart_count: int,
                     elastic_dir: Optional[str] = None,
                     env: Optional[Dict[str, str]] = None,
                     extra_env: Optional[Dict[str, str]] = None,
                     popen: Callable[..., "subprocess.Popen"] = subprocess.Popen,
                     log: Callable[[str], None] = print):
    """Launch one supervised child with the incarnation/rejoin environment
    — the spawn path shared by ``tools/watchdog.py --relaunch`` and the
    fleet's subprocess controller (``tools/fleet.py``).

    The child environment is a COPY of ``env`` (default ``os.environ``)
    with only the supervision keys layered on top — an operator-set
    variable is never clobbered unless the supervisor owns it:

    * ``TCDP_RESTART_COUNT`` — supervisor-owned, always written: the
      child Heartbeat's incarnation must be strictly larger each respawn.
    * ``TCDP_ELASTIC_DIR`` + (when the rendezvous directory holds a
      committed world epoch) ``TCDP_RENDEZVOUS_EPOCH``/``..._ADDR`` —
      only with ``elastic_dir``: the rejoin hint that lands a restarted
      host in the RUNNING world's join barrier
      (``train/rendezvous.maybe_rejoin_from_env``) instead of forming a
      fresh one.  Without a committed epoch the rendezvous keys are left
      exactly as the operator set them.
    * ``extra_env`` — caller-owned additions (the fleet's ``TCDP_JOB_ID``
      and world/device assignment); applied last, so they win.

    ``popen`` is injectable so unit tests capture the composed
    environment without forking (tests/test_fleet.py)."""
    child_env = dict(os.environ if env is None else env)
    child_env["TCDP_RESTART_COUNT"] = str(int(restart_count))
    if elastic_dir:
        from tpu_compressed_dp.train.rendezvous import (DIR_ENV, export_env,
                                                        read_epoch)
        child_env[DIR_ENV] = elastic_dir
        rec = read_epoch(elastic_dir)
        if rec is not None:
            export_env(child_env, rec)
            log(f"spawn: rejoin hint: world epoch {rec['epoch']} "
                f"@ {rec.get('address')}")
    if extra_env:
        child_env.update({str(k): str(v) for k, v in extra_env.items()})
    return popen(list(cmd), env=child_env)


def read_heartbeat(path: str) -> Optional[Dict[str, Any]]:
    """Parse a heartbeat file; ``None`` on ANY unreadable content.

    The writer's atomic-replace means a well-behaved filesystem never
    shows a torn record, but elastic gossip reads peers' files over shared
    storage where torn/truncated reads DO happen (NFS close-to-open,
    object-store gateways) — so every decode failure (truncated JSON,
    garbage bytes, a non-object payload) degrades to "no heartbeat", never
    an exception out of the failure detector."""
    try:
        with open(path, "rb") as f:
            rec = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError):
        # ValueError covers json.JSONDecodeError and UnicodeDecodeError
        return None
    return rec if isinstance(rec, dict) else None


def is_stale(path: str, max_age_s: float) -> bool:
    """True when the heartbeat is missing, unreadable, lacks a numeric
    ``ts``, or is older than ``max_age_s``."""
    hb = read_heartbeat(path)
    if hb is None:
        return True
    ts = hb.get("ts")
    if not isinstance(ts, (int, float)) or isinstance(ts, bool):
        return True
    return (time.time() - ts) > max_age_s


def check_heartbeat(path: str, *, max_age_s: float = 60.0,
                    max_wedge_steps: Optional[int] = None,
                    min_steps_per_sec: Optional[float] = None,
                    max_step_p95_ms: Optional[float] = None,
                    max_ckpt_age_s: Optional[float] = None,
                    max_stream_lag_s: Optional[float] = None,
                    max_straggler_skew_s: Optional[float] = None,
                    now: Optional[float] = None,
                    hb: Optional[Dict[str, Any]] = None) -> list:
    """Health-check a heartbeat file; returns a list of problem strings
    (empty = healthy) — the check-only half of the ROADMAP watchdog,
    consumed by ``tools/watchdog.py --check``.

    Three independent failure modes, each reading a different part of the
    payload the harnesses write:

    * **dead/stale** — file missing, unreadable, or ``ts`` older than
      ``max_age_s``: the writer thread (and so the process) is gone.
    * **wedged** — the process is alive and ``step`` advances, but
      ``last_good_step`` (the step-guard's applied-update watermark) has
      fallen more than ``max_wedge_steps`` behind: every step is being
      vetoed — exactly the wedge a liveness check alone cannot see.
    * **stalled** — the telemetry snapshot's ``steps_per_sec`` (from the
      :class:`~tpu_compressed_dp.obs.trace.StepTimeline` window) has
      dropped below ``min_steps_per_sec``: alive, applying updates, but
      crawling (data stall, thrashing input pipeline).
    * **slow tail** — the telemetry snapshot's ``step_p95_ms`` (the
      timeline window's tail latency) exceeds ``max_step_p95_ms``: the
      MEAN rate still looks fine but the tail regressed — the run's step
      budget enforced live, and the first symptom of a degrading
      interconnect or a periodic stall the mean averages away.
    * **checkpoint-stale** — ``ckpt_age_s`` (written from
      ``Checkpointer.heartbeat_fields``) plus the heartbeat's own age
      exceeds ``max_ckpt_age_s``: training advances but nothing durable is
      landing — a wedged async writer or a full/readonly checkpoint disk,
      the failure a crash would silently amplify into lost work.
    * **stream-stale** — ``stream_lag_s`` (written from
      ``StreamWriter.heartbeat_fields``, or by ``tools/stream_serve.py``
      on the consumer side) plus the heartbeat's own age exceeds
      ``max_stream_lag_s``: the delta state stream has stopped advancing —
      warm rejoin and the model-push channel are serving stale parameters.
    * **straggler** — ``straggler_skew_s`` (the flight recorder's live
      cross-rank skew of the mean host step time, from
      ``FlightRecorder.publish``) exceeds ``max_straggler_skew_s``: one
      rank is pacing every collective for the whole world — the failure
      mode worth catching BEFORE it becomes a peer-timeout remesh.

    Wedge/stall/checkpoint checks are skipped when their payload fields are
    absent (guard/telemetry/checkpointing off) — absence of optional
    telemetry is not a fault.
    Pass ``hb`` (an already-parsed record) to check a single consistent
    read — callers that also inspect the payload should read once and
    share it, not race a concurrent ``os.replace`` between two reads.
    """
    now = time.time() if now is None else now
    if hb is None:
        hb = read_heartbeat(path)
    if hb is None:
        return [f"heartbeat missing or unreadable: {path}"]
    problems = []
    age = now - float(hb.get("ts", 0.0))
    if age > max_age_s:
        problems.append(
            f"stale: heartbeat is {age:.1f}s old (> {max_age_s:g}s) — "
            "worker dead or hung")
    if max_wedge_steps is not None and "last_good_step" in hb:
        lag = int(hb.get("step", 0)) - int(hb["last_good_step"])
        if lag > max_wedge_steps:
            problems.append(
                f"wedged: last applied update is {lag} steps behind the "
                f"attempt counter (> {max_wedge_steps}) — every step is "
                "being skipped")
    tele = hb.get("telemetry") or {}
    if (min_steps_per_sec is not None
            and tele.get("steps_per_sec") is not None
            and float(tele["steps_per_sec"]) < min_steps_per_sec):
        problems.append(
            f"stalled: step rate {float(tele['steps_per_sec']):.4g}/s "
            f"below the {min_steps_per_sec:g}/s floor")
    if (max_step_p95_ms is not None
            and tele.get("step_p95_ms") is not None
            and float(tele["step_p95_ms"]) > max_step_p95_ms):
        problems.append(
            f"slow tail: p95 step time {float(tele['step_p95_ms']):.4g}ms "
            f"exceeds the {max_step_p95_ms:g}ms bound — the tail regressed "
            "past the run's modeled/pinned budget")
    if max_ckpt_age_s is not None and hb.get("ckpt_age_s") is not None:
        # the payload's age was computed when the heartbeat was written;
        # add the heartbeat's own age so a dying writer cannot freeze the
        # checkpoint clock at a healthy-looking value
        ckpt_age = float(hb["ckpt_age_s"]) + max(age, 0.0)
        if ckpt_age > max_ckpt_age_s:
            problems.append(
                f"checkpoint stale: last durable save {ckpt_age:.1f}s ago "
                f"(> {max_ckpt_age_s:g}s, last_ckpt_step="
                f"{hb.get('last_ckpt_step')}) — a crash now loses that much "
                "work")
    if max_stream_lag_s is not None and hb.get("stream_lag_s") is not None:
        # same heartbeat-age correction as the checkpoint clock: a dying
        # writer must not freeze the stream lag at a healthy value
        lag = float(hb["stream_lag_s"]) + max(age, 0.0)
        if lag > max_stream_lag_s:
            problems.append(
                f"stream stale: last delta segment {lag:.1f}s ago "
                f"(> {max_stream_lag_s:g}s, stream_last_step="
                f"{hb.get('stream_last_step')}) — warm rejoin and serving "
                "consumers are falling behind the run")
    skew = hb.get("straggler_skew_s", tele.get("straggler_skew_s"))
    if max_straggler_skew_s is not None and skew is not None:
        if float(skew) > max_straggler_skew_s:
            rank = hb.get("straggler_rank")
            problems.append(
                f"straggler: cross-rank step-time skew {float(skew):.4g}s "
                f"exceeds the {max_straggler_skew_s:g}s bound"
                + (f" (slowest rank {int(rank)})"
                   if isinstance(rank, (int, float)) and rank >= 0 else "")
                + " — one rank is pacing the whole world's collectives")
    return problems


def run_with_recovery(
    epoch_fn: Callable[[Any, int], Any],
    state: Any,
    epochs: int,
    *,
    checkpointer=None,
    start_epoch: int = 0,
    max_retries: int = 3,
    on_restore: Optional[Callable[[Any], Any]] = None,
    flight=None,
) -> Tuple[Any, Dict[str, int]]:
    """Run ``state = epoch_fn(state, epoch)`` for each epoch, restoring from
    ``checkpointer`` (latest step) and retrying after exceptions.

    ``on_restore`` re-places a restored state onto the mesh (e.g.
    ``TrainState.with_mesh_sharding`` / ``place_lm_state``).  Epoch indices
    re-run after a restore are derived from the checkpoint meta's ``epoch``
    (saved by the harnesses), falling back to restarting the failed epoch.
    Returns ``(state, {'failures': n, 'restores': m})``.

    :class:`Preempted` is re-raised untouched (the harness's emergency-save
    path owns it, not the retry budget).  Restore-time *corruption* never
    consumes a retry either: ``Checkpointer.restore`` walks back to the
    newest verifiable checkpoint internally, so a torn latest write costs a
    rollback (accounted in ``ckpt/rollback_steps``), not a failure.

    ``flight`` (a :class:`~tpu_compressed_dp.obs.flight.FlightRecorder`)
    dumps a blackbox bundle when the retry budget is exhausted — the
    TERMINAL error, the one the process dies with; per-retry failures are
    recoverable by construction and stay out of the shared dir.
    """
    failures = restores = 0
    epoch = start_epoch
    while epoch < epochs:
        try:
            state = epoch_fn(state, epoch)
            failures = 0  # progress resets the retry budget
            epoch += 1
        except (KeyboardInterrupt, SystemExit, Preempted):
            raise
        except Exception as train_err:
            failures += 1
            if checkpointer is None or failures > max_retries:
                if flight is not None:
                    flight.observe(train_err, retries=failures - 1,
                                   terminal=True)
                raise
            try:
                state, meta = checkpointer.restore(state)
            except FileNotFoundError:
                # crashed before the FIRST checkpoint existed: there is
                # nothing to replay from, and letting the restore's
                # FileNotFoundError propagate would mask the actual
                # training failure the operator needs to see
                if flight is not None:
                    flight.observe(train_err, retries=failures - 1,
                                   terminal=True)
                raise train_err
            if on_restore is not None:
                state = on_restore(state)
            restores += 1
            epoch = int(meta.get("epoch", epoch - 1)) + 1
    return state, {"failures": failures, "restores": restores}
