#!/usr/bin/env python
"""Heartbeat watchdog — check and (now) relaunch halves of the ROADMAP
watchdog item.

``--check`` reads the liveness file a harness writes under ``--heartbeat``
(payload: ``ts``, ``step``, ``last_good_step``, and the telemetry snapshot
the observability layer added — step rate, p95 step latency) and exits
nonzero when the run is unhealthy, so a cron job / systemd timer /
supervisor can alert or relaunch:

  exit 0  healthy
  exit 1  unhealthy (stale / wedged / stalled; reasons on stdout)
  exit 2  heartbeat missing or unreadable

Checks (see :func:`tpu_compressed_dp.utils.resilience.check_heartbeat`):

  * **stale** — ``ts`` older than ``--max_age``: process dead or hung.
  * **wedged** — ``step - last_good_step > --max_wedge``: alive but every
    step is being vetoed by the step guard (the failure liveness alone
    cannot see; pair with ``--guard``).
  * **stalled** — telemetry ``steps_per_sec`` below ``--min_step_rate``:
    alive and applying updates, but crawling.
  * **slow tail** — telemetry ``step_p95_ms`` above ``--max_step_p95_ms``:
    the mean rate still passes but the tail latency regressed past the
    run's budget (set it from the run's own steady ``step_p95_ms`` x 1.1).
  * **checkpoint-stale** — heartbeat ``ckpt_age_s`` (plus the heartbeat's
    own age) exceeds ``--max_ckpt_age``: the run is making progress it
    could not recover — a crash now loses that much work.
  * **stream-stale** — heartbeat ``stream_lag_s`` (plus the heartbeat's
    own age) exceeds ``--max_stream_lag``: the delta state stream stopped
    advancing — warm rejoin and serving consumers are going stale.
  * **straggler** — heartbeat ``straggler_skew_s`` (the flight recorder's
    live cross-rank step-time skew) exceeds ``--max_straggler_skew``: one
    rank is pacing the whole world's collectives.

``--relaunch`` is the acting half: it supervises the training command given
after ``--``, runs the SAME health check every ``--interval`` seconds
(after a ``--grace`` warm-up so the first heartbeat can appear), and on an
unhealthy/missing verdict kills the child (if still alive — a wedged run is
alive but useless), waits out a capped exponential backoff, and respawns.
A healthy check resets the backoff; a clean child exit (rc 0) ends
supervision; after ``--max_relaunches`` restarts it gives up with the
child's last exit code (or 1).  The restart budget is CONSECUTIVE — any
healthy check refills it — so a long-lived run that crashes once a day is
not eventually abandoned.

A child that exits ``PREEMPT_EXIT`` (75) respawns immediately — no
backoff, no budget burn — but that free pass is rate-capped: more than
``--max_preempts`` preempt exits within ``--preempt_window`` seconds is a
preempt STORM (a scheduler or broken environment preempting in a tight
loop) and is handled like any unhealthy verdict.

With ``--elastic_dir`` the relaunch is ELASTIC-aware: every spawn exports
``TCDP_RESTART_COUNT`` (the child's heartbeat incarnation) plus, when the
rendezvous directory holds a committed world epoch, the epoch and
coordinator address (``TCDP_RENDEZVOUS_EPOCH``/``TCDP_RENDEZVOUS_ADDR``)
— so a restarted host rejoins the RUNNING world's readmit barrier instead
of forming a fresh one (train/rendezvous.py).  A child that parks on its
join deadline exits nonzero; the watchdog's backoff is the retry loop.

Usage::

    python tools/watchdog.py --check --heartbeat /path/hb.json
    python tools/watchdog.py --check --heartbeat hb.json \\
        --max_age 120 --max_wedge 200 --min_step_rate 0.01
    python tools/watchdog.py --relaunch --heartbeat hb.json \\
        --interval 30 --grace 120 --max_relaunches 5 -- \\
        python -m tpu_compressed_dp.harness.dawn --synthetic --guard \\
            --heartbeat hb.json
"""

from __future__ import annotations

import argparse
import collections
import os
import subprocess
import sys
import time
from typing import Callable, List, Optional

from tpu_compressed_dp.utils.resilience import (PREEMPT_EXIT, check_heartbeat,
                                                read_heartbeat,
                                                spawn_supervised)


def run_check(args) -> int:
    # single read: passing the parsed record into check_heartbeat keeps the
    # verdict and the printed payload consistent even if the harness's
    # atomic os.replace lands mid-check
    hb = read_heartbeat(args.heartbeat)
    if hb is None:
        print(f"watchdog: MISSING {args.heartbeat}")
        return 2
    problems = check_heartbeat(
        args.heartbeat,
        max_age_s=args.max_age,
        max_wedge_steps=args.max_wedge,
        min_steps_per_sec=args.min_step_rate,
        max_step_p95_ms=args.max_step_p95_ms,
        max_ckpt_age_s=args.max_ckpt_age,
        max_stream_lag_s=args.max_stream_lag,
        max_straggler_skew_s=args.max_straggler_skew,
        hb=hb,
    )
    if problems:
        for pr in problems:
            print(f"watchdog: UNHEALTHY: {pr}")
        return 1
    tele = hb.get("telemetry") or {}
    rate = tele.get("steps_per_sec")
    print("watchdog: healthy "
          f"(step={hb.get('step')}, last_good_step={hb.get('last_good_step')}"
          + (f", {rate:.3g} steps/s" if isinstance(rate, (int, float)) else "")
          + ")")
    return 0


def kill_child(child, term_timeout_s: float = 10.0) -> None:
    """Terminate a (possibly wedged) child: SIGTERM, bounded wait, SIGKILL.
    A no-op when the child already exited."""
    if child.poll() is not None:
        return
    child.terminate()
    try:
        child.wait(timeout=term_timeout_s)
    except Exception:
        child.kill()
        child.wait()


def supervise(spawn: Callable[[], "subprocess.Popen"],
              check: Callable[[], int],
              *,
              interval_s: float,
              grace_s: float,
              max_relaunches: int,
              backoff_s: float = 5.0,
              backoff_cap_s: float = 300.0,
              sleep: Callable[[float], None] = time.sleep,
              kill: Callable[..., None] = kill_child,
              log: Callable[[str], None] = print,
              max_checks: Optional[int] = None,
              preempt_exit_code: Optional[int] = PREEMPT_EXIT,
              max_preempts: Optional[int] = 8,
              preempt_window_s: float = 600.0) -> int:
    """The relaunch decision loop, with every side effect injectable so the
    unit test can drive it against a fake child and a scripted check
    sequence (tests/test_observability.py::TestWatchdogRelaunch).

    Protocol per tick: sleep ``interval_s``; a child that exited cleanly
    (rc 0) ends supervision with 0; a child that exited with
    ``preempt_exit_code`` (the harness's PREEMPT_EXIT after a SIGTERM
    emergency save) is respawned IMMEDIATELY — no backoff and no burn of
    the consecutive budget, preemption being the environment's fault, not
    the run's; otherwise consult ``check`` (the heartbeat verdict — 0
    healthy / 1 unhealthy / 2 missing).  Healthy
    resets the consecutive-restart counter (and so the backoff).  Unhealthy
    or missing: if the consecutive budget is spent, give up (child's exit
    code, else 1); otherwise kill whatever is left of the child, back off
    ``backoff_s * 2^consecutive`` capped at ``backoff_cap_s``, respawn, and
    re-enter the grace period (no checks for ``grace_s`` — a fresh process
    needs time to write its first heartbeat).

    **Preempt-storm guard**: free preempt respawns are rate-capped — more
    than ``max_preempts`` preempt exits inside a sliding
    ``preempt_window_s`` window stops counting as "the environment's
    fault" (a scheduler or broken env preempting in a tight loop would
    otherwise respawn forever, never touching the budget) and falls
    through to the unhealthy path: consecutive budget, capped backoff,
    give-up with the child's exit code.  ``max_preempts=None`` disables
    the cap.  The window clock is the supervisor's own cumulative slept
    time (deterministic under the injected ``sleep``).
    """
    child = spawn()
    consecutive = 0
    grace_until = grace_s  # relative clock: ticks since (re)launch
    ticks_since_launch = 0.0
    slept = 0.0  # cumulative slept time: the storm window's clock
    preempts: "collections.deque[float]" = collections.deque()
    checks = 0
    try:
        while True:
            sleep(interval_s)
            slept += interval_s
            ticks_since_launch += interval_s
            if child.poll() is not None and child.returncode == 0:
                log("watchdog: child exited cleanly; supervision done")
                return 0
            storm = False
            if (child.poll() is not None and preempt_exit_code is not None
                    and child.returncode == preempt_exit_code):
                preempts.append(slept)
                while preempts and slept - preempts[0] > preempt_window_s:
                    preempts.popleft()
                if max_preempts is None or len(preempts) <= max_preempts:
                    # preemption is not a failure: the child cut an
                    # emergency checkpoint and exited deliberately.
                    # Respawn NOW — no backoff, no consecutive-budget
                    # burn, no health check consumed (the freed capacity
                    # may already be back)
                    log(f"watchdog: child preempted "
                        f"(exit {preempt_exit_code}); relaunching "
                        "immediately")
                    child = spawn()
                    ticks_since_launch = 0.0
                    continue
                storm = True
                log(f"watchdog: preempt storm: {len(preempts)} preempt "
                    f"exits within {preempt_window_s:g}s (cap "
                    f"{max_preempts}) — treating as unhealthy")
            if not storm and ticks_since_launch < grace_until:
                continue  # fresh (re)launch: let the heartbeat appear
            rc = 1 if storm else check()
            checks += 1
            if rc == 0:
                consecutive = 0
            else:
                if consecutive >= max_relaunches:
                    died_rc = child.poll()
                    kill(child)
                    # a positive rc is the child's own failure code;
                    # killed-by-us (negative) or alive-but-wedged reports 1
                    code = (died_rc if died_rc is not None and died_rc > 0
                            else 1)
                    log(f"watchdog: giving up after {consecutive} "
                        f"consecutive relaunches (exit {code})")
                    return int(code)
                delay = min(backoff_s * (2.0 ** consecutive), backoff_cap_s)
                log(f"watchdog: unhealthy (check rc={rc}); relaunch "
                    f"#{consecutive + 1}/{max_relaunches} after {delay:.0f}s "
                    "backoff")
                kill(child)
                sleep(delay)
                slept += delay
                child = spawn()
                consecutive += 1
                ticks_since_launch = 0.0
            if max_checks is not None and checks >= max_checks:
                # test hook: bounded supervision
                kill(child)
                return 0
    except BaseException:
        # Ctrl-C or an unexpected check/spawn error must not orphan the
        # training child: a detached run keeps writing the heartbeat, and
        # a restarted watchdog would then supervise a process it never
        # spawned (both reporting healthy on the same file).
        kill(child)
        raise


def run_relaunch(args, cmd: List[str]) -> int:
    if not cmd:
        print("watchdog: --relaunch needs the training command after `--`")
        return 2

    # seed from our own environment so a re-executed watchdog keeps the
    # child's incarnation monotone instead of resetting it to 0
    launches = {"n": int(os.environ.get("TCDP_RESTART_COUNT", "0") or 0)}

    def spawn():
        # spawn_supervised composes the child env: TCDP_RESTART_COUNT
        # seeds the child Heartbeat's incarnation (strictly larger per
        # respawn, so a relaunched worker's heartbeats are
        # distinguishable from its previous life's stale file), and with
        # --elastic_dir the committed-epoch rejoin hint lands the child
        # in the RUNNING world's join barrier
        # (train/rendezvous.maybe_rejoin_from_env) instead of a fresh one
        child = spawn_supervised(
            cmd, restart_count=launches["n"],
            elastic_dir=getattr(args, "elastic_dir", None),
            log=lambda s: print(f"watchdog: {s}"))
        launches["n"] += 1
        print(f"watchdog: launching: {' '.join(cmd)}")
        return child

    return supervise(
        spawn, lambda: run_check(args),
        interval_s=args.interval, grace_s=args.grace,
        max_relaunches=args.max_relaunches,
        backoff_s=args.backoff, backoff_cap_s=args.backoff_cap,
        max_preempts=(None if args.max_preempts <= 0 else args.max_preempts),
        preempt_window_s=args.preempt_window)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", action="store_true",
                      help="one-shot health check (exit 0/1/2)")
    mode.add_argument("--relaunch", action="store_true",
                      help="supervise the command after `--`: restart on "
                           "wedge/death with capped backoff")
    p.add_argument("--heartbeat", type=str, required=True,
                   help="heartbeat JSON path (harness --heartbeat)")
    p.add_argument("--max_age", type=float, default=60.0,
                   help="seconds before a heartbeat counts as stale "
                        "(choose > the harness --heartbeat_interval)")
    p.add_argument("--max_wedge", type=int, default=None,
                   help="max steps last_good_step may trail the attempt "
                        "counter (default: no wedge check)")
    p.add_argument("--min_step_rate", type=float, default=None,
                   help="min telemetry steps/sec (default: no stall check)")
    p.add_argument("--max_step_p95_ms", type=float, default=None,
                   help="max telemetry p95 step latency in ms; "
                        "default: no tail-latency check")
    p.add_argument("--max_ckpt_age", type=float, default=None,
                   help="max seconds since the run's last durable "
                        "checkpoint (heartbeat ckpt_age_s + heartbeat age; "
                        "default: no checkpoint-staleness check)")
    p.add_argument("--max_stream_lag", type=float, default=None,
                   help="max seconds since the last delta-stream segment "
                        "(heartbeat stream_lag_s + heartbeat age; default: "
                        "no stream-staleness check)")
    p.add_argument("--max_straggler_skew", type=float, default=None,
                   help="max cross-rank step-time skew in seconds "
                        "(heartbeat straggler_skew_s, from the flight "
                        "recorder's live phase profiles; default: no "
                        "straggler check)")
    p.add_argument("--interval", type=float, default=30.0,
                   help="relaunch mode: seconds between health checks")
    p.add_argument("--grace", type=float, default=120.0,
                   help="relaunch mode: seconds after a (re)launch before "
                        "checks resume (first heartbeat + compile time)")
    p.add_argument("--max_relaunches", type=int, default=5,
                   help="relaunch mode: consecutive restarts before giving "
                        "up (a healthy check refills the budget)")
    p.add_argument("--backoff", type=float, default=5.0,
                   help="relaunch mode: initial backoff seconds (doubles "
                        "per consecutive restart)")
    p.add_argument("--backoff_cap", type=float, default=300.0,
                   help="relaunch mode: backoff ceiling")
    p.add_argument("--max_preempts", type=int, default=8,
                   help="relaunch mode: preempt-storm guard — more than "
                        "this many PREEMPT_EXIT respawns inside "
                        "--preempt_window seconds counts as unhealthy "
                        "(consecutive budget + backoff) instead of a free "
                        "immediate relaunch; <= 0 disables the cap")
    p.add_argument("--preempt_window", type=float, default=600.0,
                   help="relaunch mode: sliding window (seconds of "
                        "supervisor slept time) for --max_preempts")
    p.add_argument("--elastic_dir", type=str, default=None,
                   help="relaunch mode: the run's shared rendezvous/gossip "
                        "directory (harness --elastic_dir); exports the "
                        "committed world epoch + coordinator address to "
                        "the child so a restarted host REJOINS the running "
                        "world instead of forming a fresh one")
    argv = list(sys.argv[1:] if argv is None else argv)
    # split at the FIRST `--`: left side is parsed STRICTLY (a misspelled
    # watchdog flag is an argparse error, never silently folded into the
    # child command), right side is the training command verbatim
    if "--" in argv:
        cut = argv.index("--")
        argv, cmd = argv[:cut], argv[cut + 1:]
    else:
        cmd = []
    args = p.parse_args(argv)
    if args.check:
        if cmd:
            p.error("--check takes no training command (drop the `-- ...`)")
        return run_check(args)
    return run_relaunch(args, cmd)


if __name__ == "__main__":
    sys.exit(main())
