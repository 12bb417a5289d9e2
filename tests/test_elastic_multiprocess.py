"""Real multi-process elastic drills (train/rendezvous.py end to end).

Everything in tests/test_elastic.py and tests/test_rendezvous.py runs the
protocol single-process with injected seams; this module is the other
half: actual ``jax.distributed`` worlds of 2 OS processes on the CPU
backend, where a peer's death really wedges the collectives and the
survivor must rendezvous, re-init, and remesh to keep training.

Both drills live in the slow tier: they burn wall-clock on real
peer-timeout windows.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WATCHDOG = os.path.join(REPO, "tools", "watchdog.py")

pytestmark = pytest.mark.slow


def _free_port() -> int:
    # OS-assigned: a hardcoded port collides with concurrent pytest
    # sessions or a leftover child from a timed-out run
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(devices_per_proc: int = 2, **extra) -> dict:
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS": " ".join(flags)})
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _dawn_cmd(rank: int, port: int, elastic_dir: str, log_dir: str, *,
              epochs: int, peer_timeout: float = 4.0,
              heartbeat: str = None) -> list:
    cmd = [sys.executable, "-m", "tpu_compressed_dp.harness.dawn",
           "--synthetic", "--synthetic_n", "512", "--epochs", str(epochs),
           "--batch_size", "64", "--channels_scale", "0.125",
           "--compress", "entiremodel", "--method", "topk", "--ratio", "0.1",
           "--error_feedback",
           "--elastic", "--elastic_dir", elastic_dir,
           "--elastic_min_world", "2",
           "--peer_timeout", str(peer_timeout),
           "--coordinator", f"127.0.0.1:{port}",
           "--num_processes", "2", "--process_id", str(rank),
           "--log_dir", log_dir]
    if heartbeat:
        cmd += ["--heartbeat", heartbeat, "--heartbeat_interval", "1"]
    return cmd


def _wait_for(predicate, deadline_s: float, what: str):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out after {deadline_s:g}s waiting for {what}")


def _gossip_step(elastic_dir, rank):
    try:
        with open(os.path.join(elastic_dir, f"rank{rank}.json")) as f:
            return json.load(f).get("step", -1)
    except (OSError, ValueError):
        return -1


def _read_epoch(elastic_dir):
    try:
        with open(os.path.join(elastic_dir, "epoch.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


@pytest.mark.timeout(420)
def test_kill_one_process_survivor_remeshes(tmp_path):
    """SIGKILL one of two dawn processes mid-training: the survivor's
    bounded fetch / gossip staleness converts the wedge into PeerFailed,
    the rendezvous commits epoch 1 over the survivor alone, jax.distributed
    re-initialises at num_processes=1, and training completes at W-1
    (2 of 4 data rows) with exit 0."""
    port = _free_port()
    elastic_dir = str(tmp_path / "elastic")
    procs = [
        subprocess.Popen(
            _dawn_cmd(r, port, elastic_dir, str(tmp_path / f"log{r}"),
                      epochs=8),
            env=_env(), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in (0, 1)]
    try:
        # let the world form and take a few real steps (both ranks beating)
        _wait_for(lambda: _gossip_step(elastic_dir, 0) >= 2
                  and _gossip_step(elastic_dir, 1) >= 2,
                  180, "both ranks to start stepping")
        procs[1].send_signal(signal.SIGKILL)
        procs[1].wait(timeout=30)
        out0, _ = procs[0].communicate(timeout=300)
        assert procs[0].returncode == 0, out0[-3000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    rec = _read_epoch(elastic_dir)
    assert rec is not None, "no epoch was ever committed"
    assert rec["epoch"] >= 1 and [int(r) for r in rec["ranks"]] == [0]
    assert out0.count("re-initialised") >= 1, out0[-3000:]


@pytest.mark.timeout(540)
def test_watchdog_relaunch_rejoins_running_world(tmp_path):
    """The full readmission loop: kill rank 1, wait for the survivor to
    commit the shrunken epoch, then hand rank 1 to ``tools/watchdog.py
    --relaunch --elastic_dir`` — its spawn exports the committed epoch, the
    child parks in the join barrier, the survivor's epoch-boundary
    ``rejoin_barrier`` readmits it, and BOTH sides exit 0 with a final
    epoch naming ranks [0, 1] again."""
    port = _free_port()
    elastic_dir = str(tmp_path / "elastic")
    hb1 = str(tmp_path / "hb1.json")
    p0 = subprocess.Popen(
        _dawn_cmd(0, port, elastic_dir, str(tmp_path / "log0"), epochs=24),
        env=_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    p1 = subprocess.Popen(
        _dawn_cmd(1, port, elastic_dir, str(tmp_path / "log1"), epochs=24,
                  heartbeat=hb1),
        env=_env(), cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    wd = None
    try:
        _wait_for(lambda: _gossip_step(elastic_dir, 0) >= 2
                  and _gossip_step(elastic_dir, 1) >= 2,
                  180, "both ranks to start stepping")
        p1.send_signal(signal.SIGKILL)
        p1.wait(timeout=30)
        # survivor detects, votes alone, commits the shrunken world
        _wait_for(lambda: (_read_epoch(elastic_dir) or {}).get("ranks")
                  == [0], 120, "the survivor to commit the W-1 epoch")
        shrunk = _read_epoch(elastic_dir)["epoch"]
        # the watchdog's spawn reads epoch.json and exports the rejoin
        # hint; the child lands in the running world's join barrier
        wd = subprocess.Popen(
            [sys.executable, WATCHDOG, "--relaunch",
             "--heartbeat", hb1, "--interval", "5", "--grace", "120",
             "--max_relaunches", "3", "--backoff", "2",
             "--elastic_dir", elastic_dir, "--"]
            + _dawn_cmd(1, port, elastic_dir, str(tmp_path / "log1b"),
                        epochs=24, heartbeat=hb1),
            env=_env(TCDP_RESTART_COUNT="1"), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        _wait_for(lambda: (_read_epoch(elastic_dir) or {}).get("ranks")
                  == [0, 1], 240, "the readmit barrier to re-commit [0, 1]")
        out0, _ = p0.communicate(timeout=300)
        assert p0.returncode == 0, out0[-3000:]
        outw, _ = wd.communicate(timeout=300)
        assert wd.returncode == 0, outw[-3000:]
        assert "rejoin hint" in outw
    finally:
        for p in (p0, p1, wd):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
    rec = _read_epoch(elastic_dir)
    assert rec["epoch"] > shrunk  # readmission is a NEW epoch, not a rewind
    assert [int(r) for r in rec["ranks"]] == [0, 1]
