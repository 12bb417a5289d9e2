"""Launcher coverage (VERDICT r1 missing #3 / weak #7).

The reference's cluster entry is `IMAGENET/train.py` (ncluster + NCCL ring
strings + torch.distributed.launch); ours is `tools/launch_tpu.py` with a
gcloud fan-out mode and a local multi-process mode.  The local mode is the
real test: it spawns N processes with an explicit 127.0.0.1 rendezvous —
the same multi-process path a TPU pod runs, minus the hardware — and the
dawn harness trains across them (the `CIFAR10/core.py:334` Gloo-over-TCP
equivalent).
"""

import subprocess
import sys
import os

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCHER = os.path.join(REPO, "tools", "launch_tpu.py")


class TestGcloudMode:
    def test_dry_run_prints_command(self):
        out = subprocess.run(
            [sys.executable, LAUNCHER, "--tpu", "my-pod", "--zone", "us-east5-a",
             "--", "python", "-m", "tpu_compressed_dp.harness.imagenet", "/data"],
            capture_output=True, text=True, cwd=REPO)
        assert out.returncode == 0
        assert "gcloud compute tpus tpu-vm ssh my-pod" in out.stdout
        assert "--worker=all" in out.stdout
        assert "--zone=us-east5-a" in out.stdout
        assert "harness.imagenet" in out.stdout

    def test_requires_train_cmd(self):
        out = subprocess.run([sys.executable, LAUNCHER, "--tpu", "x"],
                             capture_output=True, text=True, cwd=REPO)
        assert out.returncode != 0

    def test_requires_tpu_or_local(self):
        out = subprocess.run([sys.executable, LAUNCHER, "--", "python", "x.py"],
                             capture_output=True, text=True, cwd=REPO)
        assert out.returncode != 0


class TestLocalMode:
    @pytest.mark.timeout(300)
    @pytest.mark.usefixtures("loopback_exclusive")
    def test_two_process_dawn_trains(self, tmp_path):
        """2 processes x 2 virtual CPU devices: the dawn harness shards the
        global batch per process (`ShardedBatches`), syncs compressed
        gradients across the 4-device mesh, and both ranks exit 0."""
        import socket

        # OS-assigned free port: a hardcoded one collides with concurrent
        # pytest sessions or a leftover child from a timed-out run
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        out = subprocess.run(
            [sys.executable, LAUNCHER, "--local_procs", "2",
             "--devices_per_proc", "2", "--port", str(port), "--",
             sys.executable, "-m", "tpu_compressed_dp.harness.dawn",
             "--synthetic", "--synthetic_n", "256", "--epochs", "2",
             "--batch_size", "64", "--channels_scale", "0.125",
             "--compress", "entiremodel", "--method", "topk", "--ratio", "0.1",
             "--error_feedback", "--log_dir", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO, timeout=280)
        assert out.returncode == 0, out.stderr[-2000:]
        # rank-0-only logging: exactly one epoch table in the combined output
        assert out.stdout.count("train loss") == 1, out.stdout
        # the TSV lands with one row per epoch
        tsv = (tmp_path / "logs.tsv").read_text().strip().splitlines()
        assert len(tsv) == 3  # header + 2 epochs
