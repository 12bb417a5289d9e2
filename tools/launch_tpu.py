#!/usr/bin/env python3
"""Multi-host launcher for TPU pods and local multi-process testing.

The reference's cluster launcher (`IMAGENET/train.py`) provisions AWS
machines via ncluster, builds NCCL ring-order env strings, and runs
``torch.distributed.launch``/``mpirun`` per node (`train.py:290-449`).  On
Cloud TPU there is nothing to provision per-worker and no ring strings: every
host of a pod slice runs the SAME command; ``jax.distributed.initialize``
auto-detects the coordinator from the TPU metadata; XLA routes collectives
over ICI/DCN from the mesh layout.  So the launcher reduces to:

  gcloud mode (default) — print or run the one gcloud command that fans the
  training command to all workers:
    python tools/launch_tpu.py --tpu my-pod --zone us-central2-b -- \
        python -m tpu_compressed_dp.harness.imagenet /data --arch resnet50
  Add ``--run`` to execute (needs gcloud auth); default prints it (dry run).

  local mode — spawn N local processes with an explicit rendezvous on
  127.0.0.1, for testing the multi-process code path without hardware (each
  process gets JAX_PLATFORMS=cpu and a slice of
  xla_force_host_platform_device_count devices):
    python tools/launch_tpu.py --local_procs 2 --devices_per_proc 2 -- \
        python -m tpu_compressed_dp.harness.imagenet --synthetic ...
  The harnesses pick up --coordinator/--num_processes/--process_id from the
  injected TPU_CDP_* environment (or accept them as flags).
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys


def tpu_ssh_cmd(tpu: str, zone: str, worker: str, command: str) -> list:
    """The one gcloud TPU-VM ssh invocation every fan-out tool shares
    (also used by tools/dataset_tools.py)."""
    return [
        "gcloud", "compute", "tpus", "tpu-vm", "ssh", tpu,
        f"--zone={zone}", f"--worker={worker}", f"--command={command}",
    ]


def build_gcloud_cmd(args, train_cmd: list) -> list:
    inner = " ".join(shlex.quote(c) for c in train_cmd)
    return tpu_ssh_cmd(args.tpu, args.zone, "all",
                       f"cd {shlex.quote(args.workdir)} && {inner}")


def run_local(args, train_cmd: list) -> int:
    port = args.port
    procs = []
    for rank in range(args.local_procs):
        env = dict(os.environ)
        # replace (not append) any inherited device-count flag: duplicated
        # XLA flags are an error, and the parent may be a test process that
        # already forced its own count.  (Inline rather than
        # mesh.force_host_devices: the launcher must not import jax.)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={args.devices_per_proc}")
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": " ".join(flags),
            "TPU_CDP_COORDINATOR": f"127.0.0.1:{port}",
            "TPU_CDP_NUM_PROCESSES": str(args.local_procs),
            "TPU_CDP_PROCESS_ID": str(rank),
        })
        cmd = train_cmd + [
            "--coordinator", f"127.0.0.1:{port}",
            "--num_processes", str(args.local_procs),
            "--process_id", str(rank),
        ]
        procs.append(subprocess.Popen(cmd, env=env))
    # wait on EVERY rank (short-circuiting after the first failure would
    # orphan the rest mid-rendezvous, holding the coordinator port)
    rcs = [p.wait() for p in procs]
    return next((rc for rc in rcs if rc), 0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--tpu", type=str, default=None, help="TPU pod/slice name")
    p.add_argument("--zone", type=str, default="us-central2-b")
    p.add_argument("--workdir", type=str, default="~/tpu_compressed_dp")
    p.add_argument("--run", action="store_true",
                   help="execute the gcloud command (default: print it)")
    p.add_argument("--local_procs", type=int, default=None,
                   help="spawn N local CPU-only processes instead of gcloud "
                        "(a chip belongs to one process; these never ask "
                        "for one)")
    p.add_argument("--devices_per_proc", type=int, default=2)
    p.add_argument("--port", type=int, default=29431)
    p.add_argument("train_cmd", nargs=argparse.REMAINDER,
                   help="training command after --")
    args = p.parse_args(argv)

    train_cmd = args.train_cmd
    if train_cmd and train_cmd[0] == "--":
        train_cmd = train_cmd[1:]
    if not train_cmd:
        p.error("no training command given (append it after --)")

    if args.local_procs:
        return run_local(args, train_cmd)

    if not args.tpu:
        p.error("--tpu NAME required for gcloud mode (or use --local_procs)")
    cmd = build_gcloud_cmd(args, train_cmd)
    print(" ".join(shlex.quote(c) for c in cmd))
    if args.run:
        return subprocess.call(cmd)
    return 0


if __name__ == "__main__":
    sys.exit(main())
