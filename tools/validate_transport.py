"""Measured-vs-analytic transport validation (VERDICT r4 #4).

The paper MEASURED network traffic at the NIC (`IMAGENET/training/meter.py:
24-47,66-86`); this repo's transport numbers have so far been analytic
(``utils/meters.per_chip_traffic_bytes`` over the measured payload bytes each
sync hands its collective).  This tool closes the loop: it runs a REAL
two-process data-parallel sync on the CPU backend (collectives ride gRPC over
localhost), samples ``lo`` interface bytes around a timed window of sync
steps, and compares measured bytes/step against the analytic model.

Loopback accounting: every payload byte a rank sends appears once in ``lo``
TX and once in ``lo`` RX; we compare ``lo`` TX delta against the sum over
ranks of per-rank transmitted bytes.  A heartbeat-control window (same
duration, zero sync steps) is subtracted to remove coordination-service
baseline traffic.  Expect ratio slightly above 1 (gRPC/TCP framing, ack
overhead) — the point is the SLOPE: payload doubling must double measured
bytes, and method ordering (dense > qsgd > topk-1% > …) must match.

Usage:
    python tools/validate_transport.py --out benchmarks/transport_validation_r5.tsv
(spawns its own two worker subprocesses; CPU-only, no chip contention)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):  # script run: repo root onto sys.path
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_PARAM = 2_000_000        # synthetic gradient size (fp32: 8 MB dense payload)
PORT = 12378

CASES = [
    # label, method, mode, ratio/extra
    ("dense", None, "simulate", {}),
    ("topk-1%-wire-EF", "topk", "wire", {"ratio": 0.01, "error_feedback": True}),
    ("blocktopk-1%-wire-EF", "blocktopk", "wire",
     {"ratio": 0.01, "error_feedback": True, "block_size": 256}),
    # owner-sharded transport: the all_to_all route stage bills at
    # (W-1)/W x payload per rank, the shard-return all_gather at (W-1) x —
    # the sent_bits_alltoall bucket closes the measured-vs-analytic loop
    # for the third collective
    ("topk-1%-wire-EF-sharded", "topk", "wire",
     {"ratio": 0.01, "error_feedback": True, "transport": "sharded"}),
    # hierarchical transport on a 2x1 virtual mesh: each process is its own
    # pod (C=1, no intra-pod psum), so the measured loopback bytes are
    # EXACTLY the inter-pod route/return collectives the sent_bits_dcn
    # bucket bills — the per-fabric split's measured-vs-analytic closure
    ("topk-1%-wire-EF-hier", "topk", "wire",
     {"ratio": 0.01, "error_feedback": True, "transport": "hierarchical",
      "dp_pods": 2}),
    ("terngrad-wire", "terngrad", "wire", {}),
]


def lo_bytes():
    with open("/proc/net/dev") as f:
        for line in f.read().splitlines()[2:]:
            iface, _, rest = line.partition(":")
            if iface.strip() == "lo":
                cols = rest.split()
                return int(cols[0]), int(cols[8])
    return 0, 0


def worker(args) -> None:
    """Rank entry: real jax.distributed 2-process CPU mesh, N sync steps."""
    import jax

    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    # the parent only spawns and parses; the workers are what compiles
    setup_compile_cache()
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(f"127.0.0.1:{args.port}", args.procs, args.rank)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from tpu_compressed_dp.parallel.dp import CompressionConfig, make_grad_sync

    _, method, mode, extra = next(c for c in CASES if c[0] == args.case)
    cfg = CompressionConfig(
        method=method, granularity="entiremodel", mode=mode,
        ratio=extra.get("ratio", 0.01),
        block_size=extra.get("block_size", 256),
        transport=extra.get("transport", "allgather"),
        dp_pods=extra.get("dp_pods", 1),
        error_feedback=extra.get("error_feedback", False))
    sync = make_grad_sync(cfg, "data")
    mesh = Mesh(np.array(jax.devices()), ("data",))
    assert len(jax.devices()) == args.procs
    from jax.sharding import NamedSharding

    def one(g, ef):
        # identical key on every rank (the shared-seed contract wire
        # randomk/quantizer dither relies on)
        key = jax.random.key(7)
        synced, new_ef, _, stats = sync(
            {"g": g}, {"g": ef} if cfg.error_feedback else (), (), key)
        out = synced["g"]
        nef = new_ef["g"] if cfg.error_feedback else ef
        # per-worker counters (clips, `topk_underfull`) leave replicated,
        # as the train steps report them
        stats = {k: jax.lax.pmean(v, "data") for k, v in stats.items()}
        return out, nef, stats

    f = jax.jit(shard_map(
        one, mesh=mesh,
        in_specs=(P("data"), P("data")),
        # synced gradient is replicated post-reduction; EF stays per-rank
        out_specs=(P(), P("data"), P())))
    rng = np.random.default_rng(args.rank)
    sharded = NamedSharding(mesh, P("data"))
    nl = N_PARAM // args.procs
    g = jax.make_array_from_process_local_data(
        sharded, rng.standard_normal((1, nl)).astype(np.float32))
    ef = jax.make_array_from_process_local_data(
        sharded, np.zeros((1, nl), np.float32))
    # warmup/compile
    out, ef, stats = f(g, ef)
    jax.block_until_ready(out)
    stats = jax.device_get(stats)

    def window(steps):
        nonlocal ef
        t0, b0 = time.perf_counter(), lo_bytes()
        o = None
        for _ in range(steps):
            o, ef, _ = f(g, ef)
        if o is not None:
            jax.block_until_ready(o)
        dt = time.perf_counter() - t0
        b1 = lo_bytes()
        return dt, b1[1] - b0[1]

    # timed window, then an equal-duration idle control window (sampled
    # AROUND the sleep, so heartbeat baseline traffic is actually captured)
    dt, tx = window(args.steps)
    b0 = lo_bytes()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < dt:
        time.sleep(0.01)
    tx_idle = lo_bytes()[1] - b0[1]
    if args.rank == 0:
        rec = {
            "case": args.case,
            "steps": args.steps,
            "lo_tx_per_step": (tx - tx_idle) / args.steps,
            "lo_tx_idle_window": tx_idle,
            "sent_bits": float(stats.get("sent_bits", 0.0)),
            "sent_bits_psum": float(stats.get("sent_bits_psum", 0.0)),
            "sent_bits_allgather": float(stats.get("sent_bits_allgather", 0.0)),
            "sent_bits_alltoall": float(stats.get("sent_bits_alltoall", 0.0)),
            "sent_bits_ici": float(stats.get("sent_bits_ici", 0.0)),
            "sent_bits_dcn": float(stats.get("sent_bits_dcn", 0.0)),
            "sent_bits_dcn_route": float(
                stats.get("sent_bits_dcn_route", 0.0)),
        }
        print("RESULT " + json.dumps(rec), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="benchmarks/transport_validation_r5.tsv")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--port", type=int, default=PORT)
    # worker-mode internals
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--case", type=str, default="dense")
    args = ap.parse_args(argv)
    if args.worker:
        return worker(args)

    from tpu_compressed_dp.utils.meters import per_fabric_traffic_bytes

    rows = []
    for ci, (label, method, mode, extra) in enumerate(CASES):
        procs = []
        outs = []
        for rank in range(args.procs):
            cmd = [sys.executable, os.path.abspath(__file__), "--worker",
                   "--rank", str(rank), "--case", label,
                   "--steps", str(args.steps), "--procs", str(args.procs),
                   "--port", str(args.port + ci)]
            procs.append(subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))))
        # communicate() drains the pipes while waiting — wait()-then-read
        # deadlocks once a worker logs past the ~64 KB pipe buffer
        outs = [p.communicate()[0] for p in procs]
        rc = [p.returncode for p in procs]
        if any(rc):
            print(f"## {label}: worker failed rc={rc}\n" + outs[0][-2000:],
                  file=sys.stderr)
            continue
        rec = None
        for o in outs:
            for ln in o.splitlines():
                if ln.startswith("RESULT "):
                    rec = json.loads(ln[len("RESULT "):])
        if rec is None:
            print(f"## {label}: no RESULT line\n" + outs[0][-2000:],
                  file=sys.stderr)
            continue
        # analytic: per-rank transmitted bytes/step summed over ranks.
        # Ring all-reduce: each rank transmits 2(W-1)/W x payload;
        # all_gather of worker-distinct payloads: each rank transmits its
        # own payload (W-1) times; all_to_all (the sharded route stage):
        # each rank keeps its own bucket and transmits (W-1)/W x payload.
        w = args.procs
        psum_b = rec["sent_bits_psum"] / 8.0
        ag_b = rec["sent_bits_allgather"] / 8.0
        a2a_b = rec.get("sent_bits_alltoall", 0.0) / 8.0
        ici_b = rec.get("sent_bits_ici", 0.0) / 8.0
        dcn_b = rec.get("sent_bits_dcn", 0.0) / 8.0
        rt_b = rec.get("sent_bits_dcn_route", 0.0) / 8.0
        if psum_b == ag_b == a2a_b == 0.0 and ici_b + dcn_b == 0.0:
            psum_b = rec["sent_bits"] / 8.0
        pods = extra.get("dp_pods", 1)
        # per_fabric degenerates to the flat per_chip arithmetic at pods=1;
        # at pods>1 the hier group collectives bill with their own factors
        per_rank = sum(per_fabric_traffic_bytes(
            psum_b, ag_b, w, a2a_b, ici_b, rt_b, max(dcn_b - rt_b, 0.0),
            pods))
        analytic = per_rank * w
        measured = rec["lo_tx_per_step"]
        rows.append({
            "case": label,
            "analytic_bytes_per_step_all_ranks": round(analytic, 1),
            "measured_lo_tx_bytes_per_step": round(measured, 1),
            "ratio_measured_over_analytic": round(measured / analytic, 3)
            if analytic else "",
            "idle_window_bytes": rec["lo_tx_idle_window"],
            "steps": rec["steps"],
        })
        print(rows[-1], flush=True)
    cols = list(rows[0].keys()) if rows else []
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(
            "# Measured (loopback NIC) vs analytic transport, 2-process CPU\n"
            "# data-parallel sync over gRPC localhost "
            "(tools/validate_transport.py).\n"
            "# measured = lo TX bytes/step summed over both ranks, idle-window\n"
            "# baseline subtracted; analytic = per_chip_traffic_bytes x ranks\n"
            "# (the same single-source arithmetic every sweep/TTA artifact\n"
            "# bills).  Ratio > 1 = framing/ack overhead; the validation\n"
            "# claims are (a) ratio stable across methods, (b) method\n"
            "# ordering preserved.  Reference parity: meter.py:24-47,66-86.\n")
        f.write("\t".join(cols) + "\n")
        for r in rows:
            f.write("\t".join(str(r[c]) for c in cols) + "\n")
    print(f"wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
