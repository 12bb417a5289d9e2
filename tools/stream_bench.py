#!/usr/bin/env python
"""Delta-stream economics bench: rejoin bytes/wall-time delta-vs-full and
steady-state stream bytes per window vs full-checkpoint bytes, at the tiny
LM config (models/transformer.py ``tiny_llama``).

Two questions, each answered as a record pair (delta stream vs full
checkpoint) so the BENCH json reads as a direct comparison:

  * **rejoin** — a relaunched host needs the live params.  Warm path:
    :class:`~tpu_compressed_dp.stream.reader.StreamReader` catch-up over
    the segment stream (what ``--stream_rejoin`` does before the join
    barrier, which then SKIPS the params broadcast).  Full path: an Orbax
    restore of the newest checkpoint.  Reported: bytes moved and wall
    seconds for each, plus the ratio.
  * **steady state** — what one append window costs on disk vs one full
    checkpoint save at the same cadence: keyframe bytes, per-delta bytes,
    amortised bytes/window at ``--keyframe_every``, vs the Orbax step dir
    + manifest.

CPU-honest caveats: wall times are host/filesystem numbers on whatever
machine runs this (no TPU in the loop — the codec's select+pack runs
through the same wire kernels tier-1 exercises); parameter updates are
synthetic per-step perturbations (every coordinate moves, like an
optimizer step, which is the property that sizes a delta), not real LM
training.  The byte accounting — the point of this bench — is exact.

    python tools/stream_bench.py --out stream_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def _perturb(params, rng, scale=1e-3):
    """Synthetic optimizer step: every coordinate moves a little — the
    worst case for a delta codec and the realistic one."""
    return jax.tree.map(
        lambda p: (p + (rng.standard_normal(p.shape) * scale
                        ).astype(np.float32)), params)


def run(out: str, *, ratio: float, keyframe_every: int, steps: int,
        seed: int) -> dict:
    import tempfile

    from tpu_compressed_dp.models.transformer import init_llama, tiny_llama
    from tpu_compressed_dp.stream.delta import flatten_params
    from tpu_compressed_dp.stream.reader import StreamReader
    from tpu_compressed_dp.stream.store import (list_segments,
                                                read_segment_manifest)
    from tpu_compressed_dp.stream.writer import StreamWriter
    from tpu_compressed_dp.train.optim import SGD
    from tpu_compressed_dp.train.state import TrainState
    from tpu_compressed_dp.utils.checkpoint import Checkpointer

    cfg = tiny_llama()
    params = jax.tree.map(np.asarray,
                          init_llama(cfg, jax.random.key(seed)))
    vec, _ = flatten_params(params)
    n_params = int(vec.size)
    opt = SGD(lr=0.1, momentum=0.9)
    rng = np.random.default_rng(seed)

    records: List[dict] = []
    with tempfile.TemporaryDirectory() as td:
        sd = os.path.join(td, "stream")
        cd = os.path.join(td, "ckpt")
        w = StreamWriter(sd, ratio=ratio, keyframe_every=keyframe_every,
                         log=lambda *a, **k: None)
        state = TrainState.create(params, {}, opt.init(params), (),
                                  jax.random.key(seed))
        ckpt = Checkpointer(cd)

        # -- steady state: stream every synthetic step, checkpoint once
        t0 = time.monotonic()
        for i in range(steps):
            params = _perturb(params, rng)
            w.append(params, step=i + 1)
        append_s = time.monotonic() - t0
        import dataclasses
        state = dataclasses.replace(state, params=params,
                                    step=state.step + steps)
        t0 = time.monotonic()
        ckpt.save(state, {"step": steps})
        ckpt_save_s = time.monotonic() - t0
        ckpt.close()
        ckpt_bytes = _dir_bytes(cd)

        seg_rows = []
        for q in list_segments(sd):
            man = read_segment_manifest(sd, q)
            seg_rows.append({"seq": q, "kind": man["kind"],
                             "step": man["step"], "bytes": man["bytes"],
                             "nnz": man["nnz"],
                             "window_close": man["window_close"]})
        kf_bytes = [r["bytes"] for r in seg_rows if r["kind"] == "keyframe"]
        mid_bytes = [r["bytes"] for r in seg_rows
                     if r["kind"] == "delta" and not r["window_close"]]
        flush_bytes = [r["bytes"] for r in seg_rows
                       if r["kind"] == "delta" and r["window_close"]]
        stream_total = sum(r["bytes"] for r in seg_rows)
        # one window = keyframe + (keyframe_every - 2) Top-K deltas + the
        # window-closing flush (dense under these synthetic updates)
        window_bytes = (float(np.mean(kf_bytes))
                        + (keyframe_every - 2)
                        * float(np.mean(mid_bytes or [0.0]))
                        + float(np.mean(flush_bytes or [0.0])))

        # -- rejoin: warm catch-up vs full Orbax restore
        w.sync(params, step=steps)   # the barrier flush survivors perform
        t0 = time.monotonic()
        r = StreamReader(sd, log=lambda *a, **k: None)
        r.catch_up()
        warm = {"bytes": int(r.bytes_read),
                "segments": int(r.segments_applied),
                "wall_s": round(time.monotonic() - t0, 4),
                "exact": bool(r.exact)}
        pvec, _ = flatten_params(params)
        rvec, _ = flatten_params(r.params_like(params))
        assert np.array_equal(pvec.view(np.int32), rvec.view(np.int32)), (
            "warm rejoin reconstruction not bitwise")

        fresh = TrainState.create(
            jax.tree.map(np.zeros_like, params), {},
            opt.init(params), (), jax.random.key(seed + 1))
        t0 = time.monotonic()
        restore = Checkpointer(cd)
        restored, _meta = restore.restore(fresh)
        restore.close()
        full = {"bytes": int(ckpt_bytes),
                "wall_s": round(time.monotonic() - t0, 4)}
        fvec, _ = flatten_params(jax.tree.map(np.asarray, restored.params))
        assert np.array_equal(pvec.view(np.int32), fvec.view(np.int32)), (
            "full restore not bitwise")
        w.close()

    dense_bytes = n_params * 4
    result = {
        "n": len(seg_rows),
        "cmd": ("JAX_PLATFORMS=cpu python tools/stream_bench.py "
                f"--out {os.path.basename(out)} --ratio {ratio} "
                f"--keyframe_every {keyframe_every} --steps {steps} "
                f"--seed {seed}"),
        "rc": 0,
        "note": ("CPU smoke: wall times are host/filesystem numbers (no "
                 "TPU in the loop); updates are synthetic per-step "
                 "perturbations where EVERY coordinate moves (optimizer-"
                 "step-like, the dense worst case for the flush); byte "
                 "accounting is exact.  Rejoin reads the newest keyframe "
                 "window only (fresh-reader seek); both reconstructions "
                 "are asserted bitwise against the live params.  The "
                 "full-checkpoint bytes are the whole Orbax step dir "
                 "(params + SGD momentum, zstd-compressed)."),
        "config": {"model": "tiny_llama", "n_params": n_params,
                   "dense_param_bytes": dense_bytes, "ratio": ratio,
                   "keyframe_every": keyframe_every, "steps": steps},
        "rejoin": {
            "warm_stream": warm,
            "full_orbax": full,
            "bytes_ratio_warm_over_full": round(
                warm["bytes"] / max(full["bytes"], 1), 4),
            "wall_ratio_warm_over_full": round(
                warm["wall_s"] / max(full["wall_s"], 1e-9), 4),
        },
        "steady_state": {
            "keyframe_bytes_mean": round(float(np.mean(kf_bytes)), 1),
            "delta_mid_bytes_mean": round(
                float(np.mean(mid_bytes or [0.0])), 1),
            "flush_bytes_mean": round(
                float(np.mean(flush_bytes or [0.0])), 1),
            "window_bytes_amortised": round(window_bytes, 1),
            "bytes_per_append_amortised": round(
                window_bytes / keyframe_every, 1),
            "full_ckpt_bytes": int(ckpt_bytes),
            "full_ckpt_save_s": round(ckpt_save_s, 4),
            "append_s_total": round(append_s, 4),
            "append_ratio_vs_full_ckpt": round(
                (window_bytes / keyframe_every) / max(ckpt_bytes, 1), 6),
            "stream_total_bytes": stream_total,
        },
        "records": seg_rows,
    }
    with open(out + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(out + ".tmp", out)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    p = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--out", type=str, default="stream_bench.json")
    p.add_argument("--ratio", type=float, default=0.01)
    p.add_argument("--keyframe_every", type=int, default=8)
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    res = run(args.out, ratio=args.ratio,
              keyframe_every=args.keyframe_every, steps=args.steps,
              seed=args.seed)
    rj, ss = res["rejoin"], res["steady_state"]
    print(f"params: {res['config']['n_params']} "
          f"({res['config']['dense_param_bytes']} dense bytes)")
    print(f"rejoin warm: {rj['warm_stream']['bytes']} B "
          f"{rj['warm_stream']['wall_s']} s | full: "
          f"{rj['full_orbax']['bytes']} B {rj['full_orbax']['wall_s']} s "
          f"| bytes x{rj['bytes_ratio_warm_over_full']}")
    print(f"steady state: {ss['bytes_per_append_amortised']} B/append "
          f"vs {ss['full_ckpt_bytes']} B/full-ckpt "
          f"(x{ss['append_ratio_vs_full_ckpt']})")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
