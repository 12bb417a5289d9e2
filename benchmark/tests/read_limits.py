"""Read, on the chip and at the cell's own size, the numbers that the limits
of ``correct`` are set from: the program's gaps from the reference on many
seeds, and the lower-precision control's gaps on a few.  One process, so the
step compiles once.  Training's readings need no measured window.

    python3 benchmark/tests/read_limits.py --workload W --seeds 1,2,3 --control 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", default="")
    p.add_argument("--precision", default="fp8")
    args = p.parse_args()
    cell = run.load_cell(args.workload)

    import jax

    from feed import Feed
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("limits are read on the chip")
    prog = cell.builder.build(cell.cfg, cell.traffic, devices[:cell.chips], cell.model)
    control = {int(s) for s in args.control.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        seed32 = seed % 2147483647
        state = prog.make_state(seed32)
        feed = Feed(prog, cell.traffic["feed"], seed32)

        def epoch(st, **kw):
            st, acc = prog.run_epoch(prog.train_step, st, feed.batches(**kw))
            feed.close()
            return st, acc

        state, raw = run.drive_first_steps(prog, state, epoch, feed)
        del state
        feed.release()
        for who, precision in [("program", "float32")] + (
                [("control", args.precision)] if seed in control else []):
            got, refr = run.both_sides(cell, raw, precision)
            line = {"workload": args.workload, "seed": seed, "who": who,
                    "numbers": {n: v for n, v, _, _ in run.judge(cell, raw, {}, precision)}}
            for k in ("loss", "grad1", "mean_grad1", "dparam", "resid1"):
                if k in got:
                    line[k] = [list(map(float, got[k])), list(map(float, refr[k]))]
            print("READING " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
