"""Read, on the chip and at the cell's own size, the numbers that the limits
of ``correct`` are set from: the program's gaps from the reference on many
seeds, and the lower-precision control's gaps on a few.  One reader for both
builders (it took in ``read_limits.py``, which handed ``run.judge`` no counts
and followed the float32 reference twice on a control seed).  One process, so
the step and the reference compile once; each side is followed once and the
numbers are put together as ``run.judge`` does.  It also says where the host's
memory and the time go, phase by phase.  Training's readings need no measured
window.

    python3 benchmark/tests/read_limits_lm.py --workload W --seeds 1,2,3 --control 1
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

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
    t_last = [time.perf_counter()]
    page = os.sysconf("SC_PAGE_SIZE")

    def phase(name, seed):
        now = time.perf_counter()
        with open("/proc/self/statm") as f:
            resident = int(f.read().split()[1]) * page
        print("PHASE " + json.dumps({
            "seed": seed, "after": name, "seconds": round(now - t_last[0], 1),
            "host_resident_gb": round(resident / 1e9, 2),
            "host_peak_gb_so_far": round(resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9, 2)}), flush=True)
        t_last[0] = now

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
        phase("program's three steps and probes", seed)
        got, refr = run.both_sides(cell, raw)
        phase("program's side reduced, reference's three steps", seed)
        sides = [("program", got, "float32")]
        if seed in control:
            sides.append(("control", run.follow(cell, raw, args.precision),
                          args.precision))
            phase("control's three steps", seed)
        for who, got, precision in sides:
            line = {"workload": args.workload, "seed": seed, "who": who,
                    "numbers": run.compared_numbers(cell, got, refr, {}, precision)}
            aux = [np.asarray(a, np.float64).ravel() for a in got["aux1"]]
            if sum(a.size for a in aux) <= 64:      # the LM's per-pass numbers
                line["aux"] = [list(map(float, a)) for a in aux]
            for k in ("loss",) + tuple(cell.sync.KINDS):
                line[k] = [list(map(float, got[k])), list(map(float, refr[k]))]
            print("READING " + json.dumps(line), flush=True)
        del raw, refr, sides, got
        phase("numbers", seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
