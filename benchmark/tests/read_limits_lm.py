"""Read, on the chip and at the cell's own size, the numbers that the limits
of ``correct`` are set from: the program's gaps from the reference on many
seeds, and the lower-precision control's gaps on a few.  One process, so the
step and the reference compile once.  ``read_limits.py`` hands the comparison
no counts and computes the float32 reference twice on a control seed; at half
a billion parameters a reference pass is minutes of the chip, so this one
follows each side once and puts the numbers together as ``run.judge`` does.
It also says where the host's memory and the time go, phase by phase: the
comparison keeps whole host copies of the model.

    python3 benchmark/tests/read_limits_lm.py --workload W --seeds 1,2,3 --control 1
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

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

    import check
    from feed import Feed
    from reference import steps
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("limits are read on the chip")
    cfg, comp = cell.cfg, cell.traffic["compression"]
    prog = cell.builder.build(cfg, cell.traffic, devices[:cell.chips], cell.model)
    treedef = jax.tree.structure(cell.model.param_shapes(cfg),
                                 is_leaf=lambda s: isinstance(s, tuple))
    t_last = [time.perf_counter()]

    def phase(name, seed):
        now = time.perf_counter()
        print("PHASE " + json.dumps({
            "seed": seed, "after": name, "seconds": round(now - t_last[0], 1),
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
        follow = lambda prec: steps.train_steps(
            cell.model, cell.optim, cell.sync, cfg, comp,
            jax.tree.unflatten(treedef, raw["p0"]), raw["first"], cell.chips, prec)
        refr = follow("float32")
        phase("reference's three steps", seed)
        sides = [("program", check.program_readings(
            cell.optim, cell.sync, cfg["optimizer"], raw["p0"], raw["probe1"],
            raw["p3"], raw["loss"]), raw["probe1"]["aux"])]
        if seed in control:
            got = follow(args.precision)
            phase("control's three steps", seed)
            sides.append(("control", got, cell.model.aux_as_probed(got["aux1"], cfg)))
        for who, got, aux in sides:
            numbers = check.gap_numbers(got, refr, cell.sync.KINDS)
            numbers.update(cell.model.model_numbers(aux, refr["aux1"], cfg,
                                                    cell.check_params))
            line = {"workload": args.workload, "seed": seed, "who": who,
                    "numbers": numbers, "aux": [list(map(float, a.ravel()))
                                                for a in map(jax.numpy.asarray, aux)]}
            for k in ("loss", "grad1", "dparam"):
                line[k] = [list(map(float, got[k])), list(map(float, refr[k]))]
            print("READING " + json.dumps(line), flush=True)
        del raw, refr, sides
        phase("numbers", seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
