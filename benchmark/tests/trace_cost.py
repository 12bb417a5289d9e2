"""What tracing costs a cell, on the chip, in one process: windows of the same
step count run untraced and under each way of tracing, with the host loop's
split (wait, copy, dispatch), the rate and the traced idle share of each.
How the host tracer level was chosen, and why a loader-fed cell reports no
idle share (PERF.md section 6).

    python3 benchmark/tests/trace_cost.py --workload W [--steps 40]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
import trace_reduce  # noqa: E402

# (name, start_trace's arguments or None for an untraced window, seconds run
# under the profiler before the window opens)
VARIANTS = [
    ("untraced", None, 0.0),
    ("host2", {"host_level": 2}, 0.0),
    ("host1", {"host_level": 1}, 0.0),
    ("host1_settle2", {"host_level": 1}, 2.0),
    ("host1_xla_only_settle2", {"host_level": 1, "tpu_trace_mode": "TRACE_ONLY_XLA"}, 2.0),
    ("untraced_again", None, 0.0),
]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--seed", type=int, default=3000000021)
    args = p.parse_args()
    cell = run.load_cell(args.workload)

    import jax

    from feed import Feed
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("tracing costs are read on the chip")
    prog = cell.builder.build(cell.cfg, cell.traffic, devices[:cell.chips], cell.model)
    seed32 = args.seed % 2147483647
    state = prog.make_state(seed32)
    feed = Feed(prog, cell.traffic["feed"], seed32)
    copy_s, dispatch_s = [], []

    def timed_step(st, batch):
        t = time.perf_counter()
        copy_s.append(t - feed.ready_t)
        out = prog.train_step(st, batch)
        dispatch_s.append(time.perf_counter() - t)
        return out

    def epoch(st, **kw):
        copy_s.clear()
        dispatch_s.clear()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            st, acc = prog.run_epoch(timed_step, st, feed.batches(**kw))
            feed.close()
        wall = time.perf_counter() - t0
        return st, {"steps": acc.steps, "rate": acc.steps * prog.global_batch / wall,
                    "host_ms": {"wait": run.spread_ms(feed.wait_s),
                                "copy": run.spread_ms(copy_s),
                                "dispatch": run.spread_ms(dispatch_s)}}

    state, _ = epoch(state, seconds=run.WARM_SECONDS)
    trace_dir = os.path.join(run.ROOT, ".bench_trace", "trace_cost")
    for name, options, settle in VARIANTS:
        if options is None:
            state, note = epoch(state, count=args.steps)
        else:
            shutil.rmtree(trace_dir, ignore_errors=True)
            run.start_trace(trace_dir, **options)
            if settle:
                state, _ = epoch(state, count=int(
                    args.steps * settle / run.TRACE_SECONDS))
            state, note = epoch(state, count=args.steps)
            jax.profiler.stop_trace()
            ex = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
            note["idle_share"] = 1.0 - trace_reduce.busy_seconds(ex) / trace_reduce.window_seconds(ex)
            note["window_s"] = trace_reduce.window_seconds(ex)
            note["idle_gaps"] = trace_reduce.idle_gaps(ex, 4)
            shutil.rmtree(trace_dir, ignore_errors=True)
        print("TRACE_COST " + json.dumps({"variant": name, **note}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
