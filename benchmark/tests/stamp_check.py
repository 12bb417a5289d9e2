"""The program's completion stamps and spans held against the device trace, on
the chip, in one process: one traced window of a cell, and over it

  * clock: the start of every ``tcdp.loop.*`` annotation in the trace (plus the
    trace's ``profile_start_time``) less the start the program recorded for the
    same span of the same step: they are to coincide, so the records lay over
    the device operations with no fitted offset;
  * lateness: each step's completion stamp less the end of that step's last
    device operation (the step's event on the device's "XLA Modules" line),
    and where in the ``dispatch`` span the device took a step up when it
    was idle (the stamps take the span's end as the enqueue);
  * the stamps' starved share beside the trace's idle share of that same
    traced call, whole and with the first step left out, and the part of the
    trace's idle time that lies between steps (the rest lies between the
    operations of one step, which no stamp can see).

An untraced window of the same step count runs first, for the starved share
the profiler does not touch.  A watcher of the tool's own stamps when each
step's input batch was on the device: a step that is enqueued still waits for
its operands, which the program's ``starved`` (no step queued) does not count.
Both windows' per-step times go to ``chiprun_out/stamps_<workload>_<part>.json``.
``--recorder_steps N`` also times the recorder alone on the host: N scripted
steps with and without it.

    python3 benchmark/tests/stamp_check.py --workload W [--steps 40]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
import trace_reduce  # noqa: E402

LOOP_PREFIX = "tcdp.loop."


def recorder_cost_us(steps: int) -> dict:
    """Host microseconds a step of the loop's recording (three spans, the
    hand-over to the watcher) over ``steps`` scripted steps whose output is
    ready, against the same loop without it."""
    import jax.numpy as jnp

    from tpu_compressed_dp.obs.trace import StepTimeline

    out = {"loss": jnp.zeros(()).block_until_ready()}
    work = lambda: None

    def bare():
        t = time.perf_counter()
        for _ in range(steps):
            work(); work(); work()
        return time.perf_counter() - t

    def recorded():
        tl = StepTimeline(capacity=4096)
        tl.begin_call()
        t = time.perf_counter()
        for _ in range(steps):
            with tl.span("data_wait"):
                work()
            with tl.span("to_device"):
                work()
            with tl.span("dispatch"):
                work()
            tl.step_done(out)
        loop = time.perf_counter() - t
        tl.flush(10.0)
        return loop, time.perf_counter() - t

    bare()
    recorded()
    base = min(bare() for _ in range(3))
    runs = [recorded() for _ in range(3)]
    loop, whole = min(r[0] for r in runs), min(r[1] for r in runs)
    return {"steps": steps, "loop_us_per_step": 1e6 * (loop - base) / steps,
            "with_watcher_drained_us_per_step": 1e6 * (whole - base) / steps}


def read_trace(xplane_path: str) -> dict:
    """{"offset": profile_start_time, "spans": {(name, step): start_ns},
    "ops": [[start, end]], "modules": {name: [[start, end]]}, "lines": ...} of
    the first TPU plane; times as the trace has them (from its start)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    out = {"offset": None, "spans": {}, "ops": [], "modules": {}, "lines": {}}
    device = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            out["offset"] = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/device:TPU:") and device is None:
            device = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(LOOP_PREFIX):
                        step = dict(ev.stats).get("step")
                        out["spans"][(ev.name[len(LOOP_PREFIX):], step)] = int(ev.start_ns)
    if device is not None:
        for line in device.lines:
            events = list(line.events)
            out["lines"][line.name] = len(events)
            if line.name == "XLA Ops":
                out["ops"] = sorted([int(e.start_ns), int(e.start_ns + e.duration_ns)]
                                    for e in events)
            elif line.name == "XLA Modules":
                for e in events:
                    out["modules"].setdefault(e.name, []).append(
                        [int(e.start_ns), int(e.start_ns + e.duration_ns)])
    return out


def quantiles_us(values_ns) -> dict:
    vs = sorted(values_ns)
    if not vs:
        return {}
    pick = lambda q: vs[min(len(vs) - 1, int(q * len(vs)))] / 1e3
    return {"n": len(vs), "min": vs[0] / 1e3, "median": statistics.median(vs) / 1e3,
            "p90": pick(0.9), "max": vs[-1] / 1e3}


def lay_over(call: dict, tr: dict) -> tuple:
    """The traced call's records against the trace: (what to print, each
    step's [start, end] on the device or None where the trace shows none)."""
    recs = call["records"]
    off = tr["offset"]
    note = {"steps": len(recs), "device_lines": tr["lines"]}
    if off is None:
        note["error"] = "the trace has no profile_start_time"
        return note, None
    # ---- one clock: the annotation's start against the recorded start
    pairs = []
    for r in recs:
        for name in ("data_wait", "to_device", "dispatch"):
            key = (name, r["ord"])
            if r[name] is not None and key in tr["spans"]:
                pairs.append(tr["spans"][key] + off - r[name][0])
    note["annotation_minus_record_us"] = quantiles_us(pairs)
    # ---- the steps on the device: the module that ran once a step
    n = len(recs)
    steps = [sorted(m) for m in tr["modules"].values() if len(m) == n]
    if steps:           # of several, the one the device spent its time in
        mods = max(steps, key=lambda m: sum(e - s for s, e in m))
        note["steps_from"] = "XLA Modules"
    elif tr["ops"] and len(tr["ops"]) % n == 0:
        # every step runs the same operations: equal runs of the ops line
        k = len(tr["ops"]) // n
        runs = [tr["ops"][i * k:(i + 1) * k] for i in range(n)]
        mods = [[r[0][0], max(e for _, e in r)] for r in runs]
        note["steps_from"] = f"XLA Ops, {k} a step"
    else:
        note["error"] = ("the trace does not show the steps: modules " + json.dumps(
            {k[:60]: len(v) for k, v in tr["modules"].items()})
            + f", {len(tr['ops'])} ops")
        return note, None
    late = [r["done"] - (m[1] + off) for r, m in zip(recs, mods)
            if r["done"] is not None]
    note["stamp_lateness_us"] = quantiles_us(late)
    note["device_step_ms"] = statistics.median(m[1] - m[0] for m in mods) / 1e6
    # where in the dispatch span the device took the step up, for the steps
    # that found it idle: the stamps take the span's end as the enqueue
    idle_at = [(r, m) for r, m in zip(recs, mods)
               if r["starved"] and r["dispatch"] is not None]
    note["device_start_after_dispatch_start_us"] = quantiles_us(
        m[0] + off - r["dispatch"][0] for r, m in idle_at)
    note["dispatch_end_after_device_start_us"] = quantiles_us(
        r["dispatch"][1] - (m[0] + off) for r, m in idle_at)
    # ---- idle: the trace's, and what the stamps say
    busy = trace_reduce.union(tr["ops"])

    def clipped_ns(intervals, t0, t1):
        return trace_reduce.length([[max(s, t0), min(e, t1)] for s, e in intervals
                                    if min(e, t1) > max(s, t0)])

    def idle_ns(t0, t1):
        return (t1 - t0) - clipped_ns(busy, t0, t1)

    def between_steps_ns(t0, t1):
        """Of [t0, t1], what lies inside no step's module event."""
        return (t1 - t0) - clipped_ns(trace_reduce.union(mods), t0, t1)

    t0, t1 = call["t0"] - off, call["t1"] - off
    starved = [r["starved"] or 0 for r in recs]
    note["whole_call"] = {
        "wall_ms": (t1 - t0) / 1e6,
        "trace_idle_share": 100.0 * idle_ns(t0, t1) / (t1 - t0),
        "trace_idle_between_steps_share": 100.0 * between_steps_ns(t0, t1) / (t1 - t0),
        "stamps_starved_share": 100.0 * sum(starved) / (t1 - t0)}
    # the first step left out: from the first step's end on the device
    f0 = mods[0][1]
    note["first_step_left_out"] = {
        "wall_ms": (t1 - f0) / 1e6,
        "trace_idle_share": 100.0 * idle_ns(f0, t1) / (t1 - f0),
        "trace_idle_between_steps_share": 100.0 * between_steps_ns(f0, t1) / (t1 - f0),
        "stamps_starved_share": 100.0 * sum(starved[1:]) / (t1 - f0)}
    by = {}
    for r in recs[1:]:
        for k, v in (r["starved_by"] or {}).items():
            by[k] = by.get(k, 0) + v
    note["starved_under_ms"] = {k: v / 1e6 for k, v in by.items()}
    return note, mods


def untraced_note(call: dict) -> dict:
    recs = call["records"]
    wall = call["t1"] - call["t0"]
    done = [r["done"] for r in recs]
    gaps = [b - a for a, b in zip(done, done[1:])]
    return {"steps": len(recs), "wall_ms": wall / 1e6,
            "stamps_starved_share_first_left_out":
                100.0 * sum(r["starved"] or 0 for r in recs[1:]) / wall,
            "completion_interval_ms": {
                "median": statistics.median(gaps) / 1e6, "max": max(gaps) / 1e6}}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--seed", type=int, default=3000000024)
    p.add_argument("--recorder_steps", type=int, default=0)
    p.add_argument("--require_tpu", type=int, default=1)
    args = p.parse_args()
    cell = run.load_cell(args.workload)

    import jax

    from feed import Feed
    from tpu_compressed_dp.obs.trace import process_timeline
    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if args.require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit("stamps are held against the device trace on the chip")
    if args.recorder_steps:
        print("RECORDER_COST " + json.dumps(recorder_cost_us(args.recorder_steps)),
              flush=True)
    prog = cell.builder.build(cell.cfg, cell.traffic, devices[:cell.chips], cell.model)
    seed32 = args.seed % 2147483647
    state = prog.make_state(seed32)
    feed = Feed(prog, cell.traffic["feed"], seed32)

    # when each step's input batch was on the device, by a second watcher of
    # the tool's own: a step that is enqueued still waits for its operands
    import queue
    import threading

    inputs, input_ready = queue.SimpleQueue(), []

    def watch_inputs():
        while True:
            batch = inputs.get()
            if batch is None:
                return
            jax.block_until_ready(batch)
            input_ready.append(time.time_ns())

    threading.Thread(target=watch_inputs, daemon=True).start()

    def step(st, batch):
        inputs.put(batch)
        return prog.train_step(st, batch)

    def epoch(st, **kw):
        input_ready.clear()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            st, acc = prog.run_epoch(step, st, feed.batches(**kw))
            feed.close()
        return st, acc.steps * prog.global_batch / (time.perf_counter() - t0)

    def dump(part, call, mods=None, off=0):
        """Per-step times in ms from the call's begin, for reading by hand."""
        rel = lambda ns: None if ns is None else round((ns - call["t0"]) / 1e6, 3)
        rows = []
        for i, r in enumerate(call["records"]):
            row = {"ord": r["ord"], "t0": rel(r["t0"]),
                   **{k: [rel(r[k][0]), rel(r[k][1])] for k in
                      ("data_wait", "to_device", "dispatch") if r[k] is not None},
                   "input_ready": rel(input_ready[i]) if i < len(input_ready) else None,
                   "done": rel(r["done"]),
                   "starved": r["starved"] and r["starved"] / 1e6}
            if mods:
                row["device"] = [rel(mods[i][0] + off), rel(mods[i][1] + off)]
            rows.append(row)
        os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(run.ROOT, "chiprun_out",
                               f"stamps_{args.workload}_{part}.json"), "w") as f:
            json.dump({"fetch": [rel(t) for t in call["fetch"]], "t1": rel(call["t1"]),
                       "steps": rows}, f)

    state, _ = epoch(state, seconds=run.WARM_SECONDS)
    state, rate = epoch(state, count=args.steps)
    call = process_timeline().calls()[-1]
    dump("untraced", call)
    print("STAMP_CHECK " + json.dumps({
        "workload": args.workload, "part": "untraced", "rate": rate,
        **untraced_note(call)}), flush=True)

    trace_dir = os.path.join(run.ROOT, ".bench_trace", "stamp_check")
    shutil.rmtree(trace_dir, ignore_errors=True)
    run.start_trace(trace_dir)
    state, rate = epoch(state, count=args.steps)
    jax.profiler.stop_trace()
    call = process_timeline().calls()[-1]
    tr = read_trace(trace_reduce.find_xplane(trace_dir))
    note, mods = lay_over(call, tr)
    dump("traced", call, mods, tr["offset"] or 0)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print("STAMP_CHECK " + json.dumps({
        "workload": args.workload, "part": "traced", "rate": rate, **note}), flush=True)
    inputs.put(None)
    feed.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
