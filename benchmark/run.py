"""Run one cell of BENCHMARK.json and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name, and this file has no cell's, model's,
method's or metric's name in it:

  configs[].file                          the configuration; it names its plain
                                          reference (``"reference"``), the builder
                                          of the system under test (``"program"``)
                                          and, by ``optimizer.kind``,
                                          ``benchmark/reference/optim/<kind>.py``
  benchmark/traffic/<traffic>.json        the traffic mix; it names its sync
                                          semantics, ``benchmark/sync/<sync>.py``
  benchmark/limits/<workload>.json        the limits of ``correct``
  benchmark/layer_metrics/<metric>.py     one reader a per-layer metric (a dotted
                                          suffix such as ``mfu.fed`` falls back
                                          to ``mfu.py``)

A later PR adds a cell, a model, a compression method or a metric by adding
such files and entries, and edits none.  A name that has no file is an error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHECK_STEPS = 3        # the reference follows the first three steps
WARM_SECONDS = 4.0     # warm by time: a freshly attached chip ramps for seconds
TRACE_SECONDS = 3.0    # the traced part of a --trace 1 window, by the step count of that long
# level 1 keeps the benchmark's own annotations in a trace and leaves out the
# runtime's per-call host events
HOST_TRACER_LEVEL = 1


def read_json(path: str):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def load_module(path: str):
    """The module in the file ``path`` (relative to the checkout)."""
    full = os.path.join(ROOT, path)
    if not os.path.exists(full):
        raise SystemExit(f"no file {path}")
    name = "bench_" + os.path.splitext(path)[0].replace("/", "_").replace(".", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, full)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def make_cell(name, chips, cfg, traffic, limits, end_to_end, per_layer):
    """A cell with the modules its files name."""
    sync = load_module(f"benchmark/sync/{traffic['sync']}.py")
    sync.accepts(traffic["compression"])
    return types.SimpleNamespace(
        name=name, chips=chips, cfg=cfg, traffic=traffic,
        limits=limits["limits"], check_params=limits.get("params", {}),
        end_to_end=end_to_end, per_layer=per_layer,
        model=load_module(cfg["reference"]), builder=load_module(cfg["program"]),
        optim=load_module(f"benchmark/reference/optim/{cfg['optimizer']['kind']}.py"),
        sync=sync)


def load_cell(workload: str) -> types.SimpleNamespace:
    manifest = read_json("BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    traffic = read_json(f"benchmark/traffic/{cell['traffic']}.json")
    if traffic["chips"] != cell["chips"]:
        raise SystemExit("the traffic file and the cell disagree on chips")

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return make_cell(
        workload, cell["chips"], read_json(conf["file"]), traffic,
        read_json(f"benchmark/limits/{workload}.json"),
        [m for m in manifest["end_to_end"] if mine(m)],
        [m for m in manifest["per_layer"] if mine(m)])


def load_reader(metric: str):
    for stem in (metric, metric.split(".")[0]):
        if os.path.exists(os.path.join(HERE, "layer_metrics", stem + ".py")):
            return load_module(f"benchmark/layer_metrics/{stem}.py")
    raise SystemExit(f"no reader benchmark/layer_metrics/{metric}.py")


def device_peak_bytes(device) -> int:
    """Peak of the live buffers plus the peak reserved for the programs'
    temporaries.  This runtime counts the two apart, and the two peaks need not
    fall at the same moment, so the sum is an upper bound of the true peak."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def drive_first_steps(prog, state, epoch, feed, mark=lambda name: None):
    """The first steps, through the window's own call and feed.  What they
    leave in the state is held against the reference once the window has
    closed: losses, what the builder's probe reads after one step (optimizer
    state, residual, the model's statistics), the parameters after the last."""
    import jax
    import numpy as np

    raw = {"p0": prog.probe(state, params_only=True)["params"], "loss": []}
    for i in range(CHECK_STEPS):
        state, acc = epoch(state, count=1, keep_first=CHECK_STEPS)
        raw["loss"].append(acc.mean("loss"))
        if i == 0:
            raw["probe1"] = prog.probe(state)
            raw["counters"] = {k: acc.mean(k) for k in acc.sums
                               if k.startswith("comm/")}
            mark("compile_or_cache_load")
    raw["p3"] = prog.probe(state, params_only=True)["params"]
    raw["first"] = [(np.array(jax.device_get(b["input"]), copy=True),
                     np.array(jax.device_get(b["target"]), copy=True))
                    for b in feed.first]
    feed.first = []
    return state, raw


def follow(cell, raw, precision="float32"):
    """The reference's first steps from the program's seeded weights, on the
    program's first batches: norms, losses and the model's statistics."""
    import jax

    from reference import steps

    treedef = jax.tree.structure(cell.model.param_shapes(cell.cfg),
                                 is_leaf=lambda s: isinstance(s, tuple))
    return steps.train_steps(
        cell.model, cell.optim, cell.sync, cell.cfg, cell.traffic["compression"],
        jax.tree.unflatten(treedef, raw["p0"]), raw["first"], cell.chips, precision)


def both_sides(cell, raw, precision="float32"):
    """(the program's readings, the reference's): the first steps of both.
    The program's side is reduced first and what it was read from leaves
    ``raw``, so the reference's lists come where the program's were and not
    beside them.  ``precision`` other than float32 puts the reference computed
    in that lower precision in the program's place: the control, followed
    after the float32 reference, of which only the readings are left."""
    import check
    import flops

    if precision != "float32":
        refr = follow(cell, raw)
        return follow(cell, raw, precision), refr
    prog = check.program_readings(
        cell.optim, cell.sync, cell.cfg["optimizer"], cell.traffic["compression"],
        raw, flops.leaf_sizes(cell.model, cell.cfg))
    return prog, follow(cell, raw)


def compared_numbers(cell, prog, refr, counts, precision="float32") -> dict:
    """Every number that has a limit, from the two sides' readings."""
    import check

    numbers = check.gap_numbers(prog, refr, cell.sync.KINDS)
    if precision == "float32":
        numbers.update(cell.model.model_numbers(
            prog["aux1"], refr["aux1"], cell.cfg, cell.check_params))
        numbers.update(prog["exact"])
        numbers.update(counts)
    else:
        # the control has no state or counters of its own; its auxiliary
        # outputs stand where the program's were read back from the state
        numbers.update(cell.model.model_numbers(
            cell.model.aux_as_probed(prog["aux1"], cell.cfg), refr["aux1"],
            cell.cfg, cell.check_params))
        numbers.update({k: 0 for k in cell.limits if k not in numbers})
    return numbers


def judge(cell, raw, counts, precision="float32"):
    """[(name, value, limit, ok)] for every number compared."""
    import check

    prog, refr = both_sides(cell, raw, precision)
    return check.compare(compared_numbers(cell, prog, refr, counts, precision),
                         cell.limits)


def compare_line(name, value, limit, ok) -> str:
    return f"compare {name} = {value:.6g} (limit {limit:g}) {'ok' if ok else 'FAIL'}"


def spread_ms(seconds) -> list:
    """[10th percentile, median, 90th percentile] of host times, in ms."""
    import numpy as np

    if not len(seconds):
        return []
    return [round(1e3 * float(q), 3) for q in np.percentile(seconds, [10, 50, 90])]


def start_trace(trace_dir: str, host_level: int = HOST_TRACER_LEVEL,
                tpu_trace_mode=None):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = host_level
    if tpu_trace_mode:
        opts.advanced_configuration = {"tpu_trace_mode": tpu_trace_mode}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, wrap_step=None, warm_seconds=WARM_SECONDS,
             trace_seconds=TRACE_SECONDS) -> dict:
    """One run of one cell.  ``require_tpu=False`` and ``wrap_step`` exist for
    the tests under benchmark/tests: the first skips the look for a chip, the
    second puts a broken step under the timed path."""
    marks = [("start", T_START)]
    mark = lambda name: marks.append((name, time.perf_counter()))

    import jax
    import numpy as np

    import flops
    import trace_reduce
    from feed import Feed

    from tpu_compressed_dp.parallel.mesh import setup_compile_cache

    cache_dir = setup_compile_cache()
    # every program goes to the cache, also the sub-second ones of set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    mark("import")

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise SystemExit(
            f"the cell needs {cell.chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {dev.platform} ({dev.device_kind})")
    peaks = flops.peaks(dev.device_kind) if require_tpu else {
        "bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    devices = devices[:cell.chips]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(
            (kw.get("fun_name", "?"), duration))
        if event.endswith("backend_compile_duration") else None)
    mark("devices")

    prog = cell.builder.build(cell.cfg, cell.traffic, devices, cell.model)
    seed32 = int(seed) % 2147483647
    state = prog.make_state(seed32)
    jax.block_until_ready(state.params)
    mark("init")
    feed = Feed(prog, cell.traffic["feed"], seed32)
    mark("data_staging")

    dispatch_s, copy_s = [], []
    step = prog.train_step if wrap_step is None else wrap_step(prog.train_step)

    def timed_step(st, batch):
        t = time.perf_counter()
        copy_s.append(t - feed.ready_t)     # the loop's host-to-device copy
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = step(st, batch)
        dispatch_s.append(time.perf_counter() - t)
        return out

    def epoch(st, **kw):
        dispatch_s.clear()
        copy_s.clear()
        st, acc = prog.run_epoch(timed_step, st, feed.batches(**kw))
        feed.close()
        return st, acc

    def host_split():
        """Where the host loop's time went, per step of the epoch just run."""
        return {"wait": spread_ms(feed.wait_s), "copy": spread_ms(copy_s),
                "dispatch": spread_ms(dispatch_s)}

    n0 = len(compiles)
    state, raw = drive_first_steps(prog, state, epoch, feed, mark)
    compile_note = [[n, round(s, 2)] for n, s in compiles[n0:]]
    counters = raw["counters"]
    mark("check_steps")
    state, acc = epoch(state, seconds=warm_seconds)
    mark("warm_up")
    setup_s = time.perf_counter() - T_START

    # ---- the measured window -------------------------------------------
    compiles_before = len(compiles)
    plain = max(seconds - trace_seconds, 0.0) if trace else seconds
    t0 = time.perf_counter()
    state, acc = epoch(state, seconds=plain)
    wall = time.perf_counter() - t0
    attempted = acc.steps
    failed = 0 if np.isfinite(acc.mean("loss")) else acc.steps
    rate = acc.steps * prog.global_batch / wall if wall > 0 else 0.0
    wait_s, window_dispatch_s = list(feed.wait_s), list(dispatch_s)
    # the steps dispatched in each quarter of the dispatching time: a window
    # that is still ramping, or a host that changes pace, shows here
    quarters = np.histogram(feed.ready_ts, bins=4)[0].tolist() if feed.ready_ts else []
    window_note = {"steps": acc.steps, "wall_s": round(wall, 4),
                   "steps_by_quarter": quarters, "host_ms": host_split()}

    extract = None
    traced_steps = 0
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        start_trace(trace_dir)
        # by count, at the plain part's pace: the host runs some tens of steps
        # ahead of the device, so a deadline would trace twice its length
        pace = acc.steps / wall if wall > 0 else 0.0
        count = max(8, int(pace * trace_seconds))
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            state, tacc = epoch(state, count=count)
        jax.profiler.stop_trace()
        traced_steps = tacc.steps
        attempted += tacc.steps
        failed += 0 if np.isfinite(tacc.mean("loss")) else tacc.steps
        window_note["traced_host_ms"] = host_split()
        if not wait_s:      # host-clock spans come from the untraced part
            wait_s, window_dispatch_s = list(feed.wait_s), list(dispatch_s)
        extract = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles_in_window = len(compiles) - compiles_before
    peak = max(device_peak_bytes(d) for d in devices)

    # ---- correctness, once the window has closed and the state is freed ---
    t_check = time.perf_counter()
    constants = prog.constants
    del state, step, prog
    feed.release()
    jax.clear_caches()      # the step's executable gives back what it reserved
    rows = judge(cell, raw, {"compiles_in_window": compiles_in_window,
                             "failed_steps": failed})
    for row in rows:
        print(compare_line(*row))
    correct = all(ok for *_, ok in rows)
    check_s = time.perf_counter() - t_check

    # ---- where set-up went (a line before the last) ------------------------
    print("setup " + json.dumps({
        "setup_s": round(setup_s, 2),
        "phases": {b[0]: round(b[1] - a[1], 2) for a, b in zip(marks, marks[1:])},
        "programs": compile_note, "cache_dir": os.path.relpath(cache_dir, ROOT),
        "correctness_s_not_in_setup": round(check_s, 2), "window": window_note}))

    metrics = {}
    if not trace:
        values = {cell.traffic["rate_metric"]: rate, "peak_hbm_gb": peak / 1e9,
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise SystemExit(f"end-to-end metric {m['name']!r} has no source "
                                 "in benchmark/run.py")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(
            cfg=cell.cfg, traffic=cell.traffic, chips=cell.chips, peaks=peaks,
            flops=flops, reduce=trace_reduce, extract=extract, model=cell.model,
            sync=cell.sync, constants=constants,
            traced_steps=traced_steps, traced_rate=rate, counters=counters,
            wait_s=wait_s, dispatch_s=window_dispatch_s,
            feed_kind=cell.traffic["feed"]["kind"],
            compressed=cell.traffic["compression"].get("method") is not None)
        for m in cell.per_layer:
            value = load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_seconds(extract)
        device["window_s"] = trace_reduce.window_seconds(extract)
        result["breakdown"] = {"device_ops": trace_reduce.top_device_ops(extract),
                               "idle_gaps": trace_reduce.idle_gaps(extract)}
    # last in the line: every number compared beside its limit
    result["compared"] = {name: [value if np.isfinite(value) else str(value), limit, ok]
                          for name, value, limit, ok in rows}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    result = run_cell(load_cell(args.workload), args.seed, args.seconds,
                      bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    # and as the last lines of standard error, which a record of a run that
    # was not correct keeps
    for name, (value, limit, ok) in result["compared"].items():
        print(compare_line(name, float(value), limit, ok), file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
