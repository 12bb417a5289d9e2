"""One run of the benchmark with its step-interval record kept.

Runs ``benchmark/run.py`` of the checkout in the CURRENT directory in this
process, with the arguments given (``--workload ... --seed ... --seconds ...
--trace 0|1``), and afterwards prints one more line, ``intervals {...}``:
the completion-to-completion intervals of the measured window as the
program's own ``obs.trace.StepTimeline`` stamped them (the ``done`` stamps of
the window's ``run_train_epoch`` call: the last call of an untraced run, the
one before it when the traced part followed).  The result line says how fast
a run was; this line says why one run was not as fast as the others:

  * ``p50_ms``/``p95_ms``/``p99_ms``/``max_ms`` and ``steps``;
  * ``long``: every interval more than 1.5 x the median, as ``[step, ms]`` —
    one of hundreds of ms among intervals at the median is a stall of the
    process or the machine; none, with every interval longer, is a slower
    program (compare the runs' ``breakdown`` names and compile logs);
  * ``snapshot``: the timeline's own ``time/step_p50_ms``, ``p95``, ``p99``
    (over its whole ring: set-up's steps too);
  * with ``--intervals_out FILE``, every interval in ms as JSON.

and, where the checkout's program stamps host events (``obs.trace.HostEvents``,
PR 39; a checkout without them prints no such line), a line ``host_events
{...}`` of what the host did that is no span of the loop:

  * ``setup``: seconds of set-up (everything that ended before the window's
    call began) that each kind of event covers (``trace``, ``lower``,
    ``compile``, ``cache_read``: the length of the union, the events nest),
    ``gc_in_trace_lower_s`` (collector passes of 1 ms or more inside a trace
    or a lowering), and ``top``: per kind the five names of most inclusive
    time as ``[name, count, seconds]``;
  * ``totals_at_window``: the ring's totals ``{kind: [count, seconds]}`` when
    the window's call began (every collector pass, also the short ones);
  * ``window``: the collector's passes during the window's call
    (``gc_passes`` with every generation counted, ``gc_s``, and of the passes
    of 1 ms or more ``gc_ms_max``), and ``long``: for every interval of
    ``intervals.long`` the events that overlap it, ``[step, ms, [[kind, name,
    ms], ...]]``; an empty list is "nothing recorded".
  * with ``--events_out FILE``, every event the ring holds as ``[kind, name,
    start_ms, ms]`` (start from the first event's), in the order they ended:
    a set-up PR's per-function table is made from this.

A perf PR runs it in ``git archive`` checkouts of parent and change, side by
side in one call on the chip (parent, change, change, parent; one seed a
pair), six pairs, before it claims a gain:

    cd bench_checkout/change && python3 ../../tools/step_intervals.py \\
        --workload resnet50_topk_lw_staged --seed 3000000101 --seconds 20 --trace 0

The file imports nothing of the repository it lies in, so the same copy
drives the parent's checkout.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys


def window_intervals_ms(calls, traced: bool):
    """Intervals between consecutive ``done`` stamps of the measured window's
    call, in ms; None while a stamp is missing."""
    want = -2 if traced else -1
    if len(calls) < -want:
        return None
    done = [r["done"] for r in calls[want]["records"]]
    if len(done) < 2 or any(d is None for d in done):
        return None
    return [(b - a) / 1e6 for a, b in zip(done, done[1:])]


def summary(ms):
    q = sorted(ms)
    at = lambda p: q[min(len(q) - 1, int(p * len(q)))]
    median = statistics.median(q)
    return {"steps": len(ms) + 1, "p50_ms": median, "p95_ms": at(0.95),
            "p99_ms": at(0.99), "max_ms": q[-1],
            "long": [[i + 1, round(v, 3)] for i, v in enumerate(ms)
                     if v > 1.5 * median]}


def merged(spans) -> list:
    """Merged, sorted ``[start_ns, end_ns]`` of any spans."""
    out = []
    for start, end in sorted(spans):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def host_events_line(calls, traced: bool, before_window):
    """What the program's host events say of set-up and of the window's
    long intervals.  ``before_window(t)``: the events that overlap the time
    up to ``t``."""
    want = -2 if traced else -1
    if len(calls) < -want:
        return None
    call = calls[want]
    before = [e for e in before_window(call["t0"]) if e[3] <= call["t0"]]
    of = lambda *kinds: [(s, e) for k, _, s, e in before if k in kinds]
    seconds = lambda spans: round(sum(e - s for s, e in merged(spans)) / 1e9, 3)
    kinds = ("trace", "lower", "compile", "cache_read")
    setup = {k + "_s": seconds(of(k)) for k in kinds}
    tracing = merged(of("trace", "lower"))
    setup["gc_in_trace_lower_s"] = round(sum(
        max(0, min(e, b) - max(s, a)) for s, e in of("gc") for a, b in tracing
    ) / 1e9, 3)
    setup["top"] = {}
    for kind in kinds[:3]:
        by_name = {}
        for k, name, s, e in before:
            if k == kind:
                n, sec = by_name.get(name, (0, 0.0))
                by_name[name] = (n + 1, sec + (e - s) / 1e9)
        setup["top"][kind] = [
            [name, n, round(sec, 3)] for name, (n, sec) in
            sorted(by_name.items(), key=lambda kv: -kv[1][1])[:5]]
    at = {k: [n, round(ns / 1e9, 3)] for k, (n, ns) in call["totals0"].items()}
    gc0 = call["totals0"].get("gc", (0, 0))
    gc1 = (call["totals1"] or {}).get("gc", gc0)
    window = {"gc_passes": gc1[0] - gc0[0],
              "gc_s": round((gc1[1] - gc0[1]) / 1e9, 4),
              "gc_ms_max": round(max(((e - s) / 1e6 for k, _, s, e
                                      in call["events"] if k == "gc"),
                                     default=0.0), 3)}
    done = [r["done"] for r in call["records"]]
    if len(done) >= 2 and all(d is not None for d in done):
        ms = [(b - a) / 1e6 for a, b in zip(done, done[1:])]
        median = statistics.median(ms)
        window["long"] = [
            [i + 1, round(v, 3),
             [[k, name, round((e - s) / 1e6, 3)] for k, name, s, e in call["events"]
              if s <= done[i + 1] and e >= done[i]]]
            for i, v in enumerate(ms) if v > 1.5 * median]
    return {"setup": setup, "totals_at_window": at, "window": window}


def main(argv) -> int:
    mine = argparse.ArgumentParser(add_help=False)
    mine.add_argument("--intervals_out")
    mine.add_argument("--events_out")
    args, argv = mine.parse_known_args(argv)
    traced = "--trace" in argv and argv[argv.index("--trace") + 1] == "1"
    root = os.getcwd()
    spec = importlib.util.spec_from_file_location(
        "run", os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    sys.modules["run"] = run
    spec.loader.exec_module(run)
    rc = run.main(argv)

    from tpu_compressed_dp.obs import trace

    timeline = trace.process_timeline()
    ms = window_intervals_ms(timeline.calls(), traced)
    snapshot = timeline.snapshot()
    line = {"snapshot": {k: snapshot[k] for k in (
        "time/step_p50_ms", "time/step_p95_ms", "time/step_p99_ms")}}
    if ms is not None:
        line.update(summary(ms))
        if args.intervals_out:
            out = os.path.abspath(args.intervals_out)
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w") as f:
                json.dump([round(v, 4) for v in ms], f)
    print("intervals " + json.dumps(line), flush=True)
    if hasattr(timeline, "host_events"):
        events = host_events_line(timeline.calls(), traced,
                                  lambda t: timeline.host_events(None, t))
        if events is not None:
            print("host_events " + json.dumps(events), flush=True)
        if args.events_out:
            out = os.path.abspath(args.events_out)
            os.makedirs(os.path.dirname(out), exist_ok=True)
            held = timeline.host_events()
            base = held[0][2] if held else 0
            with open(out, "w") as f:
                json.dump([[k, name, round((s - base) / 1e6, 3),
                            round((e - s) / 1e6, 3)] for k, name, s, e in held], f)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
