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


def main(argv) -> int:
    mine = argparse.ArgumentParser(add_help=False)
    mine.add_argument("--intervals_out")
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
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
