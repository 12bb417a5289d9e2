"""The readers of source ``program_span`` on a scripted timeline: each returns
the scripted number from the ``run_train_epoch`` call before the last, and
None, never a number from another window, when the calls' step counts do not
line up with the run's.  CPU only, no device and no real clock:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_loop_readers.py -q
"""

from __future__ import annotations

import os
import sys
import threading
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402

from tpu_compressed_dp.obs import trace  # noqa: E402

MS = 1_000_000


class Clock:
    t = 0

    def __call__(self):
        return self.t


class Output:
    """A step output that is ready when the test says, at the scripted time."""

    def __init__(self, clock, done_at):
        self.clock, self.done_at = clock, done_at
        self.ready = threading.Event()

    def block_until_ready(self):
        assert self.ready.wait(10)
        self.clock.t = self.done_at


def one_call(tl, clk, steps, fetch_end):
    """A call of ``steps`` = [(data, copy, dispatch, done)], ms from the
    call's begin; its fetch ends, and the call closes, at ``fetch_end``."""
    begin = clk.t
    tl.begin_call()
    outs = []
    for data, copy, dispatch, done in steps:
        for name, ms in (("data_wait", data), ("to_device", copy),
                         ("dispatch", dispatch)):
            with tl.span(name):
                clk.t += ms * MS
        outs.append(Output(clk, begin + done * MS))
        tl.step_done({"loss": outs[-1]})
    enqueued = clk.t
    for out in outs:
        out.ready.set()
    assert tl.flush(10)
    clk.t = enqueued
    with tl.span("fetch"):
        clk.t = begin + fetch_end * MS
    tl.end_call()


# the untraced part: step 0 opens on a drained device; step 1 finds the queue
# full; step 2 waits 280 ms on its data and is enqueued 90 ms after the device
# ran dry, 84 of them inside next(); step 3 is queued behind it
UNTRACED = [(10, 1, 5, 116), (1, 1, 2, 216), (280, 1, 5, 406), (1, 1, 3, 506)]
WALL = 506.0
# twelve quick calls on a slow device: from the ninth on, eight are outstanding
# and the runtime holds the call
QUEUED = [(0, 0, 2 if i < 8 else 50, 1000 + 100 * i) for i in range(12)]


@pytest.fixture
def timeline(monkeypatch):
    clk = Clock()
    tl = trace.StepTimeline(capacity=trace.PROCESS_CAPACITY, clock=clk)
    monkeypatch.setattr(trace, "_PROCESS_TIMELINE", tl)
    return tl, clk


def script(timeline, untraced, traced_steps=2):
    tl, clk = timeline
    one_call(tl, clk, [(1, 1, 1, 50)] * 3, 60)             # a warm-up call
    one_call(tl, clk, untraced, max(s[3] for s in untraced))
    one_call(tl, clk, [(1, 1, 1, 40 * (i + 1)) for i in range(traced_steps)],
             40 * traced_steps)
    return types.SimpleNamespace(traced_steps=traced_steps,
                                 dispatch_s=[0.0] * len(untraced))


@pytest.mark.parametrize("metric,steps,expected", [
    ("loop_data_wait_ms", UNTRACED, 5.5),           # median of 10, 1, 280, 1
    ("loop_to_device_ms", UNTRACED, 1.0),
    ("loop_dispatch_ms", UNTRACED, 4.0),            # median of 5, 2, 5, 3
    ("loop_dispatch_ms", QUEUED, 2.0),              # the eight that found room
    ("device_starved_share", UNTRACED, 100 * 90 / WALL),
    ("device_starved_share.fed", UNTRACED, 100 * 90 / WALL),
    ("data_wait_starved_share", UNTRACED, 100 * 84 / WALL),
    ("step_ms_p95", UNTRACED, 181.0),               # of 100, 190, 100
    ("step_ms_p95.fed", UNTRACED, 181.0),
])
def test_reader_returns_the_scripted_number(timeline, metric, steps, expected):
    ctx = script(timeline, steps)
    assert run.load_reader(metric).read(ctx) == pytest.approx(expected)


READERS = ["loop_data_wait_ms", "loop_to_device_ms", "loop_dispatch_ms",
           "device_starved_share", "data_wait_starved_share", "step_ms_p95"]


@pytest.mark.parametrize("metric", READERS)
def test_reader_refuses_calls_that_do_not_line_up(timeline, metric, monkeypatch):
    read = run.load_reader(metric).read
    ctx = script(timeline, UNTRACED)
    assert read(ctx) is not None
    # the last call is not the traced part
    assert read(types.SimpleNamespace(traced_steps=3, dispatch_s=[0.0] * 4)) is None
    # the call before it is not the untraced part
    assert read(types.SimpleNamespace(traced_steps=2, dispatch_s=[0.0] * 5)) is None
    # an untraced run
    assert read(types.SimpleNamespace(traced_steps=0, dispatch_s=[0.0] * 4)) is None
    # a later call moves the window: what was lined up no longer is
    tl, clk = timeline
    one_call(tl, clk, [(1, 1, 1, 40), (1, 1, 1, 80)], 80)
    assert read(ctx) is None
    # a program without the timeline (the parent commit): nothing, no error
    monkeypatch.delattr(trace, "process_timeline")
    assert read(ctx) is None


def test_manifest_lists_the_new_readers():
    manifest = run.read_json("BENCHMARK.json")
    mine = [m for m in manifest["per_layer"] if m["source"] == "program_span"
            and m["name"].split(".")[0] in READERS]
    assert len(mine) == 8
    fed = [m["name"] for m in run.load_cell("resnet152_dense_fed").per_layer]
    assert {"loop_data_wait_ms", "loop_to_device_ms", "loop_dispatch_ms",
            "device_starved_share.fed", "data_wait_starved_share",
            "step_ms_p95.fed"} <= set(fed)
    staged = [m["name"] for m in run.load_cell("resnet50_dense_staged").per_layer]
    assert {"device_starved_share", "step_ms_p95"} <= set(staged)
    assert "device_starved_share.fed" not in staged
