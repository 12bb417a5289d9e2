"""The readers of the program's host events (``setup_trace_s``,
``setup_lower_s``, ``setup_compile_s``, ``setup_gc_s``, ``loop_gc_ms_max``) on
hand-made events: set-up ends where the untraced part's ``run_train_epoch``
call begins, nested events count once, and a program without the ring (the
parent commit) reads None and raises nothing.  CPU only, no device, no clock:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_host_event_readers.py -q
"""

from __future__ import annotations

import types

import pytest
from test_loop_readers import MS, UNTRACED, Clock, one_call, run

import trace_reduce

from tpu_compressed_dp.obs import trace

READERS = ["setup_trace_s", "setup_lower_s", "setup_compile_s", "setup_gc_s",
           "loop_gc_ms_max"]


def gc_pass(ring, clk, generation, start_ms, ms):
    clk.t = int(start_ms * MS)
    ring.on_gc("start", {"generation": generation})
    clk.t = int((start_ms + ms) * MS)
    ring.on_gc("stop", {"generation": generation})


def once_begun(tl, clk, stamp):
    """Run ``stamp`` right after the timeline's next call has begun, as a
    listener would stamp events while the call runs; the clock is put back."""
    begin_call = tl.begin_call

    def begin_then_stamp():
        tl.begin_call = begin_call
        begin_call()
        t = clk.t
        stamp()
        clk.t = t

    tl.begin_call = begin_then_stamp


def script(monkeypatch, capacity=trace.HOST_EVENT_CAPACITY, traced_steps=2):
    """Set-up of 1,000 ms, then a warm-up call, the untraced part (it begins
    at 1,060 ms: set-up's end) and the traced part.  In set-up, in ms: the
    step's trace [0, 100] with a jitted function's own trace [20, 40] and an
    eager operation's compile [50, 60] inside it; its lowering [90, 150],
    begun inside the trace; the compile [140, 200] with the cache's read
    [150, 190]; a second read [300, 320]; collector passes of 0.5 and 30 ms."""
    clk = Clock()
    ring = trace.HostEvents(capacity=capacity, clock=clk)
    tl = trace.StepTimeline(capacity=trace.PROCESS_CAPACITY, clock=clk,
                            events=ring)
    monkeypatch.setattr(trace, "_PROCESS_TIMELINE", tl)
    for kind, name, start, end in [
            ("trace", "inner", 20, 40), ("compile", "jit(add)", 50, 60),
            ("trace", "train_step", 0, 100), ("lower", "jit(train_step)", 90, 150),
            ("cache_read", "", 150, 190), ("compile", "jit(train_step)", 140, 200),
            ("cache_read", "", 300, 320)]:
        ring.add(kind, name, start * MS, end * MS)
    gc_pass(ring, clk, 0, 400, 0.5)
    gc_pass(ring, clk, 2, 500, 30)
    clk.t = 1000 * MS
    one_call(tl, clk, [(1, 1, 1, 50)] * 3, 60)            # warm-up: set-up still
    begin = clk.t

    def in_the_window():
        gc_pass(ring, clk, 1, begin / MS + 100, 7)
        gc_pass(ring, clk, 2, begin / MS + 200, 118)
        gc_pass(ring, clk, 0, begin / MS + 300, 0.2)
        # a trace that began in set-up and ends inside the window is no set-up
        ring.add("trace", "late", begin - 5 * MS, begin + 5 * MS)

    once_begun(tl, clk, in_the_window)
    one_call(tl, clk, UNTRACED, max(s[3] for s in UNTRACED))
    once_begun(tl, clk, lambda: gc_pass(ring, clk, 2, clk.t / MS + 1, 300))
    one_call(tl, clk, [(1, 1, 1, 400 * (i + 1)) for i in range(traced_steps)],
             400 * traced_steps)
    return types.SimpleNamespace(traced_steps=traced_steps, reduce=trace_reduce,
                                 dispatch_s=[0.0] * len(UNTRACED))


@pytest.mark.parametrize("metric,expected", [
    ("setup_trace_s", 0.100),             # the nested trace counts once
    ("setup_lower_s", 0.050),             # [90, 150] less the trace
    ("setup_compile_s", 0.050 + 0.020),   # [140, 200] less the lowering; [300, 320]
    ("setup_gc_s", 0.0305),               # the totals: the 0.5 ms pass too
    ("loop_gc_ms_max", 118.0),
    ("loop_gc_ms_max.fed", 118.0),
])
def test_reader_returns_the_scripted_number(monkeypatch, metric, expected):
    ctx = script(monkeypatch)
    assert run.load_reader(metric).read(ctx) == pytest.approx(expected)


def test_the_three_kinds_partition_what_they_cover(monkeypatch):
    ctx = script(monkeypatch)
    parts = [run.load_reader(m).read(ctx) for m in READERS[:3]]
    assert sum(parts) == pytest.approx(0.200 + 0.020)     # [0, 200] and [300, 320]


def test_a_call_without_a_long_pass_reads_zero(monkeypatch):
    clk = Clock()
    tl = trace.StepTimeline(capacity=trace.PROCESS_CAPACITY, clock=clk,
                            events=trace.HostEvents(clock=clk))
    monkeypatch.setattr(trace, "_PROCESS_TIMELINE", tl)
    one_call(tl, clk, [(1, 1, 1, 50)] * 4, 60)
    one_call(tl, clk, [(1, 1, 1, 50)] * 2, 60)
    ctx = types.SimpleNamespace(traced_steps=2, reduce=trace_reduce,
                                dispatch_s=[0.0] * 4)
    assert [run.load_reader(m).read(ctx) for m in READERS] == [0.0] * 5


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_where_there_is_nothing(monkeypatch, metric):
    read = run.load_reader(metric).read
    ctx = script(monkeypatch)
    assert read(ctx) is not None
    # the calls do not line up with the run's: never another window
    assert read(types.SimpleNamespace(traced_steps=3, reduce=trace_reduce,
                                      dispatch_s=[0.0] * 4)) is None
    # a program whose timeline knows no host events (the parent commit)
    tl = trace.process_timeline()
    bare = lambda: [{k: v for k, v in c.items()
                     if k not in ("totals0", "totals1", "events")}
                    for c in trace.StepTimeline.calls(tl)]
    monkeypatch.setattr(tl, "calls", bare)
    assert read(ctx) is None
    # a program without the timeline
    monkeypatch.delattr(trace, "process_timeline")
    assert read(ctx) is None


@pytest.mark.parametrize("metric", READERS[:3])
def test_a_ring_that_rolled_set_up_off_reads_nothing(monkeypatch, metric):
    ctx = script(monkeypatch, capacity=6)
    assert run.load_reader(metric).read(ctx) is None


def test_manifest_lists_the_new_readers():
    manifest = run.read_json("BENCHMARK.json")
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in READERS}
    assert len(mine) == 6 and all(m["source"] == "program_span" for m in mine.values())
    cells = [w["name"] for w in manifest["workloads"]]
    for name in READERS[:4]:
        assert mine[name]["moves"] == "setup_s" and mine[name]["workloads"] == cells
    fed = [m["name"] for m in run.load_cell("resnet152_dense_fed").per_layer]
    assert set(READERS[:4]) | {"loop_gc_ms_max.fed"} <= set(fed)
    assert "loop_gc_ms_max" not in fed
    staged = [m["name"] for m in run.load_cell("resnet50_topk_lw_staged").per_layer]
    assert set(READERS) <= set(staged) and "loop_gc_ms_max.fed" not in staged
