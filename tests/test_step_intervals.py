"""tools/step_intervals.py: which call of the timeline is the measured window,
and what its summary says of a stall against a slower program."""

import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "step_intervals", os.path.join(os.path.dirname(__file__), "..", "tools",
                                   "step_intervals.py"))
step_intervals = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(step_intervals)

MS = 1_000_000


def call(done_ms):
    return {"records": [{"done": None if d is None else d * MS}
                        for d in done_ms]}


WARM, WINDOW, TRACED = call([0, 50, 100]), call([0, 100, 200, 300]), call([0, 7, 14])


@pytest.mark.parametrize("calls,traced,want", [
    ([WARM, WINDOW], False, [100.0, 100.0, 100.0]),
    ([WARM, WINDOW, TRACED], True, [100.0, 100.0, 100.0]),
    ([WINDOW], True, None),                         # no call before the traced one
    ([WARM, call([0, None, 200])], False, None),    # a stamp is missing
    ([WARM, call([0])], False, None),
])
def test_window_is_the_last_untraced_call(calls, traced, want):
    assert step_intervals.window_intervals_ms(calls, traced) == want


@pytest.mark.parametrize("ms,long", [
    ([100.0] * 50 + [480.0] + [100.0] * 49, [[51, 480.0]]),   # one stall
    ([106.0] * 100, []),                                      # a slower program
])
def test_summary_tells_a_stall_from_a_slower_program(ms, long):
    s = step_intervals.summary(ms)
    assert s["long"] == long and s["steps"] == len(ms) + 1
    assert s["p50_ms"] == ms[0] and s["max_ms"] == max(ms)
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"] <= s["max_ms"]


def test_host_events_line_splits_set_up_and_names_a_long_interval():
    """Set-up is what ended before the window's call began; a long interval
    carries the events over it, an empty list where nothing was recorded."""
    t0 = 1000 * MS
    before = [("trace", "inner", 20 * MS, 40 * MS),
              ("trace", "train_step", 0, 100 * MS),
              ("lower", "jit(train_step)", 100 * MS, 150 * MS),
              ("cache_read", "", 160 * MS, 190 * MS),
              ("compile", "jit(train_step)", 150 * MS, 200 * MS),
              ("gc", "gen2", 60 * MS, 120 * MS),
              ("gc", "gen2", 500 * MS, 530 * MS),
              ("trace", "late", 990 * MS, 1010 * MS)]     # ends in the window
    window = dict(call([1000, 1100, 1200, 1500, 1600, 1700, 2100]), t0=t0,
                  totals0={"gc": (40, 95 * MS), "trace": (2, 100 * MS)},
                  totals1={"gc": (52, 220 * MS)},
                  events=[("gc", "gen2", 1210 * MS, 1328 * MS),
                          ("gc", "gen1", 1650 * MS, 1652 * MS)])
    line = step_intervals.host_events_line(
        [WARM, window, TRACED], True, lambda t: [e for e in before if e[2] <= t])
    assert line["setup"]["trace_s"] == 0.1 and line["setup"]["lower_s"] == 0.05
    assert line["setup"]["compile_s"] == 0.05
    assert line["setup"]["cache_read_s"] == 0.03
    assert line["setup"]["gc_in_trace_lower_s"] == 0.06
    assert line["setup"]["top"]["trace"] == [["train_step", 1, 0.1],
                                             ["inner", 1, 0.02]]
    assert line["totals_at_window"] == {"gc": [40, 0.095], "trace": [2, 0.1]}
    assert line["window"]["gc_passes"] == 12 and line["window"]["gc_s"] == 0.125
    assert line["window"]["gc_ms_max"] == 118.0
    assert line["window"]["long"] == [
        [3, 300.0, [["gc", "gen2", 118.0]]], [6, 400.0, []]]
    assert step_intervals.host_events_line([window], True, lambda t: []) is None
