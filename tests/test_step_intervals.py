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
