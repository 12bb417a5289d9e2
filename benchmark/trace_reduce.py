"""Reduction from a profiler trace to the numbers the per-layer readers use.

``extract`` turns an ``.xplane.pb`` into plain lists (device operations with
their ``tcdp.<phase>`` scope, the benchmark's host spans), cut to the traced
window; everything else works on that extract, so the arithmetic can be
checked against the small recorded extract under ``tests/``.

Times are nanoseconds on the profiler's clock.  A device operation is an event
of a TPU plane's "XLA Ops" line; container operations (``while``,
``conditional``, ``call``) only wrap others and are left out of sums, though
not out of the busy union, where nesting does no harm.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.data_wait", "bench.dispatch", "bench.fetch")
TO_DEVICE_SPAN = "loop.to_device"
SYNC_PHASES = ("ef", "compress", "route", "reduce", "return",
               "ici_reduce", "recompress")
_SCOPE = re.compile(r"tcdp\.([a-z_]+)")
_CONTAINERS = ("while", "conditional", "call")


_SELECT_PACK = re.compile(
    r"= \(f32\[\d+,128\]\S* s32\[\d+,128\]\S* s32\[\d+,128\]\S*\) custom-call")


def kind_of(event_name: str, target: str, opcode: str) -> str:
    """``pallas`` for a Pallas kernel (a ``tpu_custom_call``), else the HLO
    opcode.  The kernels carry no name of their own yet, so
    ``fused_select_pack`` is known by what it returns: the packed values, their
    indices and the per-segment counts (f32, s32, s32 rows of 128 lanes)."""
    if target != "tpu_custom_call":
        return opcode
    return "pallas:select_pack" if _SELECT_PACK.search(event_name) else "pallas"


def short_name(event_name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def scope_of(op_name: str) -> str:
    """Innermost ``tcdp.<phase>`` of an operation's name stack, else ''."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def extract(xplane_path: str) -> dict:
    """{"window": [t0, t1], "devices": {plane: [[name, scope, kind, start,
    dur], ...]}, "host": [[name, start, dur], ...]}; events outside the
    ``bench.window`` host span are dropped.  Scope and kind come from the
    instruction of that name in the HLO protos the trace carries."""
    from jax.profiler import ProfileData

    import xplane_pb

    instructions = xplane_pb.instructions_of_xplane(xplane_path)
    data = ProfileData.from_file(xplane_path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = short_name(ev.name)
                    op_name, target, opcode = instructions.get(name, ("", "", ""))
                    ops.append([name, scope_of(op_name),
                                kind_of(ev.name, target, opcode),
                                int(ev.start_ns), int(ev.duration_ns)])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name in HOST_SPANS:
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    t0, t1 = windows[-1][1], windows[-1][1] + windows[-1][2]
    inside = lambda s, d: s >= t0 and s + d <= t1
    spans = sorted([h for h in host if h[0] != WINDOW_SPAN and inside(h[1], h[2])],
                   key=lambda h: h[1])
    # between a batch's arrival and the step's call the loop turns the batch
    # into device arrays: the host-to-device copy of a fed cell
    holes = [[TO_DEVICE_SPAN, a[1] + a[2], b[1] - a[1] - a[2]]
             for a, b in zip(spans, spans[1:])
             if a[0] == "bench.data_wait" and b[0] == "bench.dispatch"
             and b[1] > a[1] + a[2]]
    return {
        "window": [t0, t1],
        "devices": {p: [e for e in ops if inside(e[3], e[4])]
                    for p, ops in devices.items()},
        "host": sorted(spans + holes, key=lambda h: h[1]),
    }


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def is_container(name: str) -> bool:
    return name.split(".")[0] in _CONTAINERS


def is_pallas(name: str, scope: str, kind: str) -> bool:
    return kind.startswith("pallas")


def is_select_pack(name: str, scope: str, kind: str) -> bool:
    return kind == "pallas:select_pack"


def busy_seconds(ex: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per = [length(union([e[3], e[3] + e[4]] for e in ops))
           for ops in ex["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_seconds(ex: dict) -> float:
    return (ex["window"][1] - ex["window"][0]) / 1e9


def device_seconds(ex: dict, pick) -> float:
    """Summed duration of the non-container operations ``pick(name, scope,
    category)`` selects, averaged over the devices."""
    per = [sum(e[4] for e in ops if not is_container(e[0]) and pick(e[0], e[1], e[2]))
           for ops in ex["devices"].values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def scope_seconds(ex: dict, scopes) -> float:
    return device_seconds(ex, lambda n, s, c: s in scopes)


def top_device_ops(ex: dict, n: int = 10) -> list:
    """[[scope/name, seconds]] of the operations that took most time."""
    total = {}
    for ops in ex["devices"].values():
        for name, scope, _, _, dur in ops:
            if not is_container(name):
                key = f"{scope}/{name}" if scope else name
                total[key] = total.get(key, 0) + dur
    k = max(len(ex["devices"]), 1)
    return [[name, dur / k / 1e9] for name, dur in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ex: dict, n: int = 10) -> list:
    """[[host span, seconds]]: the first device's idle time inside the window,
    each gap given to the benchmark's host span (they do not overlap) that
    covers its middle."""
    if not ex["devices"]:
        return []
    ops = ex["devices"][sorted(ex["devices"])[0]]
    busy = union([e[3], e[3] + e[4]] for e in ops)
    gaps = subtract([list(ex["window"])], busy)
    starts = [h[1] for h in ex["host"]]
    total = {}
    for s, e in gaps:
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        covered = i >= 0 and ex["host"][i][1] + ex["host"][i][2] > mid
        name = ex["host"][i][0] if covered else "other"
        total[name] = total.get(name, 0) + (e - s)
    return [[name, dur / 1e9] for name, dur in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
