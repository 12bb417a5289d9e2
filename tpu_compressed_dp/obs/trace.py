"""Phase-level step tracing: in-graph annotations + a host-side timeline.

Two complementary views of where a step's time goes:

  * **Device view** — :func:`phase` wraps each pipeline phase (compress /
    ef / route / reduce / return / update, :data:`PHASES`) in a
    ``jax.named_scope``, so XLA op names — and therefore xprof/tensorboard
    traces — attribute device time to named phases instead of a soup of
    fused ops.  Zero runtime cost: named scopes exist only at trace time.
  * **Host view** — :class:`StepTimeline` records, per step, the host
    loop's spans (:data:`LOOP_SPANS`: input-pipeline wait, host-to-device
    copy, dispatch; once per epoch call the closing fetch) and the time the
    step's outputs became ready, stamped by a watcher thread so that
    neither the compiled step nor the loop's thread waits on the device.
    From the two it derives what the device did: its time on each step and
    how long it sat with nothing queued (``starved``), attributed to the
    span the host was in.  It yields p50/p95/p99 of the completion
    intervals, the device-starved fraction, the host loop's data-wait
    fraction and the step rate — the numbers the heartbeat telemetry
    snapshot and the JSONL event stream carry.  Every span is also a
    profiler annotation (:func:`host_span`), and the clock is the one a
    captured trace's annotations are stamped with, so the records lay over
    the device operations of an ``.xplane.pb``.
  * **Host events** — :class:`HostEvents`, one process-wide ring on the
    same clock of what the host does that is no span of the loop
    (:data:`HOST_EVENT_KINDS`: every trace, lowering, compile and cache
    answer of a jitted function, every pass of the garbage collector),
    stamped where the work happens (:func:`install_host_events`).  The
    timeline lays them over its calls and steps when it is read, so
    set-up's seconds and a long step each name their cause.

This is the measurement layer the paper's thesis needs: compression claims
are stated in bits, but they live or die on *seconds per phase*
(Near-Optimal Sparse Allreduce, arXiv:2201.07598, makes the same move).
"""

from __future__ import annotations

import bisect
import collections
import gc
import queue
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax._src import dispatch as _jax_dispatch

__all__ = ["PHASES", "LOOP_SPANS", "HOST_EVENT_KINDS", "phase", "chunk",
           "host_span", "StepTimeline", "process_timeline", "percentile",
           "HostEvents", "install_host_events"]

#: The phase taxonomy — every named scope the engines and step factories
#: emit uses one of these (xprof filters on the ``tcdp.`` prefix):
#:   grad      forward + backward of the model
#:   ef        error-feedback residual accumulation
#:   compress  compression operator (top-k / quantize / low-rank factor)
#:   route     sharded transport: per-destination bucketing + all_to_all
#:   reduce    the reduction collective (psum / owner scatter-add)
#:   return    un-flatten / shard-return all_gather back to leaf shapes
#:   update    optimizer apply
#:   ici_reduce  hierarchical transport: dense intra-pod psum (both the
#:             contribution-in and combined-partial-out hops)
#:   recompress  hierarchical transport: pack + slice the pod-reduced
#:             gradient's nonzero union for the inter-pod exchange
#:   stack / attn / head_xent / exit   inside ``grad`` of the LM step: the
#:             decoder's passes over its layers, the attention calls in
#:             them, the head with its cross-entropies, and a looped
#:             model's exit gate and loss weighting
#:   ssm / ssd / moe / moe_dispatch / experts / mtp   a hybrid decoder's
#:             (models/hybrid.py): a Mamba-2 mixer and, in it, its
#:             convolution and scan; an expert layer (latent projections,
#:             shared expert) and, in it, its router, choice, sort, gathers
#:             and combine, and the grouped product of its experts; the
#:             multi-token-prediction module
#:   attn_window / mlp   the same decoder's ``laguna`` layer kinds: the
#:             attention calls of a sliding-window layer (the banded
#:             kernels; a full layer's stay under ``attn``), and a dense
#:             gated feed-forward sublayer
#:   gmu / attn_cross   a decoder-hybrid-decoder's (models/sambay.py, whose
#:             Mamba-1 mixer is under ``ssm`` and its convolution and
#:             selective scan under ``ssd``): a Gated Memory Unit on an
#:             earlier layer's scan output, and the attention calls of a
#:             cross layer on an earlier layer's keys and values
PHASES = ("grad", "ef", "compress", "route", "reduce", "return", "update",
          "ici_reduce", "recompress", "stack", "attn", "head_xent", "exit",
          "ssm", "ssd", "moe", "moe_dispatch", "experts", "mtp",
          "attn_window", "mlp", "gmu", "attn_cross")


def phase(name: str):
    """In-graph phase annotation: ``with phase('compress'): ...`` inside
    traced code names the enclosed ops ``tcdp.<name>/...`` in XLA dumps and
    xprof traces.  Usable anywhere (jit, shard_map, host code)."""
    return jax.named_scope(f"tcdp.{name}")


def chunk(index: int):
    """Per-chunk scope for the overlap subsystem
    (:mod:`tpu_compressed_dp.parallel.overlap`): chunk ``index``'s
    compress→route→reduce→return pipeline (and, in the fused train-step
    path, its optimizer-update slice) nests the :data:`PHASES` scopes under
    ``tcdp.chunk<ii>/``, so xprof — and the AOT schedule evidence
    (``tools/overlap_evidence.py``) — attribute each collective and each
    per-chunk ``tcdp.reduce`` / ``tcdp.update`` span to its chunk.  The
    index is the ISSUE order (0 = first dispatched = the reverse-topological
    head, i.e. the last parameters' gradients)."""
    return jax.named_scope(f"tcdp.chunk{index:02d}")


#: The host loop's per-step spans, in the order a step passes through
#: them; ``fetch`` (the epoch-closing metrics fetch) is once per call.
LOOP_SPANS = ("data_wait", "to_device", "dispatch")

#: Steps the process-wide timeline keeps: the last four epoch calls of up
#: to 1,024 steps each.
PROCESS_CAPACITY = 4 * 1024

#: Bound of the wait for the watcher after an epoch's fetch.  The fetch has
#: already drained the device, so the wait is the watcher's own few
#: microseconds a step; the bound only keeps a lost device from hanging
#: the loop.
FLUSH_TIMEOUT_S = 2.0


def host_span(name: str, **meta):
    """Host-side profiler annotation (``jax.profiler.TraceAnnotation``):
    marks a wall-clock span ``tcdp.<name>`` on the host timeline of a
    captured trace — for the parts of the loop that are NOT traced
    computation (input pipeline, host-to-device copy, dispatch, fetch).
    ``meta`` (``step=``) lands as the event's stats; it is encoded only
    while a trace is being captured."""
    return jax.profiler.TraceAnnotation(f"tcdp.{name}", **meta)


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list (empty -> 0.0) — the
    one percentile definition the live snapshot and the offline
    trace_report share."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _overlap(a0: int, a1: int, span: Optional[Tuple[int, int]]) -> int:
    if span is None:
        return 0
    return max(0, min(a1, span[1]) - max(a0, span[0]))


def _seconds(span: Optional[Tuple[int, int]]) -> float:
    return 0.0 if span is None else (span[1] - span[0]) / 1e9


def _ns_to_s(ns: Optional[int]) -> Optional[float]:
    return None if ns is None else ns / 1e9


def _union(intervals) -> List[List[int]]:
    """Merged, sorted ``[start, end]`` of any intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(intervals, windows) -> int:
    """Nanoseconds of the union of ``intervals`` that lie inside the
    (disjoint) ``windows``."""
    merged = _union(intervals)
    return sum(max(0, min(e, w1) - max(s, w0))
               for s, e in merged for w0, w1 in windows)


#: The kinds of host event, each stamped where the work happens:
#:   trace       a jitted function traced to a jaxpr (``name``: its
#:               ``fun_name``); traces nest: a function traced inside
#:               another's trace raises an event of its own
#:   lower       the jaxpr lowered to an MLIR module (``fun_name``)
#:   compile     the backend's compile, or the persistent cache's answer in
#:               its place (``fun_name``)
#:   cache_read  the persistent cache answered: the read and deserialisation
#:               alone, inside a ``compile`` event (``cache_hits`` and
#:               ``cache_misses`` are counted beside it, with no interval)
#:   gc          one pass of the garbage collector (``name``: ``gen0`` /
#:               ``gen1`` / ``gen2``)
#: Because events of a kind nest, an aggregate of a kind is the length of the
#: union of its intervals, never their sum; a per-name table is inclusive time.
HOST_EVENT_KINDS = ("trace", "lower", "compile", "cache_read", "gc")

#: Events the process-wide ring keeps.  The benchmark's largest set-up stamps
#: 18,300 (the Top-K cell; ResNet-152's 14,400: my chip runs, PR 39), most of
#: them the sub-millisecond traces of eager operations, so a whole set-up
#: and the first comparison after it are still in the ring when it is read.
HOST_EVENT_CAPACITY = 64 * 1024

#: A collector pass shorter than this adds to its generation's totals and
#: stays out of the ring: generation-0 passes are many and short.
GC_RING_MIN_NS = 1_000_000

#: Only a pass of this generation is also a profiler annotation
#: (``tcdp.host.gc``), so that a captured trace's idle gaps can be laid on it.
GC_ANNOTATED_GENERATION = 2

#: Disjoint recent intervals a kind's running union keeps: an event that
#: ends later can swallow only what nests inside it.
_UNION_TAIL = 32

_GC_NAMES = ("gen0", "gen1", "gen2")
_DURATION_KINDS = {
    _jax_dispatch.JAXPR_TRACE_EVENT: "trace",
    _jax_dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lower",
    _jax_dispatch.BACKEND_COMPILE_EVENT: "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read"}
_COUNTED_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                   "/jax/compilation_cache/cache_misses": "cache_misses"}


def _overlapping(events, t0: Optional[int], t1: Optional[int]) -> List:
    """Those of ``events`` that overlap ``[t0, t1]``; an open end takes
    everything on that side."""
    return [ev for ev in events
            if (t1 is None or ev[2] <= t1) and (t0 is None or ev[3] >= t0)]


class HostEvents:
    """Bounded ring of host events ``(kind, name, start_ns, end_ns)`` on
    ``clock`` (default ``time.time_ns``, the timeline's), with per-kind
    totals ``(count, nanoseconds)`` that do not roll off with the ring; the
    nanoseconds are the length of the union of the kind's intervals.

    The collector's callback runs wherever an allocation tips a threshold,
    also inside :meth:`add`, so it takes no lock: it appends to the ring
    (atomic) and adds to lists only it touches, and a reader copies the
    ring until no append came between."""

    def __init__(self, capacity: int = HOST_EVENT_CAPACITY,
                 clock: Callable[[], int] = time.time_ns):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()           # the listeners' side only
        self._totals: Dict[str, List[int]] = {}
        self._tails: Dict[str, List[Tuple[int, int]]] = {}
        self._gc = [[0, 0] for _ in _GC_NAMES]   # by generation
        self._gc_start: Optional[int] = None
        self._gc_ann = None

    # --- the stamping side ------------------------------------------------

    def add(self, kind: str, name: str, start: int, end: int) -> None:
        """One event; its kind's total grows by what no earlier event of
        the kind covered."""
        with self._lock:
            # the kind's recent intervals, disjoint and sorted: those from
            # i on end after this one starts, and up to j they overlap it
            tail = self._tails.setdefault(kind, [])
            i = len(tail)
            while i and tail[i - 1][1] >= start:
                i -= 1
            j, new, lo, hi = i, end - start, start, end
            while j < len(tail) and tail[j][0] <= end:
                a, b = tail[j]
                new -= min(b, end) - max(a, start)
                lo, hi = min(lo, a), max(hi, b)
                j += 1
            tail[i:j] = [(lo, hi)]
            del tail[:-_UNION_TAIL]
            total = self._totals.setdefault(kind, [0, 0])
            total[0] += 1
            total[1] += new
        self._ring.append((kind, name, start, end))

    def on_duration(self, event: str, duration: float, **kw) -> None:
        """``jax.monitoring`` duration listener: it learns of an event when
        the event ends."""
        kind = _DURATION_KINDS.get(event)
        if kind is not None:
            end = self._clock()
            self.add(kind, str(kw.get("fun_name", "")),
                     end - int(duration * 1e9), end)

    def on_event(self, event: str, **kw) -> None:
        """``jax.monitoring`` event listener."""
        name = _COUNTED_EVENTS.get(event)
        if name is not None:
            with self._lock:
                self._totals.setdefault(name, [0, 0])[0] += 1

    def on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` entry: two clock reads and an add a pass."""
        if phase == "start":
            if info["generation"] >= GC_ANNOTATED_GENERATION:
                self._gc_ann = host_span("host.gc")
                self._gc_ann.__enter__()
            self._gc_start = self._clock()
            return
        end = self._clock()
        start, self._gc_start = self._gc_start, None
        if self._gc_ann is not None:
            self._gc_ann.__exit__(None, None, None)
            self._gc_ann = None
        if start is None:               # installed while a pass was running
            return
        generation = info["generation"]
        total = self._gc[generation]
        total[0] += 1
        total[1] += end - start
        if end - start >= GC_RING_MIN_NS:
            self._ring.append(("gc", _GC_NAMES[generation], start, end))

    # --- the reading side -------------------------------------------------

    def events(self, t0: Optional[int] = None, t1: Optional[int] = None
               ) -> List[Tuple[str, str, int, int]]:
        """The ring's events that overlap ``[t0, t1]`` (an open end takes
        everything on that side), in the order they ended."""
        while True:
            try:
                held = list(self._ring)
                break
            except RuntimeError:        # a pass was stamped meanwhile
                continue
        return _overlapping(held, t0, t1)

    def totals(self) -> Dict[str, Tuple[int, int]]:
        """``{kind: (count, nanoseconds)}`` since the ring was made: the
        :data:`HOST_EVENT_KINDS` that occurred, the collector's also by
        generation (``gc.gen0``...), and the counts ``cache_hits`` /
        ``cache_misses`` with 0 nanoseconds."""
        with self._lock:
            out = {k: (v[0], v[1]) for k, v in self._totals.items()}
        passes = [(c, ns) for c, ns in self._gc if c]
        if passes:
            out.update(("gc." + name, (c, ns))
                       for name, (c, ns) in zip(_GC_NAMES, self._gc) if c)
            out["gc"] = (sum(c for c, _ in passes), sum(ns for _, ns in passes))
        return out


_HOST_EVENTS = HostEvents()       # the process-wide ring; empty until installed
_HOST_EVENTS_INSTALLED = False


def install_host_events() -> HostEvents:
    """Point JAX's monitoring listeners and the collector's callbacks at the
    process-wide ring, once however often it is called
    (``parallel.mesh.setup_compile_cache`` does, first in every entry
    point), and return the ring.  Always on, like the timeline: no
    switch."""
    global _HOST_EVENTS_INSTALLED
    if not _HOST_EVENTS_INSTALLED:
        _HOST_EVENTS_INSTALLED = True
        jax.monitoring.register_event_duration_secs_listener(
            _HOST_EVENTS.on_duration)
        jax.monitoring.register_event_listener(_HOST_EVENTS.on_event)
        gc.callbacks.append(_HOST_EVENTS.on_gc)
    return _HOST_EVENTS


#: what the watcher gets in place of an output for a step that raised
_NO_OUTPUT = object()


def _end_watcher(items: queue.SimpleQueue, thread: threading.Thread,
                 timeout: float) -> None:
    """End the watcher and wait for it, bounded: run when its timeline goes
    and, for one that lives as long as the process, from the interpreter's
    exit hooks, so that no thread of ours is unwound inside native code."""
    items.put(None)
    if thread is not threading.current_thread():    # a collection inside it
        thread.join(timeout)


def _watch(items: queue.SimpleQueue, clock: Callable[[], int],
           lock: threading.Lock) -> None:
    """The watcher thread: takes (record, one output of the step) in
    dispatch order, waits for the output and stamps the record; an Event
    in the queue is set when reached (:meth:`StepTimeline.flush`), None
    ends the thread."""
    prev_done: Optional[int] = None
    while True:
        item = items.get()
        if item is None:
            return
        if isinstance(item, threading.Event):
            item.set()
            continue
        rec, token = item
        done = None
        if token is not _NO_OUTPUT:
            try:
                wait = getattr(token, "block_until_ready", None)
                if wait is not None:
                    wait()           # releases the GIL while it waits
                done = clock()
            except Exception:        # noqa: BLE001 - the step failed on the
                pass                 # device: the loop's fetch reports it
        if done is not None:
            with lock:
                _stamp(rec, prev_done, done)
        prev_done = done


def _stamp(rec: Dict[str, Any], prev_done: Optional[int], done: int) -> None:
    enq = rec["end"]
    base = prev_done
    if rec["first"]:                 # a drained pipeline: idle since t0
        base = rec["t0"] if base is None else max(base, rec["t0"])
    if base is not None:
        starved = max(0, enq - base)
        rec["starved"] = starved
        rec["device"] = done - max(enq, base)
        if starved:
            by = {name: _overlap(base, enq, rec[name]) for name in LOOP_SPANS}
            by["other"] = starved - sum(by.values())
            rec["starved_by"] = by
    rec["done"] = done


class _Span:
    """One timed span of the loop: a profiler annotation and, on the same
    clock, its start and end in the timeline."""

    __slots__ = ("_tl", "_name", "_ann", "_start")

    def __init__(self, tl: "StepTimeline", name: str):
        self._tl = tl
        self._name = name

    def __enter__(self):
        tl = self._tl
        self._ann = host_span("loop." + self._name, step=tl.steps)
        self._start = tl._clock()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._tl._close_span(self._name, self._start, self._tl._clock())
        return False


class StepTimeline:
    """Ring buffer of per-step host spans and completion stamps.

    Protocol (driven by the epoch loop):

    >>> tl = StepTimeline()
    >>> tl.begin_call()                  # one epoch / one loop
    >>> while True:
    ...     with tl.span("data_wait"):   # the input pipeline's `next()`
    ...         batch = next(batches, None)
    ...     if batch is None:
    ...         break
    ...     with tl.span("to_device"):   # host-to-device copy
    ...         batch = to_device(batch)
    ...     with tl.span("dispatch"):    # async: the device runs on
    ...         state, metrics = train_step(state, batch)
    ...     tl.step_done(metrics)        # hands one output to the watcher
    >>> with tl.span("fetch"):
    ...     fetched = jax.device_get(all_metrics)
    >>> tl.end_call()                    # bounded wait for the stamps

    A record is one step: its ordinal ``ord`` (shared by its spans'
    annotations and its stamp), the ordinal ``call`` of the epoch call it
    belongs to, the step's start ``t0`` (end of the previous dispatch, or
    the :meth:`resume` mark), the three :data:`LOOP_SPANS` as ``(start,
    end)`` and ``done``, the time its outputs became ready.  All times are
    integer nanoseconds of ``clock`` (default ``time.time_ns``: the clock
    a captured trace stamps annotations with, less the trace's
    ``profile_start_time``).  ``done`` comes from one daemon watcher
    thread that takes (record, one output of the step) in dispatch order
    and waits on the output with the GIL released; a step that raised, or
    whose output never became ready, keeps ``done=None``.  With
    ``enqueued`` the end of the dispatch span and ``base`` the previous
    step's ``done`` (the step's ``t0`` after a :meth:`resume`):

      ``starved = max(0, enqueued - base)``   the device had nothing queued
      ``device  = done - max(enqueued, base)`` the device's time on the step
      ``starved_by``  the part of the starved interval under each span
                      (``other``: the loop's own code between spans)

    A late stamp (the watcher waits for the GIL like any thread) only
    shortens the next step's ``starved``.

    Memory is O(``capacity``): the ring holds the most recent steps only
    (the Timer-unbounded-append lesson, applied from day one).
    """

    def __init__(self, capacity: int = 1024,
                 clock: Callable[[], int] = time.time_ns,
                 events: Optional[HostEvents] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock
        # the host events laid over the records when they are read: the
        # process-wide ring, which is on the default clock
        self._events = _HOST_EVENTS if events is None else events
        self.records: collections.deque = collections.deque(maxlen=capacity)
        # since last drain(); a ring like `records`, so on overflow both
        # keep the NEWEST spans and drained step_spans stay consistent
        # with the snapshot() computed over the same window
        self._pending: collections.deque = collections.deque(maxlen=capacity)
        self._calls: collections.deque = collections.deque(maxlen=capacity)
        self.steps = 0                # ordinal of the next step
        self._spans: Dict[str, Tuple[int, int]] = {}
        self._first = True            # the next step opens a segment
        self._t = clock()             # step start = end of previous dispatch
        # the watcher stamps records the loop's thread has published;
        # aggregates read them: one lock for the fields both touch
        self._lock = threading.Lock()
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._watcher: Optional[threading.Thread] = None

    # --- the loop's side ------------------------------------------------

    def begin_call(self) -> None:
        """Open the records of one epoch call (``run_train_epoch``) or one
        loop; calls are numbered from 0 in the order they begin."""
        self.resume()
        self._open_call()

    def _open_call(self) -> Dict[str, Any]:
        call = self._calls[-1]["call"] + 1 if self._calls else 0
        self._calls.append({"call": call, "t0": self._t, "t1": None,
                            "fetch": None, "steps": 0,
                            "totals0": self._events.totals(), "totals1": None})
        return self._calls[-1]

    def _call(self) -> Dict[str, Any]:
        """The open call; a loop that never began one gets call 0."""
        return self._calls[-1] if self._calls else self._open_call()

    def resume(self) -> None:
        """Re-stamp the step-start mark, excluding everything since the
        last dispatch from the next step.  Called by :meth:`begin_call`,
        and after any blocking between-step work (eval, checkpointing, a
        log-cadence ``device_get`` drain) — otherwise that wall time is
        billed to the step and corrupts the fractions and percentiles."""
        self._t = self._clock()
        self._first = True
        self._spans = {}

    def span(self, name: str) -> _Span:
        """Context manager around one of :data:`LOOP_SPANS` of the step
        being built, or around the call's ``fetch``; also a profiler
        annotation ``tcdp.loop.<name>`` with ``step=`` the step's ordinal."""
        return _Span(self, name)

    def _close_span(self, name: str, start: int, end: int) -> None:
        if name == "fetch":
            self._call()["fetch"] = (start, end)
        else:
            self._spans[name] = (start, end)

    def step_done(self, outputs: Any) -> None:
        """Close the step whose spans were just recorded.  ``outputs`` is
        what the step returned (any pytree): its first leaf goes to the
        watcher, which stamps the record when the leaf is ready."""
        leaves = jax.tree_util.tree_leaves(outputs)
        self._commit(leaves[0] if leaves else _NO_OUTPUT)

    def step_failed(self) -> None:
        """Close the step being built when the loop raised inside it: the
        record keeps the spans it got and ``done=None``.  Nothing when no
        span of a new step was recorded."""
        if self._spans:
            self._commit(_NO_OUTPUT)

    def _commit(self, token: Any) -> None:
        call = self._call()
        spans, self._spans = self._spans, {}
        end = spans["dispatch"][1] if "dispatch" in spans else self._clock()
        rec = {"ord": self.steps, "call": call["call"],
               "first": self._first, "t0": self._t, "end": end,
               "data_wait": spans.get("data_wait"),
               "to_device": spans.get("to_device"),
               "dispatch": spans.get("dispatch"),
               "done": None, "device": None, "starved": None,
               "starved_by": None}
        self.steps += 1
        call["steps"] += 1
        self._t = end
        self._first = False
        self.records.append(rec)
        self._pending.append(rec)
        if self._watcher is None:
            # the thread holds the queue, not the timeline: when the
            # timeline goes, or the interpreter exits with it alive (a
            # finalizer is an exit hook too), the thread is ended and joined
            self._watcher = threading.Thread(
                target=_watch, args=(self._queue, self._clock, self._lock),
                name="tcdp-step-stamps", daemon=True)
            self._watcher.start()
            weakref.finalize(self, _end_watcher, self._queue, self._watcher,
                             FLUSH_TIMEOUT_S)
        self._queue.put((rec, token))

    def end_call(self, timeout: float = FLUSH_TIMEOUT_S) -> bool:
        """Close the call after its fetch: wait (bounded) until the
        watcher has stamped every step handed to it, so the call's records
        are whole.  False when the bound ran out."""
        whole = self.flush(timeout)
        call = self._call()
        call["t1"], call["totals1"] = self._clock(), self._events.totals()
        return whole

    def flush(self, timeout: float = FLUSH_TIMEOUT_S) -> bool:
        """Wait until the watcher has passed every step handed to it so
        far, at most ``timeout`` seconds; False when it has not."""
        if self._watcher is None:
            return True
        passed = threading.Event()
        self._queue.put(passed)
        return passed.wait(timeout)

    # --- views ------------------------------------------------------------

    def calls(self) -> List[Dict[str, Any]]:
        """The last calls, oldest first: ``{"call", "t0", "t1", "fetch",
        "steps", "totals0", "totals1", "records", "events"}`` with ``t0``
        the call's begin, ``t1`` its end (None while open, or when the
        fetch raised), ``steps`` the steps it counted, ``totals0`` and
        ``totals1`` the host events' totals at those two moments
        (:meth:`HostEvents.totals`: every collector pass, also those too
        short for the ring), ``records`` those of its steps the ring still
        holds (copies, in dispatch order) and ``events`` the host events
        that overlap ``[t0, t1]``."""
        with self._lock:
            recs = [dict(r) for r in self.records]
        by_call: Dict[int, List[Dict[str, Any]]] = {}
        for r in recs:
            by_call.setdefault(r["call"], []).append(r)
        held = self._events.events()
        return [dict(c, records=by_call.get(c["call"], []),
                     events=_overlapping(held, c["t0"], c["t1"]))
                for c in list(self._calls)]

    def host_events(self, t0: Optional[int] = None, t1: Optional[int] = None
                    ) -> List[Tuple[str, str, int, int]]:
        """The host events ``(kind, name, start_ns, end_ns)`` that overlap
        ``[t0, t1]``: with ``t1`` the first call's ``t0``, set-up's."""
        return self._events.events(t0, t1)

    @staticmethod
    def _as_event(rec: Dict[str, Any], events=()) -> Dict[str, Any]:
        """The exported form of a record: seconds, durations for the
        spans, absolute ``t0``/``done`` on the timeline's clock; and, only
        where host ``events`` overlapped the step's host interval (``t0``
        to the end of its dispatch), ``events``: ``[[kind, name, ms,
        at_ms], ...]`` with ``ms`` the event's whole length and ``at_ms``
        its start from the step's ``t0`` (negative: it began in an earlier
        step, which carries it too)."""
        by = rec["starved_by"]
        out = {"ord": rec["ord"], "call": rec["call"],
               "t0": rec["t0"] / 1e9,
               "data": _seconds(rec["data_wait"]),
               "to_device": _seconds(rec["to_device"]),
               "dispatch": _seconds(rec["dispatch"]),
               "total": (rec["end"] - rec["t0"]) / 1e9,
               "done": _ns_to_s(rec["done"]), "device": _ns_to_s(rec["device"]),
               "starved": _ns_to_s(rec["starved"]),
               "starved_in": max(by, key=by.get) if by else None}
        if events:
            out["events"] = [
                [kind, name, (end - start) / 1e6, (start - rec["t0"]) / 1e6]
                for kind, name, start, end in events]
        return out

    def step_intervals(self) -> List[float]:
        """Seconds per step over the ring window.  A stamped step's
        interval runs from the previous step's completion to its own
        (from its ``t0`` when it opened a segment: the pipeline was
        drained); a step with no stamp, or none before it, falls back to
        its host interval, enqueue to enqueue."""
        with self._lock:
            recs = [(r["first"], r["t0"], r["end"], r["done"])
                    for r in self.records]
        out, prev = [], None
        for first, t0, end, done in recs:
            since = t0 if first else prev
            if done is not None and since is not None:
                out.append((done - since) / 1e9)
            else:
                out.append((end - t0) / 1e9)
            prev = done
        return out

    # --- aggregates over the ring window --------------------------------

    def snapshot(self) -> Dict[str, float]:
        """The registry-named telemetry summary (heartbeat / event stream /
        Prometheus payload).  The host fraction and the step rate are over
        the host loop's own time (enqueue to enqueue); the starved
        fraction is over the wall time of the window's segments, each from
        its first step's start to its last completion; the ``host/*`` keys
        are the host events inside the window's calls."""
        intervals = sorted(self.step_intervals())
        with self._lock:
            rows = [(r["first"], r["t0"], r["end"], r["done"] or 0,
                     _seconds(r["data_wait"]), r["starved"] or 0)
                    for r in self.records]
            ids = {r["call"] for r in self.records}
        host = data = starved = wall = 0
        seg_t0 = seg_end = None
        for first, t0, end, done, data_wait, starved_ns in rows:
            host += end - t0
            data += data_wait
            starved += starved_ns
            if first or seg_t0 is None:      # a segment closes, one opens
                if seg_t0 is not None:
                    wall += seg_end - seg_t0
                seg_t0 = seg_end = t0
            seg_end = max(seg_end, end, done)
        if seg_t0 is not None:
            wall += seg_end - seg_t0
        host /= 1e9
        over = lambda x, base: x / base if base > 0 else 0.0
        return {
            **self._host_summary(ids),
            "time/step_p50_ms": percentile(intervals, 0.50) * 1e3,
            "time/step_p95_ms": percentile(intervals, 0.95) * 1e3,
            "time/step_p99_ms": percentile(intervals, 0.99) * 1e3,
            "time/host_data_wait_frac": over(data, host),
            "time/device_starved_frac": over(starved, wall),
            "time/steps_per_sec": over(len(rows), host),
        }

    def _host_summary(self, call_ids) -> Dict[str, float]:
        """The host events that overlap the calls ``call_ids``, each call
        to its end (an open one to now): how many compiles (or cache
        answers) and the seconds some trace, lowering or compile covers,
        since a recompile in the loop is an operator's first question; the
        longest collector pass and the share of the calls' wall under one
        (passes of :data:`GC_RING_MIN_NS` or more: the ring's)."""
        now = self._clock()
        calls = [(c["t0"], now if c["t1"] is None else c["t1"])
                 for c in list(self._calls) if c["call"] in call_ids]
        events = [ev for ev in (self._events.events(calls[0][0], calls[-1][1])
                                if calls else [])
                  if any(ev[2] <= t1 and ev[3] >= t0 for t0, t1 in calls)]
        of = lambda *kinds: [(s, e) for k, _, s, e in events if k in kinds]
        passes = of("gc")
        wall = sum(t1 - t0 for t0, t1 in calls)
        return {
            "host/compiles": float(len(of("compile"))),
            "host/compile_s": _covered(of("trace", "lower", "compile"),
                                       calls) / 1e9,
            "host/gc_ms_max": max((e - s for s, e in passes), default=0) / 1e6,
            "host/gc_frac": _covered(passes, calls) / wall if wall > 0 else 0.0,
        }

    def drain(self) -> List[Dict[str, Any]]:
        """Per-step records (exported form) accumulated since the previous
        drain — the event stream attaches these to epoch/window records so
        tools/trace_report.py can rebuild the timeline.  Ring-bounded at
        ``capacity``: a longer window keeps its NEWEST spans (the same
        window :meth:`snapshot` summarizes), dropping the head.  Steps the
        watcher has not passed yet carry ``done=None``: :meth:`flush`
        first where the device has been drained."""
        with self._lock:
            recs = [dict(r) for r in self._pending]
        self._pending.clear()
        if not recs:
            return []
        # each event to the steps it overlapped: the steps follow one
        # another, so the first is found by its end
        ends = [r["end"] for r in recs]
        over: Dict[int, List] = {}
        for ev in self._events.events(recs[0]["t0"], ends[-1]):
            i = bisect.bisect_left(ends, ev[2])
            while i < len(recs) and recs[i]["t0"] <= ev[3]:
                over.setdefault(i, []).append(ev)
                i += 1
        return [self._as_event(r, over.get(i, ())) for i, r in enumerate(recs)]


_PROCESS_TIMELINE: Optional[StepTimeline] = None


def process_timeline() -> StepTimeline:
    """The process-wide timeline: where ``run_train_epoch`` records when
    its caller passes none, so that a caller that knows nothing of
    timelines (a benchmark's builder) still leaves its spans and stamps
    behind.  Built on first use."""
    global _PROCESS_TIMELINE
    if _PROCESS_TIMELINE is None:
        _PROCESS_TIMELINE = StepTimeline(capacity=PROCESS_CAPACITY)
    return _PROCESS_TIMELINE
