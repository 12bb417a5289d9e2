"""Observability: metric registry conformance, step timeline, event stream,
Prometheus export, watchdog, loggers, meters, profiler wiring."""

import itertools
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tpu_compressed_dp.obs import export as obs_export
from tpu_compressed_dp.obs import registry as obs_registry
from tpu_compressed_dp.obs import trace as obs_trace
from tpu_compressed_dp.obs.trace import StepTimeline
from tpu_compressed_dp.utils import meters
from tpu_compressed_dp.utils.loggers import FileLogger, NoOp, TensorboardLogger


@pytest.mark.quick
class TestMetricRegistry:
    def test_every_spec_is_wellformed(self):
        for name, ms in obs_registry.REGISTRY.items():
            assert ms.name == name
            assert ms.kind in ("counter", "gauge", "timing")
            assert ms.reduction in ("mean", "sum", "min", "max")
            assert ms.emitter in ("engine", "step", "eval", "host")

    def test_canonical_maps_engine_keys(self):
        assert obs_registry.canonical("sent_bits") == "comm/sent_bits"
        assert obs_registry.canonical("comm/sent_bits") == "comm/sent_bits"
        assert obs_registry.canonical("guard/nonfinite") == "guard/nonfinite"
        assert obs_registry.is_declared("sync_agree")
        assert not obs_registry.is_declared("made_up_key")
        assert obs_registry.undeclared(["sent_bits", "nope"]) == ["nope"]

    def test_redeclare_conflict_rejected(self):
        with pytest.raises(ValueError, match="already declared"):
            obs_registry.declare("loss", "counter", "nats", "sum", "step")
        # identical redeclaration is a no-op
        ms = obs_registry.REGISTRY["loss"]
        obs_registry.declare(ms.name, ms.kind, ms.unit, ms.reduction,
                             ms.emitter, ms.help)

    def test_prometheus_name_sanitised(self):
        assert obs_registry.prometheus_name("sent_bits") == \
            "tcdp_comm_sent_bits"
        assert obs_registry.prometheus_name("time/step_p95_ms") == \
            "tcdp_time_step_p95_ms"

    def test_diag_table_derived_from_registry(self):
        """The partitioned engine's diagnostic-reduction table is BUILT from
        the registry declarations — min -> pmin, max -> pmax."""
        import jax

        from tpu_compressed_dp.parallel import dp

        diags = obs_registry.engine_diag_reductions()
        assert diags == {"sync_agree": "min", "guard/nonfinite": "max"}
        assert set(dp._DIAG_STATS) == set(diags)
        assert dp._DIAG_STATS["sync_agree"][0] is jax.lax.pmin
        assert dp._DIAG_STATS["guard/nonfinite"][0] is jax.lax.pmax

    def test_accumulator_sum_keys_derived(self):
        from tpu_compressed_dp.utils.loggers import MetricAccumulator

        assert "correct" in MetricAccumulator.SUM_KEYS
        assert "loss_sum" in MetricAccumulator.SUM_KEYS
        assert "loss" not in MetricAccumulator.SUM_KEYS


CONFORMANCE_METHODS = [None, "topk", "blocktopk", "randomk", "thresholdv",
                       "adaptive_threshold", "terngrad", "qsgd", "powersgd"]


def _sync_stat_keys(cfg, mesh):
    """Trace one sync under shard_map (no compile/run: eval_shape) and
    return the stats keys it emits."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_compressed_dp.parallel.dp import init_comp_state, make_grad_sync

    grads = {"w": jnp.zeros((64, 8)), "b": jnp.zeros((8,))}
    sync = make_grad_sync(cfg)
    ef = (jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
          if cfg.error_feedback else ())
    comp = init_comp_state(grads, cfg)

    def f(g, e, c, k):
        # always guard-gated: covers the guard/nonfinite key; the ungated
        # path emits a strict subset
        return sync(g, e, c, k, ok=jnp.asarray(True))[3]

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(), P(), P(), P()),
                       out_specs=P(), check_vma=False)
    out = jax.eval_shape(sm, grads, ef, comp, jax.random.key(0))
    return set(out.keys())


class TestRegistryConformance:
    """Every stats key either sync engine can emit — across the FULL
    method x mode x transport x granularity matrix — must be declared in
    the metric registry.  Pure tracing (eval_shape), no compile: the whole
    matrix costs seconds, so tier-1 exercises all of it."""

    def test_all_methods_transports_granularities(self, mesh8):
        from tpu_compressed_dp.parallel.dp import CompressionConfig
        from tpu_compressed_dp.parallel.mesh import make_data_mesh

        mesh = make_data_mesh(4)
        failures = []
        seen = set()
        for m, mode, transport, gran in itertools.product(
                CONFORMANCE_METHODS, ("simulate", "wire"),
                ("allgather", "sharded", "hierarchical"),
                ("layerwise", "entiremodel", "bucketed")):
            # EF composes with everything except the unbiased quantizers
            # (wire mode rejects that combination at build time)
            ef = m not in (None, "terngrad", "qsgd")
            cfg = CompressionConfig(
                method=m, granularity=gran, mode=mode, transport=transport,
                ratio=0.25, error_feedback=ef, check_sync=True,
                dp_pods=2 if transport == "hierarchical" else 1)
            keys = _sync_stat_keys(cfg, mesh)
            seen |= keys
            bad = obs_registry.undeclared(keys)
            if bad:
                failures.append((m, mode, transport, gran, bad))
        assert not failures, f"undeclared stats keys: {failures}"
        # the matrix actually exercised the interesting keys (a silently
        # empty sweep would vacuously pass)
        for expected in ("sent_bits_psum", "sent_bits_alltoall",
                         "sent_bits_ici", "sent_bits_dcn",
                         "sent_bits_dcn_route", "shard_overflow",
                         "threshold_overflow", "sync_agree",
                         "guard/nonfinite"):
            assert expected in seen, f"matrix never emitted {expected}"

    def test_step_metric_keys_declared(self):
        """The step factories' own metric names (loss/correct/count/lr/
        tokens + guard/*) are declared too."""
        from tpu_compressed_dp.train.guard import (GuardConfig,
                                                   guard_metrics,
                                                   init_guard_state)

        gm = guard_metrics(init_guard_state(GuardConfig()))
        step_keys = {"loss", "correct", "count", "lr", "tokens",
                     "loss_sum", "correct5", *gm}
        assert obs_registry.undeclared(step_keys) == []


MS = 1_000_000      # the timeline's clock counts nanoseconds


class FakeClock:
    """Nanosecond clock the test sets; the watcher thread reads it too,
    at the time the output it waited on was scripted to be done."""
    t = 0
    stamped = 0

    def __call__(self):
        return self.t


class FakeOutput:
    """A step output whose readiness the test releases: the watcher's
    ``block_until_ready`` returns only then, at the scripted time."""

    def __init__(self, clock, done_at=None, fails=False):
        self.clock, self.done_at, self.fails = clock, done_at, fails
        self.ready = threading.Event()

    def release(self):
        self.ready.set()
        return self

    def block_until_ready(self):
        assert self.ready.wait(10), "the test never released this output"
        if self.fails:
            raise RuntimeError("the step failed on the device")
        if self.done_at is not None:      # stamps never run backwards
            self.clock.t = self.clock.stamped = max(self.clock.stamped,
                                                    self.done_at)


def _step(tl, clk, data, copy, dispatch, output):
    """One scripted step: the three spans of the given lengths (ms)."""
    for name, ms in (("data_wait", data), ("to_device", copy),
                     ("dispatch", dispatch)):
        with tl.span(name):
            clk.t += ms * MS
    tl.step_done({"loss": output})


def _scripted(capacity=8):
    """The schedule the stamp tests share, in ms: step 0 opens the call on
    a drained device (enqueued 16, done 116); step 1 is enqueued at 20 with
    the queue full (done 216); step 2 waits 280 on its data, is enqueued at
    306, 90 after the device ran dry, and is done at 406."""
    clk = FakeClock()
    tl = StepTimeline(capacity=capacity, clock=clk)
    tl.begin_call()
    outs = [FakeOutput(clk, 116 * MS), FakeOutput(clk, 216 * MS),
            FakeOutput(clk, 406 * MS)]
    _step(tl, clk, 10, 1, 5, outs[0])
    _step(tl, clk, 1, 1, 2, outs[1])
    _step(tl, clk, 280, 1, 5, outs[2])
    return tl, clk, outs


@pytest.mark.quick
class TestStepTimeline:
    @pytest.mark.parametrize("step,device,starved,by,under", [
        # the first step: the pipeline was drained, idle from the call's begin
        (0, 100, 16, {"data_wait": 10, "to_device": 1, "dispatch": 5,
                      "other": 0}, "data_wait"),
        # a full queue: enqueued long before the previous step was done
        (1, 100, 0, None, None),
        # starved under data_wait: 84 of the 90 ms fall in next()
        (2, 100, 90, {"data_wait": 84, "to_device": 1, "dispatch": 5,
                      "other": 0}, "data_wait"),
    ])
    def test_stamps_give_device_starved_and_cover(self, step, device,
                                                  starved, by, under):
        tl, clk, outs = _scripted()
        for out in outs:
            out.release()
        assert tl.flush(10)
        rec = tl.calls()[0]["records"][step]
        assert rec["ord"] == step and rec["first"] == (step == 0)
        assert rec["device"] == device * MS
        assert rec["starved"] == starved * MS
        assert rec["starved_by"] == (
            by and {k: v * MS for k, v in by.items()})
        ev = tl.drain()[step]
        assert ev["starved_in"] == under
        assert ev["device"] == pytest.approx(device / 1e3)
        assert ev["starved"] == pytest.approx(starved / 1e3)

    def test_stamps_arrive_in_dispatch_order(self):
        """Released last-first, the outputs are still stamped first-last:
        the watcher waits on them in the order they were dispatched."""
        tl, clk, outs = _scripted()
        outs[2].release()
        outs[1].release()
        assert not tl.flush(0.05)       # step 0 holds the queue
        assert all(r["done"] is None for r in tl.calls()[0]["records"])
        outs[0].release()
        assert tl.flush(10)
        done = [r["done"] for r in tl.calls()[0]["records"]]
        assert done == [116 * MS, 216 * MS, 406 * MS]

    def test_snapshot_percentiles_from_completion_intervals(self):
        tl, clk, outs = _scripted()
        for out in outs:
            out.release()
        assert tl.flush(10)
        # completion intervals 116 (from the call's begin), 100, 190; the
        # host's enqueue intervals were 16, 4 and 286
        assert sorted(tl.step_intervals()) == pytest.approx([.100, .116, .190])
        snap = tl.snapshot()
        assert snap["time/step_p50_ms"] == pytest.approx(116.0)
        assert snap["time/step_p95_ms"] == pytest.approx(190.0)
        assert snap["time/host_data_wait_frac"] == pytest.approx(291 / 306)
        assert snap["time/device_starved_frac"] == pytest.approx(106 / 406)
        assert snap["time/steps_per_sec"] == pytest.approx(3 / .306)

    def test_step_without_stamp_falls_back_to_host_interval(self):
        """A step whose output failed on the device keeps done=None; it
        and the step after it (no completion to measure from) report the
        host's enqueue interval, and the watcher goes on."""
        clk = FakeClock()
        tl = StepTimeline(capacity=8, clock=clk)
        tl.begin_call()
        outs = [FakeOutput(clk, 50 * MS), FakeOutput(clk, fails=True),
                FakeOutput(clk, 90 * MS), FakeOutput(clk, 120 * MS)]
        for out in outs:
            _step(tl, clk, 1, 1, 2, out.release())
        assert tl.flush(10)
        recs = tl.calls()[0]["records"]
        assert [r["done"] for r in recs] == [50 * MS, None, 90 * MS, 120 * MS]
        assert recs[2]["starved"] is None and recs[2]["device"] is None
        assert recs[3]["device"] == 30 * MS
        assert tl.step_intervals() == pytest.approx([.050, .004, .004, .030])

    def test_ring_bounds_memory_and_drain(self):
        clk = FakeClock()
        tl = StepTimeline(capacity=4, clock=clk)
        for _ in range(10):
            _step(tl, clk, 1, 0, 1, 0.0)    # a plain value is ready at once
        assert tl.flush(10)
        assert len(tl.records) == 4      # ring: most recent only
        assert tl.steps == 10
        call = tl.calls()[-1]
        assert call["steps"] == 10 and len(call["records"]) == 4
        drained = tl.drain()
        assert len(drained) <= 4         # pending is capacity-bounded too
        assert tl.drain() == []          # drained once
        assert {"t0", "data", "to_device", "dispatch", "total", "done",
                "device", "starved", "starved_in", "ord", "call"} \
            == set(drained[0])
        assert [d["ord"] for d in drained] == [6, 7, 8, 9]

    def test_resume_excludes_between_step_work(self):
        """Blocking between-step work (eval, checkpoint saves, a log-window
        device_get drain) must not be billed to the next step."""
        clk = FakeClock()
        tl = StepTimeline(capacity=8, clock=clk)
        tl.begin_call()
        outs = [FakeOutput(clk, 1500 * MS), FakeOutput(clk, 102_500 * MS)]
        _step(tl, clk, 100, 0, 900, outs[0])
        clk.t += 100_000 * MS    # epoch-end eval + checkpoint
        tl.resume()
        _step(tl, clk, 100, 0, 900, outs[1])
        outs[0].release()
        outs[1].release()
        assert tl.flush(10)
        evs = tl.drain()
        assert evs[1]["data"] == pytest.approx(0.1)
        assert evs[1]["total"] == pytest.approx(1.0)
        # a segment opener: starved since the resume mark, not since step 0
        assert evs[1]["starved"] == pytest.approx(1.0)
        snap = tl.snapshot()
        assert snap["time/host_data_wait_frac"] == pytest.approx(0.1)
        assert snap["time/step_p95_ms"] == pytest.approx(1500.0)

    def test_snapshot_keys_declared(self):
        tl, clk, outs = _scripted()
        assert obs_registry.undeclared(tl.snapshot()) == []
        assert {"time/host_data_wait_frac", "time/device_starved_frac"} \
            <= set(tl.snapshot())
        assert not obs_registry.is_declared("time/data_wait_frac")
        for out in outs:
            out.release()

    def test_run_train_epoch_records_into_process_timeline(self, monkeypatch):
        """With no timeline passed the loop's four spans, their step
        ordinals and the stamps land in the process-wide timeline, grouped
        by call, and every span is a profiler annotation."""
        from tpu_compressed_dp.harness.loop import run_train_epoch

        clk = FakeClock()
        tl = StepTimeline(capacity=obs_trace.PROCESS_CAPACITY, clock=clk)
        monkeypatch.setattr(obs_trace, "_PROCESS_TIMELINE", tl)
        seen = []
        real_span = obs_trace.host_span

        def host_span(name, **meta):
            seen.append((name, meta.get("step")))
            return real_span(name, **meta)

        monkeypatch.setattr(obs_trace, "host_span", host_span)

        def batches(n):
            for _ in range(n):
                clk.t += 3 * MS
                yield {"input": np.zeros((2,), np.float32)}

        def train_step(state, batch):
            clk.t += 2 * MS
            return state + 1, {"loss": 1.0, "count": 2.0}

        state, acc = run_train_epoch(train_step, 0, batches(3))
        state, acc = run_train_epoch(train_step, state, batches(2))
        assert state == 5 and acc.steps == 2
        assert obs_trace.process_timeline() is tl
        calls = tl.calls()
        assert [c["call"] for c in calls] == [0, 1]
        assert [c["steps"] for c in calls] == [3, 2]
        assert [r["ord"] for c in calls for r in c["records"]] == [0, 1, 2, 3, 4]
        for c in calls:
            assert c["fetch"] is not None and c["t1"] >= c["fetch"][1]
            assert [r["first"] for r in c["records"]] == \
                [True] + [False] * (c["steps"] - 1)
            for r in c["records"]:
                assert r["data_wait"][1] - r["data_wait"][0] == 3 * MS
                assert r["dispatch"][1] - r["dispatch"][0] == 2 * MS
                assert r["data_wait"][1] <= r["to_device"][0] \
                    <= r["to_device"][1] <= r["dispatch"][0]
                assert r["done"] is not None    # whole when the call returns
        # annotations: the three spans of every step under its ordinal (the
        # exhausted iterator's last next() opens one more data_wait), and
        # the fetch once a call
        for ordinal in range(5):
            for name in ("loop.data_wait", "loop.to_device", "loop.dispatch"):
                assert (name, ordinal) in seen
        assert [n for n, _ in seen].count("loop.fetch") == 2

    def test_raising_step_leaves_no_stamp_and_flush_is_bounded(self):
        from tpu_compressed_dp.harness.loop import run_train_epoch

        clk = FakeClock()
        tl = StepTimeline(capacity=8, clock=clk)
        stuck = FakeOutput(clk)             # never released

        def train_step(state, batch):
            if state == 2:
                raise RuntimeError("boom")
            return state + 1, {"loss": stuck if state == 1 else 0.0}

        with pytest.raises(RuntimeError, match="boom") as info:
            run_train_epoch(train_step, 0,
                            iter([{"input": np.zeros(2)}] * 4), timeline=tl)
        assert info.value.elastic_state == 2
        recs = tl.calls()[0]["records"]
        assert len(recs) == 3 and recs[2]["dispatch"] is not None
        t0 = time.monotonic()
        assert not tl.flush(0.05)           # bounded: step 1 never completes
        assert time.monotonic() - t0 < 5
        assert [r["done"] is None for r in tl.calls()[0]["records"]] == \
            [False, True, True]
        assert tl._watcher.daemon           # the exit does not wait for it
        stuck.release()
        assert tl.flush(10)
        assert tl.calls()[0]["records"][2]["done"] is None   # it raised

    def test_interpreter_exits_with_a_stamp_outstanding(self):
        """A process whose watcher still waits on an output that never
        becomes ready exits when its main thread does."""
        code = (
            "import threading\n"
            "from tpu_compressed_dp.obs.trace import StepTimeline\n"
            "class Never:\n"
            "    def block_until_ready(self): threading.Event().wait()\n"
            "tl = StepTimeline()\n"
            "with tl.span('dispatch'): pass\n"
            "tl.step_done({'loss': Never()})\n"
            "print('flushed', tl.flush(0.05))\n")
        out = subprocess.run([sys.executable, "-c", code], timeout=120,
                             capture_output=True, text=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        assert "flushed False" in out.stdout


def _gc_pass(ring, clk, generation, start_ms, ms):
    """One scripted collector pass, through the callback gc would call."""
    clk.t = int(start_ms * MS)
    ring.on_gc("start", {"generation": generation})
    clk.t = int((start_ms + ms) * MS)
    ring.on_gc("stop", {"generation": generation})


@pytest.mark.quick
class TestHostEvents:
    """The ring of host events under a scripted clock, its listeners in a
    live process, and what the timeline's views make of them."""

    def test_ring_is_bounded_and_totals_survive_roll_off(self):
        clk = FakeClock()
        ring = obs_trace.HostEvents(capacity=4, clock=clk)
        for i in range(10):
            ring.add("compile", f"jit(f{i})", i * 10 * MS, (i * 10 + 5) * MS)
        held = ring.events()
        assert len(held) == 4 and [e[1] for e in held] == [
            "jit(f6)", "jit(f7)", "jit(f8)", "jit(f9)"]
        assert ring.totals() == {"compile": (10, 50 * MS)}
        # a view between two times: what overlaps it, ends included
        assert [e[1] for e in ring.events(75 * MS, 80 * MS)] == [
            "jit(f7)", "jit(f8)"]
        with pytest.raises(ValueError, match="capacity"):
            obs_trace.HostEvents(capacity=0)

    @pytest.mark.parametrize("events,covered_ms", [
        # a jitted function traced inside another's trace ends first
        ([(20, 40), (0, 100)], 100),
        # ... and an outer one swallows two that were apart
        ([(10, 20), (50, 60), (0, 100)], 100),
        # apart: the sum; touching or overlapping: the union
        ([(0, 10), (30, 40)], 20),
        ([(0, 10), (5, 25), (25, 30)], 30),
        # out of order (two threads): still the union
        ([(50, 60), (0, 55)], 60),
    ])
    def test_a_kind_total_is_the_union_of_its_intervals(self, events,
                                                        covered_ms):
        ring = obs_trace.HostEvents(clock=FakeClock())
        for start, end in events:
            ring.add("trace", "f", start * MS, end * MS)
        assert ring.totals()["trace"] == (len(events), covered_ms * MS)
        assert len(ring.events()) == len(events)     # the ring keeps each

    def test_duration_listener_stamps_back_from_the_end(self):
        """A duration listener learns of an event when it ends."""
        from jax._src import dispatch

        clk = FakeClock()
        ring = obs_trace.HostEvents(clock=clk)
        clk.t = 500 * MS
        ring.on_duration(dispatch.JAXPR_TRACE_EVENT, 0.2, fun_name="step")
        ring.on_duration(dispatch.JAXPR_TO_MLIR_MODULE_EVENT, 0.05,
                         fun_name="jit(step)")
        ring.on_duration(dispatch.BACKEND_COMPILE_EVENT, 0.1,
                         fun_name="jit(step)")
        ring.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.03)
        ring.on_duration("/jax/compilation_cache/compile_time_saved_sec", 9.0)
        ring.on_event("/jax/compilation_cache/cache_hits")
        ring.on_event("/jax/compilation_cache/cache_misses")
        ring.on_event("/jax/compilation_cache/cache_misses")
        ring.on_event("/jax/compilation_cache/tasks_using_cache")
        assert ring.events() == [
            ("trace", "step", 300 * MS, 500 * MS),
            ("lower", "jit(step)", 450 * MS, 500 * MS),
            ("compile", "jit(step)", 400 * MS, 500 * MS),
            ("cache_read", "", 470 * MS, 500 * MS)]
        assert ring.totals() == {
            "trace": (1, 200 * MS), "lower": (1, 50 * MS),
            "compile": (1, 100 * MS), "cache_read": (1, 30 * MS),
            "cache_hits": (1, 0), "cache_misses": (2, 0)}
        assert set(ring.totals()) - {"cache_hits", "cache_misses"} \
            <= set(obs_trace.HOST_EVENT_KINDS)

    def test_short_collector_pass_is_counted_and_not_ringed(self):
        clk = FakeClock()
        ring = obs_trace.HostEvents(clock=clk)
        _gc_pass(ring, clk, 0, 10, 0.4)
        _gc_pass(ring, clk, 0, 20, 0.2)
        _gc_pass(ring, clk, 1, 30, 3)
        _gc_pass(ring, clk, 2, 40, 118)
        ring.on_gc("stop", {"generation": 2})    # installed mid-pass: no start
        assert ring.events() == [("gc", "gen1", 30 * MS, 33 * MS),
                                 ("gc", "gen2", 40 * MS, 158 * MS)]
        assert obs_trace.GC_RING_MIN_NS == MS
        assert ring.totals() == {
            "gc.gen0": (2, 600_000), "gc.gen1": (1, 3 * MS),
            "gc.gen2": (1, 118 * MS), "gc": (4, 121 * MS + 600_000)}

    def test_threads_stamp_and_read_with_no_lost_update(self):
        """Sixteen threads stamp events while the collector's callback
        (lock-free: it can fire inside ``add``) stamps passes and a reader
        copies the ring: every event is counted once, every nanosecond
        once, and no reader sees a ring changing under it."""
        import gc

        ring = obs_trace.HostEvents(capacity=64)
        passes = []
        count_pass = lambda phase, info: phase == "stop" and passes.append(1)
        threads, each = 16, 400
        errors = []

        def stamp(k):
            try:
                for i in range(each):
                    at = (k * each + i) * 10 * MS
                    ring.add("trace", f"f{k}", at, at + 4 * MS)
                    junk = [[j] for j in range(50)]     # feeds the collector
                    ring.on_event("/jax/compilation_cache/cache_hits")
            except Exception as exc:                    # noqa: BLE001
                errors.append(exc)

        interval = sys.getswitchinterval()
        gc.callbacks.extend([ring.on_gc, count_pass])
        sys.setswitchinterval(1e-6)
        try:
            workers = [threading.Thread(target=stamp, args=(k,))
                       for k in range(threads)]
            for w in workers:
                w.start()
            deadline = time.monotonic() + 60
            while any(w.is_alive() for w in workers):
                assert time.monotonic() < deadline
                assert len(ring.events()) <= 64
                ring.totals()
            for w in workers:
                w.join(10)
                assert not w.is_alive()
        finally:
            sys.setswitchinterval(interval)
            gc.callbacks.remove(ring.on_gc)
            gc.callbacks.remove(count_pass)
        assert errors == []
        totals = ring.totals()
        assert totals["trace"] == (threads * each, threads * each * 4 * MS)
        assert totals["cache_hits"] == (threads * each, 0)
        assert passes and totals["gc"][0] == len(passes)

    def test_install_twice_installs_one_listener_of_each_kind(self):
        import gc

        from jax._src import monitoring

        ring = obs_trace.install_host_events()
        assert obs_trace.install_host_events() is ring
        mine = lambda fns: [f for f in fns
                            if getattr(f, "__self__", None) is ring]
        assert len(mine(monitoring.get_event_duration_listeners())) == 1
        assert len(mine(monitoring.get_event_listeners())) == 1
        assert len(mine(gc.callbacks)) == 1

    def test_setup_compile_cache_installs_them(self, monkeypatch):
        """Every entry point calls it first: no entry point is edited."""
        import jax

        from tpu_compressed_dp.parallel import mesh as mesh_mod

        monkeypatch.setattr(obs_trace, "_HOST_EVENTS_INSTALLED", True)
        called = []
        monkeypatch.setattr(mesh_mod, "install_host_events",
                            lambda: called.append(1))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert mesh_mod.setup_compile_cache() == "/somewhere/else"
        assert called == [1]

    def test_a_jitted_function_leaves_one_event_of_each_kind(self):
        """A real ``jax.jit``: the first call traces, lowers and compiles
        under the function's name, the second does none of the three."""
        import jax

        ring = obs_trace.install_host_events()

        @jax.jit
        def tcdp_fresh_function_39(x):
            return x * 2 + 1

        t0 = time.time_ns()
        tcdp_fresh_function_39(np.ones(3, np.float32)).block_until_ready()
        t1 = time.time_ns()
        mine = [e for e in ring.events(t0, t1)
                if "tcdp_fresh_function_39" in e[1]]
        assert sorted(e[0] for e in mine) == ["compile", "lower", "trace"]
        assert {e[0]: e[1] for e in mine}["trace"] == "tcdp_fresh_function_39"
        assert all(t0 <= e[2] <= e[3] <= t1 for e in mine)
        tcdp_fresh_function_39(np.ones(3, np.float32)).block_until_ready()
        again = [e for e in ring.events(t1, None)
                 if "tcdp_fresh_function_39" in e[1] and e[2] > t1]
        assert again == []

    def test_collect_inside_an_open_call_lands_in_its_events(self):
        """The process-wide ring under the real clock: a ``gc.collect()``
        while a call is open is the call's, the step's and the snapshot's."""
        import gc

        obs_trace.install_host_events()
        tl = StepTimeline(capacity=8)
        junk = [[i] for i in range(200_000)]      # a pass worth a millisecond
        tl.begin_call()
        with tl.span("dispatch"):
            gc.collect()
        tl.step_done({"loss": 1.0})
        with tl.span("dispatch"):
            pass
        tl.step_done({"loss": 1.0})
        tl.end_call()
        del junk
        call = tl.calls()[-1]
        passes = [e for e in call["events"] if e[:2] == ("gc", "gen2")]
        assert passes and all(
            call["t0"] <= e[2] <= e[3] <= call["t1"] for e in passes)
        gc0, gc1 = call["totals0"].get("gc", (0, 0)), call["totals1"]["gc"]
        assert gc1[0] > gc0[0] and gc1[1] - gc0[1] >= passes[0][3] - passes[0][2]
        snap = tl.snapshot()
        longest = max(e[3] - e[2] for e in passes) / 1e6
        assert snap["host/gc_ms_max"] == pytest.approx(longest)
        assert 0 < snap["host/gc_frac"] <= 1
        assert tl.host_events(call["t0"], call["t1"]) == call["events"]
        first, second = tl.drain()
        assert ["gc", "gen2"] in [e[:2] for e in first["events"]]

    def test_step_exports_events_only_where_one_overlapped(self):
        clk = FakeClock()
        ring = obs_trace.HostEvents(clock=clk)
        tl = StepTimeline(capacity=8, clock=clk, events=ring)
        tl.begin_call()
        _step(tl, clk, 1, 1, 2, 0.0)               # 0-4 ms
        _step(tl, clk, 1, 1, 129, 0.0)             # 4-135 ms: the pause
        _step(tl, clk, 1, 1, 2, 0.0)               # 135-139 ms
        now = clk.t
        _gc_pass(ring, clk, 2, 10, 118)
        ring.add("compile", "jit(step)", 3 * MS, 5 * MS)   # over two steps
        clk.t = now
        assert tl.flush(10)
        tl.end_call()
        one, two, three = tl.drain()
        assert one["events"] == [["compile", "jit(step)", 2.0, 3.0]]
        assert two["events"] == [["gc", "gen2", 118.0, 6.0],
                                 ["compile", "jit(step)", 2.0, -1.0]]
        assert "events" not in three
        assert set(three) == {"t0", "data", "to_device", "dispatch", "total",
                              "done", "device", "starved", "starved_in",
                              "ord", "call"}
        call = tl.calls()[0]
        assert call["events"] == ring.events() and call["totals0"] == {}
        assert call["totals1"]["gc.gen2"] == (1, 118 * MS)

    def test_snapshot_counts_compiles_inside_the_windows_calls(self):
        clk = FakeClock()
        ring = obs_trace.HostEvents(clock=clk)
        tl = StepTimeline(capacity=8, clock=clk, events=ring)
        ring.add("compile", "jit(init)", 0, 50 * MS)         # before any call
        clk.t = 100 * MS
        tl.begin_call()                                      # 100-400 ms
        _step(tl, clk, 0, 0, 300, 0.0)
        ring.add("trace", "step", 100 * MS, 200 * MS)
        ring.add("trace", "inner", 120 * MS, 140 * MS)
        ring.add("lower", "jit(step)", 190 * MS, 250 * MS)
        ring.add("compile", "jit(step)", 250 * MS, 300 * MS)
        _gc_pass(ring, clk, 2, 340, 30)
        _gc_pass(ring, clk, 1, 380, 3)
        clk.t = 400 * MS
        assert tl.flush(10)
        tl.end_call()
        ring.add("compile", "jit(eval)", 450 * MS, 480 * MS)  # between calls
        snap = tl.snapshot()
        assert snap["host/compiles"] == 1.0
        assert snap["host/compile_s"] == pytest.approx(0.200)  # the union
        assert snap["host/gc_ms_max"] == pytest.approx(30.0)
        assert snap["host/gc_frac"] == pytest.approx(33 / 300)
        assert obs_registry.undeclared(snap) == []

    @pytest.mark.parametrize("key", ["host/compiles", "host/compile_s",
                                     "host/gc_ms_max", "host/gc_frac"])
    def test_host_keys_are_declared_and_exported(self, key, tmp_path):
        assert obs_registry.is_declared(key)
        assert obs_registry.spec(key).emitter == "host"
        snap = StepTimeline(clock=FakeClock()).snapshot()
        assert snap[key] == 0.0                   # an empty window reads 0
        body = obs_export.write_prometheus(snap, str(tmp_path / "m.prom"))
        assert f"# HELP {obs_registry.prometheus_name(key)} " in body

    def test_process_exits_clean_after_run_train_epoch(self):
        """The watcher is ended and joined from an exit hook: a process
        that ran one ``run_train_epoch`` leaves nothing on standard error
        (on the TPU's host the thread was unwound inside native code and
        the run's last 2,000 characters were a SIGABRT trace)."""
        code = (
            "import threading, numpy as np\n"
            "from tpu_compressed_dp.harness.loop import run_train_epoch\n"
            "from tpu_compressed_dp.obs import trace\n"
            "step = lambda s, b: (s + 1, {'loss': 1.0, 'count': 2.0})\n"
            "s, acc = run_train_epoch(step, 0, iter([{'input': np.zeros(2)}] * 3))\n"
            "w = trace.process_timeline()._watcher\n"
            "import atexit\n"
            "atexit.register(lambda: print('watcher alive at exit:', w.is_alive()))\n"
            "print('steps', acc.steps, w.name, w.is_alive())\n")
        out = subprocess.run([sys.executable, "-c", code], timeout=120,
                             capture_output=True, text=True,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0 and out.stderr == "", out.stderr
        assert "steps 3 tcdp-step-stamps True" in out.stdout

    def test_watcher_is_joined_when_its_timeline_goes(self):
        import gc

        tl = StepTimeline(capacity=4, clock=FakeClock())
        _step(tl, tl._clock, 1, 0, 1, 0.0)
        assert tl.flush(10)
        watcher = tl._watcher
        assert watcher.is_alive()
        del tl
        gc.collect()
        assert not watcher.is_alive()


@pytest.mark.quick
class TestTimerRegression:
    def test_constant_memory_and_split_semantics(self, monkeypatch):
        """utils/timer.Timer kept every split timestamp forever (unbounded
        on long runs); it must keep only the last one, with identical
        split/total semantics."""
        from tpu_compressed_dp.utils import timer as timer_mod

        t = {"now": 100.0}
        monkeypatch.setattr(timer_mod.time, "time", lambda: t["now"])
        tm = timer_mod.Timer()
        assert not hasattr(tm, "times")   # the unbounded list is gone
        t["now"] = 101.5
        assert tm(include_in_total=True) == pytest.approx(1.5)
        t["now"] = 102.0
        assert tm(include_in_total=False) == pytest.approx(0.5)
        t["now"] = 104.0
        assert tm() == pytest.approx(2.0)
        assert tm.total_time == pytest.approx(3.5)  # excluded split stays out
        # a long run's split count leaves no growing state behind
        for _ in range(1000):
            t["now"] += 0.001
            tm()
        assert isinstance(tm.last_time, float)


@pytest.mark.quick
class TestEventStreamAndPrometheus:
    def test_stream_schema_and_roundtrip(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with obs_export.EventStream(p, meta={"harness": "t"}) as es:
            es.emit("step", step=1, metrics={"loss": 1.0})
        events = obs_export.read_events(p)
        assert [e["kind"] for e in events] == ["run_start", "step", "run_end"]
        assert all(e["v"] == obs_export.SCHEMA_VERSION for e in events)
        assert all("ts" in e for e in events)
        assert events[0]["harness"] == "t"
        assert events[1]["metrics"] == {"loss": 1.0}
        # append-only: a resumed run extends the same file
        with obs_export.EventStream(p) as es:
            es.emit("step", step=2)
        assert len(obs_export.read_events(p)) == 6

    def test_prometheus_textfile(self, tmp_path):
        p = str(tmp_path / "m.prom")
        obs_export.write_prometheus(
            {"comm/sent_bits": 1.5e6, "made/up": 2.0, "skipme": "str"},
            p, labels={"harness": "dawn"})
        body = open(p).read()
        # everything exposes as gauge: the harnesses write per-window
        # aggregates, not running totals — a counter TYPE would make
        # Prometheus rate() treat every dip as a reset
        assert "# TYPE tcdp_comm_sent_bits gauge" in body
        assert '# HELP tcdp_comm_sent_bits' in body
        assert 'tcdp_comm_sent_bits{harness="dawn"} 1.5e+06' in body
        assert "# TYPE tcdp_made_up gauge" in body
        assert "skipme" not in body

    def test_telemetry_snapshot(self):
        clk = FakeClock()
        tl = StepTimeline(clock=clk)
        _step(tl, clk, 1000, 0, 1000, FakeOutput(clk, 2500 * MS).release())
        assert tl.flush(10)
        snap = obs_export.telemetry_snapshot(tl, step=7, last_good_step=5)
        assert snap["step"] == 7 and snap["last_good_step"] == 5
        assert snap["steps_per_sec"] == pytest.approx(0.5)
        # the step was done 0.5 s after its enqueue: the stamp says 2.5 s
        assert snap["step_p95_ms"] == pytest.approx(2500.0)
        assert snap["device_starved_frac"] == pytest.approx(2.0 / 2.5)
        assert "data_wait_frac" not in snap


@pytest.mark.quick
class TestWatchdog:
    def _hb(self, tmp_path, **kw):
        import time as _time

        p = str(tmp_path / "hb.json")
        rec = {"ts": _time.time(), "step": 100, "last_good_step": 100}
        rec.update(kw)
        json.dump(rec, open(p, "w"))
        return p

    def test_healthy(self, tmp_path):
        from tpu_compressed_dp.utils.resilience import check_heartbeat

        p = self._hb(tmp_path, telemetry={"steps_per_sec": 2.0})
        assert check_heartbeat(p, max_age_s=60, max_wedge_steps=10,
                               min_steps_per_sec=0.1) == []

    def test_stale_wedged_stalled_missing(self, tmp_path):
        import time as _time

        from tpu_compressed_dp.utils.resilience import check_heartbeat

        p = self._hb(tmp_path, ts=_time.time() - 999, last_good_step=10,
                     telemetry={"steps_per_sec": 0.001})
        probs = check_heartbeat(p, max_age_s=60, max_wedge_steps=50,
                                min_steps_per_sec=0.1)
        assert len(probs) == 3
        assert any("stale" in x for x in probs)
        assert any("wedged" in x for x in probs)
        assert any("stalled" in x for x in probs)
        missing = check_heartbeat(str(tmp_path / "no.json"))
        assert missing and "missing" in missing[0]
        # absent optional fields skip their checks, not fail them
        q = str(tmp_path / "hb2.json")
        json.dump({"ts": _time.time(), "step": 5}, open(q, "w"))
        assert check_heartbeat(q, max_age_s=60, max_wedge_steps=1,
                               min_steps_per_sec=1.0) == []

    def test_cli_exit_codes(self, tmp_path):
        import time as _time

        import tools.watchdog as wd

        p = self._hb(tmp_path)
        assert wd.main(["--check", "--heartbeat", p]) == 0
        json.dump({"ts": _time.time() - 999, "step": 1}, open(p, "w"))
        assert wd.main(["--check", "--heartbeat", p]) == 1
        assert wd.main(["--check", "--heartbeat",
                        str(tmp_path / "no.json")]) == 2

    def test_max_ckpt_age_cli(self, tmp_path):
        """--max_ckpt_age reads the Checkpointer.heartbeat_fields payload
        the harnesses fold into the heartbeat (ISSUE 9 satellite)."""
        import tools.watchdog as wd

        p = self._hb(tmp_path, last_ckpt_step=50, ckpt_age_s=500.0)
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_ckpt_age", "1000"]) == 0
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_ckpt_age", "60"]) == 1
        # without the flag the checkpoint clock is never consulted
        assert wd.main(["--check", "--heartbeat", p]) == 0

    def test_max_stream_lag_cli(self, tmp_path):
        """--max_stream_lag reads the StreamWriter.heartbeat_fields payload
        the harnesses fold into the heartbeat (delta-stream satellite)."""
        import tools.watchdog as wd

        p = self._hb(tmp_path, stream_last_step=50, stream_lag_s=500.0)
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_stream_lag", "1000"]) == 0
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_stream_lag", "60"]) == 1
        # without the flag the stream clock is never consulted
        assert wd.main(["--check", "--heartbeat", p]) == 0

    def test_max_straggler_skew_cli(self, tmp_path):
        """--max_straggler_skew reads the flight recorder's live
        straggler_skew_s the harnesses fold into the heartbeat."""
        import tools.watchdog as wd

        p = self._hb(tmp_path, straggler_skew_s=2.5, straggler_rank=3)
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_straggler_skew", "5"]) == 0
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_straggler_skew", "1"]) == 1
        # without the flag the skew gauge is never consulted
        assert wd.main(["--check", "--heartbeat", p]) == 0

    def test_max_straggler_skew_unit(self, tmp_path):
        from tpu_compressed_dp.utils.resilience import check_heartbeat

        p = self._hb(tmp_path, straggler_skew_s=2.5, straggler_rank=3)
        probs = check_heartbeat(p, max_straggler_skew_s=1.0)
        assert probs and "straggler" in probs[0]
        assert check_heartbeat(p, max_straggler_skew_s=5.0) == []
        # a heartbeat that never published the gauge skips the check
        q = self._hb(tmp_path)
        assert check_heartbeat(q, max_straggler_skew_s=0.001) == []

    def test_max_step_p95_cli(self, tmp_path):
        """--max_step_p95_ms reads the telemetry snapshot's tail latency."""
        import tools.watchdog as wd

        p = self._hb(tmp_path, telemetry={"step_p95_ms": 1800.0})
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_step_p95_ms", "2000"]) == 0
        assert wd.main(["--check", "--heartbeat", p,
                        "--max_step_p95_ms", "1500"]) == 1
        # without the flag the tail latency is never consulted
        assert wd.main(["--check", "--heartbeat", p]) == 0

    def test_max_step_p95_unit(self, tmp_path):
        from tpu_compressed_dp.utils.resilience import check_heartbeat

        p = self._hb(tmp_path, telemetry={"step_p95_ms": 1800.0})
        probs = check_heartbeat(p, max_step_p95_ms=1500.0)
        assert probs and "slow tail" in probs[0]
        assert check_heartbeat(p, max_step_p95_ms=2000.0) == []
        # a heartbeat whose telemetry never published p95 skips the check
        q = self._hb(tmp_path, telemetry={"steps_per_sec": 2.0})
        assert check_heartbeat(q, max_step_p95_ms=0.001) == []


@pytest.mark.quick
class TestWatchdogRelaunch:
    """The relaunch decision loop (tools/watchdog.py supervise) against a
    fake child and a scripted heartbeat-verdict sequence: restart on
    wedge/death with doubling backoff, budget refilled by a healthy check,
    give-up after max_relaunches CONSECUTIVE restarts, clean exit ends
    supervision."""

    class _Child:
        def __init__(self, rc=None):
            self.rc = rc  # None = still running

        def poll(self):
            return self.rc

        @property
        def returncode(self):
            return self.rc

    def _drive(self, verdicts, *, max_relaunches=2, grace=0.0, interval=1.0,
               child_rcs=(), max_checks=None):
        import tools.watchdog as wd

        spawned, killed, sleeps = [], [], []

        def spawn():
            rc = (child_rcs[len(spawned)] if len(spawned) < len(child_rcs)
                  else None)
            c = self._Child(rc)
            spawned.append(c)
            return c

        it = iter(verdicts)
        rc = wd.supervise(
            spawn, lambda: next(it),
            interval_s=interval, grace_s=grace,
            max_relaunches=max_relaunches, backoff_s=5.0, backoff_cap_s=40.0,
            sleep=sleeps.append, kill=lambda c, **k: killed.append(c),
            log=lambda m: None, max_checks=max_checks)
        return rc, spawned, killed, sleeps

    def test_clean_exit_ends_supervision(self):
        rc, spawned, killed, _ = self._drive([], child_rcs=[0])
        assert rc == 0 and len(spawned) == 1 and killed == []

    def test_wedge_relaunches_with_doubling_backoff_then_gives_up(self):
        rc, spawned, killed, sleeps = self._drive([1, 1, 1], max_relaunches=2)
        assert rc == 1  # wedged (alive) children report generic failure
        assert len(spawned) == 1 + 2  # initial + both budgeted relaunches
        assert len(killed) == 3  # 2 relaunch kills + the give-up kill
        # sleep trace: tick, backoff 5, tick, backoff 10 (doubled), tick
        assert sleeps == [1.0, 5.0, 1.0, 10.0, 1.0]

    def test_dead_childs_exit_code_propagates_on_give_up(self):
        rc, spawned, _, _ = self._drive([1], max_relaunches=0, child_rcs=[7])
        assert rc == 7 and len(spawned) == 1

    def test_preempt_exit_relaunches_immediately_without_backoff(self):
        """PREEMPT_EXIT (emergency checkpoint cut, deliberate exit) respawns
        NOW: no backoff sleep, no kill, no consecutive-budget burn — proven
        by a ZERO relaunch budget and an EMPTY verdict script (a consumed
        health check would raise StopIteration)."""
        from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

        rc, spawned, killed, sleeps = self._drive(
            [], child_rcs=[PREEMPT_EXIT, 0], max_relaunches=0)
        assert rc == 0
        assert len(spawned) == 2       # respawned despite max_relaunches=0
        assert killed == []
        assert sleeps == [1.0, 1.0]    # two plain ticks, no backoff inserted

    def test_healthy_check_refills_budget_and_resets_backoff(self):
        rc, spawned, _, sleeps = self._drive([1, 0, 1], max_relaunches=2,
                                             max_checks=3)
        assert rc == 0  # bounded by max_checks, never gave up
        assert len(spawned) == 3
        backoffs = [s for s in sleeps if s != 1.0]
        assert backoffs == [5.0, 5.0]  # second wedge backs off from the base

    def test_grace_period_suppresses_checks_after_each_launch(self):
        rc, _, _, sleeps = self._drive([0, 0], grace=2.5, max_checks=2)
        assert rc == 0
        # 2 silent warm-up ticks before the 1st check, then 2 checked ticks
        assert sleeps == [1.0, 1.0, 1.0, 1.0]

    def test_cli_requires_command(self, capsys):
        import tools.watchdog as wd

        assert wd.main(["--relaunch", "--heartbeat", "hb.json"]) == 2
        assert "training command" in capsys.readouterr().out

    def test_exception_kills_child_not_orphans(self):
        """Ctrl-C (or a check() crash) mid-supervision must kill the child
        on the way out — a detached run would keep refreshing the
        heartbeat under a restarted watchdog's feet."""
        import tools.watchdog as wd

        spawned, killed = [], []

        def spawn():
            c = self._Child(None)
            spawned.append(c)
            return c

        def check():
            raise KeyboardInterrupt

        with pytest.raises(KeyboardInterrupt):
            wd.supervise(spawn, check, interval_s=1.0, grace_s=0.0,
                         max_relaunches=2, sleep=lambda s: None,
                         kill=lambda c, **k: killed.append(c),
                         log=lambda m: None)
        assert killed == spawned  # the (only) child was cleaned up


class TestPreemptStorm:
    """The preempt-storm guard: free PREEMPT_EXIT respawns are rate-capped
    — more than ``max_preempts`` inside the sliding window falls through
    to the unhealthy path (budget, backoff, give-up) instead of respawning
    forever on the supervisor's dime."""

    def _drive(self, child_rcs, *, max_preempts, preempt_window_s=600.0,
               max_relaunches=0, verdicts=()):
        import tools.watchdog as wd

        spawned, killed, sleeps = [], [], []

        def spawn():
            rc = (child_rcs[len(spawned)] if len(spawned) < len(child_rcs)
                  else None)
            c = TestWatchdogRelaunch._Child(rc)
            spawned.append(c)
            return c

        it = iter(verdicts)
        rc = wd.supervise(
            spawn, lambda: next(it), interval_s=1.0, grace_s=0.0,
            max_relaunches=max_relaunches, backoff_s=5.0,
            backoff_cap_s=40.0, sleep=sleeps.append,
            kill=lambda c, **k: killed.append(c), log=lambda m: None,
            max_preempts=max_preempts, preempt_window_s=preempt_window_s)
        return rc, spawned, killed, sleeps

    def test_storm_gives_up_with_childs_exit_code(self):
        from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

        rc, spawned, killed, sleeps = self._drive(
            [PREEMPT_EXIT] * 3, max_preempts=2)
        # two free respawns, the third preempt in the window is the storm:
        # zero budget left => give up, propagating the child's exit 75
        assert rc == PREEMPT_EXIT
        assert len(spawned) == 3
        assert len(killed) == 1
        assert sleeps == [1.0, 1.0, 1.0]  # never a backoff, never a check

    def test_storm_spends_the_budget_before_giving_up(self):
        from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

        rc, spawned, killed, sleeps = self._drive(
            [PREEMPT_EXIT, PREEMPT_EXIT, 0], max_preempts=1,
            max_relaunches=1)
        # preempt #2 is the storm, but one budgeted relaunch remains: kill,
        # back off, respawn — and that child exits cleanly
        assert rc == 0
        assert len(spawned) == 3 and len(killed) == 1
        assert sleeps == [1.0, 1.0, 5.0, 1.0]

    def test_preempts_outside_the_window_never_storm(self):
        from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

        # window shorter than the tick spacing: each preempt evicts the
        # previous from the deque — five in a row stay "free" even at cap 1
        rc, spawned, killed, sleeps = self._drive(
            [PREEMPT_EXIT] * 5 + [0], max_preempts=1, preempt_window_s=0.5)
        assert rc == 0
        assert len(spawned) == 6 and killed == []
        assert sleeps == [1.0] * 6

    def test_cap_none_disables_the_guard(self):
        from tpu_compressed_dp.utils.resilience import PREEMPT_EXIT

        rc, spawned, killed, _ = self._drive(
            [PREEMPT_EXIT] * 9 + [0], max_preempts=None)
        assert rc == 0 and len(spawned) == 10 and killed == []

class TestJobNamespacing:
    """Per-job telemetry namespacing (--job_id / $TCDP_JOB_ID): two jobs
    sharing one textfile-collector or heartbeat dir must never clobber
    each other's files, and the exposition carries a job label."""

    def test_job_scoped_path(self):
        assert obs_export.job_scoped_path("/x/hb.json", "jobA") \
            == "/x/jobA.hb.json"
        assert obs_export.job_scoped_path("hb.json", "jobA") == "jobA.hb.json"
        assert obs_export.job_scoped_path("/x/hb.json", None) == "/x/hb.json"
        assert obs_export.job_scoped_path(None, "jobA") is None

    def test_prom_labels_and_job_scoped_args(self):
        import argparse

        from tpu_compressed_dp.harness import loop

        args = argparse.Namespace(job_id="lm-a")
        assert loop.job_scoped(args, "/m/metrics.prom") \
            == "/m/lm-a.metrics.prom"
        assert loop.prom_labels(args, harness="lm") \
            == {"harness": "lm", "job": "lm-a"}
        solo = argparse.Namespace(job_id=None)
        assert loop.job_scoped(solo, "/m/metrics.prom") == "/m/metrics.prom"
        assert loop.prom_labels(solo, harness="lm") == {"harness": "lm"}

    def test_job_id_defaults_from_fleet_env(self, monkeypatch):
        import argparse

        from tpu_compressed_dp.harness import loop

        monkeypatch.setenv("TCDP_JOB_ID", "from-env")
        p = argparse.ArgumentParser()
        loop.add_telemetry_args(p)
        assert p.parse_args([]).job_id == "from-env"
        assert p.parse_args(["--job_id", "cli-wins"]).job_id == "cli-wins"

    def test_two_jobs_share_a_prom_dir_without_clobbering(self, tmp_path):
        base = str(tmp_path / "metrics.prom")
        for job in ("jobA", "jobB"):
            obs_export.write_prometheus(
                {"fleet/world": 4.0}, obs_export.job_scoped_path(base, job),
                labels={"job": job})
        a = (tmp_path / "jobA.metrics.prom").read_text()
        b = (tmp_path / "jobB.metrics.prom").read_text()
        assert 'job="jobA"' in a and 'job="jobB"' in b
        assert not (tmp_path / "metrics.prom").exists()

    def test_heartbeat_is_job_scoped_and_labelled(self, tmp_path):
        import argparse

        from tpu_compressed_dp.harness import loop
        from tpu_compressed_dp.utils.resilience import read_heartbeat

        args = argparse.Namespace(job_id="lm-a",
                                  heartbeat=str(tmp_path / "hb.json"),
                                  heartbeat_interval=30.0)
        hb = loop.make_heartbeat(args)
        try:
            hb.update(step=3)
        finally:
            hb.stop()
        rec = read_heartbeat(str(tmp_path / "lm-a.hb.json"))
        assert rec is not None and rec["job"] == "lm-a"
        assert not (tmp_path / "hb.json").exists()

    def test_fleet_metrics_declared_in_registry(self):
        from tpu_compressed_dp.obs import registry

        for name in ("fleet/world", "fleet/applied_updates",
                     "fleet/jobs_running", "fleet/devices_free",
                     "fleet/evictions", "fleet/shrinks", "fleet/readmits"):
            assert registry.is_declared(name), name
            assert registry.spec(name).emitter == "host", name


@pytest.mark.quick
class TestTraceReport:
    def _events(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with obs_export.EventStream(p, meta={"harness": "dawn"}) as es:
            spans = [{"t0": 10.0 + i, "data": 0.2, "to_device": 0.1,
                      "dispatch": 0.7, "total": 1.0, "done": None,
                      "device": None, "starved": None, "starved_in": None}
                     for i in range(4)]
            # one step carries its completion stamp
            spans[1].update(done=12.0, device=0.5, starved=0.3,
                            starved_in="data_wait")
            es.emit("epoch", epoch=1, step=4,
                    metrics={"train loss": 2.0, "comm MB/s": 3.25},
                    throughput={"throughput/examples_per_sec": 512.0,
                                "throughput/mfu": 0.5},
                    guard={"guard/skipped": 1.0},
                    timeline={}, step_spans=spans)
            es.emit("guard", epoch=1, step=4, **{"guard/skipped": 1.0})
        return p

    def test_render_and_chrome(self, tmp_path):
        import tools.trace_report as tr

        events = obs_export.read_events(self._events(tmp_path))
        bd = tr.phase_breakdown(events)
        assert bd["data"]["mean_ms"] == pytest.approx(200.0)
        assert bd["data"]["share"] == pytest.approx(0.2)
        assert bd["to_device"]["share"] == pytest.approx(0.1)
        # device and starved: over the one step that has a stamp
        assert bd["device"]["mean_ms"] == pytest.approx(500.0)
        assert bd["starved"]["share"] == pytest.approx(0.3)
        rows = tr.throughput_rows(events)
        assert rows[0]["rate"] == 512.0 and rows[0]["mfu"] == 0.5
        report = tr.render_report(events)
        assert "per-phase step-time breakdown" in report
        assert "MFU" in report and "guard events: 1" in report
        assert "device starved under: data_wait 300.00 ms" in report
        ch = tr.chrome_trace_events(events)
        # 4 steps x (data + to_device + dispatch) on the host's thread, and
        # the stamped step's starved + device on the device's
        assert len(ch) == 14
        dev = [e for e in ch if e["tid"] == 1]
        assert [e["name"] for e in dev] == ["starved", "device"]
        # device ends at done (12.0 s, 2 s after the first t0)
        assert dev[1]["ts"] + dev[1]["dur"] == pytest.approx(2.0e6)
        assert dev[0]["ts"] + dev[0]["dur"] == pytest.approx(dev[1]["ts"])
        assert all(e["ph"] == "X" and e["dur"] > 0 for e in ch)
        out = str(tmp_path / "chrome.json")
        assert tr.main([self._events(tmp_path), "--chrome", out]) == 0
        assert json.load(open(out))["traceEvents"]

    def test_long_steps_name_their_cause(self, tmp_path):
        """Every host interval over twice the median is printed with the
        host events that overlapped it, or "nothing recorded"; the events
        get a lane of their own in the chrome export."""
        import tools.trace_report as tr

        p = str(tmp_path / "ev.jsonl")
        spans = [{"ord": 400 + i, "call": 3, "t0": 10.0 + 0.1 * i, "data": 0.0,
                  "to_device": 0.0, "dispatch": 0.1, "total": 0.1, "done": None,
                  "device": None, "starved": None, "starved_in": None}
                 for i in range(20)]
        spans[12].update(total=0.231, dispatch=0.231, events=[
            ["compile", "jit(step)", 2.0, 1.0], ["gc", "gen2", 118.0, 6.0]])
        spans[13].update(events=[["gc", "gen2", 118.0, -94.0]])    # its tail
        spans[17].update(total=0.35, dispatch=0.35)
        with obs_export.EventStream(p) as es:
            es.emit("epoch", epoch=1, step=20, metrics={}, throughput={},
                    guard={}, timeline={}, step_spans=spans)
        events = obs_export.read_events(p)
        # an added optional key: the stream's version stays
        assert all(e["v"] == obs_export.SCHEMA_VERSION == 1 for e in events)
        report = tr.render_report(events)
        assert "step 412: 231 ms, gc gen2 118 ms, compile jit(step) 2 ms" \
            in report
        assert "step 417: 350 ms, nothing recorded" in report
        assert "step 413" not in report
        lane = [e for e in tr.chrome_trace_events(events) if e["tid"] == 2]
        # the pass that overlapped two steps is drawn once, where it began
        assert sorted(e["name"] for e in lane) == ["compile jit(step)",
                                                   "gc gen2"]
        gc_ev = next(e for e in lane if e["name"] == "gc gen2")
        assert gc_ev["ts"] == pytest.approx(1.206e6) and gc_ev["dur"] == 118e3
        assert all(e["cat"] == "host_event" and e["ph"] == "X" for e in lane)

    def test_schema_guard(self, tmp_path):
        import tools.trace_report as tr

        with pytest.raises(ValueError, match="schema version"):
            tr.check_schema([{"v": 999, "kind": "epoch"}])

    def test_schedule_section(self, tmp_path, capsys):
        """--schedule folds the overlap_evidence per-chunk placement table
        into the report (the device-side overlap view the host timeline
        cannot carry)."""
        import tools.trace_report as tr

        sched = tmp_path / "overlap.txt"
        sched.write_text(
            "# header comment\n"
            "== topk1%-EF-bucketed4MB-overlap4: 4 collective instr ==\n"
            "   all-reduce     chunk=c00  operands=  1 ~    9.44 MB  "
            "compute_after=  70 ( 60.0%)\n"
            "   summary: first=60.0% mean=45.0% last=20.0%\n")
        out = tr.render_schedule(str(sched))
        assert "chunk=c00" in out and "summary: first=60.0%" in out
        assert "# header comment" not in out
        assert tr.main([self._events(tmp_path),
                        "--schedule", str(sched)]) == 0
        assert "compiled-schedule overlap" in capsys.readouterr().out
        # --json must carry the schedule too, not silently drop the flag
        assert tr.main([self._events(tmp_path), "--json",
                        "--schedule", str(sched)]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert any("chunk=c00" in ln for ln in payload["schedule"])
        missing = tr.render_schedule(str(tmp_path / "nope.txt"))
        assert "unreadable" in missing


@pytest.mark.quick
class TestProfileTraceContext:
    def test_stops_on_exception(self, monkeypatch):
        """The hoisted profiler context must stop the trace when the epoch
        raises (the leak the copy-pasted start/stop pairs had)."""
        import jax

        from tpu_compressed_dp.harness.loop import profile_trace

        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", None)))
        with pytest.raises(RuntimeError):
            with profile_trace("/tmp/x") as active:
                assert active
                raise RuntimeError("mid-epoch failure")
        assert calls == [("start", "/tmp/x"), ("stop", None)]
        # falsy dir: no-op, nothing started
        with profile_trace(None) as active:
            assert not active
        assert len(calls) == 2


@pytest.mark.quick
class TestTensorboardLogger:
    @pytest.mark.slow  # ~9 s TF import; events/prom/heartbeat are the
    # primary telemetry surfaces and stay tier-1
    def test_writes_scalars_and_json(self, tmp_path):
        tb = TensorboardLogger(str(tmp_path / "tb"))
        tb.update_examples_count(512)
        tb.log_scalar("losses/train_loss", 1.5)
        tb.update_examples_count(512)
        tb.log_scalar("losses/train_loss", 1.2)
        tb.log_metrics({"net/x": 3.0, "skip": "str"})
        tb.close()
        data = json.load(open(tmp_path / "tb" / "scalars.json"))
        assert data["losses/train_loss"] == [[512, 1.5], [1024, 1.2]]
        assert data["net/x"] == [[1024, 3.0]]
        assert any(f.startswith("events") for f in os.listdir(tmp_path / "tb"))

    def test_non_master_is_noop(self, tmp_path):
        tb = TensorboardLogger(str(tmp_path / "tb2"), is_master=False)
        assert isinstance(tb, NoOp)
        tb.log_scalar("x", 1.0)  # absorbs anything
        tb.close()
        assert not (tmp_path / "tb2").exists()

    def test_disabled_without_dir(self):
        assert isinstance(TensorboardLogger(None), NoOp)


@pytest.mark.quick
class TestFileLogger:
    def test_level_routing(self, tmp_path, capsys):
        log = FileLogger(str(tmp_path), rank=3)
        log.debug("dbg")
        log.info("inf")
        log.event("~~1\t0.1\t90\t95")
        verbose = (tmp_path / "verbose.log").read_text()
        event = (tmp_path / "event.log").read_text()
        debug = (tmp_path / "debug.log").read_text()
        assert "inf" in verbose and "~~1" in verbose and "dbg" not in verbose
        assert "~~1" in event and "inf" not in event
        assert "dbg" in debug and "DEBUG" in debug
        assert "3: inf" in capsys.readouterr().out  # rank-prefixed console

    def test_non_master_console_only(self, tmp_path):
        FileLogger(None, rank=1, is_master=False).info("x")
        assert not os.listdir(tmp_path)


@pytest.mark.quick
class TestMeters:
    def test_network_bytes_reads_proc(self):
        recv, transmit = meters.network_bytes()
        assert recv >= 0 and transmit >= 0

    def test_network_meter_interval(self):
        m = meters.NetworkMeter()
        rg, tg = m.update_bandwidth()
        assert rg >= 0 and tg >= 0

    def test_time_meter(self):
        m = meters.TimeMeter()
        m.batch_loaded()
        m.batch_dispatched()
        s = m.summary()
        assert s["data ms/batch"] >= 0 and s["dispatch ms/batch"] >= 0

    def test_comm_meter(self):
        m = meters.CommMeter(world=8)
        m.update({"comm/sent_bits": 8e6, "comm/dense_elems": 1e6})
        m.update({"comm/sent_bits": 8e6, "comm/dense_elems": 1e6})
        out = m.gbps()
        assert out["net/payload_mb_per_step"] == pytest.approx(1.0)
        assert out["net/compression_frac"] == pytest.approx(0.25)
        assert out["net/allreduce_gbps_per_chip"] > 0


@pytest.mark.slow
def test_imagenet_harness_tensorboard_integration(tmp_path):
    # full imagenet-harness run (~60 s CPU): the tensorboard/event-stream
    # surface it exercises end-to-end stays tier-1-covered by the dawn/LM
    # e2e runs and the TestTraceReport/TestEventStream units; slow-marked
    # so tier-1 keeps headroom under its 870 s budget
    from tpu_compressed_dp.harness import imagenet as h

    ev_path = str(tmp_path / "events.jsonl")
    summary = h.main([
        "--synthetic", "--synthetic_n", "64", "--num_classes", "4",
        "--arch", "resnet18", "--width", "8", "--short_epoch", "--workers", "2",
        "--compress", "layerwise", "--method", "randomk", "--ratio", "0.1",
        "--logdir", str(tmp_path), "--tensorboard", "--events", ev_path,
    ])
    scalars = json.load(open(tmp_path / "tb" / "scalars.json"))
    assert "losses/top5" in scalars and "net/payload_mb_per_step" in scalars
    assert len(scalars["losses/train_loss"]) == 3  # smoke schedule: 3 epochs
    # x-axis is cumulative examples
    xs = [p[0] for p in scalars["losses/train_loss"]]
    assert xs == sorted(xs) and xs[0] > 0
    assert "~~0" in (tmp_path / "event.log").read_text()
    assert (tmp_path / "logs.tsv").exists()
    # throughput + comm-rate columns reach the epoch summary
    assert summary["img/s"] > 0
    assert summary["comm MB/s"] > 0
    # the JSONL event stream parses, is schema-versioned, and feeds
    # trace_report's breakdown + throughput tables without error
    import tools.trace_report as tr

    events = obs_export.read_events(ev_path)
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("epoch") == 3
    assert all(e["v"] == obs_export.SCHEMA_VERSION for e in events)
    ep = next(e for e in events if e["kind"] == "epoch")
    assert ep["throughput"]["throughput/examples_per_sec"] > 0
    assert ep["step_spans"] and ep["timeline"]["time/steps_per_sec"] > 0
    report = tr.render_report(events)
    assert "per-phase step-time breakdown" in report and "MFU" in report


@pytest.mark.quick
class TestEventStreamRotation:
    """--events_max_mb size-capped streams: rotation is atomic, every
    record carries its segment index, and the reader stitches segments
    back into one ordered stream (ISSUE 15 satellite)."""

    def test_rotate_and_stitch(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with obs_export.EventStream(p, meta={"harness": "t"},
                                    max_bytes=256) as es:
            for i in range(20):
                es.emit("step", step=i, metrics={"loss": 1.0})
        segs = obs_export.list_segments(p)
        assert segs, "256-byte cap over 20 records must rotate"
        # live file still parses on its own; stitched view sees everything
        assert os.path.exists(p)
        events = obs_export.read_all_events(p)
        kinds = [e["kind"] for e in events]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        assert [e["step"] for e in events if e["kind"] == "step"] \
            == list(range(20))
        # every record names its segment; indices ascend across the stitch
        seg_ids = [e["seg"] for e in events]
        assert seg_ids == sorted(seg_ids)
        assert seg_ids[-1] == len(segs)
        # no torn tmp files left behind by the atomic replace
        assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]

    def test_resume_continues_numbering(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with obs_export.EventStream(p, max_bytes=200) as es:
            for i in range(10):
                es.emit("step", step=i)
        n_segs = len(obs_export.list_segments(p))
        assert n_segs >= 1
        with obs_export.EventStream(p, max_bytes=200) as es:
            for i in range(10, 20):
                es.emit("step", step=i)
        assert len(obs_export.list_segments(p)) > n_segs
        steps = [e["step"] for e in obs_export.read_all_events(p)
                 if e["kind"] == "step"]
        assert steps == list(range(20))

    def test_unbounded_stays_single_file(self, tmp_path):
        p = str(tmp_path / "ev.jsonl")
        with obs_export.EventStream(p) as es:
            for i in range(50):
                es.emit("step", step=i)
        assert obs_export.list_segments(p) == []
        assert len(obs_export.read_all_events(p)) \
            == len(obs_export.read_events(p)) == 52


@pytest.mark.quick
class TestFlightRecorder:
    def _fl(self, tmp_path=None, **kw):
        from tpu_compressed_dp.obs.flight import FlightRecorder

        kw.setdefault("rank", 0)
        kw.setdefault("capacity", 8)
        if tmp_path is not None:
            kw.setdefault("directory", str(tmp_path))
        return FlightRecorder(**kw)

    def test_rings_bounded_under_hammer(self):
        """O(capacity) memory: 10k notes never grow any ring past the
        cap, while the counters keep exact totals (ISSUE 15 acceptance)."""
        fl = self._fl(capacity=8)
        for i in range(10_000):
            fl.note_step(i, {"loss": 1.0, "guard/skipped": 0.0})
        snap = fl.snapshot()
        assert all(len(ring) <= 8 for ring in snap["rings"].values())
        # note_step with a guard/ key writes two records (step + guard)
        assert snap["records"] == 20_000
        m = fl.metrics()
        assert m["flight/records"] == 20_000.0
        assert m["flight/dumps"] == 0.0 and m["flight/last_dump_step"] == -1.0
        # newest records win: the step ring holds the tail of the run
        assert [r["step"] for r in snap["rings"]["step"]] \
            == list(range(9_992, 10_000))

    def test_unknown_channel_and_bad_capacity(self):
        from tpu_compressed_dp.obs.flight import FlightRecorder

        fl = self._fl()
        with pytest.raises(ValueError, match="unknown flight channel"):
            fl.record("typo", "oops")
        with pytest.raises(ValueError, match="capacity"):
            FlightRecorder(capacity=0)

    def test_observe_dump_roundtrip(self, tmp_path):
        from tpu_compressed_dp.obs import flight as fli
        from tpu_compressed_dp.train.elastic import PeerFailed

        fl = self._fl(tmp_path, meta={"harness": "t"})
        fl.note_step(5, {"loss": 2.0})
        err = PeerFailed((3, 1), step=5, reason="gossip stale")
        path = fl.observe(err)
        assert path == fli.bundle_path(str(tmp_path), 0)
        bundles = fli.read_bundles(str(tmp_path))
        assert set(bundles) == {0}
        b = bundles[0]
        assert fli.validate_bundle(b) == []
        assert b["reason"] == "peer_failed" and b["step"] == 5
        assert b["error"]["failed"] == [1, 3]  # ctor sorts the tuple
        assert b["rings"]["fault"][-1]["kind"] == "peer_failed"
        assert b["rings"]["step"][-1]["metrics"] == {"loss": 2.0}
        assert fl.metrics()["flight/dumps"] == 1.0
        assert fl.metrics()["flight/last_dump_step"] == 5.0

    def test_observe_without_directory_is_noop_dump(self):
        fl = self._fl()
        assert fl.observe(RuntimeError("boom"), step=1) is None
        assert fl.metrics()["flight/dumps"] == 0.0
        assert fl.snapshot()["rings"]["fault"]  # evidence still recorded

    def test_classify_failure_mapping(self):
        from tpu_compressed_dp.obs.flight import classify_failure
        from tpu_compressed_dp.train.elastic import PeerFailed
        from tpu_compressed_dp.train.guard import GuardExceeded
        from tpu_compressed_dp.utils import chaos, resilience
        from tpu_compressed_dp.utils.checkpoint import CheckpointCorrupt

        assert classify_failure(GuardExceeded("wedged")) == "guard_exceeded"
        assert classify_failure(PeerFailed((1,))) == "peer_failed"
        assert classify_failure(resilience.Preempted("sig")) == "preempt"
        assert classify_failure(CheckpointCorrupt("bad")) == "ckpt_corrupt"
        assert classify_failure(chaos.ChaosCrash("kill")) == "chaos_crash"
        assert classify_failure(RuntimeError("?")) == "error"

    def test_note_chaos_uses_fault_kind(self):
        from tpu_compressed_dp.utils.chaos import ChaosConfig

        fl = self._fl()
        fl.note_chaos(ChaosConfig(kind="nan", target="grads", every=1,
                                  worker=1))
        fl.note_chaos("nan:grads")  # spec-string form
        fl.note_chaos(None)  # disarmed: no record
        ring = fl.snapshot()["rings"]["chaos"]
        assert len(ring) == 2
        assert ring[0]["kind"] == "nan" and ring[0]["worker"] == 1
        assert ring[1]["kind"] == "armed" and ring[1]["spec"] == "nan:grads"

    def test_publish_single_rank_degrades(self, tmp_path):
        fl = self._fl(tmp_path)
        fl.note_spans([{"t0": 1.0, "data": 0.1, "total": 1.0}])
        g = fl.publish()
        assert g == {"straggler/skew_s": 0.0, "straggler/rank": -1.0,
                     "straggler/frac": 0.0}

    def test_registry_conformance(self):
        """Every gauge the recorder exports (counters + live straggler
        family) is registry-declared with a host emitter (TCDP103)."""
        from tpu_compressed_dp.obs.flight import straggler_gauges

        fl = self._fl()
        names = set(fl.metrics()) | set(straggler_gauges({}))
        assert names == {"flight/records", "flight/dumps",
                         "flight/last_dump_step", "straggler/skew_s",
                         "straggler/rank", "straggler/frac"}
        for name in names:
            assert obs_registry.is_declared(name), name
            assert obs_registry.spec(name).emitter == "host", name


@pytest.mark.quick
class TestStragglerEndToEnd:
    """Scripted skewed timelines -> shared phase profiles -> live
    straggler_* gauges -> heartbeat -> watchdog exit 1 (ISSUE 15
    acceptance: the whole live path, no training loop required)."""

    def _publish(self, tmp_path):
        from tpu_compressed_dp.obs.flight import FlightRecorder

        gauges = {}
        for rank, step_s in ((0, 0.10), (1, 0.10), (2, 0.25)):
            fl = FlightRecorder(rank=rank, capacity=16,
                                directory=str(tmp_path))
            fl.note_spans([{"t0": float(i), "data": step_s / 2,
                            "dispatch": step_s / 2, "total": step_s}
                           for i in range(4)])
            gauges = fl.publish()
        return gauges

    def test_gauges_to_watchdog(self, tmp_path):
        import time as _time

        import tools.watchdog as wd

        g = self._publish(tmp_path)
        assert g["straggler/rank"] == 2.0
        assert g["straggler/skew_s"] == pytest.approx(0.15)
        assert g["straggler/frac"] == pytest.approx(1.5)
        # the harness folds the gauges into the heartbeat top level...
        hb = str(tmp_path / "hb.json")
        json.dump({"ts": _time.time(), "step": 10, "last_good_step": 10,
                   "straggler_skew_s": g["straggler/skew_s"],
                   "straggler_rank": g["straggler/rank"]}, open(hb, "w"))
        # ...and the watchdog turns a breach into exit 1
        assert wd.main(["--check", "--heartbeat", hb,
                        "--max_straggler_skew", "0.05"]) == 1
        assert wd.main(["--check", "--heartbeat", hb,
                        "--max_straggler_skew", "0.5"]) == 0

    def test_prometheus_export(self, tmp_path):
        g = self._publish(tmp_path)
        prom = str(tmp_path / "m.prom")
        obs_export.write_prometheus(g, prom, labels={"harness": "t"})
        body = open(prom).read()
        assert "# TYPE tcdp_straggler_skew_s gauge" in body
        assert 'tcdp_straggler_rank{harness="t"} 2' in body

    def test_offline_matches_live(self, tmp_path):
        """postmortem's straggler_from_bundles recomputes the SAME gauges
        from dumped timing rings — one skew definition, two surfaces."""
        from tpu_compressed_dp.obs.flight import FlightRecorder, read_bundles
        from tools.postmortem import straggler_from_bundles

        live = self._publish(tmp_path)
        for rank, step_s in ((0, 0.10), (1, 0.10), (2, 0.25)):
            fl = FlightRecorder(rank=rank, capacity=16,
                                directory=str(tmp_path))
            fl.note_spans([{"t0": float(i), "data": step_s / 2,
                            "dispatch": step_s / 2, "total": step_s}
                           for i in range(4)])
            fl.dump("error")
        offline = straggler_from_bundles(read_bundles(str(tmp_path)))
        assert offline == pytest.approx(live)


@pytest.mark.quick
class TestTraceReportMerge:
    def _rank_events(self, tmp_path, rank, lag=0.0):
        p = str(tmp_path / f"ev.rank{rank}.jsonl")
        with obs_export.EventStream(p, meta={"harness": "t"}) as es:
            spans = [{"t0": 100.0 * rank + i, "data": 0.2,
                      "dispatch": 0.8 + lag, "total": 1.0 + lag}
                     for i in range(3)]
            es.emit("epoch", epoch=1, step=3, metrics={},
                    throughput={}, guard={}, timeline={}, step_spans=spans)
        return p

    def test_merge_cli(self, tmp_path):
        import tools.trace_report as tr

        p0 = self._rank_events(tmp_path, 0)
        p1 = self._rank_events(tmp_path, 1, lag=0.5)
        out = str(tmp_path / "merged.json")
        assert tr.main([p0, p1, "--merge", "--chrome", out]) == 0
        trace = json.load(open(out))
        evs = trace["traceEvents"]
        # one process lane per rank, named via metadata events
        meta = [e for e in evs if e["ph"] == "M"]
        assert {(e["pid"], e["args"]["name"]) for e in meta} \
            == {(0, "rank 0"), (1, "rank 1")}
        by_pid = {pid: [e for e in evs if e["ph"] == "X" and e["pid"] == pid]
                  for pid in (0, 1)}
        assert len(by_pid[0]) == 6 and len(by_pid[1]) == 6  # 3 steps x 2 ph
        # spans align on each rank's own first t0 (host clocks are
        # per-process): both lanes start at ts 0
        assert min(e["ts"] for e in by_pid[0]) == 0.0
        assert min(e["ts"] for e in by_pid[1]) == 0.0
        # the lagging rank's dispatch spans are visibly longer
        d0 = [e for e in by_pid[0] if e["name"] == "dispatch"][0]["dur"]
        d1 = [e for e in by_pid[1] if e["name"] == "dispatch"][0]["dur"]
        assert d1 == pytest.approx(d0 + 0.5e6)

    def test_merge_flag_errors(self, tmp_path):
        import tools.trace_report as tr

        p0 = self._rank_events(tmp_path, 0)
        p1 = self._rank_events(tmp_path, 1)
        with pytest.raises(SystemExit):  # multi-file needs --merge
            tr.main([p0, p1, "--chrome", str(tmp_path / "x.json")])
        with pytest.raises(SystemExit):  # --merge needs --chrome
            tr.main([p0, p1, "--merge"])

    def test_merge_reads_rotated_streams(self, tmp_path):
        """A size-capped (--events_max_mb) per-rank stream merges whole:
        the stitcher feeds the lane builder, not just the live file."""
        import tools.trace_report as tr

        p0 = str(tmp_path / "r0.jsonl")
        with obs_export.EventStream(p0, max_bytes=200) as es:
            for i in range(3):
                es.emit("epoch", epoch=i, step=i + 1, metrics={},
                        throughput={}, guard={}, timeline={},
                        step_spans=[{"t0": float(i), "data": 0.1,
                                     "dispatch": 0.2, "total": 0.3}])
        assert obs_export.list_segments(p0)
        p1 = self._rank_events(tmp_path, 1)
        out = str(tmp_path / "merged.json")
        assert tr.main([p0, p1, "--merge", "--chrome", out]) == 0
        evs = json.load(open(out))["traceEvents"]
        lane0 = [e for e in evs if e["ph"] == "X" and e["pid"] == 0]
        assert len(lane0) == 6  # all 3 rotated-away steps x 2 phases


@pytest.mark.quick
class TestPostmortemClassify:
    """Verdict taxonomy priority order on synthetic bundles (the chaos
    drill covers the real failure paths; these pin the tie-breaks)."""

    def _bundle(self, rank, reason, *, step=None, error=None, rings=None):
        from tpu_compressed_dp.obs.flight import CHANNELS, FLIGHT_SCHEMA

        base = {ch: [] for ch in CHANNELS}
        base.update(rings or {})
        return {"v": FLIGHT_SCHEMA, "kind": "blackbox", "rank": rank,
                "reason": reason, "step": step, "seq": 1, "capacity": 8,
                "meta": {}, "error": error, "extra": None,
                "counts": {"records": 1, "dumps": 1}, "rings": base}

    def test_priority_order(self):
        from tools.postmortem import classify

        corrupt = self._bundle(1, "ckpt_corrupt", step=7,
                               error={"message": "manifest sha mismatch"})
        preempt = self._bundle(0, "preempt", step=7, error={"signum": 15})
        peer = self._bundle(2, "peer_failed", step=7,
                            error={"failed": [0]})
        guard = self._bundle(3, "guard_exceeded", step=7, error={})
        v = classify({0: preempt, 1: corrupt, 2: peer, 3: guard})
        assert (v["kind"], v["rank"]) == ("corruption", 1)
        v = classify({0: preempt, 2: peer, 3: guard})
        assert (v["kind"], v["rank"]) == ("preempt", 0)
        v = classify({2: peer, 3: guard})
        assert (v["kind"], v["rank"]) == ("dead_peer", 0)
        v = classify({3: guard})
        assert (v["kind"], v["rank"]) == ("guard", -1)

    def test_nan_names_injected_worker(self):
        from tools.postmortem import classify

        chaos_rec = {"kind": "nan", "seq": 0, "t": 0.0, "target": "grads",
                     "every": 1, "worker": 2, "crash_at_step": -1}
        b = self._bundle(0, "guard_exceeded", step=4, error={},
                         rings={"chaos": [chaos_rec]})
        v = classify({0: b})
        assert (v["kind"], v["rank"], v["step"]) == ("nan", 2, 4)
        assert "grads" in v["detail"]

    def test_dead_peer_chaos_fallback_requires_armed_crash(self):
        from tools.postmortem import classify

        # survivors raised a bare PeerFailed with no .failed evidence
        def peer(rings=None):
            return self._bundle(0, "peer_failed", step=3, error={},
                                rings=rings)

        armed = {"kind": "crash", "seq": 0, "t": 0.0, "worker": 1,
                 "crash_at_step": 3}
        v = classify({0: peer({"chaos": [armed]})})
        assert (v["kind"], v["rank"]) == ("dead_peer", 1)
        # an unarmed config (crash_at_step=-1) must NOT name a scapegoat
        unarmed = dict(armed, crash_at_step=-1)
        v = classify({0: peer({"chaos": [unarmed]})})
        assert (v["kind"], v["rank"]) == ("dead_peer", -1)

    def test_straggler_fallback_and_unknown(self):
        from tools.postmortem import STRAGGLER_FRAC, classify

        def timing(step_s):
            return {"timing": [{"kind": "span", "seq": i, "t": 0.0,
                                "data": step_s / 2, "total": step_s}
                               for i in range(4)]}

        slow = self._bundle(1, "error", rings=timing(0.4))
        fast = self._bundle(0, "error", rings=timing(0.1))
        v = classify({0: fast, 1: slow})
        assert (v["kind"], v["rank"]) == ("straggler", 1)
        # under the skew floor the verdict stays unknown, not straggler
        near = self._bundle(1, "error",
                            rings=timing(0.1 * (1 + STRAGGLER_FRAC / 2)))
        v = classify({0: fast, 1: near})
        assert v["kind"] == "unknown"
        assert classify({})["kind"] == "unknown"
        assert classify({})["rank"] == -1

    def test_merge_timeline_order_and_report(self):
        from tools import postmortem as pm

        b0 = self._bundle(
            0, "peer_failed", step=2, error={"failed": [1]},
            rings={"step": [{"kind": "metrics", "seq": 0, "t": 0.1,
                             "step": 1},
                            {"kind": "metrics", "seq": 1, "t": 0.2,
                             "step": 2}],
                   "fault": [{"kind": "peer_failed", "seq": 2, "t": 0.3}]})
        b1 = self._bundle(
            1, "chaos_crash", step=2, error={},
            rings={"step": [{"kind": "metrics", "seq": 0, "t": 0.1,
                             "step": 2}]})
        merged = pm.merge_timeline({0: b0, 1: b1})
        # stepped records first (step, rank, seq); step-less sort last
        assert [(r["rank"], r.get("step")) for r in merged] \
            == [(0, 1), (0, 2), (1, 2), (0, None)]
        report = pm.render_report({0: b0, 1: b1})
        assert report.splitlines()[0].startswith("postmortem: dead_peer")
        assert "cross-rank timeline" in report
        assert pm.verdict_line(pm.classify({0: b0, 1: b1})) \
            == report.splitlines()[0]

    def test_report_names_what_covered_a_long_step(self):
        """Drained step records reach the recorder's ``timing`` ring with
        their ``events`` (absolute times stay out), the bundle's schema
        version stays, and the report prints each long step's cause."""
        from tools import postmortem as pm
        from tpu_compressed_dp.obs.flight import (FLIGHT_SCHEMA,
                                                  FlightRecorder,
                                                  validate_bundle)

        clk = FakeClock()
        ring = obs_trace.HostEvents(clock=clk)
        tl = StepTimeline(capacity=16, clock=clk, events=ring)
        tl.begin_call()
        for i in range(8):
            _step(tl, clk, 1, 1, 131 if i == 5 else 8, 0.0)
        now = clk.t
        _gc_pass(ring, clk, 2, 55, 118)             # inside step 5's dispatch
        clk.t = now
        assert tl.flush(10)
        fl = FlightRecorder(rank=0, capacity=16)
        fl.note_spans(tl.drain())
        timing = fl.snapshot()["rings"]["timing"]
        assert [("events" in r) for r in timing] == [i == 5 for i in range(8)]
        assert timing[5]["events"] == [["gc", "gen2", 118.0, 5.0]]
        assert not {"t0", "done", "ord", "call"} & set(timing[5])
        bundle = self._bundle(0, "error", rings={"timing": timing})
        assert bundle["v"] == FLIGHT_SCHEMA == 1 and validate_bundle(bundle) == []
        report = pm.render_report({0: bundle})
        assert "rank 0: host intervals over twice" in report
        assert "step 5: 133 ms, gc gen2 118 ms" in report
        # the profile sums numbers only: the list does not break it
        assert fl.phase_profile()["phases"]["total"] == pytest.approx(.203)

    def test_cli_json_and_missing_dir(self, tmp_path, capsys):
        from tools import postmortem as pm
        from tpu_compressed_dp.obs.flight import FlightRecorder
        from tpu_compressed_dp.train.guard import GuardExceeded

        assert pm.main([str(tmp_path / "empty")]) == 2
        fl = FlightRecorder(rank=0, capacity=8, directory=str(tmp_path))
        fl.note_step(3, {"loss": float("nan")})
        fl.observe(GuardExceeded("skip streak 2 exceeded"), step=3)
        capsys.readouterr()
        assert pm.main([str(tmp_path), "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["verdict"]["kind"] == "guard"
        assert payload["ranks"]["0"]["reason"] == "guard_exceeded"
        assert payload["ranks"]["0"]["problems"] == []
        assert payload["timeline"]
