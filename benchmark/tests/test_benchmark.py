"""Tests of the benchmark's own yardstick.  Not part of the repo's tier-1
suite; run them here on the CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 -m pytest benchmark/tests -q

The rehearsal cells are the real harness at a tiny width (control flow only:
no time measured here is a metric), the control is the reference in the next
precision down, and the planted-fault tests put a broken step under the timed
path: one that returns its state unchanged, drops the momentum, leaves out half
the batch, compiles inside the window or miscounts its bits.
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
import xplane_pb  # noqa: E402


def read(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def tiny_cell(traffic_name: str):
    path = f"benchmark/traffic/{traffic_name}.json"
    if not os.path.exists(os.path.join(ROOT, path)):     # a cell that has not landed
        path = f"benchmark/tests/data/{traffic_name}.json"
    traffic = read(path)
    if traffic["feed"]["kind"] == "loader":
        traffic["feed"]["dataset_images"] = 512
    manifest = read("BENCHMARK.json")
    rate = traffic["rate_metric"]
    return run.make_cell(
        "tiny_" + traffic_name, traffic["chips"],
        read("benchmark/tests/data/tiny_resnet50.json"), traffic,
        read("benchmark/tests/data/tiny_limits%s.json"
             % ("_topk" if traffic["compression"]["method"] else "")),
        [m for m in manifest["end_to_end"]
         if m["name"] in (rate, "peak_hbm_gb", "setup_s")], [])


# ------------------------------------------------------------------ manifest

def test_every_cell_finds_its_files():
    manifest = read("BENCHMARK.json")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert e2e == {"throughput", "throughput_fed", "peak_hbm_gb", "setup_s"}
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.traffic["rate_metric"] in {m["name"] for m in cell.end_to_end}
        assert cell.per_layer, w["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert run.load_reader(m["name"]).UNIT == m["unit"]
        for w in m["workloads"]:
            moved = next(x for x in manifest["end_to_end"] if x["name"] == m["moves"])
            assert w in moved.get("workloads", [w])


def test_a_method_without_stated_semantics_is_refused():
    """A traffic file whose compression no sync file states is an error, never
    followed as if it were dense or Top-K."""
    for sync, comp in (("dense", {"method": "terngrad"}),
                       ("topk_layerwise_ef", {"method": "topk", "ratio": 0.01,
                                              "granularity": "entiremodel",
                                              "mode": "wire", "error_feedback": True}),
                       ("no_such_semantics", {"method": None})):
        traffic = dict(read("benchmark/traffic/dense_staged.json"),
                       sync=sync, compression=comp)
        with pytest.raises(SystemExit):
            run.make_cell("x", 1, read("benchmark/tests/data/tiny_resnet50.json"),
                          traffic, {"limits": {}}, [], [])


def test_a_limit_that_nothing_computes_is_an_error():
    import check

    with pytest.raises(KeyError):
        check.compare({"loss_gap": 0.0}, {"loss_gap": 1.0, "payload_exact": 0})
    with pytest.raises(KeyError):
        check.compare({"loss_gap": 0.0, "extra": 0.0}, {"loss_gap": 1.0})


def test_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50_dense_staged", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout


# ----------------------------------------------------------- trace reduction

def test_interval_arithmetic():
    u = tr.union([[5, 7], [0, 2], [1, 3], [7, 9]])
    assert u == [[0, 3], [5, 9]] and tr.length(u) == 7
    assert tr.subtract([[0, 10]], u) == [[3, 5], [9, 10]]
    assert tr.subtract([[0, 3], [4, 8]], [[1, 2], [2, 5], [7, 20]]) == [[0, 1], [5, 7]]


def test_hlo_join_reads_scope_and_target():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("tcdp.grad"):
            y = jnp.dot(x, x)
        with jax.named_scope("tcdp.update"):
            return jnp.sin(y) + 1

    compiled = jax.jit(f).lower(jnp.ones((64, 64))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    found = xplane_pb.instructions_of_module(module.as_serialized_hlo_module_proto())
    scopes = {tr.scope_of(op_name) for op_name, _, _ in found.values()}
    assert {"grad", "update"} <= scopes
    assert tr.short_name("%fusion.12 = bf16[8]{0} fusion(%p)") == "fusion.12"
    assert tr.scope_of("jit(f)/tcdp.compress/tcdp.route/scatter") == "route"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "extract_topk_steps.json.gz"), "rt") as f:
        return json.load(f)


def test_reduction_on_a_recorded_trace(recorded):
    """A few steps of resnet50_topk_lw_staged on the v5e (my chip run, PR 23),
    cut to whole steps; the numbers asserted were worked out once by the slow
    route below."""
    ex, steps = recorded["extract"], recorded["steps"]
    ops = next(iter(ex["devices"].values()))
    t0, t1 = ex["window"]
    # busy union against a brute-force sweep over event boundaries
    edges = sorted({e[3] for e in ops} | {e[3] + e[4] for e in ops})
    starts = sorted(e[3] for e in ops)
    ends = sorted(e[3] + e[4] for e in ops)
    import bisect
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if bisect.bisect_right(starts, a) - bisect.bisect_right(ends, a) > 0)
    assert abs(tr.busy_seconds(ex) * 1e9 - busy) < 1
    idle = dict(tr.idle_gaps(ex, n=100))
    assert abs(sum(idle.values()) * 1e9 - ((t1 - t0) - busy)) < 1e3
    assert set(idle) <= set(tr.HOST_SPANS) | {tr.TO_DEVICE_SPAN, "other"}
    # scope sums against a plain loop
    for scope in ("grad", "update", "compress"):
        want = sum(e[4] for e in ops if e[1] == scope and not tr.is_container(e[0]))
        assert abs(tr.scope_seconds(ex, (scope,)) * 1e9 - want) < 1
    assert tr.scope_seconds(ex, ("grad",)) > 10 * tr.scope_seconds(ex, ("update",))
    # kernel events by name: one select+pack launch per large leaf per step
    launches = sum(1 for e in ops if tr.is_select_pack(*e[:3]))
    assert launches == recorded["select_pack_leaves"] * steps
    assert all(e[0].startswith("tcdp.compress") or e[0].startswith("closed_call")
               for e in ops if tr.is_pallas(*e[:3]))
    assert tr.top_device_ops(ex, 3)[0][1] > 0


# ------------------------------------------------- the harness, tiny, on CPU

@pytest.mark.parametrize("traffic", ["dense_staged", "topk_lw_staged",
                                     "dense_fed", "topk_lw_staged_w4"])
def test_rehearsal_runs_and_is_correct(traffic):
    result = run.run_cell(tiny_cell(traffic), 7, 1.0, False, require_tpu=False,
                          warm_seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"      # no number here is a metric


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    def broken(train_step):
        def step(state, batch):
            _, metrics = train_step(jax_copy(state), batch)
            return state, metrics
        return step

    import jax
    import jax.numpy as jnp

    def jax_copy(state):          # the real step donates its argument
        return jax.tree.map(jnp.copy, state)

    result = run.run_cell(tiny_cell("dense_staged"), 7, 0.5, False,
                          require_tpu=False, warm_seconds=0.2, wrap_step=broken)
    assert result["correct"] is False


def failed_numbers(result_rows):
    return {name for name, _, _, ok in result_rows if not ok}


def run_and_keep_rows(cell, wrap):
    """run_cell, and the rows of its comparison beside the result."""
    import check

    kept, compare = [], check.compare

    def spy(numbers, limits):
        kept.extend(compare(numbers, limits))
        return list(kept)

    check.compare = spy
    try:
        result = run.run_cell(cell, 7, 0.3, False, require_tpu=False,
                              warm_seconds=0.1, wrap_step=wrap)
    finally:
        check.compare = compare
    return result, kept


def test_a_dropped_momentum_is_not_correct():
    """The optimizer's momentum buffer zeroed before every step: the first
    gradient is untouched, the parameters' change after three steps is 3 where
    it should be 1 + 1.9 + 2.71 (median gap 0.47)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    def no_momentum(train_step):
        def step(state, batch):
            zero = jax.tree.map(jnp.zeros_like, state.opt_state)
            return train_step(dataclasses.replace(state, opt_state=zero), batch)
        return step

    result, rows = run_and_keep_rows(tiny_cell("dense_staged"), no_momentum)
    assert result["correct"] is False
    assert failed_numbers(rows) == {"dparam_median_gap"}


def test_a_part_of_the_batch_left_out_is_not_correct():
    """The second half of every batch replaced by a copy of the first: half
    the rows never reach the gradient or the batch statistics."""
    import jax.numpy as jnp

    def half_batch(train_step):
        def step(state, batch):
            half = batch["input"].shape[0] // 2
            return train_step(state, {k: jnp.concatenate([v[:half], v[:half]])
                                      for k, v in batch.items()})
        return step

    result, rows = run_and_keep_rows(tiny_cell("dense_staged"), half_batch)
    assert result["correct"] is False
    assert {"grad1_median_gap", "bn_var_gap"} <= failed_numbers(rows)


def test_a_compile_inside_the_window_is_not_correct():
    """A program that is new in every call after the check steps: the runs of
    the window compile, which the warm-up was there to prevent."""
    import time

    import jax

    def compiling(train_step):
        calls = []

        def step(state, batch):
            calls.append(1)
            if len(calls) > run.CHECK_STEPS:
                jax.jit(lambda x: x * time.time())(1.0)     # a constant never seen
            return train_step(state, batch)
        return step

    result, rows = run_and_keep_rows(tiny_cell("dense_staged"), compiling)
    assert result["correct"] is False
    assert failed_numbers(rows) == {"compiles_in_window"}


def test_a_wrong_bit_count_is_not_correct():
    def miscounting(train_step):
        def step(state, batch):
            state, metrics = train_step(state, batch)
            return state, {**metrics, "comm/sent_bits": metrics["comm/sent_bits"] * 2}
        return step

    result = run.run_cell(tiny_cell("topk_lw_staged"), 7, 0.5, False,
                          require_tpu=False, warm_seconds=0.2,
                          wrap_step=miscounting)
    assert result["correct"] is False


def test_the_lower_precision_control_is_not_correct():
    """The reference computed in fp8, put in the program's place, fails; computed
    in the program's own bf16 it passes.  At a width the CPU can hold (the two
    come apart less here than at the published widths, where the chip readings
    in PERF.md were taken)."""
    import jax
    import numpy as np

    cell = tiny_cell("dense_staged")
    ref = cell.model
    cell.cfg = cfg = read("benchmark/tests/data/small_resnet50.json")
    limits = read("benchmark/tests/data/small_limits.json")
    cell.limits, cell.check_params = limits["limits"], limits["params"]
    seed = 5
    params = ref.make_params(cfg, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    b, s = cfg["per_chip_batch"], cfg["image_size"]
    base = rng.uniform(64, 192, (3, b, 1, 1, 3))
    batches = [(np.clip(base[i] + rng.uniform(-48, 48, (b, s, s, 3)), 0, 255)
                .astype(np.uint8), rng.integers(0, cfg["num_classes"], b))
               for i in range(3)]
    raw = {"p0": [np.asarray(l) for l in jax.tree.leaves(params)], "first": batches}
    assert all(ok for *_, ok in run.judge(cell, raw, {}, precision="bfloat16"))
    failed = failed_numbers(run.judge(cell, raw, {}, precision="fp8"))
    assert {"bn_var_gap", "bn_var_median_gap"} <= failed
