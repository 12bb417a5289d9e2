"""The ``laguna`` cell rehearsed at a tiny width on the CPU (control flow only:
no time measured here is a metric), with the faults its comparison must catch
planted under the timed path, and the lower-precision control.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_laguna_cell.py -q
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
from test_benchmark import failed_numbers, read, run_and_keep_rows  # noqa: E402

CELL = "laguna_xs2_dense_staged"


def tiny_cell():
    manifest = read("BENCHMARK.json")
    return run.make_cell(
        "tiny_laguna_dense_staged", 1,
        read("benchmark/tests/data/tiny_laguna.json"),
        read("benchmark/traffic/dense_staged.json"),
        read("benchmark/tests/data/tiny_limits_laguna.json"),
        [m for m in manifest["end_to_end"]
         if m["name"] in ("throughput", "peak_hbm_gb", "setup_s")], [])


def variant_step(cell, **variant):
    """wrap_step putting the program's own step, built anew from other
    settings (or with a fault patched into the model's module) on the same
    mesh, under the timed path."""
    def wrap(train_step):
        from tpu_compressed_dp.train.lm_step import make_lm_mesh

        *_, step = cell.builder.make_step(cell.cfg, cell.traffic,
                                          make_lm_mesh(cell.chips, 1, 1), **variant)
        return step
    return wrap


def test_the_cell_finds_its_files_and_counts_its_work():
    cell = run.load_cell(CELL)
    assert cell.builder.__file__.endswith("programs/laguna_dp.py")
    assert cell.model.__file__.endswith("reference/laguna.py")
    with open(os.path.join(ROOT, "benchmark/reference/laguna.py")) as f:
        assert "tpu_compressed_dp" not in f.read()        # nothing of the program
    import flops

    sizes = flops.leaf_sizes(cell.model, cell.cfg)
    assert (sum(sizes), len(sizes)) == (cell.cfg["parameters"],
                                        cell.cfg["parameter_leaves"]) == (691625216, 79)
    uncut = dict(cell.cfg, **{k: v for k, v in cell.cfg["published"].items()
                              if k != "parameters"})
    assert round(sum(flops.leaf_sizes(cell.model, uncut)) / 1e9, 2) == 33.44
    fwd = cell.model.forward_flops_per_sample(cell.cfg)
    assert fwd == pytest.approx(6.568e12, rel=1e-3)
    assert cell.model.attention_flops_per_sample(cell.cfg) / 3 / fwd == pytest.approx(0.251, abs=1e-3)
    assert cell.model.window_attention_flops_per_sample(cell.cfg) / 3 / fwd == pytest.approx(
        0.0608, abs=1e-3)
    # the band: T x 512 less the first window's triangle, 64 heads, 3 layers
    assert cell.model.window_attention_flops_per_sample(cell.cfg) == (
        3 * 3 * 64 * 2 * 2.0 * (8192 * 512 - 512 * 511 / 2) * 128)
    hc = cell.builder.laguna_config(cell.cfg)
    assert (hc.pattern, hc.experts_held, hc.n_routed_experts, hc.vocab_held,
            hc.full_heads, hc.window_heads, hc.window) == (
        "FDWEWEWEFE", 32, 256, 12544, 48, 64, 512)
    names = {m["name"] for m in cell.per_layer}
    assert {"window_attn_device_ms", "window_attn_roofline", "experts_device_ms",
            "experts_roofline", "expert_rows_per_step", "flash_attn_device_ms",
            "flash_attn_roofline", "moe_device_ms", "moe_dispatch_device_ms", "mfu",
            "grad_device_ms", "update_device_ms", "stack_device_ms",
            "head_xent_device_ms"} <= names
    assert set(cell.limits) >= {"expert_rows_gap", "route_mass_gap"}
    # every published number of the catalog's entry, but the cuts the
    # benchmark's entry lists, each with its published value beside it
    manifest = read("BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell.cfg["name"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == entry["source"])
    differ = {k for k, v in row["config"].items() if cell.cfg.get(k) != v}
    assert differ == set(entry["reduced"]) - {"train_steps", "data"}
    assert {k: cell.cfg["published"][k] for k in differ} == {
        k: row["config"][k] for k in differ}


def test_the_new_readers_read_their_scopes_and_nothing_without_them():
    """A program without the scopes or the counter (the parent, another cell)
    leaves the five new metrics out; with them the two shares are the model
    file's operations over the peak over the time under the scope, and the
    full layers' kernels stay with ``flash_attn_device_ms``."""
    import trace_reduce

    cell = run.load_cell(CELL)
    ops = [["fusion.1", "grad", "fusion", 0, 500],
           ["custom-call.2", "attn", "pallas", 500, 4_000_000]]
    ctx = types.SimpleNamespace(
        extract={"window": [0, 1000], "devices": {"/device:TPU:0": ops}, "host": []},
        traced_steps=2, reduce=trace_reduce, model=cell.model, cfg=cell.cfg,
        constants={}, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    new = ("window_attn_device_ms", "window_attn_roofline", "experts_device_ms",
           "experts_roofline", "expert_rows_per_step")
    read_ = lambda name: run.load_reader(name).read(ctx)
    for name in new:
        assert read_(name) is None, name
    ops += [["custom-call.3", "attn_window", "pallas", 600, 60_000_000],
            ["fusion.4", "attn_window", "fusion", 700, 1_000_000],     # not a kernel
            ["while.5", "experts", "while", 800, 9_000_000],          # a container
            ["fusion.6", "experts", "fusion", 900, 20_000_000],
            ["fusion.7", "moe_dispatch", "fusion", 950, 5_000_000]]
    ctx.constants["expert_rows_per_step"] = 65536.0
    assert read_("flash_attn_device_ms") == pytest.approx(2.0)
    assert read_("window_attn_device_ms") == pytest.approx(30.0)
    assert read_("experts_device_ms") == pytest.approx(10.0)
    assert read_("moe_device_ms") == pytest.approx(12.5)
    assert read_("expert_rows_per_step") == 65536.0
    flops = cell.model.window_attention_flops_per_sample(cell.cfg) * 2 * 2
    assert read_("window_attn_roofline") == pytest.approx(100 * flops / 197e12 / 0.060)
    assert read_("experts_roofline") == pytest.approx(
        100 * 3 * 6 * 2048 * 512 * 65536 * 2 / 197e12 / 0.020)
    # a model file that does not count the band reads no share
    ouro = run.load_cell("ouro_2p6b_dense_staged")
    ctx.model, ctx.cfg = ouro.model, ouro.cfg
    assert read_("window_attn_roofline") is None and read_("experts_roofline") is None


def test_rehearsal_runs_and_is_correct():
    result = run.run_cell(tiny_cell(), 7, 1.0, False, require_tpu=False,
                          warm_seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"      # no number here is a metric


def test_the_builder_keeps_the_rows_the_held_experts_computed():
    """``constants["expert_rows_per_step"]`` after an epoch: every token's
    ``num_experts_per_tok`` choices in each of the four sparse layers when
    every expert is held."""
    import jax

    cell = tiny_cell()
    cfg = dict(cell.cfg, num_experts=16)
    prog = cell.builder.build(cfg, cell.traffic, jax.devices()[:1], cell.model)
    state = prog.make_state(3)
    pool = prog.make_pool(3, 2)
    assert prog.constants == {}
    state, acc = prog.run_epoch(prog.train_step, state, iter(pool))
    tokens = cfg["per_chip_batch"] * cfg["seq_len"]
    assert acc.steps == 2 and prog.constants["expert_rows_per_step"] == pytest.approx(
        4 * tokens * cfg["num_experts_per_tok"])


def _masters_in_bf16(cell):
    """wrap_step: the parameters rounded to bf16 after every step."""
    def wrap(train_step):
        import jax
        import jax.numpy as jnp

        def step(state, batch):
            state, metrics = train_step(state, batch)
            return dataclasses.replace(state, params=jax.tree.map(
                lambda p: p.astype(jnp.bfloat16).astype(p.dtype), state.params)), metrics
        return step
    return wrap


def _no_gate(hy):
    sound = hy._gated_attention_mixer
    return lambda cfg, kind, lp, x: sound(
        cfg, kind, dict(lp, w_head_gate=lp["w_head_gate"] * 0.0), x) * 2.0


def _skip_first_held_expert(hy):
    import jax.numpy as jnp

    sound = hy.dispatch
    return lambda cfg, idx, w: sound(
        cfg, jnp.where(idx == cfg.first_expert, -1, idx), w)


def _rotary_whole_head():
    from tpu_compressed_dp.models.hybrid import Rotary

    return Rotary(theta=500000.0, dim=16, yarn_factor=8.0, yarn_original=16,
                  beta_fast=4.0, beta_slow=1.0, attention_factor=1.2)


FAULTS = {
    # name: (settings variant, {attribute of models.hybrid: its faulty form},
    #        numbers of which one at least must fail)
    "window_halved": (dict(window=8), {}, {"grad1_median_gap", "grad1_gap"}),
    "window_doubled": (dict(window=32), {}, {"grad1_median_gap", "grad1_gap"}),
    "scaling_factor_1_for_2p5": (dict(routed_scale=1.0), {}, {"route_mass_gap"}),
    "a_held_expert_skipped": ({}, {"dispatch": _skip_first_held_expert},
                              {"expert_rows_gap"}),
    "gate_left_out": ({}, {"_gated_attention_mixer": _no_gate},
                      {"grad1_median_gap", "grad1_gap"}),
    "rotary_on_every_channel_of_a_full_layer": (
        "rotary_whole_head", {}, {"grad1_median_gap", "grad1_gap"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    from tpu_compressed_dp.models import hybrid as hy

    variant, patches, must_fail = FAULTS[fault]
    if variant == "rotary_whole_head":
        variant = dict(rotary_full=_rotary_whole_head())
    for name, make in patches.items():
        monkeypatch.setattr(hy, name, make(hy))
    cell = tiny_cell()
    result, rows = run_and_keep_rows(cell, variant_step(cell, **variant))
    assert result["correct"] is False
    assert must_fail & failed_numbers(rows), sorted(failed_numbers(rows))
    print(fault, "fails:", sorted(failed_numbers(rows)))


def test_masters_held_in_bf16_are_not_correct():
    cell = tiny_cell()
    result, rows = run_and_keep_rows(cell, _masters_in_bf16(cell))
    assert result["correct"] is False
    assert {"dparam_gap", "dparam_median_gap"} & failed_numbers(rows)
    print("bf16 masters fail:", sorted(failed_numbers(rows)))


def test_the_lower_precision_control_is_not_correct():
    """The reference computed in fp8, put in the program's place, fails;
    computed in the program's own bf16 it passes."""
    import jax
    import numpy as np

    cell = tiny_cell()
    cfg, seed = cell.cfg, 5
    params = cell.model.make_params(cfg, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg["vocab_size"],
                       (3, cfg["per_chip_batch"], cfg["seq_len"] + 1))
    raw = {"p0": [np.asarray(l) for l in jax.tree.leaves(params)],
           "first": [(b[:, :-1], b[:, 1:]) for b in ids]}
    assert all(ok for *_, ok in run.judge(cell, raw, {}, precision="bfloat16"))
    failed = failed_numbers(run.judge(cell, raw, {}, precision="fp8"))
    assert failed, "the fp8 control passed every limit"
    print("the fp8 control fails:", sorted(failed))
