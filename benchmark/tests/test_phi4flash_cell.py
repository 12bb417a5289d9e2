"""The ``phi4flash`` cell rehearsed at a tiny width on the CPU (control flow
only: no time measured here is a metric), with the faults its comparison must
catch planted under the timed path, and the lower-precision control.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_phi4flash_cell.py -q
"""

from __future__ import annotations

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

CELL = "phi4_mini_flash_dense_staged"


def tiny_cell():
    manifest = read("BENCHMARK.json")
    return run.make_cell(
        "tiny_phi4flash_dense_staged", 1,
        read("benchmark/tests/data/tiny_phi4flash.json"),
        read("benchmark/traffic/dense_staged.json"),
        read("benchmark/tests/data/tiny_limits_phi4flash.json"),
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
    assert cell.builder.__file__.endswith("programs/phi4flash_dp.py")
    assert cell.model.__file__.endswith("reference/phi4flash.py")
    with open(os.path.join(ROOT, "benchmark/reference/phi4flash.py")) as f:
        assert "tpu_compressed_dp" not in f.read()        # nothing of the program
    import flops

    cfg = cell.cfg
    sizes = flops.leaf_sizes(cell.model, cfg)
    assert (sum(sizes), len(sizes)) == (cfg["parameters"], cfg["parameter_leaves"]) == (
        893728256, 109)
    uncut = dict(cfg, first_layer=0, **{k: v for k, v in cfg["published"].items()
                                        if k != "parameters"})
    assert sum(flops.leaf_sizes(cell.model, uncut)) == 3852562944
    fwd = cell.model.forward_flops_per_sample(cfg)
    assert fwd == pytest.approx(16.256e12, rel=1e-3)
    t = cfg["seq_len"]
    # a full or cross layer: T^2 x 7,680 (40 maps, keys of 64 and values of 128)
    assert cell.model.attention_flops_per_sample(cfg) == 3 * t * t * 7680.0
    assert cell.model.cross_attention_flops_per_sample(cfg) == 2 * 3 * t * t * 7680.0
    assert cell.model.window_attention_flops_per_sample(cfg) == (
        3 * 40 * 2 * 192.0 * (t * 512 - 512 * 511 / 2))
    assert cell.model.ssd_flops_per_sample(cfg) == 2 * t * 5120 * (8 + 3 + 7 * 16.0)
    assert cell.model.ssd_min_bytes_per_sample(cfg) == 2 * t * (5120 * 8 + 64.0)
    hc = cell.builder.phi4flash_config(cfg)
    assert (hc.pattern, hc.first_layer, hc.vocab_held, hc.vocab_size, hc.d_inner,
            hc.ssm_state, hc.dt_rank, hc.n_heads, hc.n_kv_heads, hc.head_dim,
            hc.window, hc.ffn, hc.chunk) == (
        "SWSFGXGX", 14, 25008, 200064, 5120, 16, 160, 40, 20, 64, 512, 10240, 128)
    names = {m["name"] for m in cell.per_layer}
    assert {"gmu_device_ms", "cross_attn_device_ms", "cross_attn_roofline",
            "mlp_device_ms", "ssd_roofline", "ssm_device_ms", "flash_attn_device_ms",
            "flash_attn_roofline", "window_attn_device_ms", "window_attn_roofline",
            "mfu", "grad_device_ms", "update_device_ms", "stack_device_ms",
            "head_xent_device_ms"} <= names
    assert set(cell.limits) >= {"diff_lambda_gap", "memory_rms_gap"}
    # every number of the catalog's entry, but the cuts the benchmark's entry
    # lists, each with its published value beside it
    manifest = read("BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cfg["name"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["source_url"] == entry["source"])
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(entry["reduced"]) - {"train_steps", "data"}
    assert {k: cfg["published"][k] for k in differ} == {k: row["config"][k] for k in differ}
    assert "3,852,562,944" in cfg["published"]["parameters"]


def test_the_new_readers_read_their_scopes_and_nothing_without_them():
    """A program without the scopes (the parent, another cell) leaves the four
    new metrics out; with them the times are the scopes' and the share is the
    model file's operations over the peak over the kernels' time; the
    self-attention kernels stay with their own readers."""
    import trace_reduce

    cell = run.load_cell(CELL)
    ops = [["fusion.1", "grad", "fusion", 0, 500],
           ["custom-call.2", "attn", "pallas", 500, 4_000_000]]
    ctx = types.SimpleNamespace(
        extract={"window": [0, 1000], "devices": {"/device:TPU:0": ops}, "host": []},
        traced_steps=2, reduce=trace_reduce, model=cell.model, cfg=cell.cfg,
        constants={}, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    new = ("gmu_device_ms", "cross_attn_device_ms", "cross_attn_roofline",
           "mlp_device_ms")
    read_ = lambda name: run.load_reader(name).read(ctx)
    for name in new + ("ssd_roofline", "ssm_device_ms", "window_attn_roofline"):
        assert read_(name) is None, name
    ops += [["custom-call.3", "attn_cross", "pallas", 600, 60_000_000],
            ["fusion.4", "attn_cross", "fusion", 700, 1_000_000],      # not a kernel
            ["custom-call.5", "attn_window", "pallas", 750, 8_000_000],
            ["fusion.6", "gmu", "fusion", 800, 10_000_000],
            ["fusion.7", "mlp", "fusion", 900, 30_000_000],
            ["while.8", "ssd", "while", 950, 9_000_000_000],           # a container
            ["fusion.9", "ssd", "fusion", 960, 200_000_000],
            ["fusion.10", "ssm", "fusion", 970, 20_000_000]]
    assert read_("flash_attn_device_ms") == pytest.approx(2.0)
    assert read_("window_attn_device_ms") == pytest.approx(4.0)
    assert read_("cross_attn_device_ms") == pytest.approx(30.0)
    assert read_("gmu_device_ms") == pytest.approx(5.0)
    assert read_("mlp_device_ms") == pytest.approx(15.0)
    assert read_("ssm_device_ms") == pytest.approx(110.0)
    flops = cell.model.cross_attention_flops_per_sample(cell.cfg) * 1 * 2
    assert read_("cross_attn_roofline") == pytest.approx(100 * flops / 197e12 / 0.060)
    # the scan's share: HBM-bound by the model file's least bytes
    least = cell.model.ssd_min_bytes_per_sample(cell.cfg) / 819e9
    assert least > cell.model.ssd_flops_per_sample(cell.cfg) / 197e12
    assert read_("ssd_roofline") == pytest.approx(100 * 3 * least * 2 / 0.2)
    # a model file that does not count the cross layers reads no share
    laguna = run.load_cell("laguna_xs2_dense_staged")
    ctx.model, ctx.cfg = laguna.model, laguna.cfg
    assert read_("cross_attn_roofline") is None
    assert read_("cross_attn_device_ms") == pytest.approx(30.0)


def test_rehearsal_runs_and_is_correct():
    result = run.run_cell(tiny_cell(), 7, 1.0, False, require_tpu=False,
                          warm_seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"      # no number here is a metric


def _memory_after_its_gate(sy):
    import jax
    import jax.numpy as jnp

    sound = sy._mamba_mixer

    def faulty(cfg, lp, x):
        out, m = sound(cfg, lp, x)
        z = jnp.split(x @ lp["w_in"].astype(cfg.dtype), 2, axis=-1)[1]
        return out, sy._gated(m, z, cfg.dtype)
    return faulty


def _cross_reads_the_window_layer(sy):
    sound = sy.SambaYConfig.producers
    return lambda self: {i: (1 if self.pattern[i] == "X" else src)
                         for i, src in sound(self).items()}


def _decay_by_channel_only(sy):
    import jax.numpy as jnp

    sound = sy._decay_matrix
    return lambda a_log: jnp.broadcast_to(sound(a_log)[:, 1:2], a_log.shape)


FAULTS = {
    # name: (settings variant, {attribute of models.sambay (dotted: of its
    #        settings class): its faulty form}, numbers of which one at least
    #        must fail)
    "gmu_fed_the_memory_after_its_gate": (
        {}, {"_mamba_mixer": _memory_after_its_gate},
        {"grad1_median_gap", "grad1_gap", "memory_rms_gap"}),
    "cross_attention_fed_the_window_layers_keys_and_values": (
        {}, {"SambaYConfig.producers": _cross_reads_the_window_layer},
        {"grad1_median_gap", "grad1_gap"}),
    "lam0_by_held_position": (dict(first_layer=0), {}, {"diff_lambda_gap"}),
    "one_minus_lam0_dropped": ({}, {"_out_scale": lambda sy: lambda lam0: 1.0},
                               {"grad1_median_gap", "grad1_gap"}),
    "sub_norm_dropped": ({}, {"_sub_norm": lambda sy: lambda o, w, eps: o * w},
                         {"grad1_median_gap", "grad1_gap"}),
    "window_on_the_full_layer": (dict(pattern="SWSWGXGX"), {},
                                 {"grad1_median_gap", "grad1_gap"}),
    "decay_by_channel_only": ({}, {"_decay_matrix": _decay_by_channel_only},
                              {"memory_rms_gap", "grad1_gap", "dparam_gap"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    from tpu_compressed_dp.models import sambay as sy

    variant, patches, must_fail = FAULTS[fault]
    for name, make in patches.items():
        owner, _, attr = name.rpartition(".")
        monkeypatch.setattr(getattr(sy, owner) if owner else sy, attr, make(sy))
    cell = tiny_cell()
    result, rows = run_and_keep_rows(cell, variant_step(cell, **variant))
    assert result["correct"] is False
    assert must_fail & failed_numbers(rows), sorted(failed_numbers(rows))
    print(fault, "fails:", sorted(failed_numbers(rows)))


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
