"""The hybrid-decoder cell rehearsed at a tiny width on the CPU (control flow
only: no time measured here is a metric), with the faults its comparison must
catch planted under the timed path, and the lower-precision control.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 -m pytest benchmark/tests/test_hybrid_cell.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
from test_benchmark import failed_numbers, read, run_and_keep_rows  # noqa: E402

CELL = "nemotron3_super_dense_staged"


def tiny_cell():
    manifest = read("BENCHMARK.json")
    return run.make_cell(
        "tiny_nemotron_dense_staged", 1,
        read("benchmark/tests/data/tiny_nemotron.json"),
        read("benchmark/traffic/dense_staged.json"),
        read("benchmark/tests/data/tiny_limits_nemotron.json"),
        [m for m in manifest["end_to_end"]
         if m["name"] in ("throughput", "peak_hbm_gb", "setup_s")], [])


def variant_step(cell, **variant):
    """wrap_step putting the program's own step, built anew (from other
    settings, or with a fault patched into the model's module) on the same
    mesh, under the timed path."""
    def wrap(train_step):
        from tpu_compressed_dp.train.lm_step import make_lm_mesh

        *_, step = cell.builder.make_step(cell.cfg, cell.traffic,
                                          make_lm_mesh(cell.chips, 1, 1), **variant)
        return step
    return wrap


def test_the_cell_finds_its_files_and_counts_its_work():
    cell = run.load_cell(CELL)
    assert cell.builder.__file__.endswith("programs/hybrid_dp.py")
    assert cell.model.__file__.endswith("reference/nemotron_h.py")
    for path in ("benchmark/reference/nemotron_h.py", "benchmark/reference/ouro.py"):
        with open(os.path.join(ROOT, path)) as f:
            assert "tpu_compressed_dp" not in f.read()    # nothing of the program
    import flops

    sizes = flops.leaf_sizes(cell.model, cell.cfg)
    assert (sum(sizes), len(sizes)) == (cell.cfg["parameters"],
                                        cell.cfg["parameter_leaves"]) == (915161056, 116)
    assert cell.model.forward_flops_per_sample(cell.cfg) == pytest.approx(
        11.19e12, rel=1e-3)
    hc = cell.builder.hybrid_config(cell.cfg)
    assert (hc.pattern, hc.experts_held, hc.n_routed_experts, hc.vocab_held) == (
        "MEMEMEM*EME", 8, 512, 16384)
    names = {m["name"] for m in cell.per_layer}
    assert {"ssm_device_ms", "moe_device_ms", "moe_dispatch_device_ms",
            "ssd_roofline", "head_xent_device_ms", "flash_attn_device_ms",
            "flash_attn_roofline", "mfu", "grad_device_ms", "update_device_ms",
            "mtp_device_ms", "stack_device_ms"} <= names
    assert set(cell.limits) >= {"mtp_loss_gap", "expert_rows_gap", "route_mass_gap"}
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


def test_the_readers_read_nothing_from_a_trace_without_the_scopes():
    """A program without the scopes (the parent, another cell) leaves the five
    new metrics out; with them the roofline share is the model file's least
    time over the time under the scope."""
    import types

    import trace_reduce

    cell = run.load_cell(CELL)
    ops = [["fusion.1", "grad", "fusion", 0, 500], ["fusion.2", "stack", "fusion", 500, 100]]
    ctx = types.SimpleNamespace(
        extract={"window": [0, 1000], "devices": {"/device:TPU:0": ops}, "host": []},
        traced_steps=2, reduce=trace_reduce, model=cell.model, cfg=cell.cfg,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    new = ("ssm_device_ms", "moe_device_ms", "moe_dispatch_device_ms", "ssd_roofline",
           "mtp_device_ms")
    for name in new:
        assert run.load_reader(name).read(ctx) is None, name
    ops += [["fusion.3", "ssm", "fusion", 600, 8_000_000],
            ["fusion.4", "ssd", "fusion", 700, 20_000_000],
            ["fusion.5", "moe", "fusion", 800, 30_000_000],
            ["while.6", "experts", "fusion", 900, 9_000_000],   # a container: its body's operations count
            ["fusion.7", "experts", "fusion", 950, 6_000_000],
            ["fusion.8", "moe_dispatch", "fusion", 960, 4_000_000],
            ["fusion.9", "mtp", "fusion", 970, 3_000_000]]
    read_ = lambda name: run.load_reader(name).read(ctx)
    assert read_("ssm_device_ms") == pytest.approx(14.0)
    assert read_("moe_device_ms") == pytest.approx(20.0)
    assert read_("moe_dispatch_device_ms") == pytest.approx(2.0)
    assert read_("mtp_device_ms") == pytest.approx(1.5)
    assert read_("stack_device_ms") == pytest.approx(5e-5)   # the residue
    least = cell.model.ssd_min_bytes_per_sample(cell.cfg) / 819e9   # bytes bind
    assert least > cell.model.ssd_flops_per_sample(cell.cfg) / 197e12
    assert read_("ssd_roofline") == pytest.approx(100 * 3 * least * 2 / 0.020)
    # an Ouro trace: the model file has no scan to count
    ouro = run.load_cell("ouro_2p6b_dense_staged")
    ctx.model, ctx.cfg = ouro.model, ouro.cfg
    assert read_("ssd_roofline") is None


def test_rehearsal_runs_and_is_correct():
    result = run.run_cell(tiny_cell(), 7, 1.0, False, require_tpu=False,
                          warm_seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"      # no number here is a metric


def test_the_builder_refuses_a_tree_that_is_not_the_programs():
    import jax

    cell = tiny_cell()

    class Other:
        make_params = staticmethod(lambda cfg, key: {
            **cell.model.make_params(cfg, key), "extra": jax.numpy.zeros((3,))})

    with pytest.raises(ValueError, match="not the program's"):
        cell.builder.build(cell.cfg, cell.traffic, jax.devices()[:1],
                           Other).make_state(3)


def _route_unnormalised(hy):
    import jax
    import jax.numpy as jnp

    def route(cfg, lp, x):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), lp["router"],
                                   precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + lp["e_bias"], cfg.top_k)
        return idx, cfg.routed_scale * jnp.take_along_axis(s, idx, axis=-1)
    return route


def _gate_after_norm(hy):
    import jax
    import jax.numpy as jnp

    def gated(y, z, w, groups, eps):
        g = y.astype(jnp.float32).reshape(y.shape[:-1] + (groups, -1))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(y.shape) * w * jax.nn.silu(z.astype(jnp.float32))
    return gated


def _without(name, arg):
    """``models.hybrid.<name>`` with its ``arg``-th argument zeroed."""
    def make(hy):
        sound = getattr(hy, name)

        def faulty(*args):
            args = list(args)
            args[arg] = args[arg] * 0.0
            return sound(*args)
        return faulty
    return make


def _skip_first_held_expert(hy):
    import jax.numpy as jnp

    sound = hy.dispatch
    return lambda cfg, idx, w: sound(
        cfg, jnp.where(idx == cfg.first_expert, -1, idx), w)


FAULTS = {
    # name: (settings variant, {attribute of models.hybrid: its faulty form},
    #        numbers that must fail)
    "mtp_loss_dropped": (dict(mtp_loss_weight=0.0), {}, {"loss1_gap"}),
    "scaling_factor_1_for_5": (dict(routed_scale=1.0), {}, {"route_mass_gap"}),
    "unnormalised_weights": ({}, {"route": _route_unnormalised}, {"route_mass_gap"}),
    "d_skip_dropped": ({}, {"ssd_chunked_scan": _without("ssd_chunked_scan", 5)},
                       {"grad1_median_gap"}),
    "conv_bias_dropped": ({}, {"causal_depthwise_conv":
                               _without("causal_depthwise_conv", 2)},
                          {"grad1_median_gap"}),
    "gate_after_norm": ({}, {"_gated_group_norm": _gate_after_norm},
                        {"grad1_median_gap"}),
    "a_held_expert_skipped": ({}, {"dispatch": _skip_first_held_expert},
                              {"expert_rows_gap"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    from tpu_compressed_dp.models import hybrid as hy

    variant, patches, must_fail = FAULTS[fault]
    for name, make in patches.items():
        monkeypatch.setattr(hy, name, make(hy))
    cell = tiny_cell()
    result, rows = run_and_keep_rows(cell, variant_step(cell, **variant))
    assert result["correct"] is False
    assert must_fail <= failed_numbers(rows), sorted(failed_numbers(rows))
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
