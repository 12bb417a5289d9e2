"""The LM cell rehearsed at a tiny width on the CPU (control flow only: no
time measured here is a metric), with the faults its comparison must catch
planted under the timed path, and the lower-precision control.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 -m pytest benchmark/tests/test_lm_cell.py -q
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


def tiny_cell():
    traffic = read("benchmark/traffic/dense_staged.json")
    manifest = read("BENCHMARK.json")
    return run.make_cell(
        "tiny_ouro_dense_staged", 1, read("benchmark/tests/data/tiny_ouro.json"),
        traffic, read("benchmark/tests/data/tiny_limits_ouro.json"),
        [m for m in manifest["end_to_end"]
         if m["name"] in ("throughput", "peak_hbm_gb", "setup_s")], [])


def variant_step(cell, **variant):
    """wrap_step putting the program's own step, built from other decoder
    settings on the same mesh, under the timed path."""
    builder = cell.builder

    def wrap(train_step):
        from tpu_compressed_dp.train.lm_step import make_lm_mesh

        *_, step = builder.make_step(cell.cfg, cell.traffic,
                                     make_lm_mesh(cell.chips, 1, 1), **variant)
        return step
    return wrap


def test_the_cell_finds_its_files_and_counts_its_work():
    cell = run.load_cell("ouro_2p6b_dense_staged")
    assert cell.builder.__file__.endswith("programs/lm_dp.py")
    assert cell.model.__file__.endswith("reference/ouro.py")
    with open(os.path.join(ROOT, "benchmark/reference/ouro.py")) as f:
        assert "tpu_compressed_dp" not in f.read()       # nothing of the program
    import flops

    assert len(flops.leaf_sizes(cell.model, cell.cfg)) == 71
    assert flops.train_flops_per_sample(cell.model, cell.cfg) == pytest.approx(
        45.15e12, rel=1e-3)
    names = {m["name"] for m in cell.per_layer}
    assert {"stack_device_ms", "head_xent_device_ms", "flash_attn_device_ms",
            "flash_attn_roofline", "mfu", "grad_device_ms",
            "update_device_ms"} <= names
    assert set(cell.limits) >= {"pass_loss_gap", "exit_mass_gap"}
    # every published number of the catalog's entry, but the cut
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    differ = {k for k, v in row["config"].items() if cell.cfg.get(k) != v}
    assert differ == {"num_hidden_layers"} and cell.cfg["num_hidden_layers"] == 6


def test_the_readers_read_nothing_from_a_trace_without_the_scopes():
    """A program without the scopes (the parent, an image cell) leaves the four
    metrics out; with them the roofline share is the counted operations over
    the kernels' time."""
    import types

    import trace_reduce

    cell = run.load_cell("ouro_2p6b_dense_staged")
    ops = [["fusion.1", "grad", "fusion", 0, 500], ["custom-call.2", "compress",
                                                    "pallas", 500, 100]]
    ctx = types.SimpleNamespace(
        extract={"window": [0, 1000], "devices": {"/device:TPU:0": ops}, "host": []},
        traced_steps=1, reduce=trace_reduce, model=cell.model, cfg=cell.cfg,
        peaks={"bf16_flops": 197e12})
    for name in ("stack_device_ms", "head_xent_device_ms", "flash_attn_device_ms",
                 "flash_attn_roofline"):
        assert run.load_reader(name).read(ctx) is None, name
    ops += [["fusion.3", "stack", "fusion", 600, 2_000_000],
            ["custom-call.4", "attn", "pallas", 700, 100_000_000],
            ["fusion.5", "attn", "fusion", 800, 7_000_000],
            ["fusion.6", "head_xent", "fusion", 900, 3_000_000]]
    read_ = lambda name: run.load_reader(name).read(ctx)
    assert read_("stack_device_ms") == pytest.approx(109.0)
    assert read_("head_xent_device_ms") == pytest.approx(3.0)
    assert read_("flash_attn_device_ms") == pytest.approx(100.0)
    assert read_("flash_attn_roofline") == pytest.approx(
        100 * 4 * 6 * 16 * 6 * 4096 ** 2 * 128 / 197e12 / 0.1)


def test_rehearsal_runs_and_is_correct():
    result = run.run_cell(tiny_cell(), 7, 1.0, False, require_tpu=False,
                          warm_seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"      # no number here is a metric


def test_the_builder_refuses_a_tree_that_is_not_the_programs():
    cell = tiny_cell()
    cell.cfg = dict(cell.cfg, total_ut_steps=4)
    import jax

    prog = cell.builder.build(cell.cfg, cell.traffic, jax.devices()[:1], cell.model)
    prog.make_state(3)                                  # the sound tree passes

    class Other:
        make_params = staticmethod(lambda cfg, key: {
            **cell.model.make_params(cfg, key), "extra": jax.numpy.zeros((3,))})

    with pytest.raises(ValueError, match="not the program's"):
        cell.builder.build(cell.cfg, cell.traffic, jax.devices()[:1],
                           Other).make_state(3)


def test_a_dropped_pass_is_not_correct():
    """Three passes for four under the timed path."""
    cell = tiny_cell()
    result, rows = run_and_keep_rows(cell, variant_step(cell, n_passes=3))
    assert result["correct"] is False
    assert {"pass_loss_gap", "exit_mass_gap"} <= failed_numbers(rows)


def test_a_loss_without_the_exit_weighting_is_not_correct(monkeypatch):
    """Every pass weighted alike, whatever the gate says."""
    import jax.numpy as jnp

    from tpu_compressed_dp.train import lm_step

    cell = tiny_cell()
    sound = lm_step.exit_weighted_loss

    def unweighted(nll, gate, beta):
        _, stats = sound(nll, jnp.zeros_like(gate), beta)
        return jnp.mean(nll), dict(stats, exit_mass=jnp.full(
            (nll.shape[0],), 1.0 / nll.shape[0]))

    monkeypatch.setattr(lm_step, "exit_weighted_loss", unweighted)
    result, rows = run_and_keep_rows(cell, variant_step(cell))
    assert result["correct"] is False
    assert {"exit_mass_gap", "loss1_gap", "grad1_median_gap"} <= failed_numbers(rows)


def test_an_untied_pass_is_not_correct():
    """The comparison's other side untied: a reference whose gradient is that
    of the first pass's copy of the weights alone, as a program would leave it
    whose passes did not share their weights.  The program's tied gradient is
    then the one that does not fit."""
    import jax

    cell = tiny_cell()
    ref = cell.model

    class Untied:
        def __getattr__(self, name):
            return getattr(ref, name)

        @staticmethod
        def make_loss_and_grad(cfg, precision="float32"):
            def loss_and_grad(params, tokens, labels):
                copies = [params] * cfg["total_ut_steps"]
                (loss, aux), g = jax.value_and_grad(
                    lambda c: ref.loss_fn(c, tokens, labels, cfg, precision),
                    has_aux=True)(copies)
                return (loss, aux), g[0]
            return jax.jit(loss_and_grad)

    cell.model = Untied()
    result, rows = run_and_keep_rows(cell, None)
    assert result["correct"] is False
    assert "grad1_median_gap" in failed_numbers(rows)
    assert "loss1_gap" not in failed_numbers(rows)       # the forward is the same


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
