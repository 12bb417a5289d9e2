"""Scale-out digital twin (tpu_compressed_dp/twin/) — ISSUE 19.

The acceptance surface: every committed BENCH/MULTICHIP artifact parses
through the loader; a fit on planted alpha/beta/gamma recovers them; the
calibration fitted from the real records lands every step row within 15%
of its measured wall; the twin refuses to price an uncalibrated fabric;
``bench/sweep.py --predict`` attaches the W-projection columns; the
controller prices rungs through a TwinPricer under ``--adaptive_model
twin``; and the report CLIs run.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from tpu_compressed_dp.twin import (
    CalibRow, Calibration, CostModel, FabricParams, TwinPoint,
    UncalibratedFabricError, calibration_rows, discover_record_paths, fit,
    load_calibration, load_record_file, predict_step_ms, save_calibration,
    schedule_for_point,
)
from tpu_compressed_dp.twin.model import (
    flat_schedule, hier_schedule, schedule_features,
)
from tpu_compressed_dp.twin.records import context_key, step_row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def repo_calib():
    rows = calibration_rows(REPO)
    assert rows, "no calibration rows found at the repo root"
    return fit(rows), rows


# ------------------------------------------------------------ record loader

@pytest.mark.quick
class TestRecordLoader:
    def test_every_committed_record_parses(self):
        """Every BENCH_r*/MULTICHIP_r* artifact loads, classifies, and
        normalizes without error — the satellite that keeps the twin's
        evidence base schema-honest."""
        paths = discover_record_paths(REPO)
        assert len(paths) >= 7, paths
        shapes = {}
        for p in paths:
            rf = load_record_file(p)
            shapes[rf.source] = rf.shape
            for row in rf.rows:
                assert row.kind in ("step", "phase")
                assert row.target_ms >= 0.0
                assert row.features, row.label
                if row.kind == "step":
                    assert row.context
        # the known artifact census: sweeps carry rows, verdicts carry none
        assert shapes["BENCH_r07.json"] == "sweep"
        assert shapes["BENCH_r09.json"] == "adaptive"
        assert shapes["BENCH_r12.json"] == "stream"

    def test_loader_rejects_malformed(self, tmp_path):
        p = tmp_path / "BENCH_r99.json"
        p.write_text(json.dumps({"n": 1, "cmd": "x", "rc": 0, "records": [
            {"model": "m", "method": "none", "granularity": "g",
             "mode": "wire", "devices": 8, "batch": 64,
             "step_ms": "fast", "payload_mb_per_step": 1.0,
             "transport": "psum"}]}))
        with pytest.raises(ValueError, match="step_ms"):
            load_record_file(str(p))
        p.write_text(json.dumps({"n": 1, "cmd": "x", "rc": 0}))
        with pytest.raises(ValueError, match="unrecognized"):
            load_record_file(str(p))

    def test_context_key_pins_repeats_and_splits_configs(self):
        base = {"model": "resnet9", "method": "topk", "granularity": "e",
                "mode": "wire", "transport": "sharded", "ratio": 0.01,
                "devices": 8, "batch": 64}
        assert context_key(dict(base)) == context_key(dict(base))
        assert context_key(dict(base, dp_pods=2)) != context_key(dict(base))
        assert context_key(dict(base, pallas_mode="force")) \
            != context_key(dict(base))
        # powersgd keys on rank, not ratio
        pg = dict(base, method="powersgd", rank=4)
        assert "knob=4" in context_key(pg)


# ------------------------------------------------------------ the fitter

@pytest.mark.quick
class TestFit:
    def _synthetic_rows(self, alpha, beta, gamma, *, fabric="dcn"):
        """Rows generated from a planted (alpha, beta, gamma) + two known
        compute contexts — exactly recoverable by the lstsq."""
        truth = CostModel({fabric: FabricParams(alpha, beta, gamma, rows=1)})
        rows = []
        for i, (count, mb, w) in enumerate(
                [(1.0, 2.0, 8), (2.0, 0.5, 8), (4.0, 8.0, 4),
                 (1.0, 16.0, 16), (3.0, 1.0, 32)]):
            sched = [dataclasses.replace(
                flat_schedule(world=w, pods=2, count=count, psum_mb=mb)[0],
                fabric=fabric)]
            rows.append(CalibRow(
                source="synt", index=i, kind="phase", label=f"ph{i}",
                context=None, features=schedule_features(sched),
                target_ms=truth.comm_ms(sched)))
        for ctx, compute in (("a", 100.0), ("b", 250.0)):
            sched = flat_schedule(world=8, pods=2, count=2.0, psum_mb=4.0)
            rows.append(CalibRow(
                source="synt", index=10, kind="step", label=f"st-{ctx}",
                context=ctx, features=schedule_features(sched),
                target_ms=compute + truth.comm_ms(sched)))
        return rows

    def test_recovers_planted_params(self):
        calib = fit(self._synthetic_rows(3.0, 1.5, 0.25))
        p = calib.fabrics["dcn"]
        assert p.alpha_ms == pytest.approx(3.0, rel=1e-6)
        assert p.beta_ms_per_mb == pytest.approx(1.5, rel=1e-6)
        assert p.gamma_ms_per_hop == pytest.approx(0.25, rel=1e-6)
        assert calib.contexts["a"] == pytest.approx(100.0, rel=1e-6)
        assert calib.contexts["b"] == pytest.approx(250.0, rel=1e-6)
        assert all(abs(r.err_frac) < 1e-6 for r in calib.residuals)

    def test_clips_unphysical_params_to_zero(self):
        """Noise that would fit a negative coordinate gets clipped by the
        active-set pass; the step contexts re-solve exactly so the step
        residuals stay unpolluted."""
        rows = self._synthetic_rows(3.0, 0.0, 0.0)
        calib = fit(rows)
        p = calib.fabrics["dcn"]
        assert p.beta_ms_per_mb >= 0.0 and p.gamma_ms_per_hop >= 0.0
        for r in calib.residuals:
            if r.kind == "step":
                assert abs(r.err_frac) < 1e-6

    def test_fit_refuses_empty(self):
        with pytest.raises(ValueError, match="no calibration rows"):
            fit([])

    def test_save_load_roundtrip(self, tmp_path):
        calib = fit(self._synthetic_rows(3.0, 1.5, 0.25))
        path = str(tmp_path / "calib.json")
        save_calibration(calib, path)
        back = load_calibration(path)
        assert back.fabrics == calib.fabrics
        assert back.contexts == calib.contexts
        assert back.residuals == calib.residuals


# ------------------------------------------- modeled vs measured (real data)

class TestRealCalibration:
    def test_every_step_row_within_15_percent(self):
        """The headline acceptance bound: the twin fitted from the repo's
        own records reprices EVERY measured step row within 15%."""
        calib, rows = repo_calib()
        step = [r for r in calib.residuals if r.kind == "step"]
        assert len(step) >= 20
        for r in step:
            assert abs(r.err_frac) < 0.15, (
                f"{r.label}: modeled {r.modeled_ms:.1f} vs measured "
                f"{r.measured_ms:.1f} ({r.err_frac:+.1%})")
        assert calib.step_rms_frac < 0.15

    def test_both_fabrics_have_evidence(self):
        calib, _ = repo_calib()
        assert calib.fabrics["dcn"].rows > 0
        assert calib.fabrics["ici"].rows > 0
        for p in calib.fabrics.values():
            assert p.alpha_ms >= 0.0 and p.beta_ms_per_mb >= 0.0
            assert p.gamma_ms_per_hop >= 0.0

    def test_fit_is_deterministic(self):
        a, _ = repo_calib()
        b, _ = repo_calib()
        assert a.fabrics == b.fabrics and a.contexts == b.contexts


# ------------------------------------------------------------ forward model

@pytest.mark.quick
class TestForwardModel:
    MODEL = CostModel({"dcn": FabricParams(10.0, 1.0, 2.0, rows=5),
                       "ici": FabricParams(0.1, 0.05, 0.01, rows=5)})

    def test_refuses_uncalibrated_fabric(self):
        starved = CostModel({"ici": FabricParams(0.1, 0.05, 0.01, rows=5),
                             "dcn": FabricParams(rows=0)})
        pt = TwinPoint(world=8, transport="psum", n_params=1000, dp_pods=2)
        with pytest.raises(UncalibratedFabricError, match="dcn"):
            predict_step_ms(starved, pt)
        # the same point on a flat mesh bills ICI and prices fine
        flat = dataclasses.replace(pt, dp_pods=1)
        assert predict_step_ms(starved, flat) > 0.0

    def test_transport_schedules(self):
        n = 400_000
        for transport, pods, fabrics in (
                ("psum", 1, {"ici"}), ("psum", 2, {"dcn"}),
                ("all_gather", 2, {"dcn"}), ("sharded", 2, {"dcn"}),
                ("hierarchical", 2, {"ici", "dcn"})):
            method = "none" if transport == "psum" else "topk"
            sched = schedule_for_point(TwinPoint(
                world=8, transport=transport, n_params=n, dp_pods=pods,
                method=method, ratio=0.01))
            assert {c.fabric for c in sched} == fabrics, transport

    def test_hierarchical_beats_flat_at_scale(self):
        """The paper's point, restated by the twin: at large W the
        hierarchical transport's step time grows like pods while any flat
        collective grows like W."""
        def at(w, transport):
            return predict_step_ms(self.MODEL, TwinPoint(
                world=w, transport=transport, n_params=400_000,
                dp_pods=max(1, w // 64), method="topk", ratio=0.01))
        assert at(4096, "hierarchical") < at(4096, "all_gather")
        assert at(4096, "hierarchical") < at(4096, "sharded")
        # growth across a 16x scale-out: pods-like for hierarchical,
        # W-like for the flat collective
        hier_growth = at(4096, "hierarchical") / at(256, "hierarchical")
        flat_growth = at(4096, "all_gather") / at(256, "all_gather")
        assert hier_growth < flat_growth / 2.0

    def test_overlap_discount(self):
        pt = TwinPoint(world=8, transport="psum", n_params=400_000)
        full = predict_step_ms(self.MODEL, pt)
        half = predict_step_ms(self.MODEL, dataclasses.replace(
            pt, hideable_fraction=0.5))
        assert half == pytest.approx(full / 2.0)

    def test_hier_single_pod_degenerates_to_psum(self):
        sched = schedule_for_point(TwinPoint(
            world=8, transport="hierarchical", n_params=400_000,
            dp_pods=1, method="topk", ratio=0.01))
        assert [c.fabric for c in sched] == ["ici"]


# ------------------------------------------------------- sweep --predict

class TestSweepPredict:
    def test_attach_prediction_columns(self):
        from tpu_compressed_dp.bench.sweep import (PREDICT_WORLDS,
                                                   attach_prediction)

        calib, _ = repo_calib()
        rec = json.load(open(os.path.join(REPO, "BENCH_r10.json")))[
            "records"][2]  # topk hierarchical W=8 pods=2
        rec = dict(rec)
        attach_prediction(rec, calib)
        assert rec["pred_basis"] == "context"
        assert rec["pred_step_ms"] == pytest.approx(
            float(rec["step_ms"]), rel=0.15)
        assert abs(rec["pred_err_frac"]) < 0.15
        assert rec["pred_err_bar_ms"] > 0.0
        for w in PREDICT_WORLDS:
            assert rec[f"pred_step_ms_w{w}"] is not None
        assert tuple(PREDICT_WORLDS) == (64, 256, 1024, 4096)

    def test_unseen_config_anchors_on_measured(self):
        from tpu_compressed_dp.bench.sweep import attach_prediction

        calib, _ = repo_calib()
        rec = json.load(open(os.path.join(REPO, "BENCH_r10.json")))[
            "records"][2]
        rec = dict(rec, batch=999)  # context never benchmarked
        attach_prediction(rec, calib)
        assert rec["pred_basis"] == "measured_anchor"
        assert rec["pred_err_frac"] == pytest.approx(0.0, abs=1e-9)


# ------------------------------------------------ control-plane integration

class TestTwinPricer:
    def _pricer(self, transport="psum", world=8, pods=1):
        from tpu_compressed_dp.control.signals import TwinPricer

        calib, rows = repo_calib()
        return TwinPricer(model=calib.model, world=world, pods=pods,
                          transport=transport, calib_rows=len(rows))

    def test_comm_pricing_is_monotone_in_bits(self):
        for transport in ("psum", "all_gather", "sharded", "hierarchical"):
            pr = self._pricer(transport=transport)
            lo, hi = pr.comm_ms(1e5), pr.comm_ms(1e6)
            assert 0.0 <= lo <= hi, transport

    def test_controller_requires_pricer_for_twin(self):
        from tpu_compressed_dp.control import ControlConfig, Controller

        cfg = ControlConfig(method="topk", rungs=(0.5, 0.25),
                            budget_ms=1.0, model="twin")
        with pytest.raises(ValueError, match="TwinPricer"):
            Controller(cfg)

    def test_config_rejects_unknown_model(self):
        from tpu_compressed_dp.control import ControlConfig

        with pytest.raises(ValueError, match="flat|twin"):
            ControlConfig(method="topk", rungs=(0.5, 0.25), budget_ms=1.0,
                          model="oracle")

    def test_twin_signal_and_metrics(self):
        from tpu_compressed_dp.control import (ControlConfig, Controller,
                                               init_control_state)

        cfg = ControlConfig(method="topk", rungs=(0.5, 0.25, 0.125),
                            window=4, deadband=0.25, budget_ms=1.0,
                            bandwidth_mbps=100.0, model="twin")
        c = Controller(cfg, pricer=self._pricer())
        cs = init_control_state(cfg)
        sig = c.window_signals(mean_bits=4e5)
        assert sig.comm_ms == pytest.approx(
            self._pricer().comm_ms(4e5))
        # mid-window (accumulators live): the twin stats are exported
        cs, _ = c.tick(cs, applied=2, signals=sig)
        m = c.metrics(cs)
        assert "twin/pred_step_ms" in m and "twin/calib_rows" in m
        assert m["twin/calib_rows"] > 0
        # flat default emits no twin stats
        flat = Controller(dataclasses.replace(cfg, model="flat"))
        fs = init_control_state(cfg)
        fs, _ = flat.tick(fs, applied=2,
                          signals=flat.window_signals(mean_bits=4e5))
        assert not any(k.startswith("twin/") for k in flat.metrics(fs))

    def test_window_close_prices_through_twin(self):
        from tpu_compressed_dp.control import (ControlConfig, Controller,
                                               init_control_state)

        cfg = ControlConfig(method="topk", rungs=(0.5, 0.25, 0.125),
                            window=2, deadband=0.25, budget_ms=1.0,
                            bandwidth_mbps=100.0, model="twin")
        pr = self._pricer()
        c = Controller(cfg, pricer=pr)
        cs = init_control_state(cfg)
        cs, (dec,) = c.tick(cs, applied=2,
                            signals=c.window_signals(mean_bits=4e5))
        assert dec.comm_ms == pytest.approx(pr.comm_ms(4e5))

    def test_build_twin_pricer_from_args(self):
        import argparse

        from tpu_compressed_dp.harness.loop import build_twin_pricer

        ns = argparse.Namespace(adaptive_model="twin", twin_records=REPO,
                                dp_pods=2)
        comp = argparse.Namespace(mode="wire", transport="allgather")
        pr = build_twin_pricer(ns, comp, world=8)
        assert pr is not None and pr.transport == "all_gather"
        assert pr.world == 8 and pr.pods == 2 and pr.calib_rows > 0
        ns_flat = argparse.Namespace(adaptive_model="flat")
        assert build_twin_pricer(ns_flat, None, world=8) is None

    def test_twin_stats_registered_and_lint_clean(self):
        from tpu_compressed_dp.analysis.hostlint import STAT_FAMILIES
        from tpu_compressed_dp.obs.registry import is_declared

        for name in ("twin/pred_step_ms", "twin/pred_err_frac",
                     "twin/calib_rows"):
            assert is_declared(name), name
        assert "twin" in STAT_FAMILIES


# ------------------------------------------------------------ the CLIs

class TestTwinCLIs:
    def _run(self, argv):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run([sys.executable] + argv, cwd=REPO, env=env,
                              capture_output=True, text=True, timeout=300)

    def test_twin_report_smoke(self):
        r = self._run(["tools/twin_report.py", "--records", "."])
        assert r.returncode == 0, r.stderr
        assert "calibration" in r.stdout
        assert "modeled vs measured (step rows)" in r.stdout
        for w in (64, 256, 1024, 4096):
            assert f"W={w}" in r.stdout

    def test_control_report_twin_column(self):
        """control_report's modeled-vs-measured audit: decision rows gain
        a twin-priced comm column next to the flat price."""
        import tools.control_report as cr
        from tpu_compressed_dp.obs.export import SCHEMA_VERSION

        events = [
            {"v": SCHEMA_VERSION, "kind": "run_start",
             "transport": "allgather", "devices": 8, "dp_pods": 2},
            {"v": SCHEMA_VERSION, "kind": "control_decision", "index": 0,
             "applied": 8, "updates": 8, "knob": "ratio", "rung_to": 0,
             "value_to": 0.5, "comm_ms": 4.0, "budget_ms": 1.0,
             "bits": 4e5, "direction": "hold"},
        ]
        pricer = cr.build_pricer(events, REPO)
        assert pricer.transport == "all_gather"
        assert pricer.world == 8 and pricer.pods == 2
        rows = [{"bits": 4e5}, {"note": "no bits"}]
        cr.attach_twin_price(rows, pricer)
        assert rows[0]["twin_comm_ms"] == pytest.approx(
            pricer.comm_ms(4e5))
        assert "twin_comm_ms" not in rows[1]
        text = cr.render_report(events, pricer=pricer)
        assert "twin ms" in text and "twin: W=8 pods=2" in text
        # without the pricer the report stays byte-identical to before
        assert "twin" not in cr.render_report(events)

    def test_twin_report_json(self):
        r = self._run(["tools/twin_report.py", "--records", ".", "--json"])
        assert r.returncode == 0, r.stderr
        doc = json.loads(r.stdout)
        assert set(doc["fabrics"]) == {"dcn", "ici"}
        assert doc["projection"]
