"""tools/doctor.py + tools/bench_history.py — pure-host CLI coverage.

The doctor's verdict is PINNED on a canned bench+profile+metrics
fixture (the ISSUE-8 acceptance shape): measured ``bound:`` next to the
preserved ``bound_static``, the bucket table, achieved-vs-roof rates,
the top-3 fixes, the live-HBM section with the measured donation
verification, and the metrics summary. bench_history covers the
r01→rNN trajectory shapes, the regression threshold gate and the
``--baseline-provenance`` mixed-fingerprint refusal.
"""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_cli", os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def doctor():
    return _load_tool("doctor")


@pytest.fixture
def history():
    return _load_tool("bench_history")


# -- the canned run-dir fixture ---------------------------------------------

def _canned_attr():
    """A measured attribution: 82% dispatch — the ISSUE's example."""
    return {"dispatch_s": 8.2, "transfer_s": 0.4, "device_s": 0.9,
            "collective_s": 0.0, "host_s": 0.5, "measured_wall_s": 10.0,
            "dispatch_calls": 640, "transfer_bytes": 4096,
            "source": "timing-harness",
            "fractions": {"dispatch": 0.82, "transfer": 0.04,
                          "device": 0.09, "collective": 0.0,
                          "host": 0.05},
            "bound_measured": "latency"}


def _canned_run_dir(d):
    os.makedirs(d, exist_ok=True)
    bench = {
        "metric": "logreg_criteo_samples_per_sec_per_chip",
        "value": 1000.0, "mode": "quick",
        "workloads": {
            "ftrl_criteo": {
                "samples_per_sec_per_chip": 50000.0,
                "flops_per_sample": 1000.0,
                "hbm_bytes_per_sample": 64.0,
                "bound": "latency", "bound_static": "latency",
                "profile": _canned_attr()},
            # profiled but model-less: verdict must still render
            "kmeans_iris": {
                "samples_per_sec_per_chip": 2.0e6,
                "bound": "device",
                "profile": {**_canned_attr(), "dispatch_s": 0.5,
                            "device_s": 9.0,
                            "fractions": {"dispatch": 0.05,
                                          "transfer": 0.04,
                                          "device": 0.86,
                                          "collective": 0.0,
                                          "host": 0.05},
                            "bound_measured": "device"}},
        },
        "rig": {"dispatch_gap_est_s": 0.0128, "baseline_fp": "fp00",
                "peak_tflops": 197.0, "peak_hbm_gbps": 819.0,
                "profile": True}}
    profile = {
        "format": "alink_tpu_profile_v1", "enabled": True,
        "workloads": {"ftrl_criteo": _canned_attr()},
        "marks": [], "windows": [],
        "hbm": [{"workload": "ftrl_criteo", "scope": "comqueue.chunk",
                 "count": 4, "last_bytes": 1048576,
                 "max_bytes": 2097152}],
        "captures": [],
        "donation": {"state_bytes": 1048576, "steps": 2,
                     "donated_peak_bytes": 1048576,
                     "undonated_peak_bytes": 2097152,
                     "ratio": 0.5, "verified": True,
                     "note": "canned"}}
    metrics = [
        {"name": "alink_comqueue_program_cache_total",
         "labels": {"result": "hit"}, "value": 9},
        {"name": "alink_comqueue_program_cache_total",
         "labels": {"result": "miss"}, "value": 1},
        {"name": "alink_collective_calls_total",
         "labels": {"collective": "AllReduce"}, "value": 12},
        {"name": "alink_hbm_live_bytes",
         "labels": {"scope": "comqueue.chunk"}, "value": 1048576},
    ]
    with open(os.path.join(d, "bench.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(d, "profile.json"), "w") as f:
        json.dump(profile, f)
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        for rec in metrics:
            f.write(json.dumps(rec) + "\n")
    return d


class TestDoctorPinned:
    def test_render_pinned_on_canned_fixture(self, doctor, tmp_path,
                                             capsys):
        d = _canned_run_dir(str(tmp_path / "run"))
        assert doctor.main(["--run-dir", d]) == 0
        out = capsys.readouterr().out
        # the measured bound next to the preserved static projection
        assert "== workload: ftrl_criteo ==" in out
        assert "bound: latency (measured; static: latency)" in out
        assert "source: timing-harness" in out
        # bucket table with the 82%-dispatch headline share
        assert "host dispatch" in out
        assert " 82.0%" in out
        # top fix names dispatch batching, citing the rig floor
        assert "fix 1: 82% of measured wall is host dispatch" in out
        assert "~13 ms/dispatch" in out
        assert "batch more supersteps" in out
        # achieved-vs-roof, device-time-normalized: 50k sps / 0.09
        # device share * 1k flops = 5.6e8 flop/s
        assert "achieved (device-time)" in out
        assert "0.0006 TFLOP/s" in out
        # HBM section + the measured donation verification
        assert "== HBM (live device buffers) ==" in out
        assert "ftrl_criteo/comqueue.chunk" in out
        assert "donation: VERIFIED" in out and "0.5x" in out
        # metrics summary
        assert "program cache: 9 hits / 1 misses (90% hit rate)" in out
        assert "AllReduce=12" in out
        # the model-less workload renders too, with its honest bound
        assert "== workload: kmeans_iris ==" in out
        assert "bound: device" in out

    def test_json_verdict_shape(self, doctor, tmp_path, capsys):
        d = _canned_run_dir(str(tmp_path / "run"))
        assert doctor.main(["--run-dir", d, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["format"] == "alink_tpu_doctor_v1"
        wl = {v["workload"]: v for v in doc["workloads"]}
        v = wl["ftrl_criteo"]
        assert v["bound"] == "latency"
        assert v["bound_static"] == "latency"
        assert v["fractions"]["dispatch"] == pytest.approx(0.82)
        assert v["fixes"] and "dispatch" in v["fixes"][0]
        assert v["achieved_device_time"]["pct_peak_flops"] > 0
        assert doc["donation"]["verified"] is True
        assert doc["rig"]["dispatch_gap_est_s"] == pytest.approx(0.0128)
        assert doc["metrics"]["cache"]["hit"] == 9

    def test_multi_leg_device_time_skips_achieved(self, doctor,
                                                  tmp_path, capsys):
        """Device time merged from several program legs must not be
        normalized against one leg's headline rate: no achieved-vs-roof
        line, honest dominant-bucket fix instead."""
        d = _canned_run_dir(str(tmp_path / "run"))
        bench = json.load(open(os.path.join(d, "bench.json")))
        row = bench["workloads"]["ftrl_criteo"]
        row["profile"].update(
            dispatch_s=0.5, device_s=9.0,
            fractions={"dispatch": 0.05, "transfer": 0.04,
                       "device": 0.86, "collective": 0.0, "host": 0.05},
            bound_measured="device",
            device_scopes=["ftrl.kernel", "ftrl.snapshot"])
        with open(os.path.join(d, "bench.json"), "w") as f:
            json.dump(bench, f)
        assert doctor.main(["--run-dir", d, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        wl = {v["workload"]: v for v in doc["workloads"]}
        assert "achieved_device_time" not in wl["ftrl_criteo"]
        # the device fix must explain the multi-leg refusal, not claim
        # the (present) cost model is missing
        dev_fix = [f for f in wl["ftrl_criteo"]["fixes"]
                   if "program legs" in f]
        assert dev_fix and "ftrl.kernel" in dev_fix[0]
        assert not any("no per-sample cost model" in f
                       for f in wl["ftrl_criteo"]["fixes"])

    def test_profile_only_no_bench(self, doctor, tmp_path, capsys):
        d = _canned_run_dir(str(tmp_path / "run"))
        os.remove(os.path.join(d, "bench.json"))
        assert doctor.main(["--run-dir", d]) == 0
        out = capsys.readouterr().out
        # attribution comes straight from the profile artifact
        assert "== workload: ftrl_criteo ==" in out
        assert "donation: VERIFIED" in out

    def test_no_input_exits_1(self, doctor, tmp_path, capsys):
        assert doctor.main([]) == 1
        assert doctor.main(["--run-dir", str(tmp_path / "nope")]) == 1

    def test_driver_wrapped_bench_accepted(self, doctor, tmp_path,
                                           capsys):
        d = _canned_run_dir(str(tmp_path / "run"))
        inner = json.load(open(os.path.join(d, "bench.json")))
        with open(os.path.join(d, "bench.json"), "w") as f:
            json.dump({"rc": 0, "parsed": inner}, f)
        assert doctor.main(["--run-dir", d]) == 0
        assert "ftrl_criteo" in capsys.readouterr().out


def _round(path, workloads, fp=None, mode="quick"):
    doc = {"workloads_sps_vs": {k: [v, 1.0, 0.5]
                                for k, v in workloads.items()},
           "mode": mode}
    if fp is not None:
        doc["rig"] = {"baseline_fp": fp}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


class TestBenchHistory:
    def test_table_and_sparkline(self, history, tmp_path, capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 100.0})
        b = _round(str(tmp_path / "BENCH_r02.json"),
                   {"x": 200.0, "y": 5.0})
        assert history.main([a, b]) == 0
        out = capsys.readouterr().out
        assert "r01" in out and "r02" in out
        assert "x" in out and "y" in out
        # y missed r01 → placeholder cell and dot in the sparkline
        assert "·" in out

    def test_regression_flag_and_threshold_exit(self, history, tmp_path,
                                                capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 100.0})
        b = _round(str(tmp_path / "BENCH_r02.json"), {"x": 40.0})
        assert history.main([a, b, "--threshold", "30"]) == 2
        out = capsys.readouterr().out
        assert "REGRESSION x" in out and "-60.0%" in out
        # within threshold: exit 0
        assert history.main([a, b, "--threshold", "70"]) == 0

    def test_mixed_fingerprint_refused(self, history, tmp_path, capsys):
        a = _round(str(tmp_path / "BENCH_r05.json"), {"x": 1.0}, fp="A")
        b = _round(str(tmp_path / "BENCH_r06.json"), {"x": 2.0}, fp="B")
        assert history.main([a, b, "--baseline-provenance"]) == 3
        assert "REFUSING" in capsys.readouterr().err
        # same fingerprint passes
        c = _round(str(tmp_path / "BENCH_r07.json"), {"x": 3.0}, fp="B")
        assert history.main([b, c, "--baseline-provenance"]) == 0

    def test_fingerprint_gap_does_not_launder_rig_change(self, history,
                                                         tmp_path,
                                                         capsys):
        """fp=A, fingerprint-less round, fp=B: the refusal compares
        against the LAST KNOWN fingerprint, so the gap round cannot
        launder a rig change past --baseline-provenance."""
        a = _round(str(tmp_path / "BENCH_r05.json"), {"x": 1.0}, fp="A")
        b = _round(str(tmp_path / "BENCH_r06.json"), {"x": 2.0})
        c = _round(str(tmp_path / "BENCH_r07.json"), {"x": 3.0}, fp="B")
        assert history.main([a, b, c, "--baseline-provenance"]) == 3
        err = capsys.readouterr().err
        assert "REFUSING to compare r05 -> r07" in err

    def test_regression_across_missed_round_still_flagged(self, history,
                                                          tmp_path,
                                                          capsys):
        """r04=1000, r05 misses the workload, r06=500: the 50% drop
        compares against the last PRESENT round — a skipped round must
        not hide it from the threshold gate."""
        a = _round(str(tmp_path / "BENCH_r04.json"), {"x": 1000.0})
        b = _round(str(tmp_path / "BENCH_r05.json"), {"other": 1.0})
        c = _round(str(tmp_path / "BENCH_r06.json"), {"x": 500.0,
                                                      "other": 1.0})
        assert history.main([a, b, c, "--threshold", "30"]) == 2
        out = capsys.readouterr().out
        assert "REGRESSION x: r04 -> r06" in out

    def test_missing_fingerprint_warns_not_refuses(self, history,
                                                   tmp_path, capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 1.0})
        b = _round(str(tmp_path / "BENCH_r02.json"), {"x": 2.0}, fp="B")
        assert history.main([a, b, "--baseline-provenance"]) == 0
        assert "not verifiable" in capsys.readouterr().err

    def test_broken_round_skipped(self, history, tmp_path, capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 1.0})
        broken = str(tmp_path / "BENCH_r02.json")
        with open(broken, "w") as f:
            json.dump({"parsed": None}, f)    # the r03 incident shape
        c = _round(str(tmp_path / "BENCH_r03.json"), {"x": 2.0})
        assert history.main([a, broken, c]) == 0
        err = capsys.readouterr().err
        assert "skipping r02" in err

    def test_fewer_than_two_readable_exits_1(self, history, tmp_path,
                                             capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 1.0})
        assert history.main([a]) == 1

    def test_json_output(self, history, tmp_path, capsys):
        a = _round(str(tmp_path / "BENCH_r01.json"), {"x": 100.0})
        b = _round(str(tmp_path / "BENCH_r02.json"), {"x": 50.0})
        assert history.main([a, b, "--json", "--threshold", "10"]) == 2
        doc = json.loads(capsys.readouterr().out)
        assert doc["workloads"]["x"] == [100.0, 50.0]
        assert doc["regressions"][0]["delta_pct"] == -50.0

    def test_r01_final_line_shape(self, history, tmp_path, capsys):
        """The bare r01 dump (flagship metric only) maps onto the
        flagship workload column."""
        r01 = str(tmp_path / "BENCH_r01.json")
        with open(r01, "w") as f:
            json.dump({"metric": "logreg", "value": 123.0,
                       "unit": "sps"}, f)
        b = _round(str(tmp_path / "BENCH_r02.json"),
                   {"logreg_criteo": 456.0})
        assert history.main([r01, b]) == 0
        assert "logreg_criteo" in capsys.readouterr().out


class TestDoctorSweepVerdict:
    def _row(self, **over):
        row = {"samples_per_sec_per_chip": 95.8, "points": 24,
               "iters": 100, "dt_s": 0.25, "serial_s": 1.7,
               "speedup_vs_serial": 6.6, "sweep_full_speedup": 1.4,
               "rungs": 19, "rung_every": 5, "eta": 5,
               "pruned_fraction": 0.958, "winner_match": True,
               "parity": "bitwise", "compiled_programs": 1}
        row.update(over)
        return row

    def _render(self, doctor, row):
        doc = doctor.diagnose(
            {"workloads": {"tuning_sweep": row},
             "rig": {"dispatch_gap_est_s": 0.001, "peak_tflops": 1.0,
                     "peak_hbm_gbps": 1.0}}, None, None, 1.0, 1.0)
        return doc, doctor.render(doc)

    def test_healthy_verdict(self, doctor):
        doc, text = self._render(doctor, self._row())
        assert doc["tuning"][0]["fixes"] == []
        assert "tuning sweep: tuning_sweep" in text
        assert "6.6x the serial candidate loop" in text
        assert "96% pruned" in text
        assert "winner MATCHES serial grid" in text
        assert "per-point parity bitwise" in text
        # the sweep row never enters the generic capture-window section
        assert all(v["workload"] != "tuning_sweep"
                   for v in doc["workloads"])

    def test_fix_lines_name_the_problem(self, doctor):
        doc, text = self._render(doctor, self._row(
            parity="MISMATCH", winner_match=False,
            speedup_vs_serial=1.2, compiled_programs=24))
        fixes = "\n".join(doc["tuning"][0]["fixes"])
        assert "CRITICAL" in fixes and "bitwise" in fixes
        assert "ALINK_TPU_SWEEP_RUNG" in fixes
        assert "alink_sweep_fallback_total" in fixes
        assert "trace-shaping" in fixes
        assert "fix 1:" in text

    def test_bench_history_labels_points_per_sec(self, history):
        assert history._display_name("tuning_sweep") == \
            "tuning_sweep (points/s)"
        assert history._display_name("serve_logreg") == \
            "serve_logreg (qps)"

    def test_bench_compare_labels_points_per_sec(self, history):
        import importlib
        bc = importlib.import_module("tools.bench_compare")
        rows = [{"workload": "tuning_sweep", "old": 50.0, "new": 95.0,
                 "delta_pct": 90.0}]
        text = bc.render(rows, "a.json", "b.json")
        assert "tuning_sweep (points/s)" in text
        # the two gate tools must label rows identically (unit parity)
        for name in ("tuning_sweep", "serve_logreg",
                     "serve_logreg_sharded", "serve_logreg_p99inv",
                     "logreg_criteo"):
            assert bc._display_name(name) == history._display_name(name)


class TestKernelTierVerdicts:
    """ISSUE 13: doctor fix lines name the Pallas kernel tier when
    HBM-round-trip serving shows."""

    def test_ftrl_device_low_roof_names_ftrl_kernel(self, doctor,
                                                    tmp_path, capsys):
        """Scatter-bound FTRL gets the generic device-busy fix: the FTRL
        kernel tier and its flag are gone, so no fix names them."""
        d = _canned_run_dir(str(tmp_path / "run"))
        bench = json.load(open(os.path.join(d, "bench.json")))
        row = bench["workloads"]["ftrl_criteo"]
        # single-leg device-dominated with a cost model whose achieved
        # rate sits far under the roof — the scatter-bound signature
        row["profile"].update(
            dispatch_s=0.5, device_s=9.0,
            fractions={"dispatch": 0.05, "transfer": 0.04,
                       "device": 0.86, "collective": 0.0, "host": 0.05},
            bound_measured="device",
            device_scopes=["ftrl.kernel"])
        with open(os.path.join(d, "bench.json"), "w") as f:
            json.dump(bench, f)
        assert doctor.main(["--run-dir", d, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        wl = {v["workload"]: v for v in doc["workloads"]}
        busy = [f for f in wl["ftrl_criteo"]["fixes"]
                if f.startswith("device-busy 86% but only")]
        assert busy and "grow the shapes" in busy[0]
        assert not any("FTRL_KERNEL" in f or "scatter-bound" in f
                       for v in wl.values() for f in v["fixes"])

    def _serve_fused_doc(self, doctor, tmp_path, capsys, **row):
        d = _canned_run_dir(str(tmp_path / "run"))
        bench = json.load(open(os.path.join(d, "bench.json")))
        bench["workloads"]["serve_fused"] = {
            "samples_per_sec_per_chip": 1000.0,
            "xla_rows_per_sec_per_chip": 2000.0,
            "fused_vs_xla": 0.5, "dtype_winner": "f32",
            "label_agreement_bf16": 1.0, "label_agreement_int8": 1.0,
            "parity": "bitwise", "bound": "serving-host",
            "rig_note": "interpret-mode Pallas (no TPU)", **row}
        with open(os.path.join(d, "bench.json"), "w") as f:
            json.dump(bench, f)
        assert doctor.main(["--run-dir", d, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        return {v["workload"]: v for v in doc.get("serving", [])}

    def test_serve_fused_losing_names_recapture(self, doctor, tmp_path,
                                                capsys):
        sv = self._serve_fused_doc(doctor, tmp_path, capsys)
        v = sv["serve_fused"]
        assert v["fused_vs_xla"] == 0.5
        fix = [f for f in v["fixes"] if "ALINK_TPU_SERVE_FUSED" in f]
        assert fix and "physical TPU slice" in fix[0]

    def test_serve_fused_losing_on_native_rig_flags_regression(
            self, doctor, tmp_path, capsys):
        """A native-Mosaic rig losing fused-vs-xla is a real kernel
        regression, not an interpret artifact — the fix line must say
        so instead of telling the operator to recapture the
        measurement they already have."""
        sv = self._serve_fused_doc(doctor, tmp_path, capsys,
                                   rig_note="native Mosaic kernels")
        fix = [f for f in sv["serve_fused"]["fixes"]
               if "kernel-tier regression" in f]
        assert fix and "native rig" in fix[0]
        assert not any("recapture there" in f
                       for f in sv["serve_fused"]["fixes"])

    def test_serve_fused_parity_mismatch_is_critical(self, doctor,
                                                     tmp_path, capsys):
        sv = self._serve_fused_doc(doctor, tmp_path, capsys,
                                   parity="MISMATCH")
        fix = [f for f in sv["serve_fused"]["fixes"]
               if f.startswith("CRITICAL")]
        assert fix and "kernels/serve.py" in fix[0]
        # not the sharded-mesh message — this is the fused kernel's
        assert "serving/sharded.py" not in fix[0]

    def test_bench_history_labels_kernel_rows(self, history):
        assert history._display_name("serve_fused") \
            == "serve_fused (rows/s)"


class TestDoctorE2eVerdict:
    """ISSUE 15: the serve_online_e2e row gets its own whole-loop
    verdict section naming the weakest stage."""

    def _row(self, **over):
        row = {"samples_per_sec_per_chip": 3193.8, "qps": 3193.8,
               "p99_ms": 330.5, "windows": 4,
               "final_window_auc": 0.9963, "auc_note": None,
               "model_swaps": 4, "swap_staleness_max_ms": 1.267,
               "slo_ok": True, "slo_breaches": 0,
               "slo": [
                   {"slo": "serve_p99", "ok": True, "observed": 0.33,
                    "bound": 2.0, "detail": "x"},
                   {"slo": "swap_staleness", "ok": True,
                    "observed": 0.0013, "bound": 30.0, "detail": "x"},
                   {"slo": "final_window_auc", "ok": True,
                    "observed": 0.9963, "bound": 0.75, "detail": "x"}],
               "silent_drops": 0, "typed_rejections": 768,
               "storm_restarts": 3, "storm_bitwise_journals": True,
               "recovery_s_by_fault": {"ftrl.batch": 0.084,
                                       "ckpt.save": 0.043,
                                       "ingest.batch": 0.0005},
               "recovery_train_restart_s": 0.084,
               "recovered_compiled": True, "feeder_skipped": 1,
               "shed_requests": 0, "dt_s": 4.0}
        row.update(over)
        return row

    def _render(self, doctor, row):
        doc = doctor.diagnose(
            {"workloads": {"serve_online_e2e": row},
             "rig": {"dispatch_gap_est_s": 0.001, "peak_tflops": 1.0,
                     "peak_hbm_gbps": 1.0}}, None, None, 1.0, 1.0)
        return doc, doctor.render(doc)

    def test_healthy_verdict_names_weakest_stage(self, doctor):
        doc, text = self._render(doctor, self._row())
        v = doc["e2e"][0]
        assert v["fixes"] == []
        assert "online DAG e2e: serve_online_e2e" in text
        assert "3,194 qps steady-state" in text
        assert "4 eval windows" in text and "final AUC 0.9963" in text
        assert "journals bitwise" in text
        assert "breaker recovered to compiled" in text
        # the AUC clause runs at 75% of budget — the tightest margin —
        # so the weakest stage names train/eval quality
        assert v["weakest_stage"] == "train"
        assert "weakest stage: train" in text
        assert "verdict: healthy" in text
        # the e2e row enters NEITHER the generic capture-window section
        # NOR the per-serve-row section (it has its own)
        assert all(w["workload"] != "serve_online_e2e"
                   for w in doc["workloads"])
        assert all(w["workload"] != "serve_online_e2e"
                   for w in doc.get("serving", []))

    def test_tight_p99_margin_moves_weakest_to_serve(self, doctor):
        row = self._row()
        row["slo"][0] = {"slo": "serve_p99", "ok": True,
                         "observed": 1.9, "bound": 2.0, "detail": "x"}
        doc, _ = self._render(doctor, row)
        assert doc["e2e"][0]["weakest_stage"] == "serve"

    def test_breached_clause_and_broken_storm_are_critical(self, doctor):
        doc, text = self._render(doctor, self._row(
            slo_ok=False,
            slo=[{"slo": "final_window_auc", "ok": False,
                  "observed": 0.52, "bound": 0.75,
                  "detail": "final-window AUC 0.52 vs floor 0.75"}],
            auc_note="final-window AUC 0.52 is below the 0.75 anchor",
            storm_bitwise_journals=False, recovered_compiled=False,
            silent_drops=2))
        fixes = "\n".join(doc["e2e"][0]["fixes"])
        assert "SILENT drops" in fixes
        assert "did NOT resume bitwise" in fixes
        assert "never recovered to the compiled path" in fixes
        assert "SLO clause final_window_auc failed" in fixes
        assert "quality anchor did not clear" in fixes
        assert "CRITICAL" in text and "SLO BREACHED" in text

    def test_errored_row_renders_error(self, doctor):
        doc, text = self._render(doctor, {"error": "boom"})
        assert doc["e2e"][0]["error"] == "boom"
        assert "ERROR: boom" in text

    def test_bench_history_labels_e2e_row(self, history):
        assert history._display_name("serve_online_e2e") == \
            "serve_online_e2e (qps, whole-loop DAG)"
        import importlib
        bc = importlib.import_module("tools.bench_compare")
        assert bc._display_name("serve_online_e2e") == \
            history._display_name("serve_online_e2e")
