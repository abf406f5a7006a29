"""Guards for the driver-critical bench internals.

BENCH_r03 failed rc=124 and round 4 rebuilt bench.py around a hard
envelope; these tests pin the pieces a future edit could silently break:
the xplane profile parser's CPU fallback (the committed PROFILE artifact
depends on it) and the baseline child's recording-guided budget logic
(which decides how many fresh same-input exact pairs the driver's
accuracy delta gets).
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(monkeypatch):
    sys.path.insert(0, REPO)
    import bench as bench_mod

    return importlib.reload(bench_mod)


def test_baseline_child_carries_recording_for_over_alarm_services(
        bench, tmp_path, monkeypatch):
    """Budget logic: services whose recorded cost exceeds the alarm carry
    the recording (measured=false) instead of burning a guaranteed-alarm
    fresh attempt; cheap services are solved fresh; a stale recording
    (different subset size) must not gate anything."""
    import pickle

    monkeypatch.setenv("TW_BENCH_APPS", "hotel")
    monkeypatch.setenv("TW_BENCH_MAX_TRACES", "40")
    monkeypatch.setenv("TW_BENCH_SUBSET", "8")
    monkeypatch.setenv("TW_BENCH_BASELINE_BUDGET", "120")
    b = importlib.reload(bench)

    bundles = b.build_problems()
    bundle = tmp_path / "bundle.pkl"
    with open(bundle, "wb") as f:
        pickle.dump(bundles, f)

    # recording matching this config: frontend "too slow" for the 95s
    # alarm, search cheap — frontend must carry, search must run fresh
    rec = {
        "subset_spans": 8, "compress": b.COMPRESS,
        "services": {
            "hotel/frontend": {"finished": True, "seconds": 500.0,
                               "n_spans": 8, "accuracy": 0.875},
            "hotel/search": {"finished": True, "seconds": 0.5,
                             "n_spans": 8, "accuracy": 1.0},
        },
    }
    monkeypatch.setattr(b, "RECORDED_PATH", str(tmp_path / "rec.json"))
    with open(b.RECORDED_PATH, "w") as f:
        json.dump(rec, f)

    out = tmp_path / "baseline.json"
    b.run_baseline_child(str(bundle), str(out))
    with open(out) as f:
        report = json.load(f)
    sub = report["subset"]
    assert sub["hotel/frontend"]["measured"] is False  # carried
    assert sub["hotel/frontend"]["accuracy"] == 0.875
    assert sub["hotel/search"]["measured"] is True     # fresh
    assert report["n_fresh"] == 1 and report["n_recorded"] == 1

    # stale recording (wrong subset size): nothing carried, both fresh
    rec["subset_spans"] = 99
    with open(b.RECORDED_PATH, "w") as f:
        json.dump(rec, f)
    b.run_baseline_child(str(bundle), str(out))
    with open(out) as f:
        report2 = json.load(f)
    assert all(v["measured"] for v in report2["subset"].values())
    assert report2["n_recorded"] == 0


def test_baseline_child_skips_recorded_dnf_without_ample_budget(
        bench, tmp_path, monkeypatch):
    """A service the recording proves cannot finish (finished=false) must
    NOT get a benefit-of-the-doubt fresh attempt on a normal budget — the
    budget goes to unmeasured services instead (ADVICE r4). With an ample
    budget (> 2 alarms) the DNF service is retried."""
    import pickle

    monkeypatch.setenv("TW_BENCH_APPS", "hotel")
    monkeypatch.setenv("TW_BENCH_MAX_TRACES", "40")
    monkeypatch.setenv("TW_BENCH_SUBSET", "8")
    monkeypatch.setenv("TW_BENCH_BASELINE_BUDGET", "60")
    b = importlib.reload(bench)

    bundles = b.build_problems()
    bundle = tmp_path / "bundle.pkl"
    with open(bundle, "wb") as f:
        pickle.dump(bundles, f)

    rec = {
        "subset_spans": 8, "compress": b.COMPRESS,
        "services": {
            "hotel/frontend": {"finished": False, "seconds": 95.0,
                               "n_spans": 8, "accuracy": None},
            "hotel/search": {"finished": True, "seconds": 0.5,
                             "n_spans": 8, "accuracy": 1.0},
        },
    }
    monkeypatch.setattr(b, "RECORDED_PATH", str(tmp_path / "rec.json"))
    with open(b.RECORDED_PATH, "w") as f:
        json.dump(rec, f)

    out = tmp_path / "baseline.json"
    b.run_baseline_child(str(bundle), str(out))
    with open(out) as f:
        report = json.load(f)
    sub = report["subset"]
    # DNF carried (not retried), cheap service solved fresh
    assert sub["hotel/frontend"]["measured"] is False
    assert sub["hotel/frontend"]["finished"] is False
    assert sub["hotel/search"]["measured"] is True
    assert report["n_fresh"] == 1


def test_backend_label_flags_cpu_fallback(bench):
    """A CPU-solver report must surface as backend=cpu_fallback in the
    final JSON line so fallback numbers can never be mistaken for
    on-chip results (this bit the round-5 driver bench); real chip
    backends pass through unrelabeled."""
    assert bench.backend_label("cpu") == ("cpu_fallback", False)
    assert bench.backend_label(None) == ("cpu_fallback", False)
    assert bench.backend_label("tpu") == ("tpu", True)
    # only the TPU counts as the chip: any other platform name is
    # labeled off-chip
    assert bench.backend_label("gpu") == ("cpu_fallback", False)


@pytest.mark.aot
def test_aot_fields_summarizes_warmup_ledger(bench):
    """The AOT warmup report builder: runtime/aot.status() -> aot_*
    fields, with the miss ledger passed through verbatim and summed."""
    status = {"mode": "eager", "phase": "ready", "planned": 10,
              "compiled": 10, "compile_s": 2.7816,
              "misses": {"solve_windows_fleet[B=64,...]": 3.0,
                         "fit_gmm[e=8,n=128]": 1.0}}
    out = bench.aot_fields(status)
    assert out["aot_mode"] == "eager" and out["aot_phase"] == "ready"
    assert out["aot_lattice_size"] == 10
    assert out["aot_precompiled"] == 10
    assert out["aot_compile_s"] == 2.782
    assert out["aot_miss_count"] == 4
    assert out["aot_misses"]["fit_gmm[e=8,n=128]"] == 1.0
    # an empty status degrades to zeros, not a crash
    empty = bench.aot_fields({})
    assert empty["aot_lattice_size"] == 0
    assert empty["aot_miss_count"] == 0


@pytest.mark.aot
def test_coldstart_fields_targets_and_verdicts(bench):
    """The cold-start leg report builder: two child reports -> the
    cold_start_s/warm_start_s pair, the <5 s warm-restart verdict, the
    zero-solve-compile verdict, and the warm child's aot_* ledger."""
    cold = {"first_trace_s": 7.807, "warmup_s": 6.821,
            "fleet_backend_compiles": 0,
            "measured_compiles": {"backend_compiles": 0}}
    warm = {"first_trace_s": 3.785, "warmup_s": 2.837,
            "fleet_backend_compiles": 0,
            "measured_compiles": {"backend_compiles": 0},
            "aot": {"mode": "eager", "phase": "ready", "planned": 10,
                    "compiled": 10, "compile_s": 2.782, "misses": {}}}
    out = bench.coldstart_fields(cold, warm)
    assert out["cold_start_s"] == 7.807
    assert out["warm_start_s"] == 3.785
    assert out["coldstart_speedup"] == 2.06
    assert out["coldstart_warm_under_target"] is True
    assert out["coldstart_warm_zero_solve_compiles"] is True
    assert out["aot_lattice_size"] == 10 and out["aot_miss_count"] == 0

    # a slow warm restart or a compiling solve is flagged, not hidden
    slow = bench.coldstart_fields(
        cold, {**warm, "first_trace_s": 9.0, "fleet_backend_compiles": 2})
    assert slow["coldstart_warm_under_target"] is False
    assert slow["coldstart_warm_zero_solve_compiles"] is False
    # empty children degrade to None/False, not a crash
    empty = bench.coldstart_fields({}, {})
    assert empty["cold_start_s"] is None
    assert empty["coldstart_speedup"] is None
    assert empty["coldstart_warm_under_target"] is False


@pytest.mark.faults
def test_chaos_fields_ledger_and_delta(bench):
    """The chaos-leg report builder: fleet fault counters -> chaos_*
    ledger fields, accuracy fractions -> delta in POINTS against the
    ≤1 pt bar, dead-letter bytes passed through verbatim."""
    fault_stats = {"fault_retries": 7.0, "fault_bisections": 2.0,
                   "fault_xla_fallbacks": 1.0, "fault_host_fallbacks": 1.0,
                   "fault_quarantined": 1.0,
                   "fault_ladder": ["retry", "retry", "bisect"]}
    clean = {"hotel/frontend": 0.90, "hotel/search": 1.0}
    chaos = {"hotel/frontend": 0.90, "hotel/search": 0.99}
    out = bench.chaos_fields(fault_stats, clean, chaos, 123)
    assert out["chaos_retries"] == 7
    assert out["chaos_bisections"] == 2
    assert out["chaos_xla_fallbacks"] == 1
    assert out["chaos_host_fallbacks"] == 1
    assert out["chaos_quarantined"] == 1
    assert out["chaos_deadletter_bytes"] == 123
    # mean of (0, -1.0) pts
    assert out["chaos_accuracy_delta_pts"] == -0.5
    assert out["chaos_delta_exceeds_1pt"] is False

    # a quarantined-heavy run blows the bar -> flagged, not hidden
    bad = bench.chaos_fields({}, clean, {"hotel/frontend": 0.0,
                                         "hotel/search": 1.0}, 0)
    assert bad["chaos_delta_exceeds_1pt"] is True
    # empty accuracies degrade to None, not a crash
    empty = bench.chaos_fields({}, {}, {}, 0)
    assert empty["chaos_accuracy_delta_pts"] is None
    assert empty["chaos_delta_exceeds_1pt"] is False


@pytest.mark.precision
def test_bf16_delta_fields_per_dataset_and_warn_list(bench):
    """The bf16-vs-f32 accuracy delta aggregation: fraction accuracies
    keyed by service label -> per-dataset mean deltas in POINTS, with
    the >1 pt warn list naming datasets (apps), not services."""
    accs_f32 = {"hotel/frontend": 0.90, "hotel/search": 0.80,
                "media/compose": 0.95}
    accs_bf16 = {"hotel/frontend": 0.905, "hotel/search": 0.810,
                 "media/compose": 0.90}
    out = bench.bf16_delta_fields(accs_f32, accs_bf16)
    # hotel mean delta = (0.5 + 1.0)/2 = 0.75 pts; media = -5.0 pts
    assert out["accuracy_delta_vs_f32_per_dataset"] == {
        "hotel": 0.75, "media": -5.0}
    assert out["bf16_delta_exceeds_1pt"] == ["media"]
    # overall mean over services: (0.5 + 1.0 - 5.0) / 3
    assert out["accuracy_delta_vs_f32"] == round((0.5 + 1.0 - 5.0) / 3, 4)
    # empty input degrades to None / empty, not a crash
    empty = bench.bf16_delta_fields({}, {})
    assert empty["accuracy_delta_vs_f32"] is None
    assert empty["bf16_delta_exceeds_1pt"] == []


@pytest.mark.serve
def test_serve_fields_ledger_and_isolation_delta(bench):
    """The --serve-tenants leg's report builder: run summaries -> the
    serve_* field set the driver consumes, with the isolation metric as
    the healthy-tenant throughput delta (storm vs clean) in percent."""
    clean = dict(spans=4000, wall_s=4.0, healthy_spans=3000,
                 dispatches=3, shared_solves=2, tenant_batches=20,
                 shed_windows=1, per_tenant_min=10.0, per_tenant_max=90.0)
    storm = dict(spans=3800, wall_s=4.0, healthy_spans=2850,
                 quarantined_windows=5, deadletter_windows=5,
                 healthy_quarantined=0, healthy_shed=0,
                 faults_injected=17, spec="dispatch:0.5")
    out = bench.serve_fields(100, clean, storm)
    assert out["serve_tenants"] == 100
    assert out["serve_spans_total"] == 4000
    assert out["serve_spans_per_s"] == 1000.0
    assert out["serve_fleet_dispatches"] == 3
    assert out["serve_shared_solves"] == 2
    assert out["serve_tenant_batches"] == 20
    assert out["serve_shed_windows"] == 1
    assert out["serve_per_tenant_spans_per_s_min"] == 10.0
    assert out["serve_per_tenant_spans_per_s_max"] == 90.0
    assert out["serve_storm_spec"] == "dispatch:0.5"
    assert out["serve_storm_injected"] == 17
    assert out["serve_quarantined_windows"] == 5
    assert out["serve_deadletter_windows"] == 5
    assert out["serve_healthy_spans_per_s_clean"] == 750.0
    assert out["serve_healthy_spans_per_s_storm"] == 712.5
    assert out["serve_isolation_delta_pct"] == -5.0
    assert out["serve_only_faulty_tenant_accrues"] is True
    # a storm that taxes neighbors flips the isolation verdict
    bad = bench.serve_fields(
        100, clean, dict(storm, healthy_quarantined=2))
    assert bad["serve_only_faulty_tenant_accrues"] is False
    # empty/zero inputs degrade to None rates, never divide-by-zero
    empty = bench.serve_fields(0, {}, {})
    assert empty["serve_spans_per_s"] is None
    assert empty["serve_isolation_delta_pct"] is None


def test_continuous_fields_slo_and_throughput_verdicts(bench):
    """The --continuous leg's report builder: fixed-pump vs continuous
    run summaries -> the continuous_* field set, with the two headline
    verdicts (beats the fixed pump on spans/s; worst-tenant p99 inside
    the SLO) and the zero-steady-compiles flag."""
    fixed = dict(spans=4000, wall_s=4.0, p99_max_ms=900.0, dispatches=6)
    cont = dict(spans=4000, wall_s=2.0, p99_max_ms=750.0, dispatches=9,
                steady_compiles=0, h2d_bytes_ring=1234.0,
                h2d_bytes_index=5678.0,
                continuous=dict(dispatches=7, urgent_dispatches=2))
    out = bench.continuous_fields(100, 2000.0, fixed, cont)
    assert out["continuous_tenants"] == 100
    assert out["continuous_slo_p99_ms"] == 2000.0
    assert out["continuous_spans_per_s"] == 2000.0
    assert out["continuous_spans_per_s_fixed_pump"] == 1000.0
    assert out["continuous_speedup_vs_fixed_pct"] == 100.0
    assert out["continuous_beats_fixed_pump"] is True
    assert out["continuous_seal_emit_p99_ms_max"] == 750.0
    assert out["continuous_seal_emit_p99_ms_max_fixed"] == 900.0
    assert out["continuous_p99_within_slo"] is True
    assert out["continuous_dispatches"] == 7
    assert out["continuous_urgent_dispatches"] == 2
    assert out["continuous_steady_compiles"] == 0
    assert out["continuous_zero_steady_compiles"] is True
    assert out["continuous_h2d_bytes_ring"] == 1234.0
    assert out["continuous_h2d_bytes_index"] == 5678.0
    # an SLO breach and a recompiling steady state flip the verdicts
    slow = bench.continuous_fields(
        100, 2000.0, fixed,
        dict(cont, p99_max_ms=2500.0, steady_compiles=3))
    assert slow["continuous_p99_within_slo"] is False
    assert slow["continuous_zero_steady_compiles"] is False
    # empty/zero inputs degrade to None rates, never divide-by-zero
    empty = bench.continuous_fields(0, 2000.0, {}, {})
    assert empty["continuous_spans_per_s"] is None
    assert empty["continuous_speedup_vs_fixed_pct"] is None
    assert empty["continuous_p99_within_slo"] is None


def test_overlap_fields_ring_engagement_and_throughput_verdicts(bench):
    """The --serve-overlap leg's report builder: serial (inflight=1) vs
    ring run summaries -> the overlap_* field set, with the headline
    triple (beats serial on spans/s; measured overlap_pct > 0 with the
    ring actually engaged; worst-tenant p99 inside the SLO) and the
    zero-steady-compiles flag."""
    serial = dict(spans=3000, wall_s=3.0, p99_max_ms=900.0)
    ring = dict(spans=3000, wall_s=2.0, p99_max_ms=1100.0,
                steady_compiles=0,
                ring=dict(enabled=True, inflight_limit=2, outstanding=0,
                          submitted=12, completed=12, aborted=0,
                          overlap_pct=37.5))
    out = bench.overlap_fields(24, 2, 2000.0, serial, ring)
    assert out["overlap_tenants"] == 24
    assert out["overlap_inflight"] == 2
    assert out["overlap_spans_per_s"] == 1500.0
    assert out["overlap_spans_per_s_serial"] == 1000.0
    assert out["overlap_speedup_vs_serial_pct"] == 50.0
    assert out["overlap_beats_serial"] is True
    assert out["overlap_pct"] == 37.5
    assert out["overlap_ring_engaged"] is True
    assert out["overlap_tickets_submitted"] == 12
    assert out["overlap_tickets_completed"] == 12
    assert out["overlap_tickets_aborted"] == 0
    assert out["overlap_seal_emit_p99_ms_max"] == 1100.0
    assert out["overlap_seal_emit_p99_ms_max_serial"] == 900.0
    assert out["overlap_p99_within_slo"] is True
    assert out["overlap_zero_steady_compiles"] is True
    # a ring that never held two tickets at once is NOT engaged — and a
    # recompiling or SLO-breaching ring flips its verdicts
    idle = bench.overlap_fields(
        24, 2, 2000.0, serial,
        dict(ring, p99_max_ms=2500.0, steady_compiles=2,
             ring=dict(ring["ring"], overlap_pct=0.0)))
    assert idle["overlap_ring_engaged"] is False
    assert idle["overlap_p99_within_slo"] is False
    assert idle["overlap_zero_steady_compiles"] is False
    # empty/zero inputs degrade to None rates, never divide-by-zero
    empty = bench.overlap_fields(0, 1, 2000.0, {}, {})
    assert empty["overlap_spans_per_s"] is None
    assert empty["overlap_speedup_vs_serial_pct"] is None
    assert empty["overlap_p99_within_slo"] is None
    assert empty["overlap_ring_engaged"] is False


@pytest.mark.wal
def test_wal_fields_overhead_and_compile_verdicts(bench):
    """The --wal leg's report builder: per-sync-policy run summaries ->
    the wal_* field set, with the headline pair (batch policy's
    throughput overhead vs WAL-off <= 10%; zero steady compiles on
    every pass) and per-policy ack-latency passthrough."""
    passes = dict(
        off=dict(spans=3000, wall_s=3.0, ack_p50_ms=0.9, ack_p99_ms=5.0,
                 steady_compiles=0),
        batch=dict(spans=2910, wall_s=3.0, ack_p50_ms=2.1,
                   ack_p99_ms=10.0, steady_compiles=0, wal_appends=144),
        always=dict(spans=2700, wall_s=3.0, ack_p50_ms=4.2,
                    ack_p99_ms=14.0, steady_compiles=0, wal_appends=144),
    )
    out = bench.wal_fields(6, passes)
    assert out["wal_tenants"] == 6
    assert out["wal_off_spans_per_s"] == 1000.0
    assert out["wal_batch_spans_per_s"] == 970.0
    assert out["wal_batch_overhead_pct"] == 3.0
    assert out["wal_batch_within_overhead"] is True
    assert out["wal_batch_appends"] == 144
    assert "wal_off_appends" not in out  # no log to count when off
    assert out["wal_always_ack_p99_ms"] == 14.0
    assert out["wal_zero_steady_compiles"] is True
    # a batch pass pricier than the 10% budget, or any recompiling
    # pass, flips its verdict
    slow = bench.wal_fields(6, dict(
        passes, batch=dict(passes["batch"], spans=2500),
        always=dict(passes["always"], steady_compiles=2)))
    assert slow["wal_batch_overhead_pct"] > 10.0
    assert slow["wal_batch_within_overhead"] is False
    assert slow["wal_zero_steady_compiles"] is False
    # empty/zero inputs degrade to None rates, never divide-by-zero
    empty = bench.wal_fields(0, dict(off={}, batch={}, always={}))
    assert empty["wal_off_spans_per_s"] is None
    assert empty["wal_batch_overhead_pct"] is None
    assert empty["wal_batch_within_overhead"] is None


@pytest.mark.collector
def test_capture_fields_hardening_verdicts(bench):
    """The --capture leg's report builder: clean/skew/lossy run
    summaries -> the capture_* field set, with the three headline
    verdicts (skew corrected, churn tolerated, loss degrading
    gracefully) and the no-crash gate."""
    clean = dict(completed=True, spans=120, acc=100.0, loss={},
                 loss_rate=0.0, rekeyed=1, conf_mean=1.0)
    skewed = dict(completed=True, acc=99.5, skew_detected_us=-251000.0)
    lossy = dict(completed=True, acc=70.0,
                 loss={"dropped_chunk": 40, "half_open": 6},
                 loss_rate=0.25, conf_mean=0.75, conf_discount=0.75)
    out = bench.capture_fields(clean, skewed, lossy, 250000.0)
    assert out["capture_acc_clean"] == 100.0
    assert out["capture_skew_acc_delta_pts"] == 0.5
    assert out["capture_skew_detected_us"] == -251000.0
    assert out["capture_skew_corrected_ok"] is True
    assert out["capture_rekeyed_streams"] == 1
    assert out["capture_churn_tolerated"] is True
    assert out["capture_loss_counters"] == {"dropped_chunk": 40,
                                            "half_open": 6}
    assert out["capture_loss_counted"] is True
    assert out["capture_conf_discounted"] is True
    assert out["capture_no_crash"] is True
    assert out["capture_graceful"] is True

    # a skew fit off by >20% of the injection flips the correction flag
    bad_fit = bench.capture_fields(
        clean, dict(skewed, skew_detected_us=-100000.0), lossy, 250000.0)
    assert bad_fit["capture_skew_corrected_ok"] is False
    # a skew-leg accuracy collapse flips it too (fit alone isn't enough)
    bad_acc = bench.capture_fields(
        clean, dict(skewed, acc=40.0), lossy, 250000.0)
    assert bad_acc["capture_skew_corrected_ok"] is False
    # undiscounted confidence under loss = silent wrong traces -> not
    # graceful
    silent = bench.capture_fields(
        clean, skewed, dict(lossy, conf_discount=1.0, conf_mean=1.0),
        250000.0)
    assert silent["capture_conf_discounted"] is False
    assert silent["capture_graceful"] is False
    # a crashed leg fails the no-crash gate, never hides
    crashed = bench.capture_fields(
        clean, skewed, dict(completed=False, error="Boom"), 250000.0)
    assert crashed["capture_no_crash"] is False
    assert crashed["capture_graceful"] is False
    # empty summaries degrade to None/False, not exceptions
    empty = bench.capture_fields({}, {}, {}, 0.0)
    assert empty["capture_acc_clean"] is None
    assert empty["capture_skew_corrected_ok"] is False


def test_ingest_fields_ledger_and_ratio(bench):
    """The --ingest-only leg's report builder: pack timings under both
    TW_COLUMNAR settings -> the pack_* field set (spans/s, s/window, and
    the columnar-vs-object speedup the >=10x acceptance bar reads)."""
    out = bench.ingest_fields(100_000, 500, col_s=0.05, obj_s=1.0)
    assert out["ingest_spans"] == 100_000
    assert out["ingest_windows"] == 500
    assert out["pack_spans_per_s"] == 2_000_000.0
    assert out["pack_s_per_window"] == 0.0001
    assert out["pack_spans_per_s_object"] == 100_000.0
    assert out["pack_columnar_speedup"] == 20.0
    # empty/zero inputs degrade to None, never divide-by-zero
    empty = bench.ingest_fields(0, 0, 0.0, 0.0)
    assert empty["pack_spans_per_s"] is None
    assert empty["pack_s_per_window"] is None
    assert empty["pack_columnar_speedup"] is None


def test_wire_fields_ledger_and_speedups(bench):
    """The --wire-ingest leg's report builder: payload->store parse
    timings under the native, pure-Python, and object front ends -> the
    wire_* field set (the r18 >=5x acceptance bar reads wire_speedup)."""
    out = bench.wire_fields(100_000, 20_000, wire_s=0.5, python_s=2.0,
                            obj_s=2.5)
    assert out["wire_spans"] == 100_000
    assert out["wire_traces"] == 20_000
    assert out["wire_spans_per_s"] == 200_000.0
    assert out["wire_spans_per_s_python"] == 50_000.0
    assert out["wire_spans_per_s_object"] == 40_000.0
    assert out["wire_speedup"] == 5.0
    assert out["wire_speedup_python"] == 1.25
    # empty/zero inputs degrade to None, never divide-by-zero
    empty = bench.wire_fields(0, 0, 0.0, 0.0, 0.0)
    assert empty["wire_spans_per_s"] is None
    assert empty["wire_speedup"] is None
    assert empty["wire_speedup_python"] is None


def test_ingest_leg_small_run_parity_and_fields(bench, monkeypatch):
    """A tiny end-to-end --ingest-only run: both paths pack byte-identical
    blocks and every ledger field lands in the report."""
    report = bench.run_ingest_leg(2000)
    assert report["mode"] == "ingest"
    assert report["pack_parity_ok"] is True
    assert report["ingest_spans"] >= 1900
    assert report["ingest_windows"] > 0
    assert report["pack_spans_per_s"] > 0
    assert report["pack_spans_per_s_object"] > 0
    assert report["pack_columnar_speedup"] > 0


def test_telemetry_fields_agreement_and_mismatch(bench):
    """The obs-registry agreement proof: fleet ledger counter deltas ==
    the legacy stage-stats dict; gauge-mirrored high-water marks are
    excluded (read from the snapshot, not hardcoded); a counter the
    registry never saw is a NAMED mismatch."""
    snap0 = {'tw_fleet_ledger_total{key="wait_s"}': 1.0,
             'tw_fleet_ledger_total{key="fleet_dispatches"}': 3.0}
    snap1 = {'tw_fleet_ledger_total{key="wait_s"}': 1.5,
             'tw_fleet_ledger_total{key="fleet_dispatches"}': 5.0,
             'tw_fleet_gauge{key="pipeline_depth"}': 4.0}
    stats = {"wait_s": 0.5, "fleet_dispatches": 2.0,
             "pipeline_depth": 4.0,          # gauge key: excluded
             "fault_ladder": ["retry"]}      # list-valued: excluded
    out = bench.telemetry_fields(stats, snap0, snap1)
    assert out["telemetry_matches_legacy"] is True
    assert out["telemetry_mismatch_keys"] == []
    assert out["telemetry_snapshot"] == {"fleet_dispatches": 2.0,
                                         "wait_s": 0.5}

    rogue = dict(stats, rogue_counter=1.0)
    out2 = bench.telemetry_fields(rogue, snap0, snap1)
    assert out2["telemetry_matches_legacy"] is False
    assert out2["telemetry_mismatch_keys"] == ["rogue_counter"]


@pytest.mark.quality
def test_confidence_fields_summary(bench, monkeypatch):
    """The quality-ledger report builder: per-item confidence maps ->
    population/mean/min, the TW_CONF_LOW low share, and the OT-override
    share; empty input degrades to None fields, not a crash."""
    monkeypatch.setenv("TW_CONF_LOW", "0.5")
    maps = [
        {("t", "a"): {"conf": 1.0, "not_best": False},
         ("t", "b"): {"conf": 0.5, "not_best": True}},
        None,  # a quarantined/None slot must not crash the summary
        {("t", "c"): {"conf": 0.25, "not_best": True}},
    ]
    out = bench.confidence_fields(maps)
    assert out["conf_spans"] == 3
    assert out["conf_mean"] == pytest.approx((1.0 + 0.5 + 0.25) / 3,
                                             abs=1e-4)
    assert out["conf_min"] == 0.25
    assert out["conf_low_frac"] == pytest.approx(2 / 3, abs=1e-4)
    assert out["conf_overridden_frac"] == pytest.approx(2 / 3, abs=1e-4)

    empty = bench.confidence_fields([])
    assert empty["conf_spans"] == 0
    assert empty["conf_mean"] is None
    assert empty["conf_low_frac"] is None


@pytest.mark.quality
def test_scorecard_fields_regimes_and_calibration_flags(bench):
    """The scorecard-leg report builder: per-regime matrix passthrough,
    TPU-minus-best-baseline deltas, and BOTH calibration verdicts (the
    noise-aware monotone flag and the crude top-vs-bottom check)."""
    card = {
        "per_regime": {
            "sequential": {"fcfs": 1.0, "weaver_tpu": 1.0},
            "fanout": {"fcfs": 0.1, "wap5": 0.0, "weaver_tpu": 0.3},
        },
        "weaver_exact_subset_spans": 12,
        "calibration": [
            {"decile": 1, "conf_lo": 0.2, "conf_hi": 0.5, "n": 20,
             "accuracy": 0.2},
            {"decile": 2, "conf_lo": 0.5, "conf_hi": 1.0, "n": 20,
             "accuracy": 0.9},
        ],
        "calibration_monotone_ok": True,
        "calibration_violations": [],
    }
    out = bench.scorecard_fields(card)
    assert out["scorecard_regimes"] == card["per_regime"]
    assert out["scorecard_tpu_minus_best_baseline"] == {
        "sequential": 0.0, "fanout": 0.2}
    assert out["scorecard_exact_subset_spans"] == 12
    assert out["scorecard_calibration_monotone_ok"] is True
    assert out["scorecard_top_vs_bottom_ok"] is True
    assert out["scorecard_calibration_violations"] == []

    # an inverted table flags BOTH verdicts (warn surface, not a crash)
    inv = dict(card, calibration=list(reversed(card["calibration"])),
               calibration_monotone_ok=False,
               calibration_violations=["decile 2 ..."])
    out2 = bench.scorecard_fields(inv)
    assert out2["scorecard_calibration_monotone_ok"] is False
    assert out2["scorecard_top_vs_bottom_ok"] is False
    assert out2["scorecard_calibration_violations"] == ["decile 2 ..."]

    # degenerate cards (no calibration rows) stay well-formed
    bare = bench.scorecard_fields({"per_regime": {}, "calibration": []})
    assert bare["scorecard_top_vs_bottom_ok"] is None
    assert bare["scorecard_tpu_minus_best_baseline"] == {}


@pytest.mark.campaign
def test_campaign_fields_flatten_artifact_headlines(bench):
    """The campaign-leg report builder: per-rung sustained spans/s,
    the steady zero-compile gate, the accuracy floor, and the
    multislice agreement flag — flattened from one CAMPAIGN_* artifact
    (docs/CAMPAIGN.md)."""
    art = dict(
        name="mini",
        plan=dict(devices=2, slices=2),
        rungs=[
            dict(rung="a",
                 manifest=dict(spans=1000),
                 steady=dict(spans_per_s=2500.0, backend_compiles=0,
                             aot_misses=[], quarantined=0),
                 accuracy=dict(e2e_pct=100.0),
                 multislice=dict(agree=True)),
            dict(rung="b",
                 manifest=dict(spans=3000),
                 steady=dict(spans_per_s=4000.0, backend_compiles=2,
                             aot_misses=["solve_windows_fleet[B=64]"],
                             quarantined=1),
                 accuracy=dict(e2e_pct=98.5),
                 multislice=None),
        ])
    out = bench.campaign_fields(art)
    assert out["campaign_rungs"] == 2
    assert out["campaign_devices"] == 2
    assert out["campaign_spans_total"] == 4000
    assert out["campaign_spans_per_s"] == {"a": 2500.0, "b": 4000.0}
    assert out["campaign_accuracy_e2e_min"] == 98.5
    assert out["campaign_steady_compiles"] == 2
    assert out["campaign_aot_misses"] == 1
    assert out["campaign_quarantined"] == 1
    assert out["campaign_multislice_agree"] is True
    # empty artifact degrades to counts, not a crash
    empty = bench.campaign_fields(dict(name="x", plan={}, rungs=[]))
    assert empty["campaign_rungs"] == 0
    assert empty["campaign_accuracy_e2e_min"] is None


def test_device_peaks_come_from_the_table_or_raise(bench):
    """Utilization denominators are a chip's published peaks, keyed by
    device_kind; a device missing from the table is an error, never a
    default."""
    v5e = bench.device_peaks("TPU v5 lite")
    assert v5e == dict(flops=197e12, hbm_bytes_s=819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        bench.device_peaks("cpu")
