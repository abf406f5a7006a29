"""The trace reduction and the metric readers on fixed inputs.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trace  # noqa: E402

MS = 1_000_000  # ns


def _events():
    """Two rounds from 0 to 100 ms; ops overlap and nest; one kernel."""
    ops = [(10 * MS, 30 * MS, "while"),
           (20 * MS, 25 * MS, "fusion"),   # nested in the while
           (28 * MS, 40 * MS, "sinkhorn_log_pallas"),
           (70 * MS, 80 * MS, "while"),
           (95 * MS, 120 * MS, "copy")]  # past the end
    host = [(0, 50 * MS, trace.ROUND_SPAN),
            (50 * MS, 100 * MS, trace.ROUND_SPAN),
            (40 * MS, 70 * MS, "tw:fleet:dispatch"),
            (130 * MS, 140 * MS, "tw:fleet:decode")]
    return dict(devices={"/device:TPU:0": dict(ops=ops)},
                host={"spans": host})


def test_busy_is_the_union_of_ops_inside_the_rounds():
    red = trace.reduce_events(_events())
    assert red["window_s"] == pytest.approx(0.100)
    # [10, 40] + [70, 80] + [95, 100]
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["rounds"] == 2 and red["chips"] == 1
    gaps = dict(red["idle_gaps"])
    # [0, 10] and [80, 95] under a round only, [40, 70] under the dispatch
    assert gaps["tw:fleet:dispatch"] == pytest.approx(0.030)
    assert gaps[trace.ROUND_SPAN] == pytest.approx(0.025)
    ops = dict(red["device_ops"])
    # self time: the while less its nested fusion and the overlapping
    # kernel's first 2 ms, plus the second while
    assert ops["while"] == pytest.approx(0.013 + 0.010)
    assert ops["fusion"] == pytest.approx(0.005)
    assert ops["copy"] == pytest.approx(0.005)


def test_op_label_drops_the_instruction_suffix():
    assert trace.op_label("%sinkhorn_log_pallas.7 = f32[2,8] custom-call("
                          "f32[2,8] %x), custom_call_target=\"tpu\"") == \
        "sinkhorn_log_pallas"
    assert trace.op_label("%while.331 = (f32[4]) while(...)") == "while"
    assert trace.op_label("jit_ring_append(1031)") == "jit_ring_append(1031)"


def test_selected_time_and_readers():
    events = _events()
    assert trace.selected_s(
        events, lambda label: label == "sinkhorn_log_pallas") == \
        pytest.approx(0.012)
    red = trace.reduce_events(events)
    m = dict(trace=red, trace_events=events, traced_spans=20000,
             spans_done=40000, window_s=2.0, setup_s=30.0,
             stats=dict(pack_s=0.2, decode_s=0.1, plan_fit_s=0.1,
                        fleet_dispatches=8.0),
             grade_last=dict(trace_accuracy_pct=99.5))
    spec = harness.benchmark_spec(ROOT)
    got = harness.read_metrics(spec["end_to_end"] + spec["per_layer"], m)
    assert got["recon_spans_per_s"]["value"] == pytest.approx(20000.0)
    assert got["setup_s"]["value"] == 30.0
    assert got["trace_accuracy_pct"]["value"] == 99.5
    assert got["fleet_host_ms_per_kspan.batch"]["value"] == pytest.approx(10.0)
    assert got["dispatches_per_kspan.batch"]["value"] == pytest.approx(0.2)
    assert got["device_busy_ms_per_kspan.batch"]["value"] == \
        pytest.approx(45.0 / 20.0)
    assert got["device_idle_pct.batch"]["value"] == pytest.approx(55.0)


def test_no_tpu_plane_is_an_error(tmp_path):
    jax = pytest.importorskip("jax")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.ROUND_SPAN):
            jax.numpy.ones(8).sum().block_until_ready()
    with pytest.raises(ValueError, match="no /device:TPU plane"):
        trace.load_events(trace.latest_xplane(str(tmp_path)))


def test_recorded_chip_round():
    """One traced round of an earlier call-graph cell on a TPU v5e, whose
    dense windows ran the Pallas Sinkhorn kernel (the device ops inside
    the round and the host spans, as ``load_events`` read them)."""
    import gzip
    import json

    path = os.path.join(ROOT, "benchmark", "tests", "data",
                        "alib-dense.round.events.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    red = trace.reduce_events(events)
    assert red["rounds"] == 1 and red["chips"] == 1
    assert red["window_s"] == pytest.approx(2.148970134)
    assert red["busy_s"] == pytest.approx(0.330829327)
    ops = dict(red["device_ops"])
    assert ops["sinkhorn_log_pallas"] == pytest.approx(0.013450594)
    # self times never add up to more than the busy union
    assert sum(ops.values()) <= red["busy_s"] + 1e-9
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert trace.selected_s(
        events, lambda label: label == "sinkhorn_log_pallas") == \
        pytest.approx(0.013450594)
