"""The fleet's one stage timer (``obs/profile.stage``) and the idle
attribution that reads its spans.

Every stats time key a solve bills comes from a stage that, under
``TW_PROFILE=1``, also opens its ``tw:fleet:<stage>`` profiler span; the
spans of one dispatch group share its ``group`` argument; with the knob
off no ``TraceAnnotation`` is built and the stats ledger is what it was.
``benchmark/idle_attribution.py`` splits the device's idle time among the
host labels by overlap: each idle instant to the innermost span then.

Synthetic items (no dataset), JAX on the CPU — tier-1.
"""

import gzip
import json
import os
import sys
import threading
from collections import defaultdict

import pytest

import jax

import traceweaver_tpu.algorithms.fleet as fleet_mod
from traceweaver_tpu.algorithms.fleet import solve_fleet
from traceweaver_tpu.obs import profile as obs_profile
from traceweaver_tpu.obs import selftrace

from test_pipeline import _mixed_items

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, idle_attribution, trace  # noqa: E402

pytestmark = pytest.mark.obs

#: the stages that bill each stats time key of a fused solve
STAGES_OF_KEY = {
    "plan_fit_s": {"plan-fit"},
    "pack_s": {"window-plan", "pack"},
    "dispatch_s": {"dispatch"},
    "wait_s": {"fetch", "flag-fetch"},
    "decode_s": {"decode"},
}
#: stages that run once per dispatch group
GROUP_STAGES = {"pack", "dispatch", "warm-dispatch", "flag-fetch",
                "redispatch", "fetch", "decode"}
#: the stats keys of a warm pipelined solve of ``_mixed_items`` (three
#: shape classes kept apart), as the fleet billed them before the stage
#: timer, and decode's count of contested out-spans
STEADY_KEYS = {
    "bytes_est_pallas", "bytes_est_xla", "compact_windows_redispatched",
    "compact_windows_total", "d2h_bytes_fetched", "d2h_bytes_flags",
    "d2h_flag_fetches", "decode_contested", "decode_s",
    "devcols_fallbacks", "dispatch_s",
    "fleet_dispatches", "fleet_dynamism_dispatches",
    "fleet_group_cost_max", "fleet_group_cost_total", "fleet_services",
    "flops_est", "fused_em_applied", "h2d_bytes_shipped",
    "kernel_xla_groups", "pack_s", "pipeline_depth", "pipeline_groups",
    "plan_fit_s", "wait_s",
}


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each span's
    name and arguments (from any thread)."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def __call__(self, name, **kwargs):
        recorder = self

        class _Span:
            def __enter__(self):
                with recorder._lock:
                    recorder.spans.append((name, kwargs))

            def __exit__(self, *exc):
                return False

        return _Span()

    def stages(self):
        """The fleet's stage spans, by stage name (the per-service
        solver's own ``tw:solve:*`` spans left out)."""
        prefix = fleet_mod.SPAN_PREFIX
        return [(name[len(prefix):], kw) for name, kw in self.spans
                if name.startswith(prefix)]


def _traced_solve(monkeypatch, pipeline="1", **kw):
    monkeypatch.setenv("TW_FLEET_MERGE", "0")
    monkeypatch.setenv("TW_PIPELINE", pipeline)
    monkeypatch.setenv("TW_PROFILE", "1")
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    stats = {}
    solve_fleet(_mixed_items(), stats=stats, **kw)
    return stats, rec.stages()


@pytest.mark.parametrize("pipeline", ["1", "0"])
def test_every_time_key_comes_with_its_span(monkeypatch, pipeline):
    stats, spans = _traced_solve(monkeypatch, pipeline)
    names = {name for name, _ in spans}
    time_keys = {k for k, v in stats.items()
                 if k.endswith("_s") and v > 0}
    assert time_keys == set(STAGES_OF_KEY)
    for key in time_keys:
        assert STAGES_OF_KEY[key] <= names, key
    # the program's root and its unbilled stages are named too
    assert {"solve", "setup", "group"} <= names
    assert sum(1 for name, _ in spans if name == "solve") == 1
    # one pack and one decode per dispatch group
    n_groups = int(stats["fleet_dispatches"])
    assert sum(1 for name, _ in spans if name == "pack") == n_groups
    assert sum(1 for name, _ in spans if name == "decode") == n_groups


def test_spans_of_one_group_share_its_group_argument(monkeypatch):
    stats, spans = _traced_solve(monkeypatch)
    args = defaultdict(set)
    stages = defaultdict(set)
    for name, kw in spans:
        if name in GROUP_STAGES:
            args[kw["group"]].add((kw["items"], kw["windows"], kw["spans"]))
            stages[kw["group"]].add(name)
    n_groups = int(stats["fleet_dispatches"])
    assert sorted(args) == list(range(n_groups))
    for g in args:
        assert len(args[g]) == 1, (g, args[g])
        assert {"pack", "dispatch", "decode"} <= stages[g]
    counts = [next(iter(a)) for a in args.values()]
    items = _mixed_items()
    assert sum(c[0] for c in counts) == len(items)
    assert sum(c[2] for c in counts) == sum(
        len(next(iter(it.in_span_partitions.values()))) for it in items)
    windows = 0
    for it in items:
        in_spans = sorted(next(iter(it.in_span_partitions.values())),
                          key=lambda sp: (sp.start_mus, sp.end_mus))
        windows += len(fleet_mod.perfect_cut_windows(
            in_spans, fleet_mod.DEFAULT_MAX_WINDOW))
    assert sum(c[1] for c in counts) == windows


@pytest.mark.parametrize("pipeline", ["1", "0"])
def test_profile_off_builds_no_annotation_and_keeps_the_ledger(
        monkeypatch, pipeline):
    monkeypatch.setenv("TW_FLEET_MERGE", "0")
    monkeypatch.setenv("TW_PIPELINE", pipeline)
    monkeypatch.delenv("TW_PROFILE", raising=False)
    solve_fleet(_mixed_items())  # compiles and device state, once

    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation built with TW_PROFILE off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    stats = {}
    solve_fleet(_mixed_items(), stats=stats)
    expected = STEADY_KEYS if pipeline == "1" else \
        STEADY_KEYS - {"pipeline_depth", "pipeline_groups"}
    assert set(stats) == expected
    assert stats["fleet_dispatches"] == 3.0
    if pipeline == "1":
        assert stats["pipeline_groups"] == 3.0


def test_budget_fallback_runs_under_its_span(monkeypatch):
    monkeypatch.setattr(fleet_mod, "FLEET_BUDGET_ELEMS", 1)
    stats, spans = _traced_solve(monkeypatch)
    names = [name for name, _ in spans]
    assert stats["fleet_fallback_budget"] == 3.0
    assert names.count("fallback") == 3
    assert "pack" not in names and "dispatch" not in names
    # the per-service solvers' own stage seconds merge under the span
    assert stats.get("decode_s", 0) > 0


class _St:
    span_prefix = fleet_mod.SPAN_PREFIX

    def __init__(self, profile=False):
        self.profile = profile
        self.billed = []

    def add(self, key, val=1.0):
        self.billed.append((key, val))


class _Clock:
    """``perf_counter`` that steps 0.5 s a read."""

    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        self.t += 0.5
        return self.t


def test_stage_bills_its_key_less_the_excluded_time(monkeypatch):
    monkeypatch.setattr(obs_profile, "time", _Clock())
    tracer = selftrace.PipelineTracer()
    prev = selftrace.install(tracer)
    try:
        st = _St()
        with obs_profile.stage(st, "flag-fetch", "wait_s", ["w1", "w2"],
                               trace_as="compact-fetch") as t:
            t.less = 0.2  # billed elsewhere
        assert t.seconds == pytest.approx(0.3)
        assert st.billed == [("wait_s", t.seconds)]
        with obs_profile.stage(st, "group"):  # no key: bills nothing
            pass
        assert len(st.billed) == 1
    finally:
        selftrace.install(prev)
    stamped = {key: [(name, round(t1 - t0)) for name, t0, t1
                     in win["stages"]]
               for key, win in tracer._windows.items()}
    # stamped under its self-trace name, 0.5 s long in microseconds
    assert stamped == {"w1": [("compact-fetch", 500000)],
                       "w2": [("compact-fetch", 500000)]}


def test_stage_that_raises_bills_nothing_and_closes_its_span(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    st = _St(profile=True)
    with pytest.raises(RuntimeError):
        with obs_profile.stage(st, "dispatch", "dispatch_s", group=4):
            raise RuntimeError("device fault")
    assert st.billed == []
    assert rec.spans == [("tw:fleet:dispatch", {"group": 4})]


def _recorded_events():
    path = os.path.join(ROOT, "benchmark", "tests", "data",
                        "alib-dense.round.events.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_idle_by_label_matches_the_trace_reduction():
    """On a recorded round, the split by overlap accounts for every idle
    ns the reduction counts, and names every label the reduction's
    midpoint rule names (a gap's midpoint lies in a piece of its own
    label)."""
    events = _recorded_events()
    red = trace.reduce_events(events, top=None)
    idle = idle_attribution.idle_by_label(events)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=0.01)
    for name, _ in red["idle_gaps"]:
        assert idle.get(name, 0.0) > 0, name
    assert max(idle, key=idle.get) == red["idle_gaps"][0][0]


MS = 1_000_000  # ns


def test_idle_readers_on_stage_spans_and_on_a_trace_without_them():
    """The readers on a round whose idle gaps fall under named stages,
    and on a recorded round of a program that had no such spans."""
    ops = [(t * MS, (t + 10) * MS, "fusion") for t in (10, 30, 50, 70)] \
        + [(90 * MS, 95 * MS, "fusion")]
    host = [(0, 100 * MS, trace.ROUND_SPAN),
            (0, 100 * MS, "tw:fleet:solve"),
            (0, 10 * MS, "tw:fleet:plan-fit"),
            (20 * MS, 30 * MS, "tw:fleet:window-plan"),
            (40 * MS, 50 * MS, "tw:fleet:pack"),
            (60 * MS, 70 * MS, "tw:fleet:decode")]
    events = dict(devices={"/device:TPU:0": dict(ops=ops)},
                  host={"spans": host})
    spec = harness.benchmark_spec(ROOT)
    names = ["idle_plan_fit_ms_per_kspan.batch",
             "idle_pack_ms_per_kspan.batch",
             "idle_decode_ms_per_kspan.batch", "idle_unnamed_pct.batch"]
    entries = [m for m in spec["per_layer"] if m["name"] in names]
    assert [m["name"] for m in entries] == names
    m = dict(trace=trace.reduce_events(events), trace_events=events,
             traced_spans=10000)
    got = {k: v["value"] for k, v in harness.read_metrics(entries, m).items()}
    # idle ms per 1000 of 10000 traced spans: a 10 ms gap under each
    # stage span
    assert got["idle_plan_fit_ms_per_kspan.batch"] == pytest.approx(1.0)
    assert got["idle_pack_ms_per_kspan.batch"] == pytest.approx(2.0)
    assert got["idle_decode_ms_per_kspan.batch"] == pytest.approx(1.0)
    # of 55 ms idle, [80, 90] and [95, 100] lie under the solve alone
    assert got["idle_unnamed_pct.batch"] == pytest.approx(100 * 15 / 55)

    old = _recorded_events()
    m = dict(trace=trace.reduce_events(old), trace_events=old,
             traced_spans=150000)
    assert harness.read_metrics(entries, m) == {}


def test_idle_gap_across_stages_is_split_by_overlap():
    """One idle gap from the end of a round's device work through decode
    and the next round's plan fit: each stage gets the part of the gap it
    covers, where the reduction's midpoint rule gives it whole to one."""
    ops = [(0, 10 * MS, "fusion"), (100 * MS, 110 * MS, "fusion")]
    host = [(0, 50 * MS, trace.ROUND_SPAN),
            (0, 45 * MS, "tw:fleet:solve"),
            (10 * MS, 40 * MS, "tw:fleet:decode"),
            (60 * MS, 110 * MS, trace.ROUND_SPAN),
            (60 * MS, 110 * MS, "tw:fleet:solve"),
            (60 * MS, 100 * MS, "tw:fleet:plan-fit")]
    events = dict(devices={"/device:TPU:0": dict(ops=ops)},
                  host={"spans": host})
    idle = idle_attribution.idle_by_label(events)
    assert idle == pytest.approx({
        "tw:fleet:decode": 0.030, "tw:fleet:solve": 0.005,
        trace.ROUND_SPAN: 0.005, "idle": 0.010,
        "tw:fleet:plan-fit": 0.040})
    assert dict(trace.reduce_events(events)["idle_gaps"]) == \
        pytest.approx({"idle": 0.090})
    m = dict(trace_events=events, traced_spans=1000)
    assert idle_attribution.unnamed_idle_pct(m) == pytest.approx(
        100 * 10 / 90)
