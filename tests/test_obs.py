"""Telemetry subsystem tests (ISSUE 9, tier-1, CPU).

Contracts covered:

- typed registry semantics: counter monotonicity, label-schema
  conflicts raise, gauge set-max, histogram buckets, thread safety of
  concurrent increments, snapshot key format;
- Prometheus text exposition (families, label escaping, histogram
  sample expansion) and the stdlib sidecar exporter end-to-end;
- the fleet ledger mirror: a real solve's registry counter deltas
  equal its legacy stats dict field-for-field (the bench
  ``telemetry_snapshot`` agreement, proven live here);
- structured event sink: fault-ladder rungs land as JSONL records next
  to the in-dict ordered list, `cli events` tails both formats;
- SELF-TRACE ROUND TRIP (the acceptance path): a solve's own emitted
  Jaeger-JSON pipeline spans parse through ingest/jaeger.py, satisfy
  parent⊇child containment, and a fix=6 serve tenant reconstructs the
  pipeline's trace WITH THE SOLVER ITSELF — every journey span
  recovered, delay-culprit query answerable over the pipeline's own
  telemetry;
- TW_PROFILE hooks are inert by default and harmless on CPU.
"""

import json
import threading
import time
import urllib.request

import pytest

import jax

# break the ingest<->runtime import cycle regardless of collection order
# (the serve import below otherwise depends on an earlier test module
# having initialized traceweaver_tpu.runtime first)
import traceweaver_tpu.runtime  # noqa: F401  — must precede serve

from traceweaver_tpu.obs import events as obs_events
from traceweaver_tpu.obs import selftrace
from traceweaver_tpu.obs.exposition import render_metrics, start_metrics_server
from traceweaver_tpu.obs.registry import (
    MetricError,
    MetricsRegistry,
    get_registry,
)
from traceweaver_tpu.serve import ServeConfig, TenantService

jax.config.update("jax_platforms", "cpu")

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# corpus helpers (the serve test fixture's shape: frontend -> search)
# ---------------------------------------------------------------------------

def hotel_trace(i, prefix="t", base_us=1_000_000.0, spacing_us=10_000.0):
    T = base_us + i * spacing_us
    tid = f"{prefix}{i:03d}"

    def span(sid, start, dur, op, refs, pid, kind):
        return dict(traceID=tid, spanID=sid, startTime=start, duration=dur,
                    operationName=op,
                    references=[{"traceID": tid, "spanID": r} for r in refs],
                    processID=pid,
                    tags=[{"key": "span.kind", "value": kind}])

    spans = [
        span("root", T, 1500.0, "HTTP GET /hotels", [], "p1", "server"),
        span("c1", T + 200, 1100.0, "call-search", ["root"], "p1", "client"),
        span("s1", T + 300, 600.0, "search", ["c1"], "p2", "server"),
    ]
    return dict(traceID=tid, spans=spans,
                processes=dict(p1={"serviceName": "frontend"},
                               p2={"serviceName": "search"}))


def hotel_payload(n_traces=24, **kw):
    return {"data": [hotel_trace(i, **kw) for i in range(n_traces)]}


def _cfg(**kw):
    base = dict(fix=2, window_us=60e6, overlap_us=5e6, ooo_bound_us=1e6,
                verbose=False, pump_windows=10**9)
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture()
def tracer():
    tr = selftrace.PipelineTracer()
    prev = selftrace.install(tr)
    yield tr
    selftrace.install(prev)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("tw_test_total", "t", labels=("key",))
    c.inc(key="a")
    c.inc(2.5, key="a")
    c.inc(key="b")
    snap = reg.snapshot()
    assert snap['tw_test_total{key="a"}'] == 3.5
    assert snap['tw_test_total{key="b"}'] == 1.0
    with pytest.raises(MetricError):
        c.inc(-1.0, key="a")  # counters are monotonic
    with pytest.raises(MetricError):
        c.inc(1.0, wrong="a")  # label schema enforced

    g = reg.gauge("tw_test_gauge", labels=("key",))
    g.set(5.0, key="depth")
    g.set_max(3.0, key="depth")  # set-if-greater: no-op
    g.set_max(9.0, key="depth")
    assert reg.snapshot()['tw_test_gauge{key="depth"}'] == 9.0

    h = reg.histogram("tw_test_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap['tw_test_seconds_bucket{le="0.1"}'] == 1.0
    assert snap['tw_test_seconds_bucket{le="1"}'] == 2.0
    assert snap['tw_test_seconds_bucket{le="+Inf"}'] == 3.0
    assert snap["tw_test_seconds_count"] == 3.0
    assert snap["tw_test_seconds_sum"] == pytest.approx(5.55)


def test_redeclaration_same_schema_ok_conflict_raises():
    reg = MetricsRegistry()
    a = reg.counter("tw_x_total", labels=("k",))
    assert reg.counter("tw_x_total", labels=("k",)) is a  # idempotent
    with pytest.raises(MetricError):
        reg.counter("tw_x_total", labels=("other",))  # label fork
    with pytest.raises(MetricError):
        reg.gauge("tw_x_total", labels=("k",))  # kind fork
    with pytest.raises(MetricError):
        reg.counter("bad name")


def test_concurrent_increments_never_drop():
    reg = MetricsRegistry()
    c = reg.counter("tw_race_total", labels=("key",))

    def spin():
        for _ in range(2000):
            c.inc(key="x")

    threads = [threading.Thread(target=spin) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.snapshot()['tw_race_total{key="x"}'] == 16000.0


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

def test_render_metrics_format_and_escaping():
    reg = MetricsRegistry()
    c = reg.counter("tw_fmt_total", "help text", labels=("svc",))
    c.inc(2, svc='we"ird\nname')
    reg.register_collector("extra", lambda: [
        ("tw_collected", "gauge", "from a collector",
         [({"kind": "x"}, 1.5)])])
    text = render_metrics(reg)
    assert "# HELP tw_fmt_total help text" in text
    assert "# TYPE tw_fmt_total counter" in text
    assert 'tw_fmt_total{svc="we\\"ird\\nname"} 2' in text
    assert "# TYPE tw_collected gauge" in text
    assert 'tw_collected{kind="x"} 1.5' in text


def test_sidecar_exporter_scrapes_over_http():
    reg = MetricsRegistry()
    reg.counter("tw_sidecar_total").inc(3)
    exporter = start_metrics_server(0, registry=reg)
    try:
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert "tw_sidecar_total 3" in body
    finally:
        exporter.shutdown()
        exporter.server_close()


# ---------------------------------------------------------------------------
# the fleet ledger mirror: live agreement with the legacy stats dict
# ---------------------------------------------------------------------------

def test_registry_deltas_match_fleet_stats_dict_on_a_real_solve():
    """The mirror is real: a solve's tw_fleet_ledger_total deltas equal
    its _Stats dict for every scalar counter key (gauge-mirrored
    high-water marks excluded) — the live form of the bench
    telemetry_snapshot agreement field."""
    reg = get_registry()
    before = reg.snapshot()
    svc = TenantService(_cfg())
    svc.ingest("agree", hotel_payload())
    svc.flush()
    after = reg.snapshot()

    gauge_keys = {k.split('"')[1] for k in after
                  if k.startswith("tw_fleet_gauge{")}
    deltas = {}
    for name, val in after.items():
        if name.startswith("tw_fleet_ledger_total{"):
            d = val - before.get(name, 0.0)
            if d:
                deltas[name.split('"')[1]] = d
    legacy = {k: float(v) for k, v in svc.fleet_stats.items()
              if isinstance(v, (int, float)) and k not in gauge_keys}
    assert legacy, "solve produced no scalar ledger"
    for k, v in legacy.items():
        assert deltas.get(k, 0.0) == pytest.approx(v, rel=1e-6), k
    # nothing moved in the registry that the dict does not explain
    assert set(deltas) == {k for k, v in legacy.items() if v != 0}


def test_fault_ladder_counter_and_event_sink(tmp_path, monkeypatch):
    """A dispatch fault storm: ladder rungs land in the labelled
    registry counter AND as structured JSONL records (the dict's
    ordered fault_ladder list is unchanged); `cli events` tails them."""
    monkeypatch.setenv("TW_RETRY_BACKOFF_S", "0")
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"))
    prev = obs_events.install(log)
    reg = get_registry()
    before = reg.snapshot()
    try:
        svc = TenantService(_cfg())
        svc.tenant("storm").fault_spec = "dispatch:1.0,host:1.0"
        svc.ingest("storm", hotel_payload())
        svc.flush()
    finally:
        obs_events.install(prev)
    st = svc.stats("storm")
    assert st["faults"]["quarantined"] > 0
    after = reg.snapshot()
    key = 'tw_fault_ladder_events_total{key="fault_ladder",rung="quarantine"}'
    assert after.get(key, 0.0) > before.get(key, 0.0)

    recs = [json.loads(line) for line in
            open(log.path, encoding="utf-8")]
    kinds = {r["kind"] for r in recs}
    assert "fault_ladder" in kinds
    assert "fault_injected" in kinds  # runtime/faults.py emits too
    rungs = [r["event"] for r in recs if r["kind"] == "fault_ladder"]
    assert "quarantine" in rungs
    assert all("ts" in r for r in recs)

    # the tail subcommand reads the sink (and dead-letter format alike)
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = obs_events.tail_main([log.path, "-n", "0"])
    assert rc == 0
    text = out.getvalue()
    assert "fault_ladder/quarantine" in text
    assert "fault_injected/dispatch" in text


def test_events_truncate_splices_like_the_deadletter_sink(tmp_path):
    log = obs_events.EventLog(str(tmp_path / "ev.jsonl"))
    log.emit("k", "one")
    offset = log.offset
    log.emit("k", "two")
    log.truncate(offset)
    log.emit("k", "three")
    log.close()
    events = [json.loads(line)["event"]
              for line in open(log.path, encoding="utf-8")]
    assert events == ["one", "three"]


def test_events_tail_kind_filter(tmp_path):
    """--kind shows only matching records; dead-letter-shaped records
    (no kind field) are filtered out rather than crashing the filter."""
    import contextlib
    import io

    log = obs_events.EventLog(str(tmp_path / "kinds.jsonl"))
    log.emit("fault_ladder", "retry")
    log.emit("confidence_drift", "shift", key="svc", psi=0.41)
    log.emit("fault_ladder", "bisect")
    with open(log.path, "a", encoding="utf-8") as f:  # dead-letter shape
        f.write(json.dumps({"window": 3, "reason": "quarantined"}) + "\n")
    log.close()

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = obs_events.tail_main([log.path, "-n", "0",
                                   "--kind", "fault_ladder"])
    assert rc == 0
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    assert len(lines) == 2
    assert "fault_ladder/retry" in lines[0]
    assert "fault_ladder/bisect" in lines[1]
    assert all("confidence_drift" not in ln and "deadletter" not in ln
               for ln in lines)
    # -n bounds the non-follow read too
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert obs_events.tail_main([log.path, "-n", "1"]) == 0
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    assert len(lines) == 1 and "deadletter" in lines[0]


class _TailProc:
    """A `cli events --follow` subprocess with line-buffered capture
    (the events path imports no JAX, so startup is fast)."""

    def __init__(self, path, *extra):
        import subprocess
        import sys

        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "traceweaver_tpu.runtime.cli",
             "events", path, "-n", "0", "--follow", *extra],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        self.lines = []
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self):
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))

    def wait_for(self, needle, timeout=10.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if any(needle in ln for ln in self.lines):
                return True
            time.sleep(0.05)
        return False

    def stop(self):
        import signal as _signal

        self.proc.send_signal(_signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=10)
        except Exception:
            self.proc.kill()
            rc = self.proc.wait()
        self._thread.join(timeout=5)
        return rc


def test_events_tail_follow_sees_new_records(tmp_path):
    log = obs_events.EventLog(str(tmp_path / "follow.jsonl"))
    log.emit("k", "pre-existing")
    tail = _TailProc(log.path)
    try:
        assert tail.wait_for("k/pre-existing")
        log.emit("k", "arrived-live")
        assert tail.wait_for("k/arrived-live")
        # --kind filtering applies live too
        log.emit("other", "filtered")
        log.emit("k", "kept")
        assert tail.wait_for("k/kept")
        assert not any("other/filtered" in ln for ln in tail.lines) \
            or True  # no --kind on this proc: both pass; filter below
    finally:
        rc = tail.stop()
    log.close()
    assert rc == 0  # SIGINT exits the follow loop cleanly


def test_events_tail_follow_survives_truncate_splice(tmp_path):
    """The checkpoint/resume splice mid-follow: the sink truncates back
    to a recorded offset and re-appends. The follower must pick up the
    re-emitted records from the splice point instead of blocking forever
    at its stale (now past-EOF) offset."""
    log = obs_events.EventLog(str(tmp_path / "splice.jsonl"))
    log.emit("k", "one")
    offset = log.offset
    log.emit("k", "two")
    tail = _TailProc(log.path, "--kind", "k")
    try:
        assert tail.wait_for("k/two")
        log.truncate(offset)          # rewind past the follower's offset
        log.emit("k", "respliced")    # the re-emitted record
        assert tail.wait_for("k/respliced"), (
            "follower stuck at a stale offset after truncate")
        # the live --kind filter held throughout
        log.emit("noise", "skipme")
        log.emit("k", "after")
        assert tail.wait_for("k/after")
        assert not any("noise/skipme" in ln for ln in tail.lines)
    finally:
        rc = tail.stop()
    log.close()
    assert rc == 0


# ---------------------------------------------------------------------------
# self-trace round trip (the acceptance path)
# ---------------------------------------------------------------------------

def _containment_ok(trace_json):
    spans = {s["spanID"]: s for s in trace_json["spans"]}
    for s in trace_json["spans"]:
        for ref in s["references"]:
            p = spans[ref["spanID"]]
            if not (p["startTime"] <= s["startTime"]
                    and p["startTime"] + p["duration"]
                    >= s["startTime"] + s["duration"]):
                return False
    return True


def test_selftrace_roundtrip_solver_reconstructs_own_pipeline(tracer):
    """THE acceptance round trip: a solve's own emitted Jaeger-JSON
    pipeline spans (window journey: ingest → seal → pack → dispatch →
    ... → emit, trace context carried through the pack thread and
    decode workers) are ingested through ingest/jaeger.py (fix=6) and
    reconstructed BY THE SOLVER — every journey span recovered into one
    well-formed trace, and the delay-culprit query answers over the
    pipeline's own telemetry."""
    svc = TenantService(_cfg())
    svc.ingest("alpha", hotel_payload())
    svc.flush()
    assert len(tracer) == 1  # one window journeyed

    payload = tracer.payload()
    assert len(payload["data"]) == 1
    trace_json = payload["data"][0]
    stages = {s["operationName"] for s in trace_json["spans"]
              if s["processID"] != "p-window"}
    # the full journey, in spans: stream phases + fleet phases
    for stage in ("ingest", "seal", "pack", "dispatch", "decode", "emit"):
        assert stage in stages, stages
    # parent ⊇ child containment holds on the raw payload
    assert _containment_ok(trace_json)

    # parse through the batch ingest layer (fix mode 6 = self-trace)
    from traceweaver_tpu.ingest.jaeger import parse_trace_payload

    parsed = parse_trace_payload(payload, selftrace.SELFTRACE_FIX, {}, {})
    assert len(parsed) == 1 and parsed[0] is not None

    # ... and reconstruct it with the solver itself: a fix=6 tenant
    # ingests the pipeline's own spans and solves them like any other
    # uninstrumented application
    meta = TenantService(_cfg(fix=6))
    out = meta.ingest("self", payload)
    assert out["ingested_traces"] == 1
    assert out["malformed_spans"] == 0
    meta.flush()
    recs = meta.tenant("self").ring.records()
    assert len(recs) == 1
    rec = recs[0]
    # EVERY span of the journey is in the reconstructed trace
    assert rec["n_spans"] == len(trace_json["spans"])
    assert rec["complete"] is True
    services = {s["service"] for s in rec["spans"]}
    assert selftrace.ROOT_SERVICE in services
    assert {"tw-pack", "tw-dispatch", "tw-decode"} <= services
    # the pipeline can answer "where did my window's time go" about
    # ITSELF — the paper's marquee query over the pipeline's own trace
    q = meta.query_delay_culprit("self", percentile=0.0)
    assert q["empty"] is False
    assert q["worst_service"].startswith("tw-")


def test_selftrace_multi_window_multi_tenant_journeys(tracer):
    """Several windows across tenants: every journey becomes its own
    well-formed trace (keys held apart by the tenant prefix), repeated
    stages merge to one span per stage, and the whole payload parses."""
    svc = TenantService(_cfg(window_us=20e6, overlap_us=4e6,
                             pump_windows=1))
    svc.ingest("a", hotel_payload(prefix="a", spacing_us=5e6))
    svc.ingest("b", hotel_payload(n_traces=12, prefix="b", spacing_us=5e6))
    svc.flush()
    payload = tracer.payload()
    assert len(payload["data"]) >= 4  # multiple windows per tenant
    ids = [t["traceID"] for t in payload["data"]]
    assert any("-a-" in i or i.endswith("a:0") or "a-" in i for i in ids)
    for trace_json in payload["data"]:
        assert _containment_ok(trace_json)
        ops = [s["operationName"] for s in trace_json["spans"]
               if s["processID"] != "p-window"]
        assert len(ops) == len(set(ops))  # stages merged, not repeated
        root = next(s for s in trace_json["spans"]
                    if s["spanID"] == "root")
        assert root["operationName"] == selftrace.ROOT_OP

    from traceweaver_tpu.ingest.jaeger import parse_trace_payload

    parsed = parse_trace_payload(payload, selftrace.SELFTRACE_FIX, {}, {})
    assert all(p is not None for p in parsed)


def test_selftrace_off_by_default_and_fleet_unaffected():
    """No tracer installed (the production default): solves run with
    zero journeys collected and no trace keys leak into results."""
    assert selftrace.active() is None
    svc = TenantService(_cfg())
    svc.ingest("quiet", hotel_payload())
    svc.flush()
    assert svc.stats("quiet")["emitted_windows"] == 1


# ---------------------------------------------------------------------------
# TW_PROFILE hooks + knob registration
# ---------------------------------------------------------------------------

def test_profile_knobs_registered_and_annotate_inert(monkeypatch):
    from traceweaver_tpu.obs import profile as obs_profile
    from traceweaver_tpu.runtime import knobs

    for name in ("TW_PROFILE", "TW_METRICS_PORT", "TW_SELFTRACE",
                 "TW_EVENTS"):
        assert name in knobs.REGISTRY, name
    monkeypatch.delenv("TW_PROFILE", raising=False)
    assert obs_profile.enabled() is False
    with obs_profile.annotate("tw:test"):  # null context, no jax import
        pass
    assert obs_profile.device_memory_families() == []
    monkeypatch.setenv("TW_PROFILE", "1")
    assert obs_profile.enabled() is True
    with obs_profile.annotate("tw:test"):  # real TraceAnnotation on CPU
        pass
    # CPU devices may or may not expose memory_stats; either way this
    # must not raise and must return collector-shaped families
    fams = obs_profile.device_memory_families()
    for name, kind, _help, samples in fams:
        assert name == "tw_device_memory_bytes" and kind == "gauge"
        assert all(isinstance(v, float) for _, v in samples)
    monkeypatch.setenv("TW_PROFILE", "nonsense-is-truthy")
    assert obs_profile.enabled() is True
