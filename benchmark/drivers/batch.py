"""Batch reconstruction cells: the fleet solve over one seeded corpus.

Set-up (counted in ``setup_s``): draw the corpus from the seed, write it
in the Jaeger layout of the program's call-graph loader, load it through
that loader (``load_corpus`` fix 5, then ``build_service_problem``),
build one ``FleetItem`` per solvable service as ``cli campaign run``
does, and solve whole rounds until a round compiles nothing. The plan
cache and the device state carry across rounds, as in a long-lived
reconstructor.

The written corpus is kept under ``.benchwork/<cell>/corpus/<key>``,
the key a digest of the configuration, the mix and the seed, so a later
run of the same seed in this checkout reads it instead of writing it
again. The loaded problems are not kept: loading is the program's own
code, and its time is part of set-up.

Window: whole ``solve_fleet`` rounds until ``seconds`` have passed; the
rate is every span of every round over the window's whole length. With
``trace`` the profiler records a stretch of rounds after the first.

After the window, every round's assignments are compared with the
generator's truth (``checks/callgraph_truth.py``).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from typing import Dict, List

from benchmark import harness
from benchmark import trace as trace_mod
from benchmark.checks import callgraph_truth

WARMUP_MAX_ROUNDS = 10
#: profile at least this long, and at least TRACE_ROUNDS whole rounds,
#: starting at the window's 2nd round
TRACE_SECONDS = 2.0
TRACE_ROUNDS = 2


def _sum_stats(rounds: List[Dict]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for st in rounds:
        for k, v in st.items():
            if isinstance(v, (int, float)):
                out[k] = out.get(k, 0.0) + float(v)
    return out


def _supervisor(stats: Dict[str, float]) -> Dict[str, float]:
    """The solve supervisor's fault-ladder counters and which program
    each dispatch group ran, from summed round stats."""
    return {k: v for k, v in sorted(stats.items())
            if k.startswith(("fault_", "kernel_"))}


def _profile_options(jax):
    """Device ops and named host spans only: the default host tracer
    level and the Python tracer slow host-bound rounds about threefold,
    which would read as device idle time."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def compare(grades: List[Dict], spans_lost: int, limits: Dict) -> Dict:
    """The compared numbers over every round of the window, each with its
    limit: traces linked wrong in the worst round, incoming spans left
    without an answer, and spans the loader dropped."""
    return {
        "wrong_trace_pct": dict(
            value=max(g["wrong_trace_pct"] for g in grades),
            limit=limits["wrong_trace_pct"]),
        "in_spans_unanswered": dict(
            value=sum(g["unanswered_in_spans"] for g in grades), limit=0),
        "spans_lost_at_ingest": dict(value=spans_lost, limit=0),
    }


def write_corpus(gen, graphs, config: Dict, traffic: Dict, seed: int,
                 work: str) -> List[str]:
    """The corpus's directories, written once per configuration, mix and
    seed; a directory without its ``complete`` mark is written anew."""
    key = hashlib.sha256(json.dumps([config, traffic, int(seed)],
                                    sort_keys=True).encode()).hexdigest()
    root = os.path.join(work, "corpus", key[:16])
    mark = os.path.join(root, "complete")
    if os.path.exists(mark):
        with open(mark) as f:
            return [os.path.join(root, d) for d in json.load(f)]
    dirs = gen.write_corpus(graphs, root)
    with open(mark, "w") as f:
        json.dump([os.path.relpath(d, root) for d in dirs], f)
    return dirs


def load_items(dirs: List[str], traces: List[int]):
    """The program's loader and per-service problems, as the campaign
    runner builds them, for each directory and its count of traces;
    returns the items and the spans ingested."""
    from traceweaver_tpu.runtime import knobs  # noqa: F401  (import order)

    from traceweaver_tpu.algorithms.fleet import FleetItem
    from traceweaver_tpu.ingest import (
        build_service_problem,
        infer_invocation_dag,
        load_corpus,
    )
    from traceweaver_tpu.metrics import get_ground_truth

    items = []
    spans = 0
    for si, (d, n) in enumerate(zip(dirs, traces)):
        store = load_corpus(d, fix=5, max_traces=n + 1, cache=False)
        spans += len(store.all_spans)
        for svc in sorted(store.out_spans_by_process):
            prob = build_service_problem(store, svc, deepcopy=False)
            if prob.skipped:
                continue
            true = get_ground_truth(prob.in_span_partitions,
                                    prob.out_span_partitions)
            dag = infer_invocation_dag(prob.in_span_partitions,
                                       prob.out_span_partitions, true, store)
            items.append(FleetItem(svc, prob.in_span_partitions,
                                   prob.out_span_partitions, true, dag,
                                   store=store, plan_key=f"{si}:{svc}"))
    return items, spans


def run(spec: Dict, seed: int, seconds: float, trace: bool, t0: float,
        jax) -> Dict:
    from traceweaver_tpu.algorithms.fleet import solve_fleet
    from traceweaver_tpu.algorithms.plancache import PlanCache

    config, traffic = spec["config"], spec["traffic"]
    name = spec["cell"]["name"]
    work = os.path.join(harness.WORK_DIR, name)
    gen = harness.generator(traffic)
    graphs = gen.draw_traces(config, traffic, seed)
    dirs = write_corpus(gen, graphs, config, traffic, seed, work)
    due = gen.due_answers(graphs)
    n_spans = gen.span_count(graphs)
    items, ingested = load_items(dirs, [len(t) for t in graphs])
    harness.log(f"[batch] {name} seed {seed}: {n_spans} spans drawn, "
                f"{ingested} ingested, {len(items)} services to solve "
                f"(+{time.monotonic() - t0:.1f} s)")

    compiles = harness.CompileCounter()
    plan_cache = PlanCache()

    def solve(stats: Dict):
        quarantined: List[int] = []
        outs = solve_fleet(items, stats=stats, quarantined=quarantined,
                           plan_cache=plan_cache)
        return outs, len(quarantined)

    warm, warm_s, warm_stats = [], [], []
    for _ in range(WARMUP_MAX_ROUNDS):
        before, t1 = compiles.count, time.monotonic()
        warm_stats.append({})
        solve(warm_stats[-1])
        warm.append(compiles.count - before)
        warm_s.append(round(time.monotonic() - t1, 3))
        if warm[-1] == 0 and len(warm) >= 2:
            break
    harness.log(f"[batch] warm-up rounds: compiles {warm}, seconds {warm_s}, "
                f"compile-cache hits {compiles.cache_hits}, supervisor "
                f"{_supervisor(_sum_stats(warm_stats))}")

    trace_dir = os.path.join(work, "trace")
    round_outs, round_stats, walls = [], [], []
    quarantined = 0
    tracing = False
    compiles_before = compiles.count
    t_start = time.monotonic()
    setup_s = t_start - t0
    while True:
        if trace and len(walls) == 1:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options(jax))
            tracing, t_trace, traced = True, time.monotonic(), 0
        stats: Dict = {}
        t1 = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_mod.ROUND_SPAN):
            outs, q = solve(stats)
        walls.append(time.monotonic() - t1)
        round_outs.append(outs)
        round_stats.append(stats)
        quarantined += q
        if tracing:
            traced += 1
            if (traced >= TRACE_ROUNDS
                    and time.monotonic() - t_trace >= TRACE_SECONDS):
                jax.profiler.stop_trace()
                tracing = False
        if time.monotonic() - t_start >= seconds and not tracing:
            break
    window_s = time.monotonic() - t_start
    window_compiles = compiles.count - compiles_before
    device = harness.device_entry(jax, int(spec["cell"]["chips"]))
    harness.log(f"[batch] device peak_bytes_in_use "
                f"{device['memory_peak_bytes']}")
    measurement = dict(setup_s=setup_s, window_s=window_s,
                       spans_done=n_spans * len(walls),
                       stats=_sum_stats(round_stats), trace=None,
                       trace_events=None, traced_spans=0)
    harness.log(f"[batch] window {window_s:.3f} s, {len(walls)} rounds "
                f"{[round(w, 3) for w in walls]} s, "
                f"compiles in window {window_compiles}, "
                f"quarantined {quarantined}, supervisor "
                f"{_supervisor(measurement['stats'])}")

    breakdown = None
    if trace:
        events = trace_mod.load_events(trace_mod.latest_xplane(trace_dir))
        reduced = trace_mod.reduce_events(events)
        measurement.update(trace=reduced, trace_events=events,
                           traced_spans=n_spans * reduced["rounds"])
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = dict(device_ops=reduced["device_ops"],
                         idle_gaps=reduced["idle_gaps"])

    # the reference, once the window has closed
    grades = [callgraph_truth.grade(due, outs) for outs in round_outs]
    measurement["grade_last"] = grades[-1]
    checks = compare(grades, n_spans - ingested,
                     spec["workload"]["limits"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    harness.log("[batch] worst round: %s"
                % max(grades, key=lambda g: g["wrong_trace_pct"]))
    return dict(measurement=measurement, device=device,
                breakdown=breakdown, checks=checks, correct=correct,
                attempted=len(due) * len(walls),
                failed=sum(g["unanswered_traces"] for g in grades))
