"""Fleet solve: every service's windows in one device dispatch.

The reference exploits multi-service workloads only through a host thread
pool — one ``FindAssignments`` call per service, concurrency from Python
threads (reference executor.py:1015-1026). On TPU that model leaves the
chip idle: each per-service solve is its own device program, and every
dispatch pays a fixed host round trip, so an 8-service workload pays ~8
round trips of pure latency.

This module is the TPU-native alternative (SURVEY.md §2.8 "services
become a batch dimension"): window batches of services are padded to
shared ``[B, E, W, M]`` shape classes, each window tagged with
``param_idx`` — the row of its service's DAG-structure/distribution
tables — and each class rides ONE jitted program
(:func:`traceweaver_tpu.algorithms.weaver_tpu.solve_em_fleet`), including
both EM passes and the batched BIC-GMM refit between them. Services with
similar window geometry share a class; geometry outliers get their own
dispatch rather than inflate everyone's padding (the merge budget is
backend-aware — padding is nearly-free VPU headroom on TPU, real
core-seconds on the CPU stand-in). Dispatch count drops from O(services)
to O(shape classes), typically 1-2.

Dynamism (cache-hit services with skip budget > 0, reference
exp2/run_experiment.sh:128-158) rides the fleet too: those services form
single-pass dispatch groups with bootstrap distributions and water-filled
per-window skip-cap tensors, exactly the per-service dynamism
configuration fused. The true-skips oracle ships its forced rows as
per-window force-skip tensors. Only methods that need the host in the
loop (KDE score mode, single-iteration parallel mode, the true-dist
oracle, missing DAGs) fall back to the per-service :class:`WeaverTPU`
path.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from traceweaver_tpu.algorithms import packed_layout as _layout
from traceweaver_tpu.algorithms.skips import water_fill_skip_caps
from traceweaver_tpu.algorithms.weaver_tpu import (
    DEFAULT_MAX_WINDOW,
    WeaverTPU,
    _bucket,
    _pack_problem_devcols,
    candidate_ranges,
    columnar_enabled,
    in_columns,
    out_columns,
    pack_problem,
    perfect_cut_windows,
    perfect_cut_windows_cols,
    plan_find_assignments,
    refit_fleet_params,
    scatter_window_span_stats,
    solve_em_fleet,
    solve_windows_fleet,
    window_index,
)
from traceweaver_tpu.ops import devcols as _devcols
from traceweaver_tpu.ops.pallas_sinkhorn import (
    kernel_path,
    mesh_allows_pallas,
)
from traceweaver_tpu.runtime import aot as _aot
from traceweaver_tpu.obs import events as _events
from traceweaver_tpu.obs import profile as _profile
from traceweaver_tpu.obs import quality as _quality
from traceweaver_tpu.obs import selftrace as _selftrace
from traceweaver_tpu.obs.registry import get_registry as _get_registry
from traceweaver_tpu.ops.precision import (
    precision_from_env,
    score_itemsize,
    validate_precision,
)
from traceweaver_tpu.runtime import faults as _faults
from traceweaver_tpu.runtime import knobs as _knobs
from traceweaver_tpu.spans import NA

# fleet single-dispatch budget, denominated in f32 elements for knob
# back-compat (TW_FLEET_BUDGET): live bytes of the [B, E, W, M] score
# block (the dominant allocation) are bounded by 4x this. Past it the
# padded single program would stress HBM; fall back to per-service
# dispatches instead. Group costs are counted in BYTES at the score
# precision (ops/precision.py), so a TW_PRECISION=bf16 solve fits ~2x
# the windows per dispatch and ~2x the pipeline depth under one budget.
#
# None = read TW_FLEET_BUDGET from the registry at CALL time (an env
# change between two solves takes effect without reimport —
# tests/test_analysis.py pins this); tests monkeypatch this attribute to
# force a budget directly.
FLEET_BUDGET_ELEMS: Optional[int] = None


def _fleet_budget_elems() -> int:
    if FLEET_BUDGET_ELEMS is not None:
        return FLEET_BUDGET_ELEMS
    return _knobs.get_int("TW_FLEET_BUDGET")


def _fleet_budget_bytes() -> int:
    return _fleet_budget_elems() * 4


#: the profiler span prefix of a fleet stage (``tw:fleet:<stage>``)
SPAN_PREFIX = "tw:fleet:"

# window-axis keys of a packed fleet batch, dispatch argument order
_BATCH_KEYS = ("in_start", "in_end", "in_valid", "out_start", "out_end",
               "out_valid", "skip_cap", "force_skip")

# the only window-axis tensors that still ship H2D under the
# device-resident path (TW_DEVCOLS): the per-window skip capacities and
# forced-skip rows. Everything else is assembled on device from the
# resident column rings (ops/devcols.py).
_DEVCOLS_BATCH_KEYS = ("skip_cap", "force_skip")

# per-problem param tables, dispatch argument order (after the batch keys)
_TABLE_KEYS = ("pred_mask", "root_mask", "is_last",
               "edge_wt", "edge_mu", "edge_sd",
               "in_wt", "in_mu", "in_sd",
               "ret_wt", "ret_mu", "ret_sd")


def _compaction_warm() -> int:
    """Warm sweep count before convergence compaction redispatches
    (``TW_SWEEP_WARM``, default 2 — sweep 0 plus one verification sweep,
    which certifies the large fraction of windows whose Gauss-Seidel
    assignments are already a fixed point after the forward pass).
    Declared in :mod:`traceweaver_tpu.runtime.knobs`: an unparseable
    value raises instead of silently running the default."""
    return _knobs.get_int("TW_SWEEP_WARM")


def _compaction_on() -> bool:
    """``TW_COMPACT=0`` kills convergence compaction (single fused
    dispatch per group, the pre-compaction shape)."""
    return _knobs.get_bool("TW_COMPACT")


def _pipeline_on() -> bool:
    """``TW_PIPELINE=0`` kills the pipelined dispatcher: groups pack,
    dispatch, and decode strictly sequentially on the calling thread
    (the pre-pipeline flow, kept as the bit-identical reference path and
    as the kill switch)."""
    return _knobs.get_bool("TW_PIPELINE")


def _decode_workers() -> int:
    """Worker count of the pipeline's flow pool (``TW_DECODE_WORKERS``,
    default 2). Each worker drives one group's dispatch -> compaction
    round trips -> output fetch -> decode, so this bounds how many
    groups can overlap their host-side work with other groups' device
    execution (the live-element budget bounds depth independently)."""
    return _knobs.get_int("TW_DECODE_WORKERS")


def _retry_max() -> int:
    """Bounded redispatch retries before the supervisor's ladder bisects
    (``TW_RETRY_MAX``, default 2)."""
    return _knobs.get_int("TW_RETRY_MAX")


def _retry_backoff_s() -> float:
    """Base of the exponential retry backoff, seconds
    (``TW_RETRY_BACKOFF_S``, default 0.02 — attempt k sleeps
    ``base * 2**k``; transient device faults such as OOM-under-contention
    or a lost device clear on their own, so retries must not hammer)."""
    return _knobs.get_float("TW_RETRY_BACKOFF_S")


def _fault_check(site: str, st: "_Stats") -> None:
    """Deterministic fault-injection hook (``TW_FAULTS``), ledgered.
    With no active plan this is one cached-module call returning
    immediately — the production no-fault path stays bit-identical."""
    if _faults.active() is None:
        return
    try:
        _faults.maybe_fail(site)
    except _faults.FaultError:
        st.add("faults_injected")
        st.add("faults_injected_" + site)
        raise


# obs registry mirrors (docs/OBSERVABILITY.md): every _Stats update
# ALSO lands in the process metrics registry so `GET /metrics` sees the
# fleet ledger with labels. The legacy dict stays authoritative for
# bench/executor field names; the bench `telemetry_snapshot` field
# proves the two agree (registry counter deltas == the solve's dict).
_OBS = _get_registry()
_OBS_LEDGER = _OBS.counter(
    "tw_fleet_ledger_total",
    "fleet solve ledger mirror (one series per _Stats counter key)",
    labels=("key",))
_OBS_GAUGE = _OBS.gauge(
    "tw_fleet_gauge",
    "fleet high-water marks (_Stats.record_max mirror)",
    labels=("key",))
_OBS_LADDER = _OBS.counter(
    "tw_fault_ladder_events_total",
    "solve-supervisor degradation-ladder rungs walked",
    labels=("key", "rung"))
_OBS_TENANT = _OBS.counter(
    "tw_tenant_windows_total",
    "per-tenant fleet window buckets (packed/redispatched/decoded)",
    labels=("key", "tenant"))
_OBS_DISPATCH_S = _OBS.histogram(
    "tw_dispatch_seconds",
    "per-group fleet dispatch launch time (host side)")


class _Stats:
    """Lock-guarded accumulator over the caller's stats dict.

    Under the pipelined dispatcher the pack thread, the dispatch/decode
    flow workers, and the per-service fallback pool all mutate the same
    dict; a bare ``stats[k] = stats.get(k, 0) + v`` read-modify-write
    would race and silently drop counts, so every update goes through
    one locked helper. ``d is None`` (caller passed no stats) makes the
    dict half a no-op; the obs-registry mirror runs either way, so the
    scrape surface never has blind spots (twlint TW007 enforces that no
    new counter grows outside this path).

    The serve dispatch ring leans on the same shape from the outside:
    each concurrent ``solve_fleet`` call (one per in-flight ticket)
    gets its OWN local stats dict — and therefore its own ``_Stats``
    instance and lock — so ticket dispatches never contend here; the
    per-ticket dicts are folded into the service ledger under the
    service lock at complete (serve/tenancy.py ``_merge_stats``).

    ``profile`` is ``TW_PROFILE``, read once per solve: the stage timer
    (:func:`traceweaver_tpu.obs.profile.stage`) opens its profiler span
    on it, named ``span_prefix`` and the stage."""

    span_prefix = SPAN_PREFIX

    def __init__(self, d: Optional[Dict[str, float]]):
        self.d = d
        self._lock = threading.Lock()
        self.profile = _profile.enabled()

    def add(self, key: str, val: float = 1.0) -> None:
        _OBS_LEDGER.inc(val, key=key)
        if self.d is None:
            return
        with self._lock:
            self.d[key] = self.d.get(key, 0.0) + val

    def record_max(self, key: str, val: float) -> None:
        _OBS_GAUGE.set_max(val, key=key)
        if self.d is None:
            return
        with self._lock:
            self.d[key] = max(self.d.get(key, 0.0), val)

    def merge(self, other: Dict[str, float]) -> None:
        for k, v in other.items():
            _OBS_LEDGER.inc(v, key=k)
        if self.d is None:
            return
        with self._lock:
            for k, v in other.items():
                self.d[k] = self.d.get(k, 0.0) + v

    def note(self, key: str, event: str) -> None:
        """Append to an ORDERED event list under ``key`` (the supervisor's
        degradation-ladder audit trail — ``fault_ladder``). List-valued,
        unlike every counter, so consumers that aggregate numerically
        must skip it; it serializes to JSON like the rest of the dict.
        Each event also mirrors to the labelled ladder counter and, when
        an event sink is installed (``TW_EVENTS``), to the structured
        JSONL log — the durable, timestamped copy of this list."""
        _OBS_LADDER.inc(1.0, key=key, rung=event)
        _events.emit(key, event)
        if self.d is None:
            return
        with self._lock:
            self.d.setdefault(key, []).append(event)

    def bucket(self, key: str, subkey: str, val: float = 1.0) -> None:
        """Accumulate into a nested ``{subkey: count}`` dict under
        ``key`` — the per-tenant ledger (``tenant_windows_packed`` etc.).
        Dict-valued like ``note``'s lists, so numeric aggregators skip
        it; only written when the serve layer actually tags items with
        tenants, so no-tenant callers' stats dicts are unchanged."""
        _OBS_TENANT.inc(val, key=key, tenant=subkey)
        if self.d is None:
            return
        with self._lock:
            d = self.d.setdefault(key, {})
            d[subkey] = d.get(subkey, 0.0) + val


def _as_stats(stats) -> _Stats:
    return stats if isinstance(stats, _Stats) else _Stats(stats)


def _trace_rung(keys, rung: str) -> None:
    """Record a supervisor ladder rung, an instant, on every window
    trace in ``keys`` (obs/selftrace.py; the timed stages stamp
    themselves through the stage timer). One global read and out when no
    tracer is installed (the production default)."""
    tr = _selftrace.active()
    if tr is None or not keys:
        return
    t_us = _selftrace.now_us()
    for key in keys:
        tr.stage(key, rung, t_us)


def _note_aot(st: "_Stats", shape: Optional[str]) -> None:
    """Per-solve AOT-escape ledger: a dispatched shape outside the
    precompiled lattice lands in the ordered ``aot_misses`` event list
    (runtime/aot.py names it; the horizon is tuned from this). No-op
    when no warmup armed the lattice, or on a lattice hit."""
    if shape:
        st.note("aot_misses", shape)


def _copy_async(out) -> None:
    """Start an async D2H transfer of a device handle (no-op for host
    arrays and backends without the hook)."""
    try:
        out.copy_to_host_async()
    except AttributeError:  # plain np.ndarray under some backends/flows
        pass


def _fetch(handle, st: _Stats, flow_wait=None, flag_fetch: bool = False,
           trace_keys=(), span_args=None):
    """Blocking device fetch: the ``fetch`` stage (``flag-fetch`` for a
    convergence-flag fetch, stamped ``compact-fetch`` on the windows'
    self-traces), billed to ``wait_s`` (the device-execution proxy
    stage) and to the D2H byte ledger ``d2h_bytes_fetched``. Flag
    fetches additionally land in ``d2h_bytes_flags``, making the
    compaction contract — O(B) bytes to learn the convergence set, not
    the whole packed block — auditable from stats alone. ``flow_wait``
    (a 1-element list) accumulates this flow's blocking time so the
    dispatcher can subtract it from its launch-time accounting without
    reading the shared dict back."""
    _fault_check("fetch", st)
    with _profile.stage(st, "flag-fetch" if flag_fetch else "fetch",
                        "wait_s", trace_keys, trace_as="compact-fetch",
                        **(span_args or {})) as t:
        out = np.asarray(handle)
    if flow_wait is not None:
        # twlint: disable=TW007 — flow-local wait aggregator (a 1-element
        # list returned to the dispatcher), not a ledger counter; the
        # telemetry copy is the stage timer's wait_s bill above
        flow_wait[0] += t.seconds
    st.add("d2h_bytes_fetched", float(out.nbytes))
    if flag_fetch:
        st.add("d2h_bytes_flags", float(out.nbytes))
    return out


def _fetch_flags(flags, st: _Stats, flow_wait, mesh=None, trace_keys=(),
                 span_args=None):
    """Convergence-flag fetch for one dispatch group, coalesced.

    Single device: one blocking fetch of the ``[B]`` bool array (O(B)
    bytes — the compaction contract). On a mesh the flags come back
    SHARDED, and a naive host read fans into one D2H round trip per
    shard; instead the shards are gathered onto the mesh's first device
    (ICI, device-side) and the host pays ONE transfer — billed under
    ``d2h_bytes_flags`` exactly like the single-device path, with
    ``d2h_flag_fetches`` counting fetches (one per dispatch group, not
    per shard) so the fan-in stays auditable from stats alone."""
    if mesh is not None and int(mesh.devices.size) > 1:
        from traceweaver_tpu.parallel.mesh import coalesce_to_device0

        flags = coalesce_to_device0(flags, mesh)
    st.add("d2h_flag_fetches", 1.0)
    return _fetch(flags, st, flow_wait, flag_fetch=True,
                  trace_keys=trace_keys, span_args=span_args)


class FleetItem:
    """One service's solve request (the FindAssignments argument set)."""

    def __init__(self, svc, in_span_partitions, out_span_partitions,
                 true_assignments, dag=None,
                 method="MaxScoreBatchSubsetWithSkips", store=None,
                 warm_dists=None, tenant=None, in_cols=None, out_cols=None,
                 trace_key=None, plan_key=None):
        self.svc = svc
        self.in_span_partitions = in_span_partitions
        self.out_span_partitions = out_span_partitions
        self.true_assignments = true_assignments
        self.dag = dag
        self.method = method
        # optional TraceStore for the per-service fallback path (its host
        # EM refit reads the global span table); unused by the fused path
        self.store = store
        # optional carried {edge key -> EdgeDist} (streaming warm start):
        # replaces the plan's cold fit and collapses the solve to a single
        # pass — the on-device EM refit is what the carried statistics
        # already are (stream/state.py CarriedState)
        self.warm_dists = warm_dists
        # optional tenant id (the serve layer's shared-fleet tenancy,
        # traceweaver_tpu/serve): a host-side id column carried through
        # pack -> compaction -> decode so per-tenant window counts,
        # straggler redispatches, and quarantines are attributable from
        # the stats ledger alone. None (the default, and the only value
        # every pre-serve caller produces) keeps the ledger and the
        # dispatched programs byte-identical — the column never ships to
        # the device.
        self.tenant = tenant
        # optional pre-built SpanArray columns over the SORTED partitions
        # (in: (start, end) order; out: per-endpoint ascending-start) —
        # the stream micro-batch builder hands windows over as column
        # slices so the fleet pack never re-walks span objects. Absent
        # (batch callers), _prepare converts once at the solve boundary.
        self.in_cols = in_cols
        self.out_cols = out_cols
        # optional self-trace window key (obs/selftrace.py): the host-side
        # trace context that follows this item's windows through the pack
        # thread, dispatch flows, and decode workers so the pipeline's own
        # journey spans land on the right window's trace. None (the
        # default) with no tracer installed costs one global read per
        # hook site.
        self.trace_key = trace_key
        # optional plan-cache identity (algorithms/plancache.py). Service
        # names repeat across call graphs in campaign corpora, so callers
        # that solve several graphs against ONE cache must disambiguate
        # (the campaign runner keys "store:svc"); None falls back to svc.
        self.plan_key = plan_key


def _plan_key(item: FleetItem) -> str:
    return item.plan_key if item.plan_key is not None else item.svc


def _prepare(item: FleetItem, solver: WeaverTPU,
             cached_dists=None):
    """Host preamble of FindAssignments for one item (sort, topo order,
    skip budget, distributions). Returns None when the item needs a code
    path the fleet does not cover (no DAG, KDE scoring, true-dist oracle).

    Dynamism (skip budget > 0 — the cache-hit workloads, reference
    exp2/run_experiment.sh:128-158) stays IN the fleet: those services get
    the per-service path's bootstrap distributions and a single-pass plan
    (``n_passes=1``, no EM refit — identical to ``iterations = 1`` in
    :meth:`WeaverTPU.FindAssignments`), with their water-filled skip caps
    carried as per-window tensors in the fused dispatch."""
    if item.dag is None or solver.score_mode != "mixture":
        return None
    if item.method not in ("MaxScoreBatchSubsetWithSkips",
                           "MaxScoreBatchSubsetWithTrueSkips"):
        return None
    in_ep, in_spans = next(iter(item.in_span_partitions.items()))
    in_spans = sorted(in_spans, key=lambda s: (s.start_mus, s.end_mus))
    out_eps = solver._topo_out_eps(item.out_span_partitions, item.dag)
    # the SAME plan the per-service entry point computes (one definition,
    # weaver_tpu.plan_find_assignments — the paths cannot drift); the
    # true-skips oracle's forced rows ride the dispatch as per-window
    # force-skip tensors (the device solver input, weaver_tpu.py:94)
    plan = plan_find_assignments(
        item.in_span_partitions, item.out_span_partitions, out_eps,
        item.dag, item.true_assignments, score_mode=solver.score_mode,
        true_skips=(item.method == "MaxScoreBatchSubsetWithTrueSkips"),
        # the fit is dead computation when warm/cached dists override it
        # below — same plan otherwise (budgets, dynamism, iterations)
        skip_fit=(item.warm_dists is not None or cached_dists is not None),
    )
    dists, n_passes = plan["dists"], plan["iterations"]
    if item.warm_dists is not None:
        # streaming warm start: carried per-edge statistics from earlier
        # windows replace both the cold fit and the refit pass; the item
        # joins the single-pass dispatch groups (unseen edges fall back
        # to pack_problem's near-flat wide Gaussian)
        dists, n_passes = item.warm_dists, 1
    elif cached_dists is not None:
        # plan-cache hit (algorithms/plancache.py): the previous round's
        # fitted plan — a cold fit or the decoded on-device refit tables —
        # replaces the fit AND the refit pass, exactly the warm contract
        dists, n_passes = cached_dists, 1
    # columnar handoff (TW_COLUMNAR, default): reuse the item's pre-built
    # columns (stream/serve hand their sorted window slices over) or
    # convert ONCE here — downstream windowing/ranges/pack is array work
    in_cols = out_cols = None
    if columnar_enabled():
        in_cols = (item.in_cols
                   if item.in_cols is not None
                   and len(item.in_cols) == len(in_spans)
                   else in_columns(in_spans))
        out_cols = (item.out_cols
                    if item.out_cols is not None
                    and all(ep in item.out_cols for ep in out_eps)
                    else out_columns(item.out_span_partitions, out_eps))
    return dict(in_ep=in_ep, in_spans=in_spans, out_eps=out_eps,
                skip_budget=plan["skip_budget"], dists=dists,
                n_in=plan["n_in"], n_passes=n_passes,
                force_skip_ids=plan["force_skip_ids"],
                in_cols=in_cols, out_cols=out_cols)


def _raw_cells(item: FleetItem, max_window: int) -> float:
    """Padded-compute-cell count for an item solved OUTSIDE a fused
    dispatch (host-in-the-loop fallbacks), from its raw partitions — the
    same ``n_windows * W * M * E * n_passes`` model the fused plan
    records, so mixed fused/fallback workloads attribute wall-clock on
    one scale. The pass count mirrors ``WeaverTPU.FindAssignments``:
    one pass under dynamism or the true-dist oracle, two otherwise."""
    in_spans = sorted(next(iter(item.in_span_partitions.values())),
                      key=lambda s: (s.start_mus, s.end_mus))
    out_eps = list(item.out_span_partitions)
    windows = perfect_cut_windows(in_spans, max_window)
    out_starts_np = {
        ep: np.array(sorted(float(s.start_mus)
                            for s in item.out_span_partitions[ep]))
        for ep in out_eps
    }
    ranges = candidate_ranges(in_spans, windows, out_eps, out_starts_np)
    w_b = _bucket(max(hi - lo for lo, hi in windows))
    m_b = _bucket(int((ranges[:, :, 1] - ranges[:, :, 0]).max(initial=1)))
    n_in = len(in_spans)
    dynamism = any(n_in - len(item.out_span_partitions[ep]) > 0
                   for ep in out_eps)
    n_passes = 1 if (dynamism
                     or item.method == "MaxScoreBatchSubsetWithTrueDist") \
        else 2
    return float(len(windows) * w_b * m_b * max(1, len(out_eps)) * n_passes)


def _run_fallback(entries, results, all_spans, all_processes,
                  solver_kwargs, stats, confidences=None) -> None:
    """Per-service solves for items the fused dispatch cannot carry.

    Dispatches overlap through a thread pool (the reference's own
    ThreadPool-over-services model, executor.py:1015-1026) and each
    solver's stage stats merge into the caller's dict — a mixed workload
    keeps both the overlap and the accounting it had on the pre-fleet
    bench path. ``confidences`` (the caller's per-item quality slots,
    obs/quality.py) receives each solver's own per-span records, so
    fallback-path windows carry ``tw.confidence`` exactly like fused
    ones."""
    st = _as_stats(stats)

    def run(entry):
        i, item = entry
        algo = WeaverTPU(
            item.store.all_spans if item.store else all_spans,
            item.store.all_processes if item.store else all_processes,
            **solver_kwargs)
        # oracle methods carry their flag through the fallback too
        # (the same method-name -> kwarg mapping runtime/executor.py does)
        kwargs = {}
        if item.method == "MaxScoreBatchSubsetWithTrueSkips":
            kwargs["true_skips"] = True
        elif item.method == "MaxScoreBatchSubsetWithTrueDist":
            kwargs["true_dist"] = True
        out = algo.FindAssignments(
            item.method, item.svc, item.in_span_partitions,
            item.out_span_partitions, False, [], item.true_assignments,
            item.dag, **kwargs,
        )
        return i, out, algo.stats, algo.per_span_confidence

    with _profile.stage(st, "fallback", items=len(entries)), \
            ThreadPoolExecutor(max_workers=max(1, len(entries))) as pool:
        for i, out, solver_stats, conf in pool.map(run, entries):
            results[i] = out
            if confidences is not None:
                confidences[i] = conf
            st.merge(solver_stats)


def solve_fleet(
    items: List[FleetItem],
    all_spans=None,
    all_processes=None,
    max_window: int = DEFAULT_MAX_WINDOW,
    epsilon: float = 1.0,
    n_sinkhorn: int = 40,
    n_sweeps: int = 5,
    sinkhorn_tol: float = 1e-3,
    mesh=None,
    stats: Optional[Dict[str, float]] = None,
    item_cells: Optional[List[float]] = None,
    precision: Optional[str] = None,
    quarantined: Optional[List[int]] = None,
    confidences: Optional[List[Optional[Dict]]] = None,
    plan_cache=None,
) -> List[Tuple]:
    """Solve every item, fusing eligible ones into one device dispatch.

    Dispatch groups ride a bounded multi-stage pipeline by default
    (:func:`_solve_groups_pipelined`): a pack thread builds group N+1's
    tensors while group N executes on the device, each group's
    dispatch/compaction/decode flow runs on a small worker pool
    (``TW_DECODE_WORKERS``), and the ``TW_FLEET_BUDGET`` byte budget
    bounds the live in-flight blocks (the pipeline depth limit). The
    pipeline reorders
    WORK only, never output — results are bit-identical and in input
    order; ``TW_PIPELINE=0`` restores the strictly serial flow.

    ``mesh`` (a ``jax.sharding.Mesh``) shards each dispatch group's
    window-batch axis across the mesh devices under XLA SPMD — the
    multi-chip form of the production path (the same window-axis
    sharding :class:`WeaverTPU` uses per service, applied to the fused
    program; the refit's cross-shard window gather lowers to XLA
    collectives automatically). Convergence compaction applies there
    too, with the redispatch bucketed per shard
    (:func:`traceweaver_tpu.parallel.mesh.bucket_rows_per_shard`).

    ``item_cells`` (when given, a list the caller sized to ``len(items)``)
    receives each item's padded-compute-cell count at its own shape class
    (``n_windows * W * M * E``) — the quantity the device spends time on,
    used by callers to attribute one dispatch's wall-clock to services
    (runtime executor and the parity harness share this model).

    ``precision`` (``"f32"``/``"bf16"``, default = ``TW_PRECISION``) is
    the score-block storage precision for every fused dispatch and the
    per-service fallback alike; the live-dispatch budget and the pipeline
    depth limit account in bytes at this precision.

    Every dispatch group runs under the solve SUPERVISOR: a transient
    device failure (``XlaRuntimeError``, ``RESOURCE_EXHAUSTED``, or an
    injected ``TW_FAULTS`` fault) walks an explicit degradation ladder —
    bounded retry with exponential backoff, bisection of the group to
    isolate the offending service, a fused-Pallas-free XLA redispatch,
    the per-service host fallback — and only a singleton that exhausts
    every rung is QUARANTINED: its slot gets an all-NA result, its index
    is appended to ``quarantined`` (when the caller passes a list), and
    the whole walk is ledgered in ``stats`` (``fault_retries``,
    ``fault_bisections``, ``fault_xla_fallbacks``,
    ``fault_host_fallbacks``, ``fault_quarantined``, plus the ordered
    ``fault_ladder`` event list). Non-transient errors (bugs) propagate
    unchanged. See docs/ROBUSTNESS.md.

    ``confidences`` (when given, a list the caller sized to
    ``len(items)``) receives each item's per-span reconstruction-quality
    records (``{in span id: {conf, not_best, cands, support, ...}}`` —
    :mod:`traceweaver_tpu.obs.quality`), reduced host-side from the SAME
    packed block the decode already fetched. Quarantined items get
    zero-confidence records (a fully failed window must be excludable
    from culprit queries). ``TW_CONF_DEVICE=1`` additionally dispatches
    the confidence program variant, whose quantized margin/entropy
    channels sharpen the score; at default settings the device programs
    are byte-identical to the pre-quality ones.

    ``plan_cache`` (an :class:`traceweaver_tpu.algorithms.plancache.PlanCache`)
    amortizes the host plan fit across repeated solves of the same
    services: hits skip the per-item distribution fit AND collapse the
    two-pass EM to a single warm pass (the ``warm_dists`` contract);
    misses are admitted back — single-pass items from their prepared
    dists, two-pass items from the decoded on-device refit tables
    (:func:`traceweaver_tpu.algorithms.weaver_tpu.dists_from_tables`),
    so the next solve starts where this one's EM ended. Host plan time
    is ledgered under ``plan_fit_s`` either way. Items carrying
    ``warm_dists`` bypass the cache entirely (the stream layer owns its
    own carried state).

    Returns one FindAssignments-style 6-tuple per item, in order:
    ``(all_assignments, all_topk, not_best_count, n_spans,
    per_span_candidates, cnt_unassigned)``.
    """
    st = _as_stats(stats)
    results: List[Optional[Tuple]] = [None] * len(items)
    with _profile.stage(st, "solve", items=len(items)):
        with _profile.stage(st, "setup"):
            # the fused path shards any mesh size (rows pad to a
            # multiple); the per-service fallback solver requires a
            # power-of-two mesh, so a non-pow2 mesh degrades fallback
            # items to single-device rather than crashing the whole
            # mixed solve on WeaverTPU's assert
            n_mesh = int(mesh.devices.size) if mesh is not None else 1
            fallback_mesh = mesh if n_mesh & (n_mesh - 1) == 0 else None
            precision = validate_precision(
                precision if precision is not None
                else precision_from_env())
            solver_kwargs = dict(max_window=max_window, epsilon=epsilon,
                                 n_sinkhorn=n_sinkhorn, n_sweeps=n_sweeps,
                                 sinkhorn_tol=sinkhorn_tol,
                                 mesh=fallback_mesh, precision=precision)
            solver = WeaverTPU(all_spans, all_processes, **solver_kwargs)
            hypers_common = _hypers_common(
                epsilon, n_sinkhorn, n_sweeps, sinkhorn_tol, precision,
                mesh, plan_cache)
            # supervisor context: what the degradation ladder needs to
            # route a failing singleton to the per-service host fallback,
            # where it records quarantined item indices for the caller
            # (the stream service dead-letters the owning windows from
            # this list), and the caller's per-item confidence slots the
            # decode fills
            ctx = dict(all_spans=all_spans, all_processes=all_processes,
                       solver_kwargs=solver_kwargs,
                       quarantined=(quarantined if quarantined is not None
                                    else []),
                       confidences=confidences)

        with _profile.stage(st, "plan-fit", "plan_fit_s", items=len(items)):
            prepared, fallback_entries = _plan_fit(
                items, solver, plan_cache, item_cells, max_window)
        if fallback_entries:
            _run_fallback(fallback_entries, results, all_spans,
                          all_processes, solver_kwargs, st,
                          confidences=confidences)
        if not prepared:
            return results  # type: ignore[return-value]

        with _profile.stage(st, "window-plan", "pack_s",
                            items=len(prepared)):
            plans = [_window_plan(i, item, prep, max_window, item_cells)
                     for i, item, prep in prepared]

        with _profile.stage(st, "group", items=len(plans)):
            specs, over_budget = _group_specs(
                _shape_classes(plans), score_itemsize(precision), st)
        for group in over_budget:
            # padded group block would stress HBM: per-service
            # dispatches. The counter accumulates — a mixed workload can
            # trip the budget on several groups and the ledger must say
            # how many.
            _run_fallback([(p[0], p[1]) for p in group], results,
                          all_spans, all_processes, solver_kwargs, st,
                          confidences=confidences)
            st.add("fleet_fallback_budget", 1.0)
        if not specs:
            return results  # type: ignore[return-value]

        from traceweaver_tpu.runtime.jax_cache import (
            compile_counters,
            counters_delta,
        )

        # recompiles are the shape-class regression signal: a warm
        # steady state dispatches with zero compiles, so any nonzero
        # delta here is a new program variant (bench surfaces these per
        # run). Snapshotted around the WHOLE dispatch phase — per-dispatch
        # deltas would double count under the pipeline's concurrent flows.
        counters_before = compile_counters()
        # mesh dispatches carry cross-replica collectives (the sweep's
        # global convergence reduce, the fused refit's cross-shard
        # gather), and XLA's rendezvous matches participants by
        # per-device SUBMISSION order — two host threads racing their
        # sharded launches onto the same devices interleave run ids and
        # deadlock the whole mesh (observed live on the campaign's
        # 2-device CPU probe). Sharded groups therefore always launch
        # from the single serial flow; the pipeline's pack/dispatch
        # overlap is a single-device optimization.
        if _pipeline_on() and mesh is None:
            _solve_groups_pipelined(specs, solver, results, st,
                                    hypers_common, mesh, ctx)
        else:
            if mesh is not None and _pipeline_on() and len(specs) > 1:
                st.add("mesh_serialized_groups", float(len(specs)))
            _solve_groups_serial(specs, solver, results, st, hypers_common,
                                 mesh, ctx)
        for key, val in counters_delta(counters_before).items():
            if val:
                st.add(key, val)
    return results  # type: ignore[return-value]


def _hypers_common(epsilon, n_sinkhorn, n_sweeps, sinkhorn_tol, precision,
                   mesh, plan_cache) -> Dict:
    """The settings every dispatch group of one solve shares.

    TW_CONF_DEVICE opts every fused dispatch into the confidence program
    variant (quantized margin/entropy channels appended to the packed
    block — packed_layout.py). A static jit arg, so the default False
    keeps the dispatched programs byte-identical to the pre-quality
    ones, and an enabled steady state recompiles nothing.

    Device-resident span columns (TW_DEVCOLS, ops/devcols.py): window
    tensors become on-device gathers from per-service HBM rings, with
    only index arrays + skip/force tensors shipped per dispatch. Rides
    the columnar host path's SpanArray columns, single-device only (the
    mesh path re-places host tensors per shard); the flag travels here
    so the supervisor's bisect re-packs inherit it."""
    return dict(epsilon=epsilon, n_sinkhorn=n_sinkhorn, n_sweeps=n_sweeps,
                sinkhorn_tol=sinkhorn_tol, precision=precision,
                confidence=_quality.conf_device_enabled(),
                devcols=(_devcols.devcols_enabled()
                         and columnar_enabled() and mesh is None),
                # host-only (like devcols): the dispatcher admits two-pass
                # refit tables back into the plan cache; never forwarded
                # to a device program
                plan_cache=plan_cache)


def _plan_fit(items, solver, plan_cache, item_cells, max_window):
    """Each item's host plan (:func:`_prepare`, through the plan cache
    when one is given). Returns the prepared ``(i, item, prep)`` triples
    and the ``(i, item)`` pairs that need the per-service path."""
    prepared = []
    fallback_entries = []
    for i, item in enumerate(items):
        cached = (plan_cache.lookup(_plan_key(item))
                  if plan_cache is not None and item.warm_dists is None
                  else None)
        prep = _prepare(item, solver, cached_dists=cached)
        if prep is None:
            # host-in-the-loop configuration: per-service path
            fallback_entries.append((i, item))
            if item_cells is not None:
                item_cells[i] = _raw_cells(item, max_window)
        else:
            if (plan_cache is not None and cached is None
                    and item.warm_dists is None and prep["n_passes"] == 1):
                # single-pass miss (dynamism): there is no refit to admit
                # later, so the bootstrap fit that just ran IS the plan
                plan_cache.admit(_plan_key(item), prep["dists"])
            prepared.append((i, item, prep))
    return prepared, fallback_entries


def _window_plan(i, item, prep, max_window, item_cells):
    """One prepared item's windows, candidate ranges, skip caps and
    bucketed window geometry: the plan tuple the grouping and the packer
    read (``(i, item, prep, windows, ranges, skip_caps, w_b, m_b)``)."""
    in_spans, out_eps = prep["in_spans"], prep["out_eps"]
    in_cols, out_cols = prep["in_cols"], prep["out_cols"]
    if in_cols is not None:
        # columnar: windowing + ranges from the partition columns
        windows = perfect_cut_windows_cols(in_cols, max_window)
        out_starts_np = {ep: out_cols[ep].start for ep in out_eps}
    else:
        windows = perfect_cut_windows(in_spans, max_window)
        out_starts_np = {
            ep: np.array(sorted(float(s.start_mus)
                                for s in item.out_span_partitions[ep]))
            for ep in out_eps
        }
    ranges = candidate_ranges(in_spans, windows, out_eps, out_starts_np,
                              in_cols=in_cols)
    skip_caps = water_fill_skip_caps(
        windows, ranges, len(in_spans),
        [len(item.out_span_partitions[ep]) for ep in out_eps])
    w_b = _bucket(max(hi - lo for lo, hi in windows))
    m_b = _bucket(int((ranges[:, :, 1] - ranges[:, :, 0]).max(initial=1)))
    if item_cells is not None:
        item_cells[i] = (len(windows) * w_b * m_b
                         * max(1, len(out_eps)) * prep["n_passes"])
    return (i, item, prep, windows, ranges, skip_caps, w_b, m_b)


def _merge_budget() -> int:
    """Padded cells two shape classes may add by merging.

    Services with very different window geometry must not share one
    padded shape: hotel_load150's search (724 windows of 8x8x2) padded
    to its frontend's 32x32x3 pays 24x its own compute in padding. Small
    classes merge upward while the extra padded area stays under a
    budget that reflects the backend: on TPU padded cells are
    nearly-free VPU work and a saved dispatch is a saved host round trip
    (merge aggressively); on the CPU padded cells are real core-seconds
    (merge conservatively). ``TW_FLEET_MERGE`` overrides it (0 = never
    merge shape classes)."""
    merge_env = _knobs.get_int("TW_FLEET_MERGE")
    if merge_env is not None:
        return merge_env
    import jax

    return (1 << 24) if jax.default_backend() == "tpu" else (1 << 20)


def _shape_classes(plans) -> List[List]:
    """Group window plans into dispatch shape classes: one fused program
    per class.

    The class key includes the endpoint-count bucket: an E=12 service
    fused with an E=1 service would pay 12x endpoint padding on the
    score block and E^2 growth on the refit rows — exactly the padding
    class the merge budget exists to arbitrate, so E outliers start in
    their own class and only merge if the padded cost approves. The pass
    count splits classes too: single-pass (dynamism) and two-pass (fused
    EM) services run different device programs and cannot share a
    dispatch."""
    merge_budget = _merge_budget()

    def shape_cost(group):
        w = max(p[6] for p in group)
        m = max(p[7] for p in group)
        e = max(len(p[2]["out_eps"]) for p in group)
        return sum(len(p[3]) for p in group) * w * m * e

    classes: Dict[Tuple[int, int, int, int], List] = {}
    for plan in plans:
        e_b = _bucket(len(plan[2]["out_eps"]), minimum=1)
        classes.setdefault(
            (plan[2]["n_passes"], plan[6], plan[7], e_b), []).append(plan)
    ordered = sorted(classes, key=lambda k: (k[0], k[1] * k[2] * k[3]))
    groups: List[List] = []
    carry: List = []
    for idx, key in enumerate(ordered):
        wins = carry + classes[key]
        if idx + 1 < len(ordered) and ordered[idx + 1][0] == key[0]:
            nxt = wins + classes[ordered[idx + 1]]
            extra = shape_cost(nxt) - shape_cost(wins) \
                - shape_cost(classes[ordered[idx + 1]])
            if extra <= merge_budget:
                carry = wins
                continue
        groups.append(wins)
        carry = []
    if carry:
        groups.append(carry)
    return groups


def _group_specs(groups, itemsize: int, st: "_Stats"):
    """Padded geometry and byte cost of each group. Returns the specs to
    dispatch, each indexed in dispatch order (the ``group`` argument of
    its stage spans), and the groups over the live-dispatch budget,
    which go to the per-service path."""
    specs: List[_GroupSpec] = []
    over_budget: List[List] = []
    for group in groups:
        spec = _make_spec(group, itemsize, index=len(specs))
        if spec.cost > _fleet_budget_bytes():
            over_budget.append(group)
            continue
        # depth-limit observability (bytes): the largest single admission
        # and the total the budget must amortize (budget < total => the
        # pipeline gate/serial drain actually engaged on this workload)
        st.record_max("fleet_group_cost_max", float(spec.cost))
        st.add("fleet_group_cost_total", float(spec.cost))
        specs.append(spec)
    return specs, over_budget


class _GroupSpec:
    """One shape-class dispatch group plus its padded geometry and budget
    cost (live BYTES while its blocks are in flight, dtype-aware at the
    score precision — the unit the pipeline depth limit is denominated
    in). ``span_args`` are the arguments every stage span of the group
    carries (``group`` index in dispatch order, ``items``, ``windows``,
    ``spans``), so its pack, dispatch, fetches and decode join in a
    profiler trace."""

    __slots__ = ("group", "W_pad", "M_pad", "E_pad", "bmax", "n_passes",
                 "cost", "span_args")

    def __init__(self, group, W_pad, M_pad, E_pad, bmax, n_passes, cost,
                 span_args):
        self.group = group
        self.W_pad = W_pad
        self.M_pad = M_pad
        self.E_pad = E_pad
        self.bmax = bmax
        self.n_passes = n_passes
        self.cost = cost
        self.span_args = span_args


def _make_spec(group: List, itemsize: int, index: int) -> _GroupSpec:
    """Padded geometry + byte cost of one dispatch group. One definition
    shared by the initial shape-class grouping and the supervisor's
    bisection rung, so a bisected half is budgeted and padded by exactly
    the rules the full group was (per-plan W/M buckets are already
    powers of two, so halves cannot mint unbucketed shapes). ``index``
    is the group's place in dispatch order; a bisected half keeps its
    whole group's."""
    W_pad = max(p[6] for p in group)
    M_pad = max(p[7] for p in group)
    E_pad = max(len(p[2]["out_eps"]) for p in group)
    n_passes = group[0][2]["n_passes"]  # uniform within a class
    n_windows_total = sum(len(p[3]) for p in group)
    bmax = max(len(p[3]) for p in group)
    P = len(group)
    # Ne family rows per service in the fused refit (in/edge/return)
    Ne = E_pad + E_pad * E_pad + E_pad
    score_elems = n_windows_total * E_pad * W_pad * M_pad
    # the fused refit gathers each service's window rows: [P*Ne, Bmax*W]
    # (single-pass dynamism groups never refit)
    refit_elems = P * Ne * bmax * W_pad if n_passes == 2 else 0
    # cost in BYTES, dtype-aware: score blocks at the configured
    # precision's itemsize (bf16 = half), the refit samples always
    # f32 (GMM EM stays full-precision)
    cost = score_elems * itemsize + refit_elems * 4
    span_args = dict(group=index, items=P, windows=n_windows_total,
                     spans=sum(p[2]["n_in"] for p in group))
    return _GroupSpec(group, W_pad, M_pad, E_pad, bmax, n_passes, cost,
                      span_args)


# ---------------------------------------------------------------------------
# Solve supervisor: retry -> bisect -> XLA -> host fallback -> quarantine
# ---------------------------------------------------------------------------

def _attempt_group(solver, pg, spec, results, st, hypers_common, mesh,
                   ctx=None):
    """One supervised dispatch+decode attempt of a packed group — the
    unit every ladder rung retries. ``pg`` stays host-side NumPy, so a
    failed attempt's donated device buffers never poison the retry:
    every attempt places fresh device copies."""
    _fault_check("dispatch", st)
    pend = _dispatch_packed(pg, spec, st, hypers_common, mesh)
    _decode_group(solver, pend, results, st, ctx=ctx)


def _enter_ladder(err, solver, pg, spec, results, st, hypers_common, mesh,
                  ctx):
    """Classify a group failure: transient faults walk the degradation
    ladder; anything else (a bug) propagates unchanged."""
    if not _faults.is_transient_fault(err):
        raise err
    st.add("fault_dispatch_errors")
    _degrade_group(err, solver, pg, spec, results, st, hypers_common, mesh,
                   ctx)


def _degrade_group(err, solver, pg, spec, results, st, hypers_common, mesh,
                   ctx):
    """Walk the explicit degradation ladder for one failed dispatch group.

    1. **retry** — up to ``TW_RETRY_MAX`` redispatches with exponential
       backoff (``TW_RETRY_BACKOFF_S``): transient faults (OOM under
       contention, lost device, injected ``TW_FAULTS`` draws) usually
       clear here, at full fidelity.
    2. **bisect** — split the group in half and re-enter the ladder per
       half: a single poisoned service must not take its whole shape
       class down. Halves re-pack through :func:`_make_spec` /
       :func:`_pack_group`, so their shapes stay power-of-two bucketed.
    3. **xla** — a surviving singleton redispatches with the Pallas
       kernel pinned off (``pallas=False`` static arg): a
       Mosaic/kernel-specific failure gets the plain XLA program, which
       is algorithm-identical (tests/test_pallas_kernel.py).
    4. **host** — the per-service host fallback (:func:`_run_fallback`,
       the reference's own per-service path).
    5. **quarantine** — the item's slot gets an all-NA result, its index
       lands in ``ctx["quarantined"]``, and the poison window is the
       CONSUMER's problem (the stream service dead-letters it; batch
       callers see the counted all-NA result) — never a silent drop.

    Every step is ledgered: counters per rung plus the ordered
    ``fault_ladder`` event list."""
    retry_max = _retry_max()
    backoff = _retry_backoff_s()
    # ladder rungs stamp the affected windows' self-traces too, so a
    # reconstructed pipeline trace shows WHERE a window's time went when
    # the supervisor engaged (tw-retry/tw-bisect/... stage services)
    rung_keys = sorted({p[1].trace_key for p in spec.group
                        if p[1].trace_key is not None})

    def _maybe_rebuild(e: BaseException) -> None:
        # ring-invalidate-and-rebuild rung: a devcols-site fault means
        # the resident arenas can no longer be trusted, and unlike the
        # transient faults the retry/bisect rungs were built for, a
        # poisoned ring would corrupt every later dispatch that gathers
        # from it — rebuild from the host mirrors BEFORE retrying
        dc = pg.get("devcols_items")
        if dc and _is_devcols_fault(e):
            _rebuild_rings([r for it in dc
                            for r in (it["ring_in"], it["ring_out"])], st)

    _maybe_rebuild(err)
    for attempt in range(retry_max):
        if backoff > 0:
            time.sleep(backoff * (2 ** attempt))
        st.add("fault_retries")
        st.note("fault_ladder", "retry")
        _trace_rung(rung_keys, "retry")
        try:
            _attempt_group(solver, pg, spec, results, st, hypers_common,
                           mesh, ctx)
            st.add("fault_recovered_retry")
            return
        except Exception as e:  # noqa: BLE001 — classified below
            if not _faults.is_transient_fault(e):
                raise
            err = e
            _maybe_rebuild(err)

    if len(spec.group) > 1:
        # bisect: isolate the offender instead of failing the class
        st.add("fault_bisections")
        st.note("fault_ladder", "bisect")
        _trace_rung(rung_keys, "bisect")
        mid = len(spec.group) // 2
        itemsize = score_itemsize(hypers_common.get("precision", "f32"))
        for half in (spec.group[:mid], spec.group[mid:]):
            half_spec = _make_spec(half, itemsize,
                                   spec.span_args["group"])
            half_pg = _pack_group(half_spec, hypers_common, st)
            try:
                _attempt_group(solver, half_pg, half_spec, results, st,
                               hypers_common, mesh, ctx)
            except Exception as e:  # noqa: BLE001
                _enter_ladder(e, solver, half_pg, half_spec, results, st,
                              hypers_common, mesh, ctx)
        return

    # --- singleton rungs -------------------------------------------------
    st.add("fault_xla_fallbacks")
    st.note("fault_ladder", "xla")
    _trace_rung(rung_keys, "xla-fallback")
    try:
        _attempt_group(solver, pg, spec, results, st,
                       {**hypers_common, "pallas": False}, mesh, ctx)
        return
    except Exception as e:  # noqa: BLE001
        if not _faults.is_transient_fault(e):
            raise
        err = e

    plan = spec.group[0]
    st.add("fault_host_fallbacks")
    st.note("fault_ladder", "host")
    _trace_rung(rung_keys, "host-fallback")
    try:
        _fault_check("host", st)
        _run_fallback([(plan[0], plan[1])], results, ctx["all_spans"],
                      ctx["all_processes"], ctx["solver_kwargs"], st,
                      confidences=ctx.get("confidences"))
        if results[plan[0]] is not None:
            return
    except Exception as e:  # noqa: BLE001
        if not _faults.is_transient_fault(e):
            raise
        err = e

    st.add("fault_quarantined")
    st.note("fault_ladder", "quarantine")
    _trace_rung(rung_keys, "quarantine")
    results[plan[0]] = _quarantine_result(plan)
    if ctx.get("confidences") is not None:
        # a quarantined window's reconstruction is all-NA: zero
        # confidence by definition, so culprit queries can exclude it
        ctx["confidences"][plan[0]] = {
            s.GetId(): _quality.zero_confidence()
            for s in plan[2]["in_spans"]}
    ctx["quarantined"].append(plan[0])


def _quarantine_result(plan) -> Tuple:
    """The poison-window result: a structurally valid FindAssignments
    6-tuple with every incoming span unassigned (NA at every endpoint),
    so batch consumers grade it as what it is — a fully failed window —
    instead of crashing on a missing slot. ``cnt_unassigned`` equals the
    span count, which is also the conservation quantity the stream's
    dead-letter accounting checks."""
    prep = plan[2]
    out_eps = prep["out_eps"]
    in_ids = [s.GetId() for s in prep["in_spans"]]
    all_assignments = {ep: {iid: NA for iid in in_ids} for ep in out_eps}
    all_topk = {ep: {iid: [] for iid in in_ids} for ep in out_eps}
    return (all_assignments, all_topk, 0, prep["n_in"],
            {iid: 0 for iid in in_ids}, len(in_ids))


def _solve_groups_serial(specs, solver, results, st, hypers_common, mesh,
                         ctx):
    """The ``TW_PIPELINE=0`` reference flow: pack -> dispatch strictly in
    order on the calling thread, decoding (and draining the live-element
    budget) exactly as the pre-pipeline dispatcher did. Failures enter
    the degradation ladder per group; the happy path is byte-identical
    to the unsupervised flow."""
    pending = []
    total_live = 0

    def finish(entry):
        spec, pg, pend = entry
        try:
            _decode_group(solver, pend, results, st, ctx=ctx)
        except Exception as e:  # noqa: BLE001
            _enter_ladder(e, solver, pg, spec, results, st, hypers_common,
                          mesh, ctx)

    for spec in specs:
        if total_live + spec.cost > _fleet_budget_bytes():
            # keep every live dispatch under one budget: drain first
            for entry in pending:
                finish(entry)
            pending = []
            total_live = 0
        total_live += spec.cost
        pg = _pack_group(spec, hypers_common, st)
        try:
            _fault_check("dispatch", st)
            pend = _dispatch_packed(pg, spec, st, hypers_common, mesh)
        except Exception as e:  # noqa: BLE001
            # a launch-time failure: this group degrades synchronously
            _enter_ladder(e, solver, pg, spec, results, st, hypers_common,
                          mesh, ctx)
            continue
        pending.append((spec, pg, pend))
    for entry in pending:
        finish(entry)


def _solve_groups_pipelined(specs, solver, results, st, hypers_common,
                            mesh, ctx):
    """Bounded multi-stage pipeline over the dispatch groups.

    - a single pack thread builds group N+1's host tensors while group N
      executes on the device (``pack_s`` no longer serializes against
      ``wait_s`` on the main thread);
    - each group's dispatch -> compaction round trips -> output fetch ->
      decode flow runs on a small worker pool (``TW_DECODE_WORKERS``),
      so one group's host-side flag gather or decode never idles the
      device: other flows' dispatches keep it fed (the event-driven
      warm->gather->redispatch requirement);
    - the live-dispatch bound (``TW_FLEET_BUDGET``, counted in BYTES at
      the score precision) is the pipeline depth limit: the gate blocks
      before admitting a group that would push the in-flight byte total
      past one budget — a bf16 solve's groups cost half, so the same
      budget admits ~2x the depth.

    Only WORK is reordered, never output: every flow writes its items'
    input-order ``results`` slots and runs byte-for-byte the serial
    path's math (tests/test_pipeline.py pins pipelined == TW_PIPELINE=0,
    compacted two-pass EM and budget-drain paths included).
    """
    gate = threading.Condition()
    live = {"elems": 0, "flows": 0}
    st.add("pipeline_groups", float(len(specs)))

    def flow(pg, spec):
        try:
            try:
                _attempt_group(solver, pg, spec, results, st, hypers_common,
                               mesh, ctx)
            except Exception as e:  # noqa: BLE001 — transient faults
                # degrade on THIS flow worker (the ladder's retries and
                # sub-dispatches keep riding the pool, so other flows'
                # device work still overlaps); non-transient errors
                # re-raise and propagate through fut.result() below
                _enter_ladder(e, solver, pg, spec, results, st,
                              hypers_common, mesh, ctx)
        finally:
            with gate:
                live["elems"] -= spec.cost
                live["flows"] -= 1
                gate.notify_all()

    pack_pool = ThreadPoolExecutor(max_workers=1,
                                   thread_name_prefix="tw-fleet-pack")
    flow_pool = ThreadPoolExecutor(max_workers=_decode_workers(),
                                   thread_name_prefix="tw-fleet-flow")
    try:
        pack_futs = [pack_pool.submit(_pack_group, spec, hypers_common, st)
                     for spec in specs]
        flow_futs = []
        for spec, fut in zip(specs, pack_futs):
            pg = fut.result()
            with gate:
                # depth limit: admit the group only when its blocks fit
                # the live-element budget (a lone over-budget group was
                # already routed to the per-service fallback upstream)
                while live["elems"] > 0 and \
                        live["elems"] + spec.cost > _fleet_budget_bytes():
                    gate.wait()
                # twlint: disable=TW007 — admission-gate state under the
                # gate condition lock, not telemetry; the observable copy
                # is the pipeline_depth record_max mirror below
                live["elems"] += spec.cost
                # twlint: disable=TW007 — same: gate state, mirrored below
                live["flows"] += 1
                st.record_max("pipeline_depth", float(live["flows"]))
            flow_futs.append(flow_pool.submit(flow, pg, spec))
        for fut in flow_futs:
            fut.result()  # propagate flow errors to the caller
    finally:
        pack_pool.shutdown(wait=True)
        flow_pool.shutdown(wait=True)


def _rebuild_rings(rings, st: _Stats) -> None:
    """The supervisor's ring-invalidate-and-rebuild rung: each faulted
    ring's device buffer is reconstructed from its host mirror (slot
    assignments preserved, so in-flight index arrays stay valid —
    :meth:`traceweaver_tpu.ops.devcols.ColumnRing.rebuild`), the
    re-shipped arena is billed to ``h2d_bytes_ring`` (a rebuild must
    never look free), and the rung lands in the ladder event list, the
    labelled ladder counter, and the ``TW_EVENTS`` sink like every
    other supervisor rung."""
    seen = {}
    for ring in rings:
        seen[id(ring)] = ring
    for ring in seen.values():
        st.add("h2d_bytes_ring", float(ring.rebuild()))
    if seen:
        st.add("devcols_ring_rebuilds", float(len(seen)))
        st.note("fault_ladder", "ring-rebuild")


def _is_devcols_fault(err: BaseException) -> bool:
    """Did this failure originate at the injector's ``devcols`` site?
    Only those faults implicate ring contents — a dispatch/fetch fault
    walks the plain ladder without re-shipping arenas (and without
    perturbing the pinned ladder ledgers of non-devcols chaos runs)."""
    return isinstance(err, _faults.FaultError) and "'devcols'" in str(err)


def _resolve_group_devcols(group, st: _Stats):
    """Resolve every item of a dispatch group onto its device-resident
    column rings (``TW_DEVCOLS``): per item, the in partition and each
    endpoint's out slice map to live ring slots, appending only spans
    not already resident (the H2D saving — ``h2d_bytes_ring`` counts
    what actually shipped). Returns one
    ``(in_slots, out_slots, ring_in, ring_out)`` tuple per item, or
    None when ANY partition cannot ride the resident path
    (non-integral timestamps, window origins outside the rings' int32
    epoch span, partitions larger than a ring) — the whole group then
    falls back to the host packer, counted in ``devcols_fallbacks``;
    mixed-path groups would make the parity contract unauditable."""
    store = _devcols.get_store()
    resolved = []
    for i, item, prep, windows, ranges, skip_caps, _, _ in group:
        in_cols = prep.get("in_cols")
        out_cols = prep.get("out_cols")
        if in_cols is None or out_cols is None or not windows:
            return None
        ring_in = store.ring(item.tenant, item.svc, "in")
        ring_out = store.ring(item.tenant, item.svc, "out")
        scope = (item.tenant, item.svc)
        try:
            # fault site "devcols", ring-append flavor: a failed append
            # leaves the donated device buffer in an unknown state, and
            # a poisoned ring would corrupt every LATER dispatch that
            # gathers from it — so the recovery is not a bare retry but
            # the ring-invalidate-and-rebuild rung (host mirror → fresh
            # device buffer, slots preserved), counted and evented,
            # before the resolve proceeds
            _fault_check("devcols", st)
        except _faults.FaultError:
            _rebuild_rings((ring_in, ring_out), st)
        in_slots = ring_in.resolve(in_cols, ledger=st.add, scope=scope)
        if in_slots is None:
            return None
        out_slots = {}
        for ep in prep["out_eps"]:
            slots = ring_out.resolve(out_cols[ep], endpoint=ep,
                                     ledger=st.add, scope=scope)
            if slots is None:
                return None
            out_slots[ep] = slots
        # window origins must be representable relative to BOTH rings'
        # epochs (the assembly subtracts them on device in int32)
        origins = in_cols.start[[lo for lo, _ in windows]]
        for ring in (ring_in, ring_out):
            if ring.epoch is None:
                return None
            rel = origins - ring.epoch
            if np.any(np.abs(rel) >= _devcols._INT32_SPAN):
                return None
        resolved.append((in_slots, out_slots, ring_in, ring_out))
    return resolved


def _pack_group(spec: _GroupSpec, hypers_common, st: _Stats):
    """Host packing of one shape-class group (pure NumPy — safe on the
    pipeline's pack thread), timed as the ``pack`` stage, then the
    group's dispatch ledger and analytic op accounting."""
    # self-trace context for this group: every distinct window key whose
    # item rides this dispatch (carried on the ticket — the decode
    # worker that finishes the flow stamps the same keys)
    trace_keys = sorted({item.trace_key for _, item, *_ in spec.group
                         if item.trace_key is not None})
    with _profile.stage(st, "pack", "pack_s", trace_keys,
                        **spec.span_args):
        pg = _pack_tensors(spec, hypers_common, st)
    pg["trace_keys"] = trace_keys
    st.add("fleet_dispatches", 1.0)
    st.add("fleet_services", float(len(pg["per_item_pack"])))
    if st.d is not None:
        # analytic op accounting (UPPER BOUND — sweep and Sinkhorn loops
        # exit early on convergence), same model as WeaverTPU._solve_once
        n_sweeps = hypers_common["n_sweeps"]
        n_sinkhorn = hypers_common["n_sinkhorn"]
        itemsize = score_itemsize(hypers_common.get("precision", "f32"))
        K = pg["params"]["in_wt"].shape[2]
        W_pad, E_pad = spec.W_pad, spec.E_pad
        cells = (pg["n_rows"] * E_pad * W_pad * spec.M_pad
                 * n_sweeps * spec.n_passes)
        st.add("flops_est", cells * (
            8.0 * K * (min(pg["max_preds"], E_pad)
                       + min(pg["max_succs"], E_pad) + 2)
            + 6.0 * 2 * n_sinkhorn
            + 8.0 * max(1, W_pad.bit_length())
        ))
        # score-block HBM traffic at the configured precision's itemsize
        # (bf16 halves it); the Pallas term keeps the f32 plan write
        st.add("bytes_est_xla", cells * float(itemsize) * 2 * n_sinkhorn)
        st.add("bytes_est_pallas", cells * (float(itemsize) + 2 * 4.0))
        if spec.n_passes == 2:
            # counts fused EM dispatches (the grouping may produce several)
            st.add("fused_em_applied", 1.0)
        else:
            st.add("fleet_dynamism_dispatches", 1.0)
    return pg


def _pack_tensors(spec: _GroupSpec, hypers_common, st: _Stats):
    """The packed group: concatenated window tensors, stacked param
    tables, the refit row maps.

    Under ``TW_DEVCOLS`` the pack thread feeds INDEX ARRAYS, not
    tensors: each item packs through
    :func:`~traceweaver_tpu.algorithms.weaver_tpu._pack_problem_devcols`
    (ring-slot maps over the resident device columns) and only the
    skip/force tensors concatenate host-side; the dispatch assembles
    the window tensors on device. A group whose partitions cannot ride
    the resident path falls back to the host packer wholesale
    (``devcols_fallbacks``)."""
    group = spec.group
    W_pad, M_pad, E_pad, bmax = spec.W_pad, spec.M_pad, spec.E_pad, spec.bmax
    arrays_cat: Dict[str, List[np.ndarray]] = {}
    param_rows: Dict[str, List[np.ndarray]] = {k: [] for k in _TABLE_KEYS}
    per_item_pack = []
    param_idx = []
    # tenancy id column (serve layer): per-window tenant indices into a
    # group-local table, carried HOST-SIDE alongside the packed batch —
    # pack tags it, compaction attributes straggler redispatches with it,
    # decode attributes decoded windows with it. It never ships to the
    # device, so the dispatched programs (and the no-tenant ledger) stay
    # byte-identical to the pre-tenancy path.
    tenant_table = sorted({item.tenant for _, item, *_ in group
                           if item.tenant is not None})
    tenant_of = {t: ti for ti, t in enumerate(tenant_table)}
    tenant_idx: List[int] = []
    use_devcols = bool(hypers_common.get("devcols"))
    devcols_items: List[Dict] = []
    dc_resolved = None
    if use_devcols:
        dc_resolved = _resolve_group_devcols(group, st)
        if dc_resolved is None:
            st.add("devcols_fallbacks")
            use_devcols = False
    batch_keys = _DEVCOLS_BATCH_KEYS if use_devcols else _BATCH_KEYS
    for p, (i, item, prep, windows, ranges, skip_caps, _, _) in enumerate(group):
        if use_devcols:
            in_slots, out_slots, ring_in, ring_out = dc_resolved[p]
            packed = _pack_problem_devcols(
                prep["in_spans"], item.out_span_partitions, prep["out_eps"],
                prep["dists"], prep["in_ep"], item.dag,
                in_slots, out_slots, ring_in, ring_out,
                force_skip_ids=prep["force_skip_ids"],
                parallel=False, windows=windows,
                pad_w=W_pad, pad_m=M_pad, pad_e=E_pad,
                ranges=ranges, skip_caps=skip_caps,
                in_cols=prep.get("in_cols"), out_cols=prep.get("out_cols"),
            )
        else:
            packed = pack_problem(
                prep["in_spans"], item.out_span_partitions, prep["out_eps"],
                prep["dists"], prep["in_ep"], item.dag,
                force_skip_ids=prep["force_skip_ids"],
                parallel=False, windows=windows,
                pad_w=W_pad, pad_m=M_pad, pad_e=E_pad,
                ranges=ranges, skip_caps=skip_caps,
                in_cols=prep.get("in_cols"), out_cols=prep.get("out_cols"),
            )
        a = packed.arrays
        n_w = len(windows)
        for key in batch_keys:
            # drop pack_problem's power-of-two B padding: the fleet batch
            # is exact, and decode indexes out_ids by original row b which
            # is preserved under row slicing
            arrays_cat.setdefault(key, []).append(a[key][:n_w])
        if use_devcols:
            dc = packed.devcols
            devcols_items.append(dict(
                n_w=n_w, ring_in=dc["ring_in"], ring_out=dc["ring_out"],
                in_idx=dc["in_idx"][:n_w], out_idx=dc["out_idx"][:n_w],
                origin_in=dc["origin_in"][:n_w],
                origin_out=dc["origin_out"][:n_w]))
        # keep the id maps consistent with the sliced row count
        # (_decode sizes its gather table from the assign rows it is given)
        packed.truncate_rows(n_w)
        for key in param_rows:
            param_rows[key].append(a[key])
        param_idx.extend([p] * n_w)
        tenant_idx.extend([tenant_of.get(item.tenant, -1)] * n_w)
        if item.tenant is not None:
            st.bucket("tenant_windows_packed", item.tenant, float(n_w))
        per_item_pack.append((i, item, prep, packed, n_w))

    batch = {k: np.concatenate(v, axis=0) for k, v in arrays_cat.items()}
    params = {k: np.stack(v, axis=0) for k, v in param_rows.items()}
    pidx = np.asarray(param_idx, dtype=np.int32)
    # static neighbour bounds over the whole group (fleet max in/out
    # degree, power-of-two bucketed): the score build gathers only real
    # DAG edges instead of evaluating all E_pad per endpoint
    pm_all = params["pred_mask"]
    _mp = _bucket(max(1, int(pm_all.sum(axis=2).max(initial=0))), minimum=1)
    _ms = _bucket(max(1, int(pm_all.sum(axis=1).max(initial=0))), minimum=1)
    # each service's contiguous window-row block, for the gathered refit
    P = len(per_item_pack)
    n_windows_total = len(param_idx)
    window_rows = np.zeros((P, bmax), dtype=np.int32)
    window_valid = np.zeros((P, bmax), dtype=bool)
    row0 = 0
    for p, (_, _, _, _, n_w) in enumerate(per_item_pack):
        window_rows[p, :n_w] = np.arange(row0, row0 + n_w, dtype=np.int32)
        window_valid[p, :n_w] = True
        row0 += n_w
    return dict(batch=batch, params=params, pidx=pidx,
                window_rows=window_rows, window_valid=window_valid,
                per_item_pack=per_item_pack, max_preds=_mp, max_succs=_ms,
                tenant_table=tenant_table,
                tenant_col=np.asarray(tenant_idx, dtype=np.int32),
                n_rows=n_windows_total,
                devcols_items=devcols_items if use_devcols else None)


def _dispatch_packed(pg, spec: _GroupSpec, st: _Stats, hypers_common,
                     mesh=None):
    """Launch one packed group's device program(s) and return its pending
    ``(per_item_pack, out)`` decode ticket.

    ``out`` is an async device handle for the single-dispatch flows and
    an already-merged host array for the compacted multi-dispatch flow.
    Convergence compaction (host in the loop): the vmapped sweep
    while_loop runs EVERY window until the slowest one's Gauss-Seidel
    assignments stabilize — converged windows' updates are select-masked
    into no-ops but still burn VPU cycles. So each solve pass runs as
    (1) a warm dispatch capped at TW_SWEEP_WARM sweeps, (2) a host-side
    gather of the windows whose convergence flag (its own [B] bool
    output, fetched ALONE — O(B) bytes) is still false, bucketed
    per shard to a power of two (the existing shape-class discipline, so
    redispatch batch sizes cannot multiply compiled variants), (3) a
    full-sweep redispatch of only those rows, scattered back over the
    warm output. Converged windows keep their warm output — the sweep
    loop's exactness argument (a reproducing sweep is a fixed point)
    makes that output bit-identical to what the full-budget run would
    have produced, and the redispatch reruns stragglers from sweep 0, so
    compaction is output-identical to the uncompacted dispatch by
    construction (tests/test_compaction.py pins this down). Two-pass
    (fused EM) groups split into warm/full pass 0 -> one refit dispatch
    (weaver_tpu.refit_fleet_params — the same refit solve_em_fleet runs
    in-graph) -> warm/full pass 1. With ``mesh``, the window-batch axis
    is padded to the mesh size and sharded (XLA SPMD); padded rows are
    invalid everywhere and decoded by nobody, and the compacted
    redispatch buckets its rows PER SHARD (mesh.bucket_rows_per_shard).
    """
    # which program the group's [W+1, M+1] OT blocks run (Sinkhorn
    # kernel / plain XLA), counted once per dispatch group
    allow_pallas = (hypers_common.get("pallas", True)
                    and mesh_allows_pallas(mesh))
    path = kernel_path(
        spec.W_pad + 1, spec.M_pad + 1,
        score_itemsize(hypers_common.get("precision", "f32")),
        allow_pallas=allow_pallas)
    st.add(f"kernel_{path}_groups")
    batch, params, pidx = pg["batch"], pg["params"], pg["pidx"]
    window_rows, window_valid = pg["window_rows"], pg["window_valid"]
    dc_items = pg.get("devcols_items")
    assemble = (_make_assembler(dc_items, batch, st)
                if dc_items is not None else None)
    if assemble is not None:
        # bounded compile lattice under continuous batching: the group's
        # service-count and refit-row-map axes pad to pow2 like every
        # other dispatch shape (all-invalid padding services)
        params, window_rows, window_valid = _pad_tables_pow2(
            params, window_rows, window_valid)
    # the host-side tenancy column rides the dispatch ticket so the
    # compacted flow can attribute straggler redispatches per tenant;
    # None whenever no item in the group is tenant-tagged (every
    # pre-serve caller), keeping this flow untouched
    tenant_table = pg.get("tenant_table") or None
    tenant_col = pg.get("tenant_col") if tenant_table else None
    n_passes = spec.n_passes
    n_sweeps = hypers_common["n_sweeps"]
    hypers = dict(epsilon=hypers_common["epsilon"],
                  n_sinkhorn=hypers_common["n_sinkhorn"],
                  sinkhorn_tol=hypers_common["sinkhorn_tol"],
                  precision=hypers_common.get("precision", "f32"),
                  # the supervisor's XLA rung, and every dispatch sharded
                  # over several devices, pin the Pallas kernels off (a
                  # distinct static-arg program variant); the default
                  # True is the historical program and cache key
                  pallas=allow_pallas,
                  # the quality-telemetry program variant (TW_CONF_DEVICE;
                  # packed_layout.py): default False = the historical
                  # packed block, byte-identical programs
                  confidence=hypers_common.get("confidence", False),
                  max_preds=pg["max_preds"], max_succs=pg["max_succs"])
    warm = _compaction_warm()
    use_compact = (_compaction_on() and warm < n_sweeps
                   and pg["n_rows"] > 1)
    if mesh is not None:
        # batch rows pad to the mesh size ON THE HOST and stay numpy here:
        # the compacted flow gathers redispatch rows from these host
        # tensors and places fresh sharded copies per dispatch (the
        # donated device buffers of an earlier dispatch cannot be reused).
        # The padded size is bucket_rows_per_shard — pow2 rows per shard
        # — not just a multiple of the mesh: raw row counts vary per
        # group, and an unbucketed mesh batch axis would mint one
        # compiled sharded program per count, putting the whole mesh
        # family outside any finite AOT lattice (runtime/aot.py
        # enumerates exactly these pow2-per-shard sizes)
        from traceweaver_tpu.parallel.mesh import (
            _pad_batch,
            bucket_rows_per_shard,
        )

        n_dev = int(mesh.devices.size)
        batch, true_b = _pad_batch(
            batch, bucket_rows_per_shard(pg["n_rows"], n_dev))
        pidx = np.concatenate(
            [pidx, np.zeros(batch["in_start"].shape[0] - true_b,
                            dtype=pidx.dtype)])
        if tenant_col is not None:
            # mesh padding rows belong to no tenant (-1): they are
            # all-invalid windows decoded by nobody, so they must not
            # surface in anyone's redispatch attribution either
            tenant_col = np.concatenate(
                [tenant_col,
                 np.full(batch["in_start"].shape[0] - true_b, -1,
                         dtype=tenant_col.dtype)])
    trace_keys = pg.get("trace_keys") or ()
    span_args = spec.span_args
    # this flow's blocking time (compacted intermediate fetches), so
    # dispatch_s below stays pure launch/host time even when several
    # flows bill wait_s to the shared dict concurrently
    flow_wait = [0.0]
    # plan-cache admission sink: the compacted two-pass flow surfaces its
    # between-pass refit tables here so the fitted plan the device just
    # computed is kept for the next solve (admitted below, after the
    # dispatch accounting closes — decode work, not launch time)
    plan_cache = hypers_common.get("plan_cache")
    refit_sink = [] if (plan_cache is not None and n_passes == 2) else None
    with _profile.stage(st, "dispatch", "dispatch_s", trace_keys,
                        **span_args) as timed:
        if use_compact:
            out = _solve_group_compacted(
                batch, pidx, params, _tables_of(params), window_rows,
                window_valid, n_passes, n_sweeps, warm, hypers, st,
                mesh=mesh, flow_wait=flow_wait,
                tenant_col=tenant_col, tenant_table=tenant_table,
                trace_keys=trace_keys, assemble=assemble,
                refit_sink=refit_sink, span_args=span_args)
        elif assemble is not None:
            # device-resident path: window tensors are assembled on device
            # from the rings; only index arrays + skip/force shipped. The
            # batch-row axis pads to a power of two with all-invalid rows
            # (decoded by nobody, converge instantly) so the continuous-
            # batching scheduler's varying admission counts dispatch against
            # a BOUNDED shape lattice — steady state mints zero compiles
            # (tests/test_continuous.py pins it)
            pad_b = _bucket(pg["n_rows"], minimum=1) - pg["n_rows"]
            common = assemble(None, pad_b) + (_pad_pidx(pidx, pad_b),)
            if n_passes == 2:
                _note_aot(st, _aot.note_fleet(
                    "solve_em_fleet", common, _tables_of(params), n_sweeps,
                    hypers, window_rows=window_rows))
                out, _ = solve_em_fleet(
                    *common, window_rows, window_valid, *_tables_of(params),
                    n_sweeps=n_sweeps, **hypers,
                )
            else:
                _note_aot(st, _aot.note_fleet(
                    "solve_windows_fleet", common, _tables_of(params),
                    n_sweeps, hypers))
                out, _ = solve_windows_fleet(
                    *common, *_tables_of(params), n_sweeps=n_sweeps,
                    **hypers,
                )
        else:
            if mesh is not None:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec

                from traceweaver_tpu.parallel.mesh import put_sharded

                # put_sharded: window-axis keys sharded, everything else
                # (param tables, window_rows/valid) replicated
                placed = put_sharded(
                    {**batch, **params,
                     "window_rows": window_rows,
                     "window_valid": window_valid},
                    mesh)
                batch = {k: placed[k] for k in batch}
                params = {k: placed[k] for k in params}
                window_rows = placed["window_rows"]
                window_valid = placed["window_valid"]
                pidx = jax.device_put(
                    pidx,
                    NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])))
            _bill_shipped(st, batch)
            common = tuple(batch[k] for k in _BATCH_KEYS) + (pidx,)
            if n_passes == 2:
                _note_aot(st, _aot.note_fleet(
                    "solve_em_fleet", common, _tables_of(params), n_sweeps,
                    hypers, window_rows=window_rows, mesh=mesh))
                out, _ = solve_em_fleet(
                    *common, window_rows, window_valid, *_tables_of(params),
                    n_sweeps=n_sweeps, **hypers,
                )
            else:
                _note_aot(st, _aot.note_fleet(
                    "solve_windows_fleet", common, _tables_of(params),
                    n_sweeps, hypers, mesh=mesh))
                out, _ = solve_windows_fleet(
                    *common, *_tables_of(params), n_sweeps=n_sweeps,
                    **hypers,
                )
        timed.less = flow_wait[0]
    _OBS_DISPATCH_S.observe(timed.seconds)
    _copy_async(out)
    if refit_sink:
        # two-pass admission: decode the refit tables the device already
        # computed back into per-service dists and keep them — the next
        # solve's cache hit repacks them bit-exactly and runs single-pass
        # (= this solve's pass 1). Billed to plan_fit_s: this is residual
        # host planning riding the flow worker, not launch time. Under
        # the pipeline it overlaps the next group's pack/dispatch — the
        # overlapped-residual-planning contract.
        from traceweaver_tpu.algorithms.weaver_tpu import dists_from_tables

        with _profile.stage(st, "plan-fit", "plan_fit_s", **span_args):
            tables9 = tuple(np.asarray(t) for t in refit_sink[0])
            for p, (_, item, prep, _, _) in enumerate(pg["per_item_pack"]):
                if item.warm_dists is not None:
                    continue
                plan_cache.admit(
                    _plan_key(item),
                    dists_from_tables(prep["out_eps"], prep["in_ep"],
                                      *(t[p] for t in tables9)))
    # the decode ticket carries the program-variant flag so the decode
    # worker splits the packed channels by the layout the dispatch used
    return (pg["per_item_pack"], out, hypers.get("confidence", False),
            span_args)


def _tables_of(params: Dict) -> Tuple:
    return tuple(params[k] for k in _TABLE_KEYS)


def _pad_tables_pow2(params: Dict, window_rows: np.ndarray,
                     window_valid: np.ndarray):
    """Pow2-pad the per-service table axes of one packed group: the
    stacked param tables' ``P`` axis (services in the group) and the
    refit row map's ``Bmax`` axis (max windows per service). Under
    continuous batching the admission scheduler hands the fleet
    arbitrary tenant subsets, so P and Bmax vary per dispatch — without
    padding, every distinct count is a fresh compiled program and the
    steady state never stops compiling. Padding services follow the
    all-invalid convention of ``pack_problem``'s ``pad_e`` endpoints:
    false masks, zero weights, unit σ; no window row ever points at
    them (only pow2 batch padding rows do, and those are all-invalid)."""
    P, bmax = window_rows.shape
    P_pad = _bucket(P, minimum=1)
    bmax_pad = _bucket(bmax, minimum=1)
    if P_pad == P and bmax_pad == bmax:
        return params, window_rows, window_valid
    out = {}
    for k, a in params.items():
        pad = np.zeros((P_pad - P,) + a.shape[1:], dtype=a.dtype)
        if k.endswith("_sd"):
            pad = np.ones_like(pad)
        out[k] = np.concatenate([a, pad]) if P_pad > P else a
    wr = np.zeros((P_pad, bmax_pad), dtype=window_rows.dtype)
    wv = np.zeros((P_pad, bmax_pad), dtype=bool)
    wr[:P, :bmax] = window_rows
    wv[:P, :bmax] = window_valid
    return out, wr, wv


def _pad_pidx(pidx: np.ndarray, pad: int) -> np.ndarray:
    """Zero-extend the param-index column for pow2 batch-row padding
    (padding rows are all-invalid windows; the row-0 tables they point
    at never see a valid span)."""
    if not pad:
        return pidx
    return np.concatenate([pidx, np.zeros(pad, dtype=pidx.dtype)])


def _bill_shipped(st: _Stats, arrs: Dict) -> None:
    """H2D byte ledger, shipped side: every host window tensor placed on
    device for a dispatch (fresh copies per attempt/pass — each
    placement is real H2D traffic and bills again). The resident
    path's counterpart ledgers are ``h2d_bytes_ring`` (column appends)
    and ``h2d_bytes_index`` (gather index arrays), so a ``TW_DEVCOLS``
    solve can never silently claim zero traffic while still shipping."""
    st.add("h2d_bytes_shipped",
           float(sum(np.asarray(arrs[k]).nbytes
                     for k in _BATCH_KEYS if k in arrs)))


def _make_assembler(dc_items: List[Dict], batch: Dict, st: _Stats):
    """Build the device-assembly closure for one packed group
    (``TW_DEVCOLS``): ``assemble(active, pad)`` returns the eight
    window tensors of ``_BATCH_KEYS`` order for the given row subset
    (``active=None`` = all rows, ascending indices otherwise) plus
    ``pad`` trailing all-invalid rows — the drop-in replacement for
    host-tensor placement at every dispatch site (warm, compacted
    redispatch, retry). The rings are global per-partition arenas, so
    the WHOLE group assembles in ONE jitted gather over host-built
    index arrays (row selection/padding is NumPy — no eager device op
    ever sees a data-dependent shape, which is what keeps the steady
    state at zero compiles). Each call gathers FRESH device tensors, so
    donated buffers of a failed attempt can never poison a retry, and
    ships only int32 index arrays (``h2d_bytes_index``) plus the small
    skip/force tensors (``h2d_bytes_shipped``)."""
    ring_in = dc_items[0]["ring_in"]
    ring_out = dc_items[0]["ring_out"]
    cat = (lambda key: dc_items[0][key] if len(dc_items) == 1
           else np.concatenate([it[key] for it in dc_items]))
    in_idx, out_idx = cat("in_idx"), cat("out_idx")
    origin_in, origin_out = cat("origin_in"), cat("origin_out")

    def assemble(active: Optional[np.ndarray], pad: int) -> Tuple:
        # fault site "devcols", resident-assembly flavor: raised here it
        # surfaces from the dispatch attempt and enters the supervisor
        # ladder, whose first move for a devcols fault is the
        # ring-invalidate-and-rebuild rung (_degrade_group) — every
        # retry then re-gathers from a rebuilt, trusted arena
        _fault_check("devcols", st)

        def rows(arr, fill):
            a = arr if active is None else arr[active]
            if pad:
                a = np.concatenate(
                    [a, np.full((pad,) + a.shape[1:], fill,
                                dtype=a.dtype)])
            return a

        si, so = rows(in_idx, -1), rows(out_idx, -1)
        oi, oo = rows(origin_in, 0), rows(origin_out, 0)
        st.add("h2d_bytes_index",
               float(si.nbytes + so.nbytes + oi.nbytes + oo.nbytes))
        _note_aot(st, _aot.note_assemble(int(ring_in.cap), si, so))
        outs = _devcols.assemble_resident(ring_in, ring_out,
                                          si, so, oi, oo)
        skip_cap = rows(batch["skip_cap"], 0)
        force_skip = rows(batch["force_skip"], False)
        st.add("h2d_bytes_shipped",
               float(skip_cap.nbytes + force_skip.nbytes))
        return tuple(outs) + (skip_cap, force_skip)

    # true (un-padded) row count, for callers that pad the batch-row
    # axis to pow2 and must slice per-row outputs (convergence flags)
    # back to the real windows
    assemble.n_rows = int(in_idx.shape[0])
    return assemble


def _compacted_pass(batch, pidx, tables, n_sweeps, warm, hypers, stats,
                    mesh=None, flow_wait=None, tenant_col=None,
                    tenant_table=None, trace_keys=(), assemble=None,
                    span_args=None):
    """One solve pass as warm dispatch + compacted full redispatch.

    Returns the packed [B, E, W, 3+topk] output as a host array,
    bit-identical to a single ``n_sweeps`` dispatch of the same batch
    (see the compaction comment on :func:`_dispatch_packed`).

    The host never blocks on the packed warm block just to LEARN the
    convergence set: the flags ride their own ``[B]`` bool device array
    (the packed-output split, ``weaver_tpu._pack_solver_outputs``) and
    are fetched alone — B bytes instead of the whole
    ``[B, E, W, 3+topk]`` block — while the warm block streams D2H
    asynchronously, overlapping the gather and the redispatch compute
    (the ``copy-start`` D2H cost the r05 profile billed at parity with
    the sweep loops themselves).

    With ``mesh``, inputs stay host-side NumPy and every dispatch places
    fresh sharded copies; the redispatch batch is bucketed PER SHARD
    (:func:`traceweaver_tpu.parallel.mesh.bucket_rows_per_shard`) so
    multi-chip runs compact too — each device receives a power-of-two
    row count and the total divides evenly across the mesh. Per-window
    outputs are sharding-independent (the solve is a vmap over windows),
    so 1- and N-device compacted runs stay identical. ``span_args`` are
    the group's stage-span arguments."""
    st = _as_stats(stats)
    span_args = span_args or {}
    n_shards = int(mesh.devices.size) if mesh is not None else 1

    def place(arrs, pidx_np):
        _bill_shipped(st, arrs)
        if mesh is None:
            return tuple(arrs[k] for k in _BATCH_KEYS) + (pidx_np,)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from traceweaver_tpu.parallel.mesh import put_sharded

        placed = put_sharded({k: arrs[k] for k in _BATCH_KEYS}, mesh)
        pj = jax.device_put(
            pidx_np, NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])))
        return tuple(placed[k] for k in _BATCH_KEYS) + (pj,)

    tables_dev = tables
    if mesh is not None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        tables_dev = tuple(jax.device_put(np.asarray(t), rep)
                           for t in tables)

    with _profile.stage(st, "warm-dispatch", **span_args):
        if assemble is not None:
            # pow2 batch-row padding (all-invalid rows): bounded shape
            # lattice under continuous batching, see _dispatch_packed
            pad0 = _bucket(assemble.n_rows, minimum=1) - assemble.n_rows
            warm_common = assemble(None, pad0) + (_pad_pidx(pidx, pad0),)
        else:
            warm_common = place(batch, pidx)
        _note_aot(st, _aot.note_fleet(
            "solve_windows_fleet", warm_common, tables_dev, warm,
            hypers, mesh=mesh))
        out_warm, flags = solve_windows_fleet(
            *warm_common, *tables_dev, n_sweeps=warm, **hypers)
    # the big warm block starts its D2H NOW — it overlaps the flag fetch,
    # the host gather, and the redispatch's device execution below
    _copy_async(out_warm)
    converged = _fetch_flags(flags, st, flow_wait, mesh=mesh,
                             trace_keys=trace_keys,
                             span_args=span_args).astype(bool)
    if assemble is not None:
        # drop the pow2 padding rows: all-invalid windows converge by
        # construction and must not inflate the compaction ledger (or
        # reach the redispatch row gather)
        converged = converged[:assemble.n_rows]
    active = np.flatnonzero(~converged)
    st.add("compact_windows_total", float(converged.shape[0]))
    st.add("compact_windows_redispatched", float(active.size))
    if tenant_col is not None and active.size:
        # tenancy attribution of the straggler set: which tenant's
        # windows are still burning redispatch cycles (the serve layer's
        # per-tenant cost ledger; -1 rows are untagged/mesh padding)
        ids, counts = np.unique(np.asarray(tenant_col)[active],
                                return_counts=True)
        for t_i, c in zip(ids, counts):
            if t_i >= 0:
                st.bucket("tenant_windows_redispatched",
                          tenant_table[int(t_i)], float(c))
    if active.size == 0:
        return _fetch(out_warm, st, flow_wait, span_args=span_args)

    from traceweaver_tpu.parallel.mesh import bucket_rows_per_shard

    b_pad = bucket_rows_per_shard(int(active.size), n_shards)
    pad = b_pad - int(active.size)
    pidx_active = np.asarray(pidx)[active]
    if pad:
        pidx_active = np.concatenate(
            [pidx_active, np.zeros(pad, dtype=pidx_active.dtype)])
    if assemble is not None:
        # resident path: re-gather the straggler rows from the rings on
        # device (the warm dispatch donated its assembled tensors; a
        # fresh assembly is index-array traffic only, never a re-ship
        # of the column data)
        redispatch_common = assemble(active, pad) + (pidx_active,)
    else:
        gathered = {}
        for k in _BATCH_KEYS:
            a = np.asarray(batch[k])[active]
            if pad:
                # padding rows are all-invalid windows: no valid spans or
                # columns, so they assign nothing and are decoded by nobody
                # (same convention as pack_problem's pad_b rows)
                a = np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)])
            gathered[k] = a
        redispatch_common = place(gathered, pidx_active)
    with _profile.stage(st, "redispatch", trace_keys=trace_keys,
                        **span_args):
        _note_aot(st, _aot.note_fleet(
            "solve_windows_fleet", redispatch_common, tables_dev,
            n_sweeps, hypers, mesh=mesh))
        out_full, _ = solve_windows_fleet(
            *redispatch_common, *tables_dev,
            n_sweeps=n_sweeps, **hypers)
    _copy_async(out_full)
    out = _fetch(out_warm, st, flow_wait, span_args=span_args).copy()
    out[active] = _fetch(out_full, st, flow_wait,
                         span_args=span_args)[:active.size]
    return out


def _solve_group_compacted(batch, pidx, params, tables, window_rows,
                           window_valid, n_passes, n_sweeps, warm, hypers,
                           stats, mesh=None, flow_wait=None,
                           tenant_col=None, tenant_table=None,
                           trace_keys=(), assemble=None, refit_sink=None,
                           span_args=None):
    """Compacted replacement for one fused group dispatch: per-pass
    warm/redispatch compaction, with the two-pass EM's on-device refit as
    its own dispatch between the passes (same refit program
    ``solve_em_fleet`` runs in-graph, so the flows cannot drift).
    ``batch`` stays host-side NumPy throughout — each dispatch places
    (and, mesh-less, uploads) fresh device copies, which is what makes
    the donated window tensors safe to regather for the redispatch.
    Under ``assemble`` (TW_DEVCOLS) there ARE no host window tensors:
    every dispatch, and the refit's sample extraction, re-gathers fresh
    device tensors from the resident rings instead."""
    st = _as_stats(stats)
    out0 = _compacted_pass(batch, pidx, tables, n_sweeps, warm, hypers, st,
                           mesh=mesh, flow_wait=flow_wait,
                           tenant_col=tenant_col, tenant_table=tenant_table,
                           trace_keys=trace_keys, assemble=assemble,
                           span_args=span_args)
    if n_passes == 1:
        return out0
    if assemble is not None:
        # refit inputs straight off the rings (device tensors; the refit
        # program does not donate, so they survive the call), padded to
        # the same pow2 row count as out0 so the refit program's shapes
        # ride the bounded lattice too
        pad0 = _bucket(assemble.n_rows, minimum=1) - assemble.n_rows
        bi = dict(zip(_BATCH_KEYS, assemble(None, pad0)))
        pidx_refit = _pad_pidx(np.asarray(pidx), pad0)
    else:
        bi = batch
        pidx_refit = pidx
    assign_refit = out0[..., _layout.CH_ASSIGN].astype(np.int32)
    # the refit's inputs stay host NumPy on BOTH paths (the mesh flow
    # hands it the pre-placement tensors), so its compiled program is
    # the single-device one regardless of mesh — note with shards=1
    _note_aot(st, _aot.note_refit(assign_refit, window_rows,
                                  bi["out_start"]))
    new_tables = refit_fleet_params(
        assign_refit,
        bi["in_start"], bi["in_end"], bi["in_valid"],
        bi["out_start"], bi["out_end"], pidx_refit,
        window_rows, window_valid,
        params["pred_mask"], params["root_mask"],
        params["edge_wt"], params["edge_mu"], params["edge_sd"],
        params["in_wt"], params["in_mu"], params["in_sd"],
        params["ret_wt"], params["ret_mu"], params["ret_sd"])
    if mesh is not None:
        # pass 1 re-places everything itself; hand it host tables so the
        # replicated device_put starts from committed-free arrays — a
        # LEDGERED fetch (the refit tables are small, but the block on
        # the refit program's execution is real device wait)
        new_tables = tuple(_fetch(t, st, flow_wait, span_args=span_args)
                           for t in new_tables)
    if refit_sink is not None:
        # plan-cache admission material: the dispatcher decodes these
        # AFTER its dispatch accounting closes (device handles are fine —
        # by then pass 1 has long since forced the refit's execution)
        refit_sink.append(new_tables)
    return _compacted_pass(batch, pidx, tables[:3] + tuple(new_tables),
                           n_sweeps, warm, hypers, st, mesh=mesh,
                           flow_wait=flow_wait,
                           tenant_col=tenant_col, tenant_table=tenant_table,
                           trace_keys=trace_keys, assemble=assemble,
                           span_args=span_args)


def _decode_group(solver, pend, results, stats, ctx=None):
    """Fetch one group's packed output and decode it per service (the
    ``decode`` stage).

    Safe on a pipeline decode worker: every write lands in that group's
    own input-order ``results`` slots (and its own ``confidences``
    slots) and all counter updates go through the lock-guarded
    accumulator."""
    st = _as_stats(stats)
    per_item_pack, out, conf_device, span_args = pend
    confidences = (ctx or {}).get("confidences")
    if not _quality.conf_enabled():
        confidences = None
    # the compacted flow already fetched + merged on the host; the
    # single-dispatch flows hand over an async device handle
    o = (out if isinstance(out, np.ndarray)
         else _fetch(out, st, span_args=span_args))
    trace_keys = sorted({item.trace_key for _, item, *_ in per_item_pack
                         if item.trace_key is not None})
    with _profile.stage(st, "decode", "decode_s", trace_keys, **span_args):
        row = 0
        for i, item, prep, packed, n_w in per_item_pack:
            results[i] = _decode_item(solver, item, prep, packed,
                                      o[row:row + n_w], conf_device,
                                      confidences, i, st)
            row += n_w


def _decode_item(solver, item, prep, packed, rows, conf_device,
                 confidences, i, st: _Stats) -> Tuple:
    """One service's FindAssignments 6-tuple from its rows of the packed
    block; its per-span quality records go to ``confidences[i]`` when
    the caller keeps them."""
    n_w = rows.shape[0]
    if item.tenant is not None:
        # tenancy column, decode end: packed == decoded per tenant is
        # the conservation check the serve tests assert from stats
        st.bucket("tenant_windows_decoded", item.tenant, float(n_w))
    ch = _layout.split_packed(rows, confidence=conf_device)
    assign = ch["assign"]
    not_best = ch["not_best"]
    feas = ch["feas"]
    topk_cols = ch["topk_cols"]
    out_eps = prep["out_eps"]
    in_ids = (prep["in_cols"].ids.tolist()
              if prep.get("in_cols") is not None
              else [s.GetId() for s in prep["in_spans"]])
    n_in = prep["n_in"]

    index = window_index(packed.windows)
    all_assignments = {ep: {} for ep in out_eps}
    all_topk = {ep: {} for ep in out_eps}
    chosen_keys = solver._decode(packed, assign, topk_cols,
                                 all_assignments, all_topk, index)
    span_not_best = np.zeros(n_in, dtype=bool)
    span_cands = np.ones(n_in, dtype=np.int64)
    scatter_window_span_stats(index, not_best, feas,
                              span_not_best, span_cands)
    if confidences is not None:
        # per-span quality reductions from the SAME fetched block —
        # no extra transfer, no device change (obs/quality.py); the
        # slot write is race-free like the results slot (input-order,
        # one writer per item)
        arrs = _quality.span_confidence_arrays(
            index, rows, n_in, device=conf_device)
        confidences[i] = _quality.confidence_records(in_ids, arrs)
    unassigned, n_contested = solver._resolve_contested(
        packed, chosen_keys, index, in_ids, all_assignments, all_topk,
        prep["skip_budget"])
    # out-spans claimed by more than one window: how often the serial
    # rule engages
    st.add("decode_contested", float(n_contested))
    return (
        all_assignments, all_topk, int(span_not_best.sum()), n_in,
        dict(zip(in_ids, span_cands.tolist())),
        int(np.count_nonzero(unassigned)),
    )
